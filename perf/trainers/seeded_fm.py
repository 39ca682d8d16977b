"""The keyed tenant's weights made on the device from the job's seed.

``FMTrainer.init_global_settings`` builds the whole embedding table in host
float64 and ``multi_put``s it from a Python list of every key: about 17 GB
of host memory and minutes at 2^24 rows, on every run of every later check.
A Trainer is user code in this framework, so the benchmark's tenant brings
its own: :class:`SeededFMTrainer` overrides ONLY the initialisation (and
takes the seed it initialises from); everything the step runs — the table
schema, ``pull_keys``, ``compute`` — is ``FMTrainer``'s, untouched.

A row is a pure function of ``(seed, key)`` (:func:`seeded_rows`), so the
plain reference (perf/reference/criteo-fm.py) makes exactly the rows a
batch touches without ever holding the table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harmony_tpu.apps.widedeep import FMTrainer


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3's 32-bit finaliser (uint32 in, uint32 out)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def seeded_rows(keys: jnp.ndarray, width: int, seed,
                scale: float) -> jnp.ndarray:
    """``[n] int32 keys -> [n, width] float32`` rows: column 0 (the wide
    weight) is 0, columns 1.. are uniform with standard deviation
    ``scale`` (``FMTrainer`` draws normal(scale); uniform keeps the
    generator one fused elementwise pass — listed under ``assumed``).
    ``seed`` is a uint32 scalar and may be traced: a program that takes it
    as an argument is the same program for every seed, so the compile
    cache finds it again."""
    k = _mix32(keys.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
               + jnp.asarray(seed, jnp.uint32) * jnp.uint32(0x7F4A7C15))
    col = jnp.arange(width, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    h = _mix32(k[:, None] ^ col[None, :])
    u = (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)  # [0, 1)
    rows = (2.0 * u - 1.0) * jnp.float32(scale * 3.0 ** 0.5)
    # a select on the column index, not ``.at[:, 0].set``: the whole row
    # stays one fused elementwise pass (a [n, 1] slice pads 128-fold on
    # the TPU: 8 GB at 2^24 rows)
    return jnp.where(col[None, :] == 0, jnp.float32(0.0), rows)


class SeededFMTrainer(FMTrainer):
    """``FMTrainer`` whose dense table is filled on the device, in one
    jitted pass over the table's own (donated) storage."""

    def __init__(self, seed: int = 0, **kw) -> None:
        super().__init__(**kw)
        if self.sparse:
            raise ValueError("SeededFMTrainer fills a dense table; the "
                             "sparse table initialises lazily by itself")
        self.seed = int(seed)

    def fill_program(self, spec, sharding):
        """``(arr, seed) -> (filled arr, None)``, jitted with the storage
        donated: the table's own ``apply_step`` shape."""
        vocab, width, scale = self.vocab_size, self.width, self.init_scale

        def fill(arr, seed):
            b = jnp.arange(spec.num_blocks, dtype=jnp.int32)[:, None]
            o = jnp.arange(spec.block_size, dtype=jnp.int32)[None, :]
            keys = spec.partitioner.key_of(b, o).reshape(-1)
            rows = seeded_rows(keys, width, seed, scale)
            # the bias row and the padding past the capacity keep what the
            # table's own init gave them (reading ``arr`` also makes the
            # donation an in-place write)
            old = arr.reshape(rows.shape)
            rows = jnp.where((keys < vocab)[:, None], rows.astype(arr.dtype),
                             old)
            return rows.reshape(arr.shape), None

        return jax.jit(fill, donate_argnums=0, out_shardings=(sharding, None))

    def init_global_settings(self, ctx) -> None:
        # apply_step is the table's own path for a step that donates its
        # storage: the fill reuses the zero-initialised buffer, so the
        # table is never on the device twice
        table = ctx.model_table
        table.apply_step(self.fill_program(table.spec, table.sharding),
                         jnp.uint32(self.seed & 0xFFFFFFFF))
