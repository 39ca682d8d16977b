"""Sparse table kernels — batched row gather + row-granular segment-sum.

The two device ops that dominate NMF/LDA-style sparse workloads are the
table's keyed pull (multi_get: a batched embedding gather) and the keyed
push's duplicate fold (multi_update: a segment-sum of delta rows by
destination key). These Pallas kernels move rows without XLA's generic
gather/scatter: the gather leaves the table in HBM and issues one row DMA
per pulled key straight into the output block (indices are scalar-
prefetched, so a whole tile of row copies is in flight at once), and the
segment-sum keeps the whole accumulator resident in VMEM across the grid
so duplicate folds never touch HBM.

The kernels are TPU programs and this module never asks which platform
it is on: ``gather_rows`` / ``segment_sum_rows`` ARE the kernels
(``interpret=True`` runs their bodies in the Pallas interpreter, for CPU
tests), ``gather_rows_ref`` / ``segment_sum_rows_ref`` are the jnp
references, and callers that know their mesh pick by name
(``TableSpec.pull`` / ``TableSpec.push``). ``*_kernel_ok`` say which
shapes the kernels take.

Numerical contract: the gather reference is value-identical to the kernel
(a gather copies bytes); the segment-sum routes agree exactly when the
folded values are addition-order-insensitive (integer-valued counts, no
duplicate keys) and to float tolerance otherwise (duplicate folds may
associate differently). On any ONE route the result is deterministic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width of the VPU/MXU register file: kernel shapes must tile it.
_LANES = 128
# Sublanes of one float32 vreg: output blocks are whole groups of 8 rows
# (Mosaic refuses a block whose second-minor dim is neither a multiple of
# 8 nor the array's).
_SUBLANES = 8
# Rows gathered per grid step (row DMAs in flight at once).
_GATHER_TILE = 128
# Accumulator-residency budget for the segment-sum kernel (bytes). The
# whole [num_rows, W] accumulator block stays in VMEM across the grid
# (same output block every step => consecutive-revisit residency); bigger
# tables take the reference rather than thrash HBM per step.
_ACC_VMEM_BYTES = 8 << 20
# Delta rows folded per grid step (the scalar fold loop's span).
_FOLD_TILE = 256


def _clamp_rows(idx: jnp.ndarray, num_rows: int) -> jnp.ndarray:
    return jnp.clip(idx.astype(jnp.int32), 0, max(num_rows - 1, 0))


def gather_kernel_ok(table_shape, dtype, n: int) -> bool:
    """Shapes the gather kernel takes: float32 rows exactly one lane tile
    (128) wide — the one layout in which a table row is contiguous in
    HBM, so that a row is one DMA. Mosaic refuses a one-row slice of
    anything else (wider rows interleave in (8, 128) tiles; bf16 rows
    pair up in a sublane): those take the reference."""
    R, W = table_shape
    return (n > 0 and R > 0 and W == _LANES
            and jnp.dtype(dtype) == jnp.float32)


def gather_rows_ref(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``out[i] = table[clamp(idx[i])]`` as one XLA gather."""
    return table[_clamp_rows(idx, table.shape[0])]


def _make_gather_kernel(tile: int):
    def _gather_kernel(idx_ref, table_ref, out_ref, sem):
        """One tile of pulled rows per grid step. The table never enters
        VMEM: each row is one HBM->VMEM DMA into its slot of the output
        block, all ``tile`` of them started before the first wait (one
        shared semaphore; every copy has the same shape)."""
        base = pl.program_id(0) * tile

        def row_copy(src_row, j):
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(src_row, 1), :],
                out_ref.at[pl.ds(j, 1), :],
                sem,
            )

        def start(j, _):
            row_copy(idx_ref[base + j], j).start()
            return 0

        def wait(j, _):
            row_copy(0, j).wait()
            return 0

        jax.lax.fori_loop(0, tile, start, 0)
        jax.lax.fori_loop(0, tile, wait, 0)

    return _gather_kernel


def gather_rows(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[i] = table[idx[i]]`` — table [R, W], idx [N] int32 -> [N, W],
    as the Pallas kernel.

    Out-of-range ids — NEGATIVE included — clamp to the nearest valid row
    (jax gather's OOB clamp semantics, applied explicitly on BOTH routes:
    jnp advanced indexing would wrap negatives Python-style, which the
    kernel's clamp cannot reproduce). The batched embedding gather behind
    ``TableSpec.pull`` / multi_get.
    """
    if table.ndim != 2 or idx.ndim != 1:
        raise ValueError(f"bad shapes table={table.shape} idx={idx.shape}")
    R, W = table.shape
    N = idx.shape[0]
    if not gather_kernel_ok(table.shape, table.dtype, N):
        raise ValueError(
            f"gather_rows kernel takes float32 rows of {_LANES} lanes; "
            f"got table={table.shape} {table.dtype}, {N} ids (use "
            f"gather_rows_ref)")
    safe = _clamp_rows(idx, R)
    tile = min(_GATHER_TILE, -(-N // _SUBLANES) * _SUBLANES)
    pad = (-N) % tile
    if pad:
        safe = jnp.pad(safe, (0, pad))  # padded slots copy row 0; sliced off
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((N + pad) // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, W), lambda i, idx_ref: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _make_gather_kernel(tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N + pad, W), table.dtype),
        interpret=interpret,
        name="harmony_gather_rows",
    )(safe, table)
    return out[:N] if pad else out


def _make_fold_kernel(num_rows: int, tile: int):
    def _fold_kernel(idx_ref, delta_ref, acc_ref):
        """Grid over delta tiles; the [num_rows, W] accumulator block is
        the SAME output block every step, so it stays VMEM-resident and
        the per-row folds are VMEM read-modify-writes. Rows fold in index
        order (a sequential scalar loop), matching the reference's
        scatter-add fold order for duplicate keys."""
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(j, _):
            k = idx_ref[i * tile + j]
            ok = (k >= 0) & (k < num_rows)
            kc = jnp.clip(k, 0, num_rows - 1)
            row = delta_ref[pl.ds(j, 1), :]
            acc_ref[pl.ds(kc, 1), :] += jnp.where(ok, row, jnp.zeros_like(row))
            return 0

        jax.lax.fori_loop(0, tile, body, 0)

    return _fold_kernel


def segment_sum_kernel_ok(deltas_shape, dtype, num_rows: int) -> bool:
    """Shapes the fold kernel takes: lane-tiled float32 rows and an
    accumulator inside the VMEM residency budget."""
    N, W = deltas_shape
    return (N > 0 and W % _LANES == 0 and jnp.dtype(dtype) == jnp.float32
            and num_rows * W * 4 <= _ACC_VMEM_BYTES)


def segment_sum_rows_ref(
    deltas: jnp.ndarray, idx: jnp.ndarray, num_rows: int
) -> jnp.ndarray:
    """The fold as one XLA scatter-add; out-of-range ids contribute
    nothing."""
    ok = (idx >= 0) & (idx < num_rows)
    safe = jnp.where(ok, idx, 0)
    masked = jnp.where(ok[:, None], deltas, jnp.zeros_like(deltas))
    return jnp.zeros((num_rows, deltas.shape[1]), deltas.dtype).at[safe].add(masked)


def segment_sum_rows(
    deltas: jnp.ndarray,
    idx: jnp.ndarray,
    num_rows: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[k] = sum over i with idx[i]==k of deltas[i]`` — deltas [N, W],
    idx [N] int32 -> [num_rows, W], as the Pallas kernel. Out-of-range ids
    contribute nothing. The multi_update duplicate fold: the result is
    applied to the table with ONE dense add (``TableSpec.push``
    via="sparse"), like the mxu route but with a row-granular fold instead
    of the one-hot matmul (ops/histogram.py) — cheaper when W is wide and
    the key set is a small fraction of the table."""
    if deltas.ndim != 2 or idx.ndim != 1 or idx.shape[0] != deltas.shape[0]:
        raise ValueError(f"bad shapes deltas={deltas.shape} idx={idx.shape}")
    N, W = deltas.shape
    if not segment_sum_kernel_ok(deltas.shape, deltas.dtype, num_rows):
        raise ValueError(
            f"segment_sum_rows kernel takes float32 rows of a multiple of "
            f"{_LANES} lanes and an accumulator of at most "
            f"{_ACC_VMEM_BYTES} bytes; got deltas={deltas.shape} "
            f"{deltas.dtype}, {num_rows} rows (use segment_sum_rows_ref)")
    tile = min(_FOLD_TILE, -(-N // _SUBLANES) * _SUBLANES)
    pad = (-N) % tile
    idx32 = idx.astype(jnp.int32)
    if pad:
        # padded rows carry id -1: masked out inside the kernel
        idx32 = jnp.pad(idx32, (0, pad), constant_values=-1)
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
        N += pad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // tile,),
        in_specs=[pl.BlockSpec((tile, W), lambda i, idx_ref: (i, 0))],
        out_specs=pl.BlockSpec((num_rows, W), lambda i, idx_ref: (0, 0)),
    )
    return pl.pallas_call(
        _make_fold_kernel(num_rows, tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_rows, W), deltas.dtype),
        interpret=interpret,
        name="harmony_segment_sum_rows",
    )(idx32, deltas)


def value_width(value_shape) -> int:
    """Row width of a table value (scalars are width-1 rows)."""
    return int(np.prod(value_shape)) if value_shape else 1
