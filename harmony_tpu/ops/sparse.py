"""Sparse table kernels — batched row gather and row scatter-add.

The device ops that dominate keyed sparse workloads (FM / Wide&Deep on
Criteo-shaped ids, NMF, LDA) are the table's keyed pull (multi_get: a
batched embedding gather) and the keyed push (multi_update: every delta row
added into its destination row, duplicate keys folding). Two Pallas
kernels move rows without XLA's generic gather/scatter:

  * ``gather_rows`` leaves the table in HBM and issues one row DMA per
    pulled key straight into the output block (indices are scalar-
    prefetched whole, so a tile of row copies is in flight at once). It
    folds nothing.
  * ``scatter_add_rows`` updates the table IN PLACE (aliased, never
    streamed): XLA sorts the ids a tile at a time, the kernel folds each
    run of equal ids in VMEM — in occurrence order, on top of the table
    row — and moves each of a tile's distinct rows by one DMA each way.
    Its index operands come a tile at a time, so it takes any N.

The kernels are TPU programs and this module never asks which platform
it is on: ``gather_rows`` / ``scatter_add_rows`` ARE the kernels
(``interpret=True`` runs their bodies in the Pallas interpreter, for CPU
tests), ``*_ref`` are the jnp references, and
callers that know their mesh pick by name (``TableSpec.pull`` /
``TableSpec.push``). ``*_kernel_ok`` say which shapes the kernels take.

Numerical contract: the gather reference is value-identical to the kernel
(a gather copies bytes); the scatter-add folds a key's deltas in the
order they occur, the association of a serial scatter-add, and is
deterministic.
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


# Delta rows handled per grid step of the scatter-add kernel: the span
# inside which duplicate keys fold in VMEM (Criteo-shaped keys: 55% of a
# tile's slots are distinct at 4,096, 63% at 2,048, 25% over the whole
# batch). Measured on the chip at 212,993 keys (PERF.md PR 28): 6.90 /
# 6.43 / 6.08 ms a call at 1,024 / 2,048 / 4,096; longer rows cost more in
# the sort (its rows are padded to a multiple of 128) than they fold.
_SCATTER_TILE = 4096


def scatter_kernel_ok(table_shape, dtype, n: int) -> bool:
    """Shapes the scatter-add kernel takes: the gather kernel's (a table
    row must be one contiguous DMA each way)."""
    return gather_kernel_ok(table_shape, dtype, n)


def scatter_add_rows_ref(table: jnp.ndarray, idx: jnp.ndarray,
                         deltas: jnp.ndarray) -> jnp.ndarray:
    """``table.at[idx].add(deltas)`` as one XLA scatter; ids outside
    ``[0, R)`` — NEGATIVE included — are dropped (jnp indexing would wrap
    a negative Python-style, which no table op means)."""
    R = table.shape[0]
    idx = idx.astype(jnp.int32)
    safe = jnp.where((idx >= 0) & (idx < R), idx, R)
    return table.at[safe].add(deltas.astype(table.dtype), mode="drop")


# Bodies a trip of the kernel's scalar loops (Mosaic unrolls a fori_loop
# by 1 or by all): the loop counter and branch are paid once per trip.
_UNROLL = 8


def _trips(n, body, init):
    """``body(i, carry)`` for ``i`` in ``[0, ceil(n / _UNROLL) * _UNROLL)``
    — past ``n`` included, so ``body`` must be harmless there."""
    def trip(t, carry):
        for u in range(_UNROLL):
            carry = body(t * _UNROLL + u, carry)
        return carry
    return jax.lax.fori_loop(0, (n + _UNROLL - 1) // _UNROLL, trip, init)


def _make_scatter_kernel(num_rows: int, tile: int):
    def _scatter_kernel(held_ref, sid_ref, pos_ref, deltas_ref, table_ref,
                        out_ref, tbuf, starts, pending, sem_t, sem_o):
        """One tile of delta rows per grid step, brought into VMEM as ONE
        block (``deltas_ref``, the pipeline's copy). The tile's ids come
        sorted, each with its position in the tile. Four scalar loops, none
        with a branch in it: (1) the first sorted slot of every run of
        equal ids -> ``starts``; (2) per run, the table row HBM ->
        ``tbuf[run]``; (3) per slot, in sorted order, the delta row added
        on top — ``((row + d0) + d1) + …``; (4) per run, ``tbuf[run]``
        back to its row. ``table_ref`` is aliased onto ``out_ref`` and
        never touched: rows are read from and written to ``out_ref``
        alone, and a tile's writes are waited before the next tile's
        reads start, so a key that recurs in a later tile reads what the
        earlier one wrote."""
        del table_ref
        s = pl.program_id(0)
        held = held_ref[s]  # the tile's in-range ids: a prefix of its sort

        def rows_dma(src, i, dst, j, sem, n=1):
            return pltpu.make_async_copy(
                src.at[pl.ds(i, n), :], dst.at[pl.ds(j, n), :], sem)

        def wait_rows(n, wait_for):
            """Wait for ``n`` one-row copies, ``n % _UNROLL == 0``: a DMA
            semaphore counts bytes, so one wait of 64 rows stands for 64
            of them (a table shorter than that waits in shorter spans)."""
            for span in (64, _UNROLL, 1):
                if span <= num_rows:
                    def wait(_, c, span=span):
                        wait_for(span).wait()
                        return c
                    jax.lax.fori_loop(0, n // span, wait, 0)
                    n = n % span

        read_of = lambda n: rows_dma(out_ref, 0, tbuf, 0, sem_t, n)
        write_of = lambda n: rows_dma(tbuf, 0, out_ref, 0, sem_o, n)

        @pl.when(s >= 1)
        def _():
            wait_rows(pending[0], write_of)

        def mark(j, carry):
            run, prev = carry
            k = sid_ref[j]
            starts[run] = j  # a slot inside a run is overwritten by the next head
            return run + ((k != prev) & (k < num_rows)).astype(jnp.int32), k
        n_runs, _ = _trips(held, mark, (0, -1))
        last = jnp.maximum(n_runs - 1, 0)
        issued = (n_runs + _UNROLL - 1) // _UNROLL * _UNROLL

        def read(c, carry):
            c = jnp.minimum(c, last)  # past the last run: read it again
            rows_dma(out_ref, sid_ref[starts[c]], tbuf, c, sem_t).start()
            return carry
        _trips(n_runs, read, 0)
        wait_rows(issued, read_of)

        def fold(j, carry):
            run, prev, acc = carry
            k = sid_ref[j]
            head = k != prev
            run = run + head.astype(jnp.int32)
            acc = jnp.where(head, tbuf[pl.ds(run, 1), :], acc) \
                + deltas_ref[pl.ds(pos_ref[j], 1), :]
            tbuf[pl.ds(run, 1), :] = acc
            return run, k, acc
        # past ``held`` the ids are the dropped ones: one more "run", folded
        # into a row of tbuf that no copy reads (held < tile there)
        _trips(held, fold, (-1, -1, jnp.zeros((1, _LANES), jnp.float32)))

        def write(c, carry):
            c = jnp.minimum(c, last)  # ... and write it again: the same bytes
            rows_dma(tbuf, c, out_ref, sid_ref[starts[c]], sem_o).start()
            return carry
        _trips(n_runs, write, 0)
        pending[0] = issued

        @pl.when(s == pl.num_programs(0) - 1)
        def _():
            wait_rows(pending[0], write_of)

    return _scatter_kernel


def scatter_add_rows(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    deltas: jnp.ndarray,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``table[idx[i]] += deltas[i]`` — table [R, W], idx [N] int32, deltas
    [N, W] -> the table, updated IN PLACE (the table operand is aliased
    onto the result: donate it), as the Pallas kernel.

    The ids are cut into tiles of consecutive slots and each tile's ids
    sorted (stably, with their positions) in XLA; the kernel takes one
    tile of delta rows a grid step, folds every run of equal ids IN
    OCCURRENCE ORDER on top of the table row — ``((row + d0) + d1) + …``,
    the association of a serial scatter-add — and reads and writes each of
    the tile's distinct rows once. Ids outside ``[0, R)`` are dropped, as
    ``.at[].add`` drops them. The keyed push behind
    ``TableSpec.push(via="scatter")`` / multi_update."""
    if (table.ndim != 2 or idx.ndim != 1
            or deltas.shape != (idx.shape[0], table.shape[1])):
        raise ValueError(f"bad shapes table={table.shape} idx={idx.shape} "
                         f"deltas={deltas.shape}")
    R, W = table.shape
    N = idx.shape[0]
    if not (scatter_kernel_ok(table.shape, table.dtype, N)
            and deltas.dtype == table.dtype):
        raise ValueError(
            f"scatter_add_rows kernel takes float32 rows of {_LANES} lanes; "
            f"got table={table.shape} {table.dtype}, deltas {deltas.dtype}, "
            f"{N} ids (use scatter_add_rows_ref)")
    tile = min(_SCATTER_TILE, -(-N // _LANES) * _LANES)
    tiles = -(-N // tile)
    ids = idx.astype(jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < R), ids, R)  # dropped ids sort last
    ids = jnp.pad(ids, (0, tiles * tile - N), constant_values=R)
    ids = ids.reshape(tiles, tile)
    # XLA compiles this sort in a second when it has at most 8 rows or a
    # multiple of 128 of them, and in five otherwise (PERF.md PR 28)
    spare = (-tiles) % 128 if tiles > 8 else 0
    if spare:
        ids = jnp.pad(ids, ((0, spare), (0, 0)), constant_values=R)
    sid, pos = jax.lax.sort(
        (ids, jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)),
        dimension=1, num_keys=1, is_stable=True)
    held = jnp.sum(sid[:tiles] < R, axis=1, dtype=jnp.int32)
    if N < tile:  # one block taller than the array: give it its rows
        deltas = jnp.pad(deltas, ((0, tile - N), (0, 0)))
    smem = pl.BlockSpec((tile,), lambda s, held: (s,),
                        memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[
            smem,                                                # ids
            smem,                                                # positions
            pl.BlockSpec((tile, W), lambda s, held: (s, 0)),     # deltas
            pl.BlockSpec(memory_space=pl.ANY),                   # table
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((tile, W), table.dtype),
            pltpu.SMEM((tile + _UNROLL,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        _make_scatter_kernel(R, tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={4: 0},  # operand 0 is the prefetched scalar
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="harmony_scatter_add_rows",
    )(held, sid.reshape(-1), pos.reshape(-1), deltas, table)


def value_width(value_shape) -> int:
    """Row width of a table value (scalars are width-1 rows)."""
    return int(np.prod(value_shape)) if value_shape else 1
