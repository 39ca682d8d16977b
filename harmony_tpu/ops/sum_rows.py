"""Rows summed into token rows, a token tile at a time: ``harmony_sum_rows``.

The chunked expert layer (models/moe.py) ends each chunk and pass with

    acc[tok[i]] += src[i] * gate[i]          i over the chunk's held rows

where ``acc [T, d]`` float32 is the layer's running sum, ``src [C, d]`` what
the grouped matmuls wrote (bfloat16 in the benchmark's cells) and ``tok [C]``
each row's token. XLA's TPU scatter-add takes the rows one after another at
92-370 ns each (PERF.md, PR 35 / 37). The rows have a structure a scatter
cannot use: they are ``H`` RUNS (one a held expert), and inside a run the
token index strictly ascends — a token picks an expert once, and the sort by
expert is stable. So for a tile of ``TB`` consecutive tokens, a run's rows are
ONE contiguous range of the chunk, at most ``TB`` long, and the caller can say
where each starts (``bounds``, a count table XLA makes from the routing once
a layer, ~0.2 ms).

The kernel's grid walks the token tiles. The tile's ``[TB, d]`` block of the
sum comes and goes by its BlockSpec (aliased onto the operand: the sum is
never copied); per run, the range of source rows comes by DMA in whole
sublane groups into VMEM (the next run's copies in flight while this one
folds), is widened to float32 there, and a scalar loop adds row ``i`` — times
its gate where one is given — onto row ``tok[i] - t0``: a one-row
read-modify-write in VMEM, which Mosaic takes at any width that is a multiple
of 128 lanes (a one-row DMA from HBM it refuses beyond 128: ops/sparse.py).
Rows outside every range — the chunk's padding, zeros by the grouped matmul's
contract — are never moved, and no ``[C, d]`` float32 product is written.

Numerical contract: runs in order, rows in order, each term rounded to
float32 before it is added — ``(acc + s0 g0) + s1 g1 ...``, the association
of a serial scatter-add of ``src.astype(f32) * gate[:, None]``: the result is
that scatter's bit for bit (:func:`sum_rows_ref`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "harmony_sum_rows"
#: token tiles tried, largest first. On the chip (PERF.md, PR 37) a call at
#: SmallThinker's shape took 1.43 / 1.08 / 0.92 ms at 64 / 128 / 256: a tile's
#: fixed cost (eight runs' copies and waits) is paid half as often
_TB = (256, 128, 64, 32, 16, 8)
#: the scoped VMEM the kernel asks for (a v5e has 128 MiB; Mosaic's default
#: scope is 16 MiB, which a tile of 256 tokens x 2,560 lanes passes by a
#: hair), and what a plan may fill of it
_VMEM_LIMIT, _VMEM_FREE = 32 * 2**20, 24 * 2**20


def _group(dtype) -> int:
    """Rows of one sublane group of ``dtype``: what a DMA moves whole (8 of
    float32, 16 of bfloat16)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _vmem_bytes(tb: int, d: int, dtype) -> int:
    """VMEM a grid step needs: the sum's block in and out, double-buffered;
    two source buffers of a run's longest range; the float32 terms staged
    from one."""
    rows, size = tb + _group(dtype), jnp.dtype(dtype).itemsize
    return 4 * tb * d * 4 + 2 * rows * d * size + rows * d * 4


def tile_plan(tokens: int, d: int, dtype) -> int:
    """Tokens a grid step holds: the largest of 256..8 that divides
    ``tokens`` and fits the kernel's VMEM at this width and source dtype; a
    token count that none divides is one tile."""
    fits = [tb for tb in _TB if tokens % tb == 0
            and _vmem_bytes(tb, d, dtype) <= _VMEM_FREE]
    return fits[0] if fits else tokens


def note_plan(tokens: int, rows: int, d: int, runs: int, dtype) -> None:
    """Trace-time record of the kernel's tiling (STATUS ``kernel_plans``):
    block_q = the token tile, block_k = the chunk's rows, sub = the runs,
    grid_steps = the tiles a call walks. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        tb = tile_plan(tokens, d, dtype)
        note_kernel_plan(KERNEL_NAME, tb, rows, runs, tokens // tb, True,
                         d=d, dv=d)
    except Exception:
        pass


def sum_rows_ref(acc, src, tok, held, gate=None):
    """The serial scatter-add the kernel stands for: rows ``[0, held)`` of
    ``src``, widened, times their gates, added onto ``acc[tok]``."""
    rows = src.astype(jnp.float32)
    if gate is not None:
        rows = rows * gate[:, None]
    keep = jnp.arange(src.shape[0]) < held
    return acc.at[tok].add(jnp.where(keep[:, None], rows, 0.0))


def _make_kernel(tb: int, runs: int, group: int, gated: bool):
    def kernel(*refs):
        """One token tile per grid step: copy the sum's block, then per run
        (its copies started a run ahead) wait, stage the float32 terms, and
        fold them row by row."""
        bounds, tok = refs[:2]
        gate = refs[2] if gated else None
        src, acc, out, sbuf, terms, sems = refs[2 + gated:]
        tile = pl.program_id(0)
        t0 = tile * tb
        fresh = bounds[(pl.num_programs(0) + 1) * runs]

        @pl.when(fresh == 0)
        def _():
            out[...] = acc[...]

        @pl.when(fresh != 0)
        def _():  # a sum that holds zeros was not read: see the index map
            out[...] = jnp.zeros_like(out)

        def span(h):
            """Run ``h``'s rows for this tile: ``(first, end, the sublane
            group the first lies in, groups to move)``."""
            s, e = bounds[tile * runs + h], bounds[(tile + 1) * runs + h]
            base = s // group * group
            n = jnp.where(e > s, (e - base + group - 1) // group, 0)
            return s, e, base, n

        def copies(h, act):
            _, _, base, n = span(h)
            slot = h % 2

            def one(g, c):
                rows = pl.ds(pl.multiple_of(base + g * group, group), group)
                to = pl.ds(pl.multiple_of(g * group, group), group)
                act(pltpu.make_async_copy(src.at[rows, :],
                                          sbuf.at[slot, to, :],
                                          sems.at[slot]))
                return c
            lax.fori_loop(0, n, one, 0)

        copies(0, lambda dma: dma.start())

        def run(h, c):
            @pl.when(h + 1 < runs)
            def _():
                copies(h + 1, lambda dma: dma.start())
            copies(h, lambda dma: dma.wait())
            s, e, base, n = span(h)
            slot = h % 2

            # a term is widened, multiplied, rounded and STORED before the
            # fold reads it: one add a term, never a fused multiply-add
            def stage(g, c):
                at = pl.ds(pl.multiple_of(g * group, group), group)
                rows = sbuf[slot, at, :].astype(jnp.float32)
                if gated:
                    first = base + g * group
                    sub = lax.broadcasted_iota(jnp.int32, (group, 1), 0)
                    col = jnp.zeros((group, 1), jnp.float32)
                    for r in range(group):
                        col = jnp.where(sub == r, gate[first + r], col)
                    rows = rows * col
                terms[at, :] = rows
                return c
            lax.fori_loop(0, n, stage, 0)

            def fold(i, c):
                out[pl.ds(tok[i] - t0, 1), :] += terms[pl.ds(i - base, 1), :]
                return c
            lax.fori_loop(s, e, fold, 0)
            return c
        lax.fori_loop(0, runs, run, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def sum_rows(acc: jnp.ndarray, src: jnp.ndarray, tok: jnp.ndarray,
             bounds: jnp.ndarray, gate: Optional[jnp.ndarray] = None,
             fresh=False, *, interpret: bool = False) -> jnp.ndarray:
    """``acc[tok[i]] += src[i] * gate[i]`` over the rows ``bounds`` names —
    ``acc [T, d]`` float32, updated IN PLACE (aliased onto the result),
    ``src [C, d]`` float32 or bfloat16, ``tok [C]`` int32, ``gate [C]``
    float32 or None (no product).

    ``bounds [T / TB + 1, H]`` int32 with ``TB = tile_plan(T, d,
    src.dtype)``: rows ``[bounds[j, h], bounds[j + 1, h])`` of ``src`` are
    run ``h``'s rows whose tokens lie in tile ``j`` (``[j TB, (j + 1) TB)``),
    their tokens strictly ascending; ranges of one run are consecutive, and
    a row in no range is not moved. Runs fold in order, rows in order.
    ``fresh`` (a traced bool): the caller's word that ``acc`` holds zeros —
    the kernel then starts from zeros of its own and does not read it (half
    the HBM traffic of a call: a loop's first pass)."""
    (T, d), C = acc.shape, src.shape[0]
    tb, group = tile_plan(T, d, src.dtype), _group(src.dtype)
    tiles, H = T // tb, bounds.shape[1]
    if (acc.dtype != jnp.float32 or src.shape != (C, d) or tok.shape != (C,)
            or bounds.shape != (tiles + 1, H)
            or (gate is not None and gate.shape != (C,))):
        raise ValueError(
            f"sum_rows: acc {acc.shape} {acc.dtype}, src {src.shape}, tok "
            f"{tok.shape}, bounds {bounds.shape} (want {(tiles + 1, H)}), "
            f"gate {None if gate is None else gate.shape}")
    gated = gate is not None
    scalars = [tok.astype(jnp.int32)]
    if gated:
        scalars.append(gate.astype(jnp.float32))
    if C % group:  # a copy moves whole sublane groups: give the last its rows
        src = jnp.pad(src, ((0, -C % group), (0, 0)))
        scalars = [jnp.pad(s, (0, -C % group)) for s in scalars]
    scalars.insert(0, jnp.concatenate([
        bounds.reshape(-1), jnp.reshape(fresh, (1,))]).astype(jnp.int32))
    rows = tb + group
    # a fresh sum's block index never changes, so the pipeline fetches one
    # block and no more
    acc_block = pl.BlockSpec(
        (tb, d), lambda j, b, *_: (j * (1 - b[(tiles + 1) * H]), 0))
    return pl.pallas_call(
        _make_kernel(tb, H, group, gated),
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), acc_block],
            out_specs=pl.BlockSpec((tb, d), lambda j, *_: (j, 0)),
            scratch_shapes=[pltpu.VMEM((2, rows, d), src.dtype),
                            pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        # operands: the prefetched scalars, src, acc
        input_output_aliases={len(scalars) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, src, acc)
