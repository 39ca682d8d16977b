"""Row-section fold — an elementwise update rule over aligned row sections
of one table, applied in place.

A pytree model table holds ``[params | m | v]`` as K sections of ``rows``
rows in ONE array, and its optimizer is elementwise ACROSS them: row r of
the new parameters reads row r of p, m, v and of the gradient. XLA cannot
run that in place — a fusion may alias its operand only where it reads
the operand at the index it writes, and here each written row reads
``K - 1`` other rows of the same buffer — so the best it compiles is one
pass into K fresh section buffers and a second that copies them back
(13 section-sized streams for K = 3; PERF.md PR 30). ``fold_row_sections``
is the one pass: the table stays in HBM, aliased onto the result; blocks
of every section and of the side operand stream through VMEM double-
buffered, the rule runs on 8-row strips in registers, and each stored row
is read once and written once (2K + 1 streams).

The side operand — the gradient — comes as one array or in PIECES, ``[(
first_row, piece), ...]``: tile-aligned stretches of its rows, each an
operand of its own in HBM, read where its producer left it (a model's
gradient is a leaf's relayout copy a piece, ``LeafRows.to_pieces``; joining
them first would write the gradient a second time, 8 bytes a parameter).
Still one ``pallas_call``: the walk is a schedule of blocks that each lie
inside one piece, held in SMEM; the stored streams are read and written
the same for any pieces, and only the side block's DMA start picks its
source among the piece operands. A piece's last block ends at the piece's
end and overlaps the one before it, as the section's last block does; of
such a block only the new rows are written, and what else it read is
thrown away — every stored row is read once before it is written.

``fold_row_sections`` IS the kernel (``interpret=True`` runs its body in
the Pallas interpreter, for CPU tests); ``fold_row_sections_ref`` is the
jnp reference — the pieces concatenated, the rule on whole sections, then
one concatenate — and
callers that know their mesh pick by name (``TableSpec.
fold_row_sections``). The rule is traced once, on ``(8, W)`` strips (its
scalars arrive as ``(1, W)`` rows), with the jnp ops the reference traces
on whole sections: per element the arithmetic is the same.
"""
from __future__ import annotations

import operator
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
#: Rows of every section moved per grid step: 1 MiB a copy at 1024 lanes,
#: 2 slots x (2K + 1) buffers of VMEM.
_BLOCK_ROWS = 256
#: Strips of 8 rows per trip of the in-block loop.
_UNROLL = 2
#: Rows from which a caller should hand a stretch of ``side`` over as a
#: piece of its own rather than join it to its neighbours: a piece costs
#: the kernel up to a block of rows read twice (its last block overlaps),
#: joining costs the stretch a second pass — even at four blocks.
PIECE_ROWS = 4 * _BLOCK_ROWS

Rule = Callable[[Tuple[jnp.ndarray, ...], jnp.ndarray, jnp.ndarray],
                Sequence[jnp.ndarray]]


def sections_kernel_ok(shape, dtype, rows: int, sections: int) -> bool:
    """Whether the kernel takes a ``shape`` table holding ``sections``
    sections of ``rows`` rows from row 0: float32 rows of whole lanes,
    sections of whole 8-row tiles."""
    R, W = shape
    return (jnp.dtype(dtype) == jnp.float32 and W % _LANES == 0
            and rows >= _SUBLANES and rows % _SUBLANES == 0
            and sections >= 1 and sections * rows <= R)


def _as_pieces(side, rows: int):
    """``side`` as ``[(n_i, piece)]``: piece i's first ``n_i`` rows are
    the section's rows from its ``first_row`` to the next piece's (a
    single array is the one piece of all ``rows``)."""
    if not isinstance(side, (tuple, list)):
        return [(rows, side)]
    firsts = [first for first, _ in side]
    return [(end - first, p) for (first, p), end in zip(
        side, [*firsts[1:], rows])]


def fold_row_sections_ref(table: jnp.ndarray, side, consts, rule: Rule, *,
                          rows: int, sections: int) -> jnp.ndarray:
    """The jnp reference: ``rule`` on the whole sections, every section
    replaced by ``stored + delta``, the rows after them passed through
    (``side``: ``[rows, W]``, or ``[(first_row, piece), ...]`` as the
    kernel takes it, concatenated here; ``consts`` goes to the rule as it
    comes: rows, or true scalars)."""
    side = jnp.concatenate([p[:n] for n, p in _as_pieces(side, rows)])
    stored = tuple(table[k * rows:(k + 1) * rows] for k in range(sections))
    deltas = rule(stored, side, consts)
    parts = [s + d.astype(table.dtype) for s, d in zip(stored, deltas)]
    if sections * rows < table.shape[0]:
        parts.append(table[sections * rows:])
    return jnp.concatenate(parts)


# the schedule's lines, one column a grid step
_AT, _PIECE, _PIECE_AT, _NEW_AT, _NEW = range(5)


def _schedule(piece_rows: Sequence[int], rows: int, block: int) -> np.ndarray:
    """The walk ``[5, steps]``: every step one block of ``block`` rows
    lying inside ONE piece — its first row in the section (``_AT``), the
    piece and the block's first row in it, and which of the block's rows
    are NEW: ``_NEW`` rows from ``_NEW_AT``. A piece's last block ends at
    the piece's end and overlaps the one before it; a piece shorter than
    a block (the caller pads it to one, its rows at ``_NEW_AT``) lies in a
    block pushed back inside the section. New rows are disjoint and cover
    the section; the rest of a block is read and thrown away."""
    steps, first = [], 0
    for j, n in enumerate(piece_rows):
        if n < block:
            at = min(max(first + n - block, 0), rows - block)
            steps.append((at, j, 0, first - at, n))
        else:
            for k in range(-(-n // block)):
                at = min(k * block, n - block)
                steps.append((first + at, j, at, k * block - at,
                              at + block - k * block))
        first += n
    return np.asarray(steps, np.int32).T


def _make_kernel(rule: Rule, rows: int, sections: int, block: int,
                 n_pieces: int, steps: int):
    unroll = _UNROLL if block % (_SUBLANES * _UNROLL) == 0 else 1
    # a block's new rows are whole tiles, fewer than a block: they go out
    # as at most one copy for every set bit of their tile count
    bits = [_SUBLANES << b
            for b in reversed(range((block // _SUBLANES - 1).bit_length()))]

    def kernel(sched, consts_ref, *refs):
        pieces, (table_ref, out_ref, in_buf, out_buf, read_sem,
                 write_sem) = refs[:n_pieces], refs[n_pieces:]
        i = pl.program_id(0)

        def tile(x):
            return pl.multiple_of(x, _SUBLANES)

        def stored_reads(step, slot):
            at = sched[_AT, step]
            return [pltpu.make_async_copy(
                table_ref.at[pl.ds(tile(k * rows + at), block)],
                in_buf.at[slot, k], read_sem.at[slot])
                for k in range(sections)]

        def side_read(j, at, slot):
            return pltpu.make_async_copy(
                pieces[j].at[pl.ds(at, block)], in_buf.at[slot, sections],
                read_sem.at[slot])

        def start_reads(step, slot):
            for c in stored_reads(step, slot):
                c.start()
            # the side block from the one piece this step reads: a
            # descriptor a piece, and only the start chooses among them —
            # by halving (one nest of 60 conditionals is more than Mosaic
            # compiles)
            piece, at = sched[_PIECE, step], tile(sched[_PIECE_AT, step])

            def start_side(lo, hi):
                if hi - lo == 1:
                    side_read(lo, at, slot).start()
                    return
                mid = (lo + hi) // 2
                jax.lax.cond(piece < mid, lambda: start_side(lo, mid),
                             lambda: start_side(mid, hi))

            start_side(0, n_pieces)

        def writes(step, slot, act):
            """``act`` on the copies of the step's new rows to where they
            are stored: one a section for a whole block, else one a
            section and set bit of the row count."""
            at, n = sched[_AT, step], sched[_NEW, step]

            def copies(r, size):
                return [pltpu.make_async_copy(
                    out_buf.at[slot, k, pl.ds(tile(r), size)],
                    out_ref.at[pl.ds(tile(k * rows + at + r), size)],
                    write_sem.at[slot]) for k in range(sections)]

            @pl.when(n == block)
            def _():
                for c in copies(0, block):
                    act(c)

            @pl.when(n < block)
            def _():
                r = sched[_NEW_AT, step]
                for size in bits:
                    @pl.when((n & size) != 0)
                    def _(r=r, size=size):
                        for c in copies(r, size):
                            act(c)
                    r = r + (n & size)

        start, wait = (operator.methodcaller(m) for m in ("start", "wait"))

        slot = i % 2

        @pl.when(i == 0)
        def _():
            start_reads(0, 0)

        @pl.when(i + 1 < steps)
        def _():
            start_reads(i + 1, 1 - slot)

        for c in stored_reads(i, slot) + [side_read(0, 0, slot)]:
            c.wait()

        @pl.when(i >= 2)
        def _():  # this slot's writes of two steps ago
            writes(i - 2, slot, wait)

        consts = consts_ref[...]

        def strip(s, carry):
            for u in range(unroll):
                r = pl.multiple_of((s * unroll + u) * _SUBLANES, _SUBLANES)
                rs = pl.ds(r, _SUBLANES)
                stored = tuple(in_buf[slot, k, rs, :]
                               for k in range(sections))
                deltas = rule(stored, in_buf[slot, sections, rs, :], consts)
                for k in range(sections):
                    out_buf[slot, k, rs, :] = stored[k] + deltas[k]
            return carry

        jax.lax.fori_loop(0, block // (_SUBLANES * unroll), strip, 0)

        writes(i, slot, start)

        @pl.when(i == steps - 1)
        def _():
            if steps >= 2:
                writes(i - 1, 1 - slot, wait)
            writes(i, slot, wait)

    return kernel


def fold_row_sections(table: jnp.ndarray, side, consts: jnp.ndarray,
                      rule: Rule, *, rows: int, sections: int,
                      interpret: bool = False) -> jnp.ndarray:
    """``table[k * rows + r] += rule(stored, side, consts)[k][r]`` for
    every section k and row r, IN PLACE (the table operand is aliased onto
    the result: donate it), as the Pallas kernel.

    ``table`` is ``[R, W]``; its first ``sections * rows`` rows are the
    sections, the rest is not touched. ``side`` (the gradient) is ``[rows,
    W]``, or ``[(first_row, piece), ...]`` — its rows in pieces, each an
    operand of its own that is read where it lies: piece i, ``[>= n_i,
    W]``, holds in its first ``n_i`` rows the section's rows from its
    ``first_row`` (0 for the first) to the next piece's (``rows`` for the
    last), whole tiles. ``consts`` is ``[C, W]`` (the rule's scalars, one
    broadcast row each). ``rule(stored, side, consts)`` is elementwise: it
    gets the ``sections`` stored blocks and the side block, all one
    shape, and returns one delta per section."""
    R, W = table.shape
    pieces = [(n, p) for n, p in _as_pieces(side, rows) if n]
    if not (sections_kernel_ok(table.shape, table.dtype, rows, sections)
            and all(p.ndim == 2 and p.shape[1] == W and p.dtype == table.dtype
                    and 0 < n <= p.shape[0] and n % _SUBLANES == 0
                    for n, p in pieces)
            and sum(n for n, _ in pieces) == rows
            and consts.ndim == 2 and consts.shape[1] == W):
        raise ValueError(
            f"fold_row_sections kernel takes float32 rows of whole lanes in "
            f"sections of whole 8-row tiles; got table={table.shape} "
            f"{table.dtype}, {sections} sections of {rows} rows, side "
            f"{[(n, p.shape, str(p.dtype)) for n, p in pieces]}, consts "
            f"{consts.shape} (use fold_row_sections_ref)")
    block = min(_BLOCK_ROWS, rows)
    sched = _schedule([n for n, _ in pieces], rows, block)
    # a piece shorter than a block: zeros around it, to the block it is in
    new_at = dict(zip(sched[_PIECE], sched[_NEW_AT]))
    pieces = [p if n >= block else jnp.pad(
        p[:n], ((new_at[j], block - new_at[j] - n), (0, 0)))
        for j, (n, p) in enumerate(pieces)]
    steps = sched.shape[1]
    kernel = _make_kernel(rule, rows, sections, block, len(pieces), steps)
    buffers = 2 * (2 * sections + 1) * block * W * 4
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                               # the walk
            grid=(steps,),
            in_specs=[pl.BlockSpec(consts.shape, lambda i, sched: (0, 0)),
                      *[hbm] * len(pieces),                      # side
                      hbm],                                      # table
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2, sections + 1, block, W), table.dtype),
                pltpu.VMEM((2, sections, block, W), table.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2 + len(pieces): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (16 << 20)),
        interpret=interpret,
        name="harmony_fold_row_sections",
    )(jnp.asarray(sched), consts, *pieces, table)
