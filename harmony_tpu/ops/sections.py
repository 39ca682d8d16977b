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

``fold_row_sections`` IS the kernel (``interpret=True`` runs its body in
the Pallas interpreter, for CPU tests); ``fold_row_sections_ref`` is the
jnp reference — the rule on whole sections, then one concatenate — and
callers that know their mesh pick by name (``TableSpec.
fold_row_sections``). The rule is traced once, on ``(8, W)`` strips (its
scalars arrive as ``(1, W)`` rows), with the jnp ops the reference traces
on whole sections: per element the arithmetic is the same.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
#: Rows of every section moved per grid step: 1 MiB a copy at 1024 lanes,
#: 2 slots x (2K + 1) buffers of VMEM.
_BLOCK_ROWS = 256
#: Strips of 8 rows per trip of the in-block loop.
_UNROLL = 2

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


def fold_row_sections_ref(table: jnp.ndarray, side: jnp.ndarray,
                          consts: jnp.ndarray, rule: Rule, *, rows: int,
                          sections: int) -> jnp.ndarray:
    """The jnp reference: ``rule`` on the whole sections, every section
    replaced by ``stored + delta``, the rows after them passed through
    (``consts`` goes to the rule as it comes: rows, or true scalars)."""
    stored = tuple(table[k * rows:(k + 1) * rows] for k in range(sections))
    deltas = rule(stored, side, consts)
    parts = [s + d.astype(table.dtype) for s, d in zip(stored, deltas)]
    if sections * rows < table.shape[0]:
        parts.append(table[sections * rows:])
    return jnp.concatenate(parts)


def _make_kernel(rule: Rule, rows: int, sections: int, block: int):
    steps = -(-rows // block)
    unroll = _UNROLL if block % (_SUBLANES * _UNROLL) == 0 else 1
    # the last block ends at the section's end: it overlaps the one before
    # it, and only its ``tail`` new rows are written
    tail = rows - (steps - 1) * block

    def kernel(consts_ref, side_ref, table_ref, out_ref, in_buf, out_buf,
               read_sem, write_sem):
        i = pl.program_id(0)

        def first_row(step):
            return jnp.minimum(step * block, rows - block)

        def reads(step, slot):
            at = first_row(step)
            copies = [pltpu.make_async_copy(
                table_ref.at[pl.ds(k * rows + at, block)],
                in_buf.at[slot, k], read_sem.at[slot])
                for k in range(sections)]
            copies.append(pltpu.make_async_copy(
                side_ref.at[pl.ds(at, block)], in_buf.at[slot, sections],
                read_sem.at[slot]))
            return copies

        def writes(step, slot, n):
            # the block's last ``n`` rows, to the section's rows they are
            return [pltpu.make_async_copy(
                out_buf.at[slot, k, pl.ds(block - n, n)],
                out_ref.at[pl.ds(k * rows + first_row(step) + block - n, n)],
                write_sem.at[slot]) for k in range(sections)]

        slot = i % 2

        @pl.when(i == 0)
        def _():
            for c in reads(0, 0):
                c.start()

        @pl.when(i + 1 < steps)
        def _():
            for c in reads(i + 1, 1 - slot):
                c.start()

        for c in reads(i, slot):
            c.wait()

        @pl.when(i >= 2)
        def _():  # this slot's writes of two steps ago: always whole blocks
            for c in writes(i - 2, slot, block):
                c.wait()

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

        @pl.when(i < steps - 1)
        def _():
            for c in writes(i, slot, block):
                c.start()

        @pl.when(i == steps - 1)
        def _():
            for c in writes(i, slot, tail):
                c.start()
            if steps >= 2:
                for c in writes(i - 1, 1 - slot, block):
                    c.wait()
            for c in writes(i, slot, tail):
                c.wait()

    return kernel, steps


def fold_row_sections(table: jnp.ndarray, side: jnp.ndarray,
                      consts: jnp.ndarray, rule: Rule, *, rows: int,
                      sections: int, interpret: bool = False) -> jnp.ndarray:
    """``table[k * rows + r] += rule(stored, side, consts)[k][r]`` for
    every section k and row r, IN PLACE (the table operand is aliased onto
    the result: donate it), as the Pallas kernel.

    ``table`` is ``[R, W]``; its first ``sections * rows`` rows are the
    sections, the rest is not touched. ``side`` is ``[rows, W]`` (the
    gradient), ``consts`` ``[C, W]`` (the rule's scalars, one broadcast
    row each). ``rule(stored, side, consts)`` is elementwise: it gets the
    ``sections`` stored blocks and the side block, all one shape, and
    returns one delta per section."""
    R, W = table.shape
    if not (sections_kernel_ok(table.shape, table.dtype, rows, sections)
            and side.shape == (rows, W) and side.dtype == table.dtype
            and consts.ndim == 2 and consts.shape[1] == W):
        raise ValueError(
            f"fold_row_sections kernel takes float32 rows of whole lanes in "
            f"sections of whole 8-row tiles; got table={table.shape} "
            f"{table.dtype}, {sections} sections of {rows} rows, side "
            f"{side.shape} {side.dtype}, consts {consts.shape} (use "
            f"fold_row_sections_ref)")
    block = min(_BLOCK_ROWS, rows)
    kernel, steps = _make_kernel(rule, rows, sections, block)
    buffers = 2 * (2 * sections + 1) * block * W * 4
    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(consts.shape, lambda i: (0, 0)),        # consts
            pl.BlockSpec(memory_space=pl.ANY),                   # side
            pl.BlockSpec(memory_space=pl.ANY),                   # table
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, sections + 1, block, W), table.dtype),
            pltpu.VMEM((2, sections, block, W), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + (16 << 20)),
        interpret=interpret,
        name="harmony_fold_row_sections",
    )(consts, side, table)
