"""The rotary turn of q and k as lane rolls: ``harmony_rotary``.

``models.transformer.rope`` turns ``x [B, H, S, hd]`` by widening it,
``split``-ting the head in two halves, ``concatenate``-ing ``[-x2, x1]`` back
and multiplying by a ``[S, hd]`` cos and sin that two more ``concatenate``s
built. Half a 128-wide head is half a lane tile, so each split and
concatenate is a relayout, and XLA took 5-9 x what HBM needs for the rows
(PERF.md, PR 58). The same arithmetic needs no split:

    rotate_half(x)[i] = -x[i + w/2]   i <  w/2        (w the columns turned)
                      = +x[i - w/2]   w/2 <= i < w

so with the sign and the choice of partner folded into the SINE TABLES

    y = x * cos + roll(x, w/2) * sin_a + roll(x, hd - w/2) * sin_b

where ``sin_a`` is ``+sin`` on ``[w/2, w)`` and zero elsewhere, ``sin_b`` is
``-sin`` on ``[0, w/2)`` and zero elsewhere, and ``cos`` is 1 on the columns
that pass (``[w, hd)``). A head turned whole (``w == hd``) has one roll —
both partners are ``hd/2`` lanes away — and one sine table ``[-sin | +sin]``.
The kernel knows nothing of bases, YaRN, fractions or offsets: only tables
(:func:`tables`, built in XLA as ``rope`` builds its angles, so the two agree
to the bit) and the lane shifts that go with them.

The walk: grid ``(S / rows, B, H / group)``, heads innermost, so the table
tile's block index does not change along them and is fetched once a row tile.
A call reads and writes the rows once: ``2 * B * H * S * hd * itemsize`` bytes
(+ ``(1 + rolls) * S * hd * 4`` of tables) — at a v5e's 819 GB/s, SDAR's q
(2 x 32 heads x 8,192 positions x 128 bf16) is 268 MB = 0.33 ms, its k 0.04;
the chip read 0.42 and 0.06 (640 and 530 GB/s; PERF.md, PR 58).

The transpose to heads as an index map: q and k leave their projection as
``[B, S, H hd]`` — head ``h`` is column block ``h`` — and a custom call is
one XLA cannot fuse its transpose into. With ``heads`` the kernel reads that
layout itself (``group`` heads a step, so a row's copy is ``group`` lane
tiles long) and writes ``[B, H, S, hd]``; its backward reads the latter and
writes the former. Same body, other ``BlockSpec``s: 0.38 ms for
SmallThinker's q where XLA's copy and the kernel by heads took 0.73.

The backward: ``y = (C + S_a R + S_b R^T) x`` with ``R`` the roll; the
tables repeat over the two halves, so ``R^T S_a = -S_b R^T`` and the
transpose is the SAME kernel with the sines negated — no residual but the
tables.

Numerical contract: float32 inside, each product rounded before it is added,
ONE rounding to the input's dtype on the way out — ``rope``'s arithmetic
(``x2 * -sin`` is ``-x2 * sin``; a column that passes is ``x * 1 + p * 0``).

Where the kernel declines (:func:`plan` returns None and the caller keeps
``rope``): a head that is not a whole number of lane tiles (``hd % 128``),
positions that no row tile of 2,048..16 divides, operands that are neither
bfloat16 nor float32. Off the TPU the caller keeps ``rope`` too (the kernel
runs there only interpreted).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "harmony_rotary"
_LANES = 128
#: row tiles tried, largest first, and the elements one may hold (its float32
#: form is 1 MiB: three table tiles and the rows in and out, double-buffered,
#: stay under the scoped VMEM asked for below)
_ROWS = (2048, 1024, 512, 256, 128, 64, 32, 16)
_TILE_ELEMENTS = 2048 * _LANES
#: heads a step of the ``[B, S, H hd]`` walk takes
_GROUP = 4
_VMEM_LIMIT = 32 * 2**20


def plan(positions: int, hd: int, dtype, heads: Optional[int] = None
         ) -> Optional[Tuple[int, int]]:
    """``(rows, group)`` a grid step turns — ``group`` heads of the largest
    row tile of 2,048..16 that divides ``positions`` and fits — or None where
    the kernel declines. ``heads``: the operand lies ``[B, S, heads hd]``
    and a step takes ``_GROUP`` heads (or all, if that does not divide them)
    so that a row's copy is that many lane tiles long."""
    if hd % _LANES or jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                                 jnp.dtype(jnp.float32)):
        return None
    group = 1 if heads is None else _GROUP if heads % _GROUP == 0 else heads
    fits = [r for r in _ROWS
            if positions % r == 0 and r * group * hd <= _TILE_ELEMENTS]
    return (fits[0], group) if fits else None


def note_plan(rows: int, hd: int, turned: int, heads: int,
              grid_steps: int) -> None:
    """Trace-time record of the kernel's tiling (STATUS ``kernel_plans``):
    block_q = the row tile, block_k = d = the head width, dv = the columns
    turned, sub = the heads (batch x heads) one table tile serves — q's row
    and k's differ in it —, grid_steps = the tiles a call walks. Never
    fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        note_kernel_plan(KERNEL_NAME, rows, hd, heads, grid_steps, True,
                         d=hd, dv=turned)
    except Exception:
        pass


def tables(positions: int, hd: int, theta: float, pos_offset=0,
           width: Optional[int] = None, scaled=None
           ) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    """``(tab [1 + rolls, S, hd] float32, shifts)`` for ``rope``'s arguments:
    ``tab[0]`` the cosines, ``tab[1 + j]`` the sines that multiply
    ``roll(x, shifts[j])``. The angles are formed exactly as ``rope`` forms
    them (``pos_offset`` may be traced; ``scaled``: a ``Rotary`` under YaRN)."""
    f32 = jnp.float32
    w = hd if width is None else width
    if scaled is None:
        inv_freq = theta ** (-jnp.arange(0, w, 2, dtype=f32) / w)
    else:
        inv_freq = scaled.inv_freq(w)
    ang = (pos_offset + jnp.arange(positions, dtype=f32)
           )[:, None] * inv_freq[None, :]                        # [S, w/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaled is not None:
        cos, sin = (t * scaled.attention_factor for t in (cos, sin))
    half = w // 2
    if w == hd:
        return jnp.stack([jnp.concatenate([cos, cos], axis=-1),
                          jnp.concatenate([-sin, sin], axis=-1)]), (half,)
    zeros = lambda n: jnp.zeros((positions, n), f32)
    return jnp.stack([
        jnp.concatenate([cos, cos, jnp.ones((positions, hd - w), f32)],
                        axis=-1),
        jnp.concatenate([zeros(half), sin, zeros(hd - w)], axis=-1),
        jnp.concatenate([-sin, zeros(hd - half)], axis=-1),
    ]), (half, hd - half)


def turn_ref(x, tab, shifts):
    """What the kernel computes, in plain ``jnp``: ``x [..., S, hd]``."""
    xf = x.astype(jnp.float32)
    y = xf * tab[0]
    for j, s in enumerate(shifts):
        y = y + jnp.roll(xf, s, axis=-1) * tab[1 + j]
    return y.astype(x.dtype)


def _make_kernel(shifts, group, hd, rows_in, rows_out, back):
    """``group`` heads a grid step. ``rows_in`` / ``rows_out``: that side's
    block is ``[rows, group hd]`` — the heads side by side, as a projection
    leaves them — and not ``[group, rows, hd]``. ``back``: the transpose,
    the sines negated."""
    def kernel(tab, x, out):
        for g in range(group):
            cols = slice(g * hd, (g + 1) * hd)
            xf = (x[:, cols] if rows_in else x[g]).astype(jnp.float32)
            y = xf * tab[0]
            for j, s in enumerate(shifts):
                term = pltpu.roll(xf, s, 1) * tab[1 + j]
                y = y - term if back else y + term
            if rows_out:
                out[:, cols] = y.astype(out.dtype)
            else:
                out[g] = y.astype(out.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("shifts", "heads", "back", "interpret"))
def _turn_call(x, tab, shifts, heads, back, interpret):
    """``back``: the transpose of the turn — the sines negated. ``heads``
    None: ``x [B, H, S, hd]`` in and out. ``heads`` = H: the forward reads
    ``[B, S, H hd]`` and writes ``[B, H, S, hd]``; ``back`` reads the latter
    and writes the former."""
    by_heads = x.shape
    if heads is None:  # [B H, S, hd]: rows of ONE head's width on both sides
        x = x.reshape(-1, *x.shape[2:])
        H, rows_in, rows_out = 1, True, True
    else:
        H, rows_in, rows_out = heads, not back, back
    if rows_in:
        B, S, hd = x.shape[0], x.shape[1], x.shape[2] // H
    else:
        B, _, S, hd = x.shape
    rows, group = plan(S, hd, x.dtype, heads)
    by_row = pl.BlockSpec((None, rows, group * hd), lambda s, b, g: (b, s, g))
    by_head = pl.BlockSpec((None, group, rows, hd),
                           lambda s, b, g: (b, g, s, 0))
    y = pl.pallas_call(
        _make_kernel(shifts, group, hd, rows_in, rows_out, back),
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct(
            (B, S, H * hd) if rows_out else (B, H, S, hd), x.dtype),
        grid=(S // rows, B, H // group),
        # the table's block index holds still along the batch and the
        # heads: one fetch a row tile
        in_specs=[pl.BlockSpec((len(shifts) + 1, rows, hd),
                               lambda s, b, g: (0, s, 0)),
                  by_row if rows_in else by_head],
        out_specs=by_row if rows_out else by_head,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tab, x)
    return y.reshape(by_heads) if heads is None else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _turn(x, tab, shifts, heads, interpret):
    return _turn_call(x, tab, shifts, heads, False, interpret)


def _fwd(x, tab, shifts, heads, interpret):
    return _turn_call(x, tab, shifts, heads, False, interpret), tab


def _bwd(shifts, heads, interpret, tab, g):
    # the transpose of (C + S_a R + S_b R^T): the same turn, sines negated
    return _turn_call(g, tab, shifts, heads, True, interpret), None


_turn.defvjp(_fwd, _bwd)


def turn(x: jnp.ndarray, tab: jnp.ndarray, shifts: Tuple[int, ...], *,
         heads: Optional[int] = None, interpret: bool = False
         ) -> jnp.ndarray:
    """``x`` turned by :func:`tables`' pair: ``[B, H, S, hd]`` in x's dtype.
    ``x`` is ``[B, H, S, hd]`` or, with ``heads`` = H, ``[B, S, H hd]`` as a
    projection leaves it (head ``h`` is column block ``h``): the transpose
    to heads is then the input's index map, and the backward writes that
    layout back. Differentiable in ``x``. The shape must be one
    :func:`plan` serves; every trace notes the plan (:func:`note_plan`)."""
    if heads is None:
        B, H, S, hd = x.shape
    else:
        (B, S, width), H = x.shape, heads
        hd = width // H
    tiles = plan(S, hd, x.dtype, heads)
    if tiles is None or tab.shape != (len(shifts) + 1, S, hd) or (
            heads is not None and x.shape[2] != H * hd):
        raise ValueError(f"rotary.turn: no plan serves x {x.shape} {x.dtype} "
                         f"(heads={heads}) with tables {tab.shape}, shifts "
                         f"{shifts}")
    turned = hd if len(shifts) == 1 else 2 * shifts[0]
    note_plan(tiles[0], hd, turned, B * H, S // tiles[0] * B * H // tiles[1])
    return _turn(x, tab.astype(jnp.float32), tuple(shifts), heads, interpret)
