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

The norm a head, in the same pass (``norm=(w [hd], eps)``, PR 60). A block
that norms each head of q and k before it turns them (``head_norm``: the
Qwen3 family's) would make four float32 XLA passes and two transposes round
this kernel over rows it already holds ``hd`` lanes at a time. With ``norm``
the body first computes, per head row in float32,

    xh = x * rsqrt(mean(x^2) + eps)        n = xh * w

and turns ``n`` — the statistic is a lane reduce over the row the step has
loaded. Still ONE rounding, on the way out, where ``rms_norm`` + ``rope``
round the unit row and the weighed row to the activations' dtype before
``rope`` widens them again: measured on the chip against float64, bfloat16
rows come out 1.66e-3 of the result's RMS off (the XLA path 2.75e-3; ``dw``
2e-7 against 2.5e-3, a float32 sum for a bfloat16 one) and float32 rows
7e-8 (PERF.md, PR 60). :func:`turn_ref` states it in plain ``jnp``. Only the walk over
``[B, S, H hd]`` takes it (``heads`` = H): a block that norms its heads
hands q and k over as the projection left them, and no caller has them by
heads before the norm.

Its backward, under the same ``custom_vjp``: with ``gt = T^T g`` (the
transposed turn above, as the first stage of the body)

    dx = rstd * (w gt - xh * mean(xh * w gt))        dw = sum xh * gt

over rows and heads. ``xh`` and ``rstd`` are computed again from ``x`` in
the kernel: the residuals are ``x`` (which the projection's own backward
keeps anyway), ``w`` and the tables. THREE streams where the plain turn has
two — ``g`` by heads and ``x`` by rows in, ``dx`` by rows out: ``3 * B * H *
S * hd * itemsize`` bytes, SDAR's q 403 MB = 0.49 ms at 819 GB/s; the chip
read 0.71, and 0.50 for the forward beside the plain turn's 0.43 (the two
lane reduces a row). ``dw`` leaves the kernel as one ``[1, hd]`` partial sum
a grid step (every axis stays ``parallel``) and XLA adds the few KB.

Where the kernel declines (:func:`plan` returns None and the caller keeps
``rope`` — after ``rms_norm``, where the block norms its heads): a head that
is not a whole number of lane tiles (``hd % 128``), positions that no row
tile of 2,048..16 divides, operands that are neither bfloat16 nor float32.
Off the TPU the caller keeps ``rope`` too (the kernel runs there only
interpreted). ``norm`` changes nothing in the plan: the third stream and the
float32 temporaries fit the scoped VMEM asked for at the widest tile.
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
              grid_steps: int, normed: bool = False) -> None:
    """Trace-time record of the kernel's tiling (STATUS ``kernel_plans``):
    block_q = the row tile, block_k = d = the head width, dv = the columns
    turned, sub = the heads (batch x heads) one table tile serves — q's row
    and k's differ in it —, grid_steps = the tiles a call walks, normed =
    whether the call norms each head before it turns it. Never fails a
    trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        note_kernel_plan(KERNEL_NAME, rows, hd, heads, grid_steps, True,
                         d=hd, dv=turned, extra={"normed": normed})
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


def turn_ref(x, tab, shifts, norm=None):
    """What the kernel computes, in plain ``jnp``: ``x [..., S, hd]``.
    ``norm`` = ``(w [hd], eps)``: each head row RMS-normed and weighed in
    float32 before it turns, as the kernel does it."""
    xf = x.astype(jnp.float32)
    if norm is not None:
        w, eps = norm
        xf = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        xf = xf * w.astype(jnp.float32)
    y = xf * tab[0]
    for j, s in enumerate(shifts):
        y = y + jnp.roll(xf, s, axis=-1) * tab[1 + j]
    return y.astype(x.dtype)


def _make_kernel(shifts, group, hd, rows_in, rows_out, back, eps=None):
    """``group`` heads a grid step. ``rows_in`` / ``rows_out``: that side's
    block is ``[rows, group hd]`` — the heads side by side, as a projection
    leaves them — and not ``[group, rows, hd]``. ``back``: the transpose,
    the sines negated. ``eps``: the normed turn's kernels — forward ``(tab,
    x, w, out)``, backward ``(tab, g, x, w, dx, dw)`` with ``x`` the
    forward's input (by rows) and ``dw [1, hd]`` this step's share of the
    weight's gradient."""
    f32 = jnp.float32

    def head(ref, g, rows):
        return (ref[:, g * hd:(g + 1) * hd] if rows else ref[g]).astype(f32)

    def turned(tab, xf):
        y = xf * tab[0]
        for j, s in enumerate(shifts):
            term = pltpu.roll(xf, s, 1) * tab[1 + j]
            y = y - term if back else y + term
        return y

    def put(out, g, y):
        if rows_out:
            out[:, g * hd:(g + 1) * hd] = y.astype(out.dtype)
        else:
            out[g] = y.astype(out.dtype)

    def unit(xf):  # (x / rms(x), 1 / rms(x)) of each row
        rstd = jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return xf * rstd, rstd

    def kernel(tab, x, out):
        for g in range(group):
            put(out, g, turned(tab, head(x, g, rows_in)))

    def normed(tab, x, w, out):
        weight = w[...]
        for g in range(group):
            put(out, g, turned(tab, unit(head(x, g, rows_in))[0] * weight))

    def normed_back(tab, g, x, w, dx, dw):
        weight, share = w[...], jnp.zeros((1, hd), f32)
        for i in range(group):
            gt = turned(tab, head(g, i, rows_in))       # d loss / d (xh w)
            xh, rstd = unit(head(x, i, True))
            wg = gt * weight
            put(dx, i, rstd * (wg - xh * jnp.mean(xh * wg, axis=-1,
                                                  keepdims=True)))
            share = share + jnp.sum(xh * gt, axis=0, keepdims=True)
        dw[...] = share

    return kernel if eps is None else normed_back if back else normed


@functools.partial(jax.jit, static_argnames=(
    "shifts", "heads", "back", "eps", "interpret"))
def _turn_call(x, tab, w, of, shifts, heads, back, eps, interpret):
    """``back``: the transpose of the turn — the sines negated. ``heads``
    None: ``x [B, H, S, hd]`` in and out. ``heads`` = H: the forward reads
    ``[B, S, H hd]`` and writes ``[B, H, S, hd]``; ``back`` reads the latter
    and writes the former. ``w [hd]`` (else None) and ``eps``: the normed
    turn, ``heads`` given; its ``back`` takes ``of``, the forward's input
    ``[B, S, H hd]``, beside the cotangent ``x`` and returns ``(dx, dw
    [hd])``."""
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
    grid = (S // rows, B, H // group)
    by_row = pl.BlockSpec((None, rows, group * hd), lambda s, b, g: (b, s, g))
    by_head = pl.BlockSpec((None, group, rows, hd),
                           lambda s, b, g: (b, g, s, 0))
    # the table's block index holds still along the batch and the heads:
    # one fetch a row tile
    operands = [tab, x]
    in_specs = [pl.BlockSpec((len(shifts) + 1, rows, hd),
                             lambda s, b, g: (0, s, 0)),
                by_row if rows_in else by_head]
    out_shape = jax.ShapeDtypeStruct(
        (B, S, H * hd) if rows_out else (B, H, S, hd), x.dtype)
    out_specs = by_row if rows_out else by_head
    if w is not None:
        if back:  # the forward's input, by rows as the output
            operands.append(of)
            in_specs.append(by_row)
            out_shape = (out_shape,
                         jax.ShapeDtypeStruct(grid + (1, hd), jnp.float32))
            out_specs = (out_specs, pl.BlockSpec(
                (None, None, None, 1, hd), lambda s, b, g: (s, b, g, 0, 0)))
        operands.append(w.astype(jnp.float32).reshape(1, hd))
        in_specs.append(pl.BlockSpec((1, hd), lambda s, b, g: (0, 0)))
    y = pl.pallas_call(
        _make_kernel(shifts, group, hd, rows_in, rows_out, back, eps),
        name=KERNEL_NAME,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    if w is not None and back:
        y, dw = y[0], y[1].sum(axis=(0, 1, 2, 3))
    y = y.reshape(by_heads) if heads is None else y
    return y if w is None or not back else (y, dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _turn(x, tab, w, shifts, heads, eps, interpret):
    return _turn_call(x, tab, w, None, shifts, heads, False, eps, interpret)


def _fwd(x, tab, w, shifts, heads, eps, interpret):
    y = _turn_call(x, tab, w, None, shifts, heads, False, eps, interpret)
    return y, (tab, w, None if w is None else x)


def _bwd(shifts, heads, eps, interpret, res, g):
    # the transpose of (C + S_a R + S_b R^T): the same turn, sines negated
    tab, w, x = res
    out = _turn_call(g, tab, w, x, shifts, heads, True, eps, interpret)
    if w is None:
        return out, None, None
    return out[0], None, out[1].astype(w.dtype)


_turn.defvjp(_fwd, _bwd)


def turn(x: jnp.ndarray, tab: jnp.ndarray, shifts: Tuple[int, ...], *,
         heads: Optional[int] = None, norm=None, interpret: bool = False
         ) -> jnp.ndarray:
    """``x`` turned by :func:`tables`' pair: ``[B, H, S, hd]`` in x's dtype.
    ``x`` is ``[B, H, S, hd]`` or, with ``heads`` = H, ``[B, S, H hd]`` as a
    projection leaves it (head ``h`` is column block ``h``): the transpose
    to heads is then the input's index map, and the backward writes that
    layout back. ``norm`` = ``(w [hd], eps)``, with ``heads`` only: each
    head row is RMS-normed and weighed by ``w`` (float32 statistics) before
    it turns, in the same pass. Differentiable in ``x`` and ``w``. The shape must be one
    :func:`plan` serves; every trace notes the plan (:func:`note_plan`)."""
    if heads is None:
        B, H, S, hd = x.shape
    else:
        (B, S, width), H = x.shape, heads
        hd = width // H
    tiles = plan(S, hd, x.dtype, heads)
    w, eps = (None, None) if norm is None else norm
    if tiles is None or tab.shape != (len(shifts) + 1, S, hd) or (
            heads is not None and x.shape[2] != H * hd) or (
            w is not None and (heads is None or w.shape != (hd,))):
        raise ValueError(f"rotary.turn: no plan serves x {x.shape} {x.dtype} "
                         f"(heads={heads}) with tables {tab.shape}, shifts "
                         f"{shifts}" + ("" if w is None else
                                        f", norm weight {w.shape} (a norm "
                                        "wants heads)"))
    turned = hd if len(shifts) == 1 else 2 * shifts[0]
    note_plan(tiles[0], hd, turned, B * H, S // tiles[0] * B * H // tiles[1],
              normed=w is not None)
    return _turn(x, tab.astype(jnp.float32), w, tuple(shifts), heads,
                 None if eps is None else float(eps), interpret)
