"""A delta-rule block's convolution, SiLU, l2 norms and transposes to heads
as one pass over rows: ``harmony_conv_heads``.

``models.transformer._gdn_mixer`` / ``_kda_mixer`` took ``q | k | v`` from
their projection ``[B, S, C]`` through ``_causal_conv`` (float32, K passes),
``silu``, a ``split``, two ``_l2norm``s and three ``heads(...)`` transposes
in XLA, which laid the convolution out with the POSITIONS minor (a tap a
lane shift): a relayout in, ``f32[S, C]`` materialised twice, a fusion a
norm, three relayouts out — 4.98 GB accessed forward and 13.85 forward +
backward for Qwen3-Next's ``[16,384, 8,192]`` where 0.54 and 0.81 are
needed (PERF.md, PR 62). The same arithmetic by rows, a head (128 lanes) at
a time:

    pre_t = sum_j taps[j] * x_{t-(K-1)+j}      float32, zero before position 0
    a     = silu(pre)
    y     = a                                           section "plain"      (v)
          = a * rsqrt(sum_head(a^2) + eps)              section "l2"         (k)
          = a * rsqrt(sum_head(a^2) + eps) * hd^-1/2    section "l2_scaled"  (q)
    out[b, h, t, :] = y[t, h hd:(h+1) hd] rounded to x's dtype

The walk: a section is a static range of whole heads of the operand's
columns and ONE ``pallas_call`` of the one kernel (its own output, so
nothing is sliced or copied after it): grid ``(S / rows, B, heads / group)``,
heads innermost as in ``ops/rotary.py``. The input ``BlockSpec`` picks
column block ``first + g`` of ``[B, S, C]`` as the projection left it —
columns no section names (Qwen3-Next's ``z``) are never read —, the output
``BlockSpec`` writes ``[B, heads, S, hd]``. The K - 1 rows before a tile
come from a second view of the same operand, the 16 rows that end where
the tile starts (zeros for the first tile); a tap is a shift along
SUBLANES of float32 rows already widened in VMEM.

Bytes a call: the forward reads and writes the section's rows once,
``2 * B * S * heads * hd * itemsize`` (+ 16 / rows of halo) — Qwen3-Next's
three sections (64 heads, 16,384 positions, bf16) 0.54 GB = 0.66 ms at a
v5e's 819 GB/s; the backward reads ``x`` and ``dy`` and writes ``dx``,
0.81 GB = 0.98 ms. The chip read 1.01 ms forward (532 GB/s) and 1.98
backward (407 GB/s: two lane reduces, a sigmoid and two shifted passes a
row) in the step, where XLA's lines took 9.5 and 21.7 forward / forward +
backward; the cell's step went 494 -> 387 ms (PERF.md §6, PR 62).

The backward, under the same ``custom_vjp``: reads ``x`` (16 rows of halo
on both sides) and ``dy`` by heads (16 rows after), computes ``pre``,
``a`` and the norm's ``rstd`` again in VMEM for the tile and the 8 rows
after it, then

    da   = rstd * (c dy - n * sum_head(n * c dy))     n = a rstd, c the scale
    dpre = da * sig(pre) * (1 + pre * (1 - sig(pre)))
    dx_t = sum_i taps[K-1-i] * dpre_{t+i}             rows AFTER the tile
    dtaps[j] = sum_t dpre_t * x_{t-(K-1)+j}

and writes ``dx [B, S, columns of the section]`` by rows; ``dtaps`` leaves
as one float32 ``[K, group hd]`` partial a grid step (every axis stays
``parallel``) and XLA adds the few KB. The residuals are ``x`` — the
projection's output, which its own backward keeps anyway — and ``taps``:
nothing is named in ``ops/residuals.py``, under ``remat`` the forward runs
twice. Columns of ``x`` that no section names come back zero.

Numerical contract: the scope's own. bfloat16 or float32 in, float32
inside, each product rounded before it is added (in ``_causal_conv``'s
order), the l2 sum in float32, ONE rounding to x's dtype on the way out —
what ``q.astype(cfg.dtype)`` did. :func:`conv_heads_ref` states it in plain
``jnp``.

Where the kernel declines (:func:`plan` returns None and the caller keeps
``_causal_conv`` and the rest): a head that is not one lane tile wide
(``hd != 128``), positions that no row tile of 1,024..16 divides, more
than 9 taps (the halo kept is 8 rows), operands that are neither bfloat16
nor float32, sections that do not start at column 0 and tile whole heads.
Off the TPU the caller keeps its lines too (the kernel runs there only
interpreted).
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "harmony_conv_heads"
KINDS = ("plain", "l2", "l2_scaled")
_LANES = 128
#: rows of the halo's view (a bfloat16 tile's height) and those kept of it
_HALO, _KEPT = 16, 8
#: row tiles tried, largest first, and the elements one may hold
_ROWS = (1024, 512, 256, 128, 64, 32, 16)
_TILE_ELEMENTS = 1024 * 4 * _LANES
#: heads a step takes at most, so that a row's copy is that many lane tiles
_GROUP = 4
_VMEM_LIMIT = 48 * 2**20

Sections = Tuple[Tuple[str, int], ...]


def plan(positions: int, hd: int, dtype, sections: Sections, taps: int = 4
         ) -> Optional[Tuple[int, int]]:
    """``(rows, group)`` a grid step takes — ``group`` heads (the largest
    of 4, 2, 1 that divides every section) of the largest row tile of
    1,024..16 that divides ``positions`` and fits — or None where the
    kernel declines. ``sections``: ``((kind, heads), ...)`` from column 0."""
    if hd != _LANES or not 1 <= taps <= _KEPT + 1 or not sections or (
            jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))):
        return None
    if any(kind not in KINDS or heads < 1 for kind, heads in sections):
        return None
    group = math.gcd(_GROUP, *(heads for _, heads in sections))
    fits = [r for r in _ROWS
            if positions % r == 0 and r * group * hd <= _TILE_ELEMENTS]
    return (fits[0], group) if fits else None


def note_plan(rows: int, hd: int, group: int, sections: Sections,
              grid_steps: int) -> None:
    """Trace-time record of the kernel's tiling (STATUS ``kernel_plans``):
    block_q = the row tile, block_k = d = dv = the head width, sub = the
    heads a grid step takes, grid_steps = the tiles a forward walks over
    all sections, sections = ``kind:heads`` in column order. Never fails a
    trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        note_kernel_plan(
            KERNEL_NAME, rows, hd, group, grid_steps, True, d=hd, dv=hd,
            extra={"sections": ",".join(f"{k}:{h}" for k, h in sections)})
    except Exception:
        pass


def conv_heads_ref(x, taps, sections: Sections, eps: float):
    """What the kernel computes, in plain ``jnp`` — ``_causal_conv`` +
    ``silu`` + ``_l2norm`` + ``heads`` as the mixers wrote them: a tuple of
    ``[B, heads, S, hd]`` in x's dtype, a section each."""
    f32 = jnp.float32
    B, S = x.shape[0], x.shape[1]
    K, conv = taps.shape
    tp = jnp.pad(x[..., :conv].astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
    a = jax.nn.silu(sum(tp[:, j:j + S] * taps[j].astype(f32)
                        for j in range(K)))
    out, col = [], 0
    for kind, heads in sections:
        t = a[..., col:col + heads * _LANES]
        t = t.reshape(B, S, heads, _LANES).transpose(0, 2, 1, 3)
        if kind != "plain":
            t = t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + eps)
        if kind == "l2_scaled":
            t = t * _LANES ** -0.5
        out.append(t.astype(x.dtype))
        col += heads * _LANES
    return tuple(out)


def _make_kernel(kind, K, rows, group, hd, eps, back):
    """``group`` heads a grid step, a head at a time through the float32
    scratch ``X [8 + rows + 8, hd]`` (the rows kept of the halo before, the
    tile, and — the backward's — 8 rows after) and, the backward's, ``D
    [rows + 8, hd]`` (``dpre`` of the tile and of the 8 rows after).
    Forward ``(taps, x, before, out, X)``; backward ``(taps, x, before,
    after, dy, dy_after, dx, dtaps, X, D)``."""
    f32 = jnp.float32
    lead = _KEPT - (K - 1)          # X's row under tap 0 of the tile's row 0
    scale = hd ** -0.5

    def fill(X, x, before, after, cols, first, last):
        kept = before[:, cols].astype(f32)[_HALO - _KEPT:]
        X[0:_KEPT] = jnp.where(first, 0.0, kept)
        X[_KEPT:_KEPT + rows] = x[:, cols].astype(f32)
        if after is not None:
            kept = after[:, cols].astype(f32)[:_KEPT]
            X[_KEPT + rows:] = jnp.where(last, 0.0, kept)

    def conv(X, tp, start, n):      # rows start .. start + n of the tile
        acc = X[pl.ds(lead + start, n)] * tp[0:1]
        for j in range(1, K):
            acc = acc + X[pl.ds(lead + start + j, n)] * tp[j:j + 1]
        return acc

    def normed(a):                   # (n, rstd) of each row
        rstd = jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)
        return a * rstd, rstd

    def forward(taps, x, before, out, X):
        first = pl.program_id(0) == 0
        for g in range(group):
            cols = slice(g * hd, (g + 1) * hd)
            fill(X, x, before, None, cols, first, None)
            y = jax.nn.silu(conv(X, taps[:, cols], 0, rows))
            if kind != "plain":
                y = normed(y)[0]
            if kind == "l2_scaled":
                y = y * scale
            out[g] = y.astype(out.dtype)

    def dpre(X, tp, start, n, gy):
        pre = conv(X, tp, start, n)
        sig = jax.nn.sigmoid(pre)
        if kind != "plain":
            n_, rstd = normed(pre * sig)
            if kind == "l2_scaled":
                gy = gy * scale
            gy = rstd * (gy - n_ * jnp.sum(n_ * gy, axis=-1, keepdims=True))
        return gy * (sig * (1.0 + pre * (1.0 - sig)))

    def backward(taps, x, before, after, dy, dy_after, dx, dtaps, X, D):
        first = pl.program_id(0) == 0
        last = pl.program_id(0) == pl.num_programs(0) - 1
        for g in range(group):
            cols = slice(g * hd, (g + 1) * hd)
            tp = taps[:, cols]
            fill(X, x, before, after, cols, first, last)
            D[0:rows] = dpre(X, tp, 0, rows, dy[g].astype(f32))
            D[rows:] = dpre(X, tp, rows, _KEPT, jnp.where(
                last, 0.0, dy_after[g].astype(f32)[:_KEPT]))
            acc = D[pl.ds(0, rows)] * tp[K - 1:K]
            for i in range(1, K):
                acc = acc + D[pl.ds(i, rows)] * tp[K - 1 - i:K - i]
            dx[:, cols] = acc.astype(dx.dtype)
            d = D[0:rows]
            for j in range(K):
                dtaps[j:j + 1, cols] = jnp.sum(
                    d * X[pl.ds(lead + j, rows)], axis=0, keepdims=True)

    return backward if back else forward


@functools.partial(jax.jit, static_argnames=(
    "kind", "first", "heads", "tiles", "eps", "interpret"))
def _section_call(x, taps, dy, kind, first, heads, tiles, eps, interpret):
    """One section — ``heads`` heads from head ``first`` of ``x [B, S, C]``
    under ``taps [K, conv]`` (float32, the same columns). ``dy`` None: the
    forward, ``[B, heads, S, hd]``. ``dy [B, heads, S, hd]``: the backward,
    ``(dx [B, S, heads hd], dtaps [K, heads hd])``. ``tiles``: the
    entry's :func:`plan`."""
    B, S, _ = x.shape
    K, hd = taps.shape[0], _LANES
    rows, group = tiles
    grid = (S // rows, B, heads // group)
    halos, off = rows // _HALO, first // group
    by_row = lambda n, at: pl.BlockSpec(
        (None, n, group * hd), lambda s, b, g: (b, at(s), g + off))
    by_head = lambda n, at: pl.BlockSpec(
        (None, group, n, hd), lambda s, b, g: (b, g, at(s), 0))
    tile = lambda s: s
    before = lambda s: jnp.maximum(s * halos - 1, 0)
    after = lambda s: jnp.minimum((s + 1) * halos, S // _HALO - 1)
    tap_spec = pl.BlockSpec((K, group * hd), lambda s, b, g: (0, g + off))
    X = pltpu.VMEM((2 * _KEPT + rows, hd), jnp.float32)
    if dy is None:
        operands = (taps, x, x)
        in_specs = [tap_spec, by_row(rows, tile), by_row(_HALO, before)]
        out_shape = jax.ShapeDtypeStruct((B, heads, S, hd), x.dtype)
        out_specs = by_head(rows, tile)
        scratch = [X]
    else:
        operands = (taps, x, x, x, dy, dy)
        in_specs = [tap_spec, by_row(rows, tile), by_row(_HALO, before),
                    by_row(_HALO, after), by_head(rows, tile),
                    by_head(_HALO, after)]
        out_shape = (jax.ShapeDtypeStruct((B, S, heads * hd), x.dtype),
                     jax.ShapeDtypeStruct((S // rows, B, K, heads * hd),
                                          jnp.float32))
        out_specs = (pl.BlockSpec((None, rows, group * hd),
                                  lambda s, b, g: (b, s, g)),
                     pl.BlockSpec((None, None, K, group * hd),
                                  lambda s, b, g: (s, b, 0, g)))
        scratch = [X, pltpu.VMEM((rows + _KEPT, hd), jnp.float32)]
    out = pl.pallas_call(
        _make_kernel(kind, K, rows, group, hd, eps, dy is not None),
        name=KERNEL_NAME,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    return out if dy is None else (out[0], out[1].sum(axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv_heads(x, taps, spans, tiles, eps, interpret):
    """``spans``: ``((kind, first head, heads), ...)``, a section each."""
    return tuple(
        _section_call(x, taps, None, kind, first, heads, tiles, eps,
                      interpret)
        for kind, first, heads in spans)


def _fwd(x, taps, spans, tiles, eps, interpret):
    return _conv_heads(x, taps, spans, tiles, eps, interpret), (x, taps)


def _bwd(spans, tiles, eps, interpret, res, gs):
    x, taps = res
    parts = [_section_call(x, taps, g, kind, first, heads, tiles, eps,
                           interpret)
             for (kind, first, heads), g in zip(spans, gs)]
    dx = jnp.concatenate([p[0] for p in parts], axis=-1)
    dtaps = jnp.concatenate([p[1] for p in parts], axis=-1)
    rest = x.shape[2] - dx.shape[2]  # columns no section names: zero
    if rest:
        dx = jnp.pad(dx, ((0, 0), (0, 0), (0, rest)))
    return dx, dtaps


_conv_heads.defvjp(_fwd, _bwd)


def conv_heads(x: jnp.ndarray, taps: jnp.ndarray,
               sections: Sequence[Tuple[str, int]], eps: float, *,
               interpret: bool = False) -> Tuple[jnp.ndarray, ...]:
    """``x [B, S, C]`` as a projection left it, its first ``taps.shape[1]``
    columns convolved along ``S`` by ``taps [K, conv]`` (depthwise, causal,
    float32), through a SiLU and laid out by heads: a tuple of ``[B, heads,
    S, 128]`` in x's dtype, one a section — ``sections`` = ``((kind,
    heads), ...)`` in column order from column 0, ``kind`` one of
    :data:`KINDS` (``eps`` under the l2 norm's root). Differentiable in
    ``x`` and ``taps``; columns past the sections take a zero gradient. The
    shape must be one :func:`plan` serves; every trace notes the plan
    (:func:`note_plan`)."""
    sections = tuple((str(k), int(h)) for k, h in sections)
    B, S, C = x.shape
    K, conv = taps.shape
    tiles = plan(S, _LANES, x.dtype, sections, K)
    if tiles is None or conv != _LANES * sum(h for _, h in sections) or (
            conv > C):
        raise ValueError(
            f"conv_heads: no plan serves x {x.shape} {x.dtype} with taps "
            f"{taps.shape} and sections {sections}")
    rows, group = tiles
    note_plan(rows, _LANES, group, sections,
              S // rows * B * (conv // _LANES) // group)
    ends = list(itertools.accumulate(h for _, h in sections))
    spans = tuple((kind, end - heads, heads)
                  for (kind, heads), end in zip(sections, ends))
    return _conv_heads(x, taps.astype(jnp.float32), spans, tiles,
                       float(eps), interpret)
