"""Ragged grouped matmul for TPU: three Pallas kernels under one ``custom_vjp``.

The dropless expert layer (models/moe.py) sorts its token-slots by expert, so
an expert's rows are one contiguous run of ``x`` and the layer is

    out[r] = x[r] @ w[g(r)]          g(r) = the group whose run holds row r

with static shapes (``x [M, K]``, ``w [G, K, N]``) and run lengths known only
on the device (``group_sizes [G]`` int32, ``sum <= M``). Rows past the last
group belong to no group: no tile of them is visited, and they come back 0.

  * ``harmony_gmm_fwd``  out  [M, N] = x  [M, K] @ w[g]      (by group)
  * ``harmony_gmm_dx``   dx   [M, K] = dy [M, N] @ w[g]^T    (the same, rhs transposed)
  * ``harmony_gmm_dw``   dw[g][K, N] = x[rows of g]^T @ dy[rows of g]

The scheme is megablox's (jax.experimental.pallas.ops.tpu.megablox), written
for this repo's needs: the m dimension is cut into tiles of ``tm`` rows and
the grid walks (tile, group) VISITS — a tile that a group boundary crosses is
visited once per group it touches, consecutively, with a row mask — so the
grid along m has ``tiles_m + G - 1`` steps at most and exactly
``num_visits`` (a device scalar: the grid bound is dynamic) at run time. The
visit -> (group, tile) maps are scalar-prefetched and drive the BlockSpec
index maps. Empty groups cost nothing forward and one zeroing visit in dw;
one group holding every row, or sizes off the tile, are ordinary inputs.

The kernels choose their tiles from the shape (:func:`tile_plan`), as the
flash kernels do (ops/attention.py): a grid step costs ~0.35 us on a v5e
whatever it computes, so a step should hold as much as VMEM allows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the kernels' names in a device trace (perf/layer_metrics read them) and in
#: STATUS ``kernel_plans``
KERNEL_NAMES = {"fwd": "harmony_gmm_fwd", "dx": "harmony_gmm_dx",
                "dw": "harmony_gmm_dw"}
_TM = (512, 256, 128)          # row tiles tried, largest first
_TKN = (1024, 512, 256, 128)   # k / n tiles tried, largest first
_VMEM_FREE = 12 * 2**20        # a step up to here runs under Mosaic's 16 MiB
                               # default scoped VMEM
_SUBLANES = 8


class Tiles(NamedTuple):
    """One kernel's tiling: ``tm`` rows of the ragged dimension a grid step
    holds, by ``tk`` x ``tn`` of the weight's two dimensions."""
    tm: int
    tk: int
    tn: int


def _whole_or_tile(length: int, sizes) -> int:
    """The largest listed tile that divides ``length``; a length that none
    divides is one block as it is (a block equal to the array's dimension
    always tiles), and so is a multiple of the 128 lanes that no listed
    tile ABOVE 128 divides (1408 = 11 x 128: whole, not eleven tiles of 128
    at a grid step's fixed cost each)."""
    tile = next((s for s in sizes if length % s == 0), length)
    return length if tile == sizes[-1] else tile


def _vmem_bytes(kernel: str, t: Tiles, itemsize: int) -> int:
    """VMEM one grid step needs: the three BlockSpec tiles double-buffered
    and the f32 accumulator (dw accumulates a [tk, tn] weight tile, the
    others a [tm, tn] output tile)."""
    blocks = t.tm * t.tk + t.tk * t.tn + t.tm * t.tn
    acc = (t.tk * t.tn) if kernel == "dw" else (t.tm * t.tn)
    return 2 * blocks * itemsize + 4 * acc


def tile_plan(m: int, k: int, n: int, dtype) -> Tiles:
    """Tiles for ``x [m, k]`` against ``w [G, k, n]`` (one plan serves the
    three kernels: dx swaps the roles of k and n, dw accumulates over m).
    ``tm`` is the largest of 512/256/128 that ``m`` reaches (the caller pads
    ``m`` to a multiple: rows past the groups are never visited); ``tk`` and
    ``tn`` the largest of 1024..256 dividing k and n, else the whole width,
    shrunk in turn while a step would not fit Mosaic's default scoped VMEM
    (1408 stays whole and the other width gives way: ``[2048, 1408]`` plans
    512 x 512 x 1408, ``[1408, 2048]`` 512 x 1408 x 512)."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = next((s for s in _TM if m >= s), -(-m // _SUBLANES) * _SUBLANES)
    tk, tn = _whole_or_tile(k, _TKN), _whole_or_tile(n, _TKN)

    def halve(length, t):
        return t // 2 if t % 256 == 0 and length % (t // 2) == 0 else t

    while max(_vmem_bytes(kern, Tiles(tm, tk, tn), itemsize)
              for kern in KERNEL_NAMES) > _VMEM_FREE:
        if tk >= tn and halve(k, tk) != tk:
            tk = halve(k, tk)
        elif halve(n, tn) != tn:
            tn = halve(n, tn)
        elif halve(k, tk) != tk:
            tk = halve(k, tk)
        elif tm > _TM[-1]:
            tm //= 2
        else:
            break  # an odd width kept whole: let Mosaic say if it fits
    return Tiles(tm, tk, tn)


def _kernel_tiles(kernel: str, m: int, k: int, n: int, dtype) -> Tiles:
    """``kernel``'s tiles for ``x [m, k]``, ``w [G, k, n]``: one plan per
    weight shape; dx (``dy [m, n] @ w^T``) runs it with the roles of k and n
    swapped, so ``tk`` there tiles n and ``tn`` tiles k."""
    t = tile_plan(m, k, n, dtype)
    return Tiles(t.tm, t.tn, t.tk) if kernel == "dx" else t


def _note_plans(kernels, m: int, k: int, n: int, groups: int, dtype) -> None:
    """Trace-time record of the tiling a compiled program runs (STATUS
    ``kernel_plans``), in the columns the flash kernels use: block_q = tm,
    block_k = tk, sub = tn, and the WORST-CASE grid steps a call — every
    group boundary crossing a tile; the grid bound at run time is the
    number of (tile, group) visits the sizes need. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        plan = tile_plan(m, k, n, dtype)
        steps = ((-(-m // plan.tm) + groups - 1)
                 * (k // plan.tk) * (n // plan.tn))
        for kern in kernels:
            t = _kernel_tiles(kern, m, k, n, dtype)
            note_kernel_plan(KERNEL_NAMES[kern], t.tm, t.tk, t.tn, steps, True,
                             d=k, dv=n)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# visits: which (group, m tile) pair each grid step along m works on
# ---------------------------------------------------------------------------

def _visits(group_sizes: jnp.ndarray, m: int, tm: int, visit_empty: bool):
    """``(group_offsets [G+1], group_ids [V], tile_ids [V], num_visits)``
    with ``V = m // tm + G - 1``: visit ``i < num_visits`` works on the rows
    of group ``group_ids[i]`` inside m tile ``tile_ids[i]``; visits of one
    tile are consecutive (an output tile is revisited only back to back).
    ``visit_empty``: an empty group still gets one visit (dw must zero its
    output). Entries at and past ``num_visits`` repeat valid indices."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    V = tiles_m + G - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    empty = group_sizes == 0
    # tiles a group touches: from the tile of its first row to that of its last
    first_tile = starts // tm
    group_tiles = jnp.where(empty, 0, (ends + tm - 1) // tm - first_tile)
    if visit_empty:
        group_tiles = jnp.where(empty, 1, group_tiles)
        first_tile = jnp.minimum(first_tile, tiles_m - 1)
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), group_tiles,
                           total_repeat_length=V)
    # the j-th visit of group g is tile first_tile[g] + j
    visit_start = jnp.cumsum(group_tiles) - group_tiles
    tile_ids = (first_tile[group_ids]
                + jnp.arange(V, dtype=jnp.int32) - visit_start[group_ids])
    num_visits = group_tiles.sum().astype(jnp.int32)
    tile_ids = jnp.where(jnp.arange(V) < num_visits, tile_ids,
                         tile_ids[jnp.maximum(num_visits - 1, 0)])
    return (offsets.astype(jnp.int32), group_ids,
            jnp.clip(tile_ids, 0, tiles_m - 1).astype(jnp.int32), num_visits)


def _row_mask(offsets, group, tile, tm, cols):
    """[tm, cols] bool: the rows of m tile ``tile`` that belong to
    ``group``."""
    rows = lax.broadcasted_iota(jnp.int32, (tm, cols), 0) + tile * tm
    return jnp.logical_and(rows >= offsets[group], rows < offsets[group + 1])


def _covers(offsets, group, tile, tm):
    """The whole m tile lies inside the group: no mask needed."""
    return jnp.logical_and(offsets[group] <= tile * tm,
                           (tile + 1) * tm <= offsets[group + 1])


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _gmm_kernel(offsets, group_ids, tile_ids, x_ref, w_ref, o_ref, acc_ref, *,
                tm, tn, transpose_rhs):
    visit, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    acc_ref[...] += lax.dot_general(x_ref[...], w_ref[...], dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _store():
        group, tile = group_ids[visit], tile_ids[visit]
        whole = _covers(offsets, group, tile, tm)

        @pl.when(whole)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():  # a boundary tile keeps the rows another visit wrote
            mask = _row_mask(offsets, group, tile, tm, tn)
            o_ref[...] = jnp.where(mask, acc_ref[...],
                                   o_ref[...].astype(jnp.float32)
                                   ).astype(o_ref.dtype)


def _tgmm_kernel(offsets, group_ids, tile_ids, x_ref, dy_ref, o_ref, acc_ref,
                 *, tm, tk):
    visit = pl.program_id(2)
    group, tile = group_ids[visit], tile_ids[visit]
    last = pl.num_programs(2) - 1
    prev_group = group_ids[jnp.maximum(visit - 1, 0)]
    next_group = group_ids[jnp.minimum(visit + 1, last)]

    @pl.when(jnp.logical_or(visit == 0, prev_group != group))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((0,), (0,)), ((), ()))  # contract the rows of both
    whole = _covers(offsets, group, tile, tm)

    @pl.when(whole)
    def _():
        acc_ref[...] += lax.dot_general(x_ref[...], dy_ref[...], dims,
                                        preferred_element_type=jnp.float32)

    nonempty = offsets[group + 1] > offsets[group]

    @pl.when(jnp.logical_and(jnp.logical_not(whole), nonempty))
    def _():  # zeroed rows of ONE operand contribute nothing
        mask = _row_mask(offsets, group, tile, tm, tk)
        x = jnp.where(mask, x_ref[...].astype(jnp.float32), 0.0
                      ).astype(x_ref.dtype)  # f32 select: no bf16 VPU on a v5e
        acc_ref[...] += lax.dot_general(x, dy_ref[...], dims,
                                        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(visit == last, next_group != group))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_rows(a, m_pad):
    return a if a.shape[0] == m_pad else jnp.pad(
        a, ((0, m_pad - a.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmm(x, w, group_sizes, transpose_rhs, interpret):
    """``x [M, K] @ w[g] -> [M, N]`` (``transpose_rhs``: ``w`` is
    ``[G, N, K]``). Rows past ``sum(group_sizes)`` come back 0."""
    M, K = x.shape
    G = w.shape[0]
    N = w.shape[1] if transpose_rhs else w.shape[2]
    kernel = "dx" if transpose_rhs else "fwd"
    tm, tk, tn = _kernel_tiles(kernel, M, *((N, K) if transpose_rhs
                                            else (K, N)), x.dtype)
    m_pad = -(-M // tm) * tm
    xp = _pad_rows(x, m_pad)
    offsets, group_ids, tile_ids, num_visits = _visits(
        group_sizes, m_pad, tm, visit_empty=False)
    w_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)

    def w_index(ni, v, ki, offsets, group_ids, tile_ids):
        return ((group_ids[v], ni, ki) if transpose_rhs
                else (group_ids[v], ki, ni))

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn,
                          transpose_rhs=transpose_rhs),
        name=KERNEL_NAMES[kernel],
        out_shape=jax.ShapeDtypeStruct((m_pad, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, o, g, t: (t[v], ki)),
                pl.BlockSpec(w_block, w_index),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, v, ki, o, g, t: (t[v], ni)),
            grid=(N // tn, num_visits, K // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * (M * K * (N // tn) + M * N
                                               + K * N * group_ids.shape[0])),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, xp, w)
    # tiles past the last group were never visited: their rows are whatever
    # the buffer held
    rows = lax.broadcasted_iota(jnp.int32, (m_pad, 1), 0)
    return jnp.where(rows < offsets[G], out, 0)[:M]


@functools.partial(jax.jit, static_argnums=(3,))
def _tgmm(x, dy, group_sizes, interpret):
    """``dw[g] [K, N] = x[rows of g]^T @ dy[rows of g]``; an empty group's
    is 0."""
    M, K = x.shape
    N = dy.shape[1]
    G = group_sizes.shape[0]
    tm, tk, tn = tile_plan(M, K, N, x.dtype)
    m_pad = -(-M // tm) * tm
    offsets, group_ids, tile_ids, num_visits = _visits(
        group_sizes, m_pad, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk),
        name=KERNEL_NAMES["dw"],
        out_shape=jax.ShapeDtypeStruct((G, K, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, ki, v, o, g, t: (t[v], ki)),
                pl.BlockSpec((tm, tn), lambda ni, ki, v, o, g, t: (t[v], ni)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda ni, ki, v, o, g, t: (g[v], ki, ni)),
            grid=(N // tn, K // tk, num_visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * (M * K * (N // tn)
                                               + M * N * (K // tk)
                                               + G * K * N)),
        interpret=interpret,
    )(offsets, group_ids, tile_ids, _pad_rows(x, m_pad), _pad_rows(dy, m_pad))


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(x, w, group_sizes, interpret):
    return _fwd(x, w, group_sizes, interpret)[0]


def _fwd(x, w, group_sizes, interpret):
    _note_plans(("fwd",), x.shape[0], *w.shape[1:], w.shape[0], x.dtype)
    return _gmm(x, w, group_sizes, False, interpret), (x, w, group_sizes)


def _bwd(interpret, res, dy):
    x, w, group_sizes = res
    _note_plans(("dx", "dw"), x.shape[0], *w.shape[1:], w.shape[0], x.dtype)
    dx = _gmm(dy, w, group_sizes, True, interpret)
    dw = _tgmm(x, dy, group_sizes, interpret)
    return dx, dw, None


_grouped_matmul.defvjp(_fwd, _bwd)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """``out[r] = x[r] @ w[g(r)]`` for ``x [M, K]`` sorted by group,
    ``w [G, K, N]`` and ``group_sizes [G]`` int32 with ``sum <= M``; rows past
    the last group come back 0. Differentiable in ``x`` and ``w``.
    ``interpret`` defaults to "the traced program does not run on TPUs"
    (the Pallas interpreter: CPU tests and rehearsals)."""
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1]:
        raise ValueError(f"grouped_matmul: x {x.shape} against w {w.shape}")
    if group_sizes.shape != (w.shape[0],):
        raise ValueError(f"grouped_matmul: {w.shape[0]} groups, group_sizes "
                         f"{group_sizes.shape}")
    if interpret is None:
        from harmony_tpu.utils.platform import trace_is_tpu

        interpret = not trace_is_tpu()
    return _grouped_matmul(x, w.astype(x.dtype),
                           group_sizes.astype(jnp.int32), bool(interpret))
