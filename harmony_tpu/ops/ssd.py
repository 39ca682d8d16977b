"""The Mamba-2 state-space recurrence (SSD, arXiv:2405.21060) as a chunked
scan: two Pallas kernels under one ``custom_vjp`` on a one-chip TPU mesh, the
same chunked mathematics in XLA everywhere else — ``ops/kda.py``'s grid and
state carry with a simpler body: the decay is ONE scalar a head and position,
so nothing is solved.

A head carries a state ``S [P, N]`` (``S_0 = 0``) over the positions:

    S_t = a_t S_{t-1} + x_t b_t^T            a_t = exp(g_t) in (0, 1]
    y_t = S_t c_t

with ``x_t [P]`` the head's input (the caller has multiplied the step ``dt``
in), ``b_t, c_t [N]`` shared by the heads of a group, and ``g_t <= 0`` the
head's log-decay.

**The chunked form** (``_chunk``; ``C`` positions a chunk, ``G_r`` the
chunk's running sum of ``g`` up to and with row ``r``, ``S`` the state the
chunk starts from):

    Y  = ((c b^T) * L) x + exp(G) (c S^T)      L[r, i] = exp(G_r - G_i), i <= r
    S' = exp(G_C) S + (x exp(G_C - G))^T b

Every exponent is a difference ``G_r - G_i`` with ``r >= i``, so at most 0.
The products take the operands' dtype into the MXU (bfloat16 on hardware)
and accumulate in float32; the decays are float32.

**The kernels** (``harmony_ssd_fwd``, ``harmony_ssd_bwd``): the grid walks
(heads, chunks), one head's chunk a step (``ops.kda.tile_plan``), the chunks
in order with the state in float32 in VMEM scratch; ``b`` and ``c`` are read
through the head's group. The forward also writes the state each chunk STARTS
from; the backward walks the chunks in reverse, recomputes a chunk from that
boundary state and takes its vector-Jacobian product (``jax.vjp`` of
``_chunk`` traced into the kernel body), carrying the state's cotangent in
scratch. ``b``'s and ``c``'s cotangents leave the kernel a head each and are
summed over a group's heads by XLA, outside, as the running sum of ``g`` is.

One predicate chooses (``ops.kda._kernel_route``), as for the KDA and flash
kernels; no option and no environment variable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harmony_tpu.ops.kda import _NN, _NT, _TN, _kernel_route, tile_plan
from harmony_tpu.ops.residuals import SSD_OUT, SSD_STATE, keep

#: the kernels' names in a device trace (perf/layer_metrics read them) and in
#: STATUS ``kernel_plans``
KERNEL_NAMES = {"fwd": "harmony_ssd_fwd", "bwd": "harmony_ssd_bwd"}
CHUNK = 128    # positions a chunk (the published ``chunk_size``)


def _note_plans(kernels, bh: int, seq: int, chunk: int, p: int, n: int
                ) -> None:
    """Trace-time record (STATUS ``kernel_plans``): block_q = the chunk,
    block_k = heads x sequences. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        plan = tile_plan(bh, seq, chunk)
        for kern in kernels:
            note_kernel_plan(KERNEL_NAMES[kern], plan.chunk, bh, 0,
                             plan.grid_steps, True, d=n, dv=p)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# one chunk of one head: the mathematics all three forms share
# ---------------------------------------------------------------------------

def _chunk(x, b, c, G, S):
    """One chunk of one head: ``(y [C, P], S' [P, N])`` (float32) from ``x
    [C, P]``, ``b, c [C, N]``, the chunk's running log-decay ``G [1, C]``
    (float32, along the lanes) and the state ``S [P, N]`` (float32) the
    chunk starts from. Module docstring for the equations; products take
    ``x``'s dtype into the MXU."""
    C = x.shape[0]
    f32 = jnp.float32
    mxu = x.dtype

    def mm(a, b, dims):
        return lax.dot_general(
            a.astype(mxu), b.astype(mxu), dims, preferred_element_type=f32,
            precision=lax.Precision.HIGHEST if mxu == f32 else None)

    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    g_i = jnp.broadcast_to(G, (C, C))                   # [r, i] = G_i
    # G down the rows: the diagonal of its broadcast (no transpose)
    g_r = jnp.sum(jnp.where(ri == ci, g_i, 0.0), axis=1, keepdims=True)
    lane = lax.broadcasted_iota(jnp.int32, (1, C), 1)
    g_end = jnp.sum(jnp.where(lane == C - 1, G, 0.0), axis=1, keepdims=True)
    L = jnp.where(ci <= ri, jnp.exp(jnp.minimum(g_r - g_i, 0.0)), 0.0)
    y = mm(mm(c, b, _NT) * L, x, _NN) + jnp.exp(g_r) * mm(c, S, _NT)
    S = S * jnp.exp(g_end) + mm(x.astype(f32) * jnp.exp(g_end - g_r), b, _TN)
    return y, S


# ---------------------------------------------------------------------------
# the same mathematics in XLA: a scan over the chunks, heads vmapped
# ---------------------------------------------------------------------------

def _scan_chunks(x, b, c, G):
    """``y [BH, N, C, P]`` from ``x [BH, N, C, P]``, ``b, c [BG, N, C, n]``
    and ``G [BH, N, 1, C]``: plain JAX, so autodiff gives its backward."""
    BH, N, C, P = x.shape
    rep = BH // b.shape[0]
    chunk = jax.vmap(_chunk)

    def body(S, xs):
        xc, bc, cc, Gc = xs
        y, S = chunk(xc, jnp.repeat(bc, rep, axis=0),
                     jnp.repeat(cc, rep, axis=0), Gc, S)
        return S, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, G))
    _, y = lax.scan(body, jnp.zeros((BH, P, b.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, g_ref, y_ref, h_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    h_ref[...] = s_ref[...]
    y, s = _chunk(x_ref[...], b_ref[...], c_ref[...], g_ref[...], s_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)
    s_ref[...] = s


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, h_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dg_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    _, pull = jax.vjp(_chunk, x_ref[...], b_ref[...], c_ref[...], g_ref[...],
                      h_ref[...])
    dx, db, dc, dg, ds = pull((dy_ref[...].astype(jnp.float32), ds_ref[...]))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    dg_ref[...] = dg
    ds_ref[...] = ds


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _specs(x, b, order):
    """The BlockSpecs of ``x``-, ``b``- and ``G``-shaped operands and of the
    boundary states, the chunk axis walked by ``order(n)``."""
    BH, _, C, P = x.shape
    rep, n = BH // b.shape[0], b.shape[-1]
    return (pl.BlockSpec((None, None, C, P),
                         lambda h, i: (h, order(i), 0, 0)),
            pl.BlockSpec((None, None, C, n),
                         lambda h, i: (h // rep, order(i), 0, 0)),
            pl.BlockSpec((None, None, 1, C),
                         lambda h, i: (h, order(i), 0, 0)),
            pl.BlockSpec((None, None, P, n),
                         lambda h, i: (h, order(i), 0, 0)))


@functools.partial(jax.jit, static_argnums=(4,))
def _ssd_fwd_call(x, b, c, G, interpret):
    """``(y [BH, N, C, P], h [BH, N, P, n])``: the outputs and the state
    each chunk starts from."""
    BH, N, C, P = x.shape
    n = b.shape[-1]
    xs, bs, gs, hs = _specs(x, b, lambda i: i)
    return pl.pallas_call(
        _fwd_kernel, name=KERNEL_NAMES["fwd"],
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((BH, N, P, n), jnp.float32)),
        grid=(BH, N),
        in_specs=[xs, bs, bs, gs],
        out_specs=(xs, hs),
        scratch_shapes=[pltpu.VMEM((P, n), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, b, c, G)


@functools.partial(jax.jit, static_argnums=(6,))
def _ssd_bwd_call(x, b, c, G, h, dy, interpret):
    """The cotangents of ``x, b, c, G`` under ``dy``, the chunks walked last
    to first; ``b``'s and ``c``'s a HEAD each (``[BH, N, C, n]`` float32:
    the caller sums a group's)."""
    BH, N, C, P = x.shape
    n = b.shape[-1]
    xs, bs, gs, hs = _specs(x, b, lambda i: N - 1 - i)
    per_head = pl.BlockSpec((None, None, C, n),
                            lambda h, i: (h, N - 1 - i, 0, 0))
    return pl.pallas_call(
        _bwd_kernel, name=KERNEL_NAMES["bwd"],
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((BH, N, C, n), jnp.float32),
                   jax.ShapeDtypeStruct((BH, N, C, n), jnp.float32),
                   jax.ShapeDtypeStruct(G.shape, G.dtype)),
        grid=(BH, N),
        in_specs=[xs, bs, bs, gs, hs, xs],
        out_specs=(xs, per_head, per_head, gs),
        scratch_shapes=[pltpu.VMEM((P, n), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, b, c, G, h, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssd_kernels(x, b, c, G, interpret):
    return _ssd_fwd_call(x, b, c, G, interpret)[0]


def _ssd_kernels_fwd(x, b, c, G, interpret):
    BH, N, C, P = x.shape
    _note_plans(("fwd",), BH, N * C, C, P, b.shape[-1])
    y, h = _ssd_fwd_call(x, b, c, G, interpret)
    # what a rematerialised block keeps (ops/residuals.py)
    y, h = keep(y, SSD_OUT), keep(h, SSD_STATE)
    return y, (x, b, c, G, h)


def _ssd_kernels_bwd(interpret, res, dy):
    x, b = res[0], res[1]
    BH, N, C, P = x.shape
    _note_plans(("bwd",), BH, N * C, C, P, b.shape[-1])
    dx, db, dc, dg = _ssd_bwd_call(*res, dy, interpret)
    group = lambda t: t.reshape(b.shape[0], -1, *t.shape[1:]).sum(
        axis=1).astype(b.dtype)
    return dx, group(db), group(dc), dg


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

def ssd_scan(x, b, c, g, chunk: int = CHUNK, interpret: Optional[bool] = None):
    """``y [B, H, S, P]`` of the recurrence (module docstring) for ``x [B, H,
    S, P]``, ``b, c [B, G, S, N]`` (head ``h`` reads group ``h // (H / G)``)
    and the per-head log-decay ``g [B, H, S]`` (at most 0). Causal by
    construction; any length (padded to whole chunks with positions that
    leave the state alone). Differentiable in all four. ``interpret``: None
    takes the kernels on a one-chip TPU mesh and the XLA form elsewhere; for
    tests and microbenchmarks True / False force the kernels, interpreted or
    compiled, and ``"xla"`` the XLA form on any backend."""
    B, H, S, P = x.shape
    G, n = b.shape[1], b.shape[-1]
    if c.shape != b.shape or b.shape[::2] != (B, S) or H % G \
            or g.shape != (B, H, S):
        raise ValueError(f"ssd_scan: x {x.shape}, b {b.shape}, c {c.shape}, "
                         f"g {g.shape}")
    C = chunk
    N = -(-S // C)

    def chunks(t):  # [B, h, S, d] -> [B h, N, C, d]; padding is zeros
        t = t.reshape(-1, S, t.shape[-1])
        if N * C != S:
            t = jnp.pad(t, ((0, 0), (0, N * C - S), (0, 0)))
        return t.reshape(-1, N, C, t.shape[-1])

    run = jnp.cumsum(chunks(g.astype(jnp.float32)[..., None])[..., 0], axis=2)
    args = (chunks(x), chunks(b), chunks(c), run[:, :, None, :])
    if interpret == "xla" or (interpret is None and not _kernel_route()):
        y = _scan_chunks(*args)
    else:
        y = _ssd_kernels(*args, bool(interpret))
    return y.reshape(B * H, N * C, P)[:, :S].reshape(B, H, S, P)
