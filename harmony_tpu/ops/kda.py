"""Gated delta-rule linear attention with a per-channel decay (KDA, the token
mixer of Kimi Linear, arXiv:2510.26692) as a chunked scan: two Pallas kernels
under one ``custom_vjp`` on a one-chip TPU mesh, the same chunked mathematics
in XLA everywhere else.

A head carries a state ``S [dk, dv]`` (``S_0 = 0``) over the positions:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                       a_t = exp(g_t) in (0, 1]^dk

**The chunked form** (``_chunk``; ``C`` positions a chunk, ``G_r`` the
chunk's running sum of ``g`` up to and with row ``r``, ``S`` the state the
chunk starts from). Writing ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T`` with
``u_t = b_t (v_t - S_{t-1}^T (a_t k_t))`` and unrolling inside the chunk:

    A[r, i]   = b_r sum_c k_rc k_ic exp(G_rc - G_ic)        i <  r
    Aqk[r, i] =     sum_c q_rc k_ic exp(G_rc - G_ic)        i <= r
    (I + A) U = b v - (b k exp(G)) S             the triangular system
    O         = (q exp(G)) S + Aqk U
    S'        = Diag(exp(G_C)) S + (k exp(G_C - G))^T U

A per-channel decay does not factor as ``(k e^G)(k e^-G)^T``: ``e^-G`` alone
overflows where a channel forgets fast. Every exponent here is a difference
``G_r - G_s`` with ``r >= s``, so at most 0. Inside a sub-block of ``SUB`` rows
the pairs come from ``SUB`` row shifts (``k`` and ``G`` rolled by ``s`` rows
give every pair ``(r, r - s)`` at once, on the VPU); between sub-blocks the
difference is split at the later sub-block's first row ``n`` —
``exp(G_r - G_n) exp(G_n - G_i)``, both at most 1 — which is a matmul. The
triangular system is solved in float32 by inverting ``I + A`` block by block
(1, 2, 4 ... rows: ``X <- X - X (A on the lower-left sub-blocks) X``, the
exact block recursion, no power series); the other products take the
operands' dtype into the MXU (bfloat16 on hardware) and accumulate in float32.

**The kernels** (``harmony_kda_fwd``, ``harmony_kda_bwd``): the grid walks
(heads, chunks), one head's chunk a step, the chunks in order with the
transposed state ``S^T [dv, dk]`` in float32 in VMEM scratch (the decay then
multiplies along lanes).
The forward also writes the state each chunk STARTS from (``[N, dv, dk]`` a
head: 1/C of what keeping every position's state would take); the backward
walks the chunks in reverse, recomputes a chunk from that boundary state and
takes the chunk's vector-Jacobian product (``jax.vjp`` of ``_chunk`` traced
into the kernel body), carrying the state's cotangent in scratch. The chunk's
running sum of ``g`` and the products with ``b`` are XLA's, outside: their
derivatives are autodiff's.

One predicate chooses (``_kernel_route``: the traced program runs on a
one-chip TPU mesh), as for the flash kernels; no option and no environment
variable.
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
KERNEL_NAMES = {"fwd": "harmony_kda_fwd", "bwd": "harmony_kda_bwd"}
CHUNK = 64     # positions a chunk (the published choice)
SUB = 16       # rows a sub-block: pairs inside it come from row shifts

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


class Plan(NamedTuple):
    """``chunk`` positions of one head a grid step; ``grid_steps`` a call."""
    chunk: int
    grid_steps: int


def tile_plan(bh: int, seq: int, chunk: int = CHUNK) -> Plan:
    """The kernels' grid for ``bh`` heads (x sequences) of ``seq``
    positions: one head's chunk of ``chunk`` a step (padded to whole chunks
    with positions that change nothing; ops/ssd.py walks the same grid at
    its own chunk). Several heads a
    step buy nothing: 8 heads x 8,192 positions read 3.10 / 2.95 / 2.84 /
    2.80 ms forward at 1 / 2 / 4 / 8 heads a step (my chip run, PR 31) for
    8 times the code."""
    return Plan(chunk, bh * -(-seq // chunk))


def _note_plans(kernels, bh: int, seq: int, dk: int, dv: int) -> None:
    """Trace-time record (STATUS ``kernel_plans``): block_q = the chunk,
    block_k = heads x sequences, sub = the sub-block. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        plan = tile_plan(bh, seq)
        for kern in kernels:
            note_kernel_plan(KERNEL_NAMES[kern], plan.chunk, bh, SUB,
                             plan.grid_steps, True, d=dk, dv=dv)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# one chunk of one head: the mathematics all three forms share
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _shift_rows(x, s, on_tpu):
    """``x [C, d]`` with row ``r`` holding ``x[r - s]`` (rows wrap; callers
    mask them). In a compiled kernel the XLU's rotate, else ``jnp.roll``."""
    if s == 0:
        return x
    return pltpu.roll(x, s, 0) if on_tpu else jnp.roll(x, s, axis=0)


def _shift_rows_fwd(x, s, on_tpu):
    return _shift_rows(x, s, on_tpu), None


def _shift_rows_bwd(s, on_tpu, _, g):
    return (_shift_rows(g, (g.shape[0] - s) % g.shape[0], on_tpu),)


_shift_rows.defvjp(_shift_rows_fwd, _shift_rows_bwd)


def _chunk(q, k, kb, vb, G, St, on_tpu=False):
    """One chunk of one head: ``(o [C, dv], S'^T [dv, dk])`` from ``q, k
    [C, dk]``, ``kb = b k``, ``vb = b v [C, dv]``, the chunk's running
    log-decay ``G [C, dk]`` (float32) and the transposed state ``St
    [dv, dk]`` (float32) the chunk starts from. Module docstring for the
    equations; products take ``q``'s dtype into the MXU."""
    C = q.shape[0]
    f32 = jnp.float32
    mxu = q.dtype
    exact = lax.Precision.HIGHEST

    def mm(a, b, dims):
        return lax.dot_general(
            a.astype(mxu), b.astype(mxu), dims, preferred_element_type=f32,
            precision=exact if mxu == f32 else None)

    def mm32(a, b):
        return lax.dot_general(a, b, _NN, preferred_element_type=f32,
                               precision=exact)

    qf, kf, kbf, vbf = (t.astype(f32) for t in (q, k, kb, vb))
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.zeros((C, C), f32)
    Aqk = jnp.zeros((C, C), f32)
    # between sub-blocks: the difference split at the later one's first row
    for b in range(1, C // SUB):
        n = b * SUB
        gn = jnp.sum(jnp.where(row == n, G, 0.0), axis=0, keepdims=True)
        rowfac = jnp.exp(jnp.minimum(G - gn, 0.0))      # true for rows >= n
        kc = kf * jnp.where(row < n, jnp.exp(jnp.minimum(gn - G, 0.0)), 0.0)
        mine = jnp.logical_and(ri >= n, ri < n + SUB)
        A = A + jnp.where(mine, mm(kbf * rowfac, kc, _NT), 0.0)
        Aqk = Aqk + jnp.where(mine, mm(qf * rowfac, kc, _NT), 0.0)
    # inside a sub-block: the pairs (r, r - s), a shift of the rows each
    for s in range(min(SUB, C)):
        ks, Gs = _shift_rows(kf, s, on_tpu), _shift_rows(G, s, on_tpu)
        e = ks * jnp.exp(jnp.minimum(G - Gs, 0.0))
        here = jnp.logical_and(ci == ri - s, jnp.bitwise_and(ri, SUB - 1) >= s)
        Aqk = Aqk + jnp.where(
            here, jnp.sum(qf * e, axis=1, keepdims=True), 0.0)
        if s:
            A = A + jnp.where(
                here, jnp.sum(kbf * e, axis=1, keepdims=True), 0.0)
    # (I + A)^-1, block by block: X holds the inverses of the diagonal
    # blocks of ``size`` rows; the lower-left quarter of each block twice
    # that size is -X22 A21 X11
    X = (ri == ci).astype(f32)
    size = 1
    while size < C:
        # size is a power of two: blocks by shifts, halves by one bit
        bit = size.bit_length() - 1
        low_left = jnp.logical_and(
            jnp.right_shift(ri, bit + 1) == jnp.right_shift(ci, bit + 1),
            jnp.logical_and(jnp.bitwise_and(ri, size) != 0,
                            jnp.bitwise_and(ci, size) == 0))
        X = X - mm32(mm32(X, jnp.where(low_left, A, 0.0)), X)
        size *= 2
    U = mm(X, vbf, _NN) - mm(mm(X, kbf * jnp.exp(G), _NN), St, _NT)
    o = mm(qf * jnp.exp(G), St, _NT) + mm(Aqk, U, _NN)
    g_end = jnp.sum(jnp.where(row == C - 1, G, 0.0), axis=0, keepdims=True)
    St = St * jnp.exp(g_end) + mm(U, kf * jnp.exp(g_end - G), _TN)
    return o, St


# ---------------------------------------------------------------------------
# the same mathematics in XLA: a scan over the chunks, heads vmapped
# ---------------------------------------------------------------------------

def _scan_chunks(q, k, kb, vb, G):
    """``o [BH, N, C, dv]`` from operands ``[BH, N, C, d]``: plain JAX, so
    autodiff gives its backward."""
    BH, N, C, dk = q.shape
    dv = vb.shape[-1]
    chunk = jax.vmap(_chunk)

    def body(St, xs):
        o, St = chunk(*xs, St)
        return St, o

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, kb, vb, G))
    _, o = lax.scan(body, jnp.zeros((BH, dv, dk), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, h_ref, st_ref, *,
                on_tpu):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[...] = jnp.zeros_like(st_ref)

    h_ref[...] = st_ref[...]
    o, st = _chunk(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
                   g_ref[...], st_ref[...], on_tpu)
    o_ref[...] = o.astype(o_ref.dtype)
    st_ref[...] = st


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, h_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dst_ref, *, on_tpu):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    _, pull = jax.vjp(
        functools.partial(_chunk, on_tpu=on_tpu), q_ref[...], k_ref[...],
        kb_ref[...], vb_ref[...], g_ref[...], h_ref[...])
    dq, dk, dkb, dvb, dg, dst = pull(
        (do_ref[...].astype(jnp.float32), dst_ref[...]))
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dkb_ref[...] = dkb.astype(dkb_ref.dtype)
    dvb_ref[...] = dvb.astype(dvb_ref.dtype)
    dg_ref[...] = dg
    dst_ref[...] = dst


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnums=(5,))
def _kda_fwd_call(q, k, kb, vb, G, interpret):
    """``(o [BH, N, C, dv], h [BH, N, dv, dk])``: the outputs and the
    transposed state each chunk starts from."""
    BH, N, C, dk = q.shape
    dv = vb.shape[-1]
    at = lambda h, n: (h, n, 0, 0)
    qk = pl.BlockSpec((None, None, C, dk), at)
    vo = pl.BlockSpec((None, None, C, dv), at)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, on_tpu=not interpret),
        name=KERNEL_NAMES["fwd"],
        out_shape=(jax.ShapeDtypeStruct((BH, N, C, dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, N, dv, dk), jnp.float32)),
        grid=(BH, N),
        in_specs=[qk, qk, qk, vo, qk],
        out_specs=(vo, pl.BlockSpec((None, None, dv, dk), at)),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, kb, vb, G)


@functools.partial(jax.jit, static_argnums=(7,))
def _kda_bwd_call(q, k, kb, vb, G, h, do, interpret):
    """The cotangents of ``q, k, kb, vb, G`` under ``do``, the chunks walked
    last to first."""
    BH, N, C, dk = q.shape
    dv = vb.shape[-1]
    back = lambda h, n: (h, N - 1 - n, 0, 0)
    qk = pl.BlockSpec((None, None, C, dk), back)
    vo = pl.BlockSpec((None, None, C, dv), back)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, on_tpu=not interpret),
        name=KERNEL_NAMES["bwd"],
        out_shape=(like(q), like(k), like(kb), like(vb), like(G)),
        grid=(BH, N),
        in_specs=[qk, qk, qk, vo, qk,
                  pl.BlockSpec((None, None, dv, dk), back), vo],
        out_specs=(qk, qk, qk, vo, qk),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, kb, vb, G, h, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_kernels(q, k, kb, vb, G, interpret):
    return _kda_fwd_call(q, k, kb, vb, G, interpret)[0]


def _kda_kernels_fwd(q, k, kb, vb, G, interpret):
    BH, N, C, dk = q.shape
    _note_plans(("fwd",), BH, N * C, dk, vb.shape[-1])
    o, h = _kda_fwd_call(q, k, kb, vb, G, interpret)
    return o, (q, k, kb, vb, G, h)


def _kda_kernels_bwd(interpret, res, do):
    q, vb = res[0], res[3]
    BH, N, C, dk = q.shape
    _note_plans(("bwd",), BH, N * C, dk, vb.shape[-1])
    return _kda_bwd_call(*res, do, interpret)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

def _kernel_route() -> bool:
    """The traced program runs on a one-chip TPU mesh (a ``pallas_call`` is
    opaque to the partitioner: over more chips the XLA form is what the
    partitioner can split)."""
    from harmony_tpu.utils.platform import trace_is_tpu, trace_mesh

    mesh = trace_mesh()
    return trace_is_tpu() and (mesh is None or mesh.devices.size == 1)


def kda_attention(q, k, v, g, beta, interpret: Optional[bool] = None):
    """``o [B, H, S, dv]`` of the gated delta rule (module docstring) for
    ``q, k [B, H, S, dk]``, ``v [B, H, S, dv]``, the per-channel log-decay
    ``g [B, H, S, dk]`` (at most 0) and ``beta [B, H, S]``; ``q`` and ``k``
    as the model gives them (the caller normalises and scales). Causal by
    construction; any length (padded to whole chunks with positions that
    leave the state alone). Differentiable in all five. ``interpret``: None
    takes the kernels on a one-chip TPU mesh and the XLA form elsewhere;
    for tests and microbenchmarks True / False force the kernels,
    interpreted or compiled, and ``"xla"`` the XLA form on any backend."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or g.shape != q.shape or beta.shape != (B, H, S) \
            or v.shape[:3] != (B, H, S):
        raise ValueError(f"kda_attention: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, g {g.shape}, beta {beta.shape}")
    C = CHUNK
    N = -(-S // C)
    b = beta[..., None].astype(jnp.float32)
    kb = (b * k.astype(jnp.float32)).astype(k.dtype)
    vb = (b * v.astype(jnp.float32)).astype(v.dtype)

    def chunks(t):  # [B, H, S, d] -> [BH, N, C, d]; padding is zeros
        t = t.reshape(B * H, S, t.shape[-1])
        if N * C != S:
            t = jnp.pad(t, ((0, 0), (0, N * C - S), (0, 0)))
        return t.reshape(B * H, N, C, t.shape[-1])

    G = jnp.cumsum(chunks(g.astype(jnp.float32)), axis=2)
    args = (chunks(q), chunks(k), chunks(kb), chunks(vb), G)
    if interpret == "xla" or (interpret is None and not _kernel_route()):
        o = _scan_chunks(*args)
    else:
        o = _kda_kernels(*args, bool(interpret))
    return o.reshape(B * H, N * C, dv)[:, :S].reshape(B, H, S, dv)
