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
exact block recursion, no power series; blocks of one row are 1, so the
first level is a mask and five levels multiply); the other products take the
operands' dtype into the MXU (bfloat16 on hardware) and accumulate in float32.

**The kernels** (``harmony_kda_fwd``, ``harmony_kda_bwd``): the grid walks
(heads, chunks), one head's chunk a step (the scalar route below: several),
the chunks in order with the
transposed state ``S^T [dv, dk]`` in float32 in VMEM scratch (the decay then
multiplies along lanes).
The forward also writes the state each chunk STARTS from (``[N, dv, dk]`` a
head: 1/C of what keeping every position's state would take) and the chunk's
solve ``X = (I + A)^-1`` (``[N, C, C]`` float32, a quarter of that again).
The backward walks the chunks in reverse with the state's cotangent in
scratch, and takes each chunk's backward AROUND ``X``, never through the
recursion that made it (``_chunk_bwd``): ``U = X R`` is rebuilt from the
boundary state in three products, the application's cotangents are its own
transposes, the solve's are ``dR = X^T dU`` and ``dA = -dR U^T`` (two
products where differentiating six levels took 24 exact ones after
recomputing 12), and only the pair matrices — 16 row shifts, three split
products — go through ``jax.vjp`` (of ``_pair``, traced into the kernel
body). The chunk's running sum of ``g`` and the products with ``b`` are
XLA's, outside: their derivatives are autodiff's. The XLA form
(``_scan_chunks``) keeps autodiff through the whole of ``_chunk``: it is what
the kernels are tested against.

**One decay a head** (``gdn_attention``: Gated DeltaNet, arXiv:2412.06464,
Qwen3-Next's linear mixer). With ``G [C, 1]`` the decay leaves the sums over
channels: ``A = b (k k^T) * D`` and ``Aqk = (q k^T) * D`` with ``D[r, i] =
exp(G_r - G_i)`` — two MXU products under one mask (``_pair_scalar``) where
the channel form takes 16 row shifts and three split products; everything
after the pair matrices — the solve, the application, the backward around
``X``, the state's carry, the kept residuals — is the code above, reading a
``[C, 1]`` decay by broadcasting. Its kernels (``harmony_gdn_fwd``,
``harmony_gdn_bwd``) walk VALUE heads and read ``q, k`` at their key head
(value head ``j`` on key head ``j // (Hv / Hk)``, by the index map: no repeated
copy in HBM), take ``g`` and ``beta`` as ``[1, C]`` rows a chunk and form ``b
k``, ``b v`` inside. A grid step of theirs holds ``T`` CONSECUTIVE chunks of
one value head — ``T`` the largest of 8, 4, 2, 1 that divides the head's
chunks, from the shape alone (``gdn_plan``) — and does first what does not
read the state: ``A``, ``Aqk`` and ``X = (I + A)^-1`` depend on a chunk's
own ``q, k, beta, g``, so two chunks go through ``_pair_scalar`` and
``_solve`` on the diagonal of ONE ``[128, 128]`` tile (block-diagonal in,
block-diagonal out, exactly: half the float32 products, each at the MXU's
own size), and only ``_apply`` walks the state, chunk after chunk. The
backward runs ``_chunk_bwd`` for the step's chunks last to first in one
basic block: ``dU -> dR -> dS'`` alone waits for the chunk after. The
arrays in HBM keep their shapes, so what the forward keeps is unchanged.

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

from harmony_tpu.ops.residuals import KDA_OUT, KDA_SOLVE, KDA_STATE, keep

#: the kernels' names in a device trace (perf/layer_metrics read them) and in
#: STATUS ``kernel_plans``
KERNEL_NAMES = {"fwd": "harmony_kda_fwd", "bwd": "harmony_kda_bwd",
                "gdn_fwd": "harmony_gdn_fwd", "gdn_bwd": "harmony_gdn_bwd"}
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
    its own chunk). Several HEADS a
    step buy nothing: 8 heads x 8,192 positions read 3.10 / 2.95 / 2.84 /
    2.80 ms forward at 1 / 2 / 4 / 8 heads a step (my chip run, PR 31) for
    8 times the code. Several CHUNKS of one head a step do, where their
    solves share a tile: the scalar route's ``gdn_plan``."""
    return Plan(chunk, bh * -(-seq // chunk))


#: chunks of one value head a grid step of the scalar route, largest first:
#: ``harmony_gdn_fwd`` / ``_bwd`` alone at 32 value heads of 128 x 256 chunks
#: read 18.73 / 10.09 ms at 1, 13.84 / 7.60 at 2, 12.83 / 6.69 at 4, 12.49 /
#: 6.24 at 8; 16 read 12.36 forward for twice the code to trace and compile
#: (my chip runs, PR 63)
GDN_CHUNKS_A_STEP = (8, 4, 2, 1)


def gdn_plan(bh: int, n: int) -> Plan:
    """The scalar route's grid for ``bh`` value heads (x sequences) of ``n``
    chunks: ``T`` CONSECUTIVE chunks of one head a step, ``T`` the largest
    of ``GDN_CHUNKS_A_STEP`` that divides ``n`` (from the shape alone; 1 is
    ``tile_plan``'s walk). A step's pair matrices and solves do not read the
    state, so two chunks share one ``[2 C, 2 C]`` tile through ``_solve`` —
    the MXU's own 128 rows at ``C`` = 64, half the float32 products — and
    ``T`` chunks pay one grid step (``_gdn_fwd_kernel``)."""
    t = next(t for t in GDN_CHUNKS_A_STEP if n % t == 0)
    return Plan(t * CHUNK, bh * n // t)


def _note_plans(kernels, plan: Plan, bh: int, dk: int, dv: int) -> None:
    """Trace-time record (STATUS ``kernel_plans``): block_q = the positions
    a grid step holds, block_k = heads x sequences, sub = the sub-block.
    Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

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


def _mm(a, b, dims, mxu):
    """``a`` x ``b`` with ``mxu`` operands into the MXU, float32 out (exact
    where the operands are float32 themselves)."""
    return lax.dot_general(
        a.astype(mxu), b.astype(mxu), dims,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if mxu == jnp.float32 else None)


def _row(G, n):
    """``G``'s row ``n``, ``[1, dk]`` (a masked sum: no unaligned slice)."""
    row = lax.broadcasted_iota(jnp.int32, (G.shape[0], 1), 0)
    return jnp.sum(jnp.where(row == n, G, 0.0), axis=0, keepdims=True)


def _eye(C):
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0)
            == lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _col(x):
    """``x [1, C]`` as a column ``[C, 1]`` (a masked sum: no transpose)."""
    return jnp.sum(jnp.where(_eye(x.shape[1]), x, 0.0), axis=1, keepdims=True)


def _lane(x):
    """``x [C, 1]`` along the lanes, ``[1, C]``."""
    return jnp.sum(jnp.where(_eye(x.shape[0]), x, 0.0), axis=0, keepdims=True)


def _pair_scalar(qf, kf, kbf, G, mxu):
    """``_pair`` under ONE decay a head, ``G [C, 1]``: the decay leaves the
    sum over channels, ``A = b (k k^T) * D`` and ``Aqk = (q k^T) * D`` with
    ``D[r, i] = exp(G_r - G_i)`` — two MXU products under one ``[C, C]``
    mask of differences that are at most 0 where it is read."""
    C = qf.shape[0]
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    D = jnp.exp(jnp.minimum(G - _lane(G), 0.0))
    A = jnp.where(ci < ri, _mm(kbf, kf, _NT, mxu) * D, 0.0)
    Aqk = jnp.where(ci <= ri, _mm(qf, kf, _NT, mxu) * D, 0.0)
    return A, Aqk


def _like(x, G):
    """``x [.., dk]`` summed over the channels where the decay ``G`` is one
    scalar a head (its cotangent is then one a row)."""
    return x if G.shape[1] == x.shape[1] else jnp.sum(x, axis=1, keepdims=True)


def _pair(qf, kf, kbf, G, mxu, on_tpu=False):
    """The chunk's pair matrices ``(A, Aqk) [C, C]`` (module docstring) from
    float32 ``q, k, b k`` and ``G``; products take ``mxu`` operands. ``G
    [C, 1]`` — one decay a head — takes the scalar form."""
    if G.shape[1] == 1:
        return _pair_scalar(qf, kf, kbf, G, mxu)
    C = qf.shape[0]
    f32 = jnp.float32
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.zeros((C, C), f32)
    Aqk = jnp.zeros((C, C), f32)
    # between sub-blocks: the difference split at the later one's first row
    for b in range(1, C // SUB):
        n = b * SUB
        gn = _row(G, n)
        rowfac = jnp.exp(jnp.minimum(G - gn, 0.0))      # true for rows >= n
        kc = kf * jnp.where(row < n, jnp.exp(jnp.minimum(gn - G, 0.0)), 0.0)
        mine = jnp.logical_and(ri >= n, ri < n + SUB)
        A = A + jnp.where(mine, _mm(kbf * rowfac, kc, _NT, mxu), 0.0)
        Aqk = Aqk + jnp.where(mine, _mm(qf * rowfac, kc, _NT, mxu), 0.0)
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
    return A, Aqk


def _solve(A, rows=None):
    """``(I + A)^-1`` for a strictly lower ``A [C, C]``, in float32, block by
    block: ``X`` holds the inverses of the diagonal blocks of ``size`` rows;
    the lower-left quarter of each block twice that size is -X22 A21 X11.
    ``rows`` (static; ``C`` where None) stops the recursion at diagonal
    blocks of that many rows: an ``A`` that is zero outside them — several
    chunks' on one tile's diagonal — comes back as their inverses, each the
    value it has alone (a product of block-diagonal matrices adds exact
    zeros to each block's own sums)."""
    C = A.shape[0]
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)

    def low_left(size):
        # size is a power of two: blocks by shifts, halves by one bit
        bit = size.bit_length() - 1
        return jnp.logical_and(
            jnp.right_shift(ri, bit + 1) == jnp.right_shift(ci, bit + 1),
            jnp.logical_and(jnp.bitwise_and(ri, size) != 0,
                            jnp.bitwise_and(ci, size) == 0))

    # blocks of one row are 1: X - X M X with X = I is the mask itself
    X = (ri == ci).astype(jnp.float32) - jnp.where(low_left(1), A, 0.0)
    size = 2
    while size < (rows or C):
        M = jnp.where(low_left(size), A, 0.0)
        X = X - _mm(_mm(X, M, _NN, jnp.float32), X, _NN, jnp.float32)
        size *= 2
    return X


def _apply(qf, kf, kbf, vbf, G, St, Aqk, X, mxu):
    """``(o, S'^T)`` of the chunk from its pair matrix ``Aqk`` and the solve
    ``X = (I + A)^-1``: the last three equations of the module docstring."""
    mm = functools.partial(_mm, mxu=mxu)
    eG = jnp.exp(G)
    U = mm(X, vbf, _NN) - mm(mm(X, kbf * eG, _NN), St, _NT)
    o = mm(qf * eG, St, _NT) + mm(Aqk, U, _NN)
    g_end = _row(G, G.shape[0] - 1)
    St = St * jnp.exp(g_end) + mm(U, kf * jnp.exp(g_end - G), _TN)
    return o, St


def _chunk_keeping_solve(q, k, kb, vb, G, St, on_tpu=False):
    """``_chunk`` with the solve ``X [C, C]`` (float32) it went through: what
    the forward kernel hands the backward."""
    f32 = jnp.float32
    qf, kf, kbf, vbf = (t.astype(f32) for t in (q, k, kb, vb))
    A, Aqk = _pair(qf, kf, kbf, G, q.dtype, on_tpu)
    X = _solve(A)
    return (*_apply(qf, kf, kbf, vbf, G, St, Aqk, X, q.dtype), X)


def _chunk(q, k, kb, vb, G, St, on_tpu=False):
    """One chunk of one head: ``(o [C, dv], S'^T [dv, dk])`` from ``q, k
    [C, dk]``, ``kb = b k``, ``vb = b v [C, dv]``, the chunk's running
    log-decay ``G [C, dk]`` (float32) and the transposed state ``St
    [dv, dk]`` (float32) the chunk starts from. Module docstring for the
    equations; products take ``q``'s dtype into the MXU."""
    return _chunk_keeping_solve(q, k, kb, vb, G, St, on_tpu)[:2]


def _chunk_bwd(q, k, kb, vb, G, St, X, do, dSt, on_tpu=False):
    """The cotangents ``(dq, dk, dkb, dvb, dG, dSt)`` of ``_chunk`` under
    ``(do [C, dv], dS'^T [dv, dk])``, around the forward's ``X = (I + A)^-1``
    and never through it: with ``U = X R`` (``R = b v - (b k e^G) S``) the
    solve's cotangents are ``dR = X^T dU`` and ``dA = -dR U^T`` (its strict
    lower part: the pair matrices' masks take it). The application's
    transposes by hand, the pair matrices' by ``jax.vjp`` of ``_pair``."""
    f32 = jnp.float32
    mxu = q.dtype
    mm = functools.partial(_mm, mxu=mxu)
    qf, kf, kbf, vbf = (t.astype(f32) for t in (q, k, kb, vb))
    C = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    eG = jnp.exp(G)
    g_end = _row(G, C - 1)
    e_end, dec = jnp.exp(g_end), jnp.exp(g_end - G)
    qe, KG, kdec = qf * eG, kbf * eG, kf * dec
    (_, Aqk), pull = jax.vjp(
        lambda *a: _pair(*a, mxu, on_tpu), qf, kf, kbf, G)
    U = mm(X, vbf, _NN) - mm(mm(X, KG, _NN), St, _NT)
    dU = mm(Aqk, do, _TN) + mm(kdec, dSt, _NT)
    dR = mm(X, dU, _TN)
    dqe = mm(do, St, _NN)
    dKG = -mm(dR, St, _NN)
    dkdec = mm(U, dSt, _NN)
    dq, dk, dkb, dG = pull((-mm(dR, U, _NT), mm(do, U, _NT)))
    gk = _like(dkdec * kdec, G)
    dg_end = jnp.sum(gk, axis=0, keepdims=True) + e_end * _like(jnp.sum(
        dSt * St, axis=0, keepdims=True), G)
    dG = dG + _like(dqe * qe, G) + _like(dKG * KG, G) - gk + jnp.where(
        row == C - 1, dg_end, 0.0)
    dSt = dSt * e_end + mm(do, qe, _TN) - mm(dR, KG, _TN)
    return dq + dqe * eG, dk + dkdec * dec, dkb + dKG * eG, dR, dG, dSt


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

def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, h_ref, x_ref,
                st_ref, *, on_tpu):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[...] = jnp.zeros_like(st_ref)

    h_ref[...] = st_ref[...]
    o, st, x = _chunk_keeping_solve(
        q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], g_ref[...],
        st_ref[...], on_tpu)
    o_ref[...] = o.astype(o_ref.dtype)
    x_ref[...] = x
    st_ref[...] = st


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, h_ref, x_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dst_ref, *, on_tpu):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    dq, dk, dkb, dvb, dg, dst = _chunk_bwd(
        q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], g_ref[...],
        h_ref[...], x_ref[...], do_ref[...].astype(jnp.float32),
        dst_ref[...], on_tpu)
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
    """``(o [BH, N, C, dv], h [BH, N, dv, dk], X [BH, N, C, C])``: the
    outputs, the transposed state each chunk starts from and each chunk's
    solve ``(I + A)^-1`` (float32 both)."""
    BH, N, C, dk = q.shape
    dv = vb.shape[-1]
    at = lambda h, n: (h, n, 0, 0)
    qk = pl.BlockSpec((None, None, C, dk), at)
    vo = pl.BlockSpec((None, None, C, dv), at)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, on_tpu=not interpret),
        name=KERNEL_NAMES["fwd"],
        out_shape=(jax.ShapeDtypeStruct((BH, N, C, dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, N, dv, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, N, C, C), jnp.float32)),
        grid=(BH, N),
        in_specs=[qk, qk, qk, vo, qk],
        out_specs=(vo, pl.BlockSpec((None, None, dv, dk), at),
                   pl.BlockSpec((None, None, C, C), at)),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, kb, vb, G)


@functools.partial(jax.jit, static_argnums=(8,))
def _kda_bwd_call(q, k, kb, vb, G, h, X, do, interpret):
    """The cotangents of ``q, k, kb, vb, G`` under ``do``, the chunks walked
    last to first, each around the solve ``X`` the forward kept."""
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
                  pl.BlockSpec((None, None, dv, dk), back),
                  pl.BlockSpec((None, None, C, C), back), vo],
        out_specs=(qk, qk, qk, vo, qk),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, kb, vb, G, h, X, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_kernels(q, k, kb, vb, G, interpret):
    return _kda_fwd_call(q, k, kb, vb, G, interpret)[0]


def _kda_kernels_fwd(q, k, kb, vb, G, interpret):
    BH, N, C, dk = q.shape
    _note_plans(("fwd",), tile_plan(BH, N * C), BH, dk, vb.shape[-1])
    o, h, X = _kda_fwd_call(q, k, kb, vb, G, interpret)
    # what a rematerialised block keeps (ops/residuals.py)
    o, h, X = keep(o, KDA_OUT), keep(h, KDA_STATE), keep(X, KDA_SOLVE)
    return o, (q, k, kb, vb, G, h, X)


def _kda_kernels_bwd(interpret, res, do):
    q, vb = res[0], res[3]
    BH, N, C, dk = q.shape
    _note_plans(("bwd",), tile_plan(BH, N * C), BH, dk, vb.shape[-1])
    return _kda_bwd_call(*res, do, interpret)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


# ---------------------------------------------------------------------------
# the scalar-decay route (Gated DeltaNet): the same chunk, other operands
# ---------------------------------------------------------------------------

def _gdn_operands(q_ref, k_ref, v_ref, b_ref, g_ref):
    """A chunk's operands as ``_chunk`` takes them, from what the scalar
    route keeps in HBM: ``q, k`` of the KEY head, ``v`` of the value head,
    ``beta`` and the running log-decay ``G`` as ``[1, C]`` rows (float32).
    ``b k`` and ``b v`` are formed here, rounded as the XLA form rounds
    them."""
    f32 = jnp.float32
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    b, G = _col(b_ref[...]), _col(g_ref[...])
    kb = (b * k.astype(f32)).astype(k.dtype)
    vb = (b * v.astype(f32)).astype(v.dtype)
    return q, k, v, b, kb, vb, G


def _gdn_solved(chunks, mxu):
    """``(Aqk, X)`` of each chunk of a grid step from its float32 ``(q, k,
    b k, G)``: what a chunk computes before it meets the state. Two chunks
    go through ``_pair_scalar`` and ``_solve`` as ONE ``[2 C, 2 C]`` tile
    — stacked rows, the other chunk's columns masked off, the recursion
    stopped at ``C`` — and come back as its diagonal blocks; one chunk
    alone as ``_chunk_keeping_solve`` has it."""
    if len(chunks) == 1:
        A, Aqk = _pair_scalar(*chunks[0], mxu)
        return [(Aqk, _solve(A))]
    C = chunks[0][0].shape[0]
    ri = lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 1)
    own = (ri < C) == (ci < C)
    out = []
    for two in zip(chunks[::2], chunks[1::2]):
        A, Aqk = _pair_scalar(
            *(jnp.concatenate(pair, axis=0) for pair in zip(*two)), mxu)
        X = _solve(jnp.where(own, A, 0.0), C)
        out += [(Aqk[:C, :C], X[:C, :C]), (Aqk[C:, C:], X[C:, C:])]
    return out


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, b_ref, g_ref, o_ref, h_ref, x_ref,
                    st_ref):
    """``T`` consecutive chunks of one value head (``gdn_plan``): first what
    no chunk needs the state for — the operands, the pair matrices, the
    solves (``_gdn_solved``) — then the walk, ``_apply`` alone, in order."""
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[...] = jnp.zeros_like(st_ref)

    f32 = jnp.float32
    mxu = q_ref.dtype
    chunks = []
    for t in range(q_ref.shape[0]):
        q, k, _, _, kb, vb, G = _gdn_operands(
            *(r.at[t] for r in (q_ref, k_ref, v_ref, b_ref, g_ref)))
        chunks.append((q.astype(f32), k.astype(f32), kb.astype(f32), G,
                       vb.astype(f32)))
    solved = _gdn_solved([c[:4] for c in chunks], mxu)
    st = st_ref[...]
    for t, ((qf, kf, kbf, G, vbf), (Aqk, X)) in enumerate(zip(chunks, solved)):
        h_ref[t] = st
        x_ref[t] = X
        o, st = _apply(qf, kf, kbf, vbf, G, st, Aqk, X, mxu)
        o_ref[t] = o.astype(o_ref.dtype)
    st_ref[...] = st


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, b_ref, g_ref, h_ref, x_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, db_ref, dg_ref, dst_ref):
    """The step's ``T`` chunks last to first, ``_chunk_bwd`` each, in ONE
    basic block: only ``dU -> dR -> dS'`` of a chunk waits for the chunk
    after it, the rest (``U`` from the kept ``h`` and ``X``, the pair
    matrices' ``jax.vjp``) is the scheduler's to place under that chain."""
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    f32 = jnp.float32
    dst = dst_ref[...]
    for t in reversed(range(q_ref.shape[0])):
        q, k, v, b, kb, vb, G = _gdn_operands(
            *(r.at[t] for r in (q_ref, k_ref, v_ref, b_ref, g_ref)))
        dq, dk, dkb, dvb, dg, dst = _chunk_bwd(
            q, k, kb, vb, G, h_ref[t], x_ref[t], do_ref[t].astype(f32), dst)
        dq_ref[t] = dq.astype(dq_ref.dtype)
        dk_ref[t] = (dk + b * dkb).astype(dk_ref.dtype)
        dv_ref[t] = (b * dvb).astype(dv_ref.dtype)
        db_ref[t] = _lane(
            jnp.sum(dkb * k.astype(f32), axis=1, keepdims=True)
            + jnp.sum(dvb * v.astype(f32), axis=1, keepdims=True))
        dg_ref[t] = _lane(dg)
    dst_ref[...] = dst


def _gdn_specs(q, v, T, index):
    """``(qk, vo, row)``: the block of ``T`` chunks of a KEY head under value
    head ``h`` (``h // R``: value heads a key head, no repeated copy in
    HBM), of a value head's ``T`` chunks, and of their ``[1, C]`` rows of
    scalars."""
    (_, _, C, dk), dv = q.shape, v.shape[-1]
    R = v.shape[0] // q.shape[0]

    def shared(h, n):
        h, n, *rest = index(h, n)
        return (h // R, n, *rest)

    return (pl.BlockSpec((None, T, C, dk), shared),
            pl.BlockSpec((None, T, C, dv), index),
            pl.BlockSpec((None, T, 1, C), index))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gdn_fwd_call(q, k, v, beta, G, T, interpret):
    """``_kda_fwd_call`` on the scalar route: ``q, k [B Hk, N, C, dk]``, ``v
    [B Hv, N, C, dv]``, ``beta`` and the running log-decay ``G [B Hv, N, 1,
    C]`` (float32); ``T`` chunks a grid step (``gdn_plan``: it divides
    ``N``)."""
    BH, N, C, dv = v.shape
    dk = q.shape[-1]
    at = lambda h, n: (h, n, 0, 0)
    qk, vo, row = _gdn_specs(q, v, T, at)
    return pl.pallas_call(
        _gdn_fwd_kernel,
        name=KERNEL_NAMES["gdn_fwd"],
        out_shape=(jax.ShapeDtypeStruct((BH, N, C, dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, N, dv, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, N, C, C), jnp.float32)),
        grid=(BH, N // T),
        in_specs=[qk, qk, vo, row, row],
        out_specs=(vo, pl.BlockSpec((None, T, dv, dk), at),
                   pl.BlockSpec((None, T, C, C), at)),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, beta, G)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _gdn_bwd_call(q, k, v, beta, G, h, X, do, T, interpret):
    """The cotangents of ``q, k`` (a VALUE head each: the caller sums the
    heads that share a key head), ``v, beta, G``, the blocks of ``T`` chunks
    last to first."""
    BH, N, C, dv = v.shape
    dk = q.shape[-1]
    back = lambda h, n: (h, N // T - 1 - n, 0, 0)
    qk, vo, row = _gdn_specs(q, v, T, back)
    per_head = pl.BlockSpec((None, T, C, dk), back)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    dqk = jax.ShapeDtypeStruct((BH, N, C, dk), q.dtype)
    return pl.pallas_call(
        _gdn_bwd_kernel,
        name=KERNEL_NAMES["gdn_bwd"],
        out_shape=(dqk, dqk, like(v), like(beta), like(G)),
        grid=(BH, N // T),
        in_specs=[qk, qk, vo, row, row,
                  pl.BlockSpec((None, T, dv, dk), back),
                  pl.BlockSpec((None, T, C, C), back), vo],
        out_specs=(per_head, per_head, vo, row, row),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, beta, G, h, X, do)


def _gdn_planned(kernel, q, v):
    """``T`` of ``gdn_plan`` at the call's shape, noted under ``kernel``."""
    BH, N, _, dv = v.shape
    plan = gdn_plan(BH, N)
    _note_plans((kernel,), plan, BH, q.shape[-1], dv)
    return plan.chunk // CHUNK


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn_kernels(q, k, v, beta, G, interpret):
    T = _gdn_planned("gdn_fwd", q, v)
    return _gdn_fwd_call(q, k, v, beta, G, T, interpret)[0]


def _gdn_kernels_fwd(q, k, v, beta, G, interpret):
    T = _gdn_planned("gdn_fwd", q, v)
    o, h, X = _gdn_fwd_call(q, k, v, beta, G, T, interpret)
    o, h, X = keep(o, KDA_OUT), keep(h, KDA_STATE), keep(X, KDA_SOLVE)
    return o, (q, k, v, beta, G, h, X)


def _gdn_kernels_bwd(interpret, res, do):
    q, v = res[0], res[2]
    T = _gdn_planned("gdn_bwd", q, v)
    dq, dk, dv_, db, dG = _gdn_bwd_call(*res, do, T, interpret)
    R = v.shape[0] // q.shape[0]

    def shared(t):  # the value heads of a key head add up, in float32
        t = t.astype(jnp.float32).reshape(q.shape[0], R, *t.shape[1:])
        return t.sum(axis=1).astype(q.dtype)

    return shared(dq), shared(dk), dv_, db, dG


_gdn_kernels.defvjp(_gdn_kernels_fwd, _gdn_kernels_bwd)


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


def _chunks(t, chunk: int = CHUNK):
    """``t [B, H, S, d]`` as ``[B H, N, C, d]``: whole chunks, the padding
    zeros (positions that leave the state alone)."""
    S = t.shape[2]
    N = -(-S // chunk)
    t = t.reshape(-1, S, t.shape[-1])
    if N * chunk != S:
        t = jnp.pad(t, ((0, 0), (0, N * chunk - S), (0, 0)))
    return t.reshape(t.shape[0], N, chunk, t.shape[-1])


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

    G = jnp.cumsum(_chunks(g.astype(jnp.float32)), axis=2)
    args = (_chunks(q), _chunks(k), _chunks(kb), _chunks(vb), G)
    if interpret == "xla" or (interpret is None and not _kernel_route()):
        o = _scan_chunks(*args)
    else:
        o = _kda_kernels(*args, bool(interpret))
    return o.reshape(B * H, N * C, dv)[:, :S].reshape(B, H, S, dv)


def gdn_attention(q, k, v, g, beta, interpret: Optional[bool] = None):
    """``kda_attention`` under ONE scalar decay a head and grouped heads
    (Gated DeltaNet, Qwen3-Next's linear mixer): ``q, k [B, Hk, S, dk]``,
    ``v [B, Hv, S, dv]`` with ``Hv`` a multiple of ``Hk`` — value head ``j``
    reads key head ``j // (Hv / Hk)`` —, the log-decay ``g [B, Hv, S]`` (at
    most 0, float32) and ``beta [B, Hv, S]``. The same chunked mathematics
    (module docstring) with ``G [C, 1]``: the pair matrices are two MXU
    products under one mask of decay differences (``_pair_scalar``), the
    solve, the application, the backward around ``X`` and the kept
    residuals are ``kda_attention``'s own. Kernels ``harmony_gdn_fwd`` /
    ``harmony_gdn_bwd``: q and k are read at their key head (no repeated
    copy in HBM), ``g`` and ``beta`` travel ``[S]`` a head, and ``dq``,
    ``dk`` are summed over the value heads that share them. ``interpret``
    as ``kda_attention``'s."""
    B, Hk, S, dk = q.shape
    Hv, dv = v.shape[1], v.shape[-1]
    if k.shape != q.shape or v.shape[:3:2] != (B, S) or Hv % Hk \
            or g.shape != (B, Hv, S) or beta.shape != (B, Hv, S):
        raise ValueError(f"gdn_attention: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, g {g.shape}, beta {beta.shape}")
    C = CHUNK
    N = -(-S // C)

    chunks = _chunks
    f32 = jnp.float32
    G = jnp.cumsum(chunks(g.astype(f32)[..., None]), axis=2)   # [BHv, N, C, 1]
    b = chunks(beta.astype(f32)[..., None])
    if interpret == "xla" or (interpret is None and not _kernel_route()):
        R = Hv // Hk
        q, k = (chunks(jnp.repeat(t, R, axis=1)) for t in (q, k))
        v = chunks(v)
        kb = (b * k.astype(f32)).astype(k.dtype)
        vb = (b * v.astype(f32)).astype(v.dtype)
        o = _scan_chunks(q, k, kb, vb, G)
    else:
        rows = lambda t: t.reshape(B * Hv, N, 1, C)
        o = _gdn_kernels(chunks(q), chunks(k), chunks(v), rows(b), rows(G),
                         bool(interpret))
    return o.reshape(B * Hv, N * C, dv)[:, :S].reshape(B, Hv, S, dv)
