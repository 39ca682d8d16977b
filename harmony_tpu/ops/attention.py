"""Flash attention for TPU: Pallas forward kernel + differentiable blockwise.

The reference has no attention models at all (SURVEY.md §5.7) — long-context
support is a first-class extension of this framework, not a port. Two tiers:

  * :func:`blockwise_attention` — pure-JAX streaming-softmax attention
    (lax.scan over KV blocks, O(S) memory). Differentiable by autodiff;
    numerically identical to flash attention. Works on any backend.
  * :func:`flash_attention` — Pallas TPU kernel for the forward pass
    (grid (batch*heads, q_blocks, kv_blocks), online softmax state in VMEM
    scratch, QK^T and PV on the MXU in fp32). Backward runs through the
    blockwise implementation's VJP (recompute — the flash-attention trick of
    trading FLOPs for HBM traffic, same spirit as jax.checkpoint).

Layout: (batch, heads, seq, head_dim). head_dim should be a multiple of 128
for peak MXU utilisation; any size compiles (pallas pads tiles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30  # finite "-inf": keeps masked softmax NaN-free
_LANES = 128  # TPU lane width: per-row stats (LSE, delta) are stored
              # lane-replicated so their blocks are (8,128)-tileable


def _dot_f32(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _apply_causal_mask(s, iq, ik, block_q, block_k):
    """Mask one (q-block, kv-block) score tile. Shared by the forward and
    both backward kernels — they MUST mask identically or gradients silently
    diverge from the forward."""
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(row >= col, s, _NEG_INF)


def _resolve_scale(q, scale):
    """One source of truth for the scale default used by the primal
    forward, the VJP forward and the VJP backward."""
    return scale if scale is not None else q.shape[-1] ** -0.5


# ---------------------------------------------------------------------------
# Pure-JAX blockwise (differentiable reference path)
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_k: int = DEFAULT_BLOCK_K,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Streaming-softmax attention: scan over KV blocks carrying (acc, m, l).

    q [B,H,Sq,D], k/v [B,H,Sk,D] -> [B,H,Sq,D]. O(Sq * block_k) live memory
    instead of O(Sq*Sk); autodiff through the scan gives the memory-efficient
    backward.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, Sk)
    nk, rem = divmod(Sk, block_k)
    if rem:  # pad KV to a whole number of blocks; padded keys are masked out
        pad = block_k - rem
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nk += 1
    kb = k.reshape(B, H, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nk, block_k, D).transpose(2, 0, 1, 3, 4)

    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(Sq)[:, None]

    def step(carry, blk):
        acc, m, l = carry
        kblk, vblk, start = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        kv_pos = start + jnp.arange(block_k)[None, :]
        mask = kv_pos < Sk  # padding
        if causal:
            mask = mask & (q_pos >= kv_pos)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    # Derive the init carry from qf so its varying-axes type matches under
    # shard_map (plain zeros are "unvarying" and fail the scan's vma check
    # when attention runs inside a manual-axes region, e.g. a pipeline stage).
    acc0 = jnp.zeros_like(qf)
    m0 = jnp.full_like(qf[..., 0], _NEG_INF)
    l0 = jnp.zeros_like(qf[..., 0])
    starts = jnp.arange(nk) * block_k
    (acc, _, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, starts))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
               scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: KV blocks strictly above the diagonal contribute nothing.
    needed = True if not causal else (ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        # NATIVE-dtype operand feeds: a bf16 q/k/v runs the MXU at bf16
        # throughput with fp32 accumulation (preferred_element_type) —
        # casting operands to fp32 first (the old code) forfeited most of
        # the MXU for no accuracy the fp32 accumulator wasn't already
        # providing. The scale applies to the fp32 product, exactly.
        s = _dot_f32(q_ref[0], k_ref[0], trans_b=True) * scale  # (bq, bk)
        if causal:
            s = _apply_causal_mask(s, iq, ik, block_q, block_k)
        m_prev = m_ref[:, :1]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=1, keepdims=True)
        # p feeds the MXU in v's dtype (bf16 weights => bf16 p, the
        # standard flash trade; fp32 v keeps p fp32 so tests/CPU are exact)
        acc_ref[:] = acc_ref[:] * alpha + _dot_f32(
            p.astype(v_ref.dtype), v_ref[0])
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == nk - 1)
    def _write():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # log-sum-exp per row, consumed by the fused backward. Stored
        # broadcast across a 128-lane trailing dim: Mosaic requires the last
        # two block dims be (8,128)-tileable, and a (1, block_q) row block is
        # not — the lane-replicated layout is the canonical TPU shape for
        # per-row softmax stats (cf. jax.experimental.pallas.ops.tpu
        # flash_attention's l/m outputs).
        lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l), lse_ref.shape[1:])


def _out_struct(shape, dtype, *refs):
    """ShapeDtypeStruct whose varying-manual-axes (vma) is the union of the
    reference arrays' — required when a pallas_call runs INSIDE shard_map
    (the ring-attention inner): outputs vary over every axis an input
    does."""
    vma = frozenset()
    for r in refs:
        vma = vma | jax.typeof(r).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _flash_forward(q, k, v, causal, block_q, block_k, scale, interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq lens ({Sq},{Sk}) must divide by blocks ({block_q},{block_k})"
        )
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    grid = (B * H, Sq // block_q, Sk // block_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="harmony_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Sq, D), q.dtype, q, k, v),
            _out_struct((B * H, Sq, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            _vmem((block_q, 128)),   # running row-max m
            _vmem((block_q, 128)),   # running normaliser l
            _vmem((block_q, D)),     # unnormalised output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D), lse[:, :, 0].reshape(B, H, Sq)


def _bwd_p_ds(q, k, v, do, lse, delta, iq, ik, scale, causal,
              block_q, block_k):
    """Shared backward math for one (q-block, kv-block) tile: returns
    (p [bq,bk], ds [bq,bk]) with p the normalized softmax block.
    ``lse``/``delta`` arrive as (bq, 1) column tiles (lane 0 of the
    lane-replicated stats)."""
    # native-dtype MXU feeds with fp32 accumulation (see _fa_kernel)
    s = _dot_f32(q, k, trans_b=True) * scale                  # (bq, bk)
    if causal:
        s = _apply_causal_mask(s, iq, ik, block_q, block_k)
    p = jnp.exp(s - lse)                                      # normalized
    dp = _dot_f32(do, v, trans_b=True)
    ds = p * (dp - delta)
    return p, ds


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *,
                       scale, causal, block_q, block_k):
    ik = pl.program_id(1)   # kv block (this output tile)
    iq = pl.program_id(2)   # q blocks stream by
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = True if not causal else (iq * block_q + block_q - 1 >= ik * block_k)

    @pl.when(needed)
    def _compute():
        p, ds = _bwd_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, :, :1], delta_ref[0, :, :1],
            iq, ik, scale, causal, block_q, block_k,
        )
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)

    @pl.when(iq == nq - 1)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)   # q block (this output tile)
    ik = pl.program_id(2)   # kv blocks stream by
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    needed = True if not causal else (ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        _, ds = _bwd_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, :, :1], delta_ref[0, :, :1],
            iq, ik, scale, causal, block_q, block_k,
        )
        dq_acc[:] += scale * _dot_f32(ds.astype(k_ref.dtype), k_ref[0])

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, do, causal, block_q, block_k, scale,
                    interpret, lse_cotangent=None):
    """Fused flash backward: dK/dV kernel (grid over kv tiles) + dQ kernel
    (grid over q tiles); softmax recomputed per tile from the saved LSE —
    the O(S) memory trade the forward made, carried into the backward.

    ``lse_cotangent`` supports callers that consume the LSE output (the
    ring-attention chunk merge): d lse_r / d s_rc = p_rc, so the extra term
    is ``g_lse_r * p_rc`` — algebraically it folds into the delta:
    ds = p * (dp - (delta - g_lse)). The kernels are unchanged."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    dof = do.reshape(B * H, Sq, D)
    # Per-row stats enter lane-replicated (see _LANES note in the forward);
    # XLA materializes the broadcasts, the kernels read lane 0.
    lsef = jnp.broadcast_to(lse.reshape(B * H, Sq)[:, :, None],
                            (B * H, Sq, _LANES))
    # delta_i = dO_i . O_i (rowwise), cheap enough to leave to XLA.
    delta = jnp.einsum("bsd,bsd->bs", dof.astype(jnp.float32),
                       out.reshape(B * H, Sq, D).astype(jnp.float32))
    if lse_cotangent is not None:
        delta = delta - lse_cotangent.reshape(B * H, Sq).astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, :, None], (B * H, Sq, _LANES))

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0))
    dkv = functools.partial(
        _fa_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k)
    dk, dv = pl.pallas_call(
        dkv,
        name="harmony_flash_bwd_dkv",
        grid=(B * H, Sk // block_k, Sq // block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Sk, D), k.dtype, q, k, v, do),
            _out_struct((B * H, Sk, D), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[_vmem((block_k, D)), _vmem((block_k, D))],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    q_spec2 = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    dqk = functools.partial(
        _fa_bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k)
    dq = pl.pallas_call(
        dqk,
        name="harmony_flash_bwd_dq",
        grid=(B * H, Sq // block_q, Sk // block_k),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((B * H, Sq, D), q.dtype, q, k, v, do),
        scratch_shapes=[_vmem((block_q, D))],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused attention. Forward AND backward are Pallas TPU kernels
    (``interpret=True`` runs them in the Pallas interpreter, for CPU
    tests; off-TPU callers that want speed take
    :func:`blockwise_attention` by name): the forward saves only O(S)
    softmax statistics (LSE) and the backward recomputes each softmax tile
    from them — flash attention's memory/FLOPs trade in both directions.

    Thin wrapper over :func:`flash_attention_lse` (the kernel always writes
    the LSE output; discarding it costs nothing, and a zero LSE cotangent
    folds to the identical backward) — ONE custom_vjp to maintain."""
    out, _ = flash_attention_lse(q, k, v, causal, block_q, block_k, scale,
                                 interpret)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """:func:`flash_attention` that ALSO returns the per-row log-sum-exp
    ([B, H, Sq], fp32) — the composable form: outputs of independent KV
    chunks merge exactly via their LSEs (``ring_attention``'s flash inner).
    Differentiable in both outputs; the LSE cotangent folds into the
    backward kernels' delta term (see ``_flash_backward``)."""
    if not (q.dtype == k.dtype == v.dtype):
        raise TypeError(
            f"flash attention feeds the MXU in the operands' dtype, so "
            f"q/k/v must share one dtype (got {q.dtype}/{k.dtype}/"
            f"{v.dtype}); cast the operands before the call"
        )
    return _flash_forward(q, k, v, causal, block_q, block_k,
                          _resolve_scale(q, scale), interpret)


def _fa_lse_fwd(q, k, v, causal, block_q, block_k, scale, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              _resolve_scale(q, scale), interpret)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(causal, block_q, block_k, scale, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_backward(q, k, v, out, lse, g_out, causal, block_q, block_k,
                           _resolve_scale(q, scale), interpret,
                           lse_cotangent=g_lse)


flash_attention_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)
