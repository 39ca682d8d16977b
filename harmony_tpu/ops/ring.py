"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context training shards the *sequence* across devices; each device
holds a Q/K/V chunk and the K/V chunks rotate around the ring (ppermute
over ICI) while every device folds each visiting chunk into its local
online-softmax state. ICI transfer of chunk t+1 overlaps the attention
compute of chunk t (XLA schedules the ppermute DMA concurrently with the
einsums). Memory per device stays O(S_local^2 / ring) and the full-sequence
softmax is exact — the blockwise/flash merge, distributed.

The reference has nothing like this (SURVEY.md §5.7: its analogue of
scaling one object beyond a node is table sharding); ring attention is the
long-context capability this framework adds as first-class.

:func:`ring_attention` is written to run INSIDE ``shard_map`` (it uses
``lax.ppermute``/``axis_index``); :func:`ring_self_attention` is the
host-level convenience that wraps it in shard_map over a mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _resolve_inner(inner: str) -> str:
    # "auto" = flash only in a one-chip TPU process, einsum elsewhere. The
    # compiled kernel PLUS a multi-chip ring rotation is opted into by
    # name (inner="flash" / HARMONY_RING_INNER=flash); a failure there is
    # loud (a Mosaic compile or vma error), never a fallback.
    if inner == "auto":
        from harmony_tpu.utils.platform import env_choice, trace_is_tpu

        forced = env_choice("HARMONY_RING_INNER", ("flash", "einsum"))
        if forced:
            return forced
        return ("flash"
                if trace_is_tpu() and jax.device_count() == 1
                else "einsum")
    if inner not in ("flash", "einsum"):
        raise ValueError(f"unknown ring inner {inner!r}")
    return inner


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    inner: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded on ``axis_name``.

    q/k/v: LOCAL shards [B, H, S_local, D] (call inside shard_map).
    Returns the local output shard [B, H, S_local, D].

    ``inner`` picks how each visiting chunk is folded:
      * "flash"  — the Pallas flash kernel per chunk (scores stay in VMEM;
        MXU matmuls), merged exactly across chunks via per-row LSE
        (flash_attention_lse). Causal rings lax.switch three chunk
        relations — full / diagonal / SKIP — so fully-masked chunks cost
        nothing (the einsum inner computes-then-masks them).
      * "einsum" — the original streaming-softmax fold (any backend, any
        shape).
      * "auto"   — flash in a one-chip TPU process, einsum on multi-chip
        deployments and off-TPU. HARMONY_RING_INNER overrides (see
        _resolve_inner).

    ``interpret=True`` runs the flash inner in the Pallas interpreter (CPU
    tests)."""
    B, H, S, D = q.shape
    if k.shape[3] != D or v.shape[3] != D:
        # the folds carry one [.., D] accumulator for q, k and v alike;
        # latent attention's (192, 128) trains through the single-device
        # tiers (flash_attention, blockwise_attention, a2a_attention)
        raise ValueError(
            f"ring attention folds chunks at one head width: q {D}, k "
            f"{k.shape[3]}, v {v.shape[3]}")
    scale = scale if scale is not None else D ** -0.5
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    inner = _resolve_inner(inner)

    qf = q.astype(jnp.float32) * scale
    q_pos = my * S + jnp.arange(S)[:, None]            # global q positions

    if inner == "flash":
        return _ring_flash(qf, k, v, axis_name, causal, n, my, perm, q.dtype,
                           interpret)

    def fold(acc, m, l, kb, vb, src):
        """Merge one visiting KV chunk (home shard ``src``) into the online
        softmax state."""
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
        if causal:
            kv_pos = src * S + jnp.arange(S)[None, :]
            s = jnp.where(q_pos >= kv_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)
        )
        return acc_new, m_new, l_new

    def step(carry, t):
        acc, m, l, kb, vb = carry
        acc, m, l = fold(acc, m, l, kb, vb, (my - t) % n)
        # Rotate KV to the next device for the following step.
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (acc, m, l, kb, vb), None

    # The softmax state starts replicated but becomes device-varying inside
    # the scan. Deriving it from q (zeros_like keeps the varying-axes type)
    # gives it exactly q's manual axes — correct whether the surrounding
    # shard_map maps one axis (the ring) or several (ring + batch).
    acc0 = jnp.zeros_like(q, dtype=jnp.float32)
    m0 = jnp.full_like(q[..., 0], _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros_like(q[..., 0], dtype=jnp.float32)
    # Scan the first n-1 chunks (each ends with a rotation); the last
    # visiting chunk is folded outside the scan so its rotation — whose
    # result nothing reads — is never issued.
    (acc, m, l, kb, vb), _ = lax.scan(
        jax.checkpoint(step), (acc0, m0, l0, k, v), jnp.arange(n - 1)
    )
    acc, _, l = fold(acc, m, l, kb, vb, (my - (n - 1)) % n)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _ring_flash(qf, k, v, axis_name, causal, n, my, perm, out_dtype,
                interpret=False):
    """Flash-inner ring: each visiting chunk through the Pallas kernel
    (out_t, lse_t), merged via the numerically-safe LSE running max.

    qf is pre-scaled fp32 (the kernel is called with scale=1). The merge
    carries (num, m, den): num = unnormalized output in the running frame
    m, den = normalizer. A skipped chunk contributes lse=-inf and weight
    exactly 0 (guarded — exp(-inf - -inf) would be 1)."""
    from harmony_tpu.ops.attention import flash_attention_lse

    # positional args: custom_vjp + nondiff_argnums and keywords don't mix;
    # blocks None: the kernels tile each chunk from its shape
    def full(args):
        q_, k_, v_ = args
        return flash_attention_lse(q_, k_, v_, False, None, None, 1.0,
                                   interpret)

    def diag(args):
        q_, k_, v_ = args
        return flash_attention_lse(q_, k_, v_, True, None, None, 1.0,
                                   interpret)

    def skip(args):
        q_, _, _ = args
        # full_like, not full: both outputs must inherit q_'s varying
        # manual axes or lax.switch rejects the branches under shard_map
        # (a fresh constant is axis-invariant; the kernel outputs vary)
        return (jnp.zeros_like(q_),
                jnp.full_like(q_[..., 0], _NEG_INF, dtype=jnp.float32))

    def fold(num, m, den, kb, vb, src):
        if causal:
            rel = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_t, lse_t = lax.switch(
                rel, (full, diag, skip),
                (qf, kb.astype(jnp.float32), vb.astype(jnp.float32)),
            )
        else:
            o_t, lse_t = full(
                (qf, kb.astype(jnp.float32), vb.astype(jnp.float32))
            )
        m_new = jnp.maximum(m, lse_t)
        # exp(x - m_new) with BOTH at the finite floor must be 0, not 1:
        # a skipped/empty chunk carries no weight.
        c_prev = jnp.where(m <= _NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        c_new = jnp.where(lse_t <= _NEG_INF / 2, 0.0, jnp.exp(lse_t - m_new))
        num_new = num * c_prev[..., None] + o_t * c_new[..., None]
        den_new = den * c_prev + c_new
        return num_new, m_new, den_new

    def step(carry, t):
        num, m, den, kb, vb = carry
        num, m, den = fold(num, m, den, kb, vb, (my - t) % n)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (num, m, den, kb, vb), None

    num0 = jnp.zeros_like(qf)
    m0 = jnp.full_like(qf[..., 0], _NEG_INF)
    den0 = jnp.zeros_like(qf[..., 0])
    (num, m, den, kb, vb), _ = lax.scan(
        jax.checkpoint(step), (num0, m0, den0, k, v), jnp.arange(n - 1)
    )
    num, _, den = fold(num, m, den, kb, vb, (my - (n - 1)) % n)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(out_dtype)


def ring_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    seq_axis: str,
    batch_axis: Optional[str] = None,
    causal: bool = False,
    inner: str = "auto",
    check_vma: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Host-level wrapper: shard [B,H,S,D] inputs over ``mesh`` with the
    sequence dim on ``seq_axis`` (and optionally batch on ``batch_axis``),
    run :func:`ring_attention` under shard_map.

    ``interpret=True`` runs the flash inner in the Pallas interpreter
    (off-TPU tests) and needs ``check_vma=False``: the interpreter's
    internal slicing trips shard_map's varying-axes checker."""
    spec = P(batch_axis, None, seq_axis, None)
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                           inner=inner, interpret=interpret)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=check_vma,
    )(q, k, v)
