"""Numeric primitives shared by the model families (LM, ViT)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: valid values for a model config's ``attn`` field
ATTN_CHOICES = ("auto", "flash", "blockwise")


def dense_init(key, shape):
    """1/sqrt(fan_in)-scaled normal init for a [fan_in, ...] weight."""
    return jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5


def rms_norm(x, w, eps: float = 1e-6):
    """RMSNorm (f32 statistics regardless of activation dtype)."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def validate_attn(attn: str) -> str:
    if attn not in ATTN_CHOICES:
        raise ValueError(f"unknown attn {attn!r}; choose from {ATTN_CHOICES}")
    return attn


def flash_ok(seq: int, block: int | None = None, *, head_dim: int = 128,
             v_head_dim: int | None = None, dtype=jnp.bfloat16,
             group: int = 1, streams: int = 1) -> bool:
    """Can the Pallas flash kernels tile a self-attention over ``seq``
    positions? The kernels' own answer (ops.attention.tile_plan): the gate
    here and the kernel's ValueError cannot disagree. ``block`` is an
    explicit block size the caller will pass to flash_attention (default:
    none — the kernels choose their tiles from the shape); ``group`` the
    query heads a K/V head serves and ``streams`` the copies of the
    sequence a query head stacks (what the backward keeps resident)."""
    from harmony_tpu.ops.attention import tile_plan

    return tile_plan(seq, seq, head_dim, dtype, block_q=block, block_k=block,
                     dv=v_head_dim, group=group, streams=streams) is not None


def resolve_attn(attn: str, seq: int, block: int | None = None,
                 **operands) -> str:
    """'auto' -> 'flash' when the program being traced runs on TPUs
    (utils.platform.trace_is_tpu) and the kernels can tile (``operands``:
    flash_ok's ``head_dim`` / ``v_head_dim`` / ``dtype`` / ``group`` / ``streams``), else 'blockwise'. Call at trace
    time."""
    if attn != "auto":
        return attn
    from harmony_tpu.utils.platform import trace_is_tpu

    return ("flash" if trace_is_tpu() and flash_ok(seq, block, **operands)
            else "blockwise")


def flash_on_mesh(q, k, v, with_lse: bool = False, **kw):
    """:func:`harmony_tpu.ops.flash_attention` on [B, H, S, D] operands,
    split over the traced mesh's data axis when there is one: a
    pallas_call is opaque to the GSPMD partitioner, which would otherwise
    all-gather the batch-sharded operands and run the whole attention on
    every chip. ``with_lse``: :func:`flash_attention_lse`'s pair."""
    from jax.sharding import PartitionSpec as P

    from harmony_tpu.ops.attention import flash_attention, flash_attention_lse
    from harmony_tpu.parallel.mesh import DATA_AXIS
    from harmony_tpu.utils.platform import trace_mesh

    fn = functools.partial(
        flash_attention_lse if with_lse else flash_attention, **kw)
    mesh = trace_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn(q, k, v)
    data = mesh.shape.get(DATA_AXIS, 1)
    spec = P(DATA_AXIS) if data > 1 and q.shape[0] % data == 0 else P()
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=(spec, spec) if with_lse else spec,
                         check_vma=False)(q, k, v)
