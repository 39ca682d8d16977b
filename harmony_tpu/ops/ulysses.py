"""All-to-all (Ulysses-style) sequence parallelism — the head-scatter
alternative to ring attention.

Where ring attention keeps tokens home and rotates K/V around the ring,
the all-to-all scheme re-shards ONCE per attention call: an
``all_to_all`` turns the sequence-sharded [B, H, S/n, D] activations into
head-sharded [B, H/n, S, D], each device runs ordinary (flash/blockwise)
attention over its full sequence for its head group, and a second
``all_to_all`` restores sequence sharding. Two collectives per call
(O(B·H·S·D/n) bytes each) versus the ring's n-1 ppermutes — cheaper when
heads divide evenly and sequence chunks are large; the ring wins when
H < n or when overlap with compute matters more than collective count.

Runs INSIDE shard_map (uses ``lax.all_to_all``), mirroring
harmony_tpu.ops.ring conventions; :func:`a2a_self_attention` is the
host-level convenience wrapper. The reference has no analogue
(SURVEY.md §5.7) — long context is a first-class addition here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from harmony_tpu.ops.attention import (
    blockwise_attention,
    flash_attention,
    tile_plan,
)


def a2a_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact attention over a sequence sharded on ``axis_name`` via head
    scattering.

    q/k/v: LOCAL shards [B, H, S_local, D] (call inside shard_map); H must
    divide by the axis size. Returns the local output shard.
    """
    B, H, S_loc, D = q.shape
    n = lax.psum(1, axis_name)
    if H % n:
        raise ValueError(f"num heads {H} must divide by axis size {n}")
    # seq-sharded -> head-sharded: split heads, concat sequence.
    def scatter(t):
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter(q), scatter(k), scatter(v)   # [B, H/n, S, D]
    # Post-gather each device holds DENSE full-sequence q/k/v — exactly the
    # Pallas flash kernel's case (the edge a2a has over ring, whose inner
    # fold can't use it); blockwise is the any-backend/odd-shape tier.
    S = qh.shape[2]
    from harmony_tpu.utils.platform import trace_is_tpu

    if trace_is_tpu() and tile_plan(S, S, D, qh.dtype, causal,
                                    dv=vh.shape[3],
                                    group=qh.shape[1] // kh.shape[1]
                                    ) is not None:
        o = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        o = blockwise_attention(qh, kh, vh, causal=causal, scale=scale)
    # head-sharded -> seq-sharded: split sequence, concat heads.
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def a2a_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    seq_axis: str,
    batch_axis: Optional[str] = None,
    causal: bool = False,
) -> jnp.ndarray:
    """Host-level wrapper: shard [B,H,S,D] inputs over ``mesh`` with the
    sequence dim on ``seq_axis``, run :func:`a2a_attention` under
    shard_map."""
    spec = P(batch_axis, None, seq_axis, None)
    fn = functools.partial(a2a_attention, axis_name=seq_axis, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
