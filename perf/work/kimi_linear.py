"""Operations and bytes the KDA kernels of a Kimi Linear step NEED, computed
from the configuration's shapes — the yardstick's own arithmetic for
``kda_roofline_share`` (perf/layer_metrics/). Flash attention and the grouped
matmuls of this configuration are counted by ``perf/work/moonlight.py``
(same kernels, same field names).

**The chunked gated delta rule** (harmony_tpu/ops/kda.py has the equations),
a head, a chunk of ``C`` positions, ``dk`` / ``dv`` wide, a multiply-add as 2
FLOPs, a triangular product at the half it needs:

  forward (``harmony_kda_fwd``)
    A    = (b k e^G)(k e^-G)^T, strictly lower      C^2 dk
    Aqk  = (q e^G)(k e^-G)^T, lower                 C^2 dk
    the triangular system (I + A)[U0 | W] = [b v | b k e^G],
      by substitution                               C^2 (dk + dv)
    W S, (q e^G) S, (k e^(G_C - G))^T U             3 x 2 C dk dv
    Aqk U, lower                                    C^2 dv
                                  = C^2 (3 dk + 2 dv) + 6 C dk dv

  backward (``harmony_kda_bwd``): the chunk recomputed from its boundary
    state (that is what the kernel is defined to do, as flash attention's
    backward recomputes its scores) and two products for each product of
    the forward                   = 3 x the forward

That the kernel inverts ``I + A`` by block recursion instead of substituting,
computes whole ``C x C`` tiles and masks them, or runs float32 products as
several bfloat16 passes is the kernel's cost, not the need. Recomputation by
``jax.checkpoint`` counts as calls (each call needs its work).

**Bytes a call must move** to and from HBM, a head of ``S`` positions: in
``q, k, v`` in the activations' dtype, the log-decay ``g`` (float32, ``dk``
wide) and ``beta`` (float32); out ``o`` and the ``S / C`` boundary states
(float32 ``dk x dv``); the backward reads those and ``dO`` and writes the five
gradients. (The kernel as built is handed ``b k`` and ``b v`` in place of
``beta``: more than the need.)

The bound of a call is the larger of FLOPs / bf16 peak and bytes / HBM peak
(``perf/peaks.json``); at ``C`` 64 and 128-wide heads a forward call needs ~55
FLOPs a byte against the chip's 240, so HBM binds.
"""
from __future__ import annotations

from typing import Any, Dict

KERNELS = ("harmony_kda_fwd", "harmony_kda_bwd")
CHUNK = 64          # harmony_tpu/ops/kda.py CHUNK: the kernel's chunk
FLOAT32 = 4


def _chunk_flops(app: Dict[str, Any]) -> float:
    c, d = CHUNK, int(app["linear_head_dim"])
    return c * c * (3 * d + 2 * d) + 6.0 * c * d * d


def kda_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` needs over ``batch`` sequences."""
    chunks = (int(batch) * int(app["linear_heads"])
              * -(-int(app["max_seq"]) // CHUNK))
    return chunks * _chunk_flops(app) * {"harmony_kda_fwd": 1.0,
                                         "harmony_kda_bwd": 3.0}[kernel]


def kda_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one call of ``kernel`` must move over ``batch`` sequences."""
    d, s = int(app["linear_head_dim"]), int(app["max_seq"])
    act = 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4
    heads = int(batch) * int(app["linear_heads"])
    qkv = 3 * s * d * act
    decay_beta = s * d * FLOAT32 + s * FLOAT32
    out = s * d * act
    states = -(-s // CHUNK) * d * d * FLOAT32
    if kernel == "harmony_kda_fwd":
        return heads * float(qkv + decay_beta + out + states)
    # backward: everything the forward read, its states and dO in; the
    # gradients of q, k, v, g and beta out
    return heads * float(2 * (qkv + decay_beta) + out + states)


def bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                  peaks: Dict[str, float]) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}`` of one call: the larger of its
    compute time at the bf16 peak and its traffic time at the HBM peak."""
    flops = kda_flops_per_call(app, batch, kernel)
    nbytes = kda_bytes_per_call(app, batch, kernel)
    t_mxu, t_hbm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds_bound": max(t_mxu, t_hbm),
            "binds": "bf16 MXU peak" if t_mxu >= t_hbm else "HBM peak"}
