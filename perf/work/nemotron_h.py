"""Operations and bytes the state-space scan kernels of a Nemotron-H step
NEED, computed from the configuration's shapes — the yardstick's own
arithmetic for ``ssd_roofline_share`` (perf/layer_metrics/). Flash attention
and the grouped matmuls of this configuration are the other cells' kernels
(``perf/work/smallthinker.py``, ``perf/work/moonlight.py``).

**The chunked Mamba-2 recurrence** (harmony_tpu/ops/ssd.py has the equations),
a head, a chunk of ``C`` positions, ``P`` wide with a state of ``N``, a
multiply-add as 2 FLOPs, a triangular product at the half it needs:

  forward (``harmony_ssd_fwd``)
    c b^T, lower — ONCE A GROUP: the heads of a
      group share b and c                           C^2 N / (heads a group)
    ((c b^T) * L) x, lower                          C^2 P
    c S^T                                           2 C N P
    (x e^(G_C - G))^T b                             2 C N P
                          = C^2 (P + N / heads a group) + 4 C N P

  backward (``harmony_ssd_bwd``): the chunk recomputed from its boundary
    state (that is what the kernel is defined to do, as flash attention's
    backward recomputes its scores) and two products for each product of
    the forward                   = 3 x the forward

That the kernel forms ``c b^T`` again for every head of the group, computes
whole ``C x C`` tiles and masks them, or takes the decay matrix ``L`` through
the VPU is the kernel's cost, not the need. Recomputation by
``jax.checkpoint`` counts as calls (each call needs its work).

**Bytes a call must move** to and from HBM, a sequence of ``S`` positions: in
``x`` (``H P`` wide) in the activations' dtype, ``b`` and ``c`` (``G N`` wide:
once a group, not once a head), the log-decay (float32, a head); out ``y`` and
the ``S / C`` boundary states (float32 ``P x N`` a head); the backward reads
those and ``dy`` and writes the four gradients (``b``'s and ``c``'s once a
group). (The kernel as built writes ``b``'s and ``c``'s gradients a HEAD each
in float32 and XLA sums them: more than the need.)

The bound of a call is the larger of FLOPs / bf16 peak and bytes / HBM peak
(``perf/peaks.json``); at ``C`` 128, heads of 64 and a state of 128 a forward
call needs ~75 FLOPs a byte against the chip's 240, so HBM binds.
"""
from __future__ import annotations

from typing import Any, Dict

KERNELS = ("harmony_ssd_fwd", "harmony_ssd_bwd")
FLOAT32 = 4


def _shape(app: Dict[str, Any]):
    return (int(app["ssd_heads"]), int(app["ssd_groups"]),
            int(app["ssd_head_dim"]), int(app["ssd_state"]),
            int(app["ssd_chunk"]), int(app["max_seq"]))


def ssd_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` needs over ``batch`` sequences."""
    h, g, p, n, c, s = _shape(app)
    chunk = c * c * (p + n * g / h) + 4.0 * c * n * p
    return (int(batch) * h * -(-s // c) * chunk
            * {"harmony_ssd_fwd": 1.0, "harmony_ssd_bwd": 3.0}[kernel])


def ssd_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one call of ``kernel`` must move over ``batch`` sequences."""
    h, g, p, n, c, s = _shape(app)
    act = 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4
    x = h * s * p * act
    bc = 2 * g * s * n * act
    decay = h * s * FLOAT32
    states = h * -(-s // c) * p * n * FLOAT32
    if kernel == "harmony_ssd_fwd":
        return int(batch) * float(x + bc + decay + x + states)
    # backward: everything the forward read, its states and dy in; the
    # gradients of x, b, c and the log-decay out
    return int(batch) * float(2 * (x + bc + decay) + states + x)


def bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                  peaks: Dict[str, float]) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}`` of one call: the larger of its
    compute time at the bf16 peak and its traffic time at the HBM peak."""
    flops = ssd_flops_per_call(app, batch, kernel)
    nbytes = ssd_bytes_per_call(app, batch, kernel)
    t_mxu, t_hbm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds_bound": max(t_mxu, t_hbm),
            "binds": "bf16 MXU peak" if t_mxu >= t_hbm else "HBM peak"}
