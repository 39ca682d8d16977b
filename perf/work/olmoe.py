"""Operations the grouped-matmul kernels of an OLMoE step NEED, computed from
the configuration's shapes and the rows the program reported — the
yardstick's own arithmetic for ``gmm_roofline_share``
(perf/layer_metrics/gmm_roofline_share.py).

An expert layer multiplies each held token-slot's row three times forward
(gate and up: [d] x [d, f]; down: [f] x [f, d]) — three grouped matmuls of
``2 * d * f`` FLOPs a row — and each of those has two backward products of
the same size (``dx`` and ``dw``). Rows of slots routed to experts this chip
does not hold are not work: the kernels skip their tiles, and skipped tiles
count nothing. Recomputation would count nothing either.
"""
from __future__ import annotations

from typing import Any, Dict

#: grouped matmuls of one expert layer, by the kernel that runs them
CALLS_PER_LAYER = {"harmony_gmm_fwd": 3, "harmony_gmm_dx": 3,
                   "harmony_gmm_dw": 3}


def moe_layers(app: Dict[str, Any]) -> int:
    every = int(app.get("moe_every", 2))
    return sum(1 for i in range(int(app["n_layers"]))
               if i % every == every - 1)


def slots_per_step(app: Dict[str, Any], batch: int) -> int:
    """Token-slots ONE expert layer routes a step: tokens x top-k."""
    return int(batch) * int(app["max_seq"]) * int(app["moe_top_k"])


def gmm_flops_per_call(app: Dict[str, Any], held_rows: float) -> float:
    """FLOPs one call of any of the three kernels needs: the rows of one
    layer that are routed to held experts times ``2 * d_model * d_ff``."""
    return 2.0 * float(held_rows) * int(app["d_model"]) * int(app["d_ff"])


def gmm_flops_per_step(app: Dict[str, Any], held_rows: float) -> float:
    """All nine grouped matmuls of every expert layer, forward and backward,
    at ``held_rows`` a layer."""
    return (gmm_flops_per_call(app, held_rows)
            * sum(CALLS_PER_LAYER.values()) * moe_layers(app))
