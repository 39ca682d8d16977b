"""Operations the kernels of a Moonlight (DeepSeek-V3-shaped) step NEED,
computed from the configuration's shapes and the rows the program reported —
the yardstick's own arithmetic for ``flash_roofline_share`` and
``routed_gmm_roofline_share`` (perf/layer_metrics/).

**Which blocks route** is the program's own answer
(``TransformerConfig.moe_layers()``: the leading dense layer is not an
expert layer), so the rows of a step are divided among the layers that had
them.

**Grouped matmuls**, as in ``perf/work/olmoe.py``: three forward products a
held token-slot (gate and up ``[d] x [d, f]``, down ``[f] x [f, d]``),
``2 d f`` FLOPs a row each, and two backward products of the same size for
each (``dx``, ``dw``). Slots routed to experts this chip does not hold are
not work. The shared experts are plain XLA matmuls and are not in here.

**Flash attention** at a q.k width ``dqk = qk_nope + qk_rope`` and a value
width ``dv``, causal, so half of the ``S x S`` pairs are needed (the
diagonal's tiles compute more; that is the kernel's cost, not the need). A
head forward: ``q k^T`` and ``p v``. The two backward kernels recompute the
scores, as flash attention is defined to: ``harmony_flash_bwd_dkv`` does
``q k^T``, ``dO v^T``, ``p^T dO`` and ``ds^T q``; ``harmony_flash_bwd_dq``
does ``q k^T``, ``dO v^T`` and ``ds k``. Each product over a pair costs 2 x
its contracted or produced width. Recomputation by ``jax.checkpoint`` would
count nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

#: grouped matmuls of one expert layer, by the kernel that runs them
CALLS_PER_LAYER = {"harmony_gmm_fwd": 3, "harmony_gmm_dx": 3,
                   "harmony_gmm_dw": 3}
#: widths each flash kernel's products contract or produce, in units of
#: (dqk, dv): forward q k^T + p v; dK/dV: q k^T, ds^T q + dO v^T, p^T dO;
#: dQ: q k^T, ds k + dO v^T
FLASH_PRODUCTS = {"harmony_flash_fwd": (1, 1), "harmony_flash_bwd_dkv": (2, 2),
                  "harmony_flash_bwd_dq": (2, 1)}


def moe_layers(app: Dict[str, Any]) -> int:
    """Expert layers in the model, as the program counts them."""
    from harmony_tpu.models.transformer import TransformerConfig

    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    return len(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}).moe_layers())


def slots_per_step(app: Dict[str, Any], batch: int) -> int:
    """Token-slots ONE expert layer routes a step: tokens x top-k."""
    return int(batch) * int(app["max_seq"]) * int(app["moe_top_k"])


def gmm_flops_per_call(app: Dict[str, Any], held_rows: float) -> float:
    """FLOPs one call of any of the three grouped-matmul kernels needs: the
    rows of one layer routed to held experts times ``2 * d_model * d_ff``."""
    return 2.0 * float(held_rows) * int(app["d_model"]) * int(app["d_ff"])


def gmm_flops_per_step(app: Dict[str, Any], held_rows: float) -> float:
    """All nine grouped matmuls of every expert layer, forward and backward,
    at ``held_rows`` a layer."""
    return (gmm_flops_per_call(app, held_rows)
            * sum(CALLS_PER_LAYER.values()) * moe_layers(app))


def flash_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` needs over ``batch`` sequences: heads x
    the causal half of ``S^2`` pairs x 2 x the widths of its products."""
    n_qk, n_v = FLASH_PRODUCTS[kernel]
    dqk = int(app["qk_nope_head_dim"]) + int(app["qk_rope_head_dim"])
    pairs = int(batch) * int(app["n_heads"]) * int(app["max_seq"]) ** 2 / 2.0
    return 2.0 * pairs * (n_qk * dqk + n_v * int(app["v_head_dim"]))
