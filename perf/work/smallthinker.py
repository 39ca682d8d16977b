"""Operations and bytes the flash attention calls of a SmallThinker step NEED,
computed from the configuration's shapes — the yardstick's own arithmetic
for ``swa_flash_roofline_share`` (perf/layer_metrics/). The grouped matmuls
of this configuration are counted by ``perf/work/moonlight.py`` (same
kernels, same field names).

**Pairs by layer kind.** A block of kind ``full`` (``TransformerConfig.
layer_kinds()``) attends causally: row ``i`` of ``S`` needs keys ``0 .. i``,
``S (S + 1) / 2`` pairs a head. A block of kind ``swa`` needs the band ``0 <=
i - j < W``: ``W (W + 1) / 2`` pairs for the first ``W`` rows and ``W`` for
each of the other ``S - W`` (``W >= S``: the triangle). The tiles the band's
edges cross compute more than that and mask it away; that is the kernel's
cost, not the need (``flash_masked_share`` reads it).

**FLOPs a pair**, 2 x the width each product contracts or produces, heads of
``hd`` (q, k and v alike): the forward ``q k^T`` and ``p v`` = ``4 hd``; the
two backward kernels recompute the scores, as flash attention is defined to:
``*_bwd_dkv`` does ``q k^T``, ``dO v^T``, ``p^T dO`` and ``ds^T q`` = ``8 hd``;
``*_bwd_dq`` does ``q k^T``, ``dO v^T`` and ``ds k`` = ``6 hd``. Recomputation
by ``jax.checkpoint`` counts as calls (each call needs its work).

**Bytes a call must move** to and from HBM — BAND bytes: every operand row
the band touches, once, whatever the tiling re-reads. Every K/V row is some
row's key, so a window removes none of them: per call, the ``H`` query heads'
``q``, the ``Hkv`` K/V heads' ``k`` and ``v`` (grouped queries: once a K/V
head, not once a query head) and ``o`` in the activations' dtype, the
log-sum-exp a row in float32; the backward kernels also ``dO``, ``delta`` a
row, and their outputs (``dq``; ``dk`` and ``dv`` once a K/V head). (The
kernels as built keep the two row statistics lane-replicated, 512 bytes a row
where the need is 4: more than the need.)

The bound of a call is the larger of FLOPs / bf16 peak and bytes / HBM peak
(``perf/peaks.json``); at 16,384 positions a windowed forward needs ~1,500
FLOPs a byte against the chip's 240, so the MXU binds every call here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

FLOAT32 = 4
#: kernel name in a device trace -> (which of the three kernels, windowed)
KERNELS = {
    "harmony_flash_fwd": ("fwd", False),
    "harmony_flash_bwd_dkv": ("dkv", False),
    "harmony_flash_bwd_dq": ("dq", False),
    "harmony_flash_win_fwd": ("fwd", True),
    "harmony_flash_win_bwd_dkv": ("dkv", True),
    "harmony_flash_win_bwd_dq": ("dq", True),
}
#: products of head width a pair costs, by kernel (module docstring)
PRODUCTS = {"fwd": 2, "dkv": 4, "dq": 3}


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def band_pairs(s: int, w: int) -> int:
    """Pairs ``(i, j)`` with ``0 <= i - j < w`` among ``s`` positions."""
    w = min(int(w), int(s))
    return w * (w + 1) // 2 + (s - w) * w


def layer_kinds(app: Dict[str, Any]) -> Tuple[str, ...]:
    """Each block's kind, as the program counts them."""
    from harmony_tpu.models.transformer import TransformerConfig

    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    return TransformerConfig(
        **{k: v for k, v in app.items() if k in names}).layer_kinds()


def _shape(app: Dict[str, Any]):
    h = int(app["n_heads"])
    return (h, int(app.get("n_kv_heads") or h),
            int(app.get("mha_head_dim") or app["d_model"] // h),
            int(app["max_seq"]))


def pairs_per_head(app: Dict[str, Any], windowed: bool) -> int:
    s = _shape(app)[3]
    return band_pairs(s, app["window"]) if windowed else causal_pairs(s)


def flash_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` (a trace name of ``KERNELS``) needs over
    ``batch`` sequences: query heads x the pairs its layer kind needs x 2 x
    the widths of its products."""
    kind, windowed = KERNELS[kernel]
    h, _, hd, _ = _shape(app)
    return (2.0 * PRODUCTS[kind] * hd * int(batch) * h
            * pairs_per_head(app, windowed))


def flash_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one call of ``kernel`` must move over ``batch`` sequences
    (module docstring; the same with and without a window)."""
    kind, _ = KERNELS[kernel]
    h, hkv, hd, s = _shape(app)
    act = 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4
    q_rows, kv_rows = h * s * hd * act, hkv * s * hd * act
    stat = h * s * FLOAT32
    per_seq = {
        "fwd": q_rows + 2 * kv_rows + q_rows + stat,          # q, k, v | o, lse
        "dq": 2 * q_rows + 2 * kv_rows + 2 * stat + q_rows,   # q, dO, k, v,
                                                              # lse, delta | dq
        "dkv": 2 * q_rows + 2 * kv_rows + 2 * stat + 2 * kv_rows,  # | dk, dv
    }[kind]
    return float(int(batch) * per_seq)


def bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                  peaks: Dict[str, float]) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}`` of one call: the larger of its
    compute time at the bf16 peak and its traffic time at the HBM peak."""
    flops = flash_flops_per_call(app, batch, kernel)
    nbytes = flash_bytes_per_call(app, batch, kernel)
    t_mxu, t_hbm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds_bound": max(t_mxu, t_hbm),
            "binds": "bf16 MXU peak" if t_mxu >= t_hbm else "HBM peak"}
