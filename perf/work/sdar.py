"""Operations and bytes a block-diffusion step of SDAR NEEDS, computed from
the configuration's shapes — the yardstick's own arithmetic for
``step_mfu_share`` (``block_diffusion_flops_per_token``, named by the
configuration's ``job.flops_fn`` as ``"sdar:..."``) and for
``bd_flash_roofline_share`` (perf/layer_metrics/). The grouped matmuls of
this configuration are counted by ``perf/work/olmoe.py`` /
``perf/work/moonlight.py`` (same kernels, same field names).

**The step** (SDAR, arXiv:2510.06303; BD3-LMs, arXiv:2503.09573). A sequence
of ``L`` tokens in blocks of ``B`` goes through every layer TWICE, a clean
copy and a noised one; a clean query at position ``p`` sees the clean keys
``j`` with ``j // B <= p // B``, a noisy query the clean keys with ``j // B
< p // B`` and the noisy keys of its own block. A head and sequence that is
``L^2 / 2 + L B / 2`` clean pairs, ``L^2 / 2 - L B / 2`` noisy-to-clean pairs
and ``L B`` own-block pairs: ``L^2 + L B``, exactly. The readout takes the
noisy rows only: ONE readout a token of the corpus.

**FLOPs a token of the CORPUS** (``perf/work_models.py``'s contract; a
multiply-add is 2 FLOPs, forward 2, backward 4; recomputation — ``remat``,
the flash kernels' scores — counts nothing):

  dense            6 x 2 streams x layers x (attention's ``d (H hd + 2 Hkv
                   hd) + H hd d`` + the router's ``d E`` at its full width)
  routed           6 x 2 x layers x ``top_k x held / experts`` x ``3 d f``
                   (uniform routing: the held share of the slots)
  attention_pairs  3 x layers x H x ``2 (hd + hd)`` x ``(L + B)``
  scans            nothing: no layer scans
  readout          6 x ``d V``, once

Embedding lookups, the norms (the per-head ones too), the rotary, the mask
token's ``where`` and the optimizer count nothing: no matmul.

**The flash kernels** (``harmony_flash_bd_fwd`` / ``_bwd_dkv`` / ``_bwd_dq``):
ONE call a layer, both streams' queries stacked (``2 L`` rows a query head)
against the clean keys and values (``L`` rows a K/V head). The pairs such a
call NEEDS are the ``L^2`` a head and sequence above (the own-block ``L B``
are plain XLA, under ``blk*/mixer.streams``); FLOPs a pair by kernel and the
bytes rule are ``perf/work/smallthinker.py``'s (2 x the width each product
contracts or produces: 4 hd forward, 8 hd dK/dV, 6 hd dQ; every operand row
the call touches once: the ``H`` query heads' ``2 L`` rows of q, o, dO and dq,
the ``Hkv`` K/V heads' ``L`` rows of k, v, dk and dv, the statistics a row in
float32). At 8,192 positions the forward needs ~3,400 FLOPs a byte against
the chip's 240: the MXU binds every call.
"""
from __future__ import annotations

from typing import Any, Dict

from perf.run import load_by_path

_flash = load_by_path("work", "smallthinker")

FLOAT32 = 4
#: kernel name in a device trace -> which of the three kernels
KERNELS = {"harmony_flash_bd_fwd": "fwd", "harmony_flash_bd_bwd_dkv": "dkv",
           "harmony_flash_bd_bwd_dq": "dq"}
PRODUCTS = _flash.PRODUCTS
#: every key of ``app_params`` the count has a rule for (most: "counts
#: nothing"); another raises
KNOWN = frozenset((
    "vocab_size", "d_model", "n_heads", "n_kv_heads", "mha_head_dim",
    "n_layers", "d_ff", "max_seq", "pos", "rope_theta", "ffn",
    "tie_embeddings", "norm_eps", "head_norm", "objective",
    "diffusion_block", "mask_token", "moe_experts", "moe_top_k", "moe_every",
    "moe_experts_held", "moe_norm_topk", "moe_aux_weight", "moe_z_weight",
    "embed_std", "remat", "attn", "dtype", "optimizer", "step_size", "beta2",
    "seed"))


def _shape(app: Dict[str, Any]):
    """``(H, Hkv, hd, L, B)``, after the checks: this file counts the
    block-diffusion step of softmax-attention blocks that are every one an
    expert layer, and nothing else."""
    unknown = sorted(set(app) - KNOWN)
    if unknown or app.get("objective") != "block_diffusion" \
            or app.get("ffn") != "swiglu" or int(app.get("moe_every", 2)) != 1 \
            or not int(app.get("moe_top_k", 0)) \
            or str(app.get("pos")) != "rope":
        raise ValueError(
            f"not counted here: unknown keys {unknown}, objective "
            f"{app.get('objective')!r}, ffn {app.get('ffn')!r}, moe_every "
            f"{app.get('moe_every')!r}, moe_top_k {app.get('moe_top_k')!r}")
    h = int(app["n_heads"])
    L, B = int(app["max_seq"]), int(app["diffusion_block"])
    if B < 1 or L % B:
        raise ValueError(f"diffusion_block {B} does not divide max_seq {L}")
    return (h, int(app.get("n_kv_heads") or h),
            int(app.get("mha_head_dim") or int(app["d_model"]) // h), L, B)


def block_diffusion_flops_split(app: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one token of the corpus needs forward + backward, by
    ``perf/work_models.py`` ``PARTS`` (module docstring)."""
    h, hkv, hd, L, B = _shape(app)
    d, f, n = int(app["d_model"]), int(app["d_ff"]), int(app["n_layers"])
    experts, top_k = int(app["moe_experts"]), int(app["moe_top_k"])
    held = app.get("moe_experts_held")
    held = experts if held is None else int(held)
    attention = d * (h * hd + 2 * hkv * hd) + h * hd * d
    return {
        "dense": 6.0 * 2 * n * (attention + d * experts),
        "routed": 6.0 * 2 * n * (top_k * held / experts * 3 * d * f),
        "attention_pairs": 3.0 * n * h * 2 * (hd + hd) * (L + B),
        "scans": 0.0,
        "readout": 6.0 * d * int(app["vocab_size"]),
    }


def block_diffusion_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of the corpus needs
    (2,989,817,856.0 for ``sdar-30b-a3b``: perf/tests/test_sdar.py ``HAND``)."""
    return float(sum(block_diffusion_flops_split(app).values()))


def pairs_per_head(app: Dict[str, Any]) -> int:
    """Pairs a head and sequence of ONE stacked kernel call needs: ``L^2``
    (clean ``L^2 / 2 + L B / 2``, noisy-to-clean ``L^2 / 2 - L B / 2``)."""
    L = _shape(app)[3]
    return L * L


def flash_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` (a trace name of ``KERNELS``) needs over
    ``batch`` sequences."""
    h, _, hd, _, _ = _shape(app)
    return (2.0 * PRODUCTS[KERNELS[kernel]] * hd * int(batch) * h
            * pairs_per_head(app))


def flash_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one call of ``kernel`` must move over ``batch`` sequences."""
    h, hkv, hd, L, _ = _shape(app)
    act = 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4
    q_rows, kv_rows = h * 2 * L * hd * act, hkv * L * hd * act
    stat = h * 2 * L * FLOAT32
    per_seq = {
        "fwd": q_rows + 2 * kv_rows + q_rows + stat,          # q, k, v | o, lse
        "dq": 2 * q_rows + 2 * kv_rows + 2 * stat + q_rows,   # q, dO, k, v,
                                                              # lse, delta | dq
        "dkv": 2 * q_rows + 2 * kv_rows + 2 * stat + 2 * kv_rows,  # | dk, dv
    }[KERNELS[kernel]]
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
