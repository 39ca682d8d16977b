"""Operations and bytes a training step of Laguna-S-2.1 NEEDS, computed from
the configuration's shapes — the yardstick's own arithmetic for
``step_mfu_share`` (``train_flops_per_token``, named by the configuration's
``job.flops_fn`` as ``"laguna:..."``) and for ``hetero_flash_roofline_share``
(perf/layer_metrics/). The grouped matmuls of this configuration are counted
by ``perf/work/olmoe.py`` / ``perf/work/moonlight.py`` (same kernels, same
field names).

**The model** (``perf/reference/laguna-s-2.1.py``): softmax blocks of two
kinds, ``full`` and ``swa`` (``window_layers``), that differ in their QUERY
heads (``kind_heads``) over the same ``n_kv_heads`` K/V heads of
``mha_head_dim`` columns; a sigmoid gate a head on the attention output
(``attn_gate="head"``: one ``d x H_K`` projection a block); ``moe_first_dense``
leading blocks with a dense MLP of ``dense_d_ff`` columns, every other block a
dropless expert layer — a ``d x E`` router at its full width, ``moe_top_k`` of
``moe_experts`` experts of ``d_ff`` columns of which ``moe_experts_held`` live
here, and one shared expert of ``moe_shared_d_ff`` columns on every token.
Everything is the chip's HELD share, as the configuration states it.

**FLOPs a token of the CORPUS** (``perf/work_models.py``'s contract; a
multiply-add is 2 FLOPs, forward 2, backward 4; recomputation — ``remat``,
the flash kernels' scores — counts nothing):

  dense            6 x sum over blocks of (attention's ``d (H_K hd + 2 Hkv
                   hd) + H_K hd d``, the gate's ``d H_K``, and the dense MLP's
                   ``3 d dense_d_ff`` or the router's ``d E`` + the shared
                   expert's ``3 d moe_shared_d_ff``)
  routed           6 x expert blocks x ``top_k x held / experts`` x ``3 d f``
                   (uniform routing: the held share of the slots)
  attention_pairs  3 x sum over blocks of ``H_K x 2 (hd + hd) x pairs_K / S``:
                   ``pairs_full = S (S + 1) / 2`` (the triangle),
                   ``pairs_swa = W (W + 1) / 2 + (S - W) W`` (the band ``0 <=
                   i - j < W``), a head and sequence
  scans            nothing: no layer scans
  readout          6 x ``d V``

Embedding lookups, the norms, both rotaries (YaRN's frequencies are
constants), the gate's sigmoid and product, the optimizer and the table path
count nothing: no matmul.

**The flash kernels** as THIS tree names them (``ops/attention.py``; PERF.md
section 3): ``harmony_flash_fwd`` / ``harmony_flash_bwd`` in the ``full``
blocks, ``harmony_flash_win_fwd`` / ``harmony_flash_win_bwd`` in the ``swa``
ones — one forward and ONE backward a call since PR 50. FLOPs a pair, 2 x
the width each product contracts or produces, heads of ``hd`` (q, k and v
alike): the forward ``q k^T`` and ``p v`` = ``4 hd``; the fused backward's
FIVE products ``q k^T``, ``dO v^T``, ``p^T dO``, ``ds^T q`` and ``ds k`` = ``10
hd`` (PERF.md section 7's count; its one recomputed score tile counts as the
call's work, as flash attention is defined to). A kernel is credited by ITS
kind's head count and mask: ``H_full`` heads x the triangle, ``H_swa`` heads x
the band. **Bytes a call must move** (``perf/work/smallthinker.py``'s rule:
every operand row the call touches once, whatever the tiling re-reads): the
``H_K`` query heads' q and o, the ``Hkv`` K/V heads' k and v, the log-sum-exp a
row in float32; the backward q, dO, k, v, two statistics a row in and dq, dk,
dv out. The bound of a call is the larger of FLOPs / bf16 peak and bytes / HBM
peak (``perf/peaks.json``): at 16,384 positions a windowed forward at 512 keys
needs ~450 FLOPs a byte against the chip's 240 and its backward ~730 — the
MXU binds every call, the windowed ones by under twice and three times; the
full-causal calls need ~7,000 and ~11,000.
"""
from __future__ import annotations

from typing import Any, Dict

from perf.run import load_by_path

_flash = load_by_path("work", "smallthinker")

FLOAT32 = 4
#: kernel name in a device trace -> (forward or the fused backward, the kind
#: of block that calls it)
KERNELS = {
    "harmony_flash_fwd": ("fwd", "full"),
    "harmony_flash_bwd": ("bwd", "full"),
    "harmony_flash_win_fwd": ("fwd", "swa"),
    "harmony_flash_win_bwd": ("bwd", "swa"),
}
#: products of head width a pair costs, by kernel (module docstring)
PRODUCTS = {"fwd": 2, "bwd": 5}
#: every key of ``app_params`` the count has a rule for (most: "counts
#: nothing"); another raises
KNOWN = frozenset((
    "vocab_size", "d_model", "n_heads", "n_kv_heads", "mha_head_dim",
    "n_layers", "d_ff", "max_seq", "pos", "ffn", "tie_embeddings", "norm_eps",
    "window", "window_layers", "kind_heads", "kind_rope", "attn_gate",
    "moe_experts", "moe_top_k", "moe_every", "moe_experts_held",
    "moe_norm_topk", "moe_routed_scale", "moe_shared_experts",
    "moe_shared_d_ff", "moe_first_dense", "dense_d_ff", "moe_aux_weight",
    "moe_z_weight", "embed_std", "remat", "attn", "dtype", "optimizer",
    "step_size", "beta2", "seed"))


#: pairs a head and sequence needs: the triangle, and the band ``0 <= i - j <
#: w`` (``perf/work/smallthinker.py``'s, the same masks)
causal_pairs, band_pairs = _flash.causal_pairs, _flash.band_pairs


def _checked(app: Dict[str, Any]) -> None:
    """This file counts next-token training of gated softmax blocks of two
    kinds, a leading dense MLP and expert layers in every other block, and
    nothing else."""
    unknown = sorted(set(app) - KNOWN)
    if unknown or app.get("ffn") != "swiglu" or app.get("attn_gate") != "head" \
            or int(app.get("moe_every", 2)) != 1 \
            or not int(app.get("moe_top_k", 0)) \
            or int(app.get("moe_shared_experts", 0)) != 1 \
            or not app.get("window_layers") \
            or set(app.get("kind_heads") or {}) - {"full", "swa"}:
        raise ValueError(
            f"not counted here: unknown keys {unknown}, ffn "
            f"{app.get('ffn')!r}, attn_gate {app.get('attn_gate')!r}, "
            f"moe_every {app.get('moe_every')!r}, moe_top_k "
            f"{app.get('moe_top_k')!r}, moe_shared_experts "
            f"{app.get('moe_shared_experts')!r}, window_layers "
            f"{app.get('window_layers')!r}, kind_heads "
            f"{app.get('kind_heads')!r}")


def kinds(app: Dict[str, Any]):
    """Each block's kind, ``"swa"`` for those in ``window_layers``."""
    swa = {int(i) for i in app["window_layers"]}
    return tuple("swa" if i in swa else "full"
                 for i in range(int(app["n_layers"])))


def heads(app: Dict[str, Any], kind: str) -> int:
    """The query heads of a block of ``kind``."""
    return int((app.get("kind_heads") or {}).get(kind, app["n_heads"]))


def _shape(app: Dict[str, Any]):
    """``(Hkv, hd, S)``."""
    h = int(app["n_heads"])
    return (int(app.get("n_kv_heads") or h),
            int(app.get("mha_head_dim") or int(app["d_model"]) // h),
            int(app["max_seq"]))


def pairs_per_head(app: Dict[str, Any], kind: str) -> int:
    s = _shape(app)[2]
    return band_pairs(s, app["window"]) if kind == "swa" else causal_pairs(s)


def train_flops_split(app: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one token of the corpus needs forward + backward, by
    ``perf/work_models.py`` ``PARTS`` (module docstring)."""
    _checked(app)
    hkv, hd, s = _shape(app)
    d, f = int(app["d_model"]), int(app["d_ff"])
    experts, top_k = int(app["moe_experts"]), int(app["moe_top_k"])
    held = app.get("moe_experts_held")
    held = experts if held is None else int(held)
    first = int(app.get("moe_first_dense", 0))
    f_dense = int(app.get("dense_d_ff") or f)
    f_shared = int(app.get("moe_shared_d_ff") or f)
    dense = pairs = 0.0
    for i, kind in enumerate(kinds(app)):
        h = heads(app, kind)
        dense += d * (h * hd + 2 * hkv * hd) + h * hd * d + d * h
        dense += (3 * d * f_dense if i < first
                  else d * experts + 3 * d * f_shared)
        pairs += h * 2 * (hd + hd) * pairs_per_head(app, kind) / s
    expert_blocks = int(app["n_layers"]) - first
    return {
        "dense": 6.0 * dense,
        "routed": 6.0 * expert_blocks * (top_k * held / experts * 3 * d * f),
        "attention_pairs": 3.0 * pairs,
        "scans": 0.0,
        "readout": 6.0 * d * int(app["vocab_size"]),
    }


def train_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of the corpus needs
    (perf/tests/test_laguna.py ``HAND`` holds the configuration to a count
    by hand)."""
    return float(sum(train_flops_split(app).values()))


def flash_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one call of ``kernel`` (a trace name of ``KERNELS``) needs over
    ``batch`` sequences: its kind's query heads x the pairs its kind's mask
    needs x 2 x the widths of its products."""
    which, kind = KERNELS[kernel]
    hd = _shape(app)[1]
    return (2.0 * PRODUCTS[which] * hd * int(batch) * heads(app, kind)
            * pairs_per_head(app, kind))


def flash_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one call of ``kernel`` must move over ``batch`` sequences
    (module docstring; a window removes no operand row)."""
    which, kind = KERNELS[kernel]
    hkv, hd, s = _shape(app)
    h = heads(app, kind)
    act = 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4
    q_rows, kv_rows = h * s * hd * act, hkv * s * hd * act
    stat = h * s * FLOAT32
    per_seq = {
        "fwd": q_rows + 2 * kv_rows + q_rows + stat,          # q, k, v | o, lse
        "bwd": 2 * q_rows + 2 * kv_rows + 2 * stat            # q, dO, k, v,
        + q_rows + 2 * kv_rows,                               # lse, delta |
    }[which]                                                  # dq, dk, dv
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
