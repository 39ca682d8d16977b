"""Operations and bytes a training step of Qwen3-Next NEEDS, computed from the
configuration's shapes — the yardstick's own arithmetic for ``step_mfu_share``
(``train_flops_per_token``, named by the configuration's ``job.flops_fn`` as
``"qwen3_next:..."``), for ``hetero_flash_roofline_share`` (``KERNELS`` /
``bound_seconds`` / ``heads``) and for ``gdn_roofline_share``
(``GDN_KERNELS`` / ``gdn_bound_seconds``). The grouped matmuls of this
configuration are counted by ``perf/work/olmoe.py`` / ``perf/work/
moonlight.py`` (same kernels, same field names).

**The model** (``perf/reference/qwen3-next-80b-a3b.py``): the blocks in
``linear_layers`` mix by Gated DeltaNet — ONE projection ``d x (2 Hk dh + 2 Hv
dh)``, the scalars' ``d x 2 Hv``, out ``Hv dh x d`` — every other block by
gated softmax attention (``attn_gate="element"``: the q block twice as wide,
``d x (2 H hd + 2 Hkv hd)``, out ``H hd x d``); every block a dropless expert
layer: a ``d x E`` router at its full width, ``moe_top_k`` of ``moe_experts``
experts of ``d_ff`` columns of which ``moe_experts_held`` live here, and one
shared expert on every token. Everything is the chip's HELD share.

**FLOPs a token of the CORPUS** (``perf/work_models.py``'s contract; a
multiply-add is 2 FLOPs, forward 2, backward 4; recomputation counts
nothing):

  dense            6 x sum over blocks of (the mixer's matrices above, the
                   router's ``d E`` and the shared expert's ``3 d f_shared``)
  routed           6 x blocks x ``top_k x held / experts`` x ``3 d f``
  attention_pairs  3 x softmax blocks x ``H x 2 (hd + hd) x (S + 1) / 2``
  scans            3 x delta-rule blocks x ``Hv`` x the chunk's products a
                   position, ``(C^2 (3 dk + 2 dv) + 6 C dk dv) / C``
                   (``perf/work/kimi_linear.py``'s count of the chunked rule,
                   asked there: the algorithm's products, whatever forms the
                   pair matrices)
  readout          6 x ``d V``

Embedding lookups, the norms, the rotary, the convolution (4 taps a column),
the gates' sigmoids and products, the shared expert's ``d``-wide gate (a
vector product a token), the optimizer and the table path count nothing: no
matmul.

**The flash kernels** (``ops/attention.py``): ``harmony_flash_fwd`` and the
fused ``harmony_flash_bwd``, ``H`` heads x the triangle; forward two products
of head width a pair, backward five (``perf/work/laguna.py``'s rule, asked
there for the pairs). Bytes: every operand row a call touches once.

**The delta-rule kernels, the SCALAR need** whichever kernel runs
(``harmony_gdn_*``, or ``harmony_kda_*`` fed a broadcast decay): FLOPs as
``scans`` above (a backward CALL 3 x the forward: the kernel recomputes its
chunk); bytes: q and k ONCE a KEY head, v and o a value head, ``g`` and
``beta`` float32 ``[S]`` a value head, the ``S / C`` boundary states float32
``dk x dv`` a value head; the backward reads all of that and ``dO`` and writes
dq, dk (a key head), dv, dg, dbeta. At 32 value heads of 128 over 8,192
positions a forward call needs ~0.6 ms at the HBM peak: HBM binds.
"""
from __future__ import annotations

from typing import Any, Dict

from perf.run import load_by_path

_flash = load_by_path("work", "smallthinker")
_kda = load_by_path("work", "kimi_linear")

FLOAT32 = 4
CHUNK = _kda.CHUNK
#: kernel name in a device trace -> (forward or the fused backward, the kind
#: of block that calls it) — ``hetero_flash_roofline_share``'s table
KERNELS = {"harmony_flash_fwd": ("fwd", "full"),
           "harmony_flash_bwd": ("bwd", "full")}
PRODUCTS = {"fwd": 2, "bwd": 5}
#: the delta-rule kernels a trace may name -> forward or backward: the scalar
#: route's own, and the channel route's where that is what runs
GDN_KERNELS = {"harmony_gdn_fwd": "fwd", "harmony_gdn_bwd": "bwd",
               "harmony_kda_fwd": "fwd", "harmony_kda_bwd": "bwd"}
#: every key of ``app_params`` the count has a rule for; another raises
KNOWN = frozenset((
    "vocab_size", "d_model", "n_heads", "n_kv_heads", "mha_head_dim",
    "n_layers", "d_ff", "max_seq", "pos", "rope_theta", "rope_fraction",
    "ffn", "tie_embeddings", "norm_eps", "head_norm", "attn_gate",
    "norm_offset", "linear_layers", "linear_kind", "linear_heads",
    "linear_value_heads", "linear_head_dim", "short_conv", "moe_experts",
    "moe_top_k", "moe_every", "moe_experts_held", "moe_norm_topk",
    "moe_shared_experts", "moe_shared_d_ff", "moe_shared_gate",
    "moe_aux_weight", "moe_z_weight", "embed_std", "remat", "attn", "dtype",
    "optimizer", "step_size", "beta2", "seed"))

causal_pairs = _flash.causal_pairs


def _checked(app: Dict[str, Any]) -> None:
    """This file counts next-token training of Gated DeltaNet blocks beside
    element-gated softmax blocks, experts in every block, and nothing else."""
    unknown = sorted(set(app) - KNOWN)
    if unknown or app.get("ffn") != "swiglu" \
            or app.get("attn_gate") != "element" \
            or app.get("linear_kind") != "gdn" \
            or int(app.get("moe_every", 2)) != 1 \
            or not int(app.get("moe_top_k", 0)) \
            or int(app.get("moe_shared_experts", 0)) != 1:
        raise ValueError(
            f"not counted here: unknown keys {unknown}, ffn "
            f"{app.get('ffn')!r}, attn_gate {app.get('attn_gate')!r}, "
            f"linear_kind {app.get('linear_kind')!r}, moe_every "
            f"{app.get('moe_every')!r}, moe_top_k {app.get('moe_top_k')!r}, "
            f"moe_shared_experts {app.get('moe_shared_experts')!r}")


def kinds(app: Dict[str, Any]):
    """Each block's kind: ``"gdn"`` for those in ``linear_layers``, else
    ``"full"`` (softmax over the whole causal past)."""
    linear = {int(i) for i in app["linear_layers"]}
    return tuple("gdn" if i in linear else "full"
                 for i in range(int(app["n_layers"])))


def heads(app: Dict[str, Any], kind: str) -> int:
    """The heads of a block of ``kind``: a softmax block's query heads, a
    delta-rule block's VALUE heads."""
    if kind == "gdn":
        return int(app.get("linear_value_heads") or app["linear_heads"])
    return int(app["n_heads"])


def _shape(app: Dict[str, Any]):
    """``(Hkv, hd, S)`` of the softmax blocks."""
    h = int(app["n_heads"])
    return (int(app.get("n_kv_heads") or h),
            int(app.get("mha_head_dim") or int(app["d_model"]) // h),
            int(app["max_seq"]))


def _as_kda(app: Dict[str, Any]) -> Dict[str, Any]:
    """What ``perf/work/kimi_linear.py`` reads, with a head a VALUE head."""
    return {"linear_head_dim": app["linear_head_dim"],
            "linear_heads": heads(app, "gdn"), "max_seq": app["max_seq"]}


def train_flops_split(app: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one token of the corpus needs forward + backward, by
    ``perf/work_models.py`` ``PARTS`` (module docstring)."""
    _checked(app)
    hkv, hd, s = _shape(app)
    d, f = int(app["d_model"]), int(app["d_ff"])
    experts, top_k = int(app["moe_experts"]), int(app["moe_top_k"])
    held = app.get("moe_experts_held")
    held = experts if held is None else int(held)
    f_shared = int(app.get("moe_shared_d_ff") or f)
    dh, hk = int(app["linear_head_dim"]), int(app["linear_heads"])
    hv, h = heads(app, "gdn"), heads(app, "full")
    dense = pairs = scans = 0.0
    scan_layer = _kda.kda_flops_per_call(_as_kda(app), 1, "harmony_kda_fwd") / s
    for kind in kinds(app):
        if kind == "gdn":
            dense += d * (2 * hk * dh + 2 * hv * dh) + d * 2 * hv + hv * dh * d
            scans += scan_layer
        else:
            dense += d * (2 * h * hd + 2 * hkv * hd) + h * hd * d
            pairs += h * 2 * (hd + hd) * causal_pairs(s) / s
        dense += d * experts + 3 * d * f_shared
    return {
        "dense": 6.0 * dense,
        "routed": 6.0 * int(app["n_layers"]) * (
            top_k * held / experts * 3 * d * f),
        "attention_pairs": 3.0 * pairs,
        "scans": 3.0 * scans,
        "readout": 6.0 * d * int(app["vocab_size"]),
    }


def train_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of the corpus needs
    (perf/tests/test_qwen3_next.py ``HAND`` holds the configuration to a
    count by hand)."""
    return float(sum(train_flops_split(app).values()))


def _bound(flops: float, nbytes: float, peaks: Dict[str, float]):
    t_mxu, t_hbm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds_bound": max(t_mxu, t_hbm),
            "binds": "bf16 MXU peak" if t_mxu >= t_hbm else "HBM peak"}


def _act(app: Dict[str, Any]) -> int:
    return 2 if str(app.get("dtype", "float32")) == "bfloat16" else 4


def flash_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    which, kind = KERNELS[kernel]
    _, hd, s = _shape(app)
    return (2.0 * PRODUCTS[which] * hd * int(batch) * heads(app, kind)
            * causal_pairs(s))


def flash_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    which, kind = KERNELS[kernel]
    hkv, hd, s = _shape(app)
    q_rows = heads(app, kind) * s * hd * _act(app)
    kv_rows = hkv * s * hd * _act(app)
    stat = heads(app, kind) * s * FLOAT32
    per_seq = {
        "fwd": q_rows + 2 * kv_rows + q_rows + stat,          # q, k, v | o, lse
        "bwd": 2 * q_rows + 2 * kv_rows + 2 * stat            # q, dO, k, v,
        + q_rows + 2 * kv_rows,                               # lse, delta |
    }[which]                                                  # dq, dk, dv
    return float(int(batch) * per_seq)


def bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                  peaks: Dict[str, float]) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}`` of one flash call."""
    return _bound(flash_flops_per_call(app, batch, kernel),
                  flash_bytes_per_call(app, batch, kernel), peaks)


def gdn_flops_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """FLOPs one delta-rule call needs over ``batch`` sequences: every VALUE
    head's chunks (``perf/work/kimi_linear.py``'s count a chunk)."""
    return _kda.kda_flops_per_call(
        _as_kda(app), batch, "harmony_kda_" + GDN_KERNELS[kernel])


def gdn_bytes_per_call(app: Dict[str, Any], batch: int, kernel: str) -> float:
    """Bytes one delta-rule call must move under ONE scalar decay a head
    (module docstring), whichever kernel runs."""
    dh, s, act = int(app["linear_head_dim"]), int(app["max_seq"]), _act(app)
    hk, hv = int(app["linear_heads"]), heads(app, "gdn")
    qk = 2 * hk * s * dh * act
    v = o = hv * s * dh * act
    scalars = 2 * hv * s * FLOAT32                      # g and beta
    states = hv * -(-s // CHUNK) * dh * dh * FLOAT32
    if GDN_KERNELS[kernel] == "fwd":
        return int(batch) * float(qk + v + scalars + o + states)
    # backward: everything the forward read, its states and dO in; dq, dk,
    # dv, dg and dbeta out
    return int(batch) * float(2 * (qk + v + scalars) + o + states)


def gdn_bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                      peaks: Dict[str, float]) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}`` of one delta-rule call."""
    return _bound(gdn_flops_per_call(app, batch, kernel),
                  gdn_bytes_per_call(app, batch, kernel), peaks)
