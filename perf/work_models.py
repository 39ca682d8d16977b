"""Operations and bytes a step needs, computed from the configuration's
shapes — the yardstick's own arithmetic, found by the name a configuration
file gives under ``job.flops_fn`` / ``job.bytes_fn``. ``perf/run.py`` prints
each at the run's rate as a share of the chips' peak (``model_flops_
utilisation``, ``table_bandwidth``); ``step_mfu_share`` is the first, x 100.

**Where a count may live** (``resolve``, the one place a name is looked up:
the harness, ``perf/tests/test_perf.py`` and ``perf/tests/test_step_mfu.py``
all ask it). A bare name is a function of THIS file. ``"<sibling>:<function>"``
is a function of ``perf/work/<sibling>.py``, loaded as the harness loads every
sibling (``sibling``): a configuration whose step this file's one function
cannot count — two streams through every layer, a mask that is neither a
triangle nor a band, a mixer kind ``layer_kinds`` refuses — brings its own
count beside its other work functions, in a file a ``model_config`` PR may
add, and ``step_mfu_share`` stays one metric under one name. No key of a
configuration file, no flag and no environment variable says where: the name
does.

**What such a function owes** (``count`` and ``split`` hold every one to it;
``perf/tests/test_step_mfu.py`` holds every LM configuration to a hand count):

* it takes ``job.app_params`` and nothing of the program: no
  ``TransformerConfig``, no span, no counter;
* it returns the FLOPs (``bytes_fn``: the bytes) that ONE UNIT OF THE CELL'S
  RATE needs, forward and backward. A unit is what ``job.units_per_example``
  counts: for ``lm_tokens_per_s`` a token of the tenant's CORPUS, not a
  position the step happens to compute — a step that runs a noised and a clean
  copy of each sequence through the layers needs twice the layers' FLOPs a
  token and ONE readout, and says so in its count;
* recomputation counts nothing (``remat``, flash attention's scores, a chunked
  scan's chunks);
* a key it does not know raises: a model it cannot count reports no share,
  never a plausible one;
* a ``flops_fn`` comes with a TWIN in the same file that returns the same
  number split over ``PARTS`` (``dense``, ``routed``, ``attention_pairs``,
  ``scans``, ``readout``; none negative, ``dense`` and ``readout`` positive,
  the sum equal to the count). The twin's name is the count's with its last
  ``_per_<unit>`` replaced by ``_split`` (``lm_train_flops_per_token`` /
  ``lm_train_flops_split``), so the printed line ``model_flops_utilisation``
  and ``step_mfu_share`` are computed the same way for every cell.

A name that does not resolve, or a function that returns no positive finite
number, fails the cell's file check (``test_cell_resolves_to_files``) and
makes the run print ``work_model_failed`` and report NO share — never this
file's default in its place.

``lm_train_flops_per_token``: operations one token of a WHOLE training step
of the LM tenants NEEDS, from ``job.app_params`` alone. All LM configurations
are one model class told apart by keys of ``app_params``; this file reads
those keys and nothing of the program: no ``TransformerConfig``, no span, no
counter. A key it does not know (another ``attn_kind`` or ``ffn``, a new
``*_layers`` list, another letter of ``layer_pattern``) raises: a model it
cannot count reports no share, never a plausible one.

The rules (the ``choosing-metrics`` guide: "the operations the forward and
backward passes require, not counting recomputed operations"):

**6 x the matmul parameters a token passes through** (a multiply-add as 2
FLOPs; forward 2, backward 4), from the shapes:

  softmax attention   ``d (H hd + 2 Hkv hd) + H hd d`` — ``hd`` =
    ``mha_head_dim`` or ``d / n_heads``, ``Hkv`` = ``n_kv_heads`` or ``H``
  latent attention    ``d H (nope + rope) + d (r + rope) + r H (nope + v)
                      + H v d``
  KDA                 q, k, v ``3 d H dh``, the decay's and the output gate's
    low-rank pairs ``2 (d dh + dh H dh)``, beta ``d H``, out ``H dh d``
  Mamba-2             in ``d (2 H P + 2 G N + H)``, out ``H P d``
  dense MLP           ``3 d f`` gated (``ffn="swiglu"``), ``2 d f`` not; ``f``
    = ``dense_d_ff`` in the ``moe_first_dense`` leading layers
  expert layer        the router ``d E`` (its full width, every token), the
    latent's pair ``2 d r`` (``moe_latent``), the shared MLP at
    ``moe_shared_d_ff`` or ``moe_shared_experts`` x ``d_ff`` columns, and the
    ROUTED experts (below); ungated (``moe_gated`` false) 2 matrices for 3
  readout             ``d V``, the rows held (tied or not, the matmul is done)

Embedding lookups, norms, biases, the short convolutions, the optimizer and
the table path count nothing: they are no matmul, and the last two are not
the model.

**Routed experts at what this chip holds under UNIFORM routing**:
``moe_top_k x moe_experts_held / moe_experts`` expert passes a token — from the
configuration, not from the program's counters: the metric has to be there
whatever a PR does to the program's spans and counters. It is NOMINAL in the
expert cells by ``held token-slots counted / expected`` (the router as
initialised leans; ``expert_load_max_over_mean`` and the ``gmm`` rooflines
read the rows that were really held).

**Attention pairs a token really needs**: a causal layer ``s^2 / 2`` pairs a
head and sequence (the diagonal at half, the convention the GPT-2 count has
always had: 6 L s d), a windowed one the band ``0 <= i - j < W``
(``perf/work/smallthinker.py`` ``band_pairs``, asked there) with the diagonal
at half too, so ``W >= s`` counts what a full layer does. A pair costs
``2 (d_qk + d_v)`` forward and twice that backward: flash attention's
recomputed scores are the kernel's cost, not the need.

**The chunked scans** (KDA, Mamba-2) at the FORWARD call's FLOPs of
``perf/work/kimi_linear.py`` ``kda_flops_per_call`` / ``perf/work/
nemotron_h.py`` ``ssd_flops_per_call`` (asked there, not copied), x 3: two
backward products for each forward one. Those files credit a backward CALL
3 x the forward because the kernel recomputes its chunk; recomputation counts
nothing here, as ``remat`` counts nothing.
"""
from __future__ import annotations

import functools
import math
import re
import sys
from typing import Any, Callable, Dict, List

#: the parts ``lm_train_flops_split`` returns, FLOPs a token forward + backward
PARTS = ("dense", "routed", "attention_pairs", "scans", "readout")
#: what tells the LM configurations' layers apart, as far as this file knows
LAYER_LISTS = ("n_layers", "linear_layers", "window_layers")
ATTN_KINDS, FFNS = ("mha", "mla"), ("gelu", "swiglu")


@functools.lru_cache(maxsize=None)
def sibling(name: str):
    """``perf/work/<name>.py``, loaded as the harness loads it (once)."""
    from perf.run import load_by_path

    return load_by_path("work", name)


def resolve(name: str) -> Callable[[Dict[str, Any]], Any]:
    """The function a configuration names under ``job.flops_fn`` /
    ``job.bytes_fn``: ``"<function>"`` of this file, or
    ``"<sibling>:<function>"`` of ``perf/work/<sibling>.py`` (module
    docstring). Raises where the file or the function is not there."""
    where, colon, fn = str(name).rpartition(":")
    module = sibling(where) if colon else sys.modules[__name__]
    found = getattr(module, fn, None) if fn.isidentifier() else None
    if not callable(found):
        raise ValueError(f"work function {name!r}: no callable {fn!r} in "
                         f"{getattr(module, '__file__', module)}")
    return found


def count(job: Dict[str, Any], key: str = "flops_fn") -> float:
    """What ``job[key]``'s function counts for ``job["app_params"]``: a
    positive finite number, or an error (a count of nothing, ``None`` or
    ``nan`` is no count)."""
    value = resolve(job[key])(job["app_params"])
    if not isinstance(value, (int, float)) or not math.isfinite(value) \
            or value <= 0:
        raise ValueError(f"work function {job[key]!r} returned {value!r}, "
                         "not a positive finite number")
    return float(value)


def split(job: Dict[str, Any]) -> Dict[str, float]:
    """``count(job, "flops_fn")`` by ``PARTS``, from the count's twin (module
    docstring): exactly ``PARTS``, none negative, ``dense`` and ``readout``
    positive, summing to the count — or an error."""
    name = str(job["flops_fn"])
    twin, n = re.subn(r"_per_[a-z]+$", "_split", name)
    if not n:
        raise ValueError(f"work function {name!r} does not end in "
                         "_per_<unit>: its twin has no name")
    parts = resolve(twin)(job["app_params"])
    if tuple(parts) != PARTS or min(parts.values()) < 0 \
            or not (parts["dense"] > 0 and parts["readout"] > 0) \
            or float(sum(parts.values())) != count(job, "flops_fn"):
        raise ValueError(f"{twin!r} is not {name!r} split over {PARTS}: "
                         f"{parts!r}")
    return {k: float(v) for k, v in parts.items()}


def layer_kinds(app: Dict[str, Any]) -> List[Dict[str, str]]:
    """Each layer as ``{"mixer": kind or "", "ffn": kind or ""}`` from the
    configuration's keys: a ``layer_pattern`` layer is ONE sublayer (``M``
    ssd, ``*`` full attention, ``E`` experts); every other model's block is a
    mixer (``kda`` in ``linear_layers``, else ``mla`` / ``swa`` in
    ``window_layers`` / ``full``) and a feed-forward part (``moe`` past the
    ``moe_first_dense`` leading layers, the last of every ``moe_every``;
    else ``dense``)."""
    unknown = [k for k in app if k.endswith("_layers") and k not in LAYER_LISTS]
    attn, ffn = str(app.get("attn_kind", "mha")), str(app.get("ffn", "gelu"))
    if unknown or attn not in ATTN_KINDS or ffn not in FFNS:
        raise ValueError(f"not counted here: {unknown} attn_kind {attn!r} "
                         f"ffn {ffn!r}")
    n = int(app["n_layers"])
    pattern = str(app.get("layer_pattern") or "")
    if pattern:
        if len(pattern) != n or set(pattern) - set("ME*"):
            raise ValueError(f"layer_pattern {pattern!r} for {n} layers")
        return [{"M": {"mixer": "ssd", "ffn": ""},
                 "*": {"mixer": "full", "ffn": ""},
                 "E": {"mixer": "", "ffn": "moe"}}[c] for c in pattern]
    linear = {int(i) for i in app.get("linear_layers") or ()}
    window = {int(i) for i in app.get("window_layers") or ()}
    every = int(app.get("moe_every", 2))
    first = int(app.get("moe_first_dense", 0))
    out = []
    for i in range(n):
        if i in linear:
            mixer = "kda"
        elif attn == "mla":
            mixer = "mla"
        else:
            mixer = "swa" if i in window else "full"
        moe = (int(app.get("moe_experts", 0)) > 0 and i >= first
               and i % every == every - 1)
        out.append({"mixer": mixer, "ffn": "moe" if moe else "dense"})
    return out


def _heads(app: Dict[str, Any]):
    h = int(app["n_heads"])
    return (h, int(app.get("n_kv_heads") or h),
            int(app.get("mha_head_dim") or int(app["d_model"]) // h))


def mixer_params(app: Dict[str, Any], kind: str) -> int:
    """Matmul parameters a token passes through in one mixer of ``kind``."""
    d = int(app["d_model"])
    if kind in ("full", "swa"):
        h, hkv, hd = _heads(app)
        return d * (h * hd + 2 * hkv * hd) + h * hd * d
    if kind == "mla":
        h, r = int(app["n_heads"]), int(app["kv_lora_rank"])
        nope, rope = int(app["qk_nope_head_dim"]), int(app["qk_rope_head_dim"])
        v = int(app["v_head_dim"])
        return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
            + h * v * d
    if kind == "kda":
        h, dh = int(app["linear_heads"]), int(app["linear_head_dim"])
        return 3 * d * h * dh + 2 * (d * dh + dh * h * dh) + d * h + h * dh * d
    if kind == "ssd":
        inner = int(app["ssd_heads"]) * int(app["ssd_head_dim"])
        proj = 2 * inner + 2 * int(app["ssd_groups"]) * int(app["ssd_state"]) \
            + int(app["ssd_heads"])
        return d * proj + inner * d
    if kind:
        raise ValueError(f"no mixer {kind!r}")
    return 0


def ffn_params(app: Dict[str, Any], kind: str, layer: int) -> Dict[str, float]:
    """``{"dense", "routed"}`` matmul parameters a token passes through in
    one feed-forward part: the routed experts at the held share of uniform
    routing, everything else of the part under ``dense``."""
    d, f = int(app["d_model"]), int(app["d_ff"])
    if kind == "dense":
        if layer < int(app.get("moe_first_dense", 0)):
            f = int(app.get("dense_d_ff") or f)
        gated = str(app.get("ffn", "gelu")) == "swiglu"
        return {"dense": (3 if gated else 2) * d * f, "routed": 0.0}
    if kind != "moe":
        if kind:
            raise ValueError(f"no feed-forward part {kind!r}")
        return {"dense": 0, "routed": 0.0}
    experts, top_k = int(app["moe_experts"]), int(app.get("moe_top_k", 0))
    if not top_k:  # the program's Switch path: no cell runs it
        raise ValueError("experts without moe_top_k are not counted here")
    mats = 3 if app.get("moe_gated", True) else 2
    r = int(app.get("moe_latent") or d)
    held = app.get("moe_experts_held")
    held = experts if held is None else int(held)
    dense = d * experts + (2 * d * r if app.get("moe_latent") else 0)
    if app.get("moe_shared_experts"):
        fs = int(app.get("moe_shared_d_ff")
                 or int(app["moe_shared_experts"]) * f)
        dense += mats * d * fs
    return {"dense": dense, "routed": top_k * held / experts * mats * r * f}


def attention_flops(app: Dict[str, Any], kind: str) -> float:
    """Forward FLOPs a token of one softmax-attention layer's pairs."""
    s = int(app["max_seq"])
    if kind == "mla":
        h = int(app["n_heads"])
        dqk = int(app["qk_nope_head_dim"]) + int(app["qk_rope_head_dim"])
        dv = int(app["v_head_dim"])
    elif kind in ("full", "swa"):
        h, _, dqk = _heads(app)
        dv = dqk
    else:
        return 0.0
    # twice the pairs a head and sequence, the diagonal at half
    band = sibling("smallthinker").band_pairs
    pairs2 = 2 * band(s, app["window"] if kind == "swa" else s) - s
    return (dqk + dv) * h * pairs2 / s


def scan_flops(app: Dict[str, Any], kind: str) -> float:
    """Forward FLOPs a token of one chunked-scan layer's recurrence: one
    sequence's forward call over its positions."""
    if kind == "kda":
        call = sibling("kimi_linear").kda_flops_per_call(app, 1, "harmony_kda_fwd")
    elif kind == "ssd":
        call = sibling("nemotron_h").ssd_flops_per_call(app, 1, "harmony_ssd_fwd")
    else:
        return 0.0
    return call / int(app["max_seq"])


def lm_train_flops_split(app: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one token needs forward + backward, by ``PARTS``."""
    out = dict.fromkeys(PARTS, 0.0)
    for i, layer in enumerate(layer_kinds(app)):
        ffn = ffn_params(app, layer["ffn"], i)
        out["dense"] += 6.0 * (mixer_params(app, layer["mixer"]) + ffn["dense"])
        out["routed"] += 6.0 * ffn["routed"]
        out["attention_pairs"] += 3.0 * attention_flops(app, layer["mixer"])
        out["scans"] += 3.0 * scan_flops(app, layer["mixer"])
    out["readout"] = 6.0 * int(app["d_model"]) * int(app["vocab_size"])
    return out


def lm_train_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of an LM configuration needs (module
    docstring; 797,815,296.0 for ``gpt2-124m``, as the count of its block
    alone always gave)."""
    return float(sum(lm_train_flops_split(app).values()))


def keyed_table_bytes_per_example(app: Dict[str, Any]) -> float:
    """Table bytes one example of the keyed tenant has to move: each of its
    ``num_slots`` rows read once by the pull, read and written once by the
    push (float32 rows of 1 + emb_dim)."""
    row = 4 * (1 + app["emb_dim"])
    return 3.0 * row * app["num_slots"]
