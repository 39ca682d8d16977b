"""Plain reference for ``laguna-s-2.1``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no chunks, no sort,
no table, no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops``
is used to compute it. It replays the job's first steps from the same seeded
initial parameters and the same batches and returns each step's loss.

One block of Laguna-S-2.1 (``config.json`` of ``poolside/Laguna-S-2.1``,
``model_type`` ``laguna``), pre-norm, RMSNorm, no biases; block ``l`` is of
kind ``K`` = ``full`` or ``swa`` (``layer_types``), with ``H_K`` query heads
(``num_attention_heads_per_layer``) over ``Hkv`` K/V heads of ``hd`` columns,
input ``x [S, d]``:

    a = RMSNorm(x; g1)
    q, k, v = split(a Wqkv) -> q [H_K, S, hd], k, v [Hkv, S, hd]
    q, k = rope_K(q, k)   swa:  every column, inv_freq_i = theta_s^(-2i/hd)
                          full: the first ``rot = hd / 2`` columns, inv_freq =
                                YaRN(theta_f, rot, factor, original, beta_fast,
                                beta_slow), cos and sin x attention_factor;
                                the other columns pass
    mask(i, j) = j <= i and (K is full or i - j < W)
    o_h = softmax(q_h k_{h // (H_K / Hkv)}^T / sqrt(hd) + mask) v_{h // (H_K / Hkv)}
    g = sigmoid(a Wgate^T) [S, H_K];   y = x + concat_h(g_h o_h) Wo
    b = RMSNorm(y; g2)
    l < first_dense:  out = y + (silu(b W1) * (b W3)) W2
    else:  p = softmax(b Wr) over all E;  top = top-k of p
           w = routed_scale p_top / sum(p_top)
           out = y + sum_{e in top, e held} w_e E_e(b) + E_shared(b)   # SwiGLU

YaRN (arXiv:2309.00071) as ``transformers``' ``_compute_yarn_parameters``
writes it: with ``dim(n) = rot ln(original / (2 pi n)) / (2 ln theta)``, ``low
= floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` clamped to ``[0,
rot - 1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)`` over ``i = 0 ..
rot / 2 - 1`` and ``f_i = theta^(-2i/rot)``: ``inv_freq_i = (1 - ramp_i) f_i +
ramp_i f_i / factor``. Rotate-half.

K and V are repeated to ``H_K`` heads with ``jnp.repeat``, the mask is an
explicit boolean, and attention runs a block of ``QUERY_BLOCK`` query rows at
a time so that ``H x S x S`` scores never exist at once. Then the final
RMSNorm and the untied head; loss = cross-entropy + ``moe_aux_weight`` x the
load-balance loss ``E sum_e f_e P_e`` over all the expert layers' tokens
(``f_e`` the share of tokens whose top-k holds ``e``, a count; ``P_e`` the
mean of ``softmax(r)_e``), over all ``E`` experts whatever share is held.

The chip's share (the configuration file's ``deployment``) is given as
arguments (``app``): ``kind_heads`` query heads over ``n_kv_heads`` K/V heads,
``dense_d_ff`` columns of the dense MLP, ``moe_shared_d_ff`` of the shared
expert, experts ``0 .. moe_experts_held-1`` of each layer and ``vocab_size``
rows; the router, its softmax, the top-k, the renormalisation and the balance
loss keep all ``E``.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM on the first batch with this file (``check_logits``):
its logits position by position, apart for the positions before the window's
length, from there to YaRN's original positions, and from those on, and the
gradient of its loss leaf by leaf, against this file's own in float8 as the
control; if they disagree, it returns losses that are not numbers.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_EPS = 0.9, 1e-8
QUERY_BLOCK = 256

#: arithmetic broken on purpose, so that the tests and ``check_logits`` can
#: show a tolerance tells each apart (``replay(..., ablate=...)``)
LOGIT_ABLATIONS = ("rope_swapped", "no_attention_factor", "plain_frequencies",
                   "no_gate", "gate_next_head", "window_plus_one",
                   "no_routed_scale", "fp8_operands")
#: the ablations EVERY run of the cell computes: the two that decide
#: ``correct`` of the program under test — float8 operands, the control of
#: both precisions' limits (logits and gradient), and the window one key
#: longer, which no norm of the error sees and the direction does. That the
#: limits tell the other six apart is a property of this file and of the
#: limits, not of the program: tests/test_laguna.py plants all eight
#: (``check_logits(..., ablations=LOGIT_ABLATIONS)``), and twelve seeds on the
#: chip read them 2-20 x over the limits (the configuration's
#: ``job.why.loss_rtol``)
RUN_ABLATIONS = ("window_plus_one", "fp8_operands")
#: ``check_logits``' limits by the program's activation dtype and range of
#: positions: the 90th percentile over positions of the per-position relative
#: error, and the relative RMS over all positions. Readings and reasons:
#: ``perf/configs/laguna-s-2.1.json`` ``job.why.loss_rtol``
RANGES = ("before_window", "window_to_original", "past_original")
LIMITS = {"bfloat16": {r: {"q90": 0.022, "rms": 0.03} for r in RANGES},
          "float32": {r: {"q90": 1e-4, "rms": 1e-4} for r in RANGES}}
#: ... and the third limit, whatever the dtype (:func:`toward`): how far the
#: program's error lies TOWARD an ablation, as a share of what the ablation
#: does to the reference's logits, position by position — 0 for a program
#: that computes the reference's mathematics, 1 for one that computes the
#: ablation's. The program's MEDIAN over positions must be at most
#: ``TOWARD``; the statistic tells an ablation apart where the median's own
#: scatter at this size — the program's interquartile distance over the root
#: of the positions counted — is at most ``TOWARD_ERROR`` (were the program
#: the ablation, its median would lie as surely at 1)
TOWARD, TOWARD_ERROR = 0.5, 0.05
#: ``check_logits``' limit on the program's first GRADIENT: a leaf's error
#: ``|g - g_ref|`` as a share of what float8 operands do to the same leaf
#: (``against_control``: the control reads 1), the worst leaf. Readings and
#: reasons: the same place
GRAD_LIMITS = {"bfloat16": 0.7, "float32": 1e-3}


def heads_of(app, kind):
    return int((app.get("kind_heads") or {}).get(kind, app["n_heads"]))


def qkv_widths(app, kind):
    hd = app.get("mha_head_dim") or app["d_model"] // app["n_heads"]
    hkv = app.get("n_kv_heads") or app["n_heads"]
    return heads_of(app, kind) * hd, hkv * hd, hkv * hd


def kind_of(app, i):
    return "swa" if i in app["window_layers"] else "full"


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    E = app["moe_experts"]
    H = app.get("moe_experts_held") or E
    first, fd = app["moe_first_dense"], app.get("dense_d_ff") or f
    fs = app.get("moe_shared_d_ff") or app["moe_shared_experts"] * f
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def normal(key, shape, scale=None):
        return jax.random.normal(key, shape, jnp.float32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    layers = []
    for i, kl in enumerate(k_layers):
        ks = jax.random.split(kl, 4)
        wq, wk, wv = qkv_widths(app, kind_of(app, i))
        layer = {
            "g1": jnp.ones((d,), jnp.float32), "g2": jnp.ones((d,), jnp.float32),
            "wqkv": normal(ks[0], (d, wq + wk + wv)),
            "wo": normal(ks[1], (wq, d)),
            # one row a head: [H_K, d]
            "wgate": normal(jax.random.fold_in(ks[0], 2),
                            (d, heads_of(app, kind_of(app, i)))).T}
        if i < first:
            layer.update(w1=normal(ks[2], (d, fd)), w2=normal(ks[3], (fd, d)),
                         w3=normal(jax.random.fold_in(ks[2], 1), (d, fd)))
        else:
            kr, kg, ku, kd = jax.random.split(ks[2], 4)
            ksg, ksu, ksd = jax.random.split(jax.random.fold_in(ks[2], 1), 3)
            layer.update(
                router=normal(kr, (d, E)),
                eg=normal(kg, (H, d, f)), eu=normal(ku, (H, d, f)),
                ed=normal(kd, (H, f, d)), sg=normal(ksg, (d, fs)),
                su=normal(ksu, (d, fs)), sd=normal(ksd, (fs, d)))
        layers.append(layer)
    return {
        "embed": normal(k_emb, (V, d), app.get("embed_std", 0.02)),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(spec: Dict[str, Any], rot: int, plain: bool = False):
    """The ``rot / 2`` frequencies of one kind's ``rope_parameters`` entry
    (module docstring); ``plain``: YaRN's ramp left out."""
    theta = float(spec["rope_theta"])
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    f = jnp.float32(theta) ** (-2.0 * i / rot)
    if spec.get("rope_type", "default") != "yarn" or plain:
        return f
    original = spec["original_max_position_embeddings"]
    dim = lambda n: rot * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low = max(math.floor(dim(spec.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim(spec.get("beta_slow", 1))), rot - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * f + ramp * f / float(spec["factor"])


def rotary(x, spec: Optional[Dict[str, Any]], scaled=True, plain=False):
    """Rotate-half rotary positions ``0 .. S-1`` on the first
    ``partial_rotary_factor`` of ``x [..., S, hd]``'s columns by one kind's
    ``rope_parameters`` entry (None: no positions); ``scaled`` False leaves
    ``attention_factor`` out, ``plain`` YaRN's ramp."""
    if spec is None:
        return x
    S, hd = x.shape[-2:]
    rot = int(float(spec.get("partial_rotary_factor", 1)) * hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(
        spec, rot, plain)[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    if spec.get("rope_type", "default") == "yarn" and scaled:
        factor = spec.get("attention_factor",
                          0.1 * math.log(spec["factor"]) + 1.0)
        cos, sin = cos * factor, sin * factor
    t, rest = x[..., :rot], x[..., rot:]
    t1, t2 = jnp.split(t, 2, axis=-1)
    return jnp.concatenate(
        [t * cos + jnp.concatenate([-t2, t1], axis=-1) * sin, rest], axis=-1)


def _flag(ablate, name):
    """Whether the ablation ``name`` is on: a Python bool where ``ablate`` is
    None or a name, a traced bool where it is a float32 vector of flags over
    ``LOGIT_ABLATIONS`` — ``check_logits`` passes that one, so ONE compiled
    program computes the reference and every ablation."""
    if ablate is None or isinstance(ablate, str):
        return ablate == name
    return ablate[LOGIT_ABLATIONS.index(name)] > 0


def _pick(ablate, name, broken, whole):
    """``broken()`` where the ablation ``name`` is on, else ``whole()``."""
    on = _flag(ablate, name)
    if isinstance(on, bool):
        return broken() if on else whole()
    return jnp.where(on, broken(), whole())


def _to_float8(t):
    """``t`` rounded to float8 (e4m3) with the gradient passed STRAIGHT
    THROUGH the rounding. Differentiating the casts themselves rounds every
    COTANGENT to e4m3 too, whose smallest number is 2^-9: gradients of 1e-5
    underflow, the whole gradient comes out 0 and a "control" that reads 1
    on every leaf says nothing of float8 (read on the CPU at the real
    widths, PR 54). This way the control is the gradient of the model whose
    products take float8 operands, its cotangents in float32."""
    return t + jax.lax.stop_gradient(
        t.astype(jnp.float8_e4m3fn).astype(jnp.float32) - t)


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the tolerances must
    refuse. The router's product stays float32 on both sides."""
    return lambda t: _pick(ablate, "fp8_operands", lambda: _to_float8(t),
                           lambda: t)


def _attention_one(q, k, v, window, rnd):
    """Softmax attention of one sequence, ``q, k, v [H, S, hd]`` (K and V
    already repeated to the query heads): the ``[S, S]`` boolean mask ``j <=
    i and i - j < window`` (``window`` a traced int; ``S`` or more: the whole
    causal past), a block of query rows at a time."""
    S, hd = q.shape[1], q.shape[2]
    qb = next(n for n in (QUERY_BLOCK, 128, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    @jax.checkpoint
    def rows(args):
        q_blk, row0 = args                                      # [H, qb, hd]
        s = jnp.einsum("hqd,hkd->hqk", rnd(q_blk), rnd(k)) * hd ** -0.5
        ahead = (row0 + jnp.arange(qb))[:, None] - jnp.arange(S)[None, :]
        s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)), rnd(v))

    blocks = q.reshape(q.shape[0], S // qb, qb, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], S, hd)


def _block(x, layer, app, kind: str, dense: bool, ablate):
    """One block on ``x [B, S, d]``: ``(x, token-slots by expert [E], sum
    over tokens of the router's probabilities [E])`` (zeros for a dense
    block)."""
    B, S, d = x.shape
    eps = app["norm_eps"]
    h = heads_of(app, kind)
    hkv = app.get("n_kv_heads") or app["n_heads"]
    wq, wk, _ = qkv_widths(app, kind)
    hd = wq // h
    rnd = _operands(ablate)
    a = rms_norm(x, layer["g1"], eps)
    qkv = rnd(a) @ rnd(layer["wqkv"])
    heads = lambda t: t.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    q, k, v = (heads(t) for t in jnp.split(qkv, (wq, wq + wk), axis=-1))
    ropes = app["kind_rope"]
    other = "swa" if kind == "full" else "full"

    def turn(t):
        mine = _pick(ablate, "no_attention_factor",
                     lambda: rotary(t, ropes[kind], scaled=False),
                     lambda: _pick(ablate, "plain_frequencies",
                                   lambda: rotary(t, ropes[kind], plain=True),
                                   lambda: rotary(t, ropes[kind])))
        return _pick(ablate, "rope_swapped", lambda: rotary(t, ropes[other]),
                     lambda: mine)

    q, k = turn(q), turn(k)
    if kind == "swa":
        window = _pick(ablate, "window_plus_one",
                       lambda: jnp.int32(app["window"] + 1),
                       lambda: jnp.int32(app["window"]))
    else:
        window = jnp.int32(S)
    spread = lambda t: jnp.repeat(t, h // hkv, axis=1)
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, window, rnd),
                    (q, spread(k), spread(v)))                  # [B, H, S, hd]
    gate = jax.nn.sigmoid(jnp.einsum("bsd,hd->bhs", rnd(a), rnd(layer["wgate"])))
    gate = _pick(ablate, "gate_next_head", lambda: jnp.roll(gate, 1, axis=1),
                 lambda: gate)
    o = _pick(ablate, "no_gate", lambda: o, lambda: o * gate[..., None])
    y = x + rnd(o.transpose(0, 2, 1, 3).reshape(B, S, wq)) @ rnd(layer["wo"])
    b = rms_norm(y, layer["g2"], eps)
    E = app["moe_experts"]
    t = b.reshape(B * S, d)
    tr = rnd(t)
    mlp = lambda wg, wu, wd: rnd(jax.nn.silu(tr @ rnd(wg)) * (tr @ rnd(wu))) @ rnd(wd)
    if dense:
        zeros = jnp.zeros((E,), jnp.float32)
        return (y + mlp(layer["w1"], layer["w3"], layer["w2"]).reshape(B, S, d),
                zeros, zeros)
    # the experts, on [T, d]
    top_k = app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    probs = jax.nn.softmax(t @ layer["router"], axis=-1)         # [T, E]
    _, chosen = jax.lax.top_k(probs, top_k)                      # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = probs * mask
    if app.get("moe_norm_topk"):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = _pick(ablate, "no_routed_scale", lambda: weight,
                   lambda: weight * float(app.get("moe_routed_scale", 1.0)))
    out = mlp(layer["sg"], layer["su"], layer["sd"])  # the shared expert
    for e in range(H):  # every held expert on every token, weighted
        out = out + weight[:, e:e + 1] * mlp(layer["eg"][e], layer["eu"][e],
                                             layer["ed"][e])
    return (y + out.reshape(B, S, d), jax.lax.stop_gradient(mask).sum(axis=0),
            probs.sum(axis=0))


def forward(params, inp, app, ablate=None):
    """``(logits [B, S, V], load-balance loss before its weight)``.
    ``ablate``: :func:`_flag`'s."""
    x = params["embed"][inp]
    if isinstance(ablate, str) and ablate == "no_aux":  # the loss's alone
        ablate = None
    tokens = prob = 0.0
    first = app["moe_first_dense"]
    for i, layer in enumerate(params["layers"]):
        block = jax.checkpoint(functools.partial(
            _block, app=app, kind=kind_of(app, i), dense=i < first,
            ablate=ablate))
        x, n, p = block(x, layer)
        tokens, prob = tokens + n, prob + p
    n = (len(params["layers"]) - first) * inp.shape[0] * inp.shape[1]
    lb = app["moe_experts"] * jnp.sum(tokens / n * prob / n)
    rnd = _operands(ablate)
    return (rnd(rms_norm(x, params["ln_f"], app["norm_eps"])) @ rnd(params["head"]),
            lb)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def next_token_loss(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss_and_logits(params, tokens, app, ablate=None):
    """``(loss, logits)`` on the batch ``tokens [B, S + 1]``. ``ablate``:
    :func:`_flag`'s, or ``"no_aux"``: the balance loss left out of the
    loss."""
    logits, lb = forward(params, tokens[:, :-1], app, ablate)
    weight = 0.0 if isinstance(ablate, str) and ablate == "no_aux" else (
        app["moe_aux_weight"])
    return next_token_loss(logits, tokens[:, 1:]) + weight * lb, logits


def loss_fn(params, tokens, app, ablate=None):
    return loss_and_logits(params, tokens, app, ablate)[0]


def flags_of(ablate: Optional[str]):
    """``_flag``'s vector for one of ``LOGIT_ABLATIONS`` (None: all off)."""
    flags = np.zeros(len(LOGIT_ABLATIONS), np.float32)
    if ablate is not None:
        flags[LOGIT_ABLATIONS.index(ablate)] = 1.0
    return flags


@functools.partial(jax.jit, static_argnames=("app",))
def loss_grad_logits(params, tokens, app, flags):
    """``((loss, logits), gradient)`` — the ONE compiled reference program of
    a run: ``check_logits``' logits and gradient, each ablation's and the
    float8 control's (``flags``: :func:`flags_of`, traced) and every step of
    the replay. ``app``: a ``_Static``. No argument has a default: one left
    out would be a constant of another program, compiled again."""
    return jax.value_and_grad(loss_and_logits, has_aux=True)(
        params, tokens, app, flags)


QUANTILES = (0.5, 0.9, 0.99)
DIVERGED = 1e9


def position_errors(a, b) -> Dict[str, float]:
    """Relative error of ``a`` against ``b [B, S, V]`` position by position
    (each position's error vector over its logit vector, in norm): the
    overall relative RMS and quantiles over the positions."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    if a.shape[1] == 0:
        return {"rms": 0.0, **{f"q{int(100 * q)}": 0.0 for q in QUANTILES}}
    per = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / jnp.sum(b ** 2, axis=-1))
    per = jnp.where(jnp.isfinite(per), per, DIVERGED).reshape(-1)
    qs = jnp.quantile(per, jnp.asarray(QUANTILES), method="lower")
    rms = jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2))
    return {"rms": float(jnp.where(jnp.isfinite(rms), rms, DIVERGED)),
            **{f"q{int(100 * q)}": float(v) for q, v in zip(QUANTILES, qs)}}


def range_edges(app) -> Dict[str, Any]:
    """``RANGES``' positions: a row before ``window`` sees its whole causal
    past in every block; from YaRN's ``original_max_position_embeddings`` on
    the full blocks' slowed frequencies are past what the plain ones were
    trained over."""
    window = int(app["window"])
    full = app["kind_rope"]["full"] or {}
    original = max(int(full.get("original_max_position_embeddings", window)),
                   window)
    return {"before_window": slice(0, window),
            "window_to_original": slice(window, original),
            "past_original": slice(original, None)}


def errors_by_range(a, b, app) -> Dict[str, Dict[str, float]]:
    """``position_errors`` apart for each of ``RANGES`` (an empty range
    reads zeros)."""
    return {name: position_errors(a[:, s], b[:, s])
            for name, s in range_edges(app).items()}


def toward(error, change) -> Dict[str, float]:
    """The program's ``error`` (its logits less the reference's) projected on
    an ablation's ``change`` (the ablated reference's logits less the
    reference's), as a share of the change, position by position: ``<e_p,
    c_p> / <c_p, c_p>`` — a matched filter. Rounding is no part of any
    ablation's direction, so a program that computes the reference's
    mathematics reads 0 however small the ablation is beside the rounding (a
    window one key longer moves the logits by 0.4% where bfloat16 moves them
    by 1%: no norm of the error can see it, its direction can); a program
    that computes the ablation's reads 1. ``{"median", "q25", "q75",
    "positions"}`` over the positions the ablation moves at all — the MEDIAN,
    because a near-tie in a router flips on one side or the other at a few
    positions, the SAME positions under rounding and under any ablation and
    towards the same other expert: summed over positions those few carry the
    projection (0.06-0.59 read where 0 was due, my chip runs, PR 54), while
    the quartiles do not see them."""
    num = jnp.sum(error * change, axis=-1).reshape(-1)
    den = jnp.sum(change * change, axis=-1).reshape(-1)
    moved = np.asarray(den) > 0
    if not moved.any():
        return {"median": 0.0, "q25": 0.0, "q75": 0.0, "positions": 0}
    share = np.asarray(num)[moved] / np.asarray(den)[moved]
    share = np.where(np.isfinite(share), share, DIVERGED)
    q25, median, q75 = (float(v) for v in np.quantile(share, (0.25, 0.5, 0.75)))
    return {"median": median, "q25": q25, "q75": q75,
            "positions": int(moved.sum())}


def from_program(tree: Dict[str, Any], app: Dict[str, Any]) -> Dict[str, Any]:
    """A parameter (or gradient) tree of the PROGRAM under this file's names
    (``init_params``'), the expert sub-tree flat."""
    def layer(l):
        out = {"g1": l["ln1"], "g2": l["ln2"], "wqkv": l["wqkv"],
               "wo": l["wo"], "wgate": l["wgate"]}
        if "moe" not in l:
            return {**out, "w1": l["w1"], "w2": l["w2"], "w3": l["w3"]}
        m = l["moe"]
        return {**out, "router": m["router"], "eg": m["wg"], "eu": m["wu"],
                "ed": m["wd"], "sg": m["shared_wg"], "su": m["shared_wu"],
                "sd": m["shared_wd"]}
    return {"embed": tree["embed"], "head": tree["head"], "ln_f": tree["ln_f"],
            "layers": [layer(l) for l in tree["layers"]]}


def gradient_errors(got, want, app) -> Dict[str, List[float]]:
    """``[|got - want|^2, |want|^2]`` of every leaf (both trees under this
    file's names, on the host), summed over the layers that have it — the
    mixer's leaves apart for the two KINDS of block and ``wqkv``'s q, k and v
    columns apart (``wq.swa``, ``wk.full``, ...): a backward fault in the
    windowed kernel, or in the K/V gradient that is summed over a group of 6
    or 9 query heads, then owns a leaf instead of hiding among the q columns
    of every block."""
    def add(name, a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err, norm = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        if not np.isfinite(err) or (norm == 0.0 and np.any(a)):
            err = DIVERGED
        row = sums.setdefault(name, [0.0, 0.0])
        row[0] += err
        row[1] += norm

    sums: Dict[str, List[float]] = {}
    for name in ("embed", "head", "ln_f"):
        add(name, got[name], want[name])
    for i, (a, b) in enumerate(zip(got["layers"], want["layers"])):
        kind = kind_of(app, i)
        wq, wk, _ = qkv_widths(app, kind)
        for name in b:
            if name == "wqkv":
                for part, cols in (("wq", slice(0, wq)),
                                   ("wk", slice(wq, wq + wk)),
                                   ("wv", slice(wq + wk, None))):
                    add(f"{part}.{kind}", a[name][:, cols], b[name][:, cols])
            elif name in ("g1", "wo", "wgate"):
                add(f"{name}.{kind}", a[name], b[name])
            else:
                add(name, a[name], b[name])
    return sums


def against_control(program, control) -> Dict[str, Any]:
    """The program's ``gradient_errors`` as a share of the control's, leaf
    by leaf: ``{"worst", "worst_leaf", "by_leaf": {leaf: [the program's
    relative error, the control's, their ratio]}}``. Where the control reads
    0 the program must."""
    by_leaf = {}
    for leaf, (err, norm) in program.items():
        low = control[leaf][0]
        ratio = (err / low) ** 0.5 if low > 0.0 else (
            0.0 if err == 0.0 else DIVERGED)
        scale = norm if norm > 0.0 else 1.0
        by_leaf[leaf] = [(err / scale) ** 0.5, (low / scale) ** 0.5, ratio]
    worst = max(by_leaf, key=lambda leaf: by_leaf[leaf][2])
    return {"worst": by_leaf[worst][2], "worst_leaf": worst,
            "by_leaf": by_leaf}


def check_logits(app: Dict[str, Any], tokens, seed: int,
                 program_app: Optional[Dict[str, Any]] = None,
                 ablations: Sequence[str] = RUN_ABLATIONS,
                 first: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The program on the batch ``tokens [B, S + 1]`` (``TransformerLM`` as
    the job path traces it: the configuration's dtype, the flash and
    grouped-matmul kernels where the device has them) against this file,
    from the same seeded parameters as the cell trains them. ``{"ok": bool,
    ...}``. ``program_app``: the PROGRAM's configuration where a test breaks
    the program on purpose (the reference keeps ``app``). ``ablations``:
    which of ``LOGIT_ABLATIONS`` the one compiled reference program
    (``loss_grad_logits``, a vector of flags) also computes — ``fp8_operands``
    always among them: it is the control. ``first``: a dict that receives the
    reference's ``loss`` and ``gradient`` (on the host) on this batch — the
    replay's first step, which need not be computed twice.

    LOGITS (``lm.apply`` against ``forward``), position by position and
    reported apart for the three ``RANGES`` of positions
    (``errors_by_range``): the window's edge and YaRN's range each get their
    own limit. Rounding moves EVERY position a little, and a near-tie in a
    256-wide router sends a token to another expert on one side only, which
    moves a FEW positions a lot: so two limits a range (``LIMITS``), the 90th
    percentile over positions and the RMS over all of them (which bounds the
    tail), and the program must hold both in EVERY range. The program's error
    must also lie no nearer any ablation computed than ``TOWARD`` of the way
    at the median position (:func:`toward`): the third limit. An ablation is
    told apart where it reads above the ``q90`` limit of at least one range,
    or where ``toward``'s median is sure to ``TOWARD_ERROR`` on the program
    (its interquartile distance over the root of the positions: the
    statistic then resolves 0 from 1 at this size); one that is neither
    fails the check.

    GRADIENTS (``jax.value_and_grad(lm.loss)``, the function the trainer
    differentiates, against ``loss_grad_logits``), leaf by leaf
    (``gradient_errors``: the mixer's leaves apart by kind of block): the
    backward passes of both flash kernels at groups of 6 and 9 query heads a
    K/V head, of the gate, of both rotaries, of the grouped matmuls and the
    selection, at the timed size — what the losses see only through Adam's
    first update, which keeps a gradient's SIGN alone. The control is this
    file's own gradient with every product's operands rounded to float8: the
    program's error must stay under ``GRAD_LIMITS`` of the control's on
    every leaf (``against_control``; a leaf that is simply wrong reads one
    over the control's relative error there: 4 to 60)."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    if "fp8_operands" not in ablations:
        raise ValueError("fp8_operands is the control: always computed")
    tokens = jnp.asarray(tokens)
    inp = tokens[:, :-1]
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in (program_app or app).items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    limits, grad_limit = LIMITS[dtype], GRAD_LIMITS[dtype]
    clock = {"start": time.monotonic()}
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        # every gradient waits on the HOST: the device holds one tree at a
        # time beside whatever the process already keeps there
        got_loss, got_g = jax.device_get(
            jax.jit(jax.value_and_grad(lm.loss))(params, tokens))
        got_g = from_program(got_g, app)
        clock["program_gradient"] = time.monotonic()
        got = jax.block_until_ready(jax.jit(lm.apply)(params, inp))
    del params
    clock["program"] = time.monotonic()
    static = _Static(app)
    moved, lean = {}, {}
    with jax.default_matmul_precision("highest"):
        ref = init_params(app, seed)
        (ref_loss, want), want_g = loss_grad_logits(
            ref, tokens, static, flags_of(None))
        want_g = jax.device_get(want_g)
        program = errors_by_range(got, want, app)
        error = jnp.asarray(got, jnp.float32) - want
        del got
        clock["reference"] = time.monotonic()
        for a in ablations:
            (_, broken), broken_g = loss_grad_logits(
                ref, tokens, static, flags_of(a))
            if a == "fp8_operands":
                control = gradient_errors(jax.device_get(broken_g), want_g,
                                          app)
            del broken_g
            moved[a] = {r: {k: e[k] for k in ("q90", "rms")}
                        for r, e in errors_by_range(broken, want, app).items()}
            lean[a] = toward(error, broken - want)
            del broken
        clock["ablations"] = time.monotonic()
    gradients = {"limit": grad_limit,
                 **against_control(gradient_errors(got_g, want_g, app),
                                   control),
                 "loss": abs(float(got_loss) - float(ref_loss))
                 / abs(float(ref_loss))}
    if first is not None:
        first.update(loss=float(ref_loss), gradient=want_g)
    del got_g, want_g
    by_norm = {a: any(e["q90"] > limits[r]["q90"] for r, e in moved[a].items())
               for a in ablations}
    by_direction = {a: lean[a]["positions"] > 0 and (
        (lean[a]["q75"] - lean[a]["q25"]) / lean[a]["positions"] ** 0.5
        <= TOWARD_ERROR) for a in ablations}
    detected = {a: bool(by_norm[a] or by_direction[a]) for a in ablations}
    held = (all(program[r][k] <= limits[r][k]
                for r in program for k in limits[r])
            and all(lean[a]["median"] <= TOWARD for a in ablations))
    held_g = gradients["worst"] <= grad_limit
    marks = list(clock.items())
    edges = range_edges(app)
    return {"ok": bool(held and held_g and all(detected.values())),
            "program": program, "limits": limits, "ablations": moved,
            "toward": lean, "toward_limit": TOWARD, "detected": detected,
            "detected_by_norm": by_norm, "gradients": gradients,
            "seconds": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
            "seed": int(seed), "dtype": dtype,
            "ranges": {r: [s.start, s.stop] for r, s in edges.items()}}


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, m, v, t, hyper):
    """One leaf's Adam step, in its own buffers: ``(p, m, v)``."""
    lr, b2 = hyper
    m = ADAM_B1 * m + (1 - ADAM_B1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * (m / (1 - ADAM_B1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + ADAM_EPS)
    return p, m, v


@functools.partial(jax.jit, donate_argnums=(0,))
def _adam_first_and_last(p, g, lr):
    """Adam's FIRST update where no later one follows: ``m^ = g``, ``v^ =
    g^2`` exactly (the bias corrections cancel the ``1 - beta``), so no
    moment is formed."""
    return p - lr * g / (jnp.sqrt(g * g) + ADAM_EPS)


def replay(app: Dict[str, Any], data: Sequence[np.ndarray], batch: int,
           steps: int, seed: int, ablate: Optional[str] = None,
           logits: bool = True) -> List[float]:
    """Loss of each of the first ``steps`` steps (batch ``i`` is rows
    ``[i * batch, (i + 1) * batch)`` of the data set, cycling per epoch, as
    dolphin/data.py serves them unshuffled). ``ablate``: one of
    ``LOGIT_ABLATIONS``. First, unless ``logits`` is off or an ablation is
    asked for, ``check_logits`` on the first batch: its report is printed as
    one JSON line, and where it fails every loss returned is ``nan``, which
    no tolerance accepts. Every step runs the one program
    ``loss_grad_logits`` (the check's too, whose evaluation on the first
    batch IS the first step); the last step's gradient is not used (its loss
    is computed before its update)."""
    if app.get("optimizer") != "adam":
        raise ValueError("this reference implements Adam only")
    if ablate is not None and ablate not in LOGIT_ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    tokens = np.asarray(data[0])
    nb = tokens.shape[0] // batch
    first: Dict[str, Any] = {}
    if logits and ablate is None:
        report = check_logits(dict(app), tokens[:batch], seed, first=first)
        print(json.dumps({"line": "logits_check", **report}), flush=True)
        if not report["ok"]:
            return [float("nan")] * steps
    lr, b2 = float(app["step_size"]), float(app.get("beta2") or 0.999)
    app, flags = _Static(app), flags_of(ablate)
    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = v = None
        for i in range(steps):
            toks = jnp.asarray(tokens[(i % nb) * batch:(i % nb + 1) * batch])
            if i == 0 and first:  # the check's own reference, same batch
                loss, g = first.pop("loss"), first.pop("gradient")
            else:
                (loss, _), g = loss_grad_logits(params, toks, app, flags)
            losses.append(float(loss))
            if i == steps - 1:
                break
            if i == steps - 2 and m is None:
                params = jax.tree.map(
                    lambda p, a: _adam_first_and_last(p, a, lr), params, g)
                del g  # the next step's gradient is as large again
                continue
            if m is None:
                m = jax.tree.map(jnp.zeros_like, params)
                v = jax.tree.map(jnp.zeros_like, params)
            out = jax.tree.map(
                lambda p, a, b, c: _adam_leaf(p, a, b, c, jnp.float32(i + 1),
                                              (lr, b2)), params, g, m, v)
            is_triple = lambda x: isinstance(x, tuple)
            params, m, v = (jax.tree.map(lambda x: x[j], out, is_leaf=is_triple)
                            for j in range(3))
            del g, out
    return losses
