"""Plain reference for ``qwen3-next-80b-a3b``: forward, loss, gradients and
Adam by formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no chunks, no solve,
no sort, no table, no jobserver; nothing of ``harmony_tpu/models`` or
``harmony_tpu/ops`` is used to compute it. It replays the job's first steps
from the same seeded initial parameters and the same batches and returns each
step's loss.

Qwen3-Next (``config.json`` of ``Qwen/Qwen3-Next-80B-A3B-Instruct``,
``model_type`` ``qwen3_next``; equations as ``transformers``'
``modeling_qwen3_next.py`` writes them), pre-norm, no biases anywhere. The
model's norm is ``N(x; w) = x rsqrt(mean x^2 + eps) (1 + w)`` with ``w``
starting at 0 — everywhere EXCEPT the delta-rule block's output norm, whose
weight is plain and starts at 1. Block ``i`` is softmax attention where ``(i +
1) % full_attention_interval == 0`` (every block not in ``linear_layers``),
else Gated DeltaNet; every block has experts.

    block:  x = x + Mixer(N(x; ln1));  x = x + MoE(N(x; ln2));  logits = N(x; ln_f) @ head

    Gated DeltaNet (Hk key heads, Hv value heads, dh wide; value head j reads
    key head j // (Hv / Hk)), a = N(x; ln1):
      [q | k | v | z] = a W_qkvz;  [b | a'] = a W_ba^T    (Hv scalars each)
      [q | k | v] <- silu(causal depthwise conv, K taps, no bias, over q | k | v)
      q_h <- q_h rsqrt(sum q_h^2 + 1e-6) dh^-1/2;  k_h <- k_h rsqrt(sum k_h^2 + 1e-6)
      beta_j = sigmoid(b_j);   g_j = -exp(a_log_j) softplus(a'_j + dt_bias_j)
      S_t = exp(g_t) S_{t-1};  S_t <- S_t + beta_t k_t (v_t - k_t^T S_t)^T;  o_t = S_t^T q_t
        — POSITION BY POSITION (``delta_rule``: a ``lax.scan`` over the
        positions, checkpointed in runs; no chunk, no triangular solve)
      y = concat_j [ o_norm * o_j rsqrt(mean o_j^2 + eps) * silu(z_j) ] W_o   (norm THEN gate)

    Gated attention (H query heads over Hkv K/V heads, hd wide, causal):
      [q_h | gate_h] = a W_q  (a head's hd query columns, then its hd gate columns)
      q_h <- N(q_h; q_head_norm), k_h <- N(k_h; k_head_norm)   (the 1 + w form)
      rotate-half rotary at theta on the FIRST rope_fraction x hd columns; the rest pass
      y = concat_h [ softmax(q_h k^T hd^-1/2 + causal) v * sigmoid(gate_h) ] W_o

    MoE: p = softmax(b W_r) over all E (float32); the top k, renormalised to
      sum 1; y = sum_{chosen, held} p_e SwiGLU_e(b) + sigmoid(b . shared_gate)
      SwiGLU_shared(b); + moe_aux_weight x the load-balance loss E sum_e f_e
      P_e over all the layers' tokens.

K and V are repeated to the query heads with ``jnp.repeat``, the mask is an
explicit boolean, and attention runs a block of ``QUERY_BLOCK`` query rows at
a time. The chip's share (the configuration file's ``deployment``) is given
as arguments (``app``): experts ``0 .. moe_experts_held-1`` of each layer and
``vocab_size`` rows; the router, its softmax, the top-k, the renormalisation
and the balance loss keep all ``E``. The parameter tree carries the
PROGRAM's leaf names (``TransformerLM.init``'s), drawn here with the same key
splits and scales, so a gradient of the program is compared leaf for leaf
with no renaming.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM on the first batch with this file (``check_logits``):
(a) its logits position by position, (b) the gradient of its loss leaf by
leaf against this file's own in float8 as the control, (c) its logits again
from PERTURBED parameters (``perturbed``): at initialisation ``1 + 0`` cannot
be told from a plain weight of 1 nor a gate of ``sigmoid(~0)`` from a constant
half. Where they disagree it returns losses that are not numbers.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_EPS = 0.9, 1e-8
QUERY_BLOCK = 256
SCAN_RUN = 128      # positions of the recurrence a checkpointed run
L2_EPS = 1e-6
#: what ``forward`` can be asked to get wrong — each a reading of the block
#: the limits of ``check_logits`` must refuse
LOGIT_ABLATIONS = (
    "fp8_operands", "value_head_mod", "decay_per_key_head", "no_softplus",
    "no_a_log", "beta_one", "no_l2norm", "gate_then_norm", "sigmoid_z",
    "no_conv", "plain_norm_weight", "rope_whole_head", "no_attn_gate",
    "attn_gate_next_head", "attn_gate_scalar", "no_head_norm",
    "topk_not_renormed", "no_shared_gate")
#: the two every run computes by its one compiled program: float8 operands,
#: the control of the limits, and the grouping of value heads over key heads
RUN_ABLATIONS = ("fp8_operands", "value_head_mod")
#: per-position relative error of the logits: the 90th percentile over
#: positions and the RMS over all of them (the configuration file's
#: ``loss_rtol`` has the readings both limits lie between)
LIMITS = {"bfloat16": {"q90": 0.03, "rms": 0.028},
          "float32": {"q90": 2e-4, "rms": 2e-4}}
#: the worst leaf's gradient error as a share of the float8 control's
GRAD_LIMITS = {"bfloat16": 0.74, "float32": 1e-3}
#: the perturbation of pass (c): the ``1 + w`` weights are DRAWN at this
#: deviation, ``shared_gate`` and ``w_ba`` scaled by this factor
PERTURB_STD, PERTURB_SCALE = 0.1, 4.0


def widths(app):
    """``(key, value, conv, Hv)`` of a Gated DeltaNet mixer; ``(wq, wkv, H,
    Hkv, hd)`` of an attention block: ``gdn, attn``."""
    dh, hk = app["linear_head_dim"], app["linear_heads"]
    hv = app.get("linear_value_heads") or hk
    h, hkv, hd = app["n_heads"], app["n_kv_heads"], app["mha_head_dim"]
    return ((hk * dh, hv * dh, 2 * hk * dh + hv * dh, hv),
            (h * hd, hkv * hd, h, hkv, hd))


def is_linear(app, i) -> bool:
    return i in set(app["linear_layers"])


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    the program's own leaf names."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    E, K = app["moe_experts"], app["short_conv"]
    H = app.get("moe_experts_held") or E
    fs = app.get("moe_shared_d_ff") or app["moe_shared_experts"] * f
    (key, value, conv, hv), (wq, wkv, _, _, hd) = widths(app)
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)
    f32 = jnp.float32

    def normal(k, shape, scale=None):
        return jax.random.normal(k, shape, f32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    layers = []
    for i, kl in enumerate(k_layers):
        ks = jax.random.split(kl, 4)
        layer = {"ln1": jnp.zeros((d,), f32), "ln2": jnp.zeros((d,), f32)}
        if is_linear(app, i):
            ki, kc, ka, kb = jax.random.split(ks[0], 4)
            layer["gdn"] = {
                "w_qkvz": normal(ki, (d, conv + value)),
                "conv": jax.random.uniform(kc, (K, conv), f32,
                                           -K ** -0.5, K ** -0.5),
                "w_ba": normal(kb, (d, 2 * hv)).T,
                "a_log": jnp.log(jax.random.uniform(ka, (hv,), f32, 0.0, 16.0)),
                "dt_bias": jnp.ones((hv,), f32),
                "o_norm": jnp.ones((app["linear_head_dim"],), f32),
                "wo": normal(ks[1], (value, d))}
        else:
            layer.update(wqkv=normal(ks[0], (d, 2 * wq + 2 * wkv)),
                         wo=normal(ks[1], (wq, d)),
                         q_head_norm=jnp.zeros((hd,), f32),
                         k_head_norm=jnp.zeros((hd,), f32))
        kr, kg, ku, kd = jax.random.split(ks[2], 4)
        ksg, ksu, ksd = jax.random.split(jax.random.fold_in(ks[2], 1), 3)
        layer["moe"] = {
            "router": normal(kr, (d, E)),
            "wg": normal(kg, (H, d, f)), "wu": normal(ku, (H, d, f)),
            "wd": normal(kd, (H, f, d)), "shared_wg": normal(ksg, (d, fs)),
            "shared_wu": normal(ksu, (d, fs)), "shared_wd": normal(ksd, (fs, d)),
            "shared_gate": jax.random.normal(jax.random.fold_in(ks[2], 3), (d,),
                                             f32) * d ** -0.5}
        layers.append(layer)
    return {"embed": normal(k_emb, (V, d), app.get("embed_std", 0.02)),
            "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
            "ln_f": jnp.zeros((d,), f32), "layers": layers}


def perturbed(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """``params`` (the program's tree or this file's: the same names) moved
    off the initialisation for pass (c): every ``1 + w`` weight (``ln1``,
    ``ln2``, ``ln_f``, the head norms) DRAWN ``N(0, PERTURB_STD)``, every
    ``shared_gate`` and ``w_ba`` times ``PERTURB_SCALE`` — the same draws on
    both sides."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 61)
    offsets = ("ln1", "ln2", "q_head_norm", "k_head_norm")

    def drawn(k, w):
        return jax.random.normal(k, w.shape, jnp.float32) * PERTURB_STD

    layers = []
    for i, layer in enumerate(params["layers"]):
        layer = dict(layer)
        for j, name in enumerate(offsets):
            if name in layer:
                layer[name] = drawn(jax.random.fold_in(key, 8 * i + j),
                                    layer[name])
        if "shared_gate" in layer["moe"]:  # absent from a program built wrong
            layer["moe"] = {**layer["moe"], "shared_gate":
                            layer["moe"]["shared_gate"] * PERTURB_SCALE}
        if "gdn" in layer:
            layer["gdn"] = {**layer["gdn"],
                            "w_ba": layer["gdn"]["w_ba"] * PERTURB_SCALE}
        layers.append(layer)
    return {**params, "layers": layers,
            "ln_f": drawn(jax.random.fold_in(key, 10 ** 6), params["ln_f"])}


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
    THROUGH the rounding (differentiating the casts rounds the cotangents to
    e4m3 too, and they underflow: PERF.md section 6, PR 54)."""
    return t + jax.lax.stop_gradient(
        t.astype(jnp.float8_e4m3fn).astype(jnp.float32) - t)


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states. The router's product stays
    float32 on both sides."""
    return lambda t: _pick(ablate, "fp8_operands", lambda: _to_float8(t),
                           lambda: t)


def norm(x, w, eps, ablate=None):
    """The model's norm: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    w = _pick(ablate, "plain_norm_weight", lambda: w, lambda: 1.0 + w)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta: float, width: int):
    """Rotate-half rotary on the first ``width`` columns of ``x [B, H, S,
    hd]``, positions 0 .. S-1; the other columns pass."""
    S = x.shape[2]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    t = x[..., :width]
    t1, t2 = jnp.split(t, 2, axis=-1)
    turned = t * cos + jnp.concatenate([-t2, t1], axis=-1) * sin
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring for one value head, position by
    position: ``q, k [S, dk]``, ``v [S, dv]``, ``g, beta [S]`` -> ``o [S,
    dv]``. Runs of ``SCAN_RUN`` positions are checkpointed (the backward
    keeps a state a run, not a state a position); no number changes."""
    S, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt) * state
        state = state + bt * kt[:, None] * (vt - kt @ state)[None, :]
        return state, qt @ state

    run = next(n for n in (SCAN_RUN, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    @jax.checkpoint
    def runs(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = jax.tree.map(lambda t: t.reshape(S // run, run, *t.shape[1:]),
                      (q, k, v, g, beta))
    _, o = jax.lax.scan(runs, jnp.zeros((dk, dv), jnp.float32), xs)
    return o.reshape(S, dv)


def _gdn(xn, p, app, ablate, rnd):
    """The Gated DeltaNet mixer on the normed input ``xn [B, S, d]``."""
    B, S, _ = xn.shape
    dh, hk = app["linear_head_dim"], app["linear_heads"]
    (key, value, conv, hv), _ = widths(app)
    heads = lambda t: t.reshape(B, S, -1, dh).transpose(0, 2, 1, 3)
    x = rnd(xn)
    qkvz = x @ rnd(p["w_qkvz"])
    ba = (x @ rnd(p["w_ba"].T)).transpose(0, 2, 1)               # [B, 2 Hv, S]
    mixed, K = qkvz[..., :conv], p["conv"].shape[0]
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
    mixed = jax.nn.silu(_pick(
        ablate, "no_conv", lambda: mixed,
        lambda: sum(padded[:, j:j + S] * p["conv"][j] for j in range(K))))
    q, k, v = (heads(t) for t in jnp.split(mixed, (key, 2 * key), axis=-1))

    def l2(t):
        return _pick(ablate, "no_l2norm", lambda: t, lambda: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS))

    q, k = l2(q) * dh ** -0.5, l2(k)
    # value head j reads key head j // (Hv / Hk)
    j = jnp.arange(hv)
    of_key = _pick(ablate, "value_head_mod", lambda: j % hk,
                   lambda: j // (hv // hk))
    q, k = q[:, of_key], k[:, of_key]
    beta = jax.nn.sigmoid(ba[:, :hv])
    beta = _pick(ablate, "beta_one", lambda: jnp.ones_like(beta), lambda: beta)
    a = ba[:, hv:] + p["dt_bias"][None, :, None]
    rate = _pick(ablate, "no_a_log", lambda: jnp.ones_like(p["a_log"]),
                 lambda: jnp.exp(p["a_log"]))[None, :, None]
    # without the softplus a decay could GROW the state: clipped at 0 so the
    # ablation stays a number
    g = -rate * _pick(ablate, "no_softplus", lambda: jnp.maximum(a, 0.0),
                      lambda: jax.nn.softplus(a))
    g = _pick(ablate, "decay_per_key_head",
              lambda: g[:, (j // (hv // hk)) * (hv // hk)], lambda: g)
    o = jax.vmap(jax.vmap(delta_rule))(rnd(q), rnd(k), rnd(v), g, beta)
    z = heads(qkvz[..., conv:])
    gate = _pick(ablate, "sigmoid_z", lambda: jax.nn.sigmoid(z),
                 lambda: jax.nn.silu(z))
    plain = lambda t: t * jax.lax.rsqrt(
        jnp.mean(t * t, axis=-1, keepdims=True) + app["norm_eps"]) * p["o_norm"]
    o = _pick(ablate, "gate_then_norm", lambda: plain(o * gate),
              lambda: plain(o) * gate)
    return rnd(o.transpose(0, 2, 1, 3).reshape(B, S, value)) @ rnd(p["wo"])


def _attention_one(q, k, v, rnd):
    """Causal softmax attention of one sequence, ``q, k, v [H, S, hd]`` (K
    and V already repeated to the query heads): the explicit ``[S, S]``
    boolean mask, a block of query rows at a time."""
    S, hd = q.shape[1], q.shape[2]
    qb = next(n for n in (QUERY_BLOCK, 128, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    @jax.checkpoint
    def rows(args):
        q_blk, row0 = args                                      # [H, qb, hd]
        s = jnp.einsum("hqd,hkd->hqk", rnd(q_blk), rnd(k)) * hd ** -0.5
        seen = (row0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)), rnd(v))

    blocks = q.reshape(q.shape[0], S // qb, qb, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], S, hd)


def _attention(xn, layer, app, ablate, rnd):
    """The gated softmax mixer on the normed input ``xn [B, S, d]``."""
    B, S, _ = xn.shape
    _, (wq, wkv, h, hkv, hd) = widths(app)
    eps = app["norm_eps"]
    qkv = rnd(xn) @ rnd(layer["wqkv"])
    qg, k, v = jnp.split(qkv, (2 * wq, 2 * wq + wkv), axis=-1)
    qg = qg.reshape(B, S, h, 2 * hd).transpose(0, 2, 1, 3)       # [B, h, S, 2 hd]
    q, gate = qg[..., :hd], qg[..., hd:]
    heads = lambda t: t.reshape(B, S, hkv, hd).transpose(0, 2, 1, 3)
    k, v = heads(k), heads(v)
    q = _pick(ablate, "no_head_norm", lambda: q,
              lambda: norm(q, layer["q_head_norm"], eps, ablate))
    k = _pick(ablate, "no_head_norm", lambda: k,
              lambda: norm(k, layer["k_head_norm"], eps, ablate))
    turned = int(round(app.get("rope_fraction", 1.0) * hd))
    theta = float(app["rope_theta"])
    turn = lambda t: _pick(ablate, "rope_whole_head",
                           lambda: rotary(t, theta, hd),
                           lambda: rotary(t, theta, turned))
    q, k = turn(q), turn(k)
    spread = lambda t: jnp.repeat(t, h // hkv, axis=1)
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, rnd),
                    (q, spread(k), spread(v)))                   # [B, h, S, hd]
    gate = _pick(ablate, "attn_gate_next_head",
                 lambda: jnp.roll(gate, 1, axis=1), lambda: gate)
    gate = _pick(ablate, "attn_gate_scalar", lambda: jnp.broadcast_to(
        gate.mean(axis=-1, keepdims=True), gate.shape), lambda: gate)
    o = _pick(ablate, "no_attn_gate", lambda: o,
              lambda: o * jax.nn.sigmoid(gate))
    return rnd(o.transpose(0, 2, 1, 3).reshape(B, S, wq)) @ rnd(layer["wo"])


def _experts(b, m, app, ablate, rnd):
    """The expert layer on the normed rows ``b [T, d]``: ``(out, token-slots
    by expert [E], sum over tokens of the router's probabilities [E])``."""
    E, top_k = app["moe_experts"], app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    tr = rnd(b)
    mlp = lambda wg, wu, wd: rnd(jax.nn.silu(tr @ rnd(wg)) * (tr @ rnd(wu))) @ rnd(wd)
    probs = jax.nn.softmax(b @ m["router"], axis=-1)             # [T, E]
    _, chosen = jax.lax.top_k(probs, top_k)                      # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = probs * mask
    weight = _pick(ablate, "topk_not_renormed", lambda: weight,
                   lambda: weight / (weight.sum(axis=-1, keepdims=True) + 1e-20))
    shared = mlp(m["shared_wg"], m["shared_wu"], m["shared_wd"])
    gate = jax.nn.sigmoid(jnp.sum(b * m["shared_gate"], axis=-1, keepdims=True))
    out = _pick(ablate, "no_shared_gate", lambda: shared, lambda: shared * gate)
    for e in range(H):  # every held expert on every token, weighted
        out = out + weight[:, e:e + 1] * mlp(m["wg"][e], m["wu"][e], m["wd"][e])
    return out, jax.lax.stop_gradient(mask).sum(axis=0), probs.sum(axis=0)


def _block(x, layer, app, ablate):
    """One block on ``x [B, S, d]``: ``(x, token-slots by expert [E], sum
    over tokens of the router's probabilities [E])``."""
    B, S, d = x.shape
    eps, rnd = app["norm_eps"], _operands(ablate)
    a = norm(x, layer["ln1"], eps, ablate)
    mix = (_gdn(a, layer["gdn"], app, ablate, rnd) if "gdn" in layer
           else _attention(a, layer, app, ablate, rnd))
    y = x + mix
    b = norm(y, layer["ln2"], eps, ablate).reshape(B * S, d)
    out, n, p = _experts(b, layer["moe"], app, ablate, rnd)
    return y + out.reshape(B, S, d), n, p


def forward(params, inp, app, ablate=None):
    """``(logits [B, S, V], load-balance loss before its weight)``.
    ``ablate``: :func:`_flag`'s."""
    x = params["embed"][inp]
    tokens = prob = 0.0
    for layer in params["layers"]:
        block = jax.checkpoint(functools.partial(_block, app=app, ablate=ablate))
        x, n, p = block(x, layer)
        tokens, prob = tokens + n, prob + p
    n = len(params["layers"]) * inp.shape[0] * inp.shape[1]
    lb = app["moe_experts"] * jnp.sum(tokens / n * prob / n)
    rnd = _operands(ablate)
    return (rnd(norm(x, params["ln_f"], app["norm_eps"], ablate))
            @ rnd(params["head"]), lb)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def next_token_loss(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss_and_logits(params, tokens, app, ablate=None):
    """``(loss, logits)`` on the batch ``tokens [B, S + 1]``."""
    logits, lb = forward(params, tokens[:, :-1], app, ablate)
    return (next_token_loss(logits, tokens[:, 1:])
            + app["moe_aux_weight"] * lb, logits)


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
    float8 control's (``flags``: :func:`flags_of`, traced), the perturbed
    pass and every step of the replay. ``app``: a ``_Static``. No argument
    has a default: one left out would be a constant of another program."""
    return jax.value_and_grad(loss_and_logits, has_aux=True)(
        params, tokens, app, flags)


QUANTILES = (0.5, 0.9, 0.99)
DIVERGED = 1e9


def position_errors(a, b) -> Dict[str, float]:
    """Relative error of ``a`` against ``b [B, S, V]`` position by position
    (each position's error vector over its logit vector, in norm): the
    overall relative RMS and quantiles over the positions."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    per = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / jnp.sum(b ** 2, axis=-1))
    per = jnp.where(jnp.isfinite(per), per, DIVERGED).reshape(-1)
    qs = jnp.quantile(per, jnp.asarray(QUANTILES), method="lower")
    rms = jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2))
    return {"rms": float(jnp.where(jnp.isfinite(rms), rms, DIVERGED)),
            **{f"q{int(100 * q)}": float(v) for q, v in zip(QUANTILES, qs)}}


def gradient_errors(got, want, app) -> Dict[str, List[float]]:
    """``[|got - want|^2, |want|^2]`` of every leaf (both trees under the
    program's names, on the host), summed over the layers that have it —
    ``w_qkvz`` apart by its q / k / v / z columns, ``w_ba`` by its b / a
    rows, ``wqkv`` by its query / gate / k / v columns, ``ln1`` and ``wo``
    by KIND of block: a fault in the scan's ``dq`` / ``dk`` (summed over
    the value heads that share a key head), in the gate's backward or in the
    fused head norm then owns a leaf."""
    def add(name, a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err, norm_ = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        if not np.isfinite(err) or (norm_ == 0.0 and np.any(a)):
            err = DIVERGED
        row = sums.setdefault(name, [0.0, 0.0])
        row[0] += err
        row[1] += norm_

    sums: Dict[str, List[float]] = {}
    (key, value, conv, hv), (wq, wkv, h, _, hd) = widths(app)
    for name in ("embed", "head", "ln_f"):
        add(name, got[name], want[name])
    for a, b in zip(got["layers"], want["layers"]):
        kind = "gdn" if "gdn" in b else "attn"
        add(f"ln1.{kind}", a["ln1"], b["ln1"])
        add("ln2", a["ln2"], b["ln2"])
        for name in b["moe"]:
            add(f"moe.{name}", a["moe"][name], b["moe"][name])
        if kind == "gdn":
            ga, gb = a["gdn"], b["gdn"]
            for part, cols in (("q", slice(0, key)), ("k", slice(key, 2 * key)),
                               ("v", slice(2 * key, conv)),
                               ("z", slice(conv, None))):
                add(f"w_qkvz.{part}", ga["w_qkvz"][:, cols], gb["w_qkvz"][:, cols])
            add("w_ba.b", ga["w_ba"][:hv], gb["w_ba"][:hv])
            add("w_ba.a", ga["w_ba"][hv:], gb["w_ba"][hv:])
            for name in ("conv", "a_log", "dt_bias", "o_norm"):
                add(name, ga[name], gb[name])
            add("wo.gdn", ga["wo"], gb["wo"])
            continue
        qa = np.asarray(a["wqkv"])[:, :2 * wq].reshape(-1, h, 2 * hd)
        qb = np.asarray(b["wqkv"])[:, :2 * wq].reshape(-1, h, 2 * hd)
        add("wqkv.query", qa[..., :hd], qb[..., :hd])
        add("wqkv.gate", qa[..., hd:], qb[..., hd:])
        add("wqkv.k", a["wqkv"][:, 2 * wq:2 * wq + wkv],
            b["wqkv"][:, 2 * wq:2 * wq + wkv])
        add("wqkv.v", a["wqkv"][:, 2 * wq + wkv:], b["wqkv"][:, 2 * wq + wkv:])
        for name in ("q_head_norm", "k_head_norm"):
            add(name, a[name], b[name])
        add("wo.attn", a["wo"], b["wo"])
    return sums


def against_control(program, control) -> Dict[str, Any]:
    """The program's ``gradient_errors`` as a share of the control's, leaf
    by leaf: ``{"worst", "worst_leaf", "by_leaf": {leaf: [the program's
    relative error, the control's, their ratio]}}``. Where the control reads
    0 the program must."""
    by_leaf = {}
    for leaf, (err, norm_) in program.items():
        low = control[leaf][0]
        ratio = (err / low) ** 0.5 if low > 0.0 else (
            0.0 if err == 0.0 else DIVERGED)
        scale = norm_ if norm_ > 0.0 else 1.0
        by_leaf[leaf] = [(err / scale) ** 0.5, (low / scale) ** 0.5, ratio]
    worst = max(by_leaf, key=lambda leaf: by_leaf[leaf][2])
    return {"worst": by_leaf[worst][2], "worst_leaf": worst,
            "by_leaf": by_leaf}


def check_logits(app: Dict[str, Any], tokens, seed: int,
                 program_app: Optional[Dict[str, Any]] = None,
                 ablations: Sequence[str] = RUN_ABLATIONS,
                 first: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The program on the batch ``tokens [B, S + 1]`` (``TransformerLM`` as
    the job path traces it: the configuration's dtype, the delta-rule, flash,
    rotary and grouped-matmul kernels where the device has them) against this
    file, from the same seeded parameters as the cell trains them. ``{"ok":
    bool, ...}``. ``program_app``: the PROGRAM's configuration where a test
    breaks the program on purpose (the reference keeps ``app``).
    ``ablations``: which of
    ``LOGIT_ABLATIONS`` the one compiled reference program also computes —
    ``fp8_operands`` always among them: it is the control. ``first``: a dict
    that receives the reference's ``loss`` and ``gradient`` on this batch —
    the replay's first step, which need not be computed twice.

    (a) LOGITS (``lm.apply`` against ``forward``), position by position:
    rounding moves EVERY position a little, and a near-tie in a 512-wide
    router sends a token to another expert on one side only, which moves a
    FEW positions a lot: two limits (``LIMITS``), the 90th percentile over
    positions and the RMS over all of them. Every ablation computed must
    read above the ``q90`` limit (``detected``).

    (b) GRADIENTS (``jax.value_and_grad(lm.loss)``, the function the trainer
    differentiates, against ``loss_grad_logits``), leaf by leaf
    (``gradient_errors``); the control is this file's own gradient with every
    product's operands rounded to float8: the program's error must stay under
    ``GRAD_LIMITS`` of the control's on every leaf.

    (c) the logits AGAIN from ``perturbed`` parameters on both sides, under
    (a)'s limits: what the initialisation cannot show."""
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
    # the limits are the STATED precision's (``app``), whatever a test
    # builds the program in
    dtype = jnp.dtype(app.get("dtype", "float32")).name
    limits, grad_limit = LIMITS[dtype], GRAD_LIMITS[dtype]
    clock = {"start": time.monotonic()}
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    apply = jax.jit(lm.apply)
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        # every gradient waits on the HOST: the device holds one tree at a
        # time beside whatever the process already keeps there
        got_loss, got_g = jax.device_get(
            jax.jit(jax.value_and_grad(lm.loss))(params, tokens))
        clock["program_gradient"] = time.monotonic()
        got = jax.block_until_ready(apply(params, inp))
        moved_params = perturbed(params, seed)
        del params
        got_moved = jax.block_until_ready(apply(moved_params, inp))
        del moved_params
    clock["program"] = time.monotonic()
    static = _Static(app)
    moved = {}
    with jax.default_matmul_precision("highest"):
        ref = init_params(app, seed)
        (ref_loss, want), want_g = loss_grad_logits(
            ref, tokens, static, flags_of(None))
        want_g = jax.device_get(want_g)
        program = position_errors(got, want)
        del got
        clock["reference"] = time.monotonic()
        for a in ablations:
            (_, broken), broken_g = loss_grad_logits(
                ref, tokens, static, flags_of(a))
            if a == "fp8_operands":
                control = gradient_errors(jax.device_get(broken_g), want_g,
                                          app)
            del broken_g
            moved[a] = {k: v for k, v in position_errors(broken, want).items()
                        if k in ("q90", "rms")}
            del broken
        clock["ablations"] = time.monotonic()
        (_, want_moved), _ = loss_grad_logits(
            perturbed(ref, seed), tokens, static, flags_of(None))
        second = position_errors(got_moved, want_moved)
        del got_moved, want_moved, ref
        clock["perturbed"] = time.monotonic()
    gradients = {"limit": grad_limit,
                 **against_control(gradient_errors(got_g, want_g, app),
                                   control),
                 "loss": abs(float(got_loss) - float(ref_loss))
                 / abs(float(ref_loss))}
    if first is not None:
        first.update(loss=float(ref_loss), gradient=want_g)
    del got_g, want_g
    detected = {a: bool(moved[a]["q90"] > limits["q90"]) for a in ablations}
    held = all(run[k] <= limits[k] for run in (program, second)
               for k in limits)
    held_g = gradients["worst"] <= grad_limit
    marks = list(clock.items())
    return {"ok": bool(held and held_g and all(detected.values())),
            "program": program, "perturbed": second, "limits": limits,
            "ablations": moved, "detected": detected, "gradients": gradients,
            "seconds": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
            "seed": int(seed), "dtype": dtype}


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
