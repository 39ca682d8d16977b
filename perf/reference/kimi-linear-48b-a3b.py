"""Plain reference for ``kimi-linear-48b-a3b``: forward, loss, gradients and
Adam by formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no chunks, no sort,
no table, no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops``
is used to compute it. It replays the job's first steps from the same seeded
initial parameters and the same batches and returns each step's loss.

The block is Kimi Linear's (arXiv:2510.26692; ``modeling_kimi.py`` beside the
checkpoint and flash-linear-attention's ``kda`` layer are the open
implementation), pre-norm RMSNorm throughout:

  * **KDA blocks** (``linear_layers``, 0-based here; the source's
    ``kda_layers`` counts from 1). On the normed input ``x_t``, ``H`` heads of
    ``dk = dv = linear_head_dim``: ``q~, k~, v~ = Wq x, Wk x, Wv x``; each
    through a depthwise CAUSAL convolution of ``short_conv`` taps over time
    (``y_t = sum_j w_j x_{t-(K-1)+j}``, no bias) and a SiLU; per head ``q_t =
    l2norm(q') dk^-1/2``, ``k_t = l2norm(k')`` (``x / sqrt(sum x^2 + 1e-6)``),
    ``v_t = v'``; the per-channel log-decay ``g_t = -exp(a_log_h) softplus(
    Wf_b Wf_a x_t + dt_bias)``; ``beta_t = sigmoid(w_b,h . x_t)``; the state
    ``S [dk, dv]``, ``S_0 = 0``,

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    computed HERE as a sequential ``lax.scan`` over the positions (the
    program computes it chunked: that is the check); the output ``Wo
    [rmsnorm over each head (o_t) * sigmoid(Wg_b Wg_a x_t)]``.
  * **Latent blocks** (every other block): DeepSeek-V3's latent attention
    with NO rotary at all (``mla_use_nope``): ``q = Wq x`` in heads of
    ``qk_nope + qk_rope``; ``Wkv_a x = [c | k_pe]``; ``Wkv_b rmsnorm(c)`` in
    heads of ``[k_nope | v]``; ``k = [k_nope | k_pe]``, the ONE ``k_pe`` shared
    by the heads and never turned; causal softmax at ``(nope + rope)^-1/2``.
  * **Channel mixers**: the first ``moe_first_dense`` blocks a gated-SiLU MLP
    of ``dense_d_ff``; after them ``s = sigmoid(Wr n2(h))``, the top-k of ``s +
    b`` (``b`` has no gradient and is zero as initialised), weights ``scale *
    s_e / (sum of the chosen s + 1e-20)``, gated-SiLU experts, one shared
    gated-SiLU MLP on every token.
  * A final norm, an untied head, float32 logits. Loss = cross-entropy +
    ``moe_aux_weight`` x the sequence-wise balance loss (arXiv:2412.19437 eq.
    17-20), the expert layers added.

Departures from the published model, all listed under ``assumed`` in
``perf/configs/kimi-linear-48b-a3b.json``: the output gate's second
projection has no bias; the selection bias is held at zero; the decay's and
the convolution's initial values are flash-linear-attention's.

The chip's share (the configuration file's ``deployment``): this file builds
the model with the heads, experts and vocabulary rows the chip holds —
``linear_heads`` and ``n_heads`` of the 32, experts ``0 .. moe_experts_held-1``
of the 256 (the router, its bias and the balance loss keep all 256; a token's
weights are normalised over all it chose), ``vocab_size`` rows.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM's logits on the first batch with this file's
(``check_logits``) and, if they disagree, returns losses that are not
numbers. Memory (465 M parameters on a 16 GB chip beside what the harness
holds): blocks are ``jax.checkpoint``ed, the KDA scan is checkpointed in runs
of 128 positions, and Adam is applied leaf by leaf with donated buffers; the
LAST update of a replay forms ``m^ / (sqrt(v^) + eps)`` from the gradient
and keeps no ``m``, ``v`` (two steps: parameters and one gradient live).
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
QUERY_BLOCK = 512
SCAN_RUN = 128
L2_EPS = 1e-6
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)

#: arithmetic broken on purpose, so that tests and every run's
#: ``check_logits`` can show a tolerance tells each apart
#: (``replay(..., ablate=...)``)
LOGIT_ABLATIONS = ("no_decay", "scalar_decay", "beta_one", "no_conv",
                   "no_l2norm", "no_out_gate", "rope_in_latent",
                   "softmax_scores", "fp8_operands")
ABLATIONS = LOGIT_ABLATIONS + ("no_aux",)
#: ``check_logits``, by the program's activation dtype: what the 90th
#: percentile over positions of the per-position relative error may reach,
#: and the relative RMS over all positions (reasons in its docstring)
LOGITS_Q90_TOL = {"bfloat16": 0.08, "float32": 1e-4}
LOGITS_RMS_TOL = {"bfloat16": 0.1, "float32": 1e-4}
#: the same two limits in the sharpened pass, where bfloat16 itself reads
#: more (sharper softmaxes amplify the same roundings)
SHARP_Q90_TOL = {"bfloat16": 0.14, "float32": 1e-4}
SHARP_RMS_TOL = {"bfloat16": 0.13, "float32": 1e-4}
#: ``check_logits``' second pass: what multiplies the latent blocks' ``wq``
#: and the routers on both sides, and the ablations it is there to show
SHARPEN_Q, SHARPEN_ROUTER = 4.0, 3.0
SHARP_ABLATIONS = ("rope_in_latent", "softmax_scores")


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, L, V = app["d_model"], app["n_layers"], app["vocab_size"]
    h, f = app["n_heads"], app["d_ff"]
    nope, rot = app["qk_nope_head_dim"], app["qk_rope_head_dim"]
    r, vd = app["kv_lora_rank"], app["v_head_dim"]
    Hl, dh, K = app["linear_heads"], app["linear_head_dim"], app["short_conv"]
    E = app["moe_experts"]
    H = app.get("moe_experts_held") or E
    first, fd = app["moe_first_dense"], app.get("dense_d_ff") or f
    fs = app["moe_shared_experts"] * f
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def normal(key, shape, scale=None):
        return jax.random.normal(key, shape, jnp.float32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    layers = []
    for i, kl in enumerate(k_layers):
        ks = jax.random.split(kl, 4)
        layer = {"ln1": jnp.ones((d,), jnp.float32),
                 "ln2": jnp.ones((d,), jnp.float32)}
        if i in app["linear_layers"]:
            kq, kk, kv, kcq, kck, kcv, kfa, kfb, ka, kdt, kb, kga, kgb = \
                jax.random.split(ks[0], 13)
            taps = lambda key: jax.random.uniform(
                key, (K, Hl * dh), jnp.float32, -K ** -0.5, K ** -0.5)
            dt = jnp.exp(jax.random.uniform(
                kdt, (Hl * dh,), jnp.float32, np.log(DT_RANGE[0]),
                np.log(DT_RANGE[1])))
            layer.update(
                kq=normal(kq, (d, Hl * dh)), kk=normal(kk, (d, Hl * dh)),
                kv=normal(kv, (d, Hl * dh)), cq=taps(kcq), ck=taps(kck),
                cv=taps(kcv), fa=normal(kfa, (d, dh)),
                fb=normal(kfb, (dh, Hl * dh)),
                a_log=jnp.log(jax.random.uniform(ka, (Hl,), jnp.float32,
                                                 *A_RANGE)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                wb=normal(kb, (d, Hl)), ga=normal(kga, (d, dh)),
                gb=normal(kgb, (dh, Hl * dh)),
                o_norm=jnp.ones((dh,), jnp.float32),
                ko=normal(ks[1], (Hl * dh, d)))
        else:
            kq, ka, kb = jax.random.split(ks[0], 3)
            layer.update(
                wq=normal(kq, (d, h * (nope + rot))),
                wkv_a=normal(ka, (d, r + rot)),
                kv_norm=jnp.ones((r,), jnp.float32),
                wkv_b=normal(kb, (r, h * (nope + vd))),
                wo=normal(ks[1], (h * vd, d)))
        if i < first:
            layer.update(wg=normal(ks[2], (d, fd)), wd=normal(ks[3], (fd, d)),
                         wu=normal(jax.random.fold_in(ks[2], 1), (d, fd)))
        else:
            kr, kg, ku, kd = jax.random.split(ks[2], 4)
            ksg, ksu, ksd = jax.random.split(jax.random.fold_in(ks[2], 1), 3)
            layer.update(
                router=normal(kr, (d, E)), bias=jnp.zeros((E,), jnp.float32),
                eg=normal(kg, (H, d, f)), eu=normal(ku, (H, d, f)),
                ed=normal(kd, (H, f, d)), sg=normal(ksg, (d, fs)),
                su=normal(ksu, (d, fs)), sd=normal(ksd, (fs, d)))
        layers.append(layer)
    return {
        "embed": normal(k_emb, (V, d), 0.02),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Rotate-half rotary positions on ``x [..., S, hd]`` (the
    ``rope_in_latent`` ablation only: the model has none)."""
    S, hd = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _pick(ablate, name, broken, whole):
    """``broken()`` where the ablation ``name`` is on, else ``whole()``.
    ``ablate`` is None (nothing broken: the replay's path, no select in its
    graph), a name, or a float32 vector of flags over ``LOGIT_ABLATIONS`` —
    ``check_logits`` passes that one traced, so ONE compiled program
    computes the reference and every ablation (a compile of this forward
    takes the chip's host ~33 s; ten of them were 330 s of every run)."""
    if ablate is None or isinstance(ablate, str):
        return broken() if ablate == name else whole()
    return jnp.where(ablate[LOGIT_ABLATIONS.index(name)] > 0, broken(), whole())


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the tolerances must
    refuse. The router's product stays float32 on both sides."""
    return lambda t: _pick(
        ablate, "fp8_operands",
        lambda: t.astype(jnp.float8_e4m3fn).astype(jnp.float32), lambda: t)


def _swiglu(t, wg, wu, wd, rnd=lambda t: t):
    t = rnd(t)
    return rnd(jax.nn.silu(t @ rnd(wg)) * (t @ rnd(wu))) @ rnd(wd)


def delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring for one head, position by
    position: ``q, k, g [S, dk]``, ``v [S, dv]``, ``beta [S]`` -> ``o [S,
    dv]``. Runs of ``SCAN_RUN`` positions are checkpointed (the backward
    keeps a state a run, not a state a position); no number changes."""
    S, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, None] * state
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


def _kda(xn, layer, app, ablate, rnd):
    """The KDA mixer on the normed input ``xn [B, S, d]`` -> ``[B, S, d]``."""
    B, S, _ = xn.shape
    H, dh = app["linear_heads"], app["linear_head_dim"]
    heads = lambda t: t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)

    def conv(t, taps):
        K = taps.shape[0]
        tp = jnp.pad(t, ((0, 0), (K - 1, 0), (0, 0)))
        return heads(jax.nn.silu(_pick(
            ablate, "no_conv", lambda: t,
            lambda: sum(tp[:, j:j + S] * taps[j] for j in range(K)))))

    def l2(t):
        return _pick(ablate, "no_l2norm", lambda: t, lambda: t / jnp.sqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS))

    x = rnd(xn)
    q = l2(conv(x @ rnd(layer["kq"]), layer["cq"])) * dh ** -0.5
    k = l2(conv(x @ rnd(layer["kk"]), layer["ck"]))
    v = conv(x @ rnd(layer["kv"]), layer["cv"])
    f = rnd(x @ rnd(layer["fa"])) @ rnd(layer["fb"]) + layer["dt_bias"]
    g = -jnp.exp(layer["a_log"])[None, :, None, None] * jax.nn.softplus(heads(f))
    g = _pick(ablate, "no_decay", lambda: jnp.zeros_like(g), lambda: g)
    g = _pick(ablate, "scalar_decay",  # gated DeltaNet's decay, not this one
              lambda: jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape),
              lambda: g)
    beta = jax.nn.sigmoid(xn @ layer["wb"]).transpose(0, 2, 1)   # [B, H, S]
    beta = _pick(ablate, "beta_one", lambda: jnp.ones_like(beta), lambda: beta)
    o = jax.vmap(jax.vmap(delta_rule))(rnd(q), rnd(k), rnd(v), g, beta)
    o = rms_norm(o, layer["o_norm"], app["norm_eps"])
    gate = jax.nn.sigmoid(heads(rnd(x @ rnd(layer["ga"])) @ rnd(layer["gb"])))
    o = _pick(ablate, "no_out_gate", lambda: o, lambda: o * gate)
    return rnd(o.transpose(0, 2, 1, 3).reshape(B, S, H * dh)) @ rnd(layer["ko"])


def _attention_one(q, k, v, scale, rnd=lambda t: t):
    """Causal softmax attention of one sequence, ``q, k [H, S, dqk]``,
    ``v [H, S, dv]``: the whole ``[S, S]`` score matrix, a block of query
    rows at a time."""
    S = q.shape[1]
    qb = min(QUERY_BLOCK, S)
    if S % qb:
        raise ValueError(f"{S} positions are not whole blocks of {qb} queries")

    @jax.checkpoint
    def rows(args):
        q_blk, row0 = args                                      # [H, qb, dqk]
        s = jnp.einsum("hqd,hkd->hqk", rnd(q_blk), rnd(k)) * scale
        ahead = (row0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(ahead, s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)), rnd(v))

    blocks = q.reshape(q.shape[0], S // qb, qb, -1).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], S, -1)


def _latent(xn, layer, app, ablate, rnd):
    """Latent attention with no rotary on ``xn [B, S, d]`` -> ``[B, S, d]``."""
    B, S, _ = xn.shape
    h = app["n_heads"]
    nope, rot = app["qk_nope_head_dim"], app["qk_rope_head_dim"]
    r, vd = app["kv_lora_rank"], app["v_head_dim"]
    heads = lambda t, w: t.reshape(B, S, h, w).transpose(0, 2, 1, 3)
    x = rnd(xn)
    q = heads(x @ rnd(layer["wq"]), nope + rot)
    ckv = x @ rnd(layer["wkv_a"])
    c, k_pe = ckv[..., :r], ckv[:, None, :, r:]                 # one key head
    kv = heads(rnd(rms_norm(c, layer["kv_norm"], app["norm_eps"]))
               @ rnd(layer["wkv_b"]), nope + vd)
    turn = lambda t: _pick(ablate, "rope_in_latent",
                           lambda: rotary(t, app["rope_theta"]), lambda: t)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
    k_pe = turn(k_pe)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, h, S, rot))], axis=-1)
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, (nope + rot) ** -0.5, rnd),
                    (q, k, kv[..., nope:]))
    return rnd(o.transpose(0, 2, 1, 3).reshape(B, S, h * vd)) @ rnd(layer["wo"])


def _block(x, layer, app, ablate):
    """One block on ``x [B, S, d]``: ``(x, sequence-wise balance term or 0,
    token-slots by expert or None)``."""
    B, S, d = x.shape
    eps = app["norm_eps"]
    rnd = _operands(ablate)
    mixer = _kda if "kq" in layer else _latent
    hid = x + mixer(rms_norm(x, layer["ln1"], eps), layer, app, ablate, rnd)
    t = rms_norm(hid, layer["ln2"], eps)
    if "router" not in layer:  # a leading dense layer
        return (hid + _swiglu(t, layer["wg"], layer["wu"], layer["wd"], rnd),
                0.0, None)
    # the experts, on [T, d]
    E, top_k = app["moe_experts"], app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    t = t.reshape(B * S, d)
    logits = t @ layer["router"]                                 # [T, E]
    score = _pick(ablate, "softmax_scores",
                  lambda: jax.nn.softmax(logits, axis=-1),
                  lambda: jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(layer["bias"]),
                              top_k)                             # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = score * mask
    if app["moe_norm_topk"]:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * app["moe_routed_scale"]
    y = jnp.zeros_like(t)
    for e in range(H):  # every held expert on every token, weighted
        y = y + weight[:, e:e + 1] * _swiglu(t, layer["eg"][e], layer["eu"][e],
                                             layer["ed"][e], rnd)
    y = y + _swiglu(t, layer["sg"], layer["su"], layer["sd"], rnd)
    # eq. 17-20, a sequence at a time: f counts (no gradient), P is the mean
    # score normalised over the experts
    f = jax.lax.stop_gradient(mask).reshape(B, S, E).sum(axis=1) * (
        E / (top_k * S))
    p = (score / score.sum(axis=-1, keepdims=True)).reshape(B, S, E).mean(axis=1)
    return hid + y.reshape(B, S, d), jnp.sum(f * p, axis=-1).mean(), mask.sum(axis=0)


def forward(params, inp, app, ablate=None):
    """``(logits [B, S, V], balance term summed over the expert layers,
    [token-slots by expert of each expert layer])``. ``ablate``:
    :func:`_pick`'s."""
    x = params["embed"][inp]
    if ablate == "no_aux":  # the loss's, not the logits'
        ablate = None
    block = jax.checkpoint(lambda x, layer: _block(x, layer, app, ablate))
    aux, chosen = 0.0, []
    for layer in params["layers"]:
        x, a, n = block(x, layer)
        aux = aux + a
        if n is not None:
            chosen.append(n)
    rnd = _operands(ablate)
    return (rnd(rms_norm(x, params["ln_f"], app["norm_eps"])) @ rnd(params["head"]),
            aux, chosen)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss_terms(params, tokens, app, ablate: Optional[str] = None):
    """``(cross-entropy, sequence-wise balance)`` of ``tokens[:, :-1] ->
    tokens[:, 1:]``, the second before its weight."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, aux, _ = forward(params, inp, app, ablate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tgt[..., None], axis=-1).mean(), aux


def loss_fn(params, tokens, app, ablate: Optional[str] = None):
    ce, aux = loss_terms(params, tokens, app, ablate)
    return ce + (0.0 if ablate == "no_aux" else app["moe_aux_weight"]) * aux


QUANTILES = (0.5, 0.75, 0.9, 0.99)
DIVERGED = 1e9


def position_errors(a, b) -> Dict[str, float]:
    """Relative error of ``a`` against ``b [B, S, V]`` position by position
    (each position's error vector over its logit vector, in norm): the
    overall relative RMS and quantiles over the positions. A position that
    is not finite reads ``DIVERGED`` (without the l2 norm the delta rule's
    state grows without bound), a number a JSON line can carry."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    per = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / jnp.sum(b ** 2, axis=-1))
    per = jnp.where(jnp.isfinite(per), per, DIVERGED).reshape(-1)
    qs = jnp.quantile(per, jnp.asarray(QUANTILES), method="lower")
    rms = jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2))
    return {"rms": float(jnp.where(jnp.isfinite(rms), rms, DIVERGED)),
            **{f"q{int(100 * q)}": float(v) for q, v in zip(QUANTILES, qs)}}


def sharpened(layers, names):
    """``layers`` with every latent block's ``wq`` times ``SHARPEN_Q`` and
    every router times ``SHARPEN_ROUTER`` (``names``: the two leaves' paths
    in this tree, the program's or this file's)."""
    wq, router = names
    out = []
    for layer in layers:
        layer = dict(layer)
        if wq in layer:
            layer[wq] = layer[wq] * SHARPEN_Q
        holder = layer.get("moe", layer)
        if router in holder:
            holder = {**holder, router: holder[router] * SHARPEN_ROUTER}
            layer = {**layer, "moe": holder} if "moe" in layer else holder
        out.append(layer)
    return out


def check_logits(app: Dict[str, Any], inp, seed: int) -> Dict[str, Any]:
    """The program's logits on ``inp [B, S]`` (``TransformerLM.apply`` as the
    job path traces it: the configuration's dtype, the KDA, flash and
    grouped-matmul kernels where the device has them) against ``forward`` of
    this file, from the same seeded parameters, on every position of every
    sequence. Two passes: the parameters as initialised (as the cell
    trains), then the same with the latent blocks' ``wq`` times
    ``SHARPEN_Q`` and the routers times ``SHARPEN_ROUTER`` on both sides —
    as initialised the one latent block's attention is nearly uniform over
    its keys and the router's scores lie within a unit of each other, so
    turning the rope parts or scoring by softmax moves the logits hardly
    more than bfloat16 does (0.038-0.042 and 0.076-0.134 against the
    program's 0.035-0.044); sharpened, the attention has keys to prefer and
    the scores spread, and both ablations read several times the program.
    ``{"ok": bool, ...}``.

    The error is taken position by position (``position_errors``), as in
    Moonlight's cell and for its reason: rounding moves EVERY position a
    little, and a near-tie in the 256-wide router sends a token to another
    expert on one side only, which moves a FEW positions a lot. So two
    limits, by the program's dtype and by the pass: the 90TH PERCENTILE over
    positions (what every ablation is judged by, its own 90th percentile
    against the reference's logits: ``rope_in_latent`` and
    ``softmax_scores`` in the sharpened pass, the other seven as
    initialised) and the RMS over all positions, which bounds the tail: a
    wrong chunk of 64 of 8,192 positions would not move the percentile and
    does move the RMS. float32 (the CPU rehearsal and tests): 1e-4 for
    both, summation order only (chunked against sequential). The bfloat16
    limits and the readings they lie between are in
    ``perf/configs/kimi-linear-48b-a3b.json`` ``job.why.loss_rtol``. Every
    ablation is computed again on every call, and the check fails unless
    each lies above the limit: it is shown to tell them apart on the run
    that uses it."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    inp = jnp.asarray(inp)
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    limits = {"as_initialised": {"q90": LOGITS_Q90_TOL[dtype],
                                 "rms": LOGITS_RMS_TOL[dtype]},
              "sharpened": {"q90": SHARP_Q90_TOL[dtype],
                            "rms": SHARP_RMS_TOL[dtype]}}
    clock = {"start": time.monotonic()}
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        apply = jax.jit(lm.apply)
        got = apply(params, inp)
        params["layers"] = sharpened(params["layers"], ("wq", "router"))
        got_sharp = jax.block_until_ready(apply(params, inp))
    del params
    clock["program"] = time.monotonic()
    static = _Static(app)
    logits_of = jax.jit(lambda p, t, flags: forward(p, t, static, flags)[0])

    def run(p, ablate):  # one program: the ablation is a vector of flags
        flags = np.zeros(len(LOGIT_ABLATIONS), np.float32)
        if ablate is not None:
            flags[LOGIT_ABLATIONS.index(ablate)] = 1.0
        return logits_of(p, inp, flags)

    with jax.default_matmul_precision("highest"):
        ref = init_params(app, seed)
        want = run(ref, None)
        program = {"as_initialised": position_errors(got, want)}
        del got
        clock["reference"] = time.monotonic()
        moved = {a: position_errors(run(ref, a), want)["q90"]
                 for a in LOGIT_ABLATIONS}
        judged = {a: "sharpened" if a in SHARP_ABLATIONS else "as_initialised"
                  for a in LOGIT_ABLATIONS}
        as_initialised = {a: moved[a] for a in SHARP_ABLATIONS}
        del want
        clock["ablations"] = time.monotonic()
        ref["layers"] = sharpened(ref["layers"], ("wq", "router"))
        want = run(ref, None)
        program["sharpened"] = position_errors(got_sharp, want)
        moved.update({a: position_errors(run(ref, a), want)["q90"]
                      for a in SHARP_ABLATIONS})
        clock["sharpened"] = time.monotonic()
    marks = list(clock.items())
    return {"ok": bool(all(program[p][k] <= limits[p][k]
                           for p in program for k in ("q90", "rms"))
                       and all(m > limits[judged[a]]["q90"]
                               for a, m in moved.items())),
            "program": program, "limits": limits,
            "ablations_q90": moved, "as_initialised_q90": as_initialised,
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
    ``ABLATIONS``. First, unless ``logits`` is off or an ablation is asked
    for, ``check_logits`` on the first batch: its report is printed as one
    JSON line, and where it fails every loss returned is ``nan``, which no
    tolerance accepts. The last step's gradient is never taken (its loss is
    computed before its update)."""
    if app.get("optimizer") != "adam":
        raise ValueError("this reference implements Adam only")
    if ablate is not None and ablate not in ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    tokens = np.asarray(data[0])
    nb = tokens.shape[0] // batch
    if logits and ablate is None:
        report = check_logits(dict(app), tokens[:batch, :-1], seed)
        print(json.dumps({"line": "logits_check", **report}), flush=True)
        if not report["ok"]:
            return [float("nan")] * steps
    lr, b2 = float(app["step_size"]), float(app.get("beta2") or 0.999)
    app = _Static(app)

    def seq_mean(fn):
        # a sequence at a time (both loss terms are means over sequences of
        # one length): one sequence's float32 activations are what fits
        def mean(params, toks):
            out = jax.lax.map(lambda t: fn(params, t[None], app, ablate), toks)
            return jax.tree.map(lambda a: a.mean(axis=0), out)
        return jax.jit(mean)

    loss_of = seq_mean(loss_fn)
    loss_and_grad = seq_mean(jax.value_and_grad(loss_fn))
    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = v = None
        for i in range(steps):
            toks = jnp.asarray(tokens[(i % nb) * batch:(i % nb + 1) * batch])
            if i == steps - 1:
                losses.append(float(loss_of(params, toks)))
                break
            loss, g = loss_and_grad(params, toks)
            losses.append(float(loss))
            if i == steps - 2 and m is None:
                params = jax.tree.map(
                    lambda p, a: _adam_first_and_last(p, a, lr), params, g)
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
