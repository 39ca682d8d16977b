"""Plain reference for ``zaya1-8b``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no sort, no table,
no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops`` is used
to compute it. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

One ``hybrid`` layer of ZAYA1-8B (``config.json`` of ``Zyphra/ZAYA1-8B``; the
switches of ``Zyphra/ZAYA1-base``; CCA: arXiv:2510.04476; the model:
arXiv:2511.17127), input the residual stream ``x [S, d]`` and the router
state ``r [S, R]`` of the layer before (none before the first layer).
``prev(t)`` is ``t`` moved one position later, zeros at position 0.

  attention sublayer (CCA)
    h   = RMSNorm(x; g1)
    q~  = h Wq -> [S, H, hd];  k~ = h Wk -> [S, Hkv, hd]
    v   = [h Wv_now | prev(h) Wv_prev] -> [S, Hkv, hd]   # the last half of
                                       # the K/V heads: the previous position
    c   = [q~ | k~]                                      # [S, (H + Hkv) hd]
    c1  = w0[0] * prev(c) + w0[1] * c + b0               # depthwise
    c2[g] = prev(c1)[g] W1[0, g] + c1[g] W1[1, g] + b1[g]  # a head at a time
    q   = c2[:H]  + (q~_i + k~_kv(i)) / 2,   kv(i) = i // (H / Hkv)
    k   = c2[H:] + (mean_{i in group j} q~_i + k~_j) / 2
    q   = sqrt(hd) q / |q|;   k = tau_j sqrt(hd) k / |k|   # a head, a position
    q, k = rotary on the first ``rope_fraction hd`` columns of a head
    o_i = softmax(q_i k_kv(i)^T / sqrt(hd) + causal mask) v_kv(i)
    x   = merge(x, concat_i(o_i) Wo; m1)

  expert sublayer
    h   = RMSNorm(x; g2)
    z   = h Wd + bd  (+ gamma * r, from the second layer on);  r' = z
    u   = RMSNorm(z; gr)
    p   = softmax(gelu(gelu(u W1 + b1) W2 + b2) W3)        # E + 1 outputs
    e   = argmax(p + beta);  g = p[e]                      # top-1, as it is
    y   = g (silu(h Wg_e) * h Wu_e) Wdown_e  if e < E and e is held, else 0
    x   = merge(x, y; m2)

  merge(x, y; m) = m[0] * (x + m[1]) + m[2] * (y + m[3])

K and V are repeated to ``H`` heads with ``jnp.repeat``, the mask is an
explicit boolean, and attention runs a block of ``QUERY_BLOCK`` query rows at
a time so that ``H x S x S`` scores never exist at once. Then the final
RMSNorm and the readout through the embedding's transpose; loss = mean
next-token cross-entropy (no balance loss, no z-loss: ZAYA1 balances by
``beta`` alone, whose update is a recipe step outside ``config.json``).

The chip's share (the configuration file's ``deployment``): experts ``0 ..
moe_experts_held-1`` of each layer and ``vocab_size`` rows are given as
arguments (``app``); the router keeps all ``E + 1`` outputs.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM's logits on the first batch with this file's, position
by position, and the gradient of the PROGRAM's loss with this file's, leaf by
leaf (``check_logits``), under seeded NON-TRIVIAL values of everything that is
an identity as initialised (``seeded_identities``), and, if they disagree,
returns losses that are not numbers.
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
TAPS = 2  # cca_time0 = cca_time1 = 2

#: arithmetic broken on purpose, so that tests and every run's
#: ``check_logits`` can show a tolerance tells each apart
#: (``replay(..., ablate=...)``)
LOGIT_ABLATIONS = ("no_conv0", "no_conv1", "no_mean", "no_value_shift",
                   "no_l2", "tau_one", "rope_all", "no_eda", "no_router_norm",
                   "null_to_expert0", "bias_in_weight", "plain_merge",
                   "fp8_operands")
ABLATIONS = LOGIT_ABLATIONS
#: ``check_logits``' limits by the program's activation dtype: the 90th
#: percentile over positions of the per-position relative error, and the
#: relative RMS over all positions. Readings and reasons:
#: ``perf/configs/zaya1-8b.json`` ``job.why.loss_rtol``
LIMITS = {"bfloat16": {"q90": 0.02, "rms": 0.04},
          "float32": {"q90": 1e-4, "rms": 1e-4}}
#: ``check_logits``' limit on the program's first gradient: its error
#: ``|g - g_ref|`` as a share of what float8 operands do to the same leaf,
#: the leaf's layers taken together (``against_control``), the worst leaf.
#: Readings and reasons: the same place
GRAD_LIMITS = {"bfloat16": 0.3, "float32": 1e-3}
#: how far ``seeded_identities`` moves each identity: the temperature and the
#: EDA scale multiplicatively, the merges' scale on the stream around 1 and
#: on the sublayer's output around ``MERGE_Y`` (attention's, the experts':
#: under unit embedding rows the stream is ~45 in norm, a sublayer adds a few
#: units and a top-1 weight out of a 17-way softmax is ~0.06-0.1, so at a
#: scale of 1 the weakest ablations of the expert sublayer moved the logits
#: by 2-3%, my chip runs, PR 45), the merges' and the convolutions' biases
#: around 0; the selection bias as wide as the
#: router's probabilities spread as initialised (~0.02 around 1 / 17) and
#: then as wide again, so that it decides most choices,
#: leaning toward the held experts (whose results are computed here) and
#: toward "no expert" (so that a share of the slots takes it)
IDENT_STD = {"tau": 0.3, "gamma": 0.5, "merge_scale": 0.3, "merge_bias": 0.2,
             "conv_bias": 0.3, "beta": 0.04}
BETA_HELD, BETA_NULL = 0.02, 0.08
#: the temperature's seeded values lie around exp(TAU_LOG) = 2.2, not around
#: 1: between L2-normed random q and k the scores spread by ~1, attention is
#: near uniform whatever the temperature, and at values around 1 ``tau_one``
#: and ``no_l2`` read 0.013-0.21 from seed to seed over six seeds (two under
#: the limit: my chip runs, PR 45). A trained temperature sharpens
TAU_LOG = 0.8
MERGE_Y = (6.0, 16.0)


def widths(app):
    """``(H, Hkv, hd)``: query heads, key/value heads, a head's width."""
    h = app["n_heads"]
    return h, app.get("n_kv_heads") or h, (
        app.get("mha_head_dim") or app["d_model"] // h)


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names. As initialised ``tau`` and ``gamma`` are 1,
    ``beta`` 0 with -1 on "no expert", the merges ``[1, 0, 1, 0]`` and the
    convolutions' biases 0; the router's second and third matrices are
    centred down their fan-in axis (the configuration's ``assumed.init``)."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    E, R = app["moe_experts"], app["moe_router_hidden"]
    held = app.get("moe_experts_held") or E
    out = E + bool(app.get("moe_null_expert"))
    H, Hkv, hd = widths(app)
    G = H + Hkv
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)
    f32 = jnp.float32

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) * fan_in ** -0.5

    centred = lambda w: w - w.mean(axis=0)
    # a buffer each: the replay's Adam donates every leaf
    merge = lambda: jnp.stack([jnp.ones((d,), f32), jnp.zeros((d,), f32)] * 2)
    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        wqkv = normal(ks[0], (d, (H + 2 * Hkv) * hd), d)
        wq, wk, wv = jnp.split(wqkv, (H * hd, G * hd), axis=-1)
        k0, k1 = jax.random.split(jax.random.fold_in(ks[0], 1))
        kr, kg, ku, kd = jax.random.split(ks[2], 4)
        krd, kr1, kr2, kr3 = jax.random.split(kr, 4)
        layers.append({
            "g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32),
            "wq": wq, "wk": wk,
            "wv_now": wv[:, :Hkv // 2 * hd], "wv_prev": wv[:, Hkv // 2 * hd:],
            "w0": jax.random.uniform(k0, (TAPS, G * hd), f32,
                                     -TAPS ** -0.5, TAPS ** -0.5),
            "b0": jnp.zeros((G * hd,), f32),
            "W1": normal(k1, (TAPS, G, hd, hd), TAPS * hd),
            "b1": jnp.zeros((G * hd,), f32),
            "tau": jnp.ones((Hkv,), f32),
            "wo": normal(ks[1], (H * hd, d), H * hd),
            "m1": merge(), "m2": merge(),
            "r_wd": normal(krd, (d, R), d), "r_bd": jnp.zeros((R,), f32),
            "gamma": jnp.ones((R,), f32), "gr": jnp.ones((R,), f32),
            "r_w1": normal(kr1, (R, R), R), "r_b1": jnp.zeros((R,), f32),
            "r_w2": centred(normal(kr2, (R, R), R)),
            "r_b2": jnp.zeros((R,), f32),
            "r_w3": centred(normal(kr3, (R, out), R)),
            "beta": jnp.zeros((out,), f32).at[E:].set(-1.0),
            "eg": normal(kg, (held, d, f), d), "eu": normal(ku, (held, d, f), d),
            "ed": normal(kd, (held, f, d), f)})
    return {"embed": jax.random.normal(k_emb, (V, d), f32)
            * app.get("embed_std", 0.02),
            "ln_f": jnp.ones((d,), f32), "layers": layers}


def seeded_identities(app: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """A layer each, under this file's names: seeded values for every leaf
    that is an identity as initialised (``IDENT_STD``), so that a run can
    SEE them. Given to program and reference alike by ``check_logits``."""
    d, E = app["d_model"], app["moe_experts"]
    held = app.get("moe_experts_held") or E
    out = E + bool(app.get("moe_null_expert"))
    H, Hkv, hd = widths(app)
    c, s = (H + Hkv) * hd, IDENT_STD
    f32 = jnp.float32
    layers = []
    for i in range(app["n_layers"]):
        ks = jax.random.split(jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), 45), i), 8)
        draw = lambda j, shape: jax.random.normal(ks[j], shape, f32)

        def merge(j, y_scale):
            n = draw(j, (4, d))
            return jnp.stack([1 + s["merge_scale"] * n[0], s["merge_bias"] * n[1],
                              y_scale + s["merge_scale"] * n[2],
                              s["merge_bias"] * n[3]])

        beta = s["beta"] * draw(2, (out,)) + BETA_HELD * (jnp.arange(out) < held)
        if out > E:  # "no expert" leans by BETA_NULL in every layer alike
            beta = beta.at[E].set(BETA_NULL)
        layers.append({
            "tau": jnp.exp(TAU_LOG + s["tau"] * draw(0, (Hkv,))),
            "gamma": 1 + s["gamma"] * draw(1, (app["moe_router_hidden"],)),
            "beta": beta,
            "m1": merge(3, MERGE_Y[0]), "m2": merge(4, MERGE_Y[1]),
            "b0": s["conv_bias"] * draw(5, (c,)),
            "b1": s["conv_bias"] * draw(6, (c,))})
    return layers


def as_program(ident: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """One layer of ``seeded_identities`` under the PROGRAM's names, by the
    sub-tree of a layer it belongs to (``""``: the layer itself)."""
    return {"cca": {"temp": ident["tau"], "conv0_b": ident["b0"],
                    "conv1_b": ident["b1"]},
            "moe": {"r_eda": ident["gamma"], "bias": ident["beta"]},
            "": {"merge1": ident["m1"], "merge2": ident["m2"]}}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def prev(t):
    """``t [B, S, ...]`` one position later: position 0 reads zeros."""
    return jnp.concatenate([jnp.zeros_like(t[:, :1]), t[:, :-1]], axis=1)


def rotary(x, theta, width):
    """Rotate-half rotary positions on the first ``width`` columns of ``x
    [..., S, hd]``, positions 0..S-1, as a head ``width`` wide would turn."""
    S = x.shape[-2]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    t, rest = x[..., :width], x[..., width:]
    t1, t2 = jnp.split(t, 2, axis=-1)
    return jnp.concatenate(
        [t * cos + jnp.concatenate([-t2, t1], axis=-1) * sin, rest], axis=-1)


def _flag(ablate, name):
    """Whether the ablation ``name`` is on: a Python bool where ``ablate`` is
    None or a name, a traced bool where it is a float32 vector of flags over
    ``LOGIT_ABLATIONS`` — ``check_logits`` passes that one, so ONE compiled
    program computes the reference and every ablation — or a dict of traced
    bools by name (every other name: off)."""
    if ablate is None or isinstance(ablate, str):
        return ablate == name
    if isinstance(ablate, dict):  # ``loss_and_grad``: the named ones alone
        return ablate.get(name, False)
    return ablate[LOGIT_ABLATIONS.index(name)] > 0


def _pick(ablate, name, broken, whole):
    """``broken()`` where the ablation ``name`` is on, else ``whole()``."""
    on = _flag(ablate, name)
    if isinstance(on, bool):
        return broken() if on else whole()
    return jnp.where(on, broken(), whole())


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the tolerances must
    refuse. The router's products stay float32 on both sides."""
    return lambda t: _pick(
        ablate, "fp8_operands",
        lambda: t.astype(jnp.float8_e4m3fn).astype(jnp.float32), lambda: t)


def _attention_one(q, k, v, rnd):
    """Causal softmax attention of one sequence, ``q, k, v [H, S, hd]`` (K
    and V already repeated to the query heads): an explicit ``[rows, S]``
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


def merge(x, y, m, ablate):
    return _pick(ablate, "plain_merge", lambda: x + y,
                 lambda: m[0] * (x + m[1]) + m[2] * (y + m[3]))


def attention_sublayer(x, p, app, ablate):
    """``x [B, S, d]`` after the CCA sublayer and its merge."""
    B, S, d = x.shape
    H, Hkv, hd = widths(app)
    G, rep, eps = H + Hkv, H // Hkv, app["norm_eps"]
    rnd = _operands(ablate)
    h = rms_norm(x, p["g1"], eps)
    q0 = (rnd(h) @ rnd(p["wq"])).reshape(B, S, H, hd)
    k0 = (rnd(h) @ rnd(p["wk"])).reshape(B, S, Hkv, hd)
    h_prev = _pick(ablate, "no_value_shift", lambda: h, lambda: prev(h))
    v = jnp.concatenate([rnd(h) @ rnd(p["wv_now"]),
                         rnd(h_prev) @ rnd(p["wv_prev"])],
                        axis=-1).reshape(B, S, Hkv, hd)
    c = jnp.concatenate([q0.reshape(B, S, H * hd), k0.reshape(B, S, Hkv * hd)],
                        axis=-1)
    c1 = _pick(ablate, "no_conv0", lambda: c,
               lambda: p["w0"][0] * prev(c) + p["w0"][1] * c + p["b0"])
    c1 = c1.reshape(B, S, G, hd)
    c2 = _pick(ablate, "no_conv1", lambda: c1, lambda: (
        jnp.einsum("bsgc,gcd->bsgd", rnd(prev(c1)), rnd(p["W1"][0]))
        + jnp.einsum("bsgc,gcd->bsgd", rnd(c1), rnd(p["W1"][1]))
        + p["b1"].reshape(G, hd)))
    q_mean = (q0 + jnp.repeat(k0, rep, axis=2)) / 2
    k_mean = (q0.reshape(B, S, Hkv, rep, hd).mean(axis=3) + k0) / 2
    q = c2[:, :, :H] + _pick(ablate, "no_mean", lambda: 0.0 * q_mean,
                             lambda: q_mean)
    k = c2[:, :, H:] + _pick(ablate, "no_mean", lambda: 0.0 * k_mean,
                             lambda: k_mean)
    unit = lambda t: hd ** 0.5 * t / jnp.sqrt(
        jnp.sum(t * t, axis=-1, keepdims=True))
    tau = _pick(ablate, "tau_one", lambda: jnp.ones_like(p["tau"]),
                lambda: p["tau"])
    q = _pick(ablate, "no_l2", lambda: q, lambda: unit(q))
    k = _pick(ablate, "no_l2", lambda: k, lambda: unit(k)) * tau[:, None]
    heads = lambda t: t.transpose(0, 2, 1, 3)                   # [B, h, S, hd]
    turned = int(round(app.get("rope_fraction", 1.0) * hd))
    turn = lambda t: _pick(ablate, "rope_all",
                           lambda: rotary(t, app["rope_theta"], hd),
                           lambda: rotary(t, app["rope_theta"], turned))
    q, k, v = turn(heads(q)), turn(heads(k)), heads(v)
    spread = lambda t: jnp.repeat(t, rep, axis=1)  # query head i: K/V i // rep
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, rnd),
                    (q, spread(k), spread(v)))
    y = rnd(o.transpose(0, 2, 1, 3).reshape(B, S, H * hd)) @ rnd(p["wo"])
    return merge(x, y, p["m1"], ablate)


def route(h, r, p, app, ablate):
    """``(expert [T], weight [T], state [T, R], probabilities [T, E + 1])``
    of the rows ``h [T, d]``, the router before having left ``r``."""
    E = app["moe_experts"]
    z = h @ p["r_wd"] + p["r_bd"]
    if r is not None:
        z = z + _pick(ablate, "no_eda", lambda: 0.0 * r,
                      lambda: p["gamma"] * r)
    u = _pick(ablate, "no_router_norm", lambda: z,
              lambda: rms_norm(z, p["gr"], app["norm_eps"]))
    gelu = functools.partial(jax.nn.gelu, approximate=False)
    logits = gelu(gelu(u @ p["r_w1"] + p["r_b1"]) @ p["r_w2"] + p["r_b2"]) \
        @ p["r_w3"]
    prob = jax.nn.softmax(logits, axis=-1)
    beta = jax.lax.stop_gradient(p["beta"])
    e = jnp.argmax(prob + beta, axis=-1)
    weigh = _pick(ablate, "bias_in_weight", lambda: prob + beta, lambda: prob)
    g = jnp.take_along_axis(weigh, e[:, None], axis=-1)[:, 0]
    if app.get("moe_null_expert"):
        e = _pick(ablate, "null_to_expert0",
                  lambda: jnp.where(e == E, 0, e), lambda: e)
    return e, g, z, prob


def expert_sublayer(x, r, p, app, ablate):
    """``(x, state, slots by output [E + 1])`` after the expert sublayer and
    its merge; ``state`` is what the next layer's router is handed."""
    B, S, d = x.shape
    E = app["moe_experts"]
    held = app.get("moe_experts_held") or E
    rnd = _operands(ablate)
    h = rms_norm(x, p["g2"], app["norm_eps"]).reshape(B * S, d)
    e, g, z, prob = route(h, r, p, app, ablate)
    y = jnp.zeros_like(h)
    hr = rnd(h)
    for i in range(held):  # every held expert on every token, then chosen
        hidden = jax.nn.silu(hr @ rnd(p["eg"][i])) * (hr @ rnd(p["eu"][i]))
        y = y + jnp.where(e == i, g, 0.0)[:, None] * (
            rnd(hidden) @ rnd(p["ed"][i]))
    chosen = jnp.sum(e[:, None] == jnp.arange(prob.shape[-1])[None, :], axis=0)
    return merge(x, y.reshape(B, S, d), p["m2"], ablate), z, chosen


def forward(params, inp, app, ablate=None, layers: Optional[int] = None):
    """``(logits [B, S, V], slots by layer and output [L, E + 1])``.
    ``ablate``: :func:`_flag`'s."""
    x = params["embed"][inp]
    r, chosen = None, []
    for p in params["layers"][:layers]:
        x = jax.checkpoint(functools.partial(
            attention_sublayer, app=app, ablate=ablate))(x, p)
        x, r, n = jax.checkpoint(functools.partial(
            expert_sublayer, app=app, ablate=ablate))(x, r, p)
        chosen.append(n)
    rnd = _operands(ablate)
    x = rms_norm(x, params["ln_f"], app["norm_eps"])
    return rnd(x) @ rnd(params["embed"]).T, jnp.stack(chosen)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def next_token_loss(logits, targets):
    """Mean cross-entropy of ``logits [B, S, V]`` at ``targets [B, S]``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss_fn(params, tokens, app, ablate=None):
    logits, _ = forward(params, tokens[:, :-1], app, ablate)
    return next_token_loss(logits, tokens[:, 1:])


@functools.partial(jax.jit, static_argnames=("app", "ablate"))
def loss_and_grad(params, tokens, app, ablate, fp8):
    """``(loss, gradient)`` of ``loss_fn`` — ONE compiled program for the
    replay's steps, ``check_logits``' reference gradient and, with the traced
    ``fp8`` on, its control in the precision below. ``app``: a ``_Static``;
    ``ablate``: a name or None. No argument has a default: one left out
    would be a constant of another program, compiled again."""
    return jax.value_and_grad(loss_fn)(
        params, tokens, app,
        ablate if ablate is not None else {"fp8_operands": fp8})


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


def from_program(tree: Dict[str, Any], app: Dict[str, Any]) -> Dict[str, Any]:
    """A parameter (or gradient) tree of the PROGRAM under this file's
    names: ``wqkv``'s column blocks apart, the sub-trees flat."""
    H, Hkv, hd = widths(app)
    q, k, now = H * hd, (H + Hkv) * hd, (H + Hkv) * hd + Hkv // 2 * hd

    def layer(l):
        w, c, m = l["wqkv"], l["cca"], l["moe"]
        return {"g1": l["ln1"], "g2": l["ln2"], "wq": w[:, :q],
                "wk": w[:, q:k], "wv_now": w[:, k:now], "wv_prev": w[:, now:],
                "w0": c["conv0"], "b0": c["conv0_b"], "W1": c["conv1"],
                "b1": c["conv1_b"], "tau": c["temp"], "wo": l["wo"],
                "m1": l["merge1"], "m2": l["merge2"], "r_wd": m["r_down"],
                "r_bd": m["r_down_b"], "gamma": m["r_eda"], "gr": m["r_norm"],
                "r_w1": m["r_w1"], "r_b1": m["r_b1"], "r_w2": m["r_w2"],
                "r_b2": m["r_b2"], "r_w3": m["r_w3"], "beta": m["bias"],
                "eg": m["wg"], "eu": m["wu"], "ed": m["wd"]}
    return {"embed": tree["embed"], "ln_f": tree["ln_f"],
            "layers": [layer(l) for l in tree["layers"]]}


def gradient_errors(got, want) -> Dict[str, List[float]]:
    """``[|got - want|^2, |want|^2]`` of every leaf, summed over the layers
    that have it (both trees under this file's names, on the host). A leaf
    no gradient reaches on ``want``'s side (``beta``; the first layer's
    ``gamma``) must be all zeros on the other, or its error is
    ``DIVERGED``."""
    def add(row, a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err, norm = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        if not np.isfinite(err) or (norm == 0.0 and np.any(a)):
            err = DIVERGED
        row[0] += err
        row[1] += norm

    sums = {}
    for name in ("embed", "ln_f"):
        add(sums.setdefault(name, [0.0, 0.0]), got[name], want[name])
    for a, b in zip(got["layers"], want["layers"]):
        for name in b:
            add(sums.setdefault(name, [0.0, 0.0]), a[name], b[name])
    return sums


def against_control(program, control) -> Dict[str, Any]:
    """The program's ``gradient_errors`` as a share of the control's, leaf
    by leaf: ``{"worst", "worst_leaf", "by_leaf": {leaf: [the program's
    relative error, the control's, their ratio]}}``, a leaf's layers taken
    together — a sum that nearly cancels in ONE layer (the temperature's
    two elements) magnifies every rounding there and, alone, would make
    either error follow the seed. Where the control reads 0 the program
    must."""
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


def with_identities(params, idents):
    """``params`` (this file's names) with ``seeded_identities`` written in."""
    return {**params, "layers": [{**p, **i} for p, i in
                                 zip(params["layers"], idents)]}


def check_logits(app: Dict[str, Any], tokens, seed: int) -> Dict[str, Any]:
    """The program on the batch ``tokens [B, S + 1]`` (``TransformerLM`` as
    the job path traces it: the configuration's dtype, the flash and
    grouped-matmul kernels where the device has them) against this file,
    from the same seeded parameters as the cell trains them BUT with
    ``seeded_identities`` written into both: as initialised the temperature,
    the EDA scale, the selection bias, the eight merge vectors and the
    convolutions' biases are ones and zeros, "no expert" is never chosen,
    and an error in any of them could not be seen. ``{"ok": bool, ...}``.

    LOGITS (``lm.apply`` against ``forward``), position by position.
    Rounding moves EVERY position a little, and a near-tie in the router
    sends a token to another expert (or to none) on one side only, which
    moves a FEW positions a lot: so two limits (``LIMITS``), the 90th
    percentile over positions and the RMS over all of them (which bounds the
    tail), and the program must hold both. Every ablation of
    ``LOGIT_ABLATIONS`` is computed by the one compiled reference program (a
    vector of flags) on every call and must read above the ``q90`` limit, or
    the check fails: it is shown to tell them apart on the run that uses it.
    Beside each, ``loss``: how far it moves the loss of these logits, the
    number the harness's own comparison reads.

    GRADIENTS (``jax.value_and_grad(lm.loss)``, the function the trainer
    differentiates, against ``loss_and_grad``), leaf by leaf: the backward
    passes of the flash, grouped-matmul, selection and row-sum kernels and
    of everything around them, at the timed size. The control is this file's
    own gradient with every product's operands rounded to float8, the
    nearest precision below: the program's error must stay under
    ``GRAD_LIMITS`` of the control's on every leaf (``against_control``; the
    control itself reads 1, and so does a gradient that is simply wrong:
    float8 operands leave errors as large as the gradient).

    Also reported: the share of the slots that chose no expert on the
    reference's side (the seeded bias leans that way so that
    ``null_to_expert0`` has slots to move)."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    tokens = jnp.asarray(tokens)
    inp, targets = tokens[:, :-1], tokens[:, 1:]
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    limits, grad_limit = LIMITS[dtype], GRAD_LIMITS[dtype]
    clock = {"start": time.monotonic()}
    idents = seeded_identities(app, seed)
    params = lm.init(jax.random.PRNGKey(seed))
    for layer, ident in zip(params["layers"], idents):
        for sub, leaves in as_program(ident).items():
            (layer[sub] if sub else layer).update(leaves)
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
    run_ref = jax.jit(lambda p, t, flags: forward(p, t, static, flags))

    def run(p, ablate):  # one program: the ablation is a vector of flags
        flags = np.zeros(len(LOGIT_ABLATIONS), np.float32)
        if ablate is not None:
            flags[LOGIT_ABLATIONS.index(ablate)] = 1.0
        return run_ref(p, inp, flags)

    with jax.default_matmul_precision("highest"):
        ref = with_identities(init_params(app, seed), idents)
        want, chosen = run(ref, None)
        program = position_errors(got, want)
        del got
        clock["reference"] = time.monotonic()
        want_loss = float(next_token_loss(want, targets))
        moved = {}
        for a in LOGIT_ABLATIONS:
            broken = run(ref, a)[0]
            moved[a] = {k: v for k, v in position_errors(broken, want).items()
                        if k in ("q90", "rms")}
            moved[a]["loss"] = abs(float(next_token_loss(broken, targets))
                                   - want_loss) / want_loss
        del want, broken
        clock["ablations"] = time.monotonic()
        ref_loss, want_g = jax.device_get(
            loss_and_grad(ref, tokens, static, None, False))
        low_g = jax.device_get(
            loss_and_grad(ref, tokens, static, None, True)[1])
        clock["reference_gradients"] = time.monotonic()
    gradients = {"limit": grad_limit,
                 **against_control(gradient_errors(got_g, want_g),
                                   gradient_errors(low_g, want_g)),
                 "loss": abs(float(got_loss) - float(ref_loss))
                 / float(ref_loss)}
    del got_g, want_g, low_g
    detected = {a: moved[a]["q90"] > limits["q90"] for a in LOGIT_ABLATIONS}
    held = all(program[k] <= limits[k] for k in limits)
    held_g = gradients["worst"] <= grad_limit
    marks = list(clock.items())
    chosen = np.asarray(chosen, np.float64)
    return {"ok": bool(held and held_g and all(detected.values())),
            "program": program, "limits": limits, "ablations": moved,
            "detected": detected, "gradients": gradients,
            "null_slot_share": (float(chosen[:, app["moe_experts"]:].sum()
                                      / chosen.sum())),
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
    moment is formed. A leaf no gradient reaches (``beta``; the first
    layer's ``gamma``) stays bit-equal: 0 / (0 + eps)."""
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
    tolerance accepts. Every step runs the one program ``loss_and_grad``
    (the check's too); the last step's gradient is not used."""
    if app.get("optimizer") != "adam":
        raise ValueError("this reference implements Adam only")
    if ablate is not None and ablate not in ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    tokens = np.asarray(data[0])
    nb = tokens.shape[0] // batch
    if logits and ablate is None:
        report = check_logits(dict(app), tokens[:batch], seed)
        print(json.dumps({"line": "logits_check", **report}), flush=True)
        if not report["ok"]:
            return [float("nan")] * steps
    lr, b2 = float(app["step_size"]), float(app.get("beta2") or 0.999)
    app = _Static(app)
    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = v = None
        for i in range(steps):
            toks = jnp.asarray(tokens[(i % nb) * batch:(i % nb + 1) * batch])
            loss, g = loss_and_grad(params, toks, app, ablate, False)
            losses.append(float(loss))
            if i == steps - 1:
                break
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
