"""Plain reference for ``sdar-30b-a3b``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no sort, no table,
no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops`` is used
to compute it. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

**The layer** (``sdar_moe`` is the Qwen3-MoE block; ``config.json`` of
``JetLM/SDAR-30B-A3B-Chat``), input the stream ``x [S, d]`` with a position
``pos_s`` a row:

    h   = RMSNorm(x; g1)
    q   = h Wq -> [S, H, hd];  k = h Wk,  v = h Wv -> [S, Hkv, hd]
    q   = RMSNorm over each head's hd columns (q; nq),  k likewise (k; nk)
    q, k = rotate-half rotary at pos_s, theta
    o_i = softmax(q_i k_kv(i)^T / sqrt(hd) + M) v_kv(i),   kv(i) = i // (H / Hkv)
    x   = x + concat_i(o_i) Wo
    h   = RMSNorm(x; g2)
    p   = softmax(h Wr)                      # all E experts, float32
    top = the k largest of p;  w_e = p_e / sum_{e' in top} p_e'
    x   = x + sum_{e in top, e held} w_e (silu(h Wg_e) * h Wu_e) Wd_e

then the final RMSNorm and the untied readout.

**The step** (SDAR, arXiv:2510.06303, section 3; BD3-LMs, arXiv:2503.09573).
A sequence ``x0`` of ``L`` tokens lies in blocks of ``B``; the batch says
which positions are masked (``x_t``: the mask token there, ``x0`` elsewhere)
and each block's rate ``t_b``. Here the two copies are ONE sequence of ``2 L``
rows — ``[x0 ; x_t]``, row ``r`` of stream ``r // L`` at position ``r mod L``
— under ONE dense boolean mask ``[2 L, 2 L]``, with ``beta(p) = p // B``:

    clean row p:  clean columns j with beta(j) <= beta(p);   no noisy column
    noisy row p:  clean columns j with beta(j) <  beta(p);
                  noisy columns j with beta(j) == beta(p)

built a block of ``QUERY_BLOCK`` query rows at a time so that ``H x 2L x 2L``
scores never exist at once. The logits are the noisy rows' (``L`` of them),
and

    loss = (1 / (N L)) sum_b (1 / t_b) sum_{p in b, masked} CE(logits_p, x0_p)
           + moe_aux_weight x load balance over all 2 N L positions computed

(no shift: a masked position predicts what is under the mask). Independent of
the program's cut on purpose: it never merges by log-sum-exp, never folds a
stream into the batch, never rounds the causal edge.

The chip's share (the configuration file's ``deployment``): experts ``0 ..
moe_experts_held-1`` of each layer and ``vocab_size`` rows are given as
arguments (``app``); the router keeps all ``E`` outputs and the chosen
weights are divided by the sum over ALL ``k`` chosen.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM's logits on the first batch with this file's, position
by position, and the gradient of the PROGRAM's loss with this file's, leaf by
leaf (``check_logits``), under seeded NON-TRIVIAL values of every norm weight
(ones as initialised: ``seeded_identities``), and, if they disagree, returns
losses that are not numbers.
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

#: arithmetic broken on purpose, so that tests and every run's
#: ``check_logits`` can show a tolerance tells each apart
#: (``replay(..., ablate=...)``): those that reach the LOGITS ...
LOGIT_ABLATIONS = ("no_own_block", "noisy_sees_own_clean", "clean_by_token",
                   "clean_sees_noisy", "no_head_norm", "shared_head_norm",
                   "no_renorm", "rope_2l", "fp8_operands")
#: ... and those of the LOSS alone, which the gradient shows
LOSS_ABLATIONS = ("no_rate_weight", "shifted", "unmasked_too")
ABLATIONS = LOGIT_ABLATIONS + LOSS_ABLATIONS
#: ``check_logits``' limits by the program's activation dtype, on the
#: per-position relative error: its 90th percentile over ALL positions, the
#: relative RMS over all of them, and its 90th percentile over the first
#: ``EARLY`` positions of each sequence (``early``). The program must hold all
#: three; an ablation must read above ONE of them. Readings and reasons:
#: ``perf/configs/sdar-30b-a3b.json`` ``job.why.loss_rtol``
LIMITS = {"bfloat16": {"q90": 0.02, "rms": 0.04, "early": 0.03},
          "float32": {"q90": 1e-4, "rms": 1e-4, "early": 1e-4}}
#: the positions at which ONE diffusion block is a share of what a row sees
#: that a comparison can read: a noisy row's own block is ``B`` of the ``~p``
#: keys position ``p`` sees, a 32nd or more at 4 x 32 positions and a
#: 2,000th at the end of 8,192 — over all positions the own-block term reads
#: under bfloat16's own error in every precision, here it reads 10-40 times it
EARLY = 128
#: ``check_logits``' limits on the program's first gradient: its error
#: ``|g - g_ref|`` as a share of what float8 operands do to the same leaf,
#: the leaf's layers taken together (``against_control``), every leaf under
#: the limit of its kind. ``routed``: the leaves whose gradient passes
#: through the DISCRETE choice of 8 of 128 experts (``ROUTED_LEAVES``) — the
#: choice has near-ties at every position, bfloat16 rows and float8 rows
#: alike flip some, and a flip is a whole term of the gradient whatever the
#: precision: the two stand 2-6 apart there and 7-50 on the ``other`` leaves
#: (attention's, the norms', embedding and readout). Readings and reasons:
#: the same place
GRAD_LIMITS = {"bfloat16": {"routed": 0.8, "other": 0.3},
               "float32": {"routed": 1e-3, "other": 1e-3}}
ROUTED_LEAVES = ("router", "eg", "eu", "ed", "g2")
#: how ``seeded_identities`` draws the norm weights, all ones as initialised:
#: the block norms and the final norm normal around 1, the per-head norms
#: log-normal around 1 — q's and k's drawn apart, so that sharing one weight
#: can be seen. Around 1 and not above it: a head norm that sharpens the
#: scores (weights around e^0.9 and e^0.5 were tried: scores four times as
#: wide) multiplies bfloat16's own error by thirteen and float8's by two, and
#: leaves the two precisions a factor of three apart where this leaves
#: seventeen (``job.why.loss_rtol`` has the readings)
IDENT_STD = {"block_norm": 0.2, "head_norm": 0.3}


def widths(app):
    """``(H, Hkv, hd)``: query heads, key/value heads, a head's width."""
    h = app["n_heads"]
    return h, app.get("n_kv_heads") or h, (
        app.get("mha_head_dim") or app["d_model"] // h)


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names; every norm weight is ones."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    E = app["moe_experts"]
    held = app.get("moe_experts_held") or E
    H, Hkv, hd = widths(app)
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)
    f32 = jnp.float32

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) * fan_in ** -0.5

    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        wqkv = normal(ks[0], (d, (H + 2 * Hkv) * hd), d)
        wq, wk, wv = jnp.split(wqkv, (H * hd, (H + Hkv) * hd), axis=-1)
        kr, kg, ku, kd = jax.random.split(ks[2], 4)
        layers.append({
            "g1": jnp.ones((d,), f32), "g2": jnp.ones((d,), f32),
            "wq": wq, "wk": wk, "wv": wv,
            "nq": jnp.ones((hd,), f32), "nk": jnp.ones((hd,), f32),
            "wo": normal(ks[1], (H * hd, d), H * hd),
            "router": normal(kr, (d, E), d),
            "eg": normal(kg, (held, d, f), d), "eu": normal(ku, (held, d, f), d),
            "ed": normal(kd, (held, f, d), f)})
    return {"embed": jax.random.normal(k_emb, (V, d), f32)
            * app.get("embed_std", 0.02),
            "head": normal(jax.random.fold_in(k_emb, 1), (d, V), d),
            "ln_f": jnp.ones((d,), f32), "layers": layers}


def seeded_identities(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Seeded values for every leaf that is an identity as initialised — the
    norm weights (``IDENT_STD``) — under this file's names, so
    that a run can SEE them: ``{"ln_f", "layers": [{g1, g2, nq, nk}]}``.
    Given to program and reference alike by ``check_logits``."""
    d, (_, _, hd) = app["d_model"], widths(app)
    s, f32 = IDENT_STD, jnp.float32
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 49)
    layers = []
    for i in range(app["n_layers"]):
        ks = jax.random.split(jax.random.fold_in(root, i), 4)
        layers.append({
            "g1": 1 + s["block_norm"] * jax.random.normal(ks[0], (d,), f32),
            "g2": 1 + s["block_norm"] * jax.random.normal(ks[1], (d,), f32),
            "nq": jnp.exp(s["head_norm"]
                          * jax.random.normal(ks[2], (hd,), f32)),
            "nk": jnp.exp(s["head_norm"]
                          * jax.random.normal(ks[3], (hd,), f32))})
    return {"ln_f": 1 + s["block_norm"] * jax.random.normal(
        jax.random.fold_in(root, 1000), (d,), f32), "layers": layers}


#: this file's names of a layer's seeded leaves -> the PROGRAM's
AS_PROGRAM = {"g1": "ln1", "g2": "ln2", "nq": "q_head_norm",
              "nk": "k_head_norm"}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta, pos):
    """Rotate-half rotary on ``x [..., S, hd]`` at the positions ``pos [S]``."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _flag(ablate, name):
    """Whether the ablation ``name`` is on: a Python bool where ``ablate`` is
    None or a name, a traced bool where it is a float32 vector of flags over
    ``ABLATIONS`` — ``check_logits`` passes that one, so ONE compiled program
    computes the reference and every ablation."""
    if ablate is None or isinstance(ablate, str):
        return ablate == name
    return ablate[ABLATIONS.index(name)] > 0


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
    refuse. The router's product stays float32 and its WEIGHTS unrounded, as
    the program's do; its input is an activation and is rounded as every
    activation is (the program's router reads bfloat16 rows)."""
    return lambda t: _pick(
        ablate, "fp8_operands",
        lambda: t.astype(jnp.float8_e4m3fn).astype(jnp.float32), lambda: t)


def stream_mask(rows, length: int, block: int, ablate=None):
    """The dense boolean mask of the query rows ``rows [n]`` (indices into
    the ``2 length`` rows ``[clean ; noisy]``) against all ``2 length``
    columns: ``[n, 2 length]`` (module docstring)."""
    cols = jnp.arange(2 * length)
    r_noisy, c_noisy = (rows >= length)[:, None], (cols >= length)[None, :]
    r_pos, c_pos = (rows % length)[:, None], (cols % length)[None, :]
    rb, cb = r_pos // block, c_pos // block
    clean_clean = _pick(ablate, "clean_by_token", lambda: c_pos <= r_pos,
                        lambda: cb <= rb)
    clean_noisy = _pick(ablate, "clean_sees_noisy", lambda: cb == rb,
                        lambda: jnp.zeros_like(cb == rb))
    noisy_clean = _pick(ablate, "noisy_sees_own_clean", lambda: cb <= rb,
                        lambda: cb < rb)
    noisy_noisy = _pick(ablate, "no_own_block",
                        lambda: jnp.zeros_like(cb == rb), lambda: cb == rb)
    return jnp.where(r_noisy, jnp.where(c_noisy, noisy_noisy, noisy_clean),
                     jnp.where(c_noisy, clean_noisy, clean_clean))


def _attention_one(q, k, v, length, block, rnd, ablate):
    """Masked softmax attention of one sequence of ``2 length`` rows, ``q, k,
    v [H, 2 length, hd]`` (K and V already repeated to the query heads): the
    explicit mask of ``stream_mask``, a block of query rows at a time. A row
    that sees no column (only under an ablation) yields zeros."""
    S, hd = q.shape[1], q.shape[2]
    qb = next(n for n in (QUERY_BLOCK, 128, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    @jax.checkpoint
    def rows(args):
        q_blk, row0 = args                                      # [H, qb, hd]
        s = jnp.einsum("hqd,hkd->hqk", rnd(q_blk), rnd(k)) * hd ** -0.5
        seen = stream_mask(row0 + jnp.arange(qb), length, block, ablate)
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                      0.0)
        p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("hqk,hkd->hqd", rnd(p), rnd(v))

    blocks = q.reshape(q.shape[0], S // qb, qb, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], S, hd)


def attention_sublayer(x, p, app, ablate):
    """``x [N, 2 L, d]`` after the attention sublayer."""
    N, S, d = x.shape
    H, Hkv, hd = widths(app)
    L, B, eps = S // 2, app["diffusion_block"], app["norm_eps"]
    rnd = _operands(ablate)
    h = rms_norm(x, p["g1"], eps)
    heads = lambda t, n: t.reshape(N, S, n, hd).transpose(0, 2, 1, 3)
    q = heads(rnd(h) @ rnd(p["wq"]), H)
    k = heads(rnd(h) @ rnd(p["wk"]), Hkv)
    v = heads(rnd(h) @ rnd(p["wv"]), Hkv)
    nk = _pick(ablate, "shared_head_norm", lambda: p["nq"], lambda: p["nk"])
    q = _pick(ablate, "no_head_norm", lambda: q,
              lambda: rms_norm(q, p["nq"], eps))
    k = _pick(ablate, "no_head_norm", lambda: k, lambda: rms_norm(k, nk, eps))
    pos = _pick(ablate, "rope_2l", lambda: jnp.arange(S),
                lambda: jnp.arange(S) % L)
    q, k = rotary(q, app["rope_theta"], pos), rotary(k, app["rope_theta"], pos)
    spread = lambda t: jnp.repeat(t, H // Hkv, axis=1)  # head i: K/V i // rep
    o = jax.lax.map(
        lambda qkv: _attention_one(*qkv, L, B, rnd, ablate),
        (q, spread(k), spread(v)))
    return x + rnd(o.transpose(0, 2, 1, 3).reshape(N, S, H * hd)) @ rnd(p["wo"])


def expert_sublayer(x, p, app, ablate):
    """``(x, {chosen [E], prob_sum [E]})`` after the expert sublayer."""
    N, S, d = x.shape
    E, top_k = app["moe_experts"], app["moe_top_k"]
    held = app.get("moe_experts_held") or E
    rnd = _operands(ablate)
    h = rms_norm(x, p["g2"], app["norm_eps"]).reshape(N * S, d)
    hr = rnd(h)  # the router reads the activation too: float32 products on it
    prob = jax.nn.softmax(hr @ p["router"], axis=-1)             # [T, E]
    _, chosen = jax.lax.top_k(prob, top_k)                       # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = prob * mask
    weight = _pick(ablate, "no_renorm", lambda: weight,
                   lambda: weight / weight.sum(axis=-1, keepdims=True))
    @jax.checkpoint  # the backward holds ONE expert's [T, f] rows at a time
    def one(hr, wg, wu, wd, w):
        hidden = jax.nn.silu(hr @ rnd(wg)) * (hr @ rnd(wu))
        return w * (rnd(hidden) @ rnd(wd))

    y = jnp.zeros_like(h)
    for e in range(held):  # every held expert on every token, weighted
        y = y + one(hr, p["eg"][e], p["eu"][e], p["ed"][e], weight[:, e:e + 1])
    return x + y.reshape(N, S, d), {"chosen": mask.sum(axis=0),
                                    "prob_sum": prob.sum(axis=0)}


def forward(params, tokens, masked, app, ablate=None):
    """``(logits of the noisy rows [N, L, V], [router statistics a layer])``
    on ``tokens [N, L]`` under ``masked [N, L]``."""
    L = tokens.shape[1]
    noisy = jnp.where(masked != 0, app["mask_token"], tokens)
    x = params["embed"][jnp.concatenate([tokens, noisy], axis=1)]  # [N, 2L, d]
    stats = []
    for p in params["layers"]:
        x = jax.checkpoint(functools.partial(
            attention_sublayer, app=app, ablate=ablate))(x, p)
        x, s = jax.checkpoint(functools.partial(
            expert_sublayer, app=app, ablate=ablate))(x, p)
        stats.append(s)
    rnd = _operands(ablate)
    x = rms_norm(x[:, L:], params["ln_f"], app["norm_eps"])
    return rnd(x) @ rnd(params["head"]), stats


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def diffusion_loss(logits, tokens, masked, rate, block, ablate=None):
    """``(1 / (N L)) sum_b (1 / t_b) sum_{p in b, masked} CE(logits_p,
    tokens_p)`` (module docstring)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    targets = _pick(ablate, "shifted", lambda: jnp.roll(tokens, -1, axis=1),
                    lambda: tokens)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    m = (masked != 0).astype(jnp.float32)
    m = _pick(ablate, "unmasked_too", lambda: jnp.ones_like(m), lambda: m)
    t = jnp.repeat(rate, block, axis=1)
    w = _pick(ablate, "no_rate_weight", lambda: m, lambda: m / t)
    return (nll * w).sum() / m.size


def balance_loss(stats, positions, experts):
    """``E sum_e f_e P_e`` over all layers' positions at once
    (``transformers``' ``load_balancing_loss_func``; ``f_e`` counts)."""
    n = len(stats) * positions
    chosen = sum(s["chosen"] for s in stats) / n
    mean_prob = sum(s["prob_sum"] for s in stats) / n
    return experts * jnp.sum(jax.lax.stop_gradient(chosen) * mean_prob)


def loss_fn(params, batch, app, ablate=None):
    tokens, masked, rate = batch
    logits, stats = forward(params, tokens, masked, app, ablate)
    loss = diffusion_loss(logits, tokens, masked, rate,
                          app["diffusion_block"], ablate)
    return loss + app["moe_aux_weight"] * balance_loss(
        stats, 2 * tokens.size, app["moe_experts"])


@functools.partial(jax.jit, static_argnames=("app",))
def loss_and_grad(params, batch, app, flags):
    """``(loss, gradient)`` of ``loss_fn`` — ONE compiled program for the
    replay's steps, ``check_logits``' reference gradient, its control in the
    precision below and the loss's ablations (``flags``: a float32 vector
    over ``ABLATIONS``). ``app``: a ``_Static``."""
    return jax.value_and_grad(loss_fn)(params, batch, app, flags)


def flags_of(ablate: Optional[str]) -> np.ndarray:
    flags = np.zeros(len(ABLATIONS), np.float32)
    if ablate is not None:
        flags[ABLATIONS.index(ablate)] = 1.0
    return flags


QUANTILES = (0.5, 0.9, 0.99)
DIVERGED = 1e9


def position_errors(a, b) -> Dict[str, float]:
    """Relative error of ``a`` against ``b [N, L, V]`` position by position
    (each position's error vector over its logit vector, in norm): the
    overall relative RMS, quantiles over the positions, and the 90th
    percentile over each sequence's first ``EARLY`` positions."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    per = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / jnp.sum(b ** 2, axis=-1))
    per = jnp.where(jnp.isfinite(per), per, DIVERGED)
    qs = jnp.quantile(per.reshape(-1), jnp.asarray(QUANTILES), method="lower")
    early = jnp.quantile(per[:, :EARLY].reshape(-1), 0.9, method="lower")
    rms = jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2))
    return {"rms": float(jnp.where(jnp.isfinite(rms), rms, DIVERGED)),
            **{f"q{int(100 * q)}": float(v) for q, v in zip(QUANTILES, qs)},
            "early": float(early)}


def from_program(tree: Dict[str, Any], app: Dict[str, Any]) -> Dict[str, Any]:
    """A parameter (or gradient) tree of the PROGRAM under this file's
    names: ``wqkv``'s column blocks apart, the expert sub-tree flat."""
    H, Hkv, hd = widths(app)
    q, k = H * hd, (H + Hkv) * hd

    def layer(l):
        w, m = l["wqkv"], l["moe"]
        return {"g1": l["ln1"], "g2": l["ln2"], "wq": w[:, :q],
                "wk": w[:, q:k], "wv": w[:, k:], "nq": l["q_head_norm"],
                "nk": l["k_head_norm"], "wo": l["wo"], "router": m["router"],
                "eg": m["wg"], "eu": m["wu"], "ed": m["wd"]}
    return {"embed": tree["embed"], "head": tree["head"], "ln_f": tree["ln_f"],
            "layers": [layer(l) for l in tree["layers"]]}


def gradient_errors(got, want) -> Dict[str, List[float]]:
    """``[|got - want|^2, |want|^2]`` of every leaf, summed over the layers
    that have it (both trees under this file's names, on the host)."""
    def add(row, a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err, norm = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        if not np.isfinite(err) or (norm == 0.0 and np.any(a)):
            err = DIVERGED
        row[0] += err
        row[1] += norm

    sums = {}
    for name in ("embed", "head", "ln_f"):
        add(sums.setdefault(name, [0.0, 0.0]), got[name], want[name])
    for a, b in zip(got["layers"], want["layers"]):
        for name in b:
            add(sums.setdefault(name, [0.0, 0.0]), a[name], b[name])
    return sums


def against_control(program, control, limits) -> Dict[str, Any]:
    """The program's ``gradient_errors`` as a share of the control's, leaf
    by leaf: ``{"worst", "worst_leaf", "over", "by_leaf": {leaf: [the
    program's relative error, the control's, their ratio]}}``, a leaf's
    layers taken together; ``over`` is the largest ratio as a share of ITS
    leaf's limit (``limits``: a row of ``GRAD_LIMITS``), above 1 where a leaf
    breaks it. Where the control reads 0 the program must."""
    by_leaf = {}
    for leaf, (err, norm) in program.items():
        low = control[leaf][0]
        ratio = (err / low) ** 0.5 if low > 0.0 else (
            0.0 if err == 0.0 else DIVERGED)
        scale = norm if norm > 0.0 else 1.0
        by_leaf[leaf] = [(err / scale) ** 0.5, (low / scale) ** 0.5, ratio]
    worst = max(by_leaf, key=lambda leaf: by_leaf[leaf][2])
    over = max(r[2] / limits["routed" if leaf in ROUTED_LEAVES else "other"]
               for leaf, r in by_leaf.items())
    return {"worst": by_leaf[worst][2], "worst_leaf": worst, "over": over,
            "by_leaf": by_leaf}


def with_identities(params, idents):
    """``params`` (this file's names) with ``seeded_identities`` written in."""
    return {**params, "ln_f": idents["ln_f"],
            "layers": [{**p, **i} for p, i in
                       zip(params["layers"], idents["layers"])]}


def check_logits(app: Dict[str, Any], batch, seed: int) -> Dict[str, Any]:
    """The program on the batch ``(tokens [N, L], masked, rate)``
    (``TransformerLM`` as the job path traces it: the configuration's dtype,
    the flash and grouped-matmul kernels where the device has them) against
    this file, from the same seeded parameters as the cell trains them BUT
    with ``seeded_identities`` written into both: as initialised every norm
    weight is ones and the per-head norms change nothing a run could see (a
    head of rms-normed random q has an RMS of ~1 already). ``{"ok": bool,
    ...}``.

    LOGITS (``lm.apply(lm.noised(...))`` against ``forward``), position by
    position: three limits (``LIMITS``), the 90th percentile over positions,
    the RMS over all of them (a near-tie in the router moves a FEW positions
    a lot) and the 90th percentile over the first ``EARLY`` positions (where
    one block is a readable share of the keys), and the program must hold
    all three. Every ablation of ``LOGIT_ABLATIONS`` is computed by the one
    compiled reference program (a vector of flags) on every call and must
    read above one of the limits, or the check fails: it is shown to tell
    them apart on the run that uses it.

    GRADIENTS (``jax.value_and_grad(lm.loss)``, the function the trainer
    differentiates, against ``loss_and_grad``), leaf by leaf: the backward
    passes of the flash kernels under the mask by block and stream, of the
    own-block term and the merge, of the grouped matmuls and of everything
    around them, at the timed size. The control is this file's own gradient
    with every product's operands rounded to float8, the nearest precision
    below: the program's error must stay under ``GRAD_LIMITS`` of the
    control's on every leaf, by the leaf's kind (``against_control``). The
    ablations of ``LOSS_ABLATIONS`` never reach the logits; each one's
    gradient is held to the same measure and must break a leaf's limit."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    batch = tuple(jnp.asarray(a) for a in batch)
    tokens, masked, rate = batch
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    limits, grad_limit = LIMITS[dtype], GRAD_LIMITS[dtype]
    clock = {"start": time.monotonic()}
    idents = seeded_identities(app, seed)
    params = lm.init(jax.random.PRNGKey(seed))
    params["ln_f"] = idents["ln_f"]
    for layer, ident in zip(params["layers"], idents["layers"]):
        layer.update({AS_PROGRAM[k]: v for k, v in ident.items()})
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        # every gradient waits on the HOST: the device holds one tree at a
        # time beside whatever the process already keeps there
        got_loss, got_g = jax.device_get(
            jax.jit(jax.value_and_grad(lm.loss))(params, batch))
        got_g = from_program(got_g, app)
        clock["program_gradient"] = time.monotonic()
        got = jax.block_until_ready(jax.jit(
            lambda p, t, m: lm.apply(p, lm.noised(t, m)))(params, tokens,
                                                          masked))
    del params
    clock["program"] = time.monotonic()
    static = _Static(app)
    run_ref = jax.jit(lambda p, flags: forward(p, tokens, masked, static,
                                               flags)[0])
    block = app["diffusion_block"]
    with jax.default_matmul_precision("highest"):
        ref = with_identities(init_params(app, seed), idents)
        want = run_ref(ref, flags_of(None))
        program = position_errors(got, want)
        del got
        clock["reference"] = time.monotonic()
        want_loss = float(diffusion_loss(want, tokens, masked, rate, block))
        moved = {}
        for a in LOGIT_ABLATIONS:
            broken = run_ref(ref, flags_of(a))
            moved[a] = {k: v for k, v in position_errors(broken, want).items()
                        if k in limits}
            moved[a]["loss"] = abs(float(diffusion_loss(
                broken, tokens, masked, rate, block)) - want_loss) / want_loss
        del want, broken
        clock["ablations"] = time.monotonic()
        ref_loss, want_g = jax.device_get(
            loss_and_grad(ref, batch, static, flags_of(None)))
        control = gradient_errors(jax.device_get(loss_and_grad(
            ref, batch, static, flags_of("fp8_operands"))[1]), want_g)
        clock["reference_gradients"] = time.monotonic()
        loss_moved = {}
        for a in LOSS_ABLATIONS:
            bad_loss, bad_g = jax.device_get(
                loss_and_grad(ref, batch, static, flags_of(a)))
            worst = against_control(gradient_errors(bad_g, want_g), control,
                                    grad_limit)
            loss_moved[a] = {"worst": worst["worst"], "over": worst["over"],
                             "least": min(r[2] for r in
                                          worst["by_leaf"].values()),
                             "loss": abs(float(bad_loss) - float(ref_loss))
                             / float(ref_loss)}
        del bad_g
        clock["loss_ablations"] = time.monotonic()
    gradients = {"limit": grad_limit,
                 **against_control(gradient_errors(got_g, want_g), control,
                                   grad_limit),
                 "loss": abs(float(got_loss) - float(ref_loss))
                 / float(ref_loss)}
    del got_g, want_g
    detected = {a: any(moved[a][k] > limits[k] for k in limits)
                for a in LOGIT_ABLATIONS}
    detected.update({a: loss_moved[a]["over"] > 1.0 for a in LOSS_ABLATIONS})
    held = all(program[k] <= limits[k] for k in limits)
    held_g = gradients["over"] <= 1.0
    marks = list(clock.items())
    return {"ok": bool(held and held_g and all(detected.values())),
            "program": program, "limits": limits, "ablations": moved,
            "loss_ablations": loss_moved, "detected": detected,
            "gradients": gradients,
            "masked_share": float(jnp.mean(masked != 0)),
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
    ``[i * batch, (i + 1) * batch)`` of the data set ``(tokens, masked,
    rate)``, cycling per epoch, as dolphin/data.py serves them unshuffled).
    ``ablate``: one of ``ABLATIONS``. First, unless ``logits`` is off or an
    ablation is asked for, ``check_logits`` on the first batch: its report
    is printed as one JSON line, and where it fails every loss returned is
    ``nan``, which no tolerance accepts. Every step runs the one program
    ``loss_and_grad`` (the check's too); the last step's gradient is not
    used."""
    if app.get("optimizer") != "adam":
        raise ValueError("this reference implements Adam only")
    if ablate is not None and ablate not in ABLATIONS:
        raise ValueError(f"unknown ablation {ablate!r}")
    arrays = [np.asarray(a) for a in data]
    nb = arrays[0].shape[0] // batch
    rows = lambda i: tuple(jnp.asarray(a[(i % nb) * batch:(i % nb + 1) * batch])
                           for a in arrays)
    if logits and ablate is None:
        report = check_logits(dict(app), rows(0), seed)
        print(json.dumps({"line": "logits_check", **report}), flush=True)
        if not report["ok"]:
            return [float("nan")] * steps
    lr, b2 = float(app["step_size"]), float(app.get("beta2") or 0.999)
    app = _Static(app)
    flags = flags_of(ablate)
    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = v = None
        for i in range(steps):
            loss, g = loss_and_grad(params, rows(i), app, flags)
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
