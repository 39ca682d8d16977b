"""Plain reference for ``olmoe-1b-7b``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no sort, no table,
no jobserver. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

The block is OLMoE's as ``transformers``' ``modeling_olmoe.py`` computes it
(arXiv:2409.02060): pre-norm RMSNorm (eps from the configuration), separate
q/k/v projections, RMSNorm over the whole ``d_model``-wide q and k before the
head split, rotate-half rotary positions, causal attention at scale
``head_dim ** -0.5``, an out projection; then a router over ALL experts in
float32 (softmax, top-k, weights NOT renormalised) and gated-SiLU experts;
a final norm and an untied head. The loss is the cross-entropy plus
``moe_aux_weight`` x the load-balance loss over all layers' tokens at once
(``load_balancing_loss_func``) plus ``moe_z_weight`` x the mean squared
logsumexp of the router logits.

The chip's share of the deployment (perf/configs/olmoe-1b-7b.json): only
experts ``0 .. moe_experts_held-1`` exist here, so a token's sum runs over
its top-k experts that are held; the router and both auxiliary losses keep
all experts. Experts are a plain loop over the held experts, each applied to
EVERY token under a dense [tokens, experts] weight matrix that is zero
outside the token's top-k.

``jax.checkpoint`` around a block and ``lax.map`` over the batch inside
attention bound the float32 score matrices ([16, 4096, 4096] a sequence);
they recompute and serialise, they do not change a number.

``replay`` is what the harness's ``correct`` evaluates, and a loss cannot
see everything (with unit norm weights at the start, leaving out the QK-norm
moves the first loss by 5e-5, inside bfloat16's own error). So before it
replays, it compares the PROGRAM's logits on the first batch with this
file's (``check_logits``) and, if they disagree, returns losses that are
not numbers: the cell then reports ``correct: false``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_EPS = 0.9, 1e-8

#: arithmetic broken on purpose, so that tests can show a tolerance tells
#: each apart (``replay(..., ablate=...)``)
ABLATIONS = ("no_lb", "no_z", "top_k_minus_1", "renormalize", "no_qk_norm")
#: the three of them that reach the logits (the auxiliary losses do not)
LOGIT_ABLATIONS = ("top_k_minus_1", "renormalize", "no_qk_norm")
#: ``check_logits``: the relative RMS error it accepts, by the program's
#: activation dtype (reasons in its docstring)
LOGITS_RMS_TOL = {"bfloat16": 0.025, "float32": 1e-4}


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales); the
    q/k/v projections are the column blocks of its ``wqkv``."""
    d, f, L, V = app["d_model"], app["d_ff"], app["n_layers"], app["vocab_size"]
    E = app["moe_experts"]
    H = app.get("moe_experts_held") or E
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        wq, wk, wv = jnp.split(normal(ks[0], (d, 3 * d), d ** -0.5), 3, axis=1)
        kr, kg, ku, kd = jax.random.split(ks[2], 4)
        layers.append({
            "ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
            "wq": wq, "wk": wk, "wv": wv,
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
            "wo": normal(ks[1], (d, d), d ** -0.5),
            "router": normal(kr, (d, E), d ** -0.5),
            "wg": normal(kg, (H, d, f), d ** -0.5),
            "wu": normal(ku, (H, d, f), d ** -0.5),
            "wd": normal(kd, (H, f, d), f ** -0.5),
        })
    return {
        "embed": normal(k_emb, (V, d), 0.02),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V), d ** -0.5),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Rotate-half rotary positions on ``x [..., S, hd]``."""
    S, hd = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention_one(q, k, v):
    """Causal softmax attention of one sequence: ``[H, S, hd]`` each."""
    S, hd = q.shape[-2:]
    s = jnp.einsum("hqd,hkd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)


def _block(x, layer, app, ablate):
    """One block on ``x [B, S, d]``: ``(x, router statistics)``."""
    B, S, d = x.shape
    nh, eps = app["n_heads"], app["norm_eps"]
    E, top_k = app["moe_experts"], app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    if ablate == "top_k_minus_1":
        top_k -= 1
    xn = rms_norm(x, layer["ln1"], eps)
    q, k, v = xn @ layer["wq"], xn @ layer["wk"], xn @ layer["wv"]
    if ablate != "no_qk_norm":
        q = rms_norm(q, layer["q_norm"], eps)
        k = rms_norm(k, layer["k_norm"], eps)
    heads = lambda t: t.reshape(B, S, nh, d // nh).transpose(0, 2, 1, 3)
    q, k = rotary(heads(q), app["rope_theta"]), rotary(heads(k), app["rope_theta"])
    o = jax.lax.map(lambda qkv: _attention_one(*qkv), (q, k, heads(v)))
    h = x + o.transpose(0, 2, 1, 3).reshape(B, S, d) @ layer["wo"]
    # the experts, on [T, d]
    t = rms_norm(h, layer["ln2"], eps).reshape(B * S, d)
    logits = t @ layer["router"]                                 # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)                      # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = probs * mask
    if ablate == "renormalize":
        weight = weight / weight.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(t)
    for e in range(H):  # every held expert on every token, weighted
        up = jax.nn.silu(t @ layer["wg"][e]) * (t @ layer["wu"][e])
        y = y + weight[:, e:e + 1] * (up @ layer["wd"][e])
    lse = jax.nn.logsumexp(logits, axis=-1)
    stats = {"chosen": mask.sum(axis=0), "prob_sum": probs.sum(axis=0),
             "z_sum": jnp.sum(lse * lse)}
    return h + y.reshape(B, S, d), stats


def forward(params, inp, app, ablate: Optional[str] = None):
    """``(logits [B, S, V], [router statistics of each layer])``."""
    x = params["embed"][inp]
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    stats = []
    for layer in params["layers"]:
        x, s = block(x, layer, _Static(app), ablate)
        stats.append(s)
    return rms_norm(x, params["ln_f"], app["norm_eps"]) @ params["head"], stats


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss_terms(params, tokens, app, ablate: Optional[str] = None):
    """``(cross-entropy, load-balance, router-z)`` of ``tokens[:, :-1] ->
    tokens[:, 1:]``, each before its weight."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, stats = forward(params, inp, app, ablate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tgt[..., None], axis=-1).mean()
    n = len(stats) * inp.size  # all layers' tokens at once
    chosen = sum(s["chosen"] for s in stats) / n      # sums to top_k
    mean_prob = sum(s["prob_sum"] for s in stats) / n
    lb = app["moe_experts"] * jnp.sum(jax.lax.stop_gradient(chosen) * mean_prob)
    z = sum(s["z_sum"] for s in stats) / n
    return ce, lb, z


def loss_fn(params, tokens, app, ablate: Optional[str] = None):
    ce, lb, z = loss_terms(params, tokens, app, ablate)
    lb_w = 0.0 if ablate == "no_lb" else app["moe_aux_weight"]
    z_w = 0.0 if ablate == "no_z" else app["moe_z_weight"]
    return ce + lb_w * lb + z_w * z


def rel_rms(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(((a - b) ** 2).mean()) / np.sqrt((b ** 2).mean()))


def check_logits(app: Dict[str, Any], inp, seed: int) -> Dict[str, Any]:
    """The program's logits on ``inp [B, S]`` (``TransformerLM.apply`` as the
    job path traces it: the configuration's dtype, flash attention and the
    grouped-matmul kernels where the device has them) against ``forward`` of
    this file, from the same seeded parameters. ``{"ok": bool, ...}``.

    TOLERANCE on the relative RMS error of the logits. bfloat16: 0.025 —
    8 bits of mantissa (0.4% an operation) through two blocks, and a
    near-tie in the router sends a token to another expert on one side only,
    which moves single logits by tenths (0.53 of a 5.0 range seen), so the
    RMS is judged and the maximum is not. At published widths on the v5e the
    program reads 0.0121-0.0169 (my chip runs, PR 25, seven seeds), and on
    the same seeds this file's own logits move by 0.031-0.042 without the
    QK-norm, 0.037-0.054 with top-7 for top-8 and 0.31-0.42 with
    renormalised gates (program / weakest ablation: 0.38-0.42 on every
    seed). float32 (the CPU
    rehearsal and tests): 1e-4, summation order only. The three ablations
    are computed again on every call and the check fails unless each lies
    above the tolerance: it is shown to tell them apart on the run that
    uses it."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    inp = jnp.asarray(inp)
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    tol = LOGITS_RMS_TOL[jnp.dtype(lm.config.dtype).name]
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        got = np.asarray(jax.jit(lm.apply)(params, inp), np.float32)
    del params
    static = _Static(app)
    with jax.default_matmul_precision("highest"):
        ref_params = init_params(app, seed)
        run = lambda ablate: np.asarray(jax.jit(
            lambda p, t: forward(p, t, static, ablate)[0])(ref_params, inp))
        want = run(None)
        moved = {a: rel_rms(run(a), want) for a in LOGIT_ABLATIONS}
    err = rel_rms(got, want)
    return {"ok": bool(err <= tol and all(m > tol for m in moved.values())),
            "rel_rms": err, "rms_tol": tol, "ablations_rel_rms": moved,
            "max_abs": float(np.abs(got - want).max()),
            "max_ref": float(np.abs(want).max()), "seed": int(seed),
            "dtype": jnp.dtype(lm.config.dtype).name}


def replay(app: Dict[str, Any], data: Sequence[np.ndarray], batch: int,
           steps: int, seed: int, ablate: Optional[str] = None,
           logits: bool = True) -> List[float]:
    """Loss of each of the first ``steps`` steps (batch ``i`` is rows
    ``[i * batch, (i + 1) * batch)`` of the data set, cycling per epoch, as
    dolphin/data.py serves them unshuffled). ``ablate``: one of
    ``ABLATIONS``. First, unless ``logits`` is off or an ablation is asked
    for, ``check_logits`` on the first batch: its report is printed as one
    JSON line, and where it fails every loss returned is ``nan``, which no
    tolerance accepts."""
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

    @jax.jit
    def step(params, m, v, t, toks):
        loss, g = jax.value_and_grad(loss_fn)(params, toks, app, ablate)
        tm = jax.tree.map
        m = tm(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = tm(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        params = tm(lambda p, a, b: p - lr * (a / (1 - ADAM_B1 ** t))
                    / (jnp.sqrt(b / (1 - b2 ** t)) + ADAM_EPS), params, m, v)
        return params, m, v, loss

    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        for i in range(steps):
            toks = jnp.asarray(tokens[(i % nb) * batch:(i % nb + 1) * batch])
            params, m, v, loss = step(params, m, v, jnp.float32(i + 1), toks)
            losses.append(float(loss))
    return losses
