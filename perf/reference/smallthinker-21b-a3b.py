"""Plain reference for ``smallthinker-21b-a3b``: forward, loss, gradients and
Adam by formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no chunks, no sort,
no table, no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops``
is used to compute it. It replays the job's first steps from the same seeded
initial parameters and the same batches and returns each step's loss.

One block of SmallThinker-21B-A3B (arXiv:2507.20984; ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``), layer ``l``, input ``x [S, d]``:

    r   = x Wr                                   # router logits, from the
                                                 # BLOCK'S INPUT, un-normed
    a   = RMSNorm(x; g1)
    q, k, v = split(a Wqkv) -> q [H, S, hd], k, v [Hkv, S, hd]
    l in window_layers:  q, k = rope(q, k; theta, rotate-half)
    mask(i, j) = (j <= i) and (l not in window_layers or i - j < W)
    o_h = softmax(q_h k_{h // (H / Hkv)}^T / sqrt(hd) + mask) v_{h // (H / Hkv)}
    y   = x + concat_h(o_h) Wo
    b   = RMSNorm(y; g2)
    top = top-k of softmax(r);  w = its probabilities over their sum
    out = y + sum_{e in top, e held} w_e (relu(b Wg_e) * (b Wu_e)) Wd_e

K and V are repeated to ``H`` heads with ``jnp.repeat``, the mask is an
explicit boolean, and attention runs a block of ``QUERY_BLOCK`` query rows at
a time so that ``H x S x S`` scores never exist at once. Then the final
RMSNorm and the untied head; loss = cross-entropy + ``moe_aux_weight`` x the
load-balance loss ``E sum_e f_e P_e`` over all the expert layers' tokens
(``f_e`` the share of tokens whose top-k holds ``e``, a count; ``P_e`` the
mean of ``softmax(r)_e``), over all ``E`` experts whatever share is held.

The chip's share (the configuration file's ``deployment``): experts ``0 ..
moe_experts_held-1`` of each layer and ``vocab_size`` rows are given as
arguments (``app``); the router, its softmax, the top-k, the renormalisation
and the balance loss keep all ``E``.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM's logits on the first batch with this file's
(``check_logits``), position by position, apart for the positions before and
from the window's length on, and, if they disagree, returns losses that are
not numbers.
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
#: (``replay(..., ablate=...)``)
LOGIT_ABLATIONS = ("no_window", "rope_in_full", "no_rope_in_swa",
                   "router_post_attn", "silu_experts", "kv_head_mod",
                   "fp8_operands")
ABLATIONS = LOGIT_ABLATIONS + ("no_aux",)
#: ``check_logits``' limits by the program's activation dtype: the 90th
#: percentile over positions of the per-position relative error, and the
#: relative RMS over all positions — each held in BOTH ranges of positions
#: (before the window's length, and from it on). Readings and reasons:
#: ``perf/configs/smallthinker-21b-a3b.json`` ``job.why.loss_rtol``
LIMITS = {"bfloat16": {"q90": 0.03, "rms": 0.06},
          "float32": {"q90": 1e-4, "rms": 1e-4}}


def qkv_widths(app):
    hd = app.get("mha_head_dim") or app["d_model"] // app["n_heads"]
    hkv = app.get("n_kv_heads") or app["n_heads"]
    return app["n_heads"] * hd, hkv * hd, hkv * hd


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    E = app["moe_experts"]
    H = app.get("moe_experts_held") or E
    wq, wk, wv = qkv_widths(app)
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def normal(key, shape, scale=None):
        return jax.random.normal(key, shape, jnp.float32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        kr, kg, ku, kd = jax.random.split(ks[2], 4)
        layers.append({
            "g1": jnp.ones((d,), jnp.float32), "g2": jnp.ones((d,), jnp.float32),
            "wqkv": normal(ks[0], (d, wq + wk + wv)),
            "wo": normal(ks[1], (wq, d)),
            "router": normal(kr, (d, E)),
            "eg": normal(kg, (H, d, f)), "eu": normal(ku, (H, d, f)),
            "ed": normal(kd, (H, f, d))})
    return {
        "embed": normal(k_emb, (V, d), app.get("embed_std", 0.02)),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Rotate-half rotary positions on ``x [..., S, hd]``, positions 0..S-1."""
    S, hd = x.shape[-2:]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.concatenate([fn(ang)] * 2, axis=-1) for fn in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


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


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the tolerances must
    refuse. The router's product stays float32 on both sides."""
    return lambda t: _pick(
        ablate, "fp8_operands",
        lambda: t.astype(jnp.float8_e4m3fn).astype(jnp.float32), lambda: t)


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


def _block(x, layer, app, windowed: bool, ablate):
    """One block on ``x [B, S, d]``: ``(x, token-slots by expert [E], sum
    over tokens of the router's probabilities [E])``."""
    B, S, d = x.shape
    eps = app["norm_eps"]
    h = app["n_heads"]
    hkv = app.get("n_kv_heads") or h
    wq, wk, _ = qkv_widths(app)
    hd = wq // h
    rnd = _operands(ablate)
    a = rms_norm(x, layer["g1"], eps)
    qkv = rnd(a) @ rnd(layer["wqkv"])
    heads = lambda t: t.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    q, k, v = (heads(t) for t in jnp.split(qkv, (wq, wq + wk), axis=-1))
    if windowed:
        turn = lambda t: _pick(ablate, "no_rope_in_swa", lambda: t,
                               lambda: rotary(t, app["rope_theta"]))
        window = _pick(ablate, "no_window", lambda: jnp.int32(S),
                       lambda: jnp.int32(app["window"]))
    else:
        turn = lambda t: _pick(ablate, "rope_in_full",
                               lambda: rotary(t, app["rope_theta"]), lambda: t)
        window = jnp.int32(S)
    q, k = turn(q), turn(k)
    # query head h reads K/V head h // (H / Hkv); the ablation reads h % Hkv
    spread = lambda t: _pick(ablate, "kv_head_mod",
                             lambda: jnp.tile(t, (1, h // hkv, 1, 1)),
                             lambda: jnp.repeat(t, h // hkv, axis=1))
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, window, rnd),
                    (q, spread(k), spread(v)))
    y = x + rnd(o.transpose(0, 2, 1, 3).reshape(B, S, wq)) @ rnd(layer["wo"])
    b = rms_norm(y, layer["g2"], eps)
    # the experts, on [T, d]
    E, top_k = app["moe_experts"], app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    t = b.reshape(B * S, d)
    logits = _pick(ablate, "router_post_attn", lambda: t,
                   lambda: x.reshape(B * S, d)) @ layer["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)                      # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = probs * mask
    if app.get("moe_norm_topk"):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(t)
    tr = rnd(t)
    for e in range(H):  # every held expert on every token, weighted
        g = tr @ rnd(layer["eg"][e])
        gate = _pick(ablate, "silu_experts", lambda: jax.nn.silu(g),
                     lambda: jax.nn.relu(g))
        out = out + weight[:, e:e + 1] * (
            rnd(gate * (tr @ rnd(layer["eu"][e]))) @ rnd(layer["ed"][e]))
    return (y + out.reshape(B, S, d), jax.lax.stop_gradient(mask).sum(axis=0),
            probs.sum(axis=0))


def forward(params, inp, app, ablate=None):
    """``(logits [B, S, V], load-balance loss before its weight)``.
    ``ablate``: :func:`_flag`'s."""
    x = params["embed"][inp]
    if ablate == "no_aux":  # the loss's, not the logits'
        ablate = None
    tokens = prob = 0.0
    for i, layer in enumerate(params["layers"]):
        block = jax.checkpoint(functools.partial(
            _block, app=app, windowed=i in app["window_layers"], ablate=ablate))
        x, n, p = block(x, layer)
        tokens, prob = tokens + n, prob + p
    n = len(params["layers"]) * inp.shape[0] * inp.shape[1]
    lb = app["moe_experts"] * jnp.sum(tokens / n * prob / n)
    rnd = _operands(ablate)
    return (rnd(rms_norm(x, params["ln_f"], app["norm_eps"])) @ rnd(params["head"]),
            lb)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss_fn(params, tokens, app, ablate: Optional[str] = None):
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, lb = forward(params, inp, app, ablate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tgt[..., None], axis=-1).mean()
    return ce + (0.0 if ablate == "no_aux" else app["moe_aux_weight"]) * lb


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


def errors_by_range(a, b, window) -> Dict[str, Dict[str, float]]:
    """``position_errors`` apart for the positions a window cannot reach
    (``< window``: those rows see their whole causal past in every block)
    and those it does (``>= window``)."""
    return {"before_window": position_errors(a[:, :window], b[:, :window]),
            "from_window": position_errors(a[:, window:], b[:, window:])}


def check_logits(app: Dict[str, Any], inp, seed: int) -> Dict[str, Any]:
    """The program's logits on ``inp [B, S]`` (``TransformerLM.apply`` as the
    job path traces it: the configuration's dtype, the flash and
    grouped-matmul kernels where the device has them) against ``forward`` of
    this file, from the same seeded parameters as the cell trains them, on
    every position of every sequence. ``{"ok": bool, ...}``.

    Errors are taken position by position and reported apart for the
    positions before the window's length and from it on
    (``errors_by_range``). Rounding moves EVERY position a little, and a
    near-tie in a 64-wide router sends a token to another expert on one side
    only, which moves a FEW positions a lot: so two limits (``LIMITS``), the
    90th percentile over positions and the RMS over all of them (which
    bounds the tail), and the program must hold both in BOTH ranges. Every
    ablation of ``LOGIT_ABLATIONS`` is computed by the one compiled
    reference program (a vector of flags) on every call and must read above
    the ``q90`` limit in at least one range, or the check fails: it is
    shown to tell them apart on the run that uses it. (No second pass with
    sharpened queries, as Kimi Linear's cell needs: here every ablation
    reads twelve times the program or more as initialised — 28 whole heads
    in four blocks, where that cell has one latent block of five.)"""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    inp = jnp.asarray(inp)
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    window, limits = int(app["window"]), LIMITS[dtype]
    clock = {"start": time.monotonic()}
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        got = jax.block_until_ready(jax.jit(lm.apply)(params, inp))
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
        program = errors_by_range(got, want, window)
        del got
        clock["reference"] = time.monotonic()
        moved = {a: {r: {k: e[k] for k in ("q90", "rms")}
                     for r, e in errors_by_range(run(ref, a), want,
                                                 window).items()}
                 for a in LOGIT_ABLATIONS}
        clock["ablations"] = time.monotonic()
    detected = {a: any(e["q90"] > limits["q90"] for e in moved[a].values())
                for a in LOGIT_ABLATIONS}
    held = all(program[r][k] <= limits[k] for r in program for k in limits)
    marks = list(clock.items())
    return {"ok": bool(held and all(detected.values())),
            "program": program, "limits": limits, "ablations": moved,
            "detected": detected,
            "seconds": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
            "seed": int(seed), "dtype": dtype, "window": window}


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
    loss_of = jax.jit(lambda p, t: loss_fn(p, t, app, ablate))
    loss_and_grad = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, t, app, ablate)))
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
