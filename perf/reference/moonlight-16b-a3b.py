"""Plain reference for ``moonlight-16b-a3b``: forward, loss, gradients and Adam
by formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no sort, no table,
no jobserver. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

The block is DeepSeek-V3's as ``transformers``' ``modeling_deepseek_v3.py``
computes it (arXiv:2412.19437 section 2.1; Moonlight: arXiv:2502.16982):

  * pre-norm RMSNorm; **latent attention**: ``q = Wq n1(x)`` in heads of
    ``[q_nope | q_pe]``; ``Wkv_a n1(x) = [c | k_pe]``; ``Wkv_b rmsnorm(c)`` in
    heads of ``[k_nope | v]``; rotate-half rotary on ``q_pe`` and on the ONE
    ``k_pe`` every head shares; ``k = [k_nope | k_pe]``; causal softmax at
    scale ``(nope + rope) ** -0.5``; an out projection from ``heads x v``;
  * the first ``moe_first_dense`` layers a gated-SiLU MLP of width
    ``dense_d_ff``; after them ``s = sigmoid(Wr n2(h))``, the top-k of
    ``s + b`` (``b`` a per-expert bias with no gradient), weights
    ``scale * s_e / (sum of the chosen s + 1e-20)``, gated-SiLU experts, and
    one shared gated-SiLU MLP of ``moe_shared_experts`` expert widths added
    to every token;
  * a final norm and an untied head. Loss = cross-entropy +
    ``moe_aux_weight`` x the sequence-wise balance loss (eq. 17-20), each
    expert layer's mean over the sequences, the layers added.

The chip's share of the deployment (perf/configs/moonlight-16b-a3b.json):
only experts ``0 .. moe_experts_held-1`` exist here, so a token's routed sum
runs over its chosen experts that are held — the denominator of the weights
still over all it chose; the router, its bias and the balance loss keep all
experts. Experts are a plain loop over the held experts, each applied to
EVERY token under a dense [tokens, experts] weight matrix that is zero
outside the token's choices. Attention is a plain masked softmax over
``[S, S]``, taken a block of query rows at a time (``lax.map``) so that the
float32 scores of 8,192 positions fit; ``jax.checkpoint`` around a block of
the model and around a block of queries recomputes, it changes no number.

``replay`` is what the harness's ``correct`` evaluates. Before it replays,
it compares the PROGRAM's logits on the first batch with this file's
(``check_logits``) and, if they disagree, returns losses that are not
numbers: the cell then reports ``correct: false``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_EPS = 0.9, 1e-8
QUERY_BLOCK = 512

#: arithmetic broken on purpose, so that tests and every run's
#: ``check_logits`` can show a tolerance tells each apart
#: (``replay(..., ablate=...)``)
LOGIT_ABLATIONS = ("softmax_scores", "no_renorm", "no_routed_scale",
                   "no_shared", "top_k_minus_1", "scale_nope_only",
                   "no_kv_norm", "rope_on_k_nope", "no_rope_on_k_pe",
                   "fp8_operands")
#: these two change nothing while the bias is zero: ``check_logits`` shows
#: them under a seeded non-zero bias, given to program and reference alike
BIAS_ABLATIONS = ("bias_not_in_selection", "bias_in_weights")
ABLATIONS = LOGIT_ABLATIONS + BIAS_ABLATIONS + ("no_aux",)
#: ``check_logits``, by the program's activation dtype: what the 90th
#: percentile over positions of the per-position relative error may reach,
#: and the relative RMS over all positions (reasons in its docstring)
LOGITS_Q90_TOL = {"bfloat16": 0.03, "float32": 1e-4}
LOGITS_RMS_TOL = {"bfloat16": 0.06, "float32": 1e-4}
#: the seeded bias of the second pass: as wide as the scores spread, and
#: leaning toward the held experts, so that at nearly every position it
#: decides choices whose results are computed here and would move weights
#: that count here (a plain random bias leaves that to how many of the
#: favoured experts happen to be held: the weakest ablation's 90th percentile
#: read 0.045-0.16 over three seeds)
BIAS_STD, BIAS_HELD = 0.5, 0.5


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, L, V = app["d_model"], app["n_layers"], app["vocab_size"]
    h, f = app["n_heads"], app["d_ff"]
    nope, rot = app["qk_nope_head_dim"], app["qk_rope_head_dim"]
    r, vd = app["kv_lora_rank"], app["v_head_dim"]
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
        kq, ka, kb = jax.random.split(ks[0], 3)
        layer = {
            "ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
            "wq": normal(kq, (d, h * (nope + rot))),
            "wkv_a": normal(ka, (d, r + rot)),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "wkv_b": normal(kb, (r, h * (nope + vd))),
            "wo": normal(ks[1], (h * vd, d)),
        }
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


def seeded_bias(app: Dict[str, Any], seed: int):
    E = app["moe_experts"]
    noise = BIAS_STD * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), 7), (E,), jnp.float32)
    held = jnp.arange(E) < (app.get("moe_experts_held") or E)
    return noise + BIAS_HELD * held


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


def _operands(ablate):
    """What a matrix product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the tolerances must
    refuse. The router's product stays float32 on both sides."""
    if ablate != "fp8_operands":
        return lambda t: t
    return lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _swiglu(t, wg, wu, wd, rnd=lambda t: t):
    t = rnd(t)
    return rnd(jax.nn.silu(t @ rnd(wg)) * (t @ rnd(wu))) @ rnd(wd)


def _block(x, layer, app, ablate):
    """One block on ``x [B, S, d]``: ``(x, sequence-wise balance term or 0,
    token-slots by expert)``."""
    B, S, d = x.shape
    h, eps = app["n_heads"], app["norm_eps"]
    nope, rot = app["qk_nope_head_dim"], app["qk_rope_head_dim"]
    r, vd = app["kv_lora_rank"], app["v_head_dim"]
    heads = lambda t, w: t.reshape(B, S, h, w).transpose(0, 2, 1, 3)
    rnd = _operands(ablate)
    xn = rnd(rms_norm(x, layer["ln1"], eps))
    q = heads(xn @ rnd(layer["wq"]), nope + rot)
    ckv = xn @ rnd(layer["wkv_a"])
    c, k_pe = ckv[..., :r], ckv[:, None, :, r:]                 # one key head
    if ablate != "no_kv_norm":
        c = rms_norm(c, layer["kv_norm"], eps)
    kv = heads(rnd(c) @ rnd(layer["wkv_b"]), nope + vd)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], app["rope_theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if ablate != "no_rope_on_k_pe":
        k_pe = rotary(k_pe, app["rope_theta"])
    if ablate == "rope_on_k_nope":
        k_nope = rotary(k_nope, app["rope_theta"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, h, S, rot))], axis=-1)
    scale = (nope if ablate == "scale_nope_only" else nope + rot) ** -0.5
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, scale, rnd), (q, k, v))
    hid = x + rnd(o.transpose(0, 2, 1, 3).reshape(B, S, h * vd)) @ rnd(layer["wo"])
    t = rms_norm(hid, layer["ln2"], eps)
    if "router" not in layer:  # a leading dense layer
        return (hid + _swiglu(t, layer["wg"], layer["wu"], layer["wd"], rnd),
                0.0, None)
    # the experts, on [T, d]
    E, top_k = app["moe_experts"], app["moe_top_k"]
    H = app.get("moe_experts_held") or E
    if ablate == "top_k_minus_1":
        top_k -= 1
    t = t.reshape(B * S, d)
    logits = t @ layer["router"]                                 # [T, E]
    score = (jax.nn.softmax(logits, axis=-1) if ablate == "softmax_scores"
             else jax.nn.sigmoid(logits))
    bias = jax.lax.stop_gradient(layer["bias"])
    select = score if ablate == "bias_not_in_selection" else score + bias
    _, chosen = jax.lax.top_k(select, top_k)                     # [T, k]
    mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(axis=1)  # [T, E]
    weight = (score + bias if ablate == "bias_in_weights" else score) * mask
    if app["moe_norm_topk"] and ablate != "no_renorm":
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    if ablate != "no_routed_scale":
        weight = weight * app["moe_routed_scale"]
    y = jnp.zeros_like(t)
    for e in range(H):  # every held expert on every token, weighted
        y = y + weight[:, e:e + 1] * _swiglu(t, layer["eg"][e], layer["eu"][e],
                                             layer["ed"][e], rnd)
    if ablate != "no_shared":
        y = y + _swiglu(t, layer["sg"], layer["su"], layer["sd"], rnd)
    # eq. 17-20, a sequence at a time: f counts (no gradient), P is the mean
    # score normalised over the experts
    f = jax.lax.stop_gradient(mask).reshape(B, S, E).sum(axis=1) * (
        E / (app["moe_top_k"] * S))
    p = (score / score.sum(axis=-1, keepdims=True)).reshape(B, S, E).mean(axis=1)
    return hid + y.reshape(B, S, d), jnp.sum(f * p, axis=-1).mean(), mask.sum(axis=0)


def forward(params, inp, app, ablate: Optional[str] = None):
    """``(logits [B, S, V], balance term summed over the expert layers,
    [token-slots by expert of each expert layer])``."""
    x = params["embed"][inp]
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    aux, chosen = 0.0, []
    for layer in params["layers"]:
        x, a, n = block(x, layer, _Static(app), ablate)
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


def rel_rms(a, b) -> float:
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


QUANTILES = (0.5, 0.75, 0.9, 0.99)


def position_errors(a, b) -> Dict[str, float]:
    """Relative error of ``a`` against ``b [B, S, V]`` position by position
    (each position's error vector over its logit vector, in norm): the
    overall relative RMS and quantiles over the positions."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    per = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1) / jnp.sum(b ** 2, axis=-1))
    qs = jnp.quantile(per.reshape(-1), jnp.asarray(QUANTILES))
    return {"rms": rel_rms(a, b),
            **{f"q{int(100 * q)}": float(v) for q, v in zip(QUANTILES, qs)}}


def check_logits(app: Dict[str, Any], inp, seed: int) -> Dict[str, Any]:
    """The program's logits on ``inp [B, S]`` (``TransformerLM.apply`` as the
    job path traces it: the configuration's dtype, the flash kernels at
    (192, 128) and the grouped-matmul kernels where the device has them)
    against ``forward`` of this file, from the same seeded parameters, on
    every position of every sequence. Two passes: the parameters as
    initialised (the selection bias zero, as the cell trains), then the same
    with a seeded non-zero bias on both sides, which is what shows that the
    bias reaches the selection and never a weight. ``{"ok": bool, ...}``.

    The error is taken position by position (``position_errors``), because
    it has two parts of different kinds. Rounding moves EVERY position a
    little: bfloat16 reads 0.0126-0.0140 from the median to the 90th
    percentile. And a near-tie in the router sends a token to another
    expert on one side only, which moves a FEW positions a lot (each chosen
    expert weighs ~0.4 after the renormalisation and the 2.446): 1-2% of the
    positions read ~0.24 and lift the overall RMS to 0.033-0.035 as
    initialised; under the seeded bias, which decides most choices, hardly
    any do (99th percentile 0.015, RMS 0.021-0.022). So two limits, by the
    program's dtype, in both passes: the 90TH PERCENTILE over positions at
    most 0.03 (bfloat16; what every ablation is judged by, its own 90th
    percentile against the reference's logits: the weakest read 0.075 —
    the bias leaking into the weights — and 0.13 — softmax for sigmoid;
    float8 operands read far more), and the RMS over all positions at most
    0.06, which bounds the tail: a wrong tile of 512 of 8,192 positions
    would read 0.25. float32 (the CPU rehearsal and tests): 1e-4 for both,
    summation order only. My chip runs, PR 29; the seeds' readings are in
    perf/configs/moonlight-16b-a3b.json ``job.why.loss_rtol``. Every
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
    q90_tol, rms_tol = LOGITS_Q90_TOL[dtype], LOGITS_RMS_TOL[dtype]
    bias = seeded_bias(app, seed)
    experts = lm.config.moe_layers()
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        apply = jax.jit(lm.apply)
        got = apply(params, inp)
        for i in experts:
            params["layers"][i]["moe"]["bias"] = bias
        got_biased = apply(params, inp)
    del params
    static = _Static(app)
    with jax.default_matmul_precision("highest"):
        ref = init_params(app, seed)
        biased = {**ref, "layers": [
            {**layer, "bias": bias} if i in experts else layer
            for i, layer in enumerate(ref["layers"])]}
        run = lambda p, ablate: jax.jit(
            lambda p, t: forward(p, t, static, ablate)[0])(p, inp)
        want = run(ref, None)
        program = {"as_initialised": position_errors(got, want)}
        moved = {a: position_errors(run(ref, a), want)["q90"]
                 for a in LOGIT_ABLATIONS}
        del got, want
        want = run(biased, None)
        program["seeded_bias"] = position_errors(got_biased, want)
        moved.update({a: position_errors(run(biased, a), want)["q90"]
                      for a in BIAS_ABLATIONS})
    return {"ok": bool(all(e["q90"] <= q90_tol and e["rms"] <= rms_tol
                           for e in program.values())
                       and all(m > q90_tol for m in moved.values())),
            "program": program, "q90_tol": q90_tol, "rms_tol": rms_tol,
            "ablations_q90": moved, "seed": int(seed), "dtype": dtype}


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

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, toks):
        # a sequence at a time (both loss terms are means over sequences of
        # one length): the float32 activations of one 8,192-token sequence
        # are what fits beside [params | m | v | g] on a 16 GB chip
        losses, grads = jax.lax.map(lambda t: jax.value_and_grad(loss_fn)(
            params, t[None], app, ablate), toks)
        tm = jax.tree.map
        loss, g = losses.mean(), tm(lambda a: a.mean(axis=0), grads)
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
