"""Plain reference for ``ouro-2.6b``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no table, no
jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops`` is used to
compute it. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

Ouro-2.6B (``config.json`` of ``ByteDance/Ouro-2.6B``, ``model_type``
``ouro``; the looped language model of arXiv:2510.25741): ``L`` layers run
``T = total_ut_steps`` times over ONE set of weights, four norms a block, the
final norm inside the loop, an exit after every pass. With ``RMS(x; g)`` an
RMSNorm with weight ``g``, no biases, input ``tokens [S]``:

    h(0) = embed[tokens]                       (no position table)
    for t = 1..T:                              # weights shared over t
        x = h(t-1)
        for l = 1..L:
            a = Attn_l(RMS(x; g1_l))           # causal, H heads of hd, rotate-
            x = x + RMS(a; g2_l)               #   half rotary on the whole head
            u = RMS(x; g3_l);  m = (silu(u Wg) * (u Wu)) Wd
            x = x + RMS(m; g4_l)
        h(t)      = RMS(x; g_f)                # exit t reads it, pass t+1
        logits(t) = h(t) head                  #   starts from it
        lam(t)    = sigmoid(h(t) . w_e + b_e)
    S(0) = 1;  S(t) = S(t-1) (1 - lam(t));  p(t) = lam(t) S(t-1) for t < T;
    p(T) = S(T-1)
    nll(t)[n] = -log softmax(logits(t)[n])[target[n]]
    loss = mean_n [ sum_t p(t)[n] nll(t)[n] - beta H(p[n]) ],
    H(p) = -sum_t p(t) log p(t)                (0 log 0 = 0)

(``g1..g4`` are ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``; the loss is the
paper's entropy-regularised expected loss over the exit step.) The mask is an
explicit boolean, and attention runs a block of ``QUERY_BLOCK`` query rows at
a time so that ``H x S x S`` scores never exist at once.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM on the first batch with this file (``check_logits``):
every exit's logits position by position, every exit's gate, the loss, and the
gradient of the program's loss leaf by leaf against this file's own in float8
as the control; if they disagree, it returns losses that are not numbers.
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

#: arithmetic broken on purpose, so that the tests and ``check_logits`` can
#: show a limit tells each apart (``replay(..., ablate=...)``): float8
#: operands in every product (the control); the last pass left out (``h(T) =
#: h(T-1)``: a program with ``T - 1`` passes); the next pass started from the
#: rows BEFORE the final norm; ``g2`` / ``g4`` left out; ``g2`` / ``g4`` applied
#: after the residual add (``x = RMS(x + a; g2)``); ``p`` uniform; the last
#: pass's own gate counted (``p(T) = lam(T) S(T-1)``: ``p`` no longer sums to
#: 1); the entropy term dropped; and two that leave every VALUE alone —
#: ``p`` outside the gradient, and the layers' gradient taken from the last
#: pass alone (the uses in the passes before it outside the gradient)
LOGIT_ABLATIONS = ("fp8_operands", "one_pass_less", "norm_not_fed",
                   "no_post_norm", "post_norm_after_add", "uniform_exit",
                   "last_gate_counts", "no_entropy", "exit_outside_gradient",
                   "last_pass_gradient")
#: the ablations EVERY run of the cell computes: float8 operands, the control
#: of both precisions' limits (logits and gradient), and the pass left out —
#: what a program with a loop one short would compute. That the limits tell
#: the others apart is a property of this file and of the limits, not of the
#: program: tests/test_ouro.py plants all ten
RUN_ABLATIONS = ("fp8_operands", "one_pass_less")
#: ``check_logits``' limits by the activation dtype THE CONFIGURATION STATES
#: (a program run below it is held to the stated one's). Readings and
#: reasons: ``perf/configs/ouro-2.6b.json`` ``job.why.loss_rtol``.
#: ``q90`` / ``rms``: every exit's logits, the 90th percentile over positions
#: of the per-position relative error and the relative RMS over all of them;
#: ``gate``: the largest absolute error of ``lam(t)`` (and of ``p(t)`` formed
#: from it) over exits and positions; ``loss``: the first loss's relative
#: error; ``gradient``: a leaf's error ``|g - g_ref|`` as a share of what
#: float8 operands do to the same leaf (``against_control``: the control
#: reads 1), the worst leaf; ``scalar``: the relative error of a leaf of one
#: number (``exit_b``), which no share of a control's ONE draw can bound —
#: a limit on WRONGNESS, not on precision: a wrong gradient reads 1
LIMITS = {
    "bfloat16": {"q90": 0.06, "rms": 0.06, "gate": 0.05, "loss": 2e-4,
                 "gradient": 0.15, "scalar": 0.2},
    "float32": {"q90": 1e-4, "rms": 1e-4, "gate": 1e-4, "loss": 2e-5,
                "gradient": 1e-3, "scalar": 1e-3},
}


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, L, V, f = app["d_model"], app["n_layers"], app["vocab_size"], app["d_ff"]
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def normal(key, shape, scale=None):
        return jax.random.normal(key, shape, jnp.float32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    ones = lambda: jnp.ones((d,), jnp.float32)
    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        layers.append({
            "g1": ones(), "g2": ones(), "g3": ones(), "g4": ones(),
            "wqkv": normal(ks[0], (d, 3 * d)), "wo": normal(ks[1], (d, d)),
            "wg": normal(ks[2], (d, f)), "wd": normal(ks[3], (f, d)),
            "wu": normal(jax.random.fold_in(ks[2], 1), (d, f))})
    return {
        "embed": normal(k_emb, (V, d), app.get("embed_std", 0.02)),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
        "ln_f": ones(),
        "exit_w": normal(jax.random.fold_in(k_emb, 2), (d, 1))[:, 0],
        "exit_b": jnp.zeros((), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta: float):
    """Rotate-half rotary positions ``0 .. S-1`` on every column of ``x [...,
    S, hd]``: ``inv_freq_i = theta^(-2i/hd)``."""
    S, hd = x.shape[-2:]
    inv = jnp.float32(theta) ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
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
    return jax.tree.map(lambda a, b: jnp.where(on, a, b), broken(), whole())


def _to_float8(t):
    """``t`` rounded to float8 (e4m3) with the gradient passed STRAIGHT
    THROUGH the rounding: differentiating the casts would round every
    cotangent to e4m3 too, whose smallest number is 2^-9 — gradients of 1e-5
    underflow and the control would read 0 (PERF.md section 6, PR 54)."""
    return t + jax.lax.stop_gradient(
        t.astype(jnp.float8_e4m3fn).astype(jnp.float32) - t)


def _operands(ablate):
    """What a product's operands pass through: nothing, or, under
    ``fp8_operands``, a rounding to float8 (e4m3) — the nearest precision
    below the bfloat16 the configuration states, which the limits must
    refuse."""
    return lambda t: _pick(ablate, "fp8_operands", lambda: _to_float8(t),
                           lambda: t)


def _attention_one(q, k, v, rnd):
    """Causal softmax attention of one sequence, ``q, k, v [H, S, hd]``: the
    boolean mask ``j <= i``, a block of query rows at a time."""
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


def _block(x, layer, app, ablate):
    """One block on ``x [B, S, d]`` (module docstring)."""
    B, S, d = x.shape
    eps, H = app["norm_eps"], app["n_heads"]
    hd = d // H
    rnd = _operands(ablate)

    def add(x, y, g):  # x + RMS(y; g), or one of the two broken placements
        return _pick(
            ablate, "no_post_norm", lambda: x + y,
            lambda: _pick(ablate, "post_norm_after_add",
                          lambda: rms_norm(x + y, g, eps),
                          lambda: x + rms_norm(y, g, eps)))

    a = rms_norm(x, layer["g1"], eps)
    qkv = rnd(a) @ rnd(layer["wqkv"])
    heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
    theta = float(app["rope_theta"])
    q, k = rotary(q, theta), rotary(k, theta)
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, rnd), (q, k, v))
    o = rnd(o.transpose(0, 2, 1, 3).reshape(B, S, d)) @ rnd(layer["wo"])
    x = add(x, o, layer["g2"])
    u = rnd(rms_norm(x, layer["g3"], eps))
    m = rnd(jax.nn.silu(u @ rnd(layer["wg"])) * (u @ rnd(layer["wu"]))) \
        @ rnd(layer["wd"])
    return add(x, m, layer["g4"])


def _passes(params, inp, targets, app, ablate=None):
    """``(logits, lam, nll)`` of every pass, a list of ``T`` each: ``[B, S,
    V]``, ``[B, S]`` and ``[B, S]`` (``nll`` against ``targets [B, S]``). An
    exit is ONE checkpointed function of the pass's normed rows, so that a
    gradient keeps no ``[B, S, V]`` array but the logits themselves."""
    T, eps = int(app["loop_steps"]), app["norm_eps"]
    rnd = _operands(ablate)
    block = jax.checkpoint(functools.partial(_block, app=app, ablate=ablate))

    @jax.checkpoint
    def exit_of(h, head, w, b):
        logits = rnd(h) @ rnd(head)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return (logits, jax.nn.sigmoid(jnp.sum(rnd(h) * rnd(w), axis=-1) + b),
                nll)

    x = params["embed"][inp]
    out = []
    for t in range(T):
        layers = params["passes"][t] if "passes" in params else params["layers"]
        if t < T - 1:  # the uses before the last pass, outside the gradient
            layers = _pick(ablate, "last_pass_gradient",
                           lambda: jax.lax.stop_gradient(layers),
                           lambda: layers)
        start = x
        for layer in layers:
            x = block(x, layer)
        h = rms_norm(x, params["ln_f"], eps)
        if t == T - 1:  # a loop one pass short: the last exit is the one before
            h = _pick(ablate, "one_pass_less", lambda: start, lambda: h)
        out.append(exit_of(h, params["head"], params["exit_w"],
                           params["exit_b"]))
        x = _pick(ablate, "norm_not_fed", lambda: x, lambda: h)
    return tuple(list(part) for part in zip(*out))


def forward(params, inp, app, ablate=None):
    """``(logits [T, B, S, V], lam [T, B, S])`` of every pass.
    ``params["passes"]`` (a list of ``T`` lists of layers), where present,
    gives each pass weights of its OWN in place of the shared
    ``params["layers"]``: the untied model whose gradients, summed over the
    passes, the shared weights' gradient must equal. ``ablate``:
    :func:`_flag`'s."""
    logits, lam, _ = _passes(params, inp, jnp.zeros_like(inp), app, ablate)
    return jnp.stack(logits), jnp.stack(lam)


def exit_distribution(lam, ablate=None):
    """``p [T, ...]`` of the gates ``lam [T, ...]`` (module docstring)."""
    T = lam.shape[0]
    stay, p = jnp.ones_like(lam[0]), []
    for t in range(T - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p.append(_pick(ablate, "last_gate_counts", lambda: lam[T - 1] * stay,
                   lambda: stay))
    p = jnp.stack(p)
    return _pick(ablate, "uniform_exit", lambda: jnp.full_like(p, 1.0 / T),
                 lambda: p)


def exit_loss(nll, lam, beta, ablate=None):
    """The loss of the module docstring from every pass's ``nll [T, B, S]``
    and gates ``lam [T, B, S]``."""
    p = exit_distribution(lam, ablate)
    p = _pick(ablate, "exit_outside_gradient",
              lambda: jax.lax.stop_gradient(p), lambda: p)
    live = p > 0
    entropy = -jnp.sum(jnp.where(live, p * jnp.log(jnp.where(live, p, 1.0)),
                                 0.0), axis=0)
    beta = _pick(ablate, "no_entropy", lambda: jnp.float32(0.0),
                 lambda: jnp.float32(beta))
    return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)


class _Static(dict):
    """The configuration as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def loss_and_exits(params, tokens, app, ablate=None):
    """``(loss, (logits, lam))`` on the batch ``tokens [B, S + 1]``:
    ``logits`` a tuple of the ``T`` passes' ``[B, S, V]`` (never stacked: at
    the cell's size each is 805 MB), ``lam [T, B, S]``."""
    logits, lam, nll = _passes(params, tokens[:, :-1], tokens[:, 1:], app,
                               ablate)
    lam = jnp.stack(lam)
    loss = exit_loss(jnp.stack(nll), lam,
                     app.get("exit_entropy_weight", 0.0), ablate)
    return loss, (tuple(logits), lam)


def loss_fn(params, tokens, app, ablate=None):
    return loss_and_exits(params, tokens, app, ablate)[0]


def flags_of(ablate: Optional[str]):
    """``_flag``'s vector for one of ``LOGIT_ABLATIONS`` (None: all off)."""
    flags = np.zeros(len(LOGIT_ABLATIONS), np.float32)
    if ablate is not None:
        flags[LOGIT_ABLATIONS.index(ablate)] = 1.0
    return flags


@functools.partial(jax.jit, static_argnames=("app",))
def loss_grad_logits(params, tokens, app, flags):
    """``((loss, (logits, lam)), gradient)`` — the ONE compiled reference
    program of a run: ``check_logits``' exits and gradient, each ablation's
    and the float8 control's (``flags``: :func:`flags_of`, traced) and every
    step of the replay. ``app``: a ``_Static``. No argument has a default:
    one left out would be a constant of another program, compiled again."""
    return jax.value_and_grad(loss_and_exits, has_aux=True)(
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


def exit_errors(got, want) -> List[Dict[str, float]]:
    """``position_errors`` of each exit's logits, ``got`` and ``want`` ``T``
    arrays ``[B, S, V]`` (``got`` may lie on the host: an exit at a time goes
    to the device)."""
    return [position_errors(jnp.asarray(got[t]), want[t])
            for t in range(len(want))]


def gate_errors(got, want) -> Dict[str, float]:
    """The largest absolute error of the gates ``lam [T, B, S]`` and of the
    exit distribution formed from them."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    worst = lambda a, b: float(jnp.nan_to_num(
        jnp.max(jnp.abs(a - b)), nan=DIVERGED))
    return {"lam": worst(got, want),
            "p": worst(exit_distribution(got), exit_distribution(want))}


def from_program(tree: Dict[str, Any]) -> Dict[str, Any]:
    """A parameter (or gradient) tree of the PROGRAM under this file's names
    (``init_params``')."""
    names = {"ln1": "g1", "ln1_post": "g2", "ln2": "g3", "ln2_post": "g4",
             "wqkv": "wqkv", "wo": "wo", "w1": "wg", "w3": "wu", "w2": "wd"}
    return {**{k: tree[k] for k in ("embed", "head", "ln_f", "exit_w", "exit_b")},
            "layers": [{names[k]: v for k, v in l.items()}
                       for l in tree["layers"]]}


def gradient_errors(got, want) -> Dict[str, List[float]]:
    """``[|got - want|^2, |want|^2]`` of every leaf (both trees under this
    file's names, on the host), summed over the layers — ``wqkv``'s q, k and
    v columns apart (``wq``, ``wk``, ``wv``), each of the four norms its own
    leaf."""
    def add(name, a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err, norm = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        if not np.isfinite(err) or (norm == 0.0 and np.any(a)):
            err = DIVERGED
        row = sums.setdefault(name, [0.0, 0.0])
        row[0] += err
        row[1] += norm

    sums: Dict[str, List[float]] = {}
    for name in ("embed", "head", "ln_f", "exit_w", "exit_b"):
        add(name, got[name], want[name])
    for a, b in zip(got["layers"], want["layers"]):
        for name in b:
            if name == "wqkv":
                for part, x, y in zip(("wq", "wk", "wv"),
                                      np.split(np.asarray(a[name]), 3, axis=1),
                                      np.split(np.asarray(b[name]), 3, axis=1)):
                    add(part, x, y)
            else:
                add(name, a[name], b[name])
    return sums


#: the leaves of one number: held by their own relative error (``LIMITS``
#: ``scalar``), not as a share of the control's single draw
SCALARS = ("exit_b",)


def against_control(program, control) -> Dict[str, Any]:
    """The program's ``gradient_errors`` as a share of the control's, leaf
    by leaf: ``{"worst", "worst_leaf", "scalar", "by_leaf": {leaf: [the
    program's relative error, the control's, their ratio]}}``; ``worst``
    over the leaves of more than one number, ``scalar`` the largest relative
    error of the others. Where the control reads 0 the program must."""
    by_leaf = {}
    for leaf, (err, norm) in program.items():
        low = control[leaf][0]
        ratio = (err / low) ** 0.5 if low > 0.0 else (
            0.0 if err == 0.0 else DIVERGED)
        scale = norm if norm > 0.0 else 1.0
        by_leaf[leaf] = [(err / scale) ** 0.5, (low / scale) ** 0.5, ratio]
    arrays = [leaf for leaf in by_leaf if leaf not in SCALARS]
    worst = max(arrays, key=lambda leaf: by_leaf[leaf][2])
    return {"worst": by_leaf[worst][2], "worst_leaf": worst,
            "scalar": max(by_leaf[leaf][0] for leaf in SCALARS),
            "by_leaf": by_leaf}


def _held(report: Dict[str, Any], limits: Dict[str, float]) -> Dict[str, bool]:
    """Which of ``limits`` a side's readings (``exits``, ``gate``, ``loss``,
    ``gradients``) hold."""
    return {
        "logits": all(e["q90"] <= limits["q90"] and e["rms"] <= limits["rms"]
                      for e in report["exits"]),
        "gate": max(report["gate"].values()) <= limits["gate"],
        "loss": report["loss"] <= limits["loss"],
        "gradient": report["gradients"]["worst"] <= limits["gradient"]
        and report["gradients"]["scalar"] <= limits["scalar"],
    }


def check_logits(app: Dict[str, Any], tokens, seed: int,
                 program_app: Optional[Dict[str, Any]] = None,
                 ablations: Sequence[str] = RUN_ABLATIONS,
                 first: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The program on the batch ``tokens [B, S + 1]`` (``TransformerLM`` as
    the job path traces it: the configuration's dtype, the flash and readout
    kernels where the device has them) against this file, from the same
    seeded parameters as the cell trains them. ``{"ok": bool, ...}``.
    ``program_app``: the PROGRAM's configuration where a test breaks the
    program on purpose (the reference, and the limits, keep ``app``).
    ``ablations``: which of ``LOGIT_ABLATIONS`` the one compiled reference
    program (``loss_grad_logits``, a vector of flags) also computes —
    ``fp8_operands`` always among them: it is the control. ``first``: a dict
    that receives the reference's ``loss`` and ``gradient`` (on the host) on
    this batch — the replay's first step, which need not be computed twice.

    Four readings of the program, each under its limit (``LIMITS``, by the
    dtype ``app`` states): EVERY EXIT'S LOGITS (``lm.exits`` against
    ``forward``) position by position, the 90th percentile over positions
    and the RMS over all of them; EVERY EXIT'S GATE ``lam(t)`` and the
    ``p(t)`` formed from it, the largest absolute error; the LOSS
    (``lm.loss``, the function the trainer differentiates: the fused
    readout's ``nll(t)`` under weights ``p(t)`` that carry a gradient, and
    the entropy term); its GRADIENT leaf by leaf (``gradient_errors``) as a
    share of the control's — this file's own gradient with every product's
    operands rounded to float8 (a leaf that is simply wrong reads one over the
    control's relative error there) — and the one-number leaf ``exit_b`` by
    its relative error.

    An ablation is TOLD APART where, read as if it were the program, it
    breaks at least one of the four limits; one that breaks none fails the
    check (the limits would pass a program that computes it)."""
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
    stated = jnp.dtype(app.get("dtype", "float32")).name
    limits = LIMITS[stated]
    clock = {"start": time.monotonic()}
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        # everything of the program waits on the HOST: the device holds one
        # side's exits at a time beside whatever the process keeps there
        got_loss, got_g = jax.device_get(
            jax.jit(jax.value_and_grad(lm.loss))(params, tokens))
        got_g = from_program(got_g)
        clock["program_gradient"] = time.monotonic()
        got, got_lam = jax.device_get(jax.jit(lm.exits)(params, inp))
    del params
    clock["program"] = time.monotonic()
    static = _Static(app)
    with jax.default_matmul_precision("highest"):
        ref = init_params(app, seed)
        (ref_loss, (want, want_lam)), want_g = loss_grad_logits(
            ref, tokens, static, flags_of(None))
        want_g = jax.device_get(want_g)
        ref_loss = float(ref_loss)
        rel = lambda x: abs(float(x) - ref_loss) / abs(ref_loss)
        program = {"exits": exit_errors(got, want),
                   "gate": gate_errors(got_lam, want_lam),
                   "loss": rel(got_loss)}
        del got
        clock["reference"] = time.monotonic()
        broken = {}
        for a in ablations:
            (loss, (logits, lam)), g = loss_grad_logits(
                ref, tokens, static, flags_of(a))
            broken[a] = {"exits": exit_errors(logits, want),
                         "gate": gate_errors(lam, want_lam), "loss": rel(loss),
                         "gradients": gradient_errors(jax.device_get(g),
                                                      want_g)}
            del logits, lam, g
        clock["ablations"] = time.monotonic()
    control = broken["fp8_operands"]["gradients"]
    program["gradients"] = against_control(
        gradient_errors(got_g, want_g), control)
    for a in ablations:
        broken[a]["gradients"] = against_control(broken[a]["gradients"],
                                                 control)
    if first is not None:
        first.update(loss=ref_loss, gradient=want_g)
    del got_g, want_g
    held = _held(program, limits)
    # the control reads 1 on every leaf of its own gradient by construction:
    # it is told apart by the values
    detected = {a: not all(_held(broken[a], limits).values())
                for a in ablations}
    marks = list(clock.items())
    lean = lambda r: {**r, "gradients": {k: v for k, v in r["gradients"].items()
                                         if k != "by_leaf"}}
    return {"ok": bool(all(held.values()) and all(detected.values())),
            "held": held, "program": program, "limits": limits,
            "ablations": {a: lean(r) for a, r in broken.items()},
            "detected": detected,
            "seconds": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
            "seed": int(seed), "dtype": stated}


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
