"""Plain reference for ``nemotron-3-super-120b-a12b``: forward, loss, gradients
and Adam by formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no chunks, no sort,
no table, no jobserver; nothing of ``harmony_tpu/models`` or ``harmony_tpu/ops``
is used to compute it. It replays the job's first steps from the same seeded
initial parameters and the same batches and returns each step's loss.

The model (``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``,
``model_type`` ``nemotron_h``; the Mamba-2 layer: arXiv:2405.21060). EVERY layer
``i`` is one pre-norm sublayer, chosen by letter ``i`` of ``layer_pattern``:

    x <- x + f_i(RMSNorm(x; w_i, eps))            u = RMSNorm(x; w_i, eps)

``M`` (Mamba-2; ``H`` heads of ``P``, ``G`` groups, state ``N``, ``K`` taps):

    [z | xBC | dt] = u W_in
    xBC  = SiLU(causal_depthwise_conv_K(xBC) + b_c)  ->  x [H, P], B [G, N], C [G, N]
    D_t  = softplus(dt_t + dt_bias)                a_t = exp(-D_t exp(A_log))     (a head)
    S_t  = a_t S_{t-1} + D_t x_t B_t^T  [P, N]     (head h reads group h // (H / G))
    y_t  = S_t C_t + skip x_t
    out  = RMSNorm_group(y * SiLU(z); w) W_out     (one norm a group's channels)

the recurrence as a ``lax.scan`` over TIME — one position a step, in
checkpointed runs of ``SCAN_BLOCK`` positions so that the backward keeps one
run's states and not every position's.

``*``: ``q, k, v = split(u Wqkv)`` (``Hq`` query heads over ``Hkv`` K/V heads,
query head h reads K/V head ``h // (Hq / Hkv)``), causal softmax over the whole
past, NO positions, ``Wo``; a block of ``QUERY_BLOCK`` query rows at a time.

``E`` (LatentMoE): ``s = sigmoid(u W_r)`` over ``E``; the top ``k`` of ``s +
bias``; weights ``s_e / sum over the chosen`` x ``routed_scale``; ``l = u
W_down``; ``r = sum_{e chosen, e held} w_e ReLU(l W1_e)^2 W2_e``; ``out = r W_up
+ ReLU(u V1)^2 V2``. The sequence-wise balance loss (arXiv:2412.19437 eq.
17-20) joins the cross-entropy at ``moe_aux_weight``.

Then the final RMSNorm and the untied head, float32 logits.

The chip's share (the configuration file's ``deployment``) is given as
arguments (``app``): ``ssd_heads`` / ``ssd_groups`` / ``n_heads`` /
``n_kv_heads`` / ``moe_experts_held`` / ``moe_shared_d_ff`` / ``vocab_size`` as
held; the router, its top-k, the renormalisation and the balance loss keep all
``E``.

``replay`` is what the harness's ``correct`` evaluates. Before it replays, it
compares the PROGRAM's logits on the first batch with this file's
(``check_logits``), position by position, and, if they disagree, returns
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
SCAN_BLOCK = 128
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)   # Mamba-2's own initial ranges

#: arithmetic broken on purpose, so that tests and every run's
#: ``check_logits`` can show a tolerance tells each apart
#: (``replay(..., ablate=...)``). ``wrong_group`` needs two groups or more
#: held: the cell holds one, so it is shown at the rehearse size (tests)
LOGIT_ABLATIONS = ("no_decay", "wrong_group", "gated_experts",
                   "latent_skipped", "top_k_minus_1", "no_shared",
                   "fp8_operands")
ABLATIONS = LOGIT_ABLATIONS + ("no_aux",)
#: ``check_logits``' limits by the program's activation dtype: the 90th
#: percentile over positions of the per-position relative error, and the
#: relative RMS over all positions. Readings and reasons:
#: ``perf/configs/nemotron-3-super-120b-a12b.json`` ``job.why.loss_rtol``
LIMITS = {"bfloat16": {"q90": 0.03, "rms": 0.05},
          "float32": {"q90": 1e-4, "rms": 1e-4}}


def widths(app):
    """``(heads' channels H P, the convolution's channels, W_in's columns)``
    of an ``M`` layer, and ``(q, k, v)`` of a ``*`` layer's ``Wqkv``."""
    inner = app["ssd_heads"] * app["ssd_head_dim"]
    conv = inner + 2 * app["ssd_groups"] * app["ssd_state"]
    hd = app["mha_head_dim"]
    return (inner, conv, inner + conv + app["ssd_heads"]), (
        app["n_heads"] * hd, app["n_kv_heads"] * hd, app["n_kv_heads"] * hd)


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales), under
    this file's own names."""
    d, V, f = app["d_model"], app["vocab_size"], app["d_ff"]
    E, K = app["moe_experts"], app["short_conv"]
    H = app.get("moe_experts_held") or E
    r, fs = app["moe_latent"], app["moe_shared_d_ff"]
    (inner, conv, proj), qkv = widths(app)
    pattern = app["layer_pattern"]
    k_emb, _k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed),
                                                2 + len(pattern))

    def normal(key, shape, scale=None):
        return jax.random.normal(key, shape, jnp.float32) * (
            shape[-2] ** -0.5 if scale is None else scale)

    layers = []
    for letter, kl in zip(pattern, k_layers):
        ks = jax.random.split(kl, 4)
        layer = {"g": jnp.ones((d,), jnp.float32)}
        if letter == "M":
            ki, kc, ka, kdt = jax.random.split(ks[0], 4)
            dt = jnp.exp(jax.random.uniform(
                kdt, (app["ssd_heads"],), jnp.float32, *np.log(DT_RANGE)))
            layer.update(
                w_in=normal(ki, (d, proj)),
                taps=jax.random.uniform(kc, (K, conv), jnp.float32,
                                        -K ** -0.5, K ** -0.5),
                b_c=jnp.zeros((conv,), jnp.float32),
                a_log=jnp.log(jax.random.uniform(
                    ka, (app["ssd_heads"],), jnp.float32, *A_RANGE)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                skip=jnp.ones((app["ssd_heads"],), jnp.float32),
                g_y=jnp.ones((inner,), jnp.float32),
                w_out=normal(ks[1], (inner, d)))
        elif letter == "*":
            layer.update(wqkv=normal(ks[0], (d, sum(qkv))),
                         wo=normal(ks[1], (qkv[0], d)))
        else:
            kr, _kg, ku, kd = jax.random.split(ks[2], 4)
            _ksg, ksu, ksd = jax.random.split(jax.random.fold_in(ks[2], 1), 3)
            kld, klu = jax.random.split(jax.random.fold_in(ks[2], 2))
            layer.update(
                router=normal(kr, (d, E)), bias=jnp.zeros((E,), jnp.float32),
                w1=normal(ku, (H, r, f)), w2=normal(kd, (H, f, r)),
                v1=normal(ksu, (d, fs)), v2=normal(ksd, (fs, d)),
                down=normal(kld, (d, r)), up=normal(klu, (r, d)))
        layers.append(layer)
    return {
        "embed": normal(k_emb, (V, d), app.get("embed_std", 0.02)),
        "head": normal(jax.random.fold_in(k_emb, 1), (d, V)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def _recurrence(x, b, c, a):
    """``y [S, H, P]`` of ``S_t = a_t S_{t-1} + x_t b_t^T``, ``y_t = S_t
    c_t`` for one sequence: ``x [S, H, P]``, ``b, c [S, H, N]`` (a head's own
    group already picked), ``a [S, H]``; a position a step."""
    S, H, P = x.shape
    N = b.shape[-1]
    run = next(n for n in (SCAN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    def step(state, t):
        xt, bt, ct, at = t
        state = at[:, None, None] * state + xt[:, :, None] * bt[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, ct)

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(step, state, ts)

    blocks = tuple(t.reshape(S // run, run, *t.shape[1:]) for t in (x, b, c, a))
    _, y = jax.lax.scan(block, jnp.zeros((H, P, N), jnp.float32), blocks)
    return y.reshape(S, H, P)


def _mamba(u, layer, app, ablate):
    """The ``M`` sublayer on the normed ``u [B, S, d]``."""
    B, S, _ = u.shape
    H, P, G, N = (app["ssd_heads"], app["ssd_head_dim"], app["ssd_groups"],
                  app["ssd_state"])
    K = app["short_conv"]
    (inner, conv, _), _ = widths(app)
    rnd = _operands(ablate)
    z, xbc, dt = jnp.split(rnd(u) @ rnd(layer["w_in"]), (inner, inner + conv),
                           axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + S] * layer["taps"][j]
                          for j in range(K)) + layer["b_c"])
    x, b, c = jnp.split(xbc, (inner, inner + G * N), axis=-1)
    x = x.reshape(B, S, H, P)
    # head h reads group h // (H / G); the ablation reads group h % G
    spread = lambda t: _pick(
        ablate, "wrong_group",
        lambda: jnp.tile(t.reshape(B, S, G, N), (1, 1, H // G, 1)),
        lambda: jnp.repeat(t.reshape(B, S, G, N), H // G, axis=2))
    step = jax.nn.softplus(dt + layer["dt_bias"])               # [B, S, H]
    decay = _pick(ablate, "no_decay", lambda: jnp.ones_like(step),
                  lambda: jnp.exp(-step * jnp.exp(layer["a_log"])))
    y = jax.vmap(_recurrence)(rnd(x * step[..., None]), rnd(spread(b)),
                              rnd(spread(c)), decay)
    y = y + layer["skip"][:, None] * x
    y = (y.reshape(B, S, inner) * jax.nn.silu(z)).reshape(B, S, G, inner // G)
    y = rms_norm(y, layer["g_y"].reshape(G, -1), app["norm_eps"])
    return rnd(y.reshape(B, S, inner)) @ rnd(layer["w_out"])


def _attention_one(q, k, v, rnd):
    """Causal softmax attention of one sequence, ``q, k, v [H, S, hd]`` (K
    and V already repeated to the query heads), a block of query rows at a
    time; no positions."""
    S, hd = q.shape[1], q.shape[2]
    qb = next(n for n in (QUERY_BLOCK, 128, 64, 32, 16, 8, 4, 2, 1) if S % n == 0)

    @jax.checkpoint
    def rows(args):
        q_blk, row0 = args                                      # [H, qb, hd]
        s = jnp.einsum("hqd,hkd->hqk", rnd(q_blk), rnd(k)) * hd ** -0.5
        ahead = (row0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(ahead, s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", rnd(jax.nn.softmax(s, axis=-1)), rnd(v))

    blocks = q.reshape(q.shape[0], S // qb, qb, hd).transpose(1, 0, 2, 3)
    out = jax.lax.map(rows, (blocks, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3).reshape(q.shape[0], S, hd)


def _attention(u, layer, app, ablate):
    """The ``*`` sublayer on the normed ``u [B, S, d]``."""
    B, S, _ = u.shape
    h, hkv, hd = app["n_heads"], app["n_kv_heads"], app["mha_head_dim"]
    wq, wk, _ = widths(app)[1]
    rnd = _operands(ablate)
    heads = lambda t: t.reshape(B, S, -1, hd).transpose(0, 2, 1, 3)
    q, k, v = (heads(t) for t in jnp.split(rnd(u) @ rnd(layer["wqkv"]),
                                           (wq, wq + wk), axis=-1))
    spread = lambda t: jnp.repeat(t, h // hkv, axis=1)
    o = jax.lax.map(lambda qkv: _attention_one(*qkv, rnd),
                    (q, spread(k), spread(v)))
    return rnd(o.transpose(0, 2, 1, 3).reshape(B, S, wq)) @ rnd(layer["wo"])


def _experts(u, layer, app, ablate):
    """The ``E`` sublayer on the normed ``u [B, S, d]``: ``(out, the
    sequence-wise balance term, token-slots by expert [E])``."""
    B, S, d = u.shape
    E, top_k, r = app["moe_experts"], app["moe_top_k"], app["moe_latent"]
    H = app.get("moe_experts_held") or E
    rnd = _operands(ablate)
    t = u.reshape(B * S, d)
    score = jax.nn.sigmoid(t @ layer["router"])                  # [T, E]
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(layer["bias"]),
                              top_k)                             # [T, k], best first
    picks = jax.nn.one_hot(chosen, E, dtype=jnp.float32)         # [T, k, E]
    mask = picks.sum(axis=1)
    # one expert fewer: the weakest of the chosen goes
    kept = _pick(ablate, "top_k_minus_1", lambda: mask - picks[:, -1],
                 lambda: mask)
    weight = score * kept
    if app["moe_norm_topk"]:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * app["moe_routed_scale"]
    tr = rnd(t)
    # the ablation feeds the experts the row's first r channels as they are
    lat = rnd(_pick(ablate, "latent_skipped", lambda: t[:, :r],
                    lambda: tr @ rnd(layer["down"])))
    act = lambda a: _pick(ablate, "gated_experts",
                          lambda: jax.nn.silu(a) * a,  # the gate tied to up
                          lambda: jnp.square(jax.nn.relu(a)))
    routed = jnp.zeros_like(lat)
    for e in range(H):  # every held expert on every token, weighted
        routed = routed + weight[:, e:e + 1] * (
            rnd(act(lat @ rnd(layer["w1"][e]))) @ rnd(layer["w2"][e]))
    shared = rnd(jnp.square(jax.nn.relu(tr @ rnd(layer["v1"])))) @ rnd(layer["v2"])
    out = rnd(routed) @ rnd(layer["up"]) + _pick(
        ablate, "no_shared", lambda: jnp.zeros_like(shared), lambda: shared)
    # eq. 17-20, a sequence at a time: f counts (no gradient), P is the mean
    # score normalised over the experts
    f = jax.lax.stop_gradient(mask).reshape(B, S, E).sum(axis=1) * (
        E / (top_k * S))
    p = (score / score.sum(axis=-1, keepdims=True)).reshape(B, S, E).mean(axis=1)
    return (out.reshape(B, S, d), jnp.sum(f * p, axis=-1).mean(),
            mask.sum(axis=0))


SUBLAYERS = {"M": _mamba, "*": _attention, "E": _experts}


def sublayer(x, layer, app, letter: str, ablate=None):
    """One layer on ``x [B, S, d]``: ``(x + f(norm(x)), balance term or 0,
    token-slots by expert or None)``."""
    u = rms_norm(x, layer["g"], app["norm_eps"])
    out = SUBLAYERS[letter](u, layer, app, ablate)
    if letter != "E":
        out = (out, 0.0, None)
    return x + out[0], out[1], out[2]


def forward(params, inp, app, ablate=None):
    """``(logits [B, S, V], the layers' sequence-wise balance terms
    summed)``. ``ablate``: :func:`_flag`'s."""
    x = params["embed"][inp]
    if ablate == "no_aux":  # the loss's, not the logits'
        ablate = None
    lb = 0.0
    for letter, layer in zip(app["layer_pattern"], params["layers"]):
        x, term, _ = jax.checkpoint(functools.partial(
            sublayer, app=app, letter=letter, ablate=ablate))(x, layer)
        lb = lb + term
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


def peak_bytes() -> int:
    """The process's peak of device memory so far (0 where the backend
    keeps none): printed at the check's marks, so that a run says which
    phase set ``memory_peak_bytes`` — the job's step or this file."""
    return int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))


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


def logit_ablations(app) -> Sequence[str]:
    """The ablations ``check_logits`` can show at this share: all of
    ``LOGIT_ABLATIONS``, less ``wrong_group`` where one group is held (every
    head then reads it either way)."""
    return tuple(a for a in LOGIT_ABLATIONS
                 if a != "wrong_group" or app["ssd_groups"] > 1)


def check_logits(app: Dict[str, Any], inp, seed: int) -> Dict[str, Any]:
    """The program's logits on ``inp [B, S]`` (``TransformerLM.apply`` as the
    job path traces it: the configuration's dtype, the scan, flash and
    grouped-matmul kernels where the device has them) against ``forward`` of
    this file, from the same seeded parameters as the cell trains them, on
    every position of every sequence. ``{"ok": bool, ...}``.

    Rounding moves EVERY position a little, and a near-tie in a 512-wide
    router's 22nd place sends a token to another expert on one side only,
    which moves a FEW positions a lot: so two limits (``LIMITS``), the 90th
    percentile over positions and the RMS over all of them (which bounds the
    tail), and the program must hold both. Every ablation of
    ``logit_ablations`` is computed by the one compiled reference program (a
    vector of flags) on every call and must read above the ``q90`` limit, or
    the check fails: it is shown to tell them apart on the run that uses
    it."""
    from jax.sharding import Mesh

    from harmony_tpu.models.transformer import TransformerConfig, TransformerLM
    from harmony_tpu.utils.platform import on_mesh

    inp = jnp.asarray(inp)
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in names}))
    dtype = jnp.dtype(lm.config.dtype).name
    limits = LIMITS[dtype]
    clock = {"start": time.monotonic()}
    peaks = {"before": peak_bytes()}  # the warm-up job's: init and step
    params = lm.init(jax.random.PRNGKey(seed))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with on_mesh(mesh):  # the job path's trace scope: kernels, not fallbacks
        got = jax.block_until_ready(jax.jit(lm.apply)(params, inp))
    del params
    clock["program"] = time.monotonic()
    peaks["program"] = peak_bytes()
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
        program = position_errors(got, want)
        del got
        clock["reference"] = time.monotonic()
        moved = {a: {k: v for k, v in position_errors(run(ref, a),
                                                      want).items()
                     if k in ("q90", "rms")} for a in logit_ablations(app)}
        clock["ablations"] = time.monotonic()
    peaks["reference"] = peak_bytes()
    detected = {a: e["q90"] > limits["q90"] for a, e in moved.items()}
    held = all(program[k] <= limits[k] for k in limits)
    marks = list(clock.items())
    return {"ok": bool(held and all(detected.values())),
            "program": program, "limits": limits, "ablations": moved,
            "detected": detected,
            "seconds": {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
            "peak_bytes": peaks, "seed": int(seed), "dtype": dtype}


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
    print(json.dumps({"line": "replay_memory", "peak_bytes": peak_bytes()}),
          flush=True)
    return losses
