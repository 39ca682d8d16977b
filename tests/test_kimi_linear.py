"""Kimi Linear's hybrid on the normal path (PR 31): KDA blocks — gated
delta-rule linear attention with a per-channel decay, chunked (ops/kda.py) —
three to each NoPE latent-attention block, a leading dense MLP, sigmoid top-k
experts with a shared one. ``TransformerLM`` with the architecture fields
against the plain reference the benchmark ships
(``perf/reference/kimi-linear-48b-a3b.py``: float32, the recurrence position
by position, a loop over the held experts, no chunk, no sort, no kernel).

Small, float32, seeded: d 64, 2 KDA heads of 16 with 4 taps, 4 latent heads of
(16 + 8, 16), latent 24, dense width 96, 8 experts of width 32, top-2, one
shared, 80 positions (one chunk of 64 and a part of one). Both sides are
float32 on the CPU and differ in the order of sums (chunked against
sequential), so 2e-5 relative holds for values and gradients — four decades
under the smallest effect of breaking a piece of the mathematics
(``test_tolerance_tells_broken_arithmetic_apart``).
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from harmony_tpu.models import TransformerConfig, TransformerLM  # noqa: E402
from harmony_tpu.ops import kda  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "kimi-linear-48b-a3b")
RTOL = 2e-5
APP = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=3, d_ff=32,
           max_seq=80, pos="none", rope_theta=10000.0, ffn="swiglu",
           tie_embeddings=False, norm_eps=1e-5, attn_kind="mla",
           kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, linear_layers=[0, 1], linear_heads=2,
           linear_head_dim=16, short_conv=4, moe_first_dense=1, dense_d_ff=96,
           moe_experts=8, moe_top_k=2, moe_every=1, moe_shared_experts=1,
           moe_score="sigmoid", moe_norm_topk=True, moe_routed_scale=2.446,
           moe_seq_aux=True, moe_aux_weight=0.001)
HELD = [None, 4]  # every expert here; experts 0..3 of the 8
#: the program's names for the reference's leaves
KDA = {"wq": "kq", "wk": "kk", "wv": "kv", "conv_q": "cq", "conv_k": "ck",
       "conv_v": "cv", "wf_a": "fa", "wf_b": "fb", "a_log": "a_log",
       "dt_bias": "dt_bias", "wg_a": "ga", "wg_b": "gb", "o_norm": "o_norm",
       "wo": "ko"}
DENSE = {"w1": "wg", "w3": "wu", "w2": "wd"}
EXPERTS = {"router": "router", "wg": "eg", "wu": "eu", "wd": "ed",
           "shared_wg": "sg", "shared_wu": "su", "shared_wd": "sd"}
LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def _app(held):
    return APP if held is None else {**APP, "moe_experts_held": held}


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, APP["vocab_size"], (batch, APP["max_seq"] + 1)), jnp.int32)


def _both(held, seed=5):
    app = _app(held)
    lm = TransformerLM(TransformerConfig(**app))
    return (lm, lm.init(jax.random.PRNGKey(seed)), REF._Static(app),
            REF.init_params(app, seed))


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


# -- the chunked scan against the recurrence --------------------------------

def _operands(seed, S, d, decay, heads=2):
    """q and k as the model gives them (normalised), a log-decay of about
    ``-decay`` a position and channel, beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (1, heads, S, d)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return (q, k, v, g, beta), jax.random.normal(ks[5], shape)


def _recurrence(q, k, v, g, beta):
    return jax.vmap(jax.vmap(REF.delta_rule))(q, k, v, g, beta)


@pytest.mark.parametrize("decay", [1e-4, 0.3, 30.0],
                         ids=["decay~1", "decay~0.8", "decay~0"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_chunked_scan_equals_the_recurrence(form, decay):
    """Values and all five gradients, the XLA form and the kernels
    (interpreted), at 150 positions (two chunks and a part) — with a decay
    that never forgets, an ordinary one, and one of ``exp(-20)`` a position,
    where ``exp(-G)`` alone would overflow inside a chunk."""
    args, w = _operands(3, 150, 32, decay)
    run = lambda *a: kda.kda_attention(
        *a, interpret={"xla": "xla", "kernel": True}[form])
    with jax.default_matmul_precision("highest"):
        _close(run(*args), _recurrence(*args))
        got = jax.grad(lambda *a: (run(*a) * w).sum(), argnums=range(5))(*args)
        want = jax.grad(lambda *a: (_recurrence(*a) * w).sum(),
                        argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b, 1e-4)


def test_kernels_and_xla_form_share_one_chunk_and_agree_bit_for_bit():
    args, _ = _operands(4, 128, 16, 0.3)
    a = kda.kda_attention(*args, interpret=True)
    b = kda.kda_attention(*args, interpret="xla")
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_identical_keys_and_no_decay_stay_finite_and_exact():
    """Every key the same, beta 1, no decay: ``I + A`` is all ones below
    the diagonal, the worst case for a power series; the block recursion
    solves it as the recurrence does."""
    S, d = 64, 16
    k = jnp.broadcast_to(jnp.eye(d)[0], (1, 1, S, d))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, 1, S, d))
    args = (k * d ** -0.5, k, v, jnp.zeros((1, 1, S, d)), jnp.ones((1, 1, S)))
    with jax.default_matmul_precision("highest"):
        _close(kda.kda_attention(*args, interpret=True), _recurrence(*args))


def _one_chunk(seed, decay, C=64, d=32, same_keys=False):
    """One head's chunk as ``kda_attention`` hands it to ``_chunk``, float32,
    with a state to start from and cotangents ``(do, dS')``."""
    (q, k, v, g, beta), do = _operands(seed, C, d, decay, heads=1)
    if same_keys:  # I + A all ones below the diagonal
        k = jnp.broadcast_to(jnp.eye(d)[0], k.shape)
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    q, k, v, g, do = (t[0, 0] for t in (q, k, v, g, do))
    b = beta[0, 0][:, None]
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    St = jax.random.normal(ks[0], (d, d))
    return ((q, k, b * k, b * v, jnp.cumsum(g, axis=0), St),
            (do, jax.random.normal(ks[1], (d, d))))


@pytest.mark.parametrize("case", [1e-4, 0.3, 30.0, "same keys"],
                         ids=["decay~1", "decay~0.8", "decay~0", "same-keys"])
def test_hand_derived_chunk_backward_equals_autodiff_through_the_solver(case):
    """``_chunk_bwd`` (the backward kernel's body: around the forward's
    ``(I + A)^-1``, never through it) against ``jax.vjp`` of ``_chunk``
    (through all six levels): every one of the six cotangents."""
    same = case == "same keys"
    args, cts = _one_chunk(11, 0.0 if same else case, same_keys=same)
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(kda._chunk, *args)
        want = pull(cts)
        *again, X = kda._chunk_keeping_solve(*args)
        got = kda._chunk_bwd(*args, X, *cts)
    for a, b in zip(again, out):   # one body: the same values
        np.testing.assert_array_equal(a, b)
    for name, a, b in zip(("q", "k", "kb", "vb", "G", "St"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        _close(a, b, 1e-5)


@pytest.mark.parametrize("matrix", ["all ones", "random"])
def test_the_solve_with_its_first_level_a_mask_inverts_i_plus_a(matrix):
    """Six levels, the first (blocks of one row, ``X = I``) written as the
    mask it is: against float64's inverse, on the identical-keys matrix and
    a random strictly lower one."""
    C = kda.CHUNK
    lower = np.tril(np.ones((C, C)), -1)
    A = lower if matrix == "all ones" else lower * np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (C, C)), np.float64) * 0.3
    want = np.linalg.inv(np.eye(C) + A.astype(np.float64))
    _close(kda._solve(jnp.asarray(A, jnp.float32)), want, 1e-5)


def _dots(jaxpr):
    """Every ``dot_general`` equation of a jaxpr, kernel bodies and other
    nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_dots(sub))
    return found


def _exact_square_products(jaxpr, C=64):
    exact = jax.lax.Precision.HIGHEST
    return sum(
        all(v.aval.shape == (C, C) for v in e.invars)
        and e.params["precision"] in (exact, (exact, exact))
        for e in _dots(jaxpr))


def test_only_the_forward_solves_and_in_ten_products():
    """The structural pin: at the cell's shapes the forward kernel's body
    holds the solve's ten exact ``[64, 64] x [64, 64]`` products (five
    levels of two; the level of one-row blocks is a mask) and the backward
    kernel's none — it takes ``(I + A)^-1`` from the forward and is not a
    ``jax.vjp`` through the recursion (24 more, and the 12 recomputed)."""
    BH, N, C, d = 8, 128, 64, 128
    t = jax.ShapeDtypeStruct((BH, N, C, d), jnp.bfloat16)
    G = jax.ShapeDtypeStruct((BH, N, C, d), jnp.float32)
    h = jax.ShapeDtypeStruct((BH, N, d, d), jnp.float32)
    X = jax.ShapeDtypeStruct((BH, N, C, C), jnp.float32)
    fwd = jax.make_jaxpr(lambda *a: kda._kda_fwd_call(*a, False))(
        t, t, t, t, G)
    assert [v.aval.shape for v in fwd.jaxpr.outvars] == [
        t.shape, h.shape, X.shape]
    assert _exact_square_products(fwd.jaxpr) == 10
    bwd = jax.make_jaxpr(lambda *a: kda._kda_bwd_call(*a, False))(
        t, t, t, t, G, h, X, t)
    assert _exact_square_products(bwd.jaxpr) == 0
    # 13 products of the application's transposes and the solve's two
    # cotangents, 6 + 12 of the pair matrices' vjp
    assert len(_dots(bwd.jaxpr)) == 31
    # the guard sees the solver's derivative where there is one
    through = jax.make_jaxpr(lambda *a: jax.vjp(kda._chunk, *a)[1](
        (a[3].astype(jnp.float32), a[5])))(*(
            jax.ShapeDtypeStruct(s.shape[2:], s.dtype)
            for s in (t, t, t, t, G, h)))
    assert _exact_square_products(through.jaxpr) == 10 + 20


def test_shapes_that_nothing_computes_are_refused():
    args, _ = _operands(0, 8, 16, 0.1)
    with pytest.raises(ValueError, match="kda_attention"):
        kda.kda_attention(args[0], args[1][..., :8], *args[2:])
    with pytest.raises(ValueError, match="kda_attention"):
        kda.kda_attention(*args[:4], args[4][..., :4])


def test_the_plan_is_a_chunk_of_one_head_a_step_and_pads_to_chunks():
    assert kda.tile_plan(8, 8192) == (64, 8 * 128)   # the cell's call
    assert kda.tile_plan(6, 100) == (64, 6 * 2)
    assert kda.tile_plan(1, 64) == (64, 1)


def test_kernel_plans_reach_status_with_both_widths():
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    t = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 8, 8192), jnp.float32)
    with trace_span("job.build_step", job_id="plan-kda"):
        jax.jit(jax.grad(lambda q, k, v, g, b: kda.kda_attention(
            q, k, v, g, b, interpret=False).astype(jnp.float32).sum())
        ).trace(t, t, t, g, b)
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-kda"]}
    assert set(rows) == {"harmony_kda_fwd", "harmony_kda_bwd"}
    for r in rows.values():
        assert (r["block_q"], r["block_k"], r["sub"]) == (64, 8, 16)
        assert (r["d"], r["dv"], r["grid_steps"]) == (128, 128, 1024)


def _kernel_calls(jaxpr, out=None):
    """``{kernel name: calls}`` of every ``pallas_call`` under ``jaxpr``."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            out[name] = out.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, out)
    return out


def test_the_step_holds_each_kernel_once_a_block(monkeypatch):
    """Traced for a TPU under ``remat`` at the published head width: the
    channel route's forward and backward once a KDA block (what the forward
    keeps is named, so ``remat`` does not run it again), and the by-rows
    kernel that hands them q, k and v — three projections, a call each,
    forward, again under ``remat``, backward (PR 62); STATUS
    ``kernel_plans`` names all three."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    app = {**APP, "d_model": 256, "linear_heads": 8, "linear_head_dim": 128,
           "max_seq": 2048, "moe_experts_held": 4, "dtype": jnp.bfloat16,
           "remat": True}
    lm = TransformerLM(TransformerConfig(**app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, 2049), jnp.int32)
    with trace_span("job.build_step", job_id="plan-kimi-linear"):
        traced = jax.jit(jax.grad(lm.loss)).trace(params, toks)
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    blocks = len(APP["linear_layers"])
    assert calls["harmony_kda_fwd"] == calls["harmony_kda_bwd"] == blocks
    assert calls["harmony_conv_heads"] == 3 * 3 * blocks
    assert "harmony_gdn_fwd" not in calls
    rows = {r["kernel"]: r for r in
            progcache.kernel_plans()["plan-kimi-linear"]}
    assert {"harmony_kda_fwd", "harmony_kda_bwd",
            "harmony_conv_heads"} <= set(rows)
    # the three calls tile alike and share the row: the last one's section
    conv = rows["harmony_conv_heads"]
    assert (conv["block_q"], conv["sub"], conv["grid_steps"],
            conv["sections"]) == (1024, 4, 4, "plain:8")
    text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    for scope in ("kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out"):
        assert scope in text, scope


def test_the_kernels_lower_for_a_tpu():
    """The Pallas TPU front end takes both kernel bodies at the cell's
    widths (the backward's hand-derived around the forward's solve, with
    the pair matrices' ``jax.vjp`` traced into it); Mosaic's own compile
    needs the chip or its compiler."""
    t = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 4, 256), jnp.float32)
    text = jax.jit(jax.grad(lambda q, k, v, g, b: kda.kda_attention(
        q, k, v, g, b, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).trace(t, t, t, g, b).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "harmony_kda_fwd" in text and "harmony_kda_bwd" in text


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("held", HELD)
def test_logits_match_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks), REF.forward(ref, toks, app)[0])


@pytest.mark.parametrize("held", HELD)
def test_loss_terms_and_step_vectors(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        loss, m = lm.loss_and_metrics(params, toks)
        ce, aux = REF.loss_terms(ref, toks, app)
    for got, want in ((m["ce"], ce), (m["aux_seq"], aux),
                      (loss, ce + 0.001 * aux)):
        _close(got, want)
    assert set(m) == {"ce", "aux_seq", "moe_expert_tokens", "kda_decay_mean",
                      "kda_beta_mean"}
    assert m["moe_expert_tokens"].shape == (2, 8)       # blocks 1 and 2
    assert m["kda_decay_mean"].shape == m["kda_beta_mean"].shape == (2,)
    assert ((0.5 < np.asarray(m["kda_decay_mean"]))
            & (np.asarray(m["kda_decay_mean"]) < 1.0)).all()
    assert np.allclose(m["kda_beta_mean"], 0.5, atol=0.1)


@pytest.mark.parametrize("held", HELD)
def test_gradients_match_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lm.loss)(params, toks)
        want = jax.grad(REF.loss_fn)(ref, toks, app)
    for key in ("embed", "head", "ln_f"):
        _close(got[key], want[key])
    for i, (g, w) in enumerate(zip(got["layers"], want["layers"])):
        _close(g["ln1"], w["ln1"])
        _close(g["ln2"], w["ln2"])
        if i in APP["linear_layers"]:
            for ours, theirs in KDA.items():
                _close(g["kda"][ours], w[theirs])
            _close(g["kda"]["wb"].T, w["wb"])
        else:
            for key in LATENT:
                _close(g[key], w[key])
    for ours, theirs in DENSE.items():
        _close(got["layers"][0][ours], want["layers"][0][theirs])
    for i in (1, 2):
        for ours, theirs in EXPERTS.items():
            _close(got["layers"][i]["moe"][ours], want["layers"][i][theirs])
        assert not np.asarray(got["layers"][i]["moe"]["bias"]).any()


@pytest.mark.parametrize("held", HELD)
@pytest.mark.parametrize("ablate", REF.ABLATIONS)
def test_tolerance_tells_broken_arithmetic_apart(ablate, held):
    """Every named ablation moves what it reaches — the logits, or for the
    balance loss the loss — by far more than the limit that the float32
    check uses (1e-4), with all and with half the experts held."""
    _, _, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        if ablate == "no_aux":
            whole = float(REF.loss_fn(ref, toks, app))
            moved = abs(float(REF.loss_fn(ref, toks, app, ablate)) - whole
                        ) / whole
            assert moved > 10 * RTOL, (ablate, moved)
            return
        moved = REF.position_errors(
            REF.forward(ref, toks[:, :-1], app, ablate)[0],
            REF.forward(ref, toks[:, :-1], app)[0])
    assert moved["q90"] > 100 * REF.LOGITS_Q90_TOL["float32"], (ablate, moved)


def test_replay_begins_with_the_programs_logits(capsys):
    """``replay`` prints ``check_logits``'s report and would return nan
    losses where it failed; at float32 the program reads ~1e-6 and every
    ablation far above the limit."""
    import json

    app = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "beta2": 0.95}
    losses = REF.replay(app, (np.asarray(_tokens(3)),), 2, 2, seed=9)
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["line"] == "logits_check" and report["ok"]
    assert set(report["program"]) == {"as_initialised", "sharpened"}
    for row in report["program"].values():
        assert row["q90"] < 1e-5 and row["rms"] < 1e-5
    assert set(report["ablations_q90"]) == set(REF.LOGIT_ABLATIONS)
    assert min(report["ablations_q90"].values()) > 0.01
    # the two ablations the sharpened pass is there for read more in it
    for name, before in report["as_initialised_q90"].items():
        assert name in REF.SHARP_ABLATIONS
        assert report["ablations_q90"][name] > before
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    # the two-step shortcut (no moments kept) is the general Adam's answer
    four = REF.replay(app, (np.asarray(_tokens(3)),), 2, 4, seed=9,
                      logits=False)
    assert np.allclose(four[:2], losses, rtol=1e-6)


def test_a_check_that_fails_returns_no_number(monkeypatch, capsys):
    monkeypatch.setitem(REF.LOGITS_Q90_TOL, "float32", 1e-9)
    app = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3}
    losses = REF.replay(app, (np.asarray(_tokens(3)),), 2, 2, seed=9)
    assert np.isnan(losses).all()
    capsys.readouterr()


# -- the shares add up ----------------------------------------------------------

def _head_slice(layer, names, heads, width, take):
    """The columns (rows for an output projection) of heads ``take``."""
    idx = np.concatenate([np.arange(h * width, (h + 1) * width) for h in take])
    out = {}
    for name, axis in names.items():
        out[name] = jnp.take(layer[name], idx, axis=axis)
    return out


def test_the_shares_add_up_to_the_uncut_layer():
    """What 2 head shares and 2 expert shares compute, with what every chip
    computes alike (the shared expert, the dense MLP) counted once, sums to
    the uncut reference's layer output: a KDA block with experts, and a
    latent block with the dense MLP."""
    from harmony_tpu.models.common import rms_norm
    from harmony_tpu.models.moe import moe_ffn_dropless
    from harmony_tpu.models.transformer import ffn_apply

    full = {**APP, "n_layers": 2, "linear_layers": [1], "linear_heads": 4,
            "moe_first_dense": 1}
    share = {**full, "linear_heads": 2, "n_heads": 2, "moe_experts_held": 4}
    ref = REF.init_params(full, 21)
    lm = TransformerLM(TransformerConfig(**share))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 80, 64), jnp.float32)
    eps = APP["norm_eps"]
    with jax.default_matmul_precision("highest"):
        # block 0: latent heads 4 -> 2 x 2, the dense MLP once
        layer = ref["layers"][0]
        xn = rms_norm(x, layer["ln1"], eps)
        hid = x
        for take in ((0, 1), (2, 3)):
            mine = {**_head_slice(layer, {"wq": 1}, 4, 24, take),
                    **_head_slice(layer, {"wkv_b": 1}, 4, 32, take),
                    **_head_slice(layer, {"wo": 0}, 4, 16, take),
                    "wkv_a": layer["wkv_a"], "kv_norm": layer["kv_norm"]}
            q, k, v = lm._latent_qkv(xn, mine, 0)
            o = lm._attention(q, k, v, None)
            hid = hid + o.transpose(0, 2, 1, 3).reshape(2, 80, -1) @ mine["wo"]
        dense = {ours: layer[theirs] for ours, theirs in DENSE.items()}
        out = hid + ffn_apply(lm.config, dense,
                              rms_norm(hid, layer["ln2"], eps))[0]
        _close(out, REF._block(x, layer, REF._Static(full), None)[0])
        # block 1: KDA heads 4 -> 2 x 2, experts 8 -> 2 x 4, shared once
        layer = ref["layers"][1]
        xn = rms_norm(x, layer["ln1"], eps)
        hid = x
        for take in ((0, 1), (2, 3)):
            cols = _head_slice(layer, {n: 1 for n in (
                "kq", "kk", "kv", "cq", "ck", "cv", "fb", "gb", "wb")}, 4, 16,
                take) | _head_slice(layer, {"ko": 0, "dt_bias": 0}, 4, 16, take)
            cols["wb"] = jnp.take(layer["wb"], np.asarray(take), axis=1)
            mine = {ours: cols.get(theirs, layer[theirs])
                    for ours, theirs in KDA.items()}
            mine["a_log"] = layer["a_log"][np.asarray(take)]
            mine["wb"] = cols["wb"].T
            hid = hid + lm._kda_mixer(xn, mine)[0]
        t = rms_norm(hid, layer["ln2"], eps).reshape(-1, 64)
        shared = {"sg": layer["sg"], "su": layer["su"], "sd": layer["sd"]}
        routed = 0.0
        for first in (0, 4):  # the share's experts lead, as the program holds
            order = np.r_[first:first + 4, 0:first, first + 4:8]
            moe = {"router": layer["router"][:, order],
                   "bias": layer["bias"][order],
                   "wg": layer["eg"][first:first + 4],
                   "wu": layer["eu"][first:first + 4],
                   "wd": layer["ed"][first:first + 4],
                   "shared_wg": shared["sg"], "shared_wu": shared["su"],
                   "shared_wd": shared["sd"]}
            routed = routed + moe_ffn_dropless(moe, t, lm.config.dropless_cfg,
                                               seqs=2)[0]
        once = REF._swiglu(t, shared["sg"], shared["su"], shared["sd"])
        out = hid + (routed - once).reshape(2, 80, 64)
        _close(out, REF._block(x, layer, REF._Static(full), None)[0])


# -- the configuration's answers -----------------------------------------------------

def test_init_traced_abstractly_has_inits_layout_and_the_kinds():
    model = TransformerLM(TransformerConfig(**_app(4)))
    a, b = model.init(jax.random.PRNGKey(0)), jax.eval_shape(
        model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert la.shape == lb.shape and la.dtype == lb.dtype
    assert model.config.layer_kinds() == ("kda", "kda", "mla")
    assert model.config.moe_layers() == (1, 2)
    first, second, latent = a["layers"]
    assert "kda" in first and "w1" in first and "wq" not in first
    assert "kda" in second and "moe" in second
    assert "kda" not in latent and latent["wq"].shape == (64, 4 * 24)
    p = first["kda"]
    assert p["wq"].shape == (64, 32) and p["conv_k"].shape == (4, 32)
    assert p["wf_a"].shape == (64, 16) and p["wf_b"].shape == (16, 32)
    assert p["wb"].shape == (2, 64) and p["a_log"].shape == (2,)
    assert p["o_norm"].shape == (16,) and p["wo"].shape == (32, 64)
    assert "pos" not in a  # no table, and no rotary either
    # the decay as initialised forgets slowly, never not at all
    a_log, dt = np.asarray(p["a_log"]), np.asarray(p["dt_bias"])
    assert (0.0 <= a_log).all() and (a_log <= np.log(16)).all()
    assert (np.log1p(np.exp(dt)) < 0.11).all()
    for i, kind in enumerate(TransformerConfig(
            vocab_size=8, n_layers=2).layer_kinds()):
        assert kind == "mha", i


@pytest.mark.parametrize("kw, match", [
    ({"pos": "sinusoid"}, "unknown pos"),
    ({"linear_layers": []}, "belong to KDA blocks"),
    ({"linear_layers": [], "linear_heads": 0, "linear_head_dim": 0,
      "short_conv": 0}, "pos='none' runs only beside KDA"),
    ({"linear_layers": [0, 3]}, "linear_layers lists blocks"),
    ({"linear_layers": [1, 0]}, "linear_layers lists blocks"),
    ({"linear_layers": [0, 0]}, "linear_layers lists blocks"),
    ({"linear_heads": 0}, "linear_layers lists blocks"),
    ({"short_conv": 0}, "linear_layers lists blocks"),
    ({"pos": "learned"}, "attn_kind='mla' needs"),
])
def test_inconsistent_architecture_fields_are_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**APP, **kw})


def test_rotary_beside_kda_blocks_is_allowed_and_turns_the_latent_block():
    """``pos`` stays a free field: with ``"rope"`` the latent block turns its
    rope parts (Moonlight's block), which is what the reference's
    ``rope_in_latent`` ablation computes."""
    lm = TransformerLM(TransformerConfig(**{**APP, "pos": "rope"}))
    params, ref = lm.init(jax.random.PRNGKey(5)), REF.init_params(APP, 5)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks),
               REF.forward(ref, toks, REF._Static(APP), "rope_in_latent")[0])


def test_side_steps_and_decode_refuse_the_new_block():
    from harmony_tpu.models import make_generate_fn

    lm = TransformerLM(TransformerConfig(**APP))
    with pytest.raises(ValueError, match="GPT-2-era block .* linear_layers"):
        make_generate_fn(lm, 4, 4)
    plain = TransformerConfig(vocab_size=8, n_layers=2, linear_layers=(0,),
                              linear_heads=2, linear_head_dim=16, short_conv=4)
    with pytest.raises(ValueError, match="reads no linear_layers / "
                       "linear_heads / linear_head_dim / short_conv:"):
        plain.require_classic_block("a side step")


# -- the job path: trainer, vectors out of the step, gauges, STATUS ------------------

def test_trainer_step_reports_the_kda_vectors():
    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**_app(4), optimizer="adam", step_size=1e-3,
                            beta2=0.95, row_width=256)
    model = jnp.zeros((tr.capacity, 256), jnp.float32)
    delta, m = jax.jit(tr.compute)(model, _tokens(), {
        k: jnp.float32(v) for k, v in tr.hyperparams().items()})
    assert delta.shape == model.shape
    assert set(m) == {"loss", "ce", "aux_seq", "moe_expert_tokens",
                      "kda_decay_mean", "kda_beta_mean"}
    assert m["kda_decay_mean"].shape == (2,)


def test_gauges_carry_the_blocks_index_and_the_kinds():
    from harmony_tpu.metrics import kda as kda_metrics
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    decay = np.array([[0.5, 0.9, 0.7], [0.6, 0.8, 0.99]])
    kda_metrics.observe("kda-unit", decay, decay / 2, layers=(0, 1, 4))
    kda_metrics.note_layer_kinds("kda-unit", ("kda", "kda", "mla", "kda"))
    fams = parse_exposition(get_registry().expose())
    rows = {l["layer"]: v for _, l, v in
            fams["harmony_kda_decay_mean"]["samples"] if l["job"] == "kda-unit"}
    assert rows == {"0": 0.6, "1": 0.8, "4": 0.99}  # the newest step stands
    betas = {l["layer"]: v for _, l, v in
             fams["harmony_kda_beta_mean"]["samples"] if l["job"] == "kda-unit"}
    assert betas["4"] == pytest.approx(0.495)
    kinds = {l["kind"]: v for _, l, v in
             fams["harmony_model_layers"]["samples"] if l["job"] == "kda-unit"}
    assert kinds == {"kda": 3.0, "mla": 1.0}
    assert kda_metrics.kinds_by_job()["kda-unit"] == {"kda": 3, "mla": 1}
    assert kda_metrics.stats_by_job()["kda-unit"]["decay_mean"] == (
        pytest.approx((0.6 + 0.8 + 0.99) / 3))


def _submit(job_id, trainer, app, epochs=8):
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool

    data_args = {"num_seqs": 2, "seq_len": 81, "vocab_size": 96, "seed": 7}
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id=job_id, app_type="dolphin", trainer=trainer,
            params=TrainerParams(num_epochs=epochs, num_mini_batches=1,
                                 comm_probe_period=0, app_params=app),
            num_workers=1,
            user={"data_fn": "perf.generators.random_tokens:make",
                  "data_args": data_args})
        result = server.submit(cfg).result(timeout=600)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    return (next(iter(result["workers"].values()))["losses"], status,
            data_args)


JOB_APP = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "beta2": 0.95, "seed": 11}


def test_a_tiny_kimi_linear_tenant_through_the_jobserver_equals_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    TransformerTrainer and JSON app_params (``linear_layers`` a list): the
    first four steps' losses are the reference's replay (float32 both sides,
    chunked against sequential, the table's Adam against the formula), and
    STATUS shows the kinds of block, the KDA gauges and the experts'
    counters under the blocks' indices."""
    from perf.generators import random_tokens

    losses, status, data_args = _submit(
        "kimi-tiny", "harmony_tpu.models.transformer:TransformerTrainer",
        JOB_APP)
    want = REF.replay(JOB_APP, (random_tokens.make(**data_args),), 2, 4,
                      seed=11)
    assert np.allclose(losses[:4], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    row = status["tenants"]["kimi-tiny"]
    assert row["layer_kinds"] == {"kda": 2, "mla": 1}
    assert 0.5 < row["kda"]["decay_mean"] < 1.0
    assert 0.3 < row["kda"]["beta_mean"] < 0.7
    assert 0.0 < row["moe"]["held_slot_share"] < 1.0
    fams = parse_exposition(get_registry().expose())
    layers = {l["layer"] for _, l, _ in
              fams["harmony_kda_decay_mean"]["samples"]
              if l["job"] == "kimi-tiny"}
    assert layers == {"0", "1"}
    routed = {l["layer"] for _, l, _ in
              fams["harmony_moe_expert_tokens_total"]["samples"]
              if l["job"] == "kimi-tiny"}
    assert routed == {"1", "2"}  # the dense block 0 shows no idle experts
