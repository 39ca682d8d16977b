"""Qwen3-Next as a tenant (PR 61): the configuration's fields and what stays
refused; Gated DeltaNet's scalar-decay route of ops/kda.py against the XLA
form, the channel route and the position-by-position recurrence; the program
against ``perf/reference/qwen3-next-80b-a3b.py`` (seeded parameters, logits,
every gradient leaf with and without ``remat``, Adam's steps through the
jobserver); the tie of the chip's share to the uncut layer; the check's
ablations; tracing. CPU only: float32, kernels interpreted."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
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
from harmony_tpu.ops import kda as K  # noqa: E402
from perf.generators import random_tokens  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "qwen3-next-80b-a3b")
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs",
                       "qwen3-next-80b-a3b.json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "vocab_size": 96, "step_size": 1e-3}
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}

# the configuration's own checks (perf/tests is run by hand and does not
# count): collected here too, from the same file — but for the rehearsal,
# which runs the whole harness in a child (the jobserver test below covers
# the job path)
_spec = importlib.util.spec_from_file_location(
    "perf_test_qwen3_next",
    os.path.join(ROOT, "perf", "tests", "test_qwen3_next.py"))
_perf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_perf)
globals().update({name: obj for name, obj in vars(_perf).items()
                  if name.startswith("test_")
                  and name != "test_rehearsal_runs_to_a_correct_line"})


def _config(app):
    return TransformerConfig(**{k: v for k, v in app.items() if k in FIELDS})


def _tokens(seed=0, batch=2, app=APP):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, app["vocab_size"], (batch, app["max_seq"] + 1)), jnp.int32)


@functools.lru_cache(maxsize=None)
def _both(seed=5):
    lm = TransformerLM(_config(APP))
    return (lm, lm.init(jax.random.PRNGKey(seed)), REF._Static(APP),
            REF.init_params(APP, seed))


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    scale = np.abs(b).max() or 1.0
    assert np.abs(a - b).max() <= rtol * scale, np.abs(a - b).max() / scale


# -- the fields ----------------------------------------------------------------

GDN = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=96,
           max_seq=48, pos="rope", ffn="swiglu", tie_embeddings=False,
           linear_layers=(0,), linear_heads=2, linear_head_dim=16,
           short_conv=4, linear_kind="gdn")
EXPERTS = dict(moe_experts=8, moe_top_k=2, moe_every=1, moe_shared_experts=1)


@pytest.mark.parametrize("fields,match", [
    # still refused: what each lifted refusal keeps its message for
    ({"linear_kind": "kda", "attn_gate": "head"}, "KDA blocks"),
    ({"linear_kind": "kda", "attn_gate": "element"}, "KDA blocks"),
    ({"linear_kind": "kda", "head_norm": True}, "head_norm norms each head"),
    ({"attn_kind": "mla", "kv_lora_rank": 8, "qk_nope_head_dim": 8,
      "qk_rope_head_dim": 8, "v_head_dim": 8}, "linear_kind='gdn' runs beside"),
    ({"window": 8, "window_layers": (1,)}, "linear_kind='gdn' runs beside"),
    ({"pos": "learned"}, "linear_kind='gdn' runs beside"),
    ({"cca": True, "n_kv_heads": 2}, "cca convolves"),
    ({"objective": "block_diffusion", "diffusion_block": 4, "mask_token": 1},
     "block_diffusion"),
    ({"loop_steps": 2}, "loop_steps > 1"),
    ({"linear_value_heads": 3}, "whole groups"),
    ({"linear_kind": "delta"}, "unknown linear_kind"),
    ({"attn_gate": "column"}, "unknown attn_gate"),
    ({"linear_layers": (), "linear_heads": 0, "linear_head_dim": 0,
      "short_conv": 0}, "linear_kind='gdn' names the mixer"),
    ({"linear_kind": "kda", "linear_value_heads": 4},
     "linear_kind='gdn' names the mixer"),
    ({"norm_offset": True, "qk_norm": True}, "norm_offset reads"),
    ({"moe_shared_gate": True}, "moe_shared_gate gates"),
    ({"attn_gate": "element", "linear_layers": (), "linear_heads": 0,
      "linear_head_dim": 0, "short_conv": 0, "linear_kind": "kda",
      "window": 8, "window_layers": (1,)}, "two kinds of softmax"),
])
def test_what_stays_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**GDN, **fields})


@pytest.mark.parametrize("fields", [
    {"head_norm": True}, {"attn_gate": "element"}, {"attn_gate": "head"},
    {"rope_fraction": 0.5, "mha_head_dim": 32, "n_kv_heads": 2},
    {"norm_offset": True}, {"linear_value_heads": 4},
    {**EXPERTS, "moe_shared_gate": True}, {"pos": "none"},
], ids=lambda f: "+".join(sorted(f)))
def test_what_the_lifted_refusals_allow(fields):
    """Each of ``head_norm``, ``rope_fraction`` and a gate beside Gated
    DeltaNet blocks, alone: a model that builds and whose loss and gradient
    trace (the preset below runs them all together against the reference)."""
    lm = TransformerLM(TransformerConfig(**{**GDN, **fields}))
    assert lm.config.layer_kinds() == ("gdn", "mha")
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    loss, grads = jax.eval_shape(jax.value_and_grad(lm.loss), params,
                                 jax.ShapeDtypeStruct((2, 49), jnp.int32))
    assert loss.shape == ()
    assert jax.tree.structure(grads) == jax.tree.structure(params)


@pytest.mark.parametrize("make", ["make_sp_train_step", "make_generate_fn"])
@pytest.mark.parametrize("field,value", [
    ("linear_kind", "gdn"), ("linear_value_heads", 4), ("norm_offset", True),
    ("moe_shared_gate", True), ("attn_gate", "element")])
def test_the_side_steps_and_the_decode_path_refuse_the_fields(make, field,
                                                              value):
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=2)
    cfg.require_classic_block(make)
    object.__setattr__(cfg, field, value)
    with pytest.raises(ValueError, match=f"GPT-2-era block .* {field}"):
        cfg.require_classic_block(make)


@pytest.mark.parametrize("base", ["kimi", "sdar", "laguna"])
def test_each_new_field_off_is_the_parents_program(base):
    """The new fields at their defaults, named or not, trace the program of
    a configuration that never heard of them, equation for equation (the
    pinned digests of tests/test_smallthinker.py hold the accepted presets
    to the parent's tree)."""
    name = {"kimi": "kimi-linear-48b-a3b", "sdar": "sdar-30b-a3b",
            "laguna": "laguna-s-2.1"}[base]
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        conf = json.load(f)
    app = {**conf["job"]["app_params"], **conf["rehearse"]["app_params"]}
    plain = TransformerLM(_config(app))
    named = TransformerLM(_config({
        **app, "linear_kind": "kda", "linear_value_heads": 0,
        "norm_offset": False, "moe_shared_gate": False}))
    params = jax.eval_shape(lambda: plain.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, app["max_seq"] + 1), jnp.int32)
    if plain.config.objective == "block_diffusion":
        L = app["max_seq"]
        toks = tuple(jax.ShapeDtypeStruct((2, n), t) for n, t in (
            (L, jnp.int32), (L, jnp.int8),
            (L // plain.config.diffusion_block, jnp.float32)))
    fn = lambda lm: str(jax.make_jaxpr(jax.value_and_grad(
        lm.loss_and_metrics, has_aux=True))(params, toks))
    assert fn(plain) == fn(named)


# -- the scalar-decay route of ops/kda.py -----------------------------------------

def _recurrence(q, k, v, g, beta):
    R = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(t, R, axis=1) for t in (q, k))
    return jax.vmap(jax.vmap(REF.delta_rule))(q, k, v, g, beta)


def _operands(S=150, B=1, Hk=2, Hv=4, d=16):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (B, Hk, S, d))) * d ** -0.5,
            unit(jax.random.normal(ks[1], (B, Hk, S, d))),
            jax.random.normal(ks[2], (B, Hv, S, d)),
            -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (B, Hv, S))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, Hv, S))),
            jax.random.normal(ks[5], (B, Hv, S, d)))


# lengths that take every count of chunks a grid step ``gdn_plan`` returns:
# 150 = 3 chunks (one a step; the last a part of one), 384 = 6 (two a
# step), 256 = 4 (four), 500 = 8 (eight; the last a part of one)
LENGTHS = {150: 1, 384: 2, 256: 4, 500: 8}


@pytest.mark.parametrize("route,S", [("xla", 150)] + [
    ("kernels", S) for S in LENGTHS])
def test_scalar_route_equals_the_recurrence_forward_and_backward(route, S):
    """``gdn_attention`` at two value heads a key head and lengths that are
    no whole number of chunks (150 = 2 x 64 + 22), both the XLA form and the
    interpreted kernels at every count of chunks a grid step, against the
    position-by-position recurrence: the outputs and all five gradients
    (``dq``, ``dk`` summed over the value heads that share them)."""
    *args, w = _operands(S)
    assert K.gdn_plan(4, -(-S // K.CHUNK)).chunk == LENGTHS[S] * K.CHUNK
    mode = "xla" if route == "xla" else True
    with jax.default_matmul_precision("highest"):
        got = K.gdn_attention(*args, interpret=mode)
        want = _recurrence(*args)
        _close(got, want)
        grads = jax.grad(lambda *a: (K.gdn_attention(*a, interpret=mode)
                                     * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)
        wants = jax.grad(lambda *a: (_recurrence(*a) * w).sum(),
                         argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(grads, wants):
        _close(a, b)


@pytest.mark.parametrize("S", sorted(LENGTHS))
def test_scalar_route_equals_the_channel_route_fed_a_broadcast_decay(S):
    """STEP 0's route (a): ``kda_attention`` given ``g`` broadcast over the
    channels and q, k repeated to the value heads computes the same — one
    chunk a grid step there, 1 / 2 / 4 / 8 here."""
    q, k, v, g, beta, w = _operands(S)
    R, d = v.shape[1] // q.shape[1], q.shape[-1]
    wide = lambda g: jnp.broadcast_to(g[..., None], (*g.shape, d))
    a = lambda q, k, v, g, beta: K.kda_attention(
        jnp.repeat(q, R, 1), jnp.repeat(k, R, 1), v, wide(g), beta,
        interpret=True)
    b = lambda *args: K.gdn_attention(*args, interpret=True)
    with jax.default_matmul_precision("highest"):
        _close(b(q, k, v, g, beta), a(q, k, v, g, beta))
        ga, gb = (jax.grad(lambda *args: (f(*args) * w).sum(),
                           argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
                  for f in (a, b))
    for x, y in zip(gb, ga):
        _close(x, y)


def _kernel_operands(N=8, Hk=2, Hv=4, d=16):
    """What ``gdn_attention`` hands ``_gdn_kernels`` for ``N`` whole chunks,
    and a cotangent of the output."""
    q, k, v, g, beta, w = _operands(N * K.CHUNK, Hk=Hk, Hv=Hv, d=d)
    rows = lambda t: K._chunks(t[..., None]).reshape(Hv, N, 1, K.CHUNK)
    G = jnp.cumsum(K._chunks(g[..., None]), axis=2)
    return (K._chunks(q), K._chunks(k), K._chunks(v), rows(beta),
            G.reshape(Hv, N, 1, K.CHUNK)), K._chunks(w)


@pytest.fixture(scope="module")
def one_chunk_a_step():
    """The parent's walk (``T`` = 1) of eight chunks: the operands, the
    forward's ``(o, h, X)``, a cotangent and the five gradients."""
    args, do = _kernel_operands()
    with jax.default_matmul_precision("highest"):
        kept = K._gdn_fwd_call(*args, 1, True)
        grads = K._gdn_bwd_call(*args, *kept[1:], do, 1, True)
    return args, kept, do, grads


@pytest.mark.parametrize("T", [t for t in K.GDN_CHUNKS_A_STEP if t > 1])
def test_every_count_of_chunks_a_step_computes_what_one_computes(
        one_chunk_a_step, T):
    """``T`` chunks a grid step against one: the backward — ``_chunk_bwd``
    ``T`` times in a row, the per-chunk arithmetic untouched — bit for bit;
    the forward — two chunks' solves on one ``[128, 128]`` tile, whose
    products may sum in another order — to 1e-6 of the largest element, the
    outputs, the kept states and the kept solves."""
    args, kept, do, grads = one_chunk_a_step
    with jax.default_matmul_precision("highest"):
        got = K._gdn_fwd_call(*args, T, True)
        back = K._gdn_bwd_call(*args, *kept[1:], do, T, True)
    for a, b in zip(got, kept):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * float(
            jnp.max(jnp.abs(b)))
    for a, b in zip(back, grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_two_chunks_on_one_tile_solve_as_each_alone():
    """``_solve`` stopped at ``C`` rows, fed two chunks' strictly lower
    ``A`` on the diagonal of one ``[2 C, 2 C]`` tile: each diagonal block is
    that chunk's ``(I + A)^-1`` and the rest is exactly zero; without
    ``rows`` every caller that was there gets what it got."""
    C = K.CHUNK
    a, b = (jnp.tril(0.3 * jax.random.normal(jax.random.PRNGKey(i), (C, C)),
                     -1) for i in (1, 2))
    z = jnp.zeros((C, C))
    two = K._solve(jnp.block([[a, z], [z, b]]), C)
    for got, alone in ((two[:C, :C], a), (two[C:, C:], b)):
        want = K._solve(alone)
        _close(got, want)
        _close(want @ (jnp.eye(C) + alone), jnp.eye(C))
    assert not two[:C, C:].any() and not two[C:, :C].any()
    np.testing.assert_array_equal(K._solve(a, C), K._solve(a))


@pytest.mark.parametrize("bh,n,block,steps", [
    (32, 256, 512, 1024),   # the cell's call: 8 chunks a step
    (4, 2, 128, 4),         # the rehearse preset: 80 positions, 2 chunks
    (8, 16, 512, 16),       # the pinned preset: 2 x 4 heads, 1,024 positions
    (4, 12, 256, 12),       # by 4 only
    (4, 6, 128, 12),        # by 2 only
    (4, 3, 64, 12),         # odd: tile_plan's walk
])
def test_the_plan_holds_the_most_chunks_a_step_that_divide(
        bh, n, block, steps):
    assert K.gdn_plan(bh, n) == (block, steps)
    assert K.gdn_plan(bh, n).chunk // K.CHUNK in K.GDN_CHUNKS_A_STEP
    assert K.tile_plan(bh, n * K.CHUNK) == (K.CHUNK, bh * n)


def test_scalar_route_refuses_shapes_it_does_not_serve():
    q, k, v, g, beta, _ = _operands(S=16)
    with pytest.raises(ValueError, match="gdn_attention"):
        K.gdn_attention(q, k, v[:, :3], g[:, :3], beta[:, :3])
    with pytest.raises(ValueError, match="gdn_attention"):
        K.gdn_attention(q, k, v, g[..., None], beta)
    assert set(K.KERNEL_NAMES.values()) == {
        "harmony_kda_fwd", "harmony_kda_bwd", "harmony_gdn_fwd",
        "harmony_gdn_bwd"}


# -- program against reference ----------------------------------------------------

def test_the_seeded_parameters_are_the_references():
    _, params, _, ref = _both()
    assert jax.tree.structure(params) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    gdn = params["layers"][0]["gdn"]
    assert float(jnp.abs(params["layers"][0]["ln1"]).max()) == 0.0  # 1 + w
    assert float(gdn["o_norm"].min()) == 1.0                       # plain
    moved = REF.perturbed(params, 5)
    assert float(jnp.abs(moved["layers"][3]["q_head_norm"]).max()) > 0.05
    assert float(jnp.abs(moved["ln_f"]).max()) > 0.05
    _close(moved["layers"][1]["gdn"]["w_ba"], 4.0 * params["layers"][1]["gdn"]["w_ba"])
    _close(moved["layers"][0]["moe"]["shared_gate"],
           4.0 * params["layers"][0]["moe"]["shared_gate"])


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_metrics_and_every_gradient_equal_the_reference(remat):
    """From PERTURBED parameters (``1 + w`` weights off 0, gates off a half:
    the seeded ones are ``test_every_ablation_is_refused...``'s), float32
    both sides: the chunked solve against the recurrence rounds ~1e-4 apart
    where ``w_ba`` is four times its size."""
    _, params, static, ref = _both()
    lm = TransformerLM(_config({**APP, "remat": remat}))
    params, ref = REF.perturbed(params, 5), REF.perturbed(ref, 5)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lm.loss_and_metrics, has_aux=True))(params, toks)
        (want, logits), want_g = REF.loss_grad_logits(ref, toks, static,
                                                      REF.flags_of(None))
        if not remat:
            _close(jax.jit(lm.apply)(params, toks[:, :-1]), logits, rtol=5e-4)
    _close(loss, want)
    assert set(metrics) == {"ce", "aux_lb", "aux_z", "moe_expert_tokens",
                            "gdn_decay_mean", "gdn_beta_mean",
                            "moe_shared_gate_mean"}
    assert metrics["gdn_decay_mean"].shape == (3,)
    assert metrics["moe_shared_gate_mean"].shape == (4,)
    assert 0.0 < float(metrics["gdn_decay_mean"].min()) < 1.0
    errors = REF.gradient_errors(jax.device_get(grads),
                                 jax.device_get(want_g), APP)
    assert set(errors) == {
        "embed", "head", "ln_f", "ln1.gdn", "ln1.attn", "ln2", "w_qkvz.q",
        "w_qkvz.k", "w_qkvz.v", "w_qkvz.z", "w_ba.b", "w_ba.a", "conv",
        "a_log", "dt_bias", "o_norm", "wo.gdn", "wo.attn", "wqkv.query",
        "wqkv.gate", "wqkv.k", "wqkv.v", "q_head_norm", "k_head_norm",
        "moe.router", "moe.wg", "moe.wu", "moe.wd", "moe.shared_wg",
        "moe.shared_wu", "moe.shared_wd", "moe.shared_gate"}
    for leaf, (err, norm) in errors.items():
        assert norm > 0 and (err / norm) ** 0.5 < 1e-3, (leaf, err, norm)


# -- the tie of the share ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts held 4 a chip: the four chips' ROUTED parts plus the gated
    shared expert counted once are the uncut reference's whole layer. Chip
    ``r`` is this program given experts ``4r .. 4r + 3`` as its experts 0..3
    (the router's columns rolled with them: the selection is the same)."""
    from harmony_tpu.models import moe

    E, H, d, f, T = 16, 4, 64, 32, 40
    cfg = moe.DroplessConfig(num_experts=E, top_k=4, d_model=d, d_ff=f,
                             experts_held=H, norm_topk=True, shared_experts=1,
                             shared_gate=True)
    whole = moe.init_dropless_params(
        jax.random.PRNGKey(2), dataclasses.replace(cfg, experts_held=E))
    whole["shared_gate"] = 4.0 * whole["shared_gate"]
    x = jax.random.normal(jax.random.PRNGKey(4), (T, d))
    app = {"moe_experts": E, "moe_top_k": 4, "moe_experts_held": E}
    with jax.default_matmul_precision("highest"):
        want, tokens, _ = REF._experts(x, whole, app, None, lambda t: t)
        only_shared = moe.moe_ffn_dropless(
            {**whole, **{k: jnp.zeros_like(whole[k][:H])
                         for k in ("wg", "wu", "wd")}}, x, cfg)[0]
        routed = 0.0
        for r in range(E // H):
            mine = {**whole, "router": jnp.roll(whole["router"], -r * H, axis=1),
                    **{k: whole[k][r * H:(r + 1) * H]
                       for k in ("wg", "wu", "wd")}}
            out, stats = moe.moe_ffn_dropless(mine, x, cfg)
            routed = routed + (out - only_shared)
            _close(jnp.roll(stats["tokens"], r * H), tokens)
    assert float(jnp.abs(only_shared).max()) > 0
    _close(routed + only_shared, want, rtol=1e-4)
    # the gate is read, not a constant half
    assert 0.01 < float(stats["shared_gate"]) < 0.99


# -- the check and its ablations --------------------------------------------------

@functools.lru_cache(maxsize=None)
def _report(dtype="float32", ablations=REF.LOGIT_ABLATIONS):
    return REF.check_logits({**APP, "dtype": "float32"}, np.asarray(_tokens()),
                            5, program_app={**APP, "dtype": dtype},
                            ablations=ablations)


def test_every_ablation_is_refused_and_the_program_is_not():
    report = _report()
    assert report["ok"], report
    assert set(report["detected"]) == set(REF.LOGIT_ABLATIONS)
    assert len(REF.LOGIT_ABLATIONS) == 18
    assert set(REF.RUN_ABLATIONS) < set(REF.LOGIT_ABLATIONS)
    limits = REF.LIMITS["float32"]
    for name, row in report["ablations"].items():
        assert row["q90"] > 20 * limits["q90"], (name, row)
    for run in ("program", "perturbed"):
        assert report[run]["q90"] < limits["q90"] / 4
    grads = report["gradients"]
    assert grads["worst"] < 1e-3 and grads["loss"] < 1e-6
    assert min(row[1] for row in grads["by_leaf"].values()) > 0.02
    with pytest.raises(ValueError, match="the control"):
        REF.check_logits({**APP, "dtype": "float32"}, np.asarray(_tokens()), 5,
                         ablations=("beta_one",))


@pytest.mark.parametrize("field,value", [
    ("rope_fraction", 1.0), ("norm_offset", False), ("moe_norm_topk", False)])
def test_a_program_built_wrong_is_refused(field, value):
    """The PROGRAM with one published mechanism off (the reference keeps the
    configuration): rotary over the whole head, ``w`` for ``1 + w``, the
    top-10 not renormalised."""
    program = {**APP, "dtype": "float32", field: value}
    report = REF.check_logits({**APP, "dtype": "float32"},
                              np.asarray(_tokens()), 5, program_app=program,
                              ablations=REF.RUN_ABLATIONS)
    assert not report["ok"]
    limits = REF.LIMITS["float32"]
    assert max(report["program"]["q90"], report["perturbed"]["q90"]) \
        > 20 * limits["q90"]


def test_a_fault_in_the_backward_alone_is_refused(monkeypatch):
    """``dq``, ``dk`` of the scan taken from ONE of the two value heads that
    share a key head: every value is the reference's, and the gradient's
    limit refuses the program by ``w_qkvz``'s q and k columns."""
    sound = K.gdn_attention

    @jax.custom_vjp
    def broken(q, k, v, g, beta):
        return sound(q, k, v, g, beta)

    def fwd(q, k, v, g, beta):
        return sound(q, k, v, g, beta), (q, k, v, g, beta)

    def bwd(res, do):
        q, k, v, g, beta = res
        R = v.shape[1] // q.shape[1]
        wide = lambda t: jnp.repeat(t, R, axis=1)
        _, pull = jax.vjp(lambda q, k, v, g, beta: sound(q, k, v, g, beta),
                          wide(q), wide(k), v, g, beta)
        dq, dk, dv, dg, db = pull(do)
        return dq[:, ::R], dk[:, ::R], dv, dg, db

    broken.defvjp(fwd, bwd)
    monkeypatch.setattr(K, "gdn_attention", broken)
    report = REF.check_logits({**APP, "dtype": "float32"},
                              np.asarray(_tokens()), 5,
                              ablations=REF.RUN_ABLATIONS)
    assert not report["ok"]
    limits = REF.LIMITS["float32"]
    assert report["program"]["q90"] < limits["q90"]
    assert report["perturbed"]["q90"] < limits["q90"]
    grads = report["gradients"]
    assert grads["loss"] < 1e-6 and grads["worst"] > 100 * grads["limit"]
    broken_leaves = {leaf for leaf, row in grads["by_leaf"].items()
                     if row[2] > grads["limit"]}
    assert {"w_qkvz.q", "w_qkvz.k"} <= broken_leaves
    assert grads["worst_leaf"] in ("w_qkvz.q", "w_qkvz.k", "conv")
    assert "wqkv.query" not in broken_leaves or grads["by_leaf"][
        "wqkv.query"][2] < grads["by_leaf"]["w_qkvz.q"][2]


def test_bfloat16_where_the_file_says_float32_is_refused():
    """The precision below the one stated fails the stated one's limits."""
    report = _report("bfloat16", REF.RUN_ABLATIONS)
    limits = REF.LIMITS["float32"]
    assert not report["ok"] and report["dtype"] == "float32"
    assert report["program"]["q90"] > limits["q90"]
    assert report["program"]["rms"] > limits["rms"]
    assert report["gradients"]["worst"] > 10 * report["gradients"]["limit"]


# -- the job path -------------------------------------------------------------------

JOB_APP = {**APP, "seed": 11}
DATA_ARGS = {"num_seqs": 2, "seq_len": 81, "vocab_size": 96, "seed": 7}


def test_five_steps_through_the_jobserver_equal_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    ``TransformerTrainer`` and JSON app_params: the five steps' losses are the
    reference's replay (float32 both sides, the table's Adam with both its
    moments against the formula); the gauges say the kinds, the value heads,
    each delta-rule block's decay and write strength and each layer's shared
    gate."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.metrics import kda
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.parallel import DevicePool

    app = json.loads(json.dumps(JOB_APP))
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="qwen3-next-tiny", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=5, num_mini_batches=1,
                                 comm_probe_period=0, app_params=app),
            num_workers=1,
            user={"data_fn": "perf.generators.random_tokens:make",
                  "data_args": DATA_ARGS})
        result = server.submit(cfg).result(timeout=300)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    losses = next(iter(result["workers"].values()))["losses"]
    data = (random_tokens.make(**DATA_ARGS),)
    want = REF.replay(JOB_APP, data, 2, 5, seed=11, logits=False)
    assert np.allclose(losses[:5], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    row = status["tenants"]["qwen3-next-tiny"]
    assert row["layer_kinds"] == {"gdn": 3, "mha": 1}
    fams = parse_exposition(get_registry().expose())
    mine = lambda name: {tuple(sorted(l.items())): v for _, l, v in
                         fams[name]["samples"] if l["job"] == "qwen3-next-tiny"}
    heads = {dict(k)["kind"]: v for k, v in mine("harmony_model_heads").items()}
    assert heads == {"gdn": 4.0, "mha": 4.0}  # VALUE heads; query heads
    for name in ("harmony_gdn_decay_mean", "harmony_gdn_beta_mean"):
        rows = mine(name)
        assert {dict(k)["layer"] for k in rows} == {"0", "1", "2"}
        assert all(0.0 < v < 1.0 for v in rows.values())
    gate = mine("harmony_moe_shared_gate_mean")
    assert {dict(k)["layer"] for k in gate} == {"0", "1", "2", "3"}
    assert all(0.0 < v < 1.0 for v in gate.values())
    assert set(kda.stats_by_job("gdn")["qwen3-next-tiny"]) == {
        "decay_mean", "beta_mean"}
    assert "qwen3-next-tiny" not in kda.stats_by_job("kda")


# -- tracing and the traced step ----------------------------------------------

def _kernel_calls(jaxpr, out=None):
    """``{kernel name: pallas_call equations}`` of ``jaxpr``, the equations
    of every nested jaxpr included."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            out[name] = out.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, out)
    return out


def test_the_step_holds_each_kernel_once_a_block_and_one_traced_body_a_kind(
        monkeypatch):
    """Traced for a TPU under ``remat`` at the published head widths: the
    scalar route's forward and backward once a delta-rule block, the by-rows
    kernel that hands them q, k and v a section a call, flash's once in the
    softmax block, the rotary with its fused head norm on q and k; the
    block's Python body traced once a KIND of block; ``kernel_plans`` and
    ``remat_saved`` name the kernels and what they keep; the lowered step's
    locations carry the accepted leaf scopes."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    bodies = []
    sound = TransformerLM._block
    monkeypatch.setattr(
        TransformerLM, "_block",
        lambda self, x, layer, *a, **k: bodies.append(
            "gdn" if "gdn" in layer else "mha") or sound(self, x, layer, *a, **k))
    app = {**CONF["job"]["app_params"], "d_model": 256, "vocab_size": 8192,
           "max_seq": 2048, "moe_experts": 16, "moe_top_k": 4,
           "moe_experts_held": 8, "dtype": jnp.bfloat16, "remat": True}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, 2049), jnp.int32)
    with trace_span("job.build_step", job_id="plan-qwen3-next"):
        traced = jax.jit(jax.grad(lm.loss)).trace(params, toks)
    assert sorted(bodies) == ["gdn", "mha"]
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    assert calls["harmony_gdn_fwd"] == calls["harmony_gdn_bwd"] == 3
    assert calls["harmony_flash_fwd"] == calls["harmony_flash_bwd"] == 1
    # q and k of the one softmax block: forward, again under remat, backward
    assert calls["harmony_rotary"] == 2 * 3
    # q | k | v of a delta-rule block's ONE projection, a section a call
    # (PR 62): forward, again under remat, backward, three blocks
    assert calls["harmony_conv_heads"] == 3 * 3 * 3
    assert "harmony_kda_fwd" not in calls
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-qwen3-next"]}
    assert {"harmony_gdn_fwd", "harmony_gdn_bwd", "harmony_flash_fwd",
            "harmony_flash_bwd", "harmony_rotary",
            "harmony_conv_heads"} <= set(rows)
    assert (rows["harmony_conv_heads"]["block_q"],
            rows["harmony_conv_heads"]["sub"],
            rows["harmony_conv_heads"]["sections"]) == (
        1024, 4, "l2_scaled:16,l2:16,plain:32")
    assert (rows["harmony_gdn_fwd"]["d"], rows["harmony_gdn_fwd"]["dv"],
            rows["harmony_gdn_fwd"]["block_k"]) == (128, 128, 32)
    # 32 chunks a head: eight a grid step (PR 63), 512 positions
    for name in ("harmony_gdn_fwd", "harmony_gdn_bwd"):
        assert (rows[name]["block_q"], rows[name]["grid_steps"]) == (
            8 * K.CHUNK, 32 * (2048 // K.CHUNK) // 8)
    kept = {r["name"]: r for r in progcache.remat_saved()["plan-qwen3-next"]}
    assert kept["kda_out"]["arrays"] == kept["kda_state"]["arrays"] == 3
    assert kept["kda_state"]["bytes"] == 3 * 32 * (2048 // 64) * 128 * 128 * 4
    assert kept["kda_solve"]["bytes"] == 3 * 32 * (2048 // 64) * 64 * 64 * 4
    text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    for scope in ("kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out",
                  "mixer.gate", "moe.shared", "blk3"):
        assert scope in text, scope
    assert "blk4" not in text
