"""Laguna-S-2.1's block on the normal path (PR 54): softmax blocks of two
kinds that differ in their QUERY HEAD COUNT as well as their mask, each kind
with its own rotary (YaRN on half a head in the full blocks, plain on all of
it in the windowed ones), a sigmoid gate a head on the attention output, a
leading dense layer, softmax top-k experts renormalised and scaled with one
shared expert of held columns. ``TransformerLM`` with the architecture fields
against the plain reference the benchmark ships
(``perf/reference/laguna-s-2.1.py``: float32, K and V repeated, an explicit
boolean mask, YaRN written out from the formula, a loop over the held
experts, no kernel).

Small, float32, seeded — the configuration's ``rehearse`` preset: d 64, 4
full / 6 windowed query heads over 2 K/V heads of 16, a window of 8 over 48
positions, YaRN at factor 8 over 16 original positions, blocks F S S S F with
a dense MLP in the first, 16 experts of width 32, top-4, 8 held. Both sides
are float32 on the CPU and differ in the order of sums, so 2e-5 relative
holds for values and 1e-4 for gradients.
"""
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
from harmony_tpu.models.transformer import (  # noqa: E402
    Rotary, ffn_apply, rope)
from harmony_tpu.ops import attention as A  # noqa: E402
from perf.generators import random_tokens  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "laguna-s-2.1")
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs", "laguna-s-2.1.json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "vocab_size": 96, "step_size": 1e-3}
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}

# the configuration's own checks (perf/tests is run by hand and does not
# count): collected here too, from the same file — but for the rehearsal,
# which runs the whole harness in a child (the jobserver test below covers
# the job path)
_spec = importlib.util.spec_from_file_location(
    "perf_test_laguna", os.path.join(ROOT, "perf", "tests", "test_laguna.py"))
_perf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_perf)
globals().update({name: obj for name, obj in vars(_perf).items()
                  if name.startswith("test_")
                  and name != "test_rehearsal_runs_to_a_correct_line"})


def test_the_cell_and_its_metrics_are_in_the_benchmark(monkeypatch):  # noqa: F811
    """``perf/tests/test_laguna.py``'s check holds ``hetero_flash_roofline_
    share`` to Laguna's cell ALONE, and a PR may not edit a file the benchmark
    has. The reader was written for later configurations to join ("the next
    configuration brings a work file, not a reader") and PR 57 appended one;
    PR 61 appended a cell to ``attn_gate_time_share``'s list too (its gate
    lies under the same scope): the check runs on the benchmark with the
    cells appended since taken off those two lists (Laguna's stays first)."""
    import copy

    bench = copy.deepcopy(_perf.BENCH)
    for m in bench["per_layer"]:
        if m["name"] in ("hetero_flash_roofline_share",
                         "attn_gate_time_share"):
            assert m["workloads"][0] == _perf.CELL
            m["workloads"] = m["workloads"][:1]
    monkeypatch.setattr(_perf, "BENCH", bench)
    _perf.test_the_cell_and_its_metrics_are_in_the_benchmark()


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


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


_as_reference = lambda tree: REF.from_program(tree, APP)


# -- the kernels at a 512-key window and groups of 6 and 9 -------------------

#: (positions, window, query heads over ONE K/V head, explicit block or None:
#: the plan's tiles) — the cell's window with the cell's two group sizes, at
#: a length of several windows
CASES = {
    "full-group-6": (1024, None, 6, 256),
    "window-512-group-9": (1536, 512, 9, 256),
    "window-512-group-9-planned": (1024, 512, 9, None),
}


@functools.lru_cache(maxsize=None)
def _kernel_case(name):
    S, W, H, blk = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(S + H), 4)
    q = jax.random.normal(ks[0], (1, H, S, 16))
    k = jax.random.normal(ks[1], (1, 1, S, 16))
    v = jax.random.normal(ks[2], (1, 1, S, 16))
    w = jax.random.normal(ks[3], (1, H, S, 16))

    def both(fn):
        out = fn(q, k, v)
        grads = jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2))(
            q, k, v)
        return (out, *grads)

    got = both(lambda *a: A.flash_attention(
        *a, causal=True, block_q=blk, block_k=blk, interpret=True, window=W))
    want = both(lambda *a: A.blockwise_attention(
        *a, causal=True, block_k=128, window=W))
    return got, want


@pytest.mark.parametrize("output", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_each_kernel_equals_blockwise_at_the_cells_window_and_groups(
        name, output):
    got, want = _kernel_case(name)
    for i in {"fwd": (0,), "dq": (1,), "dkv": (2, 3)}[output]:
        assert got[i].shape == want[i].shape
        assert np.isfinite(np.asarray(got[i])).all()
        _close(got[i], want[i], 1e-5)


@pytest.mark.parametrize("group,window", [(6, None), (9, 512)])
def test_the_cells_shapes_have_a_plan_and_its_band_is_counted(group, window):
    """16,384 positions, 128 wide, bfloat16, one K/V head: the plan the cell
    runs (PERF.md section 7 reads its masked share on the chip's tiles)."""
    S = 16384
    plan = A.tile_plan(S, S, 128, jnp.bfloat16, causal=True, window=window,
                       group=group)
    assert plan is not None and plan.planned
    for kern in ("fwd", "bwd"):
        t = getattr(plan, kern)
        work = A.band_work(kern, t, S, S, True, window)
        want = (S * (S + 1) // 2 if window is None else
                window * (window + 1) // 2 + (S - window) * window)
        assert work["kept"] == want <= work["computed"]
        assert (t.vmem_limit_bytes or 0) <= A._VMEM_CAP + A._VMEM_DEFAULT
    if window is not None:  # a backward tile no wider than the band
        assert plan.bwd.block_q <= window


# -- the fields ---------------------------------------------------------------

def test_layer_kinds_heads_and_rotaries_by_kind():
    from harmony_tpu.metrics import kda as kinds
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    cfg = _config(APP)
    assert cfg.layer_kinds() == ("full", "swa", "swa", "swa", "full")
    assert [cfg.heads(k) for k in cfg.layer_kinds()] == [4, 6, 6, 6, 4]
    assert cfg.qkv_widths_of("full") == (64, 32, 32)
    assert cfg.qkv_widths_of("swa") == (96, 32, 32)
    full, swa = cfg.rotary("full"), cfg.rotary("swa")
    assert (full.theta, full.fraction, full.yarn) == (
        500000.0, 0.5, (8.0, 16, 32.0, 1.0))
    assert full.attention_factor == pytest.approx(0.1 * np.log(8) + 1)
    assert swa == Rotary(10000.0, 1.0)
    # hashable and rebuilt from itself (dataclasses.replace)
    again = dataclasses.replace(cfg, remat=True)
    assert again.kind_rope == cfg.kind_rope and hash(cfg) != hash(again)
    kinds.note_layer_kinds("kinds-lg", cfg.layer_kinds(), heads={
        k: cfg.heads(k) for k in set(cfg.layer_kinds())})
    assert kinds.kinds_by_job()["kinds-lg"] == {"full": 2, "swa": 3}
    fams = parse_exposition(get_registry().expose())
    heads = {l["kind"]: v for _, l, v in fams["harmony_model_heads"]["samples"]
             if l["job"] == "kinds-lg"}
    assert heads == {"full": 4.0, "swa": 6.0}


def test_a_model_without_the_fields_reads_window_layers_as_before():
    """SmallThinker's rule stays the default reading: windowed blocks turn by
    ``rope_theta``, full ones carry no positions, every block ``n_heads``."""
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=2,
                            pos="rope", rope_theta=1.5e6, window=8,
                            window_layers=[1])
    assert cfg.rotary("full") is None
    assert cfg.rotary("swa") == Rotary(1.5e6, 1.0)
    assert (cfg.heads("full"), cfg.heads("swa"), cfg.attn_gate) == (4, 4, "none")
    plain = TransformerConfig(vocab_size=32, d_model=32, n_heads=4, pos="rope",
                              rope_fraction=0.5)
    assert plain.rotary(None) == Rotary(10000.0, 0.5)
    assert TransformerConfig(vocab_size=32, d_model=32).rotary("mha") is None


def test_yarn_frequencies_are_the_formulas():
    """The published full-attention rotary, by the formula in float64: pairs
    0-8 keep their frequency, 18-31 are slowed 128 times, a ramp between;
    ``attention_factor`` defaults to ``0.1 ln(factor) + 1``."""
    spec = CONF["rope_parameters"]["full_attention"]
    rot = Rotary.of(spec)
    got = np.asarray(rot.inv_freq(64), np.float64)
    i = np.arange(32)
    f = 500000.0 ** (-2.0 * i / 64)
    dim = lambda n: 64 * np.log(8192 / (2 * np.pi * n)) / (2 * np.log(500000.0))
    low, high = np.floor(dim(32)), np.ceil(dim(1))
    assert (low, high) == (9, 18)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, (1 - ramp) * f + ramp * f / 128, rtol=2e-6)
    np.testing.assert_allclose(got[:10], f[:10], rtol=2e-6)
    np.testing.assert_allclose(got[18:], f[18:] / 128, rtol=2e-6)
    assert Rotary.of({k: v for k, v in spec.items() if k != "attention_factor"}
                     ).attention_factor == pytest.approx(1.4852030263919618)
    # cos and sin carry the factor; the unturned half passes
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 128))
    turned = rope(x, rot.theta, width=64, scaled=rot)
    np.testing.assert_array_equal(np.asarray(turned[..., 64:]),
                                  np.asarray(x[..., 64:]))
    np.testing.assert_allclose(np.asarray(turned[:, :, 0, :64]),
                               1.4852030263919618 * np.asarray(x[:, :, 0, :64]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(turned[..., :64]),
        np.asarray(REF.rotary(x[..., :64], {**spec, "partial_rotary_factor": 1})),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fields,match", [
    (dict(kind_heads={"full": 4}), "window_layers"),
    (dict(kind_rope={"full": None, "swa": {"rope_theta": 1e4}}),
     "window_layers"),
    (dict(window=8, window_layers=[1], pos="rope", n_kv_heads=2,
          kind_heads={"swa": 5}), "whole groups"),
    (dict(window=8, window_layers=[1], pos="rope", kind_heads={"mha": 4}),
     "kind_heads names"),
    (dict(window=8, window_layers=[1], pos="rope",
          kind_rope={"swa": {"rope_theta": 1e4}}), "kind_rope BOTH"),
    (dict(window=8, window_layers=[1], pos="rope", rope_fraction=0.5,
          kind_rope={"full": None, "swa": {"rope_theta": 1e4}}),
     "rope_fraction has no part"),
    (dict(window=8, window_layers=[1], pos="rope",
          kind_rope={"full": None, "swa": {"rope_theta": 1e4, "scale": 2}}),
     "rope_type"),
    (dict(window=8, window_layers=[1], pos="rope",
          kind_rope={"full": None, "swa": {
              "rope_theta": 1e4, "partial_rotary_factor": 0.3}}),
     "whole even number"),
    (dict(window=8, window_layers=[1], pos="rope",
          kind_rope={"full": None, "swa": {
              "rope_theta": 1e4, "rope_type": "yarn", "factor": 0.5,
              "original_max_position_embeddings": 16}}), "factor >= 1"),
    (dict(attn_gate="token"), "unknown attn_gate"),
    (dict(attn_gate="head", attn_kind="mla", kv_lora_rank=8,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, pos="rope"),
     "attn_gate='head' gates"),
    (dict(attn_gate="head", linear_layers=[0], linear_heads=2,
          linear_head_dim=8, short_conv=4, pos="none"),
     "attn_gate='head' gates"),
    (dict(moe_shared_d_ff=8, moe_shared_experts=1, moe_experts=4),
     "moe_shared_d_ff is the held width"),
])
def test_fields_that_describe_no_model_are_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=3,
                          **fields)


@pytest.mark.parametrize("make", ["make_sp_train_step", "make_pp_train_step",
                                  "make_generate_fn"])
@pytest.mark.parametrize("field", ["kind_heads", "kind_rope", "attn_gate"])
def test_the_side_steps_and_the_decode_path_refuse_the_fields(make, field):
    """``require_classic_block``: each new field alone, under the GPT-2-era
    block's other defaults (a stand-in config — the real ones would be
    refused for their rotary first)."""
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=2)
    value = {"kind_heads": (("swa", 8),), "attn_gate": "head",
             "kind_rope": (("full", None), ("swa", Rotary(1e4)))}[field]
    object.__setattr__(cfg, field, value)
    with pytest.raises(ValueError, match=f"GPT-2-era block .* {field}"):
        cfg.require_classic_block(make)


# -- the model against the reference ----------------------------------------

def test_the_seeded_parameters_are_the_references():
    _, params, _, ref = _both()
    got = _as_reference(params)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_init_traced_abstractly_has_the_same_leaves():
    lm, params, _, _ = _both()
    other = jax.eval_shape(lm.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.structure(params) == jax.tree.structure(other)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(other)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_logits_equal_the_reference():
    lm, params, app, ref = _both()
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks), REF.forward(ref, toks, app)[0])


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_equal_the_reference(remat):
    lm, params, app, ref = _both()
    if remat:
        lm = TransformerLM(dataclasses.replace(lm.config, remat=True))
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lm.loss)(params, toks)
        want, want_g = jax.value_and_grad(REF.loss_fn)(ref, toks, app)
    _close(loss, want)
    got = _as_reference(grads)
    for path, w in jax.tree_util.tree_leaves_with_path(want_g):
        g = functools.reduce(lambda t, k: t[getattr(k, "key", getattr(
            k, "idx", None))], path, got)
        _close(g, w, 1e-4)


def test_two_adam_steps_equal_the_replay():
    """The reference's replay (its own Adam) against the program's loss after
    the same first update, formed by formula from the program's gradient —
    the replay as the harness calls it: after ``check_logits``, whose
    reference loss and gradient on the first batch are its first step (the
    six steps through the jobserver below replay without the check)."""
    lm, params, app, _ = _both()
    toks = _tokens(3)
    with jax.default_matmul_precision("highest"):
        want = REF.replay(dict(app), (np.asarray(toks),), 2, 2, 5)
        loss0, g = jax.value_and_grad(lm.loss)(params, toks)
        stepped = jax.tree.map(
            lambda p, a: p - APP["step_size"] * a / (jnp.abs(a) + 1e-8),
            params, g)
        loss1 = lm.loss(stepped, toks)
    _close(loss0, want[0])
    _close(loss1, want[1], 1e-4)


@functools.lru_cache(maxsize=None)
def _report(dtype):
    return REF.check_logits({**APP, "dtype": dtype}, np.asarray(_tokens()), 5,
                            ablations=REF.LOGIT_ABLATIONS)


def test_every_ablation_is_told_apart_and_the_program_is_not():
    """``check_logits`` as the cell runs it, at the test size in float32: the
    program holds both limits in all three ranges of positions, and each
    broken piece of the mathematics reads above them."""
    report = _report("float32")
    assert report["ok"], report
    assert set(report["detected"]) == set(REF.LOGIT_ABLATIONS)
    assert all(report["detected"].values())
    assert tuple(report["program"]) == REF.RANGES
    assert report["ranges"] == {"before_window": [0, 8],
                                "window_to_original": [8, 16],
                                "past_original": [16, None]}
    # the first window's positions cannot see a window's edge: a window one
    # key longer moves nothing there, and everything after
    moved = report["ablations"]["window_plus_one"]
    assert moved["before_window"]["q90"] == 0.0
    assert min(moved[r]["q90"] for r in REF.RANGES[1:]) > 1e-3


def test_a_program_with_the_window_one_key_too_long_is_refused_by_direction():
    """The third limit (``REF.toward``): the program's error projected on
    what each ablation does to the reference's logits. The whole program
    reads 0 on every ablation at every quartile of the positions; a PROGRAM whose
    window is one key too long reads 1 on ``window_plus_one`` — on the chip
    that ablation moves the logits by less than bfloat16 does, and this is
    the limit that refuses it."""
    good = _report("float32")
    for a in REF.LOGIT_ABLATIONS:
        assert abs(good["toward"][a]["q25"]) < 1e-3, a
        assert abs(good["toward"][a]["q75"]) < 1e-3, a
    app = {**APP, "dtype": "float32"}
    report = REF.check_logits(app, np.asarray(_tokens()), 5,
                              program_app={**app, "window": APP["window"] + 1},
                              ablations=REF.LOGIT_ABLATIONS)
    assert not report["ok"]
    lean = report["toward"]["window_plus_one"]
    assert 0.99 < lean["q25"] <= lean["median"] <= lean["q75"] < 1.01
    assert lean["median"] > REF.TOWARD == report["toward_limit"]
    assert lean["positions"] == 2 * (APP["max_seq"] - APP["window"])
    # the other ablations' directions are not this program's
    assert abs(report["toward"]["no_gate"]["median"]) < 0.1


def test_the_programs_gradient_is_held_leaf_by_leaf_against_the_control():
    """``check_logits``' second half: the program's gradient against the
    reference's, the mixer's leaves apart by kind of block and ``wqkv``'s q /
    k / v columns apart, as a share of what float8 operands do to the same
    leaf."""
    grads = _report("float32")["gradients"]
    assert grads["worst"] < grads["limit"] == 1e-3, grads
    assert grads["loss"] < 1e-6
    mixer = {f"{leaf}.{kind}" for kind in ("full", "swa")
             for leaf in ("g1", "wq", "wk", "wv", "wo", "wgate")}
    assert set(grads["by_leaf"]) == mixer | {
        "embed", "head", "ln_f", "g2", "w1", "w2", "w3", "router", "eg", "eu",
        "ed", "sg", "su", "sd"}
    # the control is told from the reference on every leaf: float8 operands
    # leave an error of a twentieth of the leaf or more
    assert min(row[1] for row in grads["by_leaf"].values()) > 0.02
    with pytest.raises(ValueError, match="the control"):
        REF.check_logits({**APP, "dtype": "float32"}, np.asarray(_tokens()), 5,
                         ablations=("window_plus_one",))


def _windowed_kv_zeroed(g):
    kv = REF.qkv_widths(APP, "swa")[0]
    return {**g, "layers": [
        {**l, "wqkv": l["wqkv"].at[:, kv:].set(0.0)}
        if REF.kind_of(APP, i) == "swa" else l
        for i, l in enumerate(g["layers"])]}


def _gate_backward_left_out(g):
    return {**g, "layers": [{**l, "wgate": jnp.zeros_like(l["wgate"])}
                            for l in g["layers"]]}


def _windowed_kv_of_one_head(g):
    """dK / dV of the windowed blocks as ONE query head of the group of 6
    would give them on its own: a sixth (a sum over the group left out)."""
    kv = REF.qkv_widths(APP, "swa")[0]
    return {**g, "layers": [
        {**l, "wqkv": l["wqkv"].at[:, kv:].multiply(
            APP["n_kv_heads"] / APP["kind_heads"]["swa"])}
        if REF.kind_of(APP, i) == "swa" else l
        for i, l in enumerate(g["layers"])]}


@pytest.mark.parametrize("fault,leaves", [
    (_windowed_kv_zeroed, {"wk.swa", "wv.swa"}),
    (_gate_backward_left_out, {"wgate.full", "wgate.swa"}),
    (_windowed_kv_of_one_head, {"wk.swa", "wv.swa"}),
], ids=["windowed-dkv-zeroed", "gate-backward-left-out",
        "windowed-dkv-not-summed-over-the-group"])
def test_a_fault_in_the_backward_alone_is_refused(monkeypatch, fault, leaves):
    """A program whose FORWARD is the reference's and whose gradient is wrong
    in the leaves the new shapes reach (the windowed kernel's dK / dV, the sum
    over a group of query heads, the gate's backward): the logits and the
    first loss hold, and the gradient's limit refuses it, the broken leaves
    and no other (at 64 columns float8 leaves errors as large as these
    leaves themselves, so the shares read 0.5-4 here; at the cell's size they
    read 4-11, perf/configs/laguna-s-2.1.json ``job.why.loss_rtol``). The losses alone see such a fault only through Adam's first
    update, which keeps a gradient's sign."""
    sound = TransformerLM.loss

    def loss(self, params, tokens, axis_name=None):
        @jax.custom_vjp
        def f(p):
            return sound(self, p, tokens)

        def fwd(p):
            value, g = jax.value_and_grad(lambda q: sound(self, q, tokens))(p)
            return value, fault(g)

        f.defvjp(fwd, lambda g, ct: (jax.tree.map(lambda x: ct * x, g),))
        return f(params)

    monkeypatch.setattr(TransformerLM, "loss", loss)
    report = REF.check_logits({**APP, "dtype": "float32"},
                              np.asarray(_tokens()), 5)
    assert set(report["detected"]) == set(REF.RUN_ABLATIONS)
    assert not report["ok"]
    assert all(report["program"][r]["q90"] <= REF.LIMITS["float32"][r]["q90"]
               for r in REF.RANGES)
    grads = report["gradients"]
    assert grads["loss"] < 1e-6 and grads["worst_leaf"] in leaves
    broken = {leaf for leaf, row in grads["by_leaf"].items() if row[2] > 1e-3}
    assert broken == leaves
    assert min(grads["by_leaf"][leaf][2] for leaf in leaves) > 0.5


def test_bfloat16_where_the_file_says_float32_is_refused():
    """The precision below the one stated fails the stated one's limits, in
    every range."""
    report = _report("bfloat16")
    limits = REF.LIMITS["float32"]
    for r in REF.RANGES:
        assert report["program"][r]["q90"] > limits[r]["q90"], r
        assert report["program"][r]["rms"] > limits[r]["rms"], r
    assert report["gradients"]["worst"] > 100 * REF.GRAD_LIMITS["float32"]


@pytest.mark.parametrize("ablate", REF.LOGIT_ABLATIONS)
def test_each_ablation_moves_the_reference_itself(ablate):
    """The six the issue names — the two kinds' rotaries swapped,
    ``attention_factor`` left out, the gate on the wrong head or left out,
    the window off by one, the 2.5 left out — with YaRN's ramp left out and
    float8 operands: each is told from the reference in some range."""
    _, _, app, ref = _both()
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        want = REF.forward(ref, toks, app)[0]
        broken = REF.forward(ref, toks, app, ablate)[0]
    errors = REF.errors_by_range(broken, want, app)
    assert max(e["q90"] for e in errors.values()) > 1e-3
    assert _report("float32")["detected"][ablate]


def test_the_balance_loss_is_in_the_loss():
    _, _, app, ref = _both()
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        with_aux = REF.loss_fn(ref, toks, app)
        without = REF.loss_fn(ref, toks, app, "no_aux")
    assert 1e-4 < float(with_aux - without) < 1e-2  # 0.001 x a term of ~1


# -- the job path ------------------------------------------------------------

JOB_APP = {**APP, "seed": 11}
DATA_ARGS = {"num_seqs": 2, "seq_len": 49, "vocab_size": 96, "seed": 7}


def test_six_steps_through_the_jobserver_equal_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    ``TransformerTrainer`` and JSON app_params (the per-kind fields as JSON
    objects): the six steps' losses are the reference's replay (float32 both
    sides, the table's Adam against the formula); STATUS and the gauges say
    the kinds and their heads."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.parallel import DevicePool

    app = json.loads(json.dumps(JOB_APP))
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="laguna-tiny", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=6, num_mini_batches=1,
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
    want = REF.replay(JOB_APP, data, 2, 6, seed=11, logits=False)
    assert np.allclose(losses[:6], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    row = status["tenants"]["laguna-tiny"]
    assert row["table_layout"]["tile_exact"] == 1
    assert row["layer_kinds"] == {"full": 2, "swa": 3}
    fams = parse_exposition(get_registry().expose())
    heads = {l["kind"]: v for _, l, v in fams["harmony_model_heads"]["samples"]
             if l["job"] == "laguna-tiny"}
    assert heads == {"full": 4.0, "swa": 6.0}
    slots = sum(v for _, l, v in
                fams["harmony_moe_expert_tokens_total"]["samples"]
                if l["job"] == "laguna-tiny")
    assert slots == 6 * 4 * 96 * 4  # steps x expert layers x tokens x top-4


# -- the share tied to the model ------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test, on a windowed expert block and on the leading
    dense one: what the chips that share a layer compute — each tensor rank
    its K/V head's group of query heads through its row block of ``Wo`` (with
    its rows of the gate) and its columns of the dense and the shared MLP,
    each expert rank its two experts; the residual stream, the norms and the
    router computed alike everywhere and counted once — adds up to what the
    reference gives for the uncut block. Two tensor ranks (one K/V head
    each) x eight expert ranks."""
    hd, d = 16, 64
    uncut = REF._Static({**APP, "moe_experts_held": 16, "moe_shared_d_ff": 32,
                         "dense_d_ff": 96})
    ref = REF.init_params(uncut, 7)
    share = TransformerLM(_config({
        **APP, "n_heads": 2, "n_kv_heads": 1, "moe_experts_held": 2,
        "kind_heads": {"full": 2, "swa": 3}, "moe_shared_d_ff": 16,
        "dense_d_ff": 48}))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, APP["max_seq"], d))
    norm = lambda t, w: REF.rms_norm(t, w, APP["norm_eps"])
    for index, kind, dense in ((1, "swa", False), (0, "full", True)):
        layer = ref["layers"][index]
        h = REF.heads_of(uncut, kind)
        wq = h * hd
        idle = {**layer, **({"w2": jnp.zeros_like(layer["w2"])} if dense else
                            {"ed": jnp.zeros_like(layer["ed"]),
                             "sd": jnp.zeros_like(layer["sd"])})}
        with jax.default_matmul_precision("highest"):
            whole = REF._block(x, layer, uncut, kind, dense, None)[0]
            y = REF._block(x, idle, uncut, kind, dense, None)[0]
            # attention: a tensor rank's heads, summed through Wo's row blocks
            mixed = 0.0
            for t in range(2):
                q = slice(t * (h // 2) * hd, (t + 1) * (h // 2) * hd)
                kv = lambda base: slice(base + t * hd, base + (t + 1) * hd)
                part = {"wqkv": jnp.concatenate(
                    [layer["wqkv"][:, q], layer["wqkv"][:, kv(wq)],
                     layer["wqkv"][:, kv(wq + 2 * hd)]], axis=1),
                    "wo": layer["wo"][q],
                    "wgate": layer["wgate"][t * (h // 2):(t + 1) * (h // 2)]}
                mixed = mixed + share._softmax_mixer(
                    norm(x, layer["g1"]), part, None, 0, kind)
            _close(x + mixed, y, 1e-5)
            # the feed-forward half on the summed stream
            b = norm(y, layer["g2"])
            if dense:
                out = sum(ffn_apply(share.config, {
                    "w1": layer["w1"][:, c], "w3": layer["w3"][:, c],
                    "w2": layer["w2"][c]}, b)[0]
                    for c in (slice(0, 48), slice(48, 96)))
            else:
                zeros = jnp.zeros((16, d))
                out = 0.0
                for s in range(8):  # an expert rank: its two experts first
                    perm = np.roll(np.arange(16), -2 * s)
                    out = out + ffn_apply(share.config, {"moe": {
                        "router": layer["router"][:, perm],
                        "wg": layer["eg"][perm[:2]], "wu": layer["eu"][perm[:2]],
                        "wd": layer["ed"][perm[:2]],
                        "shared_wg": layer["sg"][:, :16],
                        "shared_wu": layer["su"][:, :16],
                        "shared_wd": zeros}}, b)[0]
                for c in (slice(0, 16), slice(16, 32)):  # the shared columns
                    out = out + ffn_apply(share.config, {"moe": {
                        "router": layer["router"],
                        "wg": layer["eg"][:2], "wu": layer["eu"][:2],
                        "wd": jnp.zeros_like(layer["ed"][:2]),
                        "shared_wg": layer["sg"][:, c],
                        "shared_wu": layer["su"][:, c],
                        "shared_wd": layer["sd"][c]}}, b)[0]
        _close(y + out, whole, 1e-5)


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


def test_the_step_holds_both_kernel_sets_once_a_block_and_the_gate(
        monkeypatch):
    """Traced for a TPU under ``remat``: each kind's flash forward is in the
    loss-and-gradient program ONCE a block of its kind (PR 53's saved names
    hold with the gate's product inside the mixer: the flash output is the
    kept residual, the gate is recomputed from the block's input), STATUS
    ``kernel_plans`` rows say the group — 2 and 3 queries a K/V head here —
    and the lowered step's locations name the gate's scope."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    app = {**APP, "max_seq": 1024, "window": 256, "dtype": jnp.bfloat16,
           "remat": True}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, 1025), jnp.int32)
    with trace_span("job.build_step", job_id="plan-lg"):
        traced = jax.jit(jax.grad(lm.loss)).trace(params, toks)
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    assert {k: calls.get(k) for k in (
        "harmony_flash_fwd", "harmony_flash_bwd", "harmony_flash_win_fwd",
        "harmony_flash_win_bwd")} == {
        "harmony_flash_fwd": 2, "harmony_flash_bwd": 2,
        "harmony_flash_win_fwd": 3, "harmony_flash_win_bwd": 3}
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-lg"]}
    assert (rows["harmony_flash_fwd"]["group"],
            rows["harmony_flash_win_fwd"]["group"]) == (2, 3)
    assert (rows["harmony_flash_bwd"]["window"],
            rows["harmony_flash_win_bwd"]["window"]) == (0, 256)
    kept = {r["name"]: r for r in progcache.remat_saved()["plan-lg"]}
    assert kept["flash_out"]["arrays"] == kept["flash_lse"]["arrays"] == 5
    text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "mixer.gate" in text and "mixer.rope" in text
