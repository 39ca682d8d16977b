"""Nemotron-H's layers on the normal path (PR 38): layers that are ONE
pre-norm sublayer chosen by a pattern's letter, Mamba-2 state-space layers
through the chunked scan of ``ops/ssd.py``, and experts that are not gated,
work in a latent and share a held slice of a shared MLP. ``TransformerLM``
with the architecture fields against the plain reference the benchmark ships
(``perf/reference/nemotron-3-super-120b-a12b.py``: float32, the recurrence a
position a step, a loop over the held experts, no kernel).

Small, float32, seeded: the configuration file's ``rehearse`` preset (d 64,
all three letters, 4 state-space heads of 8 in 2 groups over 80 positions in
chunks of 32, 4 query heads over 2 K/V heads, 16 experts top-4 with 8 held in
a latent of 32). Both sides are float32 on the CPU and differ in the order of
sums — the chunked scan against the sequential one, the sorted experts
against the loop — so 2e-5 relative holds for values and gradients.
"""
from __future__ import annotations

import dataclasses
import functools
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
from harmony_tpu.models import moe as moe_mod  # noqa: E402
from harmony_tpu.ops import ssd  # noqa: E402
from perf.run import load_by_path  # noqa: E402

NAME = "nemotron-3-super-120b-a12b"
REF = load_by_path("reference", NAME)
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs", NAME + ".json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "step_size": 1e-3}
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}


def _config(app):
    return TransformerConfig(**{k: v for k, v in app.items() if k in FIELDS})


def _tokens(seed=0, batch=2, app=APP):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, app["vocab_size"], (batch, app["max_seq"] + 1)), jnp.int32)


def _both(app=APP, seed=5):
    lm = TransformerLM(_config(app))
    return (lm, lm.init(jax.random.PRNGKey(seed)), REF._Static(app),
            REF.init_params(app, seed))


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


def _layer_as_reference(layer):
    """One layer of the program's parameter (or gradient) tree under the
    reference's names."""
    if "ssd" in layer:
        p = layer["ssd"]
        return {"g": layer["ln"], "w_in": p["w_in"], "taps": p["conv"],
                "b_c": p["conv_b"], "a_log": p["a_log"],
                "dt_bias": p["dt_bias"], "skip": p["skip"],
                "g_y": p["o_norm"], "w_out": p["w_out"]}
    if "moe" in layer:
        p = layer["moe"]
        return {"g": layer["ln"], "router": p["router"], "bias": p["bias"],
                "w1": p["wu"], "w2": p["wd"], "v1": p["shared_wu"],
                "v2": p["shared_wd"], "down": p["latent_down"],
                "up": p["latent_up"]}
    return {"g": layer["ln"], "wqkv": layer["wqkv"], "wo": layer["wo"]}


def _as_reference(tree):
    return {"embed": tree["embed"], "head": tree["head"], "ln_f": tree["ln_f"],
            "layers": [_layer_as_reference(l) for l in tree["layers"]]}


# -- the model against the reference -----------------------------------------

@functools.lru_cache(maxsize=None)
def _sides():
    """Both sides on one batch, each compiled once for every test below:
    ``{"program" | "reference": (loss, aux, gradients, logits)}``."""
    lm, params, app, ref = _both()
    toks = _tokens(1)
    with jax.default_matmul_precision("highest"):
        (loss, m), g = jax.jit(jax.value_and_grad(
            lm.loss_and_metrics, has_aux=True))(params, toks)
        want, wg = jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, toks, app)))(ref)
        logits = jax.jit(lm.apply)(params, toks[:, :-1])
        wl, lb = jax.jit(lambda p: REF.forward(p, toks[:, :-1], app))(ref)
    return {"program": (loss, m, g, logits), "reference": (want, lb, wg, wl),
            "batch": toks}


def test_the_seeded_parameters_are_the_references():
    _, params, _, ref = _both()
    got = _as_reference(params)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_cells_parameter_count_is_the_files():
    """508,189,680 by part, as the configuration's ``deployment`` states it:
    counted from the shapes, nothing is allocated."""
    lm = TransformerLM(_config(CONF["job"]["app_params"]))
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape))
                            for a in jax.tree.leaves(tree))
    by_letter = {c: size(shapes["layers"][i]) for i, c in
                 enumerate(lm.config.layer_pattern)}
    assert by_letter == {"M": 13_708_592, "E": 60_035_584, "*": 5_246_976}
    assert size(shapes) == 508_189_680
    assert "508,189,680" in CONF["deployment"]


def test_logits_and_loss_equal_the_reference():
    (loss, m, _, logits), (want, lb, _, wl) = (
        _sides()[k] for k in ("program", "reference"))
    _close(logits, wl)
    _close(loss, want, 1e-6)
    _close(m["aux_seq"], lb, 1e-5)
    assert m["moe_expert_tokens"].shape == (2, APP["moe_experts"])
    assert m["ssd_decay_mean"].shape == m["ssd_dt_mean"].shape == (2,)
    assert ((0.0 < np.asarray(m["ssd_decay_mean"]))
            & (np.asarray(m["ssd_decay_mean"]) < 1.0)).all()


def test_every_leafs_gradient_equals_the_references():
    got = _as_reference(_sides()["program"][2])
    want = _sides()["reference"][2]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        if path[-1].key == "bias":  # selects, never weighs
            assert float(np.abs(np.asarray(g)).max()) == 0.0
            continue
        assert float(np.abs(np.asarray(w)).max()) > 0.0, path
        _close(g, w)


def test_remat_traces_one_body_a_layer_and_changes_nothing():
    _, params, _, _ = _both()
    again = TransformerLM(_config({**APP, "remat": True}))
    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(again.loss))(
            params, _sides()["batch"])
    _close(loss, _sides()["program"][0], 1e-6)
    for x, y in zip(jax.tree.leaves(g),
                    jax.tree.leaves(_sides()["program"][2])):
        _close(x, y, 1e-5)
    eqns = jax.make_jaxpr(again.loss)(params, _sides()["batch"]).jaxpr.eqns
    assert sum(e.primitive.name.startswith(("checkpoint", "remat"))
               for e in eqns) == len(APP["layer_pattern"])


def test_the_check_holds_the_program_and_refuses_every_ablation():
    """``check_logits`` — what decides the cell's ``correct`` — at this size
    in float32: the program within its limits, and every ablation moving
    the logits' 90th percentile by far more than the program reads."""
    report = REF.check_logits(dict(APP), _tokens(3)[:, :-1], 5)
    assert report["ok"] and report["dtype"] == "float32"
    assert report["program"]["q90"] < 1e-5
    assert set(report["ablations"]) == set(REF.LOGIT_ABLATIONS)
    for name, moved in report["ablations"].items():
        assert moved["q90"] > 0.02, (name, moved)


def test_the_losses_tell_a_missing_balance_term():
    _, _, app, ref = _both()
    toks = _sides()["batch"]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: REF.loss_fn(p, toks, app, "no_aux"))(ref)
    want = float(_sides()["reference"][0])
    assert abs(float(got) - want) > 1e-5 * want
    with pytest.raises(ValueError, match="unknown ablation"):
        REF.replay(dict(JOB_APP), (np.asarray(toks),), 2, 2, 0, "no_scan")


def test_wrong_group_is_left_out_where_one_group_is_held():
    assert "wrong_group" in REF.logit_ablations(APP)
    assert "wrong_group" not in REF.logit_ablations(CONF["job"]["app_params"])


# -- the scan against the recurrence, a position a step ----------------------

def _scan_case(S, seed, B=1, H=4, G=2, P=8, N=16):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.5, (B, H, S)), jnp.float32)
    return draw(B, H, S, P), draw(B, G, S, N), draw(B, G, S, N), g


def _by_position(x, b, c, g):
    H, G = x.shape[1], b.shape[1]
    per_head = lambda t: jnp.repeat(t, H // G, axis=1).transpose(0, 2, 1, 3)
    y = jax.vmap(REF._recurrence)(x.transpose(0, 2, 1, 3), per_head(b),
                                  per_head(c), jnp.exp(g).transpose(0, 2, 1))
    return y.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("form", ["xla", True], ids=["xla", "interpret"])
@pytest.mark.parametrize("S", [16, 32, 80, 37],
                         ids=["1chunk", "2chunks", "5chunks", "ragged"])
def test_scan_equals_the_recurrence_forward_and_backward(S, form):
    """``harmony_ssd_fwd`` / ``_bwd`` (interpreted) and the XLA form against
    the time-step recurrence: the output under one cotangent, and the
    cotangents of all four operands under two different ones."""
    args = _scan_case(S, S)
    sides = [jax.jit(jax.value_and_grad(
        lambda x, b, c, g, w, f=f: (f(x, b, c, g) * w).sum(), (0, 1, 2, 3)))
        for f in (_by_position, functools.partial(
            ssd.ssd_scan, chunk=16, interpret=form))]
    for seed in (0, 1):
        w = jnp.asarray(np.random.default_rng(seed).normal(
            size=args[0].shape), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want, got = (side(*args, w) for side in sides)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            _close(a, b, 1e-5)


def test_scan_refuses_shapes_it_cannot_group():
    x, b, c, g = _scan_case(16, 0)
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd.ssd_scan(x[:, :3], b, c, g[:, :3])      # 3 heads over 2 groups
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd.ssd_scan(x, b, c[:, :1], g)


def test_scan_kernels_lower_for_the_tpu_and_note_their_plan(monkeypatch):
    from harmony_tpu.runtime import progcache
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    x, b, c, g = (jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, 16, 512, 64), jnp.bfloat16), ((1, 1, 512, 128), jnp.bfloat16),
        ((1, 1, 512, 128), jnp.bfloat16), ((1, 16, 512), jnp.float32)))
    fn = jax.grad(lambda *a: ssd.ssd_scan(*a).astype(jnp.float32).sum(),
                  (0, 1, 2, 3))
    text = jax.jit(fn).trace(x, b, c, g).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "harmony_ssd_fwd" in text and "harmony_ssd_bwd" in text
    rows = {r["kernel"]: r for plans in progcache.kernel_plans().values()
            for r in plans if r["kernel"].startswith("harmony_ssd")}
    assert set(rows) == {"harmony_ssd_fwd", "harmony_ssd_bwd"}
    assert rows["harmony_ssd_fwd"]["block_q"] == 128
    assert rows["harmony_ssd_fwd"]["grid_steps"] == 16 * 4


# -- the expert layer: chunked, not gated, in a latent -----------------------

def test_chunked_ungated_latent_experts_equal_the_references(monkeypatch):
    """The held slots in chunks of a static capacity (row tiles of 8 so that
    a CPU test is chunked), through the hand-written backward of experts
    that are not gated: output and every gradient against the reference's
    loop over the held experts."""
    from harmony_tpu.ops import sum_rows

    monkeypatch.setattr(moe_mod, "_ROW_TILE", 8)
    monkeypatch.setattr(sum_rows, "_TB", (8,))
    app = {**APP, "moe_experts": 32, "moe_experts_held": 4}
    cfg = _config(app).dropless_cfg
    assert moe_mod.chunk_plan(32 * 4, 4, 32) == (32, 4)
    params = moe_mod.init_dropless_params(jax.random.PRNGKey(1), cfg)
    params["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (32,))
    assert "wg" not in params and "shared_wg" not in params
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 64), jnp.float32)
    ref = _layer_as_reference({"ln": jnp.ones((64,)), "moe": params})

    def program(p, x):
        out, stats = moe_mod.moe_ffn_dropless(p, x, cfg, seqs=2)
        return (out ** 2).sum() + stats["seq_lb"], out

    def reference(p, x):
        out, lb, _ = REF._experts(x.reshape(2, 16, 64), p, REF._Static(app),
                                  None)
        return (out ** 2).sum() + lb, out.reshape(32, 64)

    with jax.default_matmul_precision("highest"):
        (_, out), (gp, gx) = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(params, x)
        (_, want), (wp, wx) = jax.jit(jax.value_and_grad(
            reference, (0, 1), has_aux=True))(ref, x)
    _close(out, want)
    _close(gx, wx)
    got = _layer_as_reference({"ln": ref["g"], "moe": gp})
    for name in ("router", "w1", "w2", "v1", "v2", "down", "up"):
        _close(got[name], wp[name])


# -- the shares add up to the uncut layer ------------------------------------

#: the tie test's deployment: 4 chips share each layer 4-way — experts 4-way
#: (4 of 16 a chip), state-space heads (2 of 8, with 1 of 4 groups), query
#: heads (2 of 8, each K/V head on 2 chips) and the shared MLP's columns (12
#: of 48) 4-way
WHOLE = {**APP, "ssd_heads": 8, "ssd_groups": 4, "n_heads": 8,
         "n_kv_heads": 2, "moe_experts_held": 16, "moe_shared_d_ff": 48}
SHARES = 4
SHARE = {**WHOLE, "ssd_heads": 2, "ssd_groups": 1, "n_heads": 2,
         "n_kv_heads": 1, "moe_experts_held": 4, "moe_shared_d_ff": 12}


def _cols(w, widths, share):
    """Share ``share`` of ``SHARES`` of each column block of ``w`` (the
    blocks' widths in ``widths``)."""
    out, at = [], 0
    for n in widths:
        part = n // SHARES
        out.append(w[..., at + share * part:at + (share + 1) * part])
        at += n
    return jnp.concatenate(out, axis=-1)


def _share_of(letter, layer, s):
    """Chip ``s``'s share of the uncut reference layer ``layer``, as the
    program's parameters."""
    if letter == "M":
        (inner, conv, _), _ = REF.widths(WHOLE)
        gn = conv - inner
        in_blocks = (inner, inner, gn // 2, gn // 2, WHOLE["ssd_heads"])
        conv_blocks = in_blocks[1:4]
        rows = inner // SHARES
        return {"ln": layer["g"], "ssd": {
            "w_in": _cols(layer["w_in"], in_blocks, s),
            "conv": _cols(layer["taps"], conv_blocks, s),
            "conv_b": _cols(layer["b_c"], conv_blocks, s),
            **{name: _cols(layer[name], (WHOLE["ssd_heads"],), s)
               for name in ("a_log", "dt_bias", "skip")},
            "o_norm": _cols(layer["g_y"], (inner,), s),
            "w_out": layer["w_out"][s * rows:(s + 1) * rows]}}
    if letter == "*":
        _, (wq, wk, wv) = REF.widths(WHOLE)
        q, k, v = jnp.split(layer["wqkv"], (wq, wq + wk), axis=-1)
        kv = s // (SHARES // WHOLE["n_kv_heads"])  # the K/V head chip s reads
        hd = WHOLE["mha_head_dim"]
        rows = wq // SHARES
        return {"ln": layer["g"],
                "wqkv": jnp.concatenate(
                    [_cols(q, (wq,), s), k[:, kv * hd:(kv + 1) * hd],
                     v[:, kv * hd:(kv + 1) * hd]], axis=-1),
                "wo": layer["wo"][s * rows:(s + 1) * rows]}
    # experts: chip s holds experts [4 s, 4 s + 4) — the program holds the
    # FIRST experts_held, so the router's columns (and the bias) are turned
    # until its own come first; every chip holds the router, the bias and
    # both latent projections whole
    E, held = WHOLE["moe_experts"], SHARE["moe_experts_held"]
    turn = (jnp.arange(E) + s * held) % E
    fs = WHOLE["moe_shared_d_ff"] // SHARES
    return {"ln": layer["g"], "moe": {
        "router": layer["router"][:, turn], "bias": layer["bias"][turn],
        "wu": layer["w1"][s * held:(s + 1) * held],
        "wd": layer["w2"][s * held:(s + 1) * held],
        "shared_wu": _cols(layer["v1"], (WHOLE["moe_shared_d_ff"],), s),
        "shared_wd": layer["v2"][s * fs:(s + 1) * fs],
        "latent_down": layer["down"], "latent_up": layer["up"]}}


@pytest.mark.parametrize("letter", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_layer(letter):
    """The tie between the chip's share and the model: what every chip of
    the deployment adds to the residual stream — each its own heads, experts
    and columns through the PROGRAM's layer, the router, the latent
    projections and the norms computed alike on each and counted once — sums
    to what the REFERENCE's uncut layer adds."""
    whole = REF._Static(WHOLE)
    index = WHOLE["layer_pattern"].index(letter)
    layer = REF.init_params(WHOLE, 3)["layers"][index]
    if letter == "E":  # a selection bias that is not all zeros
        layer["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9),
                                                 layer["bias"].shape)
    lm = TransformerLM(_config(SHARE))
    kind = lm.config.layer_kinds()[index]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 80, 64), jnp.float32)
    share = jax.jit(lambda p: lm._layer(x, p, kind)[0] - x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: REF.sublayer(x, p, whole, letter)[0] - x)(
            layer)
        parts = [share(_share_of(letter, layer, s)) for s in range(SHARES)]
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    assert float(jnp.abs(parts[0] - want).max()) > 0.1 * float(
        jnp.abs(want).max())            # one share is not the layer
    _close(sum(parts), want, 5e-5)


# -- what the configuration refuses ------------------------------------------

@pytest.mark.parametrize("change, match", [
    ({"layer_pattern": "ME-ME"}, "layer_pattern"),        # an unknown letter
    ({"layer_pattern": "ME*M"}, "layer_pattern"),         # 4 letters, 5 layers
    ({"ssd_groups": 3}, "ssd_groups must divide"),
    ({"ssd_state": 0}, "'M' layer needs"),
    ({"layer_pattern": "", "n_layers": 5}, "linear_heads|layer_pattern|ssd_"),
    ({"layer_pattern": "E*E*E"}, "belong to"),
    ({"moe_top_k": 0, "moe_experts": 0}, "layer_pattern|dropless"),
    ({"pos": "learned"}, "layer_pattern"),
    ({"linear_layers": [0], "linear_heads": 2, "linear_head_dim": 8},
     "layer_pattern"),
    ({"moe_shared_experts": 2}, "moe_shared_d_ff"),
    ({"moe_act": "gelu"}, "moe_act"),
    ({"moe_gated": True, "ffn": "swiglu"}, "relu2"),
])
def test_the_configuration_refuses_what_it_cannot_run(change, match):
    with pytest.raises(ValueError, match=match):
        _config({**APP, **change})


#: a latent block's fields: since PR 54 ``moe_shared_d_ff`` also stands in
#: the expert blocks of an ``attn_kind="mha"`` model (a shared expert's held
#: columns beside a leading dense layer), and stays refused beside ``mla``
MLA = dict(attn_kind="mla", kv_lora_rank=8, qk_nope_head_dim=8,
           qk_rope_head_dim=8, v_head_dim=8, pos="rope")


@pytest.mark.parametrize("field, value, beside", [
    ("moe_gated", False, {}), ("moe_latent", 32, {}),
    ("moe_shared_d_ff", 24, MLA), ("ssd_heads", 4, {}),
    ("ssd_chunk", 32, {})])
def test_the_new_fields_belong_to_a_pattern(field, value, beside):
    base = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=32,
                ffn="swiglu", moe_experts=8, moe_top_k=2, moe_every=1,
                moe_shared_experts=1, moe_score="sigmoid", moe_seq_aux=True,
                **beside)
    TransformerConfig(**base)
    with pytest.raises(ValueError, match="layer_pattern"):
        TransformerConfig(**{**base, field: value})


def test_layer_kinds_and_moe_layers_answer_from_the_pattern():
    cfg = _config(CONF["job"]["app_params"])
    assert cfg.layer_pattern == CONF["hybrid_override_pattern"][:11]
    assert cfg.layer_kinds() == ("ssd", "moe", "ssd", "moe", "ssd", "moe",
                                 "ssd", "attn", "moe", "ssd", "moe")
    assert cfg.moe_layers() == (1, 3, 5, 8, 10)
    with pytest.raises(ValueError, match="GPT-2-era block .* layer_pattern"):
        cfg.require_classic_block("make_sp_train_step")


# -- the job path, and what an operator sees ---------------------------------

JOB_APP = {**APP, "optimizer": "adam", "step_size": 1e-3, "beta2": 0.95,
           "seed": 11}


def test_a_tiny_tenant_through_the_jobserver_equals_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    TransformerTrainer and JSON app_params: the first four steps' losses are
    the reference's replay (float32 both sides, the table's Adam against the
    formula), and STATUS shows the three kinds of layer, the state-space
    gauges and the experts' counters under the LAYERS' indices."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from perf.generators import random_tokens

    data_args = {"seq_len": 81, "vocab_size": 512, "num_seqs": 2, "seed": 11}
    server = JobServer(num_executors=1)
    server.start()
    try:
        cfg = JobConfig(
            job_id="nemotron-tiny", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=6, num_mini_batches=1,
                                 comm_probe_period=0, app_params=JOB_APP),
            num_workers=1,
            user={"data_fn": "perf.generators.random_tokens:make",
                  "data_args": data_args})
        result = server.submit(cfg).result(timeout=600)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    losses = next(iter(result["workers"].values()))["losses"]
    want = REF.replay(JOB_APP, (random_tokens.make(**data_args),), 2, 4,
                      seed=11, logits=False)
    assert np.allclose(losses[:4], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    row = status["tenants"]["nemotron-tiny"]
    assert row["layer_kinds"] == {"ssd": 2, "moe": 2, "attn": 1}
    assert 0.0 < row["ssd"]["decay_mean"] < 1.0 and row["ssd"]["dt_mean"] > 0
    assert row["kda"] is None
    fams = parse_exposition(get_registry().expose())
    mine = lambda name, key: {l[key] for _, l, _ in fams[name]["samples"]
                              if l["job"] == "nemotron-tiny"}
    assert mine("harmony_ssd_decay_mean", "layer") == {"0", "3"}
    assert mine("harmony_ssd_dt_mean", "layer") == {"0", "3"}
    assert mine("harmony_moe_expert_tokens_total", "layer") == {"1", "4"}
