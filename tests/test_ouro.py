"""Ouro-2.6B's block and step on the normal path (PR 57): a stack of layers
run ``loop_steps`` times over ONE set of weights, four norms a block, the
final norm inside the loop, and an exit after every pass (readout + a sigmoid
gate) joined into one loss by the gate's exit distribution. ``TransformerLM``
with the architecture fields against the plain reference the benchmark ships
(``perf/reference/ouro-2.6b.py``: float32, an explicit boolean mask, a Python
loop over the passes, no kernel).

Small, float32, seeded — the configuration's ``rehearse`` preset: d 64, 4
heads of 16, 2 layers run 4 times, a SwiGLU of 96, 48 positions. Both sides
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
from harmony_tpu.models import transformer as T  # noqa: E402
from perf.generators import random_tokens  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "ouro-2.6b")
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs", "ouro-2.6b.json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "vocab_size": 96, "step_size": 1e-3}
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}
LOOPED = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=96,
              max_seq=48, pos="rope", ffn="swiglu", tie_embeddings=False)

# the configuration's own checks (perf/tests is run by hand and does not
# count): collected here too, from the same file — but for the rehearsal,
# which runs the whole harness in a child (the jobserver test below covers
# the job path)
_spec = importlib.util.spec_from_file_location(
    "perf_test_ouro", os.path.join(ROOT, "perf", "tests", "test_ouro.py"))
_perf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_perf)
globals().update({name: obj for name, obj in vars(_perf).items()
                  if name.startswith("test_")
                  and name != "test_rehearsal_runs_to_a_correct_line"})


def test_the_cell_and_its_metrics_are_in_the_benchmark(monkeypatch):  # noqa: F811
    """``perf/tests/test_ouro.py``'s check counts THIRTEEN cells and holds its
    own the last of the rate's list, and a PR may not edit a file the
    benchmark has; a later ``model_config`` PR appends a cell (PR 61 did). The
    check runs on the benchmark as it stood when this cell was the newest:
    the cells appended since taken off every list."""
    import copy

    bench = copy.deepcopy(_perf.BENCH)
    names = [w["name"] for w in bench["workloads"]]
    later = set(names[names.index(_perf.CELL) + 1:])
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in later]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in later]
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


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    scale = np.abs(b).max() or 1.0
    assert np.abs(a - b).max() <= rtol * scale, np.abs(a - b).max() / scale


# -- the fields ----------------------------------------------------------------

@pytest.mark.parametrize("fields,match", [
    ({"loop_steps": 0}, "counts the passes"),
    ({"exit_entropy_weight": -0.1, "loop_steps": 2, "exit_gate": True},
     "weighs an entropy"),
    ({"exit_gate": True}, "set loop_steps > 1"),
    ({"loop_steps": 2, "exit_entropy_weight": 0.05}, "set exit_gate"),
    ({"loop_steps": 2, "pos": "learned"}, "position table would be added once"),
    ({"sandwich_norm": True, "attn_kind": "mla", "kv_lora_rank": 8,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8},
     "dense attn_kind='mha'"),
    ({"loop_steps": 2, "moe_experts": 4}, "routes several times a step"),
    ({"loop_steps": 2, "moe_experts": 4, "moe_top_k": 2},
     "routes several times a step"),
    ({"loop_steps": 2, "window": 8, "window_layers": (0,)},
     "two kinds of a windowed model"),
    ({"sandwich_norm": True, "n_kv_heads": 2, "cca": True},
     "carry no ln\\*_post leaves"),
    ({"loop_steps": 2, "linear_layers": (0,), "linear_heads": 2,
      "linear_head_dim": 8, "short_conv": 2}, "statistics are one a layer"),
    ({"loop_steps": 2, "objective": "block_diffusion", "diffusion_block": 4,
      "mask_token": 0}, "two streams have no exit"),
])
def test_fields_that_describe_no_model_are_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**LOOPED, **fields})


def test_a_layer_pattern_model_is_refused_too():
    with pytest.raises(ValueError, match="loop_steps > 1 / sandwich_norm"):
        TransformerConfig(vocab_size=96, d_model=64, n_heads=4, n_layers=2,
                          d_ff=96, pos="rope", ffn="swiglu", loop_steps=2,
                          layer_pattern="**")


@pytest.mark.parametrize("make", ["make_sp_train_step", "make_pp_train_step",
                                  "make_generate_fn"])
@pytest.mark.parametrize("field,value", [("loop_steps", 2),
                                         ("sandwich_norm", True)])
def test_the_side_steps_and_the_decode_path_refuse_the_fields(make, field,
                                                              value):
    """``require_classic_block``: each new field alone, under the GPT-2-era
    block's other defaults (a stand-in config — the real ones would be
    refused for their rotary first)."""
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=2)
    cfg.require_classic_block(make)
    object.__setattr__(cfg, field, value)
    with pytest.raises(ValueError, match=f"GPT-2-era block .* {field}"):
        cfg.require_classic_block(make)


def test_the_defaults_are_the_parents_program():
    """``loop_steps=1`` with the other fields off traces the program of a
    configuration that never heard of them, equation for equation; and a
    looped model WITHOUT the gate reads out once — the last pass alone."""
    plain = TransformerLM(TransformerConfig(**LOOPED))
    named = TransformerLM(TransformerConfig(
        **LOOPED, loop_steps=1, sandwich_norm=False, exit_gate=False,
        exit_entropy_weight=0.0))
    params = plain.init(jax.random.PRNGKey(0))
    toks = _tokens()
    fn = lambda lm: str(jax.make_jaxpr(jax.value_and_grad(
        lm.loss_and_metrics, has_aux=True))(params, toks))
    assert fn(plain) == fn(named)
    twice = TransformerLM(TransformerConfig(**LOOPED, loop_steps=2))
    jaxpr = fn(twice)
    assert jaxpr != fn(plain)
    assert jaxpr.count("log_softmax") == fn(plain).count("log_softmax")
    loss, metrics = twice.loss_and_metrics(params, toks)
    assert metrics == {}
    logits = twice.apply(params, toks[:, :-1])
    _close(loss, T._next_token_ce(logits, toks[:, 1:]))


# -- program against reference ----------------------------------------------------

def test_the_seeded_parameters_are_the_references():
    _, params, _, ref = _both()
    got = REF.from_program(params)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(ref["exit_w"]).max()) > 0  # a gate that reads its rows


def test_init_traced_abstractly_has_the_same_leaves():
    lm, params, _, _ = _both()
    host = jax.eval_shape(lm.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.structure(host) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_every_exits_logits_and_gate_equal_the_reference():
    lm, params, app, ref = _both()
    toks = _tokens()
    logits, lam = lm.exits(params, toks[:, :-1])
    want, want_lam = REF.forward(ref, toks[:, :-1], app)
    assert logits.shape == (4, 2, 48, 96) and lam.shape == (4, 2, 48)
    _close(logits, want)
    _close(lam, want_lam)
    _close(lm.apply(params, toks[:, :-1]), want[-1])  # inference: the last pass
    p = T.exit_distribution(lam)
    _close(p, REF.exit_distribution(want_lam))
    _close(p.sum(axis=0), np.ones((2, 48)))
    # each pass moves the rows: no two exits agree
    assert float(jnp.abs(want[1:] - want[:-1]).max()) > 0.1


@pytest.mark.parametrize("remat", [False, True])
def test_loss_metrics_and_every_gradient_equal_the_reference(remat):
    lm, params, app, ref = _both()
    lm = TransformerLM(dataclasses.replace(lm.config, remat=remat))
    toks = _tokens()
    (loss, metrics), g = jax.value_and_grad(lm.loss_and_metrics,
                                            has_aux=True)(params, toks)
    (want, (logits, lam)), want_g = jax.value_and_grad(
        REF.loss_and_exits, has_aux=True)(ref, toks, app)
    _close(loss, want)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(jnp.stack(logits)),
                               toks[None, :, 1:, None], axis=-1)[..., 0]
    p = REF.exit_distribution(lam)
    _close(metrics["ce"], nll[-1].mean())
    _close(metrics["ce_by_exit"], nll.mean(axis=(1, 2)))
    _close(metrics["exit_mass"], p.sum(axis=(1, 2)))
    assert float(metrics["exit_mass"].sum()) == pytest.approx(2 * 48, rel=1e-5)
    _close(metrics["exit_entropy"], -(p * jnp.log(p)).sum(axis=0).mean())
    got_g = REF.from_program(g)
    assert jax.tree.structure(got_g) == jax.tree.structure(want_g)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        assert float(jnp.abs(b).max()) > 0, path
        _close(a, b, 1e-4)


def test_the_shared_weights_gradient_is_the_sum_over_untied_passes():
    """THE TIE: the reference with a copy of the layers a pass (``passes``)
    has one gradient a copy; their sum is the gradient of the shared layers —
    the reference's own, and the program's."""
    lm, params, app, ref = _both()
    toks = _tokens()
    untied = {**{k: v for k, v in ref.items() if k != "layers"},
              "passes": [ref["layers"]] * app["loop_steps"]}
    g_untied = jax.grad(REF.loss_fn)(untied, toks, app)
    summed = jax.tree.map(lambda *gs: sum(gs), *g_untied["passes"])
    tied = jax.grad(REF.loss_fn)(ref, toks, app)
    program = REF.from_program(jax.grad(lm.loss)(params, toks))
    for a, b, c in zip(jax.tree.leaves(summed), jax.tree.leaves(tied["layers"]),
                       jax.tree.leaves(program["layers"])):
        _close(a, b, 1e-5)
        _close(c, a, 1e-4)
    # and no one pass carries it: the last pass's copy alone is far off
    last = jax.tree.leaves(g_untied["passes"][-1])
    worst = max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
                for a, b in zip(last, jax.tree.leaves(tied["layers"])))
    assert worst > 0.2


def test_the_entropy_term_and_the_guard_at_zero():
    p = jnp.asarray([[0.5, 1.0], [0.5, 0.0]])
    h, g = jax.value_and_grad(lambda q: T.exit_entropy(q).sum())(p)
    _close(h, np.log(2.0))
    assert np.isfinite(np.asarray(g)).all()
    lam = jnp.asarray([[1.0], [0.3], [0.7]])  # everything leaves at pass 1
    _close(T.exit_distribution(lam), [[1.0], [0.0], [0.0]])
    lm, params, app, ref = _both()
    toks = _tokens()
    with_term = lm.loss(params, toks)
    without = TransformerLM(dataclasses.replace(
        lm.config, exit_entropy_weight=0.0)).loss_and_metrics(params, toks)
    _close(with_term, without[0] - 0.05 * without[1]["exit_entropy"])


# -- the check and its ablations --------------------------------------------------

@functools.lru_cache(maxsize=None)
def _report(dtype="float32", ablations=REF.LOGIT_ABLATIONS):
    return REF.check_logits({**APP, "dtype": "float32"}, np.asarray(_tokens()),
                            5, program_app={**APP, "dtype": dtype},
                            ablations=ablations)


def test_every_ablation_is_told_apart_and_the_program_is_not():
    report = _report()
    assert report["ok"], report
    assert set(report["detected"]) == set(REF.LOGIT_ABLATIONS)
    assert all(report["detected"].values()) and all(report["held"].values())
    assert len(report["program"]["exits"]) == 4
    grads = report["program"]["gradients"]
    assert set(grads["by_leaf"]) == {
        "embed", "head", "ln_f", "exit_w", "exit_b", "g1", "g2", "g3", "g4",
        "wq", "wk", "wv", "wo", "wg", "wu", "wd"}
    assert grads["worst"] < 1e-3 and grads["scalar"] < 1e-3
    # the control is told from the reference on every leaf
    assert min(row[1] for row in grads["by_leaf"].values()) > 0.02
    with pytest.raises(ValueError, match="the control"):
        REF.check_logits({**APP, "dtype": "float32"}, np.asarray(_tokens()), 5,
                         ablations=("one_pass_less",))


#: which of the check's four readings each ablation breaks at this size
BREAKS = {
    "fp8_operands": {"logits", "gate", "loss"},
    "one_pass_less": {"logits", "loss", "gradient"},
    "norm_not_fed": {"logits", "gate", "loss", "gradient"},
    "no_post_norm": {"logits", "gate", "loss", "gradient"},
    "post_norm_after_add": {"logits", "gate", "loss", "gradient"},
    "uniform_exit": {"loss", "gradient"},
    "last_gate_counts": {"loss", "gradient"},
    "no_entropy": {"loss", "gradient"},
    "exit_outside_gradient": {"gradient"},
    "last_pass_gradient": {"gradient"},
}


@pytest.mark.parametrize("ablate", REF.LOGIT_ABLATIONS)
def test_each_ablation_breaks_the_readings_it_should(ablate):
    """Every ablation of the issue, read as if it were the program: the ones
    that change the forward break the logits; the loss's three break the loss
    and leave every logit alone; the two of the backward alone leave every
    VALUE alone and break the gradient."""
    report = _report()
    row = report["ablations"][ablate]
    held = REF._held(row, report["limits"])
    broken = {k for k, ok in held.items() if not ok}
    assert broken >= BREAKS[ablate], (broken, row)
    if "logits" not in BREAKS[ablate]:
        assert all(e["rms"] == 0.0 for e in row["exits"])
        assert row["gate"]["lam"] == 0.0
    if BREAKS[ablate] == {"gradient"}:
        assert row["loss"] == 0.0
    if ablate == "exit_outside_gradient":  # the gate learns nothing
        assert row["gradients"]["scalar"] == pytest.approx(1.0)
    if ablate == "one_pass_less":  # exits 1-3 are the reference's
        assert [e["rms"] > 0.1 for e in row["exits"]] == [False] * 3 + [True]


def _p_outside_the_gradient(monkeypatch):
    sound = T.exit_distribution
    monkeypatch.setattr(T, "exit_distribution",
                        lambda lam: jax.lax.stop_gradient(sound(lam)))
    return {"exit_w", "exit_b"}


def _last_pass_gradient(monkeypatch):
    """The layers' uses before the last pass outside the gradient."""
    sound_block, sound_trunk = TransformerLM._block, TransformerLM._trunk
    calls = {"n": 0}

    def trunk(self, *args, **kwargs):
        calls["n"] = 0
        return sound_trunk(self, *args, **kwargs)

    def block(self, x, layer, *args, **kwargs):
        calls["n"] += 1
        cfg = self.config
        if calls["n"] <= cfg.n_layers * (cfg.loop_steps - 1):
            layer = jax.lax.stop_gradient(layer)
        return sound_block(self, x, layer, *args, **kwargs)

    monkeypatch.setattr(TransformerLM, "_trunk", trunk)
    monkeypatch.setattr(TransformerLM, "_block", block)
    return {"g1", "g2", "g3", "g4", "wq", "wk", "wv", "wo", "wg", "wu", "wd"}


@pytest.mark.parametrize("plant", [_p_outside_the_gradient,
                                   _last_pass_gradient],
                         ids=["p-outside-the-gradient",
                              "layers-gradient-of-the-last-pass-alone"])
def test_a_fault_in_the_backward_alone_is_refused(monkeypatch, plant):
    """A PROGRAM whose every value is the reference's and whose gradient is
    wrong: the logits, the gates and the first loss hold, and the gradient's
    limit refuses it, by the leaves the fault reaches. The losses alone see
    such a fault only through Adam's first update, which keeps a gradient's
    sign."""
    leaves = plant(monkeypatch)
    report = REF.check_logits({**APP, "dtype": "float32"},
                              np.asarray(_tokens()), 5)
    assert not report["ok"]
    assert report["held"] == {"logits": True, "gate": True, "loss": True,
                              "gradient": False}
    grads = report["program"]["gradients"]
    broken = {leaf for leaf, row in grads["by_leaf"].items()
              if row[2 if leaf != "exit_b" else 0] > 1e-3}
    assert broken >= leaves, (broken, leaves)
    assert grads["worst_leaf"] in leaves


def test_bfloat16_where_the_file_says_float32_is_refused():
    """The precision below the one stated fails the stated one's limits, at
    every exit."""
    report = _report("bfloat16", REF.RUN_ABLATIONS)
    limits = REF.LIMITS["float32"]
    assert not report["ok"] and report["dtype"] == "float32"
    for e in report["program"]["exits"]:
        assert e["q90"] > limits["q90"] and e["rms"] > limits["rms"]
    assert report["program"]["gradients"]["worst"] > 100 * limits["gradient"]
    assert not report["held"]["logits"] and not report["held"]["gradient"]


# -- the job path -------------------------------------------------------------------

JOB_APP = {**APP, "seed": 11}
DATA_ARGS = {"num_seqs": 2, "seq_len": 49, "vocab_size": 96, "seed": 7}


def test_five_steps_through_the_jobserver_equal_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    ``TransformerTrainer`` and JSON app_params: the five steps' losses are the
    reference's replay (float32 both sides, the table's Adam with both its
    moments against the formula); the gauge says the passes and the counters
    where the exit mass lies."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.parallel import DevicePool

    app = json.loads(json.dumps(JOB_APP))
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="ouro-tiny", app_type="dolphin",
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
    row = status["tenants"]["ouro-tiny"]
    assert row["table_layout"]["tile_exact"] == 1
    assert row["layer_kinds"] == {"mha": 2}
    fams = parse_exposition(get_registry().expose())
    mine = lambda name: {tuple(sorted(l.items())): v for _, l, v in
                         fams[name]["samples"] if l["job"] == "ouro-tiny"}
    assert list(mine("harmony_model_loop_steps").values()) == [4.0]
    positions = sum(mine("harmony_loop_exit_positions_total").values())
    assert positions == pytest.approx(5 * 2 * 48, rel=1e-5)
    mass = mine("harmony_loop_exit_mass_total")
    assert {dict(k)["step"] for k in mass} == {"1", "2", "3", "4"}
    assert sum(mass.values()) == pytest.approx(positions, rel=1e-6)
    assert min(mass.values()) > 0.02 * positions  # a fresh gate: every pass
    assert len(mine("harmony_loop_exit_ce")) == 4


# -- the readout op under T calls and weights that carry a gradient -------------------

def test_the_readout_op_serves_every_exit_under_differentiated_row_weights():
    """At a shape the op's plan engages (128 wide, 8,192 columns, 2,048 rows:
    the kernels interpreted on the CPU), a looped model's loss calls it once
    an exit with row weights ``p(t) / N`` that are themselves differentiated:
    value and every gradient against the reference's plain logits."""
    app = {**APP, "d_model": 128, "n_heads": 2, "n_layers": 1, "d_ff": 128,
           "vocab_size": 8192, "max_seq": 1024, "loop_steps": 2}
    lm = TransformerLM(_config(app))
    toks = _tokens(3, 2, app)
    assert lm._readout_tiles(jnp.zeros((2, 1024, 128))) is not None
    params = lm.init(jax.random.PRNGKey(2))
    traced = jax.jit(jax.value_and_grad(lm.loss)).trace(params, toks)
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    assert {k: v for k, v in calls.items() if "readout" in k} == {
        "harmony_readout_fwd": 2, "harmony_readout_bwd_dx": 2,
        "harmony_readout_bwd_dw": 2}
    loss, g = traced.lower().compile()(params, toks)
    static, ref = REF._Static(app), REF.init_params(app, 2)
    want, want_g = jax.value_and_grad(REF.loss_fn)(ref, toks, static)
    _close(loss, want, 1e-4)
    got_g = REF.from_program(g)
    # the op rounds its operands to bfloat16 as the MXU does (its numerical
    # contract): held leaf by leaf in norm, the gate's leaves too
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        err = float(jnp.linalg.norm((a - b).ravel())
                    / jnp.linalg.norm(b.ravel()))
        assert err < 0.03, (path, err)


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


def test_the_step_holds_each_kernel_once_an_application_and_one_traced_body(
        monkeypatch):
    """Traced for a TPU under ``remat``: the flash forward and the fused
    backward are in the loss-and-gradient program ONCE a block APPLICATION
    (layers x passes), the readout's three kernels once an exit; the block's
    Python body is traced ONCE for all of them; ``remat_saved`` counts
    applications; the lowered step's locations name the exit's scope."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    bodies = []
    sound = TransformerLM._block
    monkeypatch.setattr(
        TransformerLM, "_block",
        lambda self, *a, **k: bodies.append(1) or sound(self, *a, **k))
    app = {**APP, "d_model": 256, "n_heads": 2, "n_layers": 3, "loop_steps": 4,
           "vocab_size": 8192, "max_seq": 2048, "dtype": jnp.bfloat16,
           "remat": True}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, 2049), jnp.int32)
    with trace_span("job.build_step", job_id="plan-ouro"):
        traced = jax.jit(jax.grad(lm.loss)).trace(params, toks)
    assert len(bodies) == 1
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    assert calls == {
        "harmony_flash_fwd": 12, "harmony_flash_bwd": 12,
        "harmony_readout_fwd": 4, "harmony_readout_bwd_dx": 4,
        "harmony_readout_bwd_dw": 4,
        # the turn of q and of k (PR 58: 128-wide heads), forward, again
        # under ``remat`` and backward, an application
        "harmony_rotary": 12 * 2 * 3}
    rows = {r["kernel"] for r in progcache.kernel_plans()["plan-ouro"]}
    assert {"harmony_flash_fwd", "harmony_flash_bwd",
            "harmony_readout_fwd", "harmony_rotary"} <= rows
    kept = {r["name"]: r for r in progcache.remat_saved()["plan-ouro"]}
    assert kept["flash_out"]["arrays"] == kept["flash_lse"]["arrays"] == 12
    assert kept["flash_out"]["bytes"] == 12 * 2048 * 256 * 2
    text = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "exit.gate" in text and "blk2" in text and "blk3" not in text
