"""The OLMoE-shaped block on the normal path (PR 25): rotary positions,
QK-norm, gated-SiLU experts routed top-k WITHOUT drops over ragged grouped
matmuls, an untied head, the two auxiliary losses — ``TransformerLM`` with the
architecture fields against the plain reference the benchmark ships
(``perf/reference/olmoe-1b-7b.py``: float32, a loop over the held experts
with a dense mask, no sort, no kernel).

Small, float32, seeded: d 64, 4 heads of 16, 8 experts of width 32, top-2,
sequence 32. Tolerances: both sides are float32 on the CPU and differ only
in the order of sums (sorted ragged groups against a dense masked loop), so
1e-5 relative holds everywhere — three decades under the smallest effect
of leaving out a piece of the mathematics (the router z-loss: 7e-3 of the
loss, ``test_tolerance_tells_broken_arithmetic_apart``).
"""
from __future__ import annotations

import hashlib
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
from harmony_tpu.models.transformer import rope  # noqa: E402
from harmony_tpu.ops.grouped_matmul import grouped_matmul, tile_plan  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "olmoe-1b-7b")
RTOL = 1e-5
APP = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=32,
           max_seq=32, pos="rope", rope_theta=10000.0, qk_norm=True,
           ffn="swiglu", tie_embeddings=False, norm_eps=1e-5, moe_experts=8,
           moe_top_k=2, moe_every=1, moe_aux_weight=0.01, moe_z_weight=0.001)
HELD = [None, 3]  # every expert here; experts 0..2 of the 8


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


# -- the block against the reference ---------------------------------------

@pytest.mark.parametrize("held", HELD)
def test_logits_match_reference(held):
    lm, params, app, ref_params = _both(held)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks), REF.forward(ref_params, toks, app)[0])


@pytest.mark.parametrize("held", HELD)
def test_loss_terms_match_reference(held):
    """Cross-entropy, load-balance and router-z each on its own."""
    lm, params, app, ref_params = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        loss, m = lm.loss_and_metrics(params, toks)
        ce, lb, z = REF.loss_terms(ref_params, toks, app)
    for got, want in ((m["ce"], ce), (m["aux_lb"], lb), (m["aux_z"], z),
                      (loss, ce + 0.01 * lb + 0.001 * z)):
        _close(got, want)
    # every token chose two experts in each of the two layers, held or not
    tokens = np.asarray(m["moe_expert_tokens"])
    assert tokens.shape == (2, 8)
    assert (tokens.sum(axis=1) == 2 * toks[:, :-1].size).all()


def test_a_token_with_no_held_expert_passes_through():
    """With experts 0..2 of 8 held, some token's top-2 holds none of them:
    the expert layer adds nothing to it, as in the reference."""
    from harmony_tpu.models.moe import moe_ffn_dropless

    lm, params, app, ref_params = _both(3)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    layer = params["layers"][0]["moe"]
    out, _ = moe_ffn_dropless(layer, x, lm.config.dropless_cfg)
    probs = jax.nn.softmax(x @ layer["router"], axis=-1)
    unheld = np.asarray((jax.lax.top_k(probs, 2)[1] >= 3).all(axis=1))
    assert 0 < unheld.sum() < 64
    assert float(np.abs(np.asarray(out)[unheld]).max()) == 0.0
    assert float(np.abs(np.asarray(out)[~unheld]).min()) >= 0.0
    assert float(np.abs(np.asarray(out)[~unheld]).max()) > 0.0


@pytest.mark.parametrize("held", HELD)
def test_gradients_match_reference(held):
    lm, params, app, ref_params = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lm.loss)(params, toks)
        want = jax.grad(REF.loss_fn)(ref_params, toks, app)
    _close(got["embed"], want["embed"])
    _close(got["head"], want["head"])
    _close(got["ln_f"], want["ln_f"])
    for g, w in zip(got["layers"], want["layers"]):
        _close(g["wqkv"], jnp.concatenate([w["wq"], w["wk"], w["wv"]], axis=1))
        for key in ("wo", "ln1", "ln2", "q_norm", "k_norm"):
            _close(g[key], w[key])
        for key in ("router", "wg", "wu", "wd"):
            _close(g["moe"][key], w[key])


@pytest.mark.parametrize("ablate", REF.ABLATIONS)
def test_tolerance_tells_broken_arithmetic_apart(ablate):
    """Dropping an auxiliary loss, one expert of the k, the QK-norm or the
    non-renormalisation moves the loss by far more than RTOL."""
    _, _, app, ref_params = _both(None)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        whole = float(REF.loss_fn(ref_params, toks, app))
        broken = float(REF.loss_fn(ref_params, toks, app, ablate))
    assert abs(broken - whole) / whole > 100 * RTOL, (ablate, whole, broken)


def test_replay_sees_each_ablation_and_adam_beta2():
    data = (np.asarray(_tokens(seed=3, batch=4)),)
    app = {**APP, "optimizer": "adam", "step_size": 1e-3, "beta2": 0.95}
    full = REF.replay(app, data, 2, 4, seed=0)
    assert len(full) == 4 and full[-1] < full[0]
    assert REF.replay({**app, "beta2": 0.999}, data, 2, 4, seed=0,
                      logits=False)[3] != full[3]
    with pytest.raises(ValueError, match="unknown ablation"):
        REF.replay(app, data, 2, 1, seed=0, ablate="no_such")


# -- rotary and QK-norm, each against a three-line formula --------------------

def test_rope_is_rotate_half():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 16), jnp.float32)
    pos = np.arange(8)[:, None]
    freq = 10000.0 ** (-np.arange(0, 16, 2) / 16)
    ang = np.concatenate([pos * freq, pos * freq], axis=-1)
    xn = np.asarray(x)
    want = xn * np.cos(ang) + np.concatenate(
        [-xn[..., 8:], xn[..., :8]], axis=-1) * np.sin(ang)
    _close(rope(x, 10000.0), want, 1e-6)
    # positions shift with the offset: rope(x, off)[s] = rope at s + off
    _close(rope(x, 10000.0, 3)[:, :, :5], rope(
        jnp.concatenate([jnp.zeros_like(x[:, :, :3]), x], axis=2),
        10000.0)[:, :, 3:8], 1e-6)


def test_qk_norm_is_rmsnorm_over_the_whole_width():
    """Normalising q over d_model (before the head split) is not per-head
    normalisation: the block with qk_norm equals the formula, and differs
    from the block without."""
    app = {**APP, "moe_experts": 0, "moe_top_k": 0, "moe_z_weight": 0.0}
    toks = _tokens()[:, :-1]
    on = TransformerLM(TransformerConfig(**app))
    params = on.init(jax.random.PRNGKey(2))
    params["layers"][0]["q_norm"] = params["layers"][0]["q_norm"] * 1.5
    off_cfg = TransformerConfig(**{**app, "qk_norm": False})
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 64), jnp.float32)
    from harmony_tpu.models.common import rms_norm

    w = params["layers"][0]["q_norm"]
    want = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-5) * w
    _close(rms_norm(x, w, 1e-5), want, 1e-6)
    a = on.apply(params, toks)
    b = TransformerLM(off_cfg).apply(params, toks)
    assert float(jnp.abs(a - b).max()) > 1e-3


# -- the grouped matmul (Pallas interpreter) against a per-expert loop ----------

GROUPS = {
    "ragged_off_the_tile": (128, [10, 37, 50, 11, 20]),
    "empty_groups": (128, [0, 40, 0, 0, 88, 0]),
    "one_group_holds_everything": (128, [0, 0, 128, 0]),
    "rows_past_the_groups": (128, [30, 0, 21]),
    "all_empty": (128, [0, 0, 0]),
    "rows_off_the_tile_and_a_group_across_tiles": (300, [100, 3, 0, 150]),
}


def _loop(x, w, sizes):
    out = jnp.zeros((x.shape[0], w.shape[2]), x.dtype)
    start = 0
    for g, n in enumerate(sizes):
        out = out.at[start:start + n].set(x[start:start + n] @ w[g])
        start += n
    return out


@pytest.mark.parametrize("case", GROUPS)
def test_grouped_matmul_forward_and_both_backward_products(case):
    m, sizes = GROUPS[case]
    rng = np.random.default_rng(len(case))
    x = jnp.asarray(rng.standard_normal((m, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), 64, 32)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((m, 32)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    _close(grouped_matmul(x, w, gs, interpret=True), _loop(x, w, sizes))
    got = jax.grad(lambda x, w: (grouped_matmul(x, w, gs, interpret=True)
                                 * c).sum(), (0, 1))(x, w)
    want = jax.grad(lambda x, w: (_loop(x, w, sizes) * c).sum(), (0, 1))(x, w)
    _close(got[0], want[0])  # dx: rows past the groups get 0
    _close(got[1], want[1])  # dw: an empty group's is 0
    assert float(jnp.abs(got[0][sum(sizes):]).max(initial=0.0)) == 0.0


def test_grouped_matmul_tiles_come_from_the_shape():
    # the cell's shapes, bf16: 512 rows a step against [1024, 1024] of a weight
    assert tuple(tile_plan(65536, 2048, 1024, jnp.bfloat16)) == (512, 1024, 1024)
    assert tuple(tile_plan(65536, 1024, 2048, jnp.bfloat16)) == (512, 1024, 1024)
    # float32 halves a width to stay under the default scoped VMEM
    t = tile_plan(65536, 2048, 1024, jnp.float32)
    assert t.tm == 512 and t.tk * t.tn < 1024 * 1024
    # small and odd shapes are one block as they are; rows pad to sublanes
    assert tuple(tile_plan(100, 64, 32, jnp.float32)) == (104, 64, 32)
    with pytest.raises(ValueError, match="grouped_matmul"):
        grouped_matmul(jnp.zeros((8, 4)), jnp.zeros((2, 5, 3)),
                       jnp.zeros((2,), jnp.int32))


# -- parameters: layouts, the pinned default, refusals --------------------------

def test_init_traced_abstractly_has_inits_layout_for_the_new_block():
    for held in HELD:
        for kw in ({}, {"moe_experts": 0, "moe_top_k": 0, "moe_z_weight": 0.0}):
            app = {**_app(held), **kw}
            if not app["moe_experts"]:
                app.pop("moe_experts_held", None)
            model = TransformerLM(TransformerConfig(**app))
            a, b = model.init(jax.random.PRNGKey(0)), jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
            assert (jax.tree_util.tree_structure(a)
                    == jax.tree_util.tree_structure(b))
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert la.shape == lb.shape and la.dtype == lb.dtype
    params = TransformerLM(TransformerConfig(**_app(3))).init(
        jax.random.PRNGKey(0))
    assert "pos" not in params and params["head"].shape == (64, 96)
    moe = params["layers"][0]["moe"]
    assert moe["router"].shape == (64, 8) and moe["wg"].shape == (3, 64, 32)
    assert moe["wd"].shape == (3, 32, 64)


def test_default_config_is_todays_block_bit_for_bit():
    """The GPT-2-era defaults: parameters, logits and loss pinned (digests
    taken on the commit before the architecture fields existed)."""
    from jax.flatten_util import ravel_pytree

    pins = {(): ("a06c832a97f8cb93", "772cbefc16aec50d", "0x1.236b9e0000000p+2"),
            (("moe_experts", 4),): ("6271ab374fa960be", "397120349eab5be8",
                                    "0x1.25b55a0000000p+2")}
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 97, (2, 17)),
                       jnp.int32)
    for kw, (p_pin, l_pin, loss_pin) in pins.items():
        lm = TransformerLM(TransformerConfig(
            vocab_size=97, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=16, **dict(kw)))
        params = lm.init(jax.random.PRNGKey(3))
        digest = lambda a: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
        assert digest(ravel_pytree(params)[0]) == p_pin
        assert digest(lm.apply(params, toks[:, :-1])) == l_pin
        assert float(lm.loss(params, toks)).hex() == loss_pin


@pytest.mark.parametrize("kw, match", [
    ({"moe_top_k": 9}, "moe_top_k"),
    ({"moe_experts": 0}, "moe_top_k"),
    ({"moe_experts_held": 9}, "moe_experts_held"),
    ({"moe_experts_held": 0}, "moe_experts_held"),
    ({"moe_top_k": 0, "moe_experts_held": 4, "moe_z_weight": 0.0}, "dropless"),
    ({"ffn": "gelu"}, "swiglu"),
    ({"ffn": "relu"}, "unknown ffn"),
    ({"pos": "alibi"}, "unknown pos"),
    ({"d_model": 60, "n_heads": 4}, "odd"),
])
def test_inconsistent_architecture_fields_are_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**APP, **kw})


def test_side_steps_and_decode_refuse_the_new_block():
    from harmony_tpu.models import make_generate_fn
    from harmony_tpu.models.transformer import make_pp_train_step

    lm = TransformerLM(TransformerConfig(**APP))
    with pytest.raises(ValueError, match="GPT-2-era block"):
        make_generate_fn(lm, 4, 4)
    with pytest.raises(ValueError, match="GPT-2-era block"):
        make_pp_train_step(lm, None)


# -- the job path: trainer, vectors out of the step, counters, STATUS -------------

def test_trainer_step_reports_terms_and_expert_tokens():
    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**_app(3), optimizer="adam", step_size=1e-3,
                            beta2=0.95, row_width=256)
    assert tr.hyperparams() == {"lr": 1e-3, "beta2": 0.95}
    assert TransformerTrainer(**APP).hyperparams() == {"lr": 0.1}
    model = jnp.zeros((tr.capacity, 256), jnp.float32)
    delta, m = jax.jit(tr.compute)(model, _tokens(), {
        k: jnp.float32(v) for k, v in tr.hyperparams().items()})
    assert delta.shape == model.shape
    assert set(m) == {"loss", "ce", "aux_lb", "aux_z", "moe_expert_tokens"}
    assert m["moe_expert_tokens"].shape == (2, 8) and m["loss"].shape == ()


def test_counters_and_status_row_from_expert_tokens():
    from harmony_tpu.metrics import moe
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    per_step = np.zeros((3, 2, 8))
    per_step[:, :, :] = [4, 2, 2, 0, 8, 0, 0, 0]  # 3 steps x 2 layers
    moe.observe("olmoe-unit", per_step, experts_held=3)
    fams = parse_exposition(get_registry().expose())
    tokens = {(l["layer"], l["expert"]): v for _, l, v in
              fams["harmony_moe_expert_tokens_total"]["samples"]
              if l["job"] == "olmoe-unit"}
    assert len(tokens) == 16 and tokens[("1", "4")] == 24.0
    row = moe.stats_by_job()["olmoe-unit"]
    assert row["held_slot_share"] == pytest.approx(8 / 16)
    assert row["load_max_over_mean"] == pytest.approx(4 / (8 / 3))
    # the benchmark's reader computes the same from the exposition
    reader = load_by_path("layer_metrics", "expert_load_max_over_mean")
    assert reader.read({"phases": {"olmoe-unit": None}}) == pytest.approx(1.5)
    assert reader.read({}) is None


def _chunk_counters(job):
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    fams = parse_exposition(get_registry().expose())
    return [sum(v for _, l, v in fams[name]["samples"] if l["job"] == job)
            for name in ("harmony_moe_chunks_total",
                         "harmony_moe_layer_calls_total")]


@pytest.mark.parametrize("overflow,ratio", [(0, 1.0), (1, 7 / 6), (2, 8 / 6)])
def test_chunk_counters_read_past_one_when_a_layer_overflows(overflow, ratio):
    """A drained window of 3 steps x 2 layers, 8,192 slots a call with 1 of
    8 experts held: capacity 2,048. ``overflow`` calls of it route 2,049 and
    more to the held expert and ran a second chunk."""
    from harmony_tpu.metrics import moe
    from harmony_tpu.models.moe import chunk_plan

    assert chunk_plan(8192, 1, 8) == (2048, 4)
    per_step = np.zeros((3, 2, 8))
    per_step[:, :, 0], per_step[:, :, 5] = 2048, 8192 - 2048
    for step in range(overflow):
        per_step[step, 1, 0], per_step[step, 1, 5] = 2049, 8192 - 2049
    job = f"olmoe-chunks-{overflow}"
    moe.observe(job, per_step, experts_held=1)
    chunks, calls = _chunk_counters(job)
    assert calls == 6 and chunks == 6 + overflow
    reader = load_by_path("layer_metrics", "moe_chunks_per_call")
    assert reader.read({"phases": {job: None}}) == pytest.approx(ratio)
    assert reader.read({}) is None
    assert reader.read({"phases": {"no-such-job": None}}) is None


def test_chunk_counters_read_zero_on_the_plain_path():
    """Every expert held (or a share that leaves nothing to cut): calls are
    counted, chunks are not, and the ratio reads 0."""
    from harmony_tpu.metrics import moe

    per_step = np.zeros((3, 2, 8))
    per_step[:, :, :] = [4, 2, 2, 0, 8, 0, 0, 0]
    moe.observe("olmoe-plain", per_step, experts_held=3)
    assert _chunk_counters("olmoe-plain") == [0, 6]
    reader = load_by_path("layer_metrics", "moe_chunks_per_call")
    assert reader.read({"phases": {"olmoe-plain": None}}) == 0.0


def test_replay_begins_with_the_programs_logits(monkeypatch, capsys):
    """What the cell's ``correct`` evaluates starts with the program's
    logits against the reference's: it passes as the program stands, it
    fails when the tolerance cannot tell the ablations apart, and a program
    that leaves out the QK-norm turns every replayed loss into ``nan``."""
    import dataclasses
    import json

    app = {**_app(3), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "seed": 5}
    toks = np.asarray(_tokens())
    report = REF.check_logits(app, toks[:, :-1], 5)
    assert report["ok"] and report["rel_rms"] < 1e-5 < report["rms_tol"]
    assert min(report["ablations_rel_rms"].values()) > 100 * report["rms_tol"]
    monkeypatch.setitem(REF.LOGITS_RMS_TOL, "float32", 10.0)
    assert not REF.check_logits(app, toks[:, :-1], 5)["ok"]
    monkeypatch.undo()
    whole = TransformerLM.apply
    monkeypatch.setattr(TransformerLM, "apply", lambda self, p, t: whole(
        TransformerLM(dataclasses.replace(self.config, qk_norm=False)), p, t))
    capsys.readouterr()
    losses = REF.replay(app, (toks,), 2, 2, seed=5)
    assert len(losses) == 2 and all(np.isnan(losses))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "logits_check" and not line["ok"]
    assert line["rel_rms"] > line["rms_tol"]


def test_a_tiny_olmoe_tenant_through_the_jobserver_equals_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    TransformerTrainer and JSON app_params: the first steps' losses are the
    reference's replay (float32 both sides), the routing reaches the
    counters and STATUS, and the grouped-matmul tiles reach kernel_plans."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool
    from perf.generators import random_tokens

    app = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "beta2": 0.95, "seed": 11}
    data_args = {"num_seqs": 2, "seq_len": 33, "vocab_size": 96, "seed": 7}
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="olmoe-tiny", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=8, num_mini_batches=1,
                                 comm_probe_period=0, app_params=app),
            num_workers=1,
            user={"data_fn": "perf.generators.random_tokens:make",
                  "data_args": data_args})
        result = server.submit(cfg).result(timeout=300)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    losses = next(iter(result["workers"].values()))["losses"]
    want = REF.replay(app, (random_tokens.make(**data_args),), 2, 4, seed=11)
    assert np.allclose(losses[:4], want, rtol=1e-5, atol=0), (losses, want)
    from harmony_tpu.metrics import moe
    from harmony_tpu.runtime import progcache

    row = moe.stats_by_job()["olmoe-tiny"]
    assert 0.0 < row["held_slot_share"] < 1.0
    assert row["load_max_over_mean"] >= 1.0
    kernels = {p["kernel"] for p in progcache.kernel_plans().get(
        "olmoe-tiny", [])}
    assert {"harmony_gmm_fwd", "harmony_gmm_dx", "harmony_gmm_dw"} <= kernels
    assert status["tenants"]["olmoe-tiny"]["moe"] == row
