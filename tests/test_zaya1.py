"""ZAYA1's block on the normal path (PR 45): attention inside a convolved,
L2-normed q/k latent (CCA) with a shifted value head and rotary on half a
head, an MLP router that hands its rows to the next layer's router and may
send a token to no expert, scaled residual merges. ``TransformerLM`` with the
architecture fields against the plain reference the benchmark ships
(``perf/reference/zaya1-8b.py``: float32, K and V repeated, an explicit
boolean mask, a loop over the held experts, no kernel).

Small, float32, seeded — the configuration's ``rehearse`` preset with a third
layer: d 64, 4 query heads over 2 K/V heads of 16, rotary on 8 of 16 columns,
4 experts + "no expert" with 2 held, a router 16 wide, 40 positions. Both
sides are float32 on the CPU and differ in the order of sums, so 2e-5 relative
holds for values and gradients. Everything that is an identity as initialised
(the temperature, the EDA scale, the selection bias, the merges, the
convolutions' biases) is given the reference's seeded non-trivial values on
both sides, or the comparison could not see it.
"""
from __future__ import annotations

import dataclasses
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
from harmony_tpu.models import transformer as T  # noqa: E402
from harmony_tpu.models.transformer import TransformerTrainer  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "zaya1-8b")
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs", "zaya1-8b.json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "n_layers": 3, "vocab_size": 96, "step_size": 1e-3}
HELD = [4, 2]  # every expert here; experts 0..1 of the 4
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}


def _config(app):
    return TransformerConfig(**{k: v for k, v in app.items() if k in FIELDS})


def _app(held):
    return {**APP, "moe_experts_held": held}


def _tokens(seed=0, batch=2, app=APP):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, app["vocab_size"], (batch, app["max_seq"] + 1)), jnp.int32)


def _seeded(params, app, seed):
    """The program's parameters with the reference's seeded identities."""
    for layer, ident in zip(params["layers"],
                            REF.seeded_identities(app, seed)):
        for sub, leaves in REF.as_program(ident).items():
            (layer[sub] if sub else layer).update(leaves)
    return params


def _both(held, seed=5):
    app = _app(held)
    lm = TransformerLM(_config(app))
    ref = REF.with_identities(REF.init_params(app, seed),
                              REF.seeded_identities(app, seed))
    return (lm, _seeded(lm.init(jax.random.PRNGKey(seed)), app, seed),
            REF._Static(app), ref)


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


def _as_reference(tree, app=APP):
    """The program's parameter (or gradient) tree under the reference's
    names."""
    return REF.from_program(tree, app)


# -- the system against the reference ----------------------------------------

def test_the_seeded_parameters_are_the_references():
    lm = TransformerLM(_config(APP))
    got = _as_reference(lm.init(jax.random.PRNGKey(3)))
    want = REF.init_params(APP, 3)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and np.array_equal(a, b)


@pytest.mark.parametrize("held", HELD, ids=["all-experts", "2-of-4"])
def test_logits_equal_the_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        want, chosen = REF.forward(ref, toks, app)
        _close(lm.apply(params, toks), want)
    # the seeded bias sends a share of the slots to no expert: the case is
    # in the comparison
    assert float(chosen[:, APP["moe_experts"]].sum()) > 0


@pytest.mark.parametrize("held", HELD, ids=["all-experts", "2-of-4"])
def test_loss_and_every_gradient_equal_the_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens(1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lm.loss)(params, toks)
        want, want_g = jax.value_and_grad(REF.loss_fn)(ref, toks, app)
    _close(loss, want)
    got = _as_reference(grads)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_g)
    for (path, b), a in zip(flat, jax.tree.leaves(got)):
        if float(jnp.abs(b).max()) == 0.0:  # beta; the first layer's gamma
            assert float(jnp.abs(a).max()) == 0.0, path
        else:
            _close(a, b, rtol=1e-4)
    for i, layer in enumerate(grads["layers"]):
        assert float(jnp.abs(layer["moe"]["bias"]).max()) == 0.0
        assert (float(jnp.abs(layer["moe"]["r_eda"]).max()) > 0.0) == (i > 0)


def test_remat_traces_the_two_streams_and_changes_nothing():
    lm, params, _, _ = _both(2)
    toks = _tokens(2)
    again = TransformerLM(dataclasses.replace(lm.config, remat=True))
    a, ga = jax.value_and_grad(lm.loss)(params, toks)
    b, gb = jax.value_and_grad(again.loss)(params, toks)
    _close(b, a, rtol=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        _close(y, x, rtol=1e-5)


def test_every_ablation_is_told_apart_and_the_program_is_not():
    with jax.default_matmul_precision("highest"):
        report = REF.check_logits(_app(2), np.asarray(_tokens(4)), 9)
    assert report["ok"], report
    assert report["program"]["q90"] <= 1e-5
    assert all(report["detected"].values())
    assert report["null_slot_share"] > 0.05
    # the first gradient, leaf by leaf, as a share of what float8 operands
    # do to the same leaf
    grads = report["gradients"]
    assert set(grads["by_leaf"]) == set(
        REF.init_params(_app(2), 9)["layers"][0]) | {"embed", "ln_f"}
    assert grads["worst"] <= 1e-4 < grads["limit"]
    assert grads["by_leaf"]["beta"] == [0.0, 0.0, 0.0]
    assert all(control > 0.05 for leaf, (_, control, _) in
               grads["by_leaf"].items() if leaf != "beta")
    assert grads["loss"] <= 1e-5
    assert all(0.0 < a["loss"] for a in report["ablations"].values())


def test_a_wrong_gradient_of_one_leaf_is_named():
    app = _app(2)
    toks = _tokens(4)
    ref = REF.with_identities(REF.init_params(app, 9),
                              REF.seeded_identities(app, 9))
    with jax.default_matmul_precision("highest"):
        want, low = (jax.device_get(REF.loss_and_grad(
            ref, toks, REF._Static(app), None, fp8)[1]) for fp8 in (False, True))
    control = REF.gradient_errors(low, want)
    assert REF.against_control(REF.gradient_errors(want, want),
                               control)["worst"] == 0.0
    assert REF.against_control(control, control)["worst"] == 1.0
    for leaf, spoil in (("tau", lambda g: 1.5 * g),          # a scale
                        ("eg", lambda g: np.zeros_like(g)),  # no backward
                        ("beta", lambda g: g + 1e-9)):       # a leak
        got = {**want, "layers": [dict(l) for l in want["layers"]]}
        got["layers"][1][leaf] = spoil(got["layers"][1][leaf])
        read = REF.against_control(REF.gradient_errors(got, want), control)
        assert read["worst_leaf"] == leaf, read
        assert read["worst"] > REF.GRAD_LIMITS["bfloat16"], (leaf, read)
        assert sum(row[2] > 0 for row in read["by_leaf"].values()) == 1


@pytest.mark.parametrize("ablate", REF.LOGIT_ABLATIONS)
def test_each_ablation_moves_the_reference_itself(ablate):
    _, _, app, ref = _both(2, seed=9)
    toks = _tokens(4)[:, :-1]
    with jax.default_matmul_precision("highest"):
        whole = REF.forward(ref, toks, app)[0]
        broken = REF.forward(ref, toks, app, ablate)[0]
    assert REF.position_errors(broken, whole)["q90"] > 1e-3


def test_init_traced_abstractly_has_the_same_leaves():
    lm = TransformerLM(_config(APP))
    a, b = lm.init(jax.random.PRNGKey(0)), jax.eval_shape(
        lm.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
    bias = a["layers"][0]["moe"]["bias"]
    assert bias.tolist() == [0.0] * 4 + [-1.0]


# -- the six assumed points, one test each -----------------------------------

def test_identities_start_where_the_configuration_says():
    """(e): tau stored as itself at 1, gamma at 1, beta 0 with -1 on "no
    expert"; the merges a plain sum, the convolutions' biases 0."""
    layer = TransformerLM(_config(APP)).init(jax.random.PRNGKey(1))["layers"][1]
    assert layer["cca"]["temp"].tolist() == [1.0, 1.0]
    assert np.all(np.asarray(layer["moe"]["r_eda"]) == 1.0)
    assert layer["moe"]["bias"].tolist() == [0.0, 0.0, 0.0, 0.0, -1.0]
    for m in (layer["merge1"], layer["merge2"]):
        assert np.array_equal(m, np.stack([np.ones(64), np.zeros(64)] * 2))
    assert not np.any(layer["cca"]["conv0_b"]) and not np.any(
        layer["cca"]["conv1_b"])
    x = jnp.ones((1, 3, 64)) * 0.3
    assert np.array_equal(T._merge(x, 2 * x, layer["merge1"]), x + 2 * x)


def test_both_convolutions_carry_a_bias():
    """(a): q and k move when either bias does, all else equal."""
    lm, params, _, _ = _both(2)
    p = params["layers"][0]["cca"]
    q, k, v = _latent_inputs(lm)
    base = lm._cca_latent(q, k, v, p)
    for name in ("conv0_b", "conv1_b"):
        moved = lm._cca_latent(q, k, v, {**p, name: p[name] + 0.5})
        assert float(jnp.abs(moved[0] - base[0]).max()) > 1e-3, name
        assert float(jnp.abs(moved[1] - base[1]).max()) > 1e-3, name
        assert np.array_equal(moved[2], base[2])  # the values: never


def test_the_router_reads_the_normed_rows(monkeypatch):
    """(b): the block hands its expert layer the rows under the second
    norm and NO other input for the router (``router_x`` is SmallThinker's
    way, the block's un-normed input)."""
    lm, params, _, _ = _both(4)
    seen, real = [], T.ffn_apply

    def spy(cfg, layer, xn, **kw):
        seen.append((xn, kw))
        return real(cfg, layer, xn, **kw)

    monkeypatch.setattr(T, "ffn_apply", spy)
    x = 7.0 * jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    _, aux, _ = lm._block(x, params["layers"][1], None,
                          route_state=jnp.ones((40, 16)))
    (xn, kw), = seen
    assert "router_x" not in kw and kw["route_state"].shape == (40, 16)
    assert np.allclose(jnp.sqrt(jnp.mean(xn * xn, axis=-1)), 1.0, atol=1e-3)
    assert aux["state"].shape == (40, 16)


def test_the_merge_adds_the_bias_before_the_scale():
    """(d): ``a_x (x + b_x) + a_y (y + b_y)``, and the first layer's first
    merge has the residual's pair like every other."""
    x, y = jnp.full((1, 2, 4), 2.0), jnp.full((1, 2, 4), 3.0)
    m = jnp.stack([jnp.full((4,), 5.0), jnp.full((4,), 0.5),
                   jnp.full((4,), 7.0), jnp.full((4,), 0.25)])
    assert np.allclose(T._merge(x, y, m), 5.0 * 2.5 + 7.0 * 3.25)
    layers = TransformerLM(_config(APP)).init(jax.random.PRNGKey(0))["layers"]
    assert all(l["merge1"].shape == l["merge2"].shape == (4, 64)
               for l in layers)


def test_slots_that_chose_no_expert_are_counted_and_add_nothing():
    """(c): a router whose bias sends EVERY slot to no expert: the layer's
    output is zero, every slot is counted as skipped and none as an
    expert's; half-way, the counts add up to the slots."""
    cfg = _config(_app(2)).dropless_cfg
    p = moe_mod.init_dropless_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (80, 64))
    p["bias"] = p["bias"].at[4].set(10.0)
    out, stats = moe_mod.moe_ffn_dropless(p, x, cfg)
    assert float(jnp.abs(out).max()) == 0.0
    assert float(stats["skipped"]) == 80 and float(stats["tokens"].sum()) == 0
    p["bias"] = p["bias"].at[4].set(0.0)
    out, stats = moe_mod.moe_ffn_dropless(p, x, cfg)
    skipped = float(stats["skipped"])
    assert 0 < skipped < 80
    assert skipped + float(stats["tokens"].sum()) == 80
    assert stats["prob_sum"].shape == (4,) and stats["state"].shape == (80, 16)


def _submit(job_id, trainer, app, epochs=8):
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool

    data_args = {"num_seqs": 2, "seq_len": 41, "vocab_size": 96, "seed": 7}
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
        result = server.submit(cfg).result(timeout=300)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    return (next(iter(result["workers"].values()))["losses"], status,
            data_args)


JOB_APP = {**_app(2), "seed": 11}


class BiasWatchingTrainer(TransformerTrainer):
    """The LM trainer with a NON-ZERO seeded selection bias, reporting the
    bias rows beside each step's loss (``seen[job]``: ``[steps, layers,
    outputs]`` a drain)."""

    seen: dict = {}

    def build_model(self, config):
        lm = TransformerLM(config)
        init = lm.init

        def seeded(rng):
            params = init(rng)
            for i, layer in enumerate(params["layers"]):
                layer["moe"]["bias"] = 0.02 * jax.random.normal(
                    jax.random.fold_in(rng, 7 + i), layer["moe"]["bias"].shape)
            return params
        lm.init = seeded
        return lm

    def loss_and_metrics_on_batch(self, params, batch):
        loss, m = super().loss_and_metrics_on_batch(params, batch)
        return loss, {**m, "moe_bias": jnp.stack(
            [l["moe"]["bias"] for l in params["layers"]])}

    def observe_step_vectors(self, job_id, vectors):
        self.seen.setdefault(job_id, []).append(
            np.asarray(vectors["moe_bias"]))
        super().observe_step_vectors(job_id, vectors)


def test_four_steps_through_the_jobserver_equal_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    TransformerTrainer and JSON app_params: the first four steps' losses are
    the reference's replay (float32 both sides, the table's Adam against the
    formula); the table is whole tiles; the counters tell the slots an
    expert elsewhere holds from those that chose none."""
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from perf.generators import random_tokens

    losses, status, data_args = _submit(
        "zaya1-tiny", "harmony_tpu.models.transformer:TransformerTrainer",
        JOB_APP)
    want = REF.replay(JOB_APP, (random_tokens.make(**data_args),), 2, 4,
                      seed=11, logits=False)
    assert np.allclose(losses[:4], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    row = status["tenants"]["zaya1-tiny"]
    assert row["table_layout"]["tile_exact"] == 1
    assert row["layer_kinds"] == {"mha": 3}
    fams = parse_exposition(get_registry().expose())
    total = lambda name: sum(v for _, l, v in fams[name]["samples"]
                             if l["job"] == "zaya1-tiny")
    slots = 8 * 3 * 80  # steps x layers x tokens, one slot a token
    assert total("harmony_moe_null_slots_total") == 0  # the bias stands at -1
    assert total("harmony_moe_expert_tokens_total") == slots
    assert (total("harmony_moe_held_slots_total")
            + total("harmony_moe_absent_slots_total")) == slots
    assert 0 < total("harmony_moe_absent_slots_total") < slots


def test_the_selection_bias_is_bit_equal_after_eight_steps():
    """(f): beta is a row of the model table like any other and the table's
    Adam visits it every step: a zero gradient leaves m = v = 0 and the row
    as it was, to the last bit, whatever its value — while a share of the
    slots goes to no expert and is counted."""
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    BiasWatchingTrainer.seen.pop("zaya1-bias", None)
    sys.modules.setdefault("test_zaya1", sys.modules[__name__])
    losses, _, _ = _submit("zaya1-bias", "test_zaya1:BiasWatchingTrainer",
                           JOB_APP)
    seen = np.concatenate(BiasWatchingTrainer.seen["zaya1-bias"])
    assert seen.shape == (8, 3, 5)  # 8 steps: the rows after 0 .. 7 updates
    assert np.abs(seen[0]).min() > 0.0
    for step in seen[1:]:
        assert step.tobytes() == seen[0].tobytes()
    assert losses[-1] < losses[0]  # while everything else did move
    fams = parse_exposition(get_registry().expose())
    null = sum(v for _, l, v in fams["harmony_moe_null_slots_total"]["samples"]
               if l["job"] == "zaya1-bias")
    assert 0 < null < 8 * 3 * 80


# -- one test a mechanism -----------------------------------------------------

def _latent_inputs(lm, seed=3, S=12):
    H, Hkv, hd = lm.config.n_heads, lm.config.kv_heads, lm.config.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, S, H * hd)),
            jax.random.normal(ks[1], (1, S, Hkv * hd)),
            jax.random.normal(ks[2], (1, S, Hkv * hd)))


@pytest.mark.parametrize("mechanism", [
    "convolutions-are-causal", "shifted-head-reads-the-previous-position",
    "norms-are-root-hd-and-tau-root-hd", "rotary-turns-half-a-head",
    "state-reaches-the-next-layer-only"])
def test_mechanism(mechanism):
    lm, params, _, _ = _both(2)
    p = params["layers"][1]["cca"]
    q, k, v = _latent_inputs(lm)
    hd = lm.config.head_dim
    if mechanism == "convolutions-are-causal":
        # moving position t moves q and k at t and t + 1 (two taps) and t + 2
        # (two convolutions), nothing before t
        t = 5
        base = lm._cca_latent(q, k, v, p)
        moved = lm._cca_latent(q.at[0, t].add(1.0), k.at[0, t].add(1.0), v, p)
        for a, b in zip(moved[:2], base[:2]):
            delta = np.abs(np.asarray(a - b)).max(axis=(0, 2, 3))
            assert np.all(delta[:t] == 0.0) and np.all(delta[t:t + 3] > 0)
            assert np.all(delta[t + 3:] == 0.0)
    elif mechanism == "shifted-head-reads-the-previous-position":
        got = np.asarray(lm._cca_latent(q, k, v, p)[2])     # [B, S, Hkv, hd]
        want = np.asarray(v).reshape(1, 12, 2, hd)
        assert np.array_equal(got[:, :, 0], want[:, :, 0])
        assert np.array_equal(got[:, 1:, 1], want[:, :-1, 1])
        assert not np.any(got[:, 0, 1])
    elif mechanism == "norms-are-root-hd-and-tau-root-hd":
        qn, kn, _ = lm._cca_latent(q, k, v, p)
        assert np.allclose(np.linalg.norm(qn, axis=-1), hd ** 0.5, rtol=1e-5)
        want = np.asarray(p["temp"]) * hd ** 0.5
        assert np.allclose(np.linalg.norm(kn, axis=-1), want, rtol=1e-5)
        assert not np.allclose(np.asarray(p["temp"]), 1.0)
    elif mechanism == "rotary-turns-half-a-head":
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 9, hd))
        turned = T.rope(x, 5e6, width=hd // 2)
        assert np.array_equal(turned[..., hd // 2:], x[..., hd // 2:])
        assert np.allclose(turned[..., :hd // 2],
                           T.rope(x[..., :hd // 2], 5e6))
        assert float(jnp.abs(turned[:, :, 1:, :hd // 2]
                             - x[:, :, 1:, :hd // 2]).max()) > 1e-3
        assert np.allclose(turned, REF.rotary(x, 5e6, hd // 2), atol=1e-6)
    else:
        # layer l + 1's router reads layer l's rows; layer l + 2's reads
        # layer l + 1's and nothing of layer l's except through them: with
        # layer l + 1's scale at zero, layer l's rows reach nobody
        cfg = lm.config.dropless_cfg
        moes = [l["moe"] for l in params["layers"]]
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 20, 64))

        def rows(z0, eda1):
            mid = {**moes[1], "r_eda": eda1 * moes[1]["r_eda"]}
            _, z1 = moe_mod._mlp_logits(mid, x[1], cfg, z0)
            return z1, moe_mod._mlp_logits(moes[2], x[2], cfg, z1)[1]

        _, z0 = moe_mod._mlp_logits(moes[0], x[0], cfg, None)
        z1, z2 = rows(z0, 1.0)
        z1_moved, z2_moved = rows(z0 + 1.0, 1.0)
        assert float(jnp.abs(z1_moved - z1).max()) > 1e-3
        assert float(jnp.abs(z2_moved - z2).max()) > 1e-3  # through z1
        z1_cut, z2_cut = rows(z0, 0.0)
        z1_cut_moved, z2_cut_moved = rows(z0 + 1.0, 0.0)
        assert np.array_equal(z1_cut_moved, z1_cut)
        assert np.array_equal(z2_cut_moved, z2_cut)  # and not directly


def test_two_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the expert sublayer as the two chips of a
    stage compute it — this chip's experts 0..1, the other's 2..3 brought to
    the front of ITS program with its router's outputs in that order — adds
    up to the uncut reference's layer. What both compute alike (the norm,
    the router, the merge's residual part) is counted once: the sum is of
    the routed parts ``y``."""
    app = _app(4)
    cfg = _config(app)
    _, params, _, ref = _both(4)
    layer, ref_layer = params["layers"][0], ref["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 64))
    # the uncut reference's routed part: the merge as m2 = [0, 0, 1, 0]
    only_y = jnp.stack([jnp.zeros(64), jnp.zeros(64), jnp.ones(64),
                        jnp.zeros(64)])
    with jax.default_matmul_precision("highest"):
        want, _, chosen = REF.expert_sublayer(
            x, None, {**ref_layer, "m2": only_y}, REF._Static(app), None)
    assert float(chosen[4]) > 0  # some slots chose no expert
    half = dataclasses.replace(cfg, moe_experts_held=2).dropless_cfg
    xn = REF.rms_norm(x, layer["ln2"], cfg.norm_eps).reshape(-1, 64)
    order = jnp.asarray([2, 3, 0, 1, 4])  # the other chip's experts first
    parts = []
    for chip in (0, 1):
        m = dict(layer["moe"])
        held = slice(0, 2) if chip == 0 else slice(2, 4)
        m.update({w: m[w][held] for w in ("wg", "wu", "wd")})
        if chip == 1:
            m["r_w3"], m["bias"] = m["r_w3"][:, order], m["bias"][order]
        with jax.default_matmul_precision("highest"):
            parts.append(moe_mod.moe_ffn_dropless(m, xn, half)[0])
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    _close(sum(parts).reshape(x.shape), want)


# -- what describes no model is refused --------------------------------------

@pytest.mark.parametrize("fields,match", [
    ({"attn_kind": "mla", "kv_lora_rank": 8, "qk_nope_head_dim": 8,
      "qk_rope_head_dim": 8, "v_head_dim": 8, "n_kv_heads": 0,
      "mha_head_dim": 0, "rope_fraction": 1.0}, "cca convolves"),
    ({"linear_layers": [0], "linear_heads": 2, "linear_head_dim": 8,
      "short_conv": 4}, "cca convolves"),
    ({"qk_norm": True, "n_kv_heads": 0}, "cca convolves"),
    ({"layer_pattern": "***", "cca": False, "merge_scaled": False,
      "moe_every": 1, "pos": "rope"}, "layer_pattern"),
    ({"moe_top_k": 0, "moe_experts_held": None, "moe_null_expert": False},
     "moe_router_hidden is the width"),
    ({"moe_router_hidden": 0}, "moe_null_expert is the MLP router's"),
    ({"rope_fraction": 0.3}, "whole, even number of columns"),
    ({"n_kv_heads": 1}, "must be even"),
])
def test_fields_that_describe_no_model_are_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        _config({**APP, **fields})
    _config(APP)  # and the model itself is accepted
