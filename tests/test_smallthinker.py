"""SmallThinker's block on the normal path (PR 36): grouped-query heads and a
window through the three flash kernels (block-skipped), window + rotary and
full + NoPE blocks in one model, the router read from the block's input,
ReLU-gated experts. ``TransformerLM`` with the architecture fields against
the plain reference the benchmark ships
(``perf/reference/smallthinker-21b-a3b.py``: float32, K and V repeated, an
explicit boolean mask, a loop over the held experts, no kernel).

Small, float32, seeded: d 64, 4 query heads over 2 K/V heads of 16, a window
of 16 over 80 positions (several windows long), 1 full + 3 windowed blocks, 8
experts of width 32, top-2. Both sides are float32 on the CPU and differ in
the order of sums, so 2e-5 relative holds for values and gradients.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import re
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
from harmony_tpu.ops import attention as A  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "smallthinker-21b-a3b")
RTOL = 2e-5
APP = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, mha_head_dim=16,
           n_layers=4, d_ff=32, max_seq=80, pos="rope", rope_theta=1.5e6,
           ffn="swiglu", tie_embeddings=False, norm_eps=1e-6, window=16,
           window_layers=[1, 2, 3], moe_experts=8, moe_top_k=2, moe_every=1,
           moe_norm_topk=True, moe_route_block_input=True, moe_act="relu",
           moe_aux_weight=0.001, optimizer="adam", step_size=1e-3, beta2=0.95)
HELD = [None, 4]  # every expert here; experts 0..3 of the 8
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}


def _config(app):
    return TransformerConfig(**{k: v for k, v in app.items() if k in FIELDS})


def _app(held):
    return APP if held is None else {**APP, "moe_experts_held": held}


def _tokens(seed=0, batch=2, app=APP):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, app["vocab_size"], (batch, app["max_seq"] + 1)), jnp.int32)


def _both(held, seed=5):
    app = _app(held)
    lm = TransformerLM(_config(app))
    return (lm, lm.init(jax.random.PRNGKey(seed)), REF._Static(app),
            REF.init_params(app, seed))


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


def _as_reference(tree):
    """The program's parameter (or gradient) tree under the reference's
    names."""
    return {"embed": tree["embed"], "head": tree["head"], "ln_f": tree["ln_f"],
            "layers": [{"g1": l["ln1"], "g2": l["ln2"], "wqkv": l["wqkv"],
                        "wo": l["wo"], "router": l["moe"]["router"],
                        "eg": l["moe"]["wg"], "eu": l["moe"]["wu"],
                        "ed": l["moe"]["wd"]} for l in tree["layers"]]}


# -- the kernels against the blockwise scan ---------------------------------

#: (positions, window, query heads, K/V heads, explicit block or None: the
#: plan's tiles) — several windows long, a window that is no multiple of the
#: tile, a length that is no multiple of a tile, one K/V head for all, no
#: window at all with grouped heads, and the plan's own tiles with sub-blocks
CASES = {
    "4-windows": (256, 64, 4, 2, 64),
    "odd-window": (384, 100, 4, 1, 128),
    "window-under-a-tile": (512, 50, 6, 2, 128),
    "odd-length": (200, 48, 4, 2, None),
    "grouped-no-window": (256, None, 4, 2, 64),
    "planned-tiles": (1024, 300, 2, 1, None),
}


@functools.lru_cache(maxsize=None)
def _kernel_case(name):
    S, W, H, Hkv, blk = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(S + (W or 0)), 4)
    q = jax.random.normal(ks[0], (1, H, S, 16))
    k = jax.random.normal(ks[1], (1, Hkv, S, 16))
    v = jax.random.normal(ks[2], (1, Hkv, S, 16))
    w = jax.random.normal(ks[3], (1, H, S, 16))

    def both(fn):
        out = fn(q, k, v)
        grads = jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2))(
            q, k, v)
        return (out, *grads)

    got = both(lambda *a: A.flash_attention(
        *a, causal=True, block_q=blk, block_k=blk, interpret=True, window=W))
    want = both(lambda *a: A.blockwise_attention(
        *a, causal=True, block_k=64, window=W))
    return got, want


@pytest.mark.parametrize("output", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_each_kernel_equals_blockwise_with_the_same_window_and_groups(
        name, output):
    """The forward's output, and the one backward kernel's dQ and its dK /
    dV, a case each."""
    got, want = _kernel_case(name)
    for i in {"fwd": (0,), "dq": (1,), "dkv": (2, 3)}[output]:
        assert got[i].shape == want[i].shape
        assert np.isfinite(np.asarray(got[i])).all()
        _close(got[i], want[i], 1e-5)


def test_blockwise_window_and_groups_equal_the_explicit_mask():
    S, W, H, Hkv = 70, 9, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, H, S, 8))
    k = jax.random.normal(ks[1], (2, Hkv, S, 8))
    v = jax.random.normal(ks[2], (2, Hkv, S, 8))
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) * 8 ** -0.5
    s = jnp.where((ahead >= 0) & (ahead < W), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      jnp.repeat(v, 2, axis=1))
    _close(A.blockwise_attention(q, k, v, causal=True, block_k=32, window=W),
           want, 1e-5)


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("S,W,tiles", [
    (1024, 256, (128, 256, 128)), (1024, 100, (256, 512, 256)),
    (512, None, (128, 512, 128)), (2048, 512, (512, 2048, 1024))])
def test_band_work_counts_what_the_mask_keeps(kernel, S, W, tiles):
    """The static count: the elements kept are the band's, the sub-blocks
    run cover every kept element, and a window runs fewer than the
    triangle."""
    bq, bk, sub = tiles
    t = A.Tiles(*((bk, bq, sub) if kernel == "bwd" else tiles))
    work = A.band_work(kernel, t, S, S, True, W)
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    keep = (ahead >= 0) & (ahead < (W or S))
    assert work["kept"] == int(keep.sum())
    assert work["kept"] == (S * (S + 1) // 2 if W is None else
                            W * (W + 1) // 2 + (S - W) * W)
    assert work["computed"] >= work["kept"]
    assert work["with_work"] <= work["grid_steps"]
    if W is not None:
        whole = A.band_work(kernel, t, S, S, True, None)
        assert work["sub_blocks"] < whole["sub_blocks"]
        assert work["grid_steps"] <= whole["grid_steps"]


def test_what_no_kernel_computes_is_refused():
    q = jnp.zeros((1, 4, 64, 8))
    with pytest.raises(ValueError, match="must divide"):
        A.flash_attention(q, q[:, :3], q[:, :3], causal=True, interpret=True)
    with pytest.raises(ValueError, match="window"):
        A.flash_attention(q, q, q, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        A.blockwise_attention(q, q, q, causal=True, window=0)


@pytest.mark.parametrize("fields,match", [
    (dict(n_kv_heads=3), "must divide"),
    (dict(window=16), "window_layers"),
    (dict(window=16, window_layers=[0], pos="learned"), "window_layers"),
    (dict(window=16, window_layers=[2, 1], pos="rope"), "window_layers"),
    (dict(moe_act="gelu", moe_experts=4, moe_top_k=2, ffn="swiglu"), "moe_act"),
    (dict(moe_route_block_input=True), "dropless"),
    (dict(n_kv_heads=2, attn_kind="mla", kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, pos="rope"), "attn_kind='mha'"),
])
def test_fields_that_describe_no_model_are_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(vocab_size=32, d_model=32, n_heads=4, n_layers=3,
                          **fields)


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("held", HELD, ids=["all-experts", "4-of-8"])
def test_logits_equal_the_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks), REF.forward(ref, toks, app)[0])


@pytest.mark.parametrize("held", HELD, ids=["all-experts", "4-of-8"])
def test_loss_and_every_gradient_equal_the_reference(held):
    lm, params, app, ref = _both(held)
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
    the same first update, formed by formula from the program's gradient."""
    lm, params, app, _ = _both(4)
    toks = _tokens(3)
    data = (np.concatenate([np.asarray(toks)] * 1),)
    with jax.default_matmul_precision("highest"):
        want = REF.replay(dict(app), data, 2, 2, 5, logits=False)
        loss0, g = jax.value_and_grad(lm.loss)(params, toks)
        stepped = jax.tree.map(
            lambda p, a: p - APP["step_size"] * a / (jnp.abs(a) + 1e-8),
            params, g)
        loss1 = lm.loss(stepped, toks)
    _close(loss0, want[0])
    _close(loss1, want[1], 1e-4)


def test_every_ablation_is_told_apart_and_the_program_is_not():
    """``check_logits`` as the cell runs it, at the test size in float32:
    the program holds both limits in both ranges of positions, and each
    broken piece of the mathematics reads above them."""
    report = REF.check_logits({**_app(4), "dtype": "float32"},
                              np.asarray(_tokens()[:, :-1]), 5)
    assert report["ok"], report
    assert set(report["detected"]) == set(REF.LOGIT_ABLATIONS)
    assert all(report["detected"].values())
    assert set(report["program"]) == {"before_window", "from_window"}
    # the first window's positions cannot see a window: dropping it moves
    # nothing there, and everything after
    moved = report["ablations"]["no_window"]
    assert moved["before_window"]["q90"] == 0.0
    assert moved["from_window"]["q90"] > 1e-3


@pytest.mark.parametrize("ablate", REF.LOGIT_ABLATIONS)
def test_each_ablation_moves_the_reference_itself(ablate):
    _, _, app, ref = _both(4)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        want = REF.forward(ref, toks, app)[0]
        broken = REF.forward(ref, toks, app, ablate)[0]
    assert REF.position_errors(broken, want)["q90"] > 1e-3


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: a windowed block's output from each of eight
    chips' shares (2 of 16 experts each; attention, the router and the norms
    computed alike on every chip and counted once) adds up to what the
    reference gives for the uncut block."""
    app = {**APP, "moe_experts": 16, "moe_top_k": 4, "n_layers": 2,
           "window_layers": [1]}
    uncut = REF._Static(app)
    ref = REF.init_params(uncut, 7)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, app["max_seq"], 64))
    share_cfg = _config({**app, "moe_experts_held": 2})
    lm = TransformerLM(share_cfg)
    with jax.default_matmul_precision("highest"):
        want = REF._block(x, ref, uncut, True, None)[0]
        total, once = 0.0, None
        for s in range(8):
            # the share's two experts first; the router's columns follow
            perm = np.roll(np.arange(16), -2 * s)
            layer = {"ln1": ref["g1"], "ln2": ref["g2"], "wqkv": ref["wqkv"],
                     "wo": ref["wo"],
                     "moe": {"router": ref["router"][:, perm],
                             "wg": ref["eg"][perm[:2]],
                             "wu": ref["eu"][perm[:2]],
                             "wd": ref["ed"][perm[:2]]}}
            out = lm._block(x, layer, None, kind="swa")[0]
            if once is None:  # what every chip computes alike: x + attention
                idle = {**layer, "moe": {**layer["moe"], "wd": jnp.zeros_like(
                    layer["moe"]["wd"])}}
                once = lm._block(x, idle, None, kind="swa")[0]
            total = total + (out - once)
    _close(once + total, want, 1e-5)


def test_chunked_relu_layer_and_its_written_backward_equal_autodiff():
    """A held share of 1/8 takes the chunked layer, whose backward is
    written by hand: ReLU's beside SiLU's."""
    cfg = moe_mod.DroplessConfig(64, 6, 32, 16, 8, norm_topk=True, act="relu")
    assert moe_mod.chunk_plan(1024 * 6, 8, 64) == (1536, 4)
    params = moe_mod.init_dropless_params(jax.random.PRNGKey(0), cfg)
    x, rx = (jax.random.normal(jax.random.PRNGKey(i), (1024, 32))
             for i in (1, 2))

    def plain(p, x, rx):
        probs = jax.nn.softmax(rx @ p["router"], axis=-1)
        top, chosen = jax.lax.top_k(probs, 6)
        w = jnp.zeros_like(probs).at[jnp.arange(1024)[:, None], chosen].set(
            top / top.sum(axis=-1, keepdims=True))
        return sum(w[:, e:e + 1] * ((jax.nn.relu(x @ p["wg"][e])
                                     * (x @ p["wu"][e])) @ p["wd"][e])
                   for e in range(8))

    layer = lambda p, x, rx: moe_mod.moe_ffn_dropless(p, x, cfg,
                                                      router_x=rx)[0]
    with jax.default_matmul_precision("highest"):
        _close(layer(params, x, rx), plain(params, x, rx), 1e-5)
        got = jax.grad(lambda *a: (layer(*a) ** 2).sum(), argnums=(0, 1, 2))(
            params, x, rx)
        want = jax.grad(lambda *a: (plain(*a) ** 2).sum(), argnums=(0, 1, 2))(
            params, x, rx)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 1e-4)


# -- the embedding's scale, and what it does to the routers ------------------

@pytest.mark.parametrize("std", [None, 0.02, 1.0])
def test_embed_std_scales_the_rows_in_every_initialiser(std):
    """``embed_std`` is the embedding rows' standard deviation in ``init``
    and the reference's ``init_params`` alike (0.02 where the field is left
    out), and moves no other parameter."""
    app = APP if std is None else {**APP, "embed_std": std}
    want = 0.02 if std is None else std
    lm = TransformerLM(_config(app))
    drawn = {"init": lm.init(jax.random.PRNGKey(3)),
             "reference": REF.init_params(app, 3)}
    for name, params in drawn.items():
        got = float(np.std(np.asarray(params["embed"])))
        assert abs(got - want) < 0.05 * want, (name, got)
    base = TransformerLM(_config(APP)).init(jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(drawn["init"]["embed"]),
                               np.asarray(base["embed"]) * (want / 0.02),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(drawn["init"]["layers"]),
                    jax.tree_util.tree_leaves(base["layers"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(drawn["init"]["head"]),
                                  np.asarray(base["head"]))


@pytest.mark.parametrize("std,collapsed", [(0.02, True), (1.0, False)],
                         ids=["gpt2-rows", "unit-rows"])
def test_unit_rows_keep_the_routers_apart_as_initialised(std, collapsed):
    """Why the configuration sets ``embed_std`` 1.0: with GPT-2's 0.02 a row
    is a fiftieth of what a block's fan-in projections add to it, the keys'
    average is the residual stream two blocks in, and a later block's router
    sends nearly every token to the same six of its 64 experts (the most
    loaded expert near the 64 / 6 = 10.7 means a total collapse gives it);
    unit rows keep every block's load within a chunk's headroom of twice the
    balanced share."""
    app = dict(APP, vocab_size=1024, d_model=256, mha_head_dim=64, d_ff=64,
               max_seq=512, window=128, moe_experts=64, moe_top_k=6,
               moe_experts_held=8, embed_std=std, dtype="float32",
               attn="blockwise")
    lm = TransformerLM(_config(app))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 513), 0, 1024)
    _, metrics = jax.jit(lm.loss_and_metrics)(
        lm.init(jax.random.PRNGKey(0)), tokens)
    load = np.asarray(metrics["moe_expert_tokens"])        # [blocks, experts]
    worst = float((load.max(axis=1) / load.mean(axis=1)).max())
    held = float((load[:, :8].sum(axis=1) / load.sum(axis=1)).max())
    if collapsed:
        assert worst > 6.0, worst
    else:
        assert worst < 3.0 and held < 0.25, (worst, held)


# -- tracing ----------------------------------------------------------------

def test_layer_kinds_say_swa_and_full():
    from harmony_tpu.metrics import kda as kinds

    cfg = _config(APP)
    assert cfg.layer_kinds() == ("full", "swa", "swa", "swa")
    kinds.note_layer_kinds("kinds-st", cfg.layer_kinds())
    assert kinds.kinds_by_job()["kinds-st"] == {"full": 1, "swa": 3}


def test_windowed_calls_carry_their_own_names_and_plan_columns(monkeypatch):
    """Lowered for a TPU, a model with both kinds of block holds both sets
    of kernel names; STATUS ``kernel_plans`` rows carry the window, the K/V
    head count and what the band needs of the plan; the masked share is a
    gauge."""
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    app = {**APP, "max_seq": 2048, "window": 256, "dtype": jnp.bfloat16,
           "moe_experts_held": 4}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, 2049), jnp.int32)
    with trace_span("job.build_step", job_id="plan-swa"):
        text = jax.jit(jax.grad(lm.loss)).trace(params, toks).lower(
            lowering_platforms=("tpu",)).as_text()
    for kern in ("fwd", "bwd"):
        assert A._KERNEL_NAMES[kern] in text
        assert A._WIN_KERNEL_NAMES[kern] in text
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-swa"]}
    win, full = rows["harmony_flash_win_fwd"], rows["harmony_flash_fwd"]
    assert (win["window"], win["kv_heads"]) == (256, 2)
    assert (full["window"], full["kv_heads"]) == (0, 2)
    assert win["sub_blocks"] < full["sub_blocks"]
    assert win["band_grid_steps"] <= win["grid_steps"]
    assert 0.0 < win["masked_share"] < 1.0
    fams = parse_exposition(get_registry().expose())
    shares = {labels["kernel"]: float(v) for _, labels, v in
              fams["harmony_flash_masked_share"]["samples"]
              if labels["job"] == "plan-swa"}
    assert set(shares) == set(A._KERNEL_NAMES.values()) | set(
        A._WIN_KERNEL_NAMES.values())
    assert shares["harmony_flash_win_fwd"] == pytest.approx(
        win["masked_share"])


# -- the other models' programs ----------------------------------------------

#: sha256 (first 16 hex digits) of each accepted LM configuration's
#: loss-and-gradient program at its rehearse preset over 1,024 positions in
#: bfloat16, traced for a TPU, RECORDED ON THE PARENT of PR 36 (commit
#: 04416a6) unless said otherwise below: the jaxpr's text (kernel bodies
#: and index maps included), and the lowered StableHLO with each Mosaic kernel's serialized body cut out
#: (it carries source lines). The new fields at their defaults must trace
#: these programs; a PR that means to change one records it again here.
# ALL EIGHT RECORDED AGAIN BY PR 50, which meant to change every one: the
# flash backward is ONE kernel (``harmony_flash_bwd``: dQ resident beside
# dK / dV) where ``_bwd_dkv`` and ``_bwd_dq`` stood, in every model that
# attends. The parent's (commit db33bdf), in the order below: 39d757c8d62c52d0
# / f24e521c6f1b759e, 2072f548af7248f4 / 4b53909c4639758e, c5d9c8027f4acd6d /
# 83dc38593d13b721, 2281e4f1a27b6ac2 / 149e47f8d1a77ef0, 35221c1eb04af432 /
# 715a9f21cc4f8472, bf4fd3bcc0118c99 / cb972f89dd457a6d, bf626cc9ea895962 /
# be5b78b8fdfb037f, a8edc618dcb24dcd / f1350c827e6a9a11
# THE JAXPR'S HASH OF ALL EIGHT RECORDED AGAIN BY PR 53, which names the
# kernels' residuals (ops/residuals.py: the text now prints ``name``
# equations, and the flash primal calls ``_fa_lse_call``) and means to change
# NO lowered program: these presets run without ``remat``, where a name is the
# identity. The lowered text's hash is the PARENT'S (commit 4c7d5be), computed
# there and here under ``_renumbered`` — new with PR 53, because each ``name``
# equation takes a number from the counter that suffixes the private
# functions' symbols (``@take_along_axis_55`` became ``_56``) and lowers to
# nothing else: not one other character of the eight texts moved. The
# parent's pairs under the old rule, in the order below: 660f2021a869c9ab /
# 8a1a3c43bb09c3ac, 75ceda348ce3154c / d43e530dff62a5a3, 531f0959486902da /
# 53d4b25acadbb869, 396810f4ec5de066 / cd739747aab22e0c, 61b6c3772b338a23 /
# c34d611c3e92c6d3, dc40c99d2568e5b5 / 9af63f44b4b6e150, 2188ab4c6c19546e /
# 912e8e5990a52d30, d0ab4711e80a87c5 / beee8b690cf0657b
PARENT_PROGRAMS = {
    "gpt2-124m": ("c632653ef1e75cc7", "51e8ebe50e9558de"),
    # every expert model RECORDED AGAIN BY PR 43, which meant to change these
    # seven and not gpt2's: the router's selection is ``harmony_top_k_rows``
    # (ops/top_k_rows.py) where ``lax.top_k`` + ``take_along_axis`` stood.
    # The parent's (commit f8cd6be), in this order: 99ea9dafb2bff55d /
    # 4c3e6855a920bbd3, 64405e693a7be5ee / dc4d831f97c232bb, 4237db3deb745649
    # / f6de1660c093e2b3
    "olmoe-1b-7b": ("679e6e2726031aa9", "98e4a9fadf9edb6c"),
    "moonlight-16b-a3b": ("e1c71b914c0647fe", "e0d9fda737edf639"),
    # Kimi Linear's two RECORDED AGAIN BY PR 46, which meant to change them
    # and no other: the KDA forward kernel also writes (I + A)^-1 and the
    # backward's body is hand-derived around it (ops/kda.py). The parent's
    # (commit eba0826): 613a652ead1e7c25 / 822ef39f72cedb41 and, chunked,
    # 900b195c6eb54df7 / 76c2b3e32f6b2c41
    "kimi-linear-48b-a3b": ("e2f9575a08338a37", "00b03bcc1f432cca"),
    # the same two with 8 of 64 experts held, top-4: the chunked expert
    # layer and its hand-written backward (the rehearse presets hold half
    # their experts and take the full-length pass); PR 37 recorded these two
    # when the layer's row sums became the kernel of ops/sum_rows.py (the
    # parent's of PR 43: a4926d2815adc9a1 / b95e5090d1b8c013 and
    # 5e16810afaf161cd / a87befccf5a25f54)
    "moonlight-16b-a3b+chunked": ("255853eab0dca75b", "7bea44c20d538df7"),
    "kimi-linear-48b-a3b+chunked": ("c3db8787899d1f74", "984238116a6f6cdd"),
    # SmallThinker's own preset (the parent's of PR 43: ca2c218290bfadc4 /
    # f29d583db0040dd7 and 70aaf04974a21872 / cb19e362794a5553, recorded on
    # the parent of PR 38)
    "smallthinker-21b-a3b": ("709017347b63e3dd", "9bbe6ef02ea94ffa"),
    "smallthinker-21b-a3b+chunked": ("9c01aa78db5036f3", "db51af1082833df1"),
    # Laguna-S-2.1's own preset, recorded by the PR that added it (PR 54:
    # per-kind head counts and rotaries, the gate a head); the seven
    # accepted configurations above and Nemotron-H's and ZAYA1's presets
    # traced the parent's programs under the new fields' defaults (88b5b68807359971 /
    # 27338aa0d2cbb5eb and 0f4b402cd00dfcc0 / 271358fd1d490474 on both trees)
    "laguna-s-2.1": ("50031e1f4b0835bd", "68986f85b99d9687"),
    # PR 56 (the readout and the cross-entropy as ONE op where its plan
    # serves the shape, ops/readout_loss.py) meant to change NO program
    # above and changed none: every rehearse preset is 64 wide, ``plan``
    # returns None there and the plain readout is the parent's, equation for
    # equation (all nine pairs are the parent's, commit f579cd5). What the
    # cells run is pinned at a preset the op engages in — gpt2's (tied) and
    # OLMoE's (a head of its own) 128 wide over 8,192 columns, 2^24 logits
    # — recorded by PR 56; the parent's programs at the same preset, plain:
    # f96d5702cd91ca47 / 04afbd327fc46630 and 6d3d23a449c1ed3a /
    # bfbcf0c316f09398
    "gpt2-124m+readout": ("3cdaeb5b997d6567", "cfe17acfe599734d"),
    "olmoe-1b-7b+readout": ("5574b70d6e1dfaca", "fdc7856073f44563"),
    # Ouro-2.6B's own preset, recorded by the PR that added it (PR 57: 2
    # layers run 4 times over one set of weights, four norms a block, an exit
    # a pass joined by the gate); every pair above is the parent's (commit
    # feca772) under the new fields' defaults, none recorded again
    "ouro-2.6b": ("ffde8f15c4af6b77", "71078731be939ba9"),
    # PR 58 (the rotary turn of q and k as ONE lane-roll kernel where its
    # plan serves the shape, ops/rotary.py) meant to change NO program above
    # and changed none: every preset's heads are 16 wide, ``plan`` returns
    # None there and ``_turned`` is ``rope`` twice, equation for equation —
    # gpt2's (learned positions), Kimi Linear's (no positions) and
    # Moonlight's (latent blocks: their rotary keeps ``rope``) with them,
    # all twelve pairs the parent's (commit 95dff55). What the cells run is
    # pinned at a preset the kernel engages in — 512 wide, heads of 128,
    # 1,024 positions: OLMoE's (QK-norm, q and k read as the projection left
    # them), SmallThinker's (28-over-4 grouped, windowed), Laguna's (two
    # kinds: YaRN on half a head = two rolls, plain on a whole head = one)
    # and Ouro's (a layer's turn four times a step) — RECORDED BY PR 58,
    # which meant to change these four; the parent's programs at the same
    # preset, ``rope`` in XLA, in this order: e7db93dd984b6f4f /
    # dd0f930dc7f05ebd, 42d953ceb953ee36 / 85c61d826e37492f,
    # a99ae645e09b7f70 / 342af6f6bfff2811, 8c72b87ff484cdad /
    # 8bae449d34655260
    "olmoe-1b-7b+rotary": ("094302b80fb7dddb", "7b218ee55bddeb64"),
    "smallthinker-21b-a3b+rotary": ("8dcf353b53475065", "685826e3ec19b101"),
    "laguna-s-2.1+rotary": ("11c72726b3016872", "feff4d686765a0aa"),
    "ouro-2.6b+rotary": ("f93bdbd386b3970a", "9ad9ead714ed8b52"),
    # SDAR's own preset — a block-diffusion step over the tuple (tokens,
    # masked, rate), a norm a head of q and k — RECORDED BY PR 60, which
    # pins it for the first time (no PR before it did, so a later one could
    # have changed it unseen). Its heads are 16 wide: ``plan`` declines, the
    # norm and ``rope`` stay in XLA and the pair IS the parent's (commit
    # 4f856ea, computed there with this file's helper). At the preset the
    # kernel engages in (heads of 128) PR 60 meant to change the program
    # and did: q and k reach ``harmony_rotary`` as the projection left
    # them and the kernel norms each head before it turns it
    # (ops/rotary.py ``turn(..., norm=)``); the parent's there, ``_norm``
    # by heads in XLA round the plain kernel: 810f57d3f7f99992 /
    # d8eb0e80fe4780f8. Every pair above is the parent's, none recorded
    # again: ``turn`` without ``norm`` traces the parent's kernel
    "sdar-30b-a3b": ("b3b0c2d6dad53fa7", "8fa884fbbbb6b95d"),
    "sdar-30b-a3b+rotary": ("b10fe6a45f72cf12", "c6770d511f1b214a"),
    # Qwen3-Next's own preset, RECORDED BY PR 61, the PR that added it (three
    # Gated DeltaNet blocks on the scalar-decay route of ops/kda.py, a gated
    # softmax block with a norm a head and a quarter of it turned, 1 + w
    # norms, a gated shared expert); every pair above is the parent's (commit
    # 6862df0) under the new fields' defaults, none recorded again — Kimi
    # Linear's two above all: ``_pair`` takes its scalar form only where the
    # decay is ``[C, 1]``, and ``_chunk_bwd``'s sums over the channels are
    # the identity where it is not
    # RECORDED AGAIN BY PR 63, which meant to change Qwen3-Next's two and no
    # other: ``harmony_gdn_fwd`` / ``_bwd`` hold eight consecutive chunks of
    # a value head a grid step (``ops.kda.gdn_plan``: 16 chunks a head at
    # this preset), two chunks' pair matrices and solves on one tile, off
    # the state's chain. The jaxprs change (the parent's, commit c97be18:
    # ed150b90b8b57e9e here and 1311e8684a010228 at ``+conv`` below); the
    # lowered texts do not — a kernel's body, its grid with it, is masked
    # out of them. Every other pair is the parent's, Kimi Linear's four
    # among them: ``_solve`` without ``rows`` traces what it traced
    "qwen3-next-80b-a3b": ("02ea663af65eb1e3", "819c84d1ec54b36e"),
    # PR 62 (a delta-rule block's convolution, SiLU, l2 norms and transposes
    # to heads as ONE by-rows kernel where its plan serves the shape,
    # ops/conv_heads.py) meant to change NO program above and changed none:
    # the presets' delta-rule heads are 16 wide, ``plan`` declines them and
    # the mixers keep their own lines, equation for equation — Kimi
    # Linear's two and Qwen3-Next's with them, every pair the parent's
    # (commit ee4a82d). What the two cells run is pinned at the preset with
    # the PUBLISHED head width (128), where the kernel engages — RECORDED BY
    # PR 62, which meant to change these two; the parent's programs at the
    # same preset, ``_causal_conv`` and the rest in XLA: d39dc12fa8834415 /
    # 5d9f5ba01f1e1cfd and c565f9e1f4f01b23 / fc707682eead3b4e
    "kimi-linear-48b-a3b+conv": ("714ab12e60457d2c", "afa5087dc07ad745"),
    "qwen3-next-80b-a3b+conv": ("9e54b78b2fd24047", "b257652905cef403"),
}
CHUNKED = {"moe_experts": 64, "moe_top_k": 4, "moe_experts_held": 8}
READOUT = {"d_model": 128, "vocab_size": 8192}
ROTARY = {"d_model": 512, "mha_head_dim": 128}
CONV = {"linear_head_dim": 128}
VARIANTS = {"": {}, "chunked": CHUNKED, "readout": READOUT, "rotary": ROTARY,
            "conv": CONV}


def _renumbered(text):
    """``text`` with the uniquing suffix of every private function's symbol
    (``@take_along_axis_55``) counted again, by symbol name in order of first
    appearance: the lowering takes the number from a counter that every
    lowered equation moves, also one that lowers to nothing."""
    seen = {}

    def again(m):
        base = seen.setdefault(m.group(1), {})
        return "@%s_%d" % (m.group(1), base.setdefault(m.group(2), len(base)))

    return re.sub(r"@(\w+?)_(\d+)\b", again, text)


def _program_hashes(config):
    name, _, variant = config.partition("+")
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        conf = json.load(f)
    app = {**conf["job"]["app_params"], **conf["rehearse"]["app_params"],
           "max_seq": 1024, "dtype": jnp.bfloat16,
           **VARIANTS[variant]}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 1025), jnp.int32)
    if lm.config.objective == "block_diffusion":  # (tokens, masked, rate)
        toks = tuple(jax.ShapeDtypeStruct((2, n), t) for n, t in (
            (1024, jnp.int32), (1024, jnp.int8),
            (1024 // lm.config.diffusion_block, jnp.float32)))
    fn = jax.value_and_grad(lm.loss_and_metrics, has_aux=True)
    jaxpr = str(jax.make_jaxpr(fn)(params, toks))
    text = jax.jit(fn).trace(params, toks).lower(
        lowering_platforms=("tpu",)).as_text()
    text = _renumbered(re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY",
                              text))
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (jaxpr, text))


@pytest.mark.parametrize("config", sorted(PARENT_PROGRAMS))
def test_other_models_step_programs_are_the_parents(monkeypatch, config):
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    assert _program_hashes(config) == PARENT_PROGRAMS[config]
