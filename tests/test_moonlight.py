"""The DeepSeek-V3-shaped block on the normal path (PR 29; Moonlight-16B-A3B's):
latent attention with a 24-wide q.k beside a 16-wide v, a leading dense layer
of its own width, a sigmoid router with a gradient-free selection bias,
renormalised and scaled top-k weights, a shared expert, the sequence-wise
balance loss — ``TransformerLM`` with the architecture fields against the
plain reference the benchmark ships (``perf/reference/moonlight-16b-a3b.py``:
float32, a masked softmax in query blocks, a loop over the held experts with
a dense mask, no sort, no kernel). And what the block forced on the shared
kernels: ``flash_attention`` with a value width of its own, grouped-matmul
tiles at a width that is a multiple of 128 and of no power of two above it.

Small, float32, seeded: d 64, 4 heads of (16 + 8, 16), latent 24, dense width
96, 8 experts of width 32, top-2, one shared, sequence 32. Tolerances: both
sides are float32 on the CPU and differ only in the order of sums, so 1e-5
relative holds everywhere — three decades under the smallest effect of
breaking a piece of the mathematics
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
from harmony_tpu.ops.attention import (  # noqa: E402
    blockwise_attention, flash_attention, tile_plan)
from harmony_tpu.ops import grouped_matmul as gmm  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "moonlight-16b-a3b")
RTOL = 1e-5
APP = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=32,
           max_seq=32, pos="rope", rope_theta=50000.0, ffn="swiglu",
           tie_embeddings=False, norm_eps=1e-5, attn_kind="mla",
           kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, moe_first_dense=1, dense_d_ff=96, moe_experts=8,
           moe_top_k=2, moe_every=1, moe_shared_experts=1,
           moe_score="sigmoid", moe_norm_topk=True, moe_routed_scale=2.446,
           moe_seq_aux=True, moe_aux_weight=0.001)
HELD = [None, 4]  # every expert here; experts 0..3 of the 8
#: the program's names for the reference's leaves
DENSE = {"w1": "wg", "w3": "wu", "w2": "wd"}
EXPERTS = {"router": "router", "wg": "eg", "wu": "eu", "wd": "ed",
           "shared_wg": "sg", "shared_wu": "su", "shared_wd": "sd"}
LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln1", "ln2")


def _app(held):
    return APP if held is None else {**APP, "moe_experts_held": held}


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, APP["vocab_size"], (batch, APP["max_seq"] + 1)), jnp.int32)


def _both(held, seed=5, biased=True):
    """Program and reference from one seed; ``biased``: the same non-zero
    selection bias on both sides (as initialised it is zero and selects
    nothing)."""
    app = _app(held)
    lm = TransformerLM(TransformerConfig(**app))
    params, ref = lm.init(jax.random.PRNGKey(seed)), REF.init_params(app, seed)
    if biased:
        bias = REF.seeded_bias(app, seed)
        params["layers"][1]["moe"]["bias"] = ref["layers"][1]["bias"] = bias
    return lm, params, REF._Static(app), ref


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert float(np.abs(got - want).max()) <= rtol * scale, (
        float(np.abs(got - want).max()) / scale)


# -- the block against the reference ---------------------------------------

@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("held", HELD)
def test_logits_match_reference(held, biased):
    lm, params, app, ref = _both(held, biased=biased)
    toks = _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        _close(lm.apply(params, toks), REF.forward(ref, toks, app)[0])


@pytest.mark.parametrize("held", HELD)
def test_loss_terms_match_reference(held):
    """Cross-entropy and the sequence-wise balance term each on its own."""
    lm, params, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        loss, m = lm.loss_and_metrics(params, toks)
        ce, aux = REF.loss_terms(ref, toks, app)
        chosen = REF.forward(ref, toks[:, :-1], app)[2]
    for got, want in ((m["ce"], ce), (m["aux_seq"], aux),
                      (loss, ce + 0.001 * aux)):
        _close(got, want)
    assert set(m) == {"ce", "aux_seq", "moe_expert_tokens"}
    # ONE expert layer (block 1): the dense block 0 routes nothing
    tokens = np.asarray(m["moe_expert_tokens"])
    assert tokens.shape == (1, 8) and lm.config.moe_layers() == (1,)
    assert tokens.sum() == 2 * toks[:, :-1].size
    np.testing.assert_array_equal(tokens[0], np.asarray(chosen[0]))


@pytest.mark.parametrize("held", HELD)
def test_gradients_match_reference(held):
    lm, params, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lm.loss)(params, toks)
        want = jax.grad(REF.loss_fn)(ref, toks, app)
    for key in ("embed", "head", "ln_f"):
        _close(got[key], want[key])
    for g, w in zip(got["layers"], want["layers"]):
        for key in LATENT:
            _close(g[key], w[key])
    for ours, theirs in DENSE.items():
        _close(got["layers"][0][ours], want["layers"][0][theirs])
    for ours, theirs in EXPERTS.items():
        _close(got["layers"][1]["moe"][ours], want["layers"][1][theirs])
    # the selection bias carries no gradient, on either side
    assert not np.asarray(got["layers"][1]["moe"]["bias"]).any()
    assert not np.asarray(want["layers"][1]["bias"]).any()


@pytest.mark.parametrize("held", HELD)
@pytest.mark.parametrize("ablate", REF.ABLATIONS)
def test_tolerance_tells_broken_arithmetic_apart(ablate, held):
    """Every named ablation moves what it reaches — the logits, or for the
    balance loss the loss — by far more than RTOL, with all and with half
    the experts held."""
    _, _, app, ref = _both(held)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        if ablate == "no_aux":
            whole = float(REF.loss_fn(ref, toks, app))
            moved = abs(float(REF.loss_fn(ref, toks, app, ablate)) - whole
                        ) / whole
        else:
            moved = REF.rel_rms(
                REF.forward(ref, toks[:, :-1], app, ablate)[0],
                REF.forward(ref, toks[:, :-1], app)[0])
    assert moved > 20 * RTOL, (ablate, moved)
    if ablate != "no_aux":
        assert moved > 100 * RTOL, (ablate, moved)


def test_selection_bias_moves_the_choice_never_the_weights():
    """``I = top-k(s + b)``; ``w_e = scale * s_e / sum_{j in I} s_j``: with a
    bias other experts are chosen, and whatever is chosen weighs what the
    unbiased scores say."""
    from harmony_tpu.models.moe import _route

    lm, params, _, _ = _both(None, biased=False)
    cfg, layer = lm.config.dropless_cfg, dict(params["layers"][1]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    score = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, layer["router"], precision=jax.lax.Precision.HIGHEST)))
    chosen = {}
    for name, bias in (("zero", np.zeros(8, np.float32)),
                       ("seeded", np.asarray(REF.seeded_bias(APP, 5)))):
        layer["bias"] = jnp.asarray(bias)
        gate, expert, _, tokens, _ = _route(layer, x, cfg, 2)
        expert = np.asarray(expert)
        np.testing.assert_array_equal(
            np.sort(expert, axis=1),
            np.sort(np.argsort(-(score + bias), axis=1)[:, :2], axis=1))
        s = np.take_along_axis(score, expert, axis=1)
        _close(gate, 2.446 * s / s.sum(axis=1, keepdims=True), 1e-6)
        assert int(np.asarray(tokens).sum()) == 128
        chosen[name] = np.sort(expert, axis=1)
    moved = (chosen["zero"] != chosen["seeded"]).any(axis=1)
    assert 0 < moved.sum() < 64


def test_a_token_with_no_held_expert_gets_the_shared_expert_only():
    from harmony_tpu.models.moe import moe_ffn_dropless

    lm, params, _, _ = _both(4, biased=False)
    cfg, layer = lm.config.dropless_cfg, params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    out, _ = moe_ffn_dropless(layer, x, cfg, seqs=2)
    score = jax.nn.sigmoid(x @ layer["router"])
    unheld = np.asarray((jax.lax.top_k(score, 2)[1] >= 4).all(axis=1))
    assert 0 < unheld.sum() < 64
    shared = (jax.nn.silu(x @ layer["shared_wg"]) * (x @ layer["shared_wu"])
              ) @ layer["shared_wd"]
    _close(np.asarray(out)[unheld], np.asarray(shared)[unheld])
    assert float(np.abs(np.asarray(out - shared)[~unheld]).max()) > 1e-3


def test_replay_sees_each_ablation_and_adam_beta2():
    data = (np.asarray(_tokens(seed=3, batch=4)),)
    app = {**APP, "optimizer": "adam", "step_size": 1e-3, "beta2": 0.95}
    full = REF.replay(app, data, 2, 4, seed=0)
    assert len(full) == 4 and full[-1] < full[0]
    assert REF.replay({**app, "beta2": 0.999}, data, 2, 4, seed=0,
                      logits=False)[3] != full[3]
    for ablate in ("no_shared", "no_aux"):
        assert REF.replay(app, data, 2, 1, seed=0, ablate=ablate)[0] != full[0]
    with pytest.raises(ValueError, match="unknown ablation"):
        REF.replay(app, data, 2, 1, seed=0, ablate="no_such")


def test_replay_begins_with_the_programs_logits(monkeypatch, capsys):
    """What the cell's ``correct`` evaluates starts with the program's
    logits against the reference's, as initialised and under a seeded bias:
    it passes as the program stands, it fails when the tolerance cannot tell
    the ablations apart, and a program that leaves out the routed scale
    turns every replayed loss into ``nan``."""
    import dataclasses
    import json

    app = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "seed": 5}
    toks = np.asarray(_tokens())
    report = REF.check_logits(app, toks[:, :-1], 5)
    assert report["ok"] and set(report["program"]) == {"as_initialised",
                                                       "seeded_bias"}
    for errors in report["program"].values():
        assert max(errors.values()) < 1e-5 < report["q90_tol"]
    assert set(report["ablations_q90"]) == set(
        REF.LOGIT_ABLATIONS + REF.BIAS_ABLATIONS)
    assert min(report["ablations_q90"].values()) > 100 * report["q90_tol"]
    monkeypatch.setitem(REF.LOGITS_Q90_TOL, "float32", 10.0)
    assert not REF.check_logits(app, toks[:, :-1], 5)["ok"]
    monkeypatch.undo()
    whole = TransformerLM.apply
    monkeypatch.setattr(TransformerLM, "apply", lambda self, p, t: whole(
        TransformerLM(dataclasses.replace(self.config, moe_routed_scale=1.0)),
        p, t))
    capsys.readouterr()
    losses = REF.replay(app, (toks,), 2, 2, seed=5)
    assert len(losses) == 2 and all(np.isnan(losses))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "logits_check" and not line["ok"]
    assert line["program"]["as_initialised"]["q90"] > line["q90_tol"]


# -- flash attention with a value width of its own (Pallas interpreter) --------

def _naive(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        mask = jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _qkv(sq, sk, d, dv, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, 2, sq, d), jnp.float32),
            jax.random.normal(ks[1], (1, 2, sk, d), jnp.float32),
            jax.random.normal(ks[2], (1, 2, sk, dv), jnp.float32),
            jax.random.normal(ks[3], (1, 2, sq, dv), jnp.float32))


@pytest.mark.parametrize("sq,sk,d,dv,causal,blocks", [
    (512, 512, 24, 16, True, {}),       # the plan's tiles: one resident
                                        # block, sub-blocks walked in-kernel
    (256, 256, 192, 128, True, {}),     # the cell's widths, one block
    (128, 256, 24, 16, True, {"block_q": 64, "block_k": 64}),   # the causal
                                        # clamps and skips, explicit blocks
    (256, 128, 16, 24, False, {"block_q": 32, "block_k": 64}),  # v the wider
    (197, 197, 24, 8, True, {}),        # one block of a length off the tile
])
def test_flash_with_a_value_width_matches_naive_and_blockwise(
        sq, sk, d, dv, causal, blocks):
    """Forward and all three gradients: the output and dV are ``dv`` wide,
    dQ and dK ``d`` wide; the blockwise tier computes the same."""
    q, k, v, w = _qkv(sq, sk, d, dv)
    out = flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
    assert out.shape == (1, 2, sq, dv)
    np.testing.assert_allclose(out, _naive(q, k, v, causal), atol=2e-5)
    np.testing.assert_allclose(blockwise_attention(q, k, v, causal=causal),
                               _naive(q, k, v, causal), atol=2e-5)
    fns = (lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                           interpret=True, **blocks),
           lambda q, k, v: blockwise_attention(q, k, v, causal=causal,
                                               block_k=64),
           lambda q, k, v: _naive(q, k, v, causal))
    flash, block, naive = (jax.grad(lambda q, k, v, f=f: (f(q, k, v) * w
                                                          ).sum(),
                                    argnums=(0, 1, 2))(q, k, v) for f in fns)
    for a, b, c, ref in zip(flash, block, naive, (q, k, v)):
        assert a.shape == ref.shape
        np.testing.assert_allclose(a, c, atol=2e-4)
        np.testing.assert_allclose(b, c, atol=2e-4)


@pytest.mark.parametrize("sq,sk,d,dtype", [
    (1024, 1024, 64, jnp.bfloat16),     # the gpt2 cells
    (4096, 4096, 128, jnp.bfloat16),    # olmoe-1b-7b.solo
    (197, 197, 64, jnp.bfloat16),       # ViT-B/16
    (8192, 8192, 128, jnp.float32),
])
def test_equal_widths_give_the_plans_of_today(sq, sk, d, dtype):
    """``dv`` given and equal to ``d`` is no ``dv`` given; the pinned tiles
    are those the cells ran before a value width existed."""
    plan = tile_plan(sq, sk, d, dtype, True)
    assert plan == tile_plan(sq, sk, d, dtype, True, dv=d)
    if (sq, d) == (1024, 64):
        assert [t[:3] for t in plan[:2]] == [
            (512, 1024, 1024), (1024, 512, 512)]
        assert all(t.vmem_limit_bytes is None for t in plan[:2])
    if (sq, d) == (4096, 128):
        assert [t[:3] for t in plan[:2]] == [
            (512, 4096, 1024), (4096, 512, 512)]
        # 18.5 MiB as the dK/dV kernel had + a head's resident dQ (4096
        # rows x 128 lanes x (4 + 2 x 2) B = 4 MiB) + Mosaic's 16
        assert plan.bwd.vmem_limit_bytes == (22.5 + 16) * 2**20


def test_the_plan_counts_a_192_wide_tile_as_256_lanes():
    from harmony_tpu.models.common import flash_ok
    from harmony_tpu.ops import attention as A

    shape = lambda d, dv: A._Shape(d, dv, 2, 8192, 0)
    for kern in ("fwd", "bwd"):
        assert (A._vmem_bytes(kern, 512, 8192, 512, shape(192, 128))
                == A._vmem_bytes(kern, 512, 8192, 512, shape(256, 128)))
        assert (A._vmem_bytes(kern, 512, 8192, 512, shape(192, 128))
                < A._vmem_bytes(kern, 512, 8192, 512, shape(192, 192)))
    # the cell's call: 8192 positions, (192, 128), bf16 — the whole K and V
    # stay resident under a vmem limit, as at 4096 x 128
    plan = tile_plan(8192, 8192, 192, jnp.bfloat16, True, dv=128)
    assert [t[:3] for t in plan[:2]] == [(512, 8192, 1024), (8192, 512, 512)]
    assert all(t.vmem_limit_bytes for t in plan[:2])
    # the backward: 35.25 MiB of tiles, accumulators and temporaries as the
    # dK/dV kernel had, a head's dQ resident beside them (8192 rows x 256
    # lanes: 8 MiB f32 + 2 x 4 MiB of output block), Mosaic's 16 on top
    assert plan.bwd.vmem_limit_bytes == (51.25 + 16) * 2**20
    assert flash_ok(8192, head_dim=192, v_head_dim=128)
    assert not flash_ok(8200, head_dim=192, v_head_dim=128)


def test_unequal_widths_are_refused_where_nothing_computes_them():
    q, k, v, _ = _qkv(64, 64, 24, 16)
    with pytest.raises(ValueError, match="only v may have a width"):
        flash_attention(q, k[..., :16], v, interpret=True)
    from harmony_tpu.ops.ring import ring_attention

    with pytest.raises(ValueError, match="one head width"):
        ring_attention(q, k, v, axis_name="seq")


def test_both_widths_reach_kernel_plans():
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    q = jax.ShapeDtypeStruct((2, 16, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 16, 8192, 128), jnp.bfloat16)
    with trace_span("job.build_step", job_id="plan-mla"):
        jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum())).trace(q, q, v)
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-mla"]}
    assert set(rows) == {"harmony_flash_fwd", "harmony_flash_bwd"}
    assert all((r["d"], r["dv"]) == (192, 128) for r in rows.values())
    assert rows["harmony_flash_fwd"]["grid_steps"] == 32 * 16


# -- grouped matmul at a width of 11 x 128 and of 3 x 128 -----------------------

def _loop(x, w, sizes):
    out = jnp.zeros((x.shape[0], w.shape[2]), x.dtype)
    start = 0
    for g, n in enumerate(sizes):
        out = out.at[start:start + n].set(x[start:start + n] @ w[g])
        start += n
    return out


@pytest.mark.parametrize("k,n", [(64, 384), (384, 64)])
def test_grouped_matmul_at_three_times_128(k, n):
    """Forward and both backward products with a width (as n, then as k)
    that is a multiple of 128 and of no listed tile above it: one block."""
    m, sizes = 300, [100, 3, 0, 150]
    assert gmm.tile_plan(m, k, n, jnp.float32)[1:] == (k, n)
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    _close(gmm.grouped_matmul(x, w, gs, interpret=True), _loop(x, w, sizes))
    got = jax.grad(lambda x, w: (gmm.grouped_matmul(x, w, gs, interpret=True)
                                 * c).sum(), (0, 1))(x, w)
    want = jax.grad(lambda x, w: (_loop(x, w, sizes) * c).sum(), (0, 1))(x, w)
    _close(got[0], want[0])
    _close(got[1], want[1])


def test_grouped_matmul_tiles_keep_1408_whole():
    # the cell's shapes, bf16: 1408 = 11 x 128 is one tile, the other width
    # gives way until a step fits the default scoped VMEM
    assert tuple(gmm.tile_plan(98304, 2048, 1408, jnp.bfloat16)) == (
        512, 512, 1408)
    assert tuple(gmm.tile_plan(98304, 1408, 2048, jnp.bfloat16)) == (
        512, 1408, 512)
    for kern in gmm.KERNEL_NAMES:
        assert gmm._vmem_bytes(kern, gmm.Tiles(512, 512, 1408), 2
                               ) <= gmm._VMEM_FREE
    # OLMoE's plan is what it was; 128 itself is still a tile
    assert tuple(gmm.tile_plan(65536, 2048, 1024, jnp.bfloat16)) == (
        512, 1024, 1024)
    assert tuple(gmm.tile_plan(512, 128, 128, jnp.bfloat16)) == (512, 128, 128)


# -- parameters: layouts, refusals ----------------------------------------------

def test_init_traced_abstractly_has_inits_layout_for_the_new_block():
    for held in HELD:
        model = TransformerLM(TransformerConfig(**_app(held)))
        a, b = model.init(jax.random.PRNGKey(0)), jax.eval_shape(
            model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        assert (jax.tree_util.tree_structure(a)
                == jax.tree_util.tree_structure(b))
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert la.shape == lb.shape and la.dtype == lb.dtype
    params = TransformerLM(TransformerConfig(**_app(4))).init(
        jax.random.PRNGKey(0))
    dense, expert = params["layers"]
    assert "moe" not in dense and dense["w1"].shape == (64, 96)
    assert "wqkv" not in dense and dense["wq"].shape == (64, 4 * 24)
    assert dense["wkv_a"].shape == (64, 24 + 8)
    assert dense["wkv_b"].shape == (24, 4 * 32) and dense["wo"].shape == (64, 64)
    moe = expert["moe"]
    assert moe["wg"].shape == (4, 64, 32) and moe["router"].shape == (64, 8)
    assert moe["shared_wd"].shape == (32, 64) and not np.asarray(moe["bias"]).any()


@pytest.mark.parametrize("kw, match", [
    ({"attn_kind": "gqa"}, "unknown attn_kind"),
    ({"attn_kind": "mha"}, "latent attention"),
    ({"v_head_dim": 0}, "attn_kind='mla' needs"),
    ({"pos": "learned"}, "attn_kind='mla' needs"),
    ({"qk_norm": True}, "attn_kind='mla' needs"),
    ({"qk_rope_head_dim": 7}, "odd"),
    ({"moe_score": "tanh"}, "unknown moe_score"),
    ({"moe_seq_aux": False}, "sequence-wise"),
    ({"moe_z_weight": 0.001}, "no router z-loss"),
    ({"moe_top_k": 0}, "dropless"),
    ({"moe_first_dense": 3}, "moe_first_dense"),
    ({"moe_first_dense": 0}, "dense_d_ff"),
    ({"moe_experts": 0, "moe_top_k": 0, "moe_shared_experts": 0,
      "moe_score": "softmax", "moe_norm_topk": False, "moe_routed_scale": 1.0,
      "moe_seq_aux": False}, "moe_first_dense"),
])
def test_inconsistent_architecture_fields_are_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**APP, **kw})


def test_expert_layers_are_one_answer():
    """``moe_layers`` is what ``is_moe_layer`` says, leading dense layers
    left out, for the three patterns the repo has."""
    cfg = TransformerConfig(**{**APP, "n_layers": 4})
    assert cfg.moe_layers() == (1, 2, 3)
    assert [cfg.ffn_width(i) for i in range(4)] == [96, 32, 32, 32]
    switch = TransformerConfig(vocab_size=8, n_layers=4, moe_experts=4)
    assert switch.moe_layers() == (1, 3)  # the last of every pair
    assert TransformerConfig(vocab_size=8, n_layers=4).moe_layers() == ()


def test_side_steps_and_decode_refuse_the_new_block():
    from harmony_tpu.models import make_generate_fn
    from harmony_tpu.models.transformer import make_pp_train_step

    lm = TransformerLM(TransformerConfig(**APP))
    with pytest.raises(ValueError, match="GPT-2-era block"):
        make_generate_fn(lm, 4, 4)
    with pytest.raises(ValueError, match="GPT-2-era block"):
        make_pp_train_step(lm, None)
    # latent attention alone is refused too, experts or not
    dense = TransformerConfig(**{
        **APP, "moe_experts": 0, "moe_top_k": 0, "moe_first_dense": 0,
        "dense_d_ff": 0, "moe_shared_experts": 0, "moe_score": "softmax",
        "moe_norm_topk": False, "moe_routed_scale": 1.0, "moe_seq_aux": False,
        "ffn": "gelu", "tie_embeddings": True})
    with pytest.raises(ValueError,
                       match="GPT-2-era block .* attn_kind / kv_lora_rank"):
        dense.require_classic_block("a side step")


# -- the job path: trainer, vectors out of the step, counters, STATUS -------------

def test_trainer_step_reports_terms_and_one_expert_layer():
    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**_app(4), optimizer="adam", step_size=1e-3,
                            beta2=0.95, row_width=256)
    model = jnp.zeros((tr.capacity, 256), jnp.float32)
    delta, m = jax.jit(tr.compute)(model, _tokens(), {
        k: jnp.float32(v) for k, v in tr.hyperparams().items()})
    assert delta.shape == model.shape
    assert set(m) == {"loss", "ce", "aux_seq", "moe_expert_tokens"}
    assert m["moe_expert_tokens"].shape == (1, 8) and m["loss"].shape == ()


def test_counters_carry_the_blocks_index():
    from harmony_tpu.metrics import moe
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    per_step = np.zeros((3, 1, 8))
    per_step[:, 0, :] = [4, 2, 2, 0, 8, 0, 0, 0]
    moe.observe("moonlight-unit", per_step, experts_held=4, layers=(1,))
    fams = parse_exposition(get_registry().expose())
    tokens = {(l["layer"], l["expert"]): v for _, l, v in
              fams["harmony_moe_expert_tokens_total"]["samples"]
              if l["job"] == "moonlight-unit"}
    assert set(layer for layer, _ in tokens) == {"1"} and len(tokens) == 8
    assert tokens[("1", "4")] == 24.0
    row = moe.stats_by_job()["moonlight-unit"]
    assert row["held_slot_share"] == pytest.approx(8 / 16)
    assert row["load_max_over_mean"] == pytest.approx(4 / 2)
    reader = load_by_path("layer_metrics", "expert_load_max_over_mean")
    assert reader.read({"phases": {"moonlight-unit": None}}) == pytest.approx(2)
    with pytest.raises(ValueError):
        moe.observe("moonlight-unit", per_step, 4, layers=(0, 1))


def _submit(job_id, trainer, app, epochs=8):
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool

    data_args = {"num_seqs": 2, "seq_len": 33, "vocab_size": 96, "seed": 7}
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


JOB_APP = {**_app(4), "dtype": "float32", "optimizer": "adam",
           "step_size": 1e-3, "beta2": 0.95, "seed": 11}


def test_a_tiny_moonlight_tenant_through_the_jobserver_equals_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    TransformerTrainer and JSON app_params: the first steps' losses are the
    reference's replay (float32 both sides), the routing reaches the
    counters under the expert block's index, and both kernels' plans reach
    kernel_plans with their widths."""
    from perf.generators import random_tokens

    losses, status, data_args = _submit(
        "moonlight-tiny", "harmony_tpu.models.transformer:TransformerTrainer",
        JOB_APP)
    want = REF.replay(JOB_APP, (random_tokens.make(**data_args),), 2, 4,
                      seed=11)
    assert np.allclose(losses[:4], want, rtol=1e-5, atol=0), (losses, want)
    from harmony_tpu.metrics import moe
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.runtime import progcache

    row = moe.stats_by_job()["moonlight-tiny"]
    assert 0.0 < row["held_slot_share"] < 1.0
    assert row["load_max_over_mean"] >= 1.0
    assert status["tenants"]["moonlight-tiny"]["moe"] == row
    layers = {l["layer"] for _, l, _ in parse_exposition(
        get_registry().expose())["harmony_moe_expert_tokens_total"]["samples"]
        if l["job"] == "moonlight-tiny"}
    assert layers == {"1"}  # the dense block 0 shows no idle experts
    # 4 of 8 experts held: nothing to cut, the plain path, no chunk counted;
    # one expert layer a step, every drained step a call
    fams = parse_exposition(get_registry().expose())
    chunks, calls = (sum(v for _, l, v in fams[name]["samples"]
                         if l["job"] == "moonlight-tiny")
                     for name in ("harmony_moe_chunks_total",
                                  "harmony_moe_layer_calls_total"))
    assert chunks == 0 and calls >= 4
    plans = {p["kernel"]: p for p in progcache.kernel_plans().get(
        "moonlight-tiny", [])}
    assert {"harmony_gmm_fwd", "harmony_gmm_dx", "harmony_gmm_dw"} <= set(plans)
    assert {(p["d"], p["dv"]) for name, p in plans.items()
            if name.startswith("harmony_gmm_")} == {(64, 32), (32, 64)}
    # the router's selection (PR 43): top-2 of 8, sigmoid scores + bias
    assert (plans["harmony_top_k_rows"]["block_k"],
            plans["harmony_top_k_rows"]["sub"]) == (8, 2)


def test_the_bias_row_is_bit_equal_after_adam_steps_through_the_jobserver():
    """The selection bias is a row of the model table like any other and
    the table's Adam visits it every step: a zero gradient leaves m = v = 0
    and the row as it was, to the last bit, whatever its value."""
    from tests.helpers import SeededBiasLMTrainer

    SeededBiasLMTrainer.seen.pop("moonlight-bias", None)
    losses, _, _ = _submit("moonlight-bias",
                           "tests.helpers:SeededBiasLMTrainer", JOB_APP)
    seen = np.concatenate(SeededBiasLMTrainer.seen["moonlight-bias"])
    assert seen.shape == (8, 1, 8)  # 8 steps: the row after 0 .. 7 updates
    assert np.abs(seen[0]).min() > 0.0
    for step in seen[1:]:
        assert step.tobytes() == seen[0].tobytes()
    assert losses[-1] < losses[0]  # while everything else did move
