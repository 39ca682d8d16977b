"""The rotary kernel (ops/rotary.py), on the CPU in interpret mode: against
``models.transformer.rope`` — the reference and the fallback — forward and
gradient; the norm a head inside it (PR 60) against ``rms_norm`` + ``rope``;
its plan; where ``_softmax_mixer`` takes it and where it keeps ``rope``.
Times and the chip are PERF.md's (PRs 58 and 60)."""
from __future__ import annotations

import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.models import TransformerConfig, TransformerLM
from harmony_tpu.models import transformer as T
from harmony_tpu.models.common import rms_norm
from harmony_tpu.models.transformer import Rotary, rope
from harmony_tpu.ops import rotary as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
#: Laguna's full blocks: YaRN on half a head, cos and sin scaled
YARN = Rotary.of({
    "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
    "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 32,
    "attention_factor": 1.2079441541679836, "partial_rotary_factor": 0.5})
#: name -> (x's shape [B, H, S, hd], dtype, rope's arguments after x)
CASES = {
    "whole-head": ((2, 3, 64, 128), BF16, dict(theta=1e4)),
    "whole-head-f32": ((2, 3, 64, 128), F32, dict(theta=1e4)),
    "half-of-128": ((1, 4, 48, 128), BF16, dict(theta=1e4, width=64)),
    "half-of-128-f32": ((1, 4, 48, 128), F32, dict(theta=1e4, width=64)),
    "yarn-half-scaled": ((1, 6, 64, 128), BF16, dict(
        theta=YARN.theta, width=YARN.width(128), scaled=YARN)),
    "yarn-whole-f32": ((1, 2, 32, 128), F32, dict(
        theta=YARN.theta, scaled=YARN._replace(fraction=1.0))),
    "offset": ((2, 2, 64, 128), BF16, dict(theta=1e6, pos_offset=4096)),
    "256-wide": ((1, 2, 32, 256), BF16, dict(theta=1e4)),
    "256-wide-f32": ((1, 2, 32, 256), F32, dict(theta=1e4)),
    "half-of-256": ((1, 2, 32, 256), BF16, dict(theta=1e4, width=128)),
    "quarter-of-128": ((1, 2, 32, 128), F32, dict(theta=1e4, width=32)),
    "several-row-tiles": ((1, 2, 4096 + 2048, 128), BF16, dict(theta=1e6)),
}


def _x(shape, dtype, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, F32
                             ).astype(dtype)


def _kernel(x, theta, pos_offset=0, width=None, scaled=None):
    """``rope``'s signature through the kernel."""
    tab, shifts = R.tables(x.shape[2], x.shape[3], theta, pos_offset, width,
                           scaled)
    return R.turn(x, tab, shifts, interpret=True)


def _near(got, want, steps, of=None):
    """Within ``steps`` rounding steps of the result's dtype at the
    operands' size (``of``: at that size throughout, a sum's)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    g, w = np.asarray(got.astype(F32)), np.asarray(want.astype(F32))
    size = np.maximum(np.abs(w), 1.0) if of is None else of
    tol = steps * float(jnp.finfo(got.dtype).eps) * size
    assert np.all(np.abs(g - w) <= tol), float(np.max(np.abs(g - w) / tol))


def _close(got, want, dtype):
    """Equal to float32 rounding: a float32 result to a few float32 ulps of
    the operands' size, a bfloat16 one to ONE rounding step (the float32
    sum may fall either side of a tie)."""
    assert got.dtype == dtype
    _near(got, want, 1 if dtype == BF16 else 4)
    # and nearly every element is the reference's to the bit
    assert np.mean(np.asarray(got == want)) > (0.99 if dtype == BF16 else 0.5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_rope(case):
    shape, dtype, args = CASES[case]
    x = _x(shape, dtype)
    _close(_kernel(x, **args), rope(x, **args), dtype)
    tab, shifts = R.tables(shape[2], shape[3], args["theta"],
                           args.get("pos_offset", 0), args.get("width"),
                           args.get("scaled"))
    # the plain form of the kernel's arithmetic is rope's to the bit
    np.testing.assert_array_equal(
        np.asarray(R.turn_ref(x, tab, shifts).astype(F32)),
        np.asarray(rope(x, **args).astype(F32)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_its_gradient_is_ropes(case):
    shape, dtype, args = CASES[case]
    x, w = _x(shape, dtype), _x(shape, F32, seed=1)
    loss = lambda f: lambda x: (f(x, **args).astype(F32) * w).sum()
    got = jax.grad(loss(_kernel))(x)
    want = jax.grad(loss(rope))(x)
    _close(got, want, dtype)


#: name -> (B, H, S, hd, rope's arguments): x lies [B, S, H hd]
ROW_CASES = {
    "sixteen-heads": (2, 16, 64, 128, dict(theta=1e4)),
    "groups-of-four": (1, 28, 64, 128, dict(theta=1e6, pos_offset=9)),
    "nine-heads-in-one": (1, 9, 48, 128, dict(theta=1e4)),
    "six-heads-half-turned": (1, 6, 48, 128, dict(
        theta=YARN.theta, width=64, scaled=YARN)),
    "one-head": (2, 1, 32, 128, dict(theta=1e4)),
    "256-wide": (1, 2, 32, 256, dict(theta=1e4)),
}


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_the_transpose_to_heads_as_the_index_map(case, dtype):
    """``x [B, S, H hd]`` as a projection leaves it: the kernel's result is
    ``rope`` of XLA's transpose, and its gradient comes back ``[B, S, H
    hd]``."""
    B, H, S, hd, args = ROW_CASES[case]
    x, w = _x((B, S, H * hd), dtype), _x((B, H, S, hd), F32, seed=1)
    to_heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    tab, shifts = R.tables(S, hd, args["theta"], args.get("pos_offset", 0),
                           args.get("width"), args.get("scaled"))
    by_rows = lambda x: R.turn(x, tab, shifts, heads=H, interpret=True)
    plain = lambda x: rope(to_heads(x), **args)
    _close(by_rows(x), plain(x), dtype)
    loss = lambda f: lambda x: (f(x).astype(F32) * w).sum()
    _close(jax.grad(loss(by_rows))(x), jax.grad(loss(plain))(x), dtype)


#: name -> (B, H, S, eps, rope's arguments): x lies [B, S, H hd], ``_GROUP``
#: heads a step or (9, 3, 2, 1) all of them. Row tiles of 2,048 and of 16
#: among them
NORM_CASES = {
    "three-heads": (2, 3, 64, 1e-6, dict(theta=1e4)),
    "two-heads-eps-1e-5": (1, 2, 64, 1e-5, dict(theta=1e4)),
    "four-heads-half-turned": (1, 4, 48, 1e-6, dict(theta=1e4, width=64)),
    "thirty-two-heads": (1, 32, 64, 1e-6, dict(theta=1e6)),
    "four-heads": (2, 4, 64, 1e-6, dict(theta=1e6, pos_offset=9)),
    "four-heads-eps-1e-5": (2, 4, 64, 1e-5, dict(theta=1e6)),
    "nine-heads-in-one": (1, 9, 48, 1e-6, dict(theta=1e4)),
    "three-heads-half-turned": (1, 3, 48, 1e-5, dict(
        theta=YARN.theta, width=64, scaled=YARN)),
    "rows-of-2048": (1, 4, 2048, 1e-6, dict(theta=1e6)),
    "rows-of-2048-one-head": (1, 1, 4096, 1e-6, dict(theta=1e6)),
    "rows-of-16": (2, 4, 16, 1e-6, dict(theta=1e4)),
    "rows-of-16-two-heads": (1, 2, 16, 1e-5, dict(theta=1e4)),
}


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(NORM_CASES))
def test_the_norm_a_head_inside_the_turn(case, dtype):
    """``turn(..., norm=(w, eps))`` — forward, ``dx`` and ``dw`` — against
    ``turn_ref`` (the norm in float32, ONE rounding: equal but for the
    order of sums) and against the parent's path, ``rms_norm`` in x's dtype
    then ``rope`` (three roundings: near)."""
    B, H, S, eps, args = NORM_CASES[case]
    hd = 128
    x = 3 * _x((B, S, H * hd), dtype)
    w, c = 1 + 0.3 * _x((hd,), F32, seed=2), _x((B, H, S, hd), F32, seed=1)
    to_heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    tab, shifts = R.tables(S, hd, args["theta"], args.get("pos_offset", 0),
                           args.get("width"), args.get("scaled"))
    fused = lambda x, w: R.turn(x, tab, shifts, heads=H, norm=(w, eps),
                                interpret=True)
    plain = lambda x, w: R.turn_ref(to_heads(x), tab, shifts, (w, eps))
    parent = lambda x, w: rope(rms_norm(to_heads(x), w.astype(dtype), eps),
                               **args)
    loss = lambda f: lambda x, w: (f(x, w).astype(F32) * c).sum()
    y, (dx, dw) = fused(x, w), jax.grad(loss(fused), (0, 1))(x, w)
    assert (y.shape, dx.shape, dw.shape, dw.dtype) == (
        (B, H, S, hd), x.shape, (hd,), F32)
    wide = 1 if dtype == BF16 else 16  # float32: the sums' order shows
    want, (want_dx, want_dw) = plain(x, w), jax.grad(loss(plain), (0, 1))(x, w)
    _near(y, want, wide)
    _near(dx, want_dx, wide)
    assert np.mean(np.asarray(y == want)) > (0.99 if dtype == BF16 else 0.5)
    sums = float(jnp.abs(want_dw).max())       # B H S float32 terms a column
    _near(dw, want_dw, 64, of=sums)
    # the parent rounds the unit rows, the weighed rows and the turn's
    # result: a few steps apart in bfloat16, and its dw is a bfloat16 sum
    old, (old_dx, old_dw) = parent(x, w), jax.grad(loss(parent), (0, 1))(x, w)
    _near(y, old, 3 if dtype == BF16 else wide)
    _near(dx, old_dx, 6 if dtype == BF16 else wide)
    np.testing.assert_allclose(dw, old_dw, atol=sums * (
        0.1 if dtype == BF16 else 1e-5))


@pytest.mark.parametrize("case", ["another-width", "by-heads"])
def test_a_norm_the_kernel_does_not_serve_is_refused(case):
    """A weight that is not a head wide; and q or k already by heads (no
    caller norms them there: the norm goes with the walk by rows)."""
    tab, shifts = R.tables(64, 128, 1e4)
    x, w, heads = {"another-width": ((1, 64, 256), 64, 2),
                   "by-heads": ((1, 2, 64, 128), 128, None)}[case]
    with pytest.raises(ValueError, match="norm weight"):
        R.turn(_x(x, BF16), tab, shifts, heads=heads,
               norm=(jnp.ones((w,), F32), 1e-6), interpret=True)


def test_a_traced_offset_is_the_static_ones_turn():
    x = _x((1, 2, 64, 128), BF16)
    traced = jax.jit(lambda x, off: _kernel(x, 1e4, off))(x, jnp.int32(37))
    _close(traced, rope(x, 1e4, 37), BF16)
    _close(traced, jax.jit(lambda x, off: rope(x, 1e4, off))(
        x, jnp.int32(37)), BF16)


@pytest.mark.parametrize("width", [None, 64], ids=["whole", "half"])
def test_turning_back_with_the_sines_negated_is_the_identity(width):
    x = _x((1, 2, 64, 128), F32)
    tab, shifts = R.tables(64, 128, 1e4, 3, width)
    back = jnp.concatenate([tab[:1], -tab[1:]])
    y = R.turn(R.turn(x, tab, shifts, interpret=True), back, shifts,
               interpret=True)
    np.testing.assert_allclose(y, x, atol=2e-6)


# -- the plan -----------------------------------------------------------------

#: the cells whose softmax blocks turn 128-wide heads: positions a sequence
CELLS = {"sdar-30b-a3b": 8192, "smallthinker-21b-a3b": 16384,
         "laguna-s-2.1": 16384, "ouro-2.6b": 4096, "olmoe-1b-7b": 4096,
         "zaya1-8b": 8192}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_plan_serves_the_cells_by_their_shape(config):
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        app = json.load(f)["job"]["app_params"]
    hd = app.get("mha_head_dim") or app["d_model"] // app["n_heads"]
    assert (hd, app["max_seq"]) == (128, CELLS[config])
    assert R.plan(app["max_seq"], hd, BF16) == (2048, 1)


@pytest.mark.parametrize("shape", [
    (64, 64, BF16),       # half a lane tile: the test models, the presets
    (64, 192, BF16),      # a lane tile and a half
    (1000, 128, BF16),    # no row tile divides the positions
    (8, 128, F32),        # under the least tile
    (64, 128, jnp.float16),
], ids=str)
def test_plan_declines(shape):
    assert R.plan(*shape) is None
    with pytest.raises(ValueError, match="no plan serves"):
        x = jnp.zeros((1, 1, shape[0], shape[1]), shape[2])
        R.turn(x, jnp.zeros((2,) + x.shape[2:], F32), (shape[1] // 2,),
               interpret=True)


def test_plan_halves_the_rows_of_a_wider_head():
    assert R.plan(8192, 256, BF16) == (1024, 1)
    assert R.plan(48, 128, BF16) == (16, 1)


# -- in the mixer ---------------------------------------------------------------

@pytest.fixture
def as_tpu(monkeypatch):
    """``_turned`` steered as a TPU trace would steer it, the kernel
    interpreted."""
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    monkeypatch.setattr(R, "turn", functools.partial(R.turn, interpret=True))


def _pallas_calls(jaxpr, out, shapes=None):
    """Every ``harmony_rotary`` call under ``jaxpr``: the id of the jitted
    body that holds it (and into ``shapes`` the rows operand's shape)."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == R.KERNEL_NAME):
            out.append(id(jaxpr))
            if shapes is not None:
                shapes.append(eqn.invars[1].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out, shapes)
    return out


def _head_rsqrts(jaxpr, out):
    """The ``rsqrt`` equations over one statistic a head and position
    (``[B, H, S, 1]``) under ``jaxpr``, kernel bodies left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "rsqrt" and eqn.invars[0].aval.ndim == 4:
            out.append(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _head_rsqrts(sub, out)
    return out


def test_grouped_heads_share_the_tables(as_tpu):
    q, k = _x((2, 8, 64, 128), BF16), _x((2, 2, 64, 128), BF16, seed=1)
    got = T._turned(q, k, 1e6, 5)
    _close(got[0], rope(q, 1e6, 5), BF16)
    _close(got[1], rope(k, 1e6, 5), BF16)
    text = str(jax.make_jaxpr(lambda q, k: T._turned(q, k, 1e6, 5))(q, k))
    assert text.count(" cos ") == 1 and text.count(" sin ") == 1


def test_one_traced_body_a_shape_not_one_a_layer(as_tpu):
    q, k = _x((1, 8, 64, 128), BF16), _x((1, 2, 64, 128), BF16, seed=1)

    def layers(q, k):
        for _ in range(3):
            q, k = T._turned(q, k, 1e6, 0)
        return (q.astype(F32) ** 2).sum() + (k.astype(F32) ** 2).sum()

    fwd = _pallas_calls(jax.make_jaxpr(layers)(q, k).jaxpr, [])
    both = _pallas_calls(
        jax.make_jaxpr(jax.grad(layers, (0, 1)))(q, k).jaxpr, [])
    assert (len(fwd), len(set(fwd))) == (6, 2)       # q's body and k's
    # ... and one each for the backward's traces of the same call
    assert (len(both), len(set(both))) == (12, 4)


@pytest.mark.parametrize("why,shape,tpu", [
    ("a 64-wide head", (1, 4, 64, 64), True),
    ("rows no tile divides", (1, 4, 40, 128), True),
    ("a CPU trace", (1, 4, 64, 128), False),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_fallbacks_trace_todays_program(monkeypatch, why, shape, tpu):
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: tpu)
    q, k = _x(shape, BF16), _x(shape[:1] + (2,) + shape[2:], BF16, seed=1)
    part = {"width": shape[3] // 2}
    got = jax.make_jaxpr(lambda q, k: T._turned(q, k, 1e4, 3, **part))(q, k)
    want = jax.make_jaxpr(lambda q, k: (rope(q, 1e4, 3, **part),
                                        rope(k, 1e4, 3, **part)))(q, k)
    assert str(got) == str(want)
    assert "pallas_call" not in str(got)


def test_the_plan_row_says_which_path_a_job_took(as_tpu):
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    wide, narrow = _x((1, 4, 64, 128), BF16), _x((1, 4, 64, 64), BF16)
    with trace_span("job.build_step", job_id="plan-rotary"):
        T._turned(wide, wide[:, :2], 1e4, 0, width=64)
    with trace_span("job.build_step", job_id="plan-rope"):
        T._turned(narrow, narrow[:, :2], 1e4, 0)
    by_rows = _x((1, 64, 4 * 128), BF16)
    with trace_span("job.build_step", job_id="plan-normed"):  # q alone
        T._turned(by_rows, by_rows[..., :256], 1e4, 0, (4, 2),
                  ((jnp.ones((128,), F32), 1e-6), None))
    plans = progcache.kernel_plans()
    rows = [r for r in plans["plan-rotary"] if r["kernel"] == R.KERNEL_NAME]
    assert {(r["block_q"], r["d"], r["dv"], r["sub"], r["grid_steps"],
             r["normed"]) for r in rows} == {
        (64, 128, 64, 4, 4, False), (64, 128, 64, 2, 2, False)}
    assert {(r["sub"], r["normed"]) for r in plans["plan-normed"]
            if r["kernel"] == R.KERNEL_NAME} == {(4, True), (2, False)}
    assert not [r for r in plans.get("plan-rope", ())
                if r["kernel"] == R.KERNEL_NAME]


def _lm(**over):
    return TransformerLM(TransformerConfig(**{**dict(
        vocab_size=256, d_model=256, n_heads=2, n_layers=2, d_ff=128,
        max_seq=64, pos="rope", attn="blockwise", dtype=BF16), **over}))


@pytest.mark.parametrize("over", [
    {}, {"n_kv_heads": 1, "mha_head_dim": 128, "n_heads": 4},
    {"rope_fraction": 0.5}, {"remat": True}, {"head_norm": True},
    {"qk_norm": True},
], ids=["plain", "grouped", "half-turned", "remat", "head-norm", "qk-norm"])
def test_the_models_loss_and_gradient_through_the_kernel(monkeypatch, over):
    from harmony_tpu.utils import platform

    lm = _lm(**over)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    plain = jax.jit(jax.value_and_grad(lm.loss))(params, toks)
    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    monkeypatch.setattr(R, "turn", functools.partial(R.turn, interpret=True))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lm.loss))(params, toks)
    shapes = []
    calls = len(_pallas_calls(jaxpr.jaxpr, [], shapes))
    # a layer's q and k, forward and backward (and the forward again under
    # remat)
    assert calls == 2 * 2 * (3 if over.get("remat") else 2)
    # q (and k, if it has several heads) comes as the projection left it,
    # [B, S, H hd], a norm a head with it (a forward's operand: the
    # backward's lies by heads)
    by_rows = sum(shape[-1] > 128 for shape in shapes)
    assert by_rows >= calls // 4
    # ... and the kernel norms the heads: outside it no rsqrt of one row a
    # head [B, H, S, 1] is left, where the plain trace has q's and k's
    assert not _head_rsqrts(jaxpr.jaxpr, [])
    if over.get("head_norm"):
        with monkeypatch.context() as m:
            m.setattr(platform, "trace_is_tpu", lambda: False)
            assert _head_rsqrts(jax.make_jaxpr(jax.value_and_grad(
                lm.loss))(params, toks).jaxpr, [])
    fused = jax.jit(jax.value_and_grad(lm.loss))(params, toks)
    np.testing.assert_allclose(fused[0], plain[0], rtol=2e-3)
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(F32))
                             / jnp.linalg.norm(b.astype(F32)))
    assert rel(fused[1]["layers"][0]["wqkv"],
               plain[1]["layers"][0]["wqkv"]) < 2e-2


@pytest.mark.parametrize("config", sorted(
    os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "perf", "configs", "*.json"))
    if "criteo" not in p))
def test_the_rehearse_presets_keep_rope(config):
    """Every preset's heads are 16 wide: no plan, today's program (what
    ``tests/test_smallthinker.py`` pins by hash)."""
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        conf = json.load(f)
    app = {**conf["job"]["app_params"], **conf["rehearse"]["app_params"]}
    hd = app.get("mha_head_dim") or app["d_model"] // app["n_heads"]
    assert R.plan(app["max_seq"], hd, F32) is None
