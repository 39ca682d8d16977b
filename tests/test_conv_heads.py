"""The delta-rule blocks' by-rows kernel (ops/conv_heads.py), on the CPU in
interpret mode: against the lines it replaces where it engages —
``_causal_conv`` + ``silu`` + ``_l2norm`` + the transposes to heads, and
``jax.vjp`` of them — forward and the gradients of ``x`` and ``taps``; its
plan; where ``_gdn_mixer`` and ``_kda_mixer`` take it and where they keep
their lines. Times and the chip are PERF.md's (PR 62)."""
from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.models import TransformerConfig, TransformerLM
from harmony_tpu.models import transformer as T
from harmony_tpu.ops import conv_heads as C

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
EPS = T.KDA_L2_EPS
Q, K_, V = "l2_scaled", "l2", "plain"
#: name -> (B, S, columns past the sections, taps, sections). Row tiles of
#: 16 (S = 48: three, so a tile has a neighbour on both sides), of 1,024
#: (S = 2,048: two) and of 32; groups of 4, 2 and 1 heads a step
CASES = {
    "q-alone": (2, 48, 0, 4, ((Q, 4),)),
    "k-alone": (2, 48, 0, 4, ((K_, 4),)),
    "v-alone": (2, 48, 0, 4, ((V, 4),)),
    "one-tile": (1, 16, 0, 4, ((Q, 2), (V, 2))),
    "one-head-a-step": (1, 32, 0, 4, ((Q, 1), (K_, 1), (V, 3))),
    "two-heads-a-step": (1, 48, 128, 4, ((Q, 2), (K_, 2), (V, 6))),
    "qwen3-next-qkvz": (1, 48, 512, 4, ((Q, 4), (K_, 4), (V, 8))),
    "rows-of-1024": (1, 2048, 0, 4, ((K_, 4),)),
    "two-taps": (1, 48, 0, 2, ((Q, 4), (V, 4))),
    "nine-taps": (2, 32, 0, 9, ((K_, 2),)),
    "one-tap": (1, 32, 0, 1, ((V, 2),)),
}
DTYPES = pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])


def _operands(case, dtype):
    B, S, rest, K, sections = CASES[case]
    conv = 128 * sum(h for _, h in sections)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3 + len(sections))
    x = jax.random.normal(ks[0], (B, S, conv + rest), F32).astype(dtype)
    taps = 0.5 * jax.random.normal(ks[1], (K, conv), F32)
    ws = [jax.random.normal(k, (B, h, S, 128), F32)
          for k, (_, h) in zip(ks[2:], sections)]
    return x, taps, sections, ws


def _lines(x, taps, sections):
    """The lines of ``_gdn_mixer`` / ``_kda_mixer`` the kernel stands for,
    with their ``astype(cfg.dtype)``."""
    B, S = x.shape[0], x.shape[1]
    heads = lambda t: t.reshape(B, S, -1, 128).transpose(0, 2, 1, 3)
    a = jax.nn.silu(T._causal_conv(x[..., :taps.shape[1]], taps))
    bounds = np.cumsum([128 * h for _, h in sections])[:-1]
    out = []
    for (kind, _), t in zip(sections, jnp.split(a, bounds, axis=-1)):
        t = heads(t)
        if kind != V:
            t = T._l2norm(t)
        out.append((t * 128 ** -0.5 if kind == Q else t).astype(x.dtype))
    return tuple(out)


def _kernel(x, taps, sections):
    return C.conv_heads(x, taps, sections, EPS, interpret=True)


def _near(got, want, steps, of=None):
    """Within ``steps`` rounding steps of the result's dtype at the
    operands' size (``of``: at that size throughout, a sum's)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    g, w = np.asarray(got.astype(F32)), np.asarray(want.astype(F32))
    size = np.maximum(np.abs(w), 1.0) if of is None else of
    tol = steps * float(jnp.finfo(got.dtype).eps) * size
    assert np.all(np.abs(g - w) <= tol), float(np.max(np.abs(g - w) / tol))


@DTYPES
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_mixers_lines(case, dtype):
    x, taps, sections, _ = _operands(case, dtype)
    got, want = _kernel(x, taps, sections), _lines(x, taps, sections)
    assert len(got) == len(sections)
    for g, w, (_, heads) in zip(got, want, sections):
        assert g.shape == (x.shape[0], heads, x.shape[1], 128)
        # ONE rounding: a bfloat16 result may fall the other side of a tie
        _near(g, w, 1 if dtype == BF16 else 8)
        assert np.mean(np.asarray(g == w)) > (0.99 if dtype == BF16 else 0.3)
    # the plain form of the kernel's arithmetic is those lines to the bit
    for r, w in zip(C.conv_heads_ref(x, taps, sections, EPS), want):
        np.testing.assert_array_equal(np.asarray(r.astype(F32)),
                                      np.asarray(w.astype(F32)))


@DTYPES
@pytest.mark.parametrize("case", sorted(CASES))
def test_its_gradients_are_the_lines(case, dtype):
    """``dx`` in x's dtype — the halo of ``dpre`` crosses a tile's END, the
    halo of ``x`` its start — and ``dtaps`` a float32 sum over all rows;
    columns past the sections (``z``) come back exactly zero."""
    x, taps, sections, ws = _operands(case, dtype)
    conv = taps.shape[1]
    loss = lambda f: lambda x, t: sum(
        (o.astype(F32) * w).sum() for o, w in zip(f(x, t, sections), ws))
    dx, dt = jax.grad(loss(_kernel), (0, 1))(x, taps)
    want_dx, want_dt = jax.grad(loss(_lines), (0, 1))(x, taps)
    assert (dx.dtype, dt.dtype, dt.shape) == (dtype, F32, taps.shape)
    scale = float(jnp.abs(want_dx.astype(F32)).max())
    _near(dx, want_dx, 2 if dtype == BF16 else 32, of=scale)
    _near(dt, want_dt, 64, of=float(jnp.abs(want_dt).max()))
    assert not np.asarray(dx[..., conv:]).any()
    if conv < x.shape[2]:
        assert not np.asarray(want_dx[..., conv:]).any()


@pytest.mark.parametrize("kind", C.KINDS)
def test_the_first_positions_see_zeros_before_them(kind):
    """Position ``t < K - 1`` convolves ``t + 1`` rows and zeros, in the
    first tile only: the rows a later tile borrows are its neighbour's."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 256), F32)
    taps = jnp.asarray([[0.5], [-1.0], [2.0], [0.25]], F32) * jnp.ones(
        (1, 256), F32)
    y, = _kernel(x, taps, ((kind, 2),))
    rows = lambda t: x[0, t].reshape(2, 128)
    finish = {V: lambda a: a,
              K_: lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True)
                                              + EPS),
              Q: lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True)
                                             + EPS) * 128 ** -0.5}[kind]
    want0 = finish(jax.nn.silu(0.25 * rows(0)))
    want1 = finish(jax.nn.silu(2.0 * rows(0) + 0.25 * rows(1)))
    want16 = finish(jax.nn.silu(0.5 * rows(13) - rows(14) + 2.0 * rows(15)
                                + 0.25 * rows(16)))
    for t, want in ((0, want0), (1, want1), (16, want16)):
        np.testing.assert_allclose(y[0, :, t], want, rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("at", [15, 16, 31, 47])
def test_a_row_reaches_the_next_three_and_no_other(at):
    """Causal over the tiles' borders (rows of 16): x at position ``at``
    moves the outputs at ``at .. at + 3`` and its gradient gathers from
    them, whichever tile holds them."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, 128), F32)
    taps = 0.5 + jax.random.uniform(jax.random.PRNGKey(2), (4, 128), F32)
    f = lambda x: _kernel(x, taps, ((V, 1),))[0]
    moved = np.asarray(jnp.abs(f(x.at[0, at].add(1.0)) - f(x))[0, 0].max(-1))
    assert set(np.nonzero(moved)[0]) == set(range(at, min(at + 4, 48)))
    cot = jnp.zeros((1, 1, 48, 128), F32).at[0, 0, at].set(1.0)
    dx, = jax.vjp(f, x)[1](cot)
    reached = np.asarray(jnp.abs(dx)[0].max(-1))
    assert set(np.nonzero(reached)[0]) == set(range(max(at - 3, 0), at + 1))


@DTYPES
def test_kimi_linears_three_calls(dtype):
    """Three projections, three tap sets, one section a call — the same
    entry ``_kda_mixer`` makes."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    for kind, kx, kt in zip((Q, K_, V), ks[:3], ks[3:]):
        x = jax.random.normal(kx, (1, 64, 1024), F32).astype(dtype)
        taps = 0.5 * jax.random.normal(kt, (4, 1024), F32)
        got, = _kernel(x, taps, ((kind, 8),))
        want, = _lines(x, taps, ((kind, 8),))
        _near(got, want, 1 if dtype == BF16 else 8)


# -- the plan -----------------------------------------------------------------

#: the delta-rule cells: positions a sequence, the sections a call, its tiles
CELLS = {
    "qwen3-next-80b-a3b": (16384, [((Q, 16), (K_, 16), (V, 32))]),
    "kimi-linear-48b-a3b": (8192, [((Q, 8),), ((K_, 8),), ((V, 8),)]),
}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_plan_serves_the_cells_by_their_shape(config):
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        app = json.load(f)["job"]["app_params"]
    positions, calls = CELLS[config]
    assert (app["linear_head_dim"], app["max_seq"], app["short_conv"]) == (
        128, positions, 4)
    key = app["linear_heads"]
    assert calls[0][0][1] == key and calls[-1][-1][1] == app.get(
        "linear_value_heads", key)
    for sections in calls:
        assert C.plan(positions, 128, BF16, sections, 4) == (1024, 4)


@pytest.mark.parametrize("why,args", [
    ("a 64-wide head", (64, 64, BF16, ((Q, 4),))),
    ("a 256-wide head", (64, 256, BF16, ((V, 4),))),
    ("rows no tile divides", (1000, 128, BF16, ((Q, 4),))),
    ("under the least tile", (8, 128, F32, ((K_, 4),))),
    ("float16", (64, 128, jnp.float16, ((Q, 4),))),
    ("int8", (64, 128, jnp.int8, ((V, 4),))),
    ("ten taps", (64, 128, BF16, ((Q, 4),), 10)),
    ("no taps", (64, 128, BF16, ((Q, 4),), 0)),
    ("no section", (64, 128, BF16, ())),
    ("a section of no heads", (64, 128, BF16, ((Q, 4), (V, 0)))),
    ("a kind it does not know", (64, 128, BF16, (("rms", 4),))),
], ids=lambda v: v if isinstance(v, str) else "")
def test_plan_declines(why, args):
    assert C.plan(*args) is None
    positions, hd, dtype, sections = args[:4]
    heads = sum(h for _, h in sections) or 1
    with pytest.raises(ValueError, match="no plan serves"):
        C.conv_heads(jnp.zeros((1, positions, heads * hd), dtype),
                     jnp.zeros((args[4] if len(args) > 4 else 4,
                                heads * hd), F32), sections, EPS,
                     interpret=True)


@pytest.mark.parametrize("why,columns,conv", [
    ("taps wider than the sections", 512, 640),
    ("taps narrower than the sections", 512, 384),
    ("an operand narrower than its sections", 384, 512),
], ids=lambda v: v if isinstance(v, str) else "")
def test_widths_that_do_not_agree_are_refused(why, columns, conv):
    with pytest.raises(ValueError, match="no plan serves"):
        C.conv_heads(jnp.zeros((1, 32, columns), BF16),
                     jnp.zeros((4, conv), F32), ((Q, 4),), EPS,
                     interpret=True)


def test_plan_takes_the_heads_a_step_that_divide_every_section():
    assert C.plan(48, 128, BF16, ((Q, 4), (V, 8))) == (16, 4)
    assert C.plan(48, 128, BF16, ((Q, 2), (V, 8))) == (16, 2)
    assert C.plan(2048, 128, F32, ((Q, 3), (V, 8))) == (1024, 1)
    assert C.plan(512 * 3, 128, BF16, ((V, 8),)) == (512, 4)


# -- in the mixers --------------------------------------------------------------

def _app(config, **over):
    """A cell's configuration at its rehearse preset's sizes but the
    PUBLISHED delta-rule head width (the presets' heads are 16 wide and the
    plan declines them)."""
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        conf = json.load(f)
    app = {**conf["job"]["app_params"], **conf["rehearse"]["app_params"],
           "vocab_size": 96, "linear_head_dim": 128, "max_seq": 48, **over}
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    return TransformerConfig(**{k: v for k, v in app.items() if k in fields})


MODELS = ["qwen3-next-80b-a3b", "kimi-linear-48b-a3b"]


@pytest.fixture
def kernel_in_the_mixers(monkeypatch):
    """``_conv_to_heads`` answered as a TPU trace answers it — and only it:
    the scans, flash and the rest keep their CPU routes —, the kernel
    interpreted."""
    from harmony_tpu.utils import platform

    sound = T._conv_to_heads
    monkeypatch.setattr(C, "conv_heads",
                        functools.partial(C.conv_heads, interpret=True))

    def steered(*args):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(platform, "trace_is_tpu", lambda: True)
            return sound(*args)

    monkeypatch.setattr(T, "_conv_to_heads", steered)


def _conv_calls(jaxpr, out):
    """The heads of every ``harmony_conv_heads`` call's by-heads operand
    under ``jaxpr`` (the forward's output, the backward's ``dy``)."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == C.KERNEL_NAME):
            out.append(max(v.aval.shape[1] for v in
                           (*eqn.invars, *eqn.outvars) if v.aval.ndim == 4))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _conv_calls(sub, out)
    return out


def _loss_and_grads(config):
    lm = TransformerLM(_app(config))
    params = lm.init(jax.random.PRNGKey(5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 96, (2, lm.config.max_seq + 1)), jnp.int32)
    return jax.jit(jax.value_and_grad(lm.loss))(params, toks)


@pytest.mark.parametrize("config", MODELS)
def test_a_model_through_the_kernel_is_the_model_through_its_lines(
        config, request):
    """Loss and every gradient of a float32 model whose delta-rule blocks
    hand q, k, v over through the kernel against the same model through
    the mixers' own lines."""
    want_loss, want = _loss_and_grads(config)
    request.getfixturevalue("kernel_in_the_mixers")
    loss, got = _loss_and_grads(config)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.abs(w).max()) or 1.0
        np.testing.assert_allclose(
            g, w, atol=2e-4 * scale, rtol=1e-3,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("config,fwd", [
    # q | k | v of ONE projection: three sections a block, three blocks
    ("qwen3-next-80b-a3b", [2, 2, 4] * 3),
    # three projections a block, a section each, two blocks
    ("kimi-linear-48b-a3b", [2, 2, 2] * 2),
])
def test_the_mixers_call_it_a_section_at_a_time(config, fwd,
                                                kernel_in_the_mixers):
    lm = TransformerLM(_app(config))
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((1, lm.config.max_seq + 1), jnp.int32)
    assert _conv_calls(jax.make_jaxpr(lm.loss)(params, toks).jaxpr,
                       []) == fwd
    both = _conv_calls(jax.make_jaxpr(jax.grad(lm.loss))(params, toks).jaxpr,
                       [])
    assert sorted(both) == sorted(fwd * 2)


@pytest.mark.parametrize("config", MODELS)
@pytest.mark.parametrize("why,over,tpu", [
    ("a 16-wide head", {"linear_head_dim": 16}, True),
    ("a 64-wide head", {"linear_head_dim": 64}, True),
    ("rows no tile divides", {"max_seq": 40}, True),
    ("a CPU trace", {}, False),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_fallbacks_keep_the_mixers_lines(monkeypatch, config, why, over,
                                             tpu):
    """Where the plan declines, and off the TPU, no kernel is traced: the
    mixers' own lines are (``tests/test_smallthinker.py`` pins their text
    at the presets)."""
    from harmony_tpu.utils import platform

    cfg = _app(config, **over)
    x = jax.ShapeDtypeStruct((1, cfg.max_seq, 128 * 8), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 128 * 8), F32)
    dh = cfg.linear_head_dim
    monkeypatch.setattr(platform, "trace_is_tpu", lambda: tpu)
    monkeypatch.setattr(C, "conv_heads", lambda *a, **k: pytest.fail(why))
    assert T._conv_to_heads(x, taps, ((Q, 128 * 8 // dh),), dh) is None


def test_a_mesh_of_several_chips_keeps_the_lines_as_the_scan_does(
        monkeypatch):
    """A ``pallas_call`` is opaque to the partitioner: where the scan takes
    its XLA form (``ops.kda._kernel_route``), so does its hand-over."""
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    monkeypatch.setattr(C, "conv_heads", lambda *a, **k: ((), (), ()))
    x = jax.ShapeDtypeStruct((2, 48, 1024), BF16)
    taps = jax.ShapeDtypeStruct((4, 1024), F32)
    with platform.on_mesh(build_mesh(jax.devices()[:2], data=2)):
        assert T._conv_to_heads(x, taps, ((Q, 8),), 128) is None
    with platform.on_mesh(build_mesh(jax.devices()[:1], data=1)):
        assert T._conv_to_heads(x, taps, ((Q, 8),), 128) is not None


def test_every_trace_notes_the_plan():
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    x = jax.ShapeDtypeStruct((1, 2048, 12 * 128), BF16)
    taps = jax.ShapeDtypeStruct((4, 8 * 128), F32)
    sections = ((Q, 2), (K_, 2), (V, 4))
    with trace_span("job.build_step", job_id="plan-conv-heads"):
        jax.make_jaxpr(lambda x, t: _kernel(x, t, sections))(x, taps)
    row, = [r for r in progcache.kernel_plans()["plan-conv-heads"]
            if r["kernel"] == C.KERNEL_NAME]
    assert (row["block_q"], row["block_k"], row["sub"], row["d"]) == (
        1024, 128, 2, 128)
    # two row tiles of four head groups
    assert (row["grid_steps"], row["sections"]) == (
        8, "l2_scaled:2,l2:2,plain:4")
