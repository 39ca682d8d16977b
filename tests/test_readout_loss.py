"""The readout-and-cross-entropy op (ops/readout_loss.py), on the CPU in
interpret mode: against the plain ``x @ head`` + ``log_softmax`` +
``take_along_axis``, forward and both gradients; its plan; its place in
``TransformerLM``'s two losses. Times and the chip are PERF.md's (PR 56)."""
from __future__ import annotations

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.models import TransformerConfig, TransformerLM
from harmony_tpu.ops import readout_loss as R
from perf.generators import block_diffusion_tokens

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = {f.name for f in dataclasses.fields(TransformerConfig)}
F32, BF16 = jnp.float32, jnp.bfloat16
#: small tiles, each kernel its own, so that a few hundred rows and columns
#: walk several tiles of every grid axis
TILES = R.Plan((128, 256), (64, 512), (128, 128))
LM_CONFIGS = sorted(
    os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(ROOT, "perf", "configs", "*.json"))
    if "criteo" not in p)
#: the ten LM cells' readouts: rows a step, width, held vocabulary, tied
CELLS = {
    "gpt2-124m.solo": (8192, 768, 50257, True),
    "gpt2-124m.pair": (4096, 768, 50257, True),
    "zaya1-8b.solo": (8192, 2048, 32784, True),
    "laguna-s-2.1.solo": (16384, 3072, 12544, False),
    "moonlight-16b-a3b.solo": (16384, 2048, 20480, False),
    "nemotron-3-super-120b-a12b.solo": (8192, 4096, 16384, False),
    "kimi-linear-48b-a3b.solo": (8192, 2304, 20480, False),
    "smallthinker-21b-a3b.solo": (16384, 2560, 18992, False),
    "olmoe-1b-7b.solo": (8192, 2048, 12576, False),
    "sdar-30b-a3b.solo": (8192, 2048, 18992, False),
}


def _operands(n, d, v, tied, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (n, d), F32).astype(dtype)
    head = jax.random.normal(k[1], (v, d) if tied else (d, v), F32) * 0.3
    targets = jax.random.randint(k[2], (n,), 0, v)
    # block diffusion's weights: non-uniform, zeros included
    w = jax.random.uniform(k[3], (n,), F32)
    return x, head, targets, jnp.where(w < 0.3, 0.0, w)


def _plain(x, head, targets, tied):
    """Today's readout and loss, float32 throughout."""
    logits = x.astype(F32) @ (head.T if tied else head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def _rounded(x, head, targets, w, tied):
    """``(nll, dx, dW)`` of the weighted sum by hand at the op's contract:
    operands rounded to bfloat16, float32 everything else, ``g`` rounded to
    bfloat16 on entering its products."""
    xb, hb = x.astype(BF16).astype(F32), head.astype(BF16).astype(F32)
    w2 = hb.T if tied else hb                                  # [d, V]
    logits = jnp.dot(xb, w2, precision="highest")
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    g = (jnp.exp(logits - lse[:, None])
         - jax.nn.one_hot(targets, logits.shape[1])) * w[:, None]
    g = g.astype(BF16).astype(F32)
    dx = jnp.dot(g, w2.T, precision="highest")
    dw = jnp.dot(xb.T, g, precision="highest")
    return nll, dx, dw.T if tied else dw


def _rel(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _op_grads(x, head, targets, w, tied, tiles=TILES):
    def loss(x, head):
        nll = R.readout_nll(x, head, targets, tied=tied, tiles=tiles,
                            interpret=True)
        return (nll * w).sum(), nll
    (_, nll), (dx, dw) = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        x, head)
    return nll, dx, dw


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("n", [256, 200], ids=["rows-whole", "rows-ragged"])
@pytest.mark.parametrize("v", [1024, 1100, 1280], ids=[
    "vocab-whole", "vocab-ragged", "vocab-ragged-to-the-widest-tile-alone"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "own-head"])
def test_the_op_equals_the_plain_readout_and_loss(tied, v, n, dtype):
    x, head, targets, w = _operands(n, 128, v, tied, dtype)
    nll, dx, dw = _op_grads(x, head, targets, w, tied)
    assert (nll.shape, nll.dtype) == ((n,), F32)
    assert (dx.shape, dx.dtype) == (x.shape, x.dtype)
    assert (dw.shape, dw.dtype) == (head.shape, head.dtype)
    # at the op's own rounding: accumulation order alone differs (and, in a
    # bfloat16 x, the rounding of dx to its dtype)
    want_nll, want_dx, want_dw = _rounded(x, head, targets, w, tied)
    np.testing.assert_allclose(nll, want_nll, rtol=0, atol=2e-5)
    assert _rel(dx, want_dx) < (3e-3 if dtype == BF16 else 2e-4)
    assert _rel(dw, want_dw) < 2e-4
    # against the plain float32 program: bfloat16 operands' worth apart
    plain = jax.value_and_grad(
        lambda x, head: (_plain(x, head, targets, tied) * w).sum(), (0, 1))
    _, (plain_dx, plain_dw) = plain(x, head)
    np.testing.assert_allclose(nll, _plain(x, head, targets, tied),
                               rtol=0, atol=0.15)
    assert _rel(dx, plain_dx) < 2e-2 and _rel(dw, plain_dw) < 2e-2


def test_rows_of_zero_weight_are_computed_and_carry_no_gradient():
    x, head, targets, w = _operands(256, 128, 1100, True, F32)
    w = w.at[:100].set(0.0)
    nll, dx, _ = _op_grads(x, head, targets, w, True)
    assert bool(jnp.isfinite(nll).all()) and float(nll[:100].min()) > 0.0
    assert float(jnp.abs(dx[:100]).max()) == 0.0


def test_the_reference_is_the_plain_program_at_the_mxus_rounding():
    x, head, targets, w = _operands(256, 128, 1100, False, BF16)
    np.testing.assert_allclose(
        R.readout_nll_ref(x, head, targets, False),
        _rounded(x, head, targets, w, False)[0], rtol=0, atol=2e-5)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_serves_every_cell_by_its_shape(cell):
    n, d, v, tied = CELLS[cell]
    tiles = R.plan(n, d, v, tied, BF16)
    assert tiles == R.plan(n, d, v, tied, BF16)  # pure
    assert tiles is not None
    for kernel, (tm, tv) in zip((R.FWD_NAME, R.DX_NAME, R.DW_NAME), tiles):
        assert n % tm == 0 and tm % 8 == 0 and tv % 128 == 0
        assert R._vmem_bytes(kernel, tm, tv, d, n, tied) <= R._VMEM_FREE


@pytest.mark.parametrize("config", LM_CONFIGS)
def test_plan_leaves_the_rehearse_presets_to_the_plain_readout(config):
    lm, _, batch = _rehearse(config)
    cfg = lm.config
    tokens = batch[0] if isinstance(batch, tuple) else batch[:, :-1]
    assert R.plan(tokens.size, cfg.d_model, cfg.vocab_size,
                  cfg.tie_embeddings, cfg.dtype) is None


@pytest.mark.parametrize("shape", [
    (2048, 64, 8192), (2048, 200, 8192),    # d not whole lane tiles
    (128, 128, 2**18),                      # under a row tile of rows
    (512, 256, 1024),                       # logits too few to matter
], ids=str)
def test_plan_is_none_outside_what_the_kernels_serve(shape):
    assert R.plan(*shape, True, BF16) is None
    assert R.plan(*shape, False, F32) is None


def test_plan_refuses_other_dtypes_and_the_op_says_so():
    assert R.plan(8192, 768, 50257, True, jnp.float16) is None
    x, head, targets, _ = _operands(256, 64, 512, True, F32)
    with pytest.raises(ValueError, match="no plan"):
        R.readout_nll(x, head, targets, tied=True, interpret=True)
    with pytest.raises(ValueError, match="readout_nll: x"):
        R.readout_nll(x, head.T, targets, tied=True, tiles=TILES,
                      interpret=True)


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_names(sub, out)
    return out


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "own-head"])
def test_one_traced_body_a_kernel_and_pass(tied):
    x, head, targets, w = _operands(256, 128, 1100, tied, BF16)

    def loss(x, head):
        return (R.readout_nll(x, head, targets, tied=tied, tiles=TILES,
                              interpret=True) * w).sum()
    fwd = _pallas_names(jax.make_jaxpr(loss)(x, head).jaxpr, [])
    both = _pallas_names(
        jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, head).jaxpr, [])
    assert fwd == [R.FWD_NAME]
    assert sorted(both) == sorted([R.FWD_NAME, R.DX_NAME, R.DW_NAME])


def test_every_trace_notes_the_three_kernels_plans():
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    x, head, targets, _ = _operands(256, 128, 1100, True, BF16)
    with trace_span("job.build_step", job_id="plan-readout"):
        R.readout_nll(x, head, targets, tied=True, tiles=TILES,
                      interpret=True)
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-readout"]}
    assert set(rows) == {R.FWD_NAME, R.DX_NAME, R.DW_NAME}
    dx = rows[R.DX_NAME]
    assert (dx["block_q"], dx["block_k"], dx["d"], dx["dv"]) == (
        64, 512, 128, 1100)
    assert dx["grid_steps"] == 4 * 3 and dx["planned"]


# -- in the model -------------------------------------------------------------

def _rehearse(config, **over):
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        conf = json.load(f)
    app = {**conf["job"]["app_params"], **conf["rehearse"]["app_params"],
           **over}
    lm = TransformerLM(TransformerConfig(
        **{k: v for k, v in app.items() if k in FIELDS}))
    cfg = lm.config
    if cfg.objective == "block_diffusion":
        batch = tuple(jnp.asarray(a) for a in block_diffusion_tokens.make(
            2, cfg.max_seq, cfg.vocab_size, cfg.diffusion_block, seed=3))
    else:
        batch = jax.random.randint(jax.random.PRNGKey(3),
                                   (2, cfg.max_seq + 1), 0, cfg.vocab_size)
    return lm, lm.init(jax.random.PRNGKey(0)), batch


def _pre_pr_ce(lm, params, batch):
    """The data term as the parent computed it: ``apply``'s logits,
    ``log_softmax``, ``take_along_axis``."""
    cfg = lm.config
    if cfg.objective == "block_diffusion":
        tokens, masked, rate = batch
        logits = lm.apply(params, lm.noised(tokens, masked))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        m = (masked != 0).astype(F32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    logp = jax.nn.log_softmax(lm.apply(params, batch[:, :-1]), axis=-1)
    return -jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1).mean()


@pytest.mark.parametrize("config", LM_CONFIGS)
def test_the_losses_equal_their_pre_pr_values_on_the_rehearse_presets(config):
    lm, params, batch = _rehearse(config)
    loss, metrics = jax.jit(lm.loss_and_metrics)(params, batch)
    ce = metrics.get("ce", loss)  # a dense model's loss is the data term
    want = jax.jit(lambda p, b: _pre_pr_ce(lm, p, b))(params, batch)
    np.testing.assert_allclose(ce, want, rtol=1e-6)


@pytest.mark.parametrize("config", ["gpt2-124m", "olmoe-1b-7b",
                                    "sdar-30b-a3b"])
def test_apply_still_returns_the_logits(config):
    lm, params, batch = _rehearse(config)
    tokens = (lm.noised(*batch[:2]) if isinstance(batch, tuple)
              else batch[:, :-1])
    logits = lm.apply(params, tokens)
    rows = batch[0].shape[0] if isinstance(batch, tuple) else tokens.shape[0]
    assert logits.shape == (rows, tokens.shape[1], lm.config.vocab_size)
    assert logits.dtype == F32
    (x,), _, _ = lm._trunk(params, tokens)  # one pass, one exit (PR 57)
    np.testing.assert_array_equal(logits, lm._readout(params, x))


#: a preset wide and long enough for ``plan``: 2 x 1,024 positions, 128
#: wide, 8,192 columns (2^24 logits)
ENGAGED = {"d_model": 128, "vocab_size": 8192, "max_seq": 1024}


@pytest.mark.parametrize("config", ["gpt2-124m", "olmoe-1b-7b"],
                         ids=["tied", "own-head"])
def test_the_loss_and_its_gradient_through_the_op_in_the_model(config,
                                                               monkeypatch):
    lm, params, batch = _rehearse(config, **ENGAGED)
    assert lm._readout_tiles(jnp.zeros((2, 1024, 128))) is not None
    fused = jax.jit(jax.value_and_grad(lm.loss))(params, batch)
    monkeypatch.setattr(R, "plan", lambda *a: None)
    plain = jax.jit(jax.value_and_grad(lm.loss))(params, batch)
    np.testing.assert_allclose(fused[0], plain[0], rtol=2e-4)
    head = "embed" if lm.config.tie_embeddings else "head"
    assert _rel(fused[1][head], plain[1][head]) < 2e-2
    assert _rel(fused[1]["layers"][0]["ln1"],
                plain[1]["layers"][0]["ln1"]) < 2e-2


def test_block_diffusions_weighted_loss_through_the_op(monkeypatch):
    lm, params, batch = _rehearse("sdar-30b-a3b", **ENGAGED, mask_token=8191)
    assert lm._readout_tiles(jnp.zeros((2, 1024, 128))) is not None
    fused = jax.jit(lm.loss_and_metrics)(params, batch)
    monkeypatch.setattr(R, "plan", lambda *a: None)
    plain = jax.jit(lm.loss_and_metrics)(params, batch)
    for key in ("ce", "masked_share", "diffusion_tokens"):
        np.testing.assert_allclose(fused[1][key], plain[1][key], rtol=2e-4)
    np.testing.assert_allclose(fused[0], plain[0], rtol=2e-4)
