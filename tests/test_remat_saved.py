"""What a rematerialised block keeps (PR 53): the kernels' ``fwd`` rules and
the router name their residuals (``ops/residuals.py``), and ``remat``
checkpoints a block under ONE ``save_only_these_names`` policy
(``models/transformer.py`` ``_remat``) — so a kernel's forward appears once a
layer in the loss-and-gradient program where a bare ``jax.checkpoint`` ran it
twice, the values are the same to the last bit, and STATUS ``remat_saved``
lists what is kept.

Two layers a model, tiny shapes, float32, the kernels in the Pallas
interpreter on the CPU: one case a kernel family, each through the wrap its
kind of model takes (``block``, ``window_layers``, ``layer_pattern``).
"""
from __future__ import annotations

import functools
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
from harmony_tpu.ops import attention, kda, residuals as R, ssd  # noqa: E402
from harmony_tpu.runtime import progcache  # noqa: E402
from harmony_tpu.tracing import trace_span  # noqa: E402
from perf.generators import block_diffusion_tokens  # noqa: E402

DENSE = dict(vocab_size=96, d_model=64, n_heads=2, n_layers=2, d_ff=64,
             max_seq=256, pos="rope", ffn="swiglu", tie_embeddings=False,
             attn="flash")
EXPERTS = dict(moe_experts=8, moe_top_k=2, moe_every=1, moe_norm_topk=True,
               moe_aux_weight=0.001)
# two KDA blocks (a chunk of 64 and a part of one); two Mamba-2 layers (two
# chunks of 32 and a part): dense, no experts
KDA = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=32,
           max_seq=80, pos="none", ffn="swiglu", tie_embeddings=False,
           attn_kind="mla", kv_lora_rank=24, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, linear_layers=[0, 1],
           linear_heads=2, linear_head_dim=16, short_conv=4)
SSD = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, mha_head_dim=16,
           n_layers=2, d_ff=48, max_seq=80, pos="none", tie_embeddings=False,
           layer_pattern="MM", ssd_heads=4, ssd_head_dim=8, ssd_groups=2,
           ssd_state=16, ssd_chunk=32, short_conv=4)


#: family -> (app_params, {forward kernel: calls a layer-pass}, {backward
#: kernel: calls}, the names the policy keeps). ``calls`` are the kernel's
#: ``pallas_call``s in ONE pass over the model's two (mixer) layers.
FAMILIES = {
    "flash": (DENSE, {"harmony_flash_fwd": 2}, {"harmony_flash_bwd": 2},
              {R.FLASH_OUT: 2, R.FLASH_LSE: 2}),
    "flash_win": ({**DENSE, "window": 128, "window_layers": [1]},
                  {"harmony_flash_fwd": 1, "harmony_flash_win_fwd": 1},
                  {"harmony_flash_bwd": 1, "harmony_flash_win_bwd": 1},
                  {R.FLASH_OUT: 2, R.FLASH_LSE: 2}),
    "flash_bd": ({**DENSE, "objective": "block_diffusion",
                  "diffusion_block": 4, "mask_token": 95},
                 {"harmony_flash_bd_fwd": 2}, {"harmony_flash_bd_bwd": 2},
                 {R.FLASH_OUT: 2, R.FLASH_LSE: 2}),
    "kda": (KDA,
            {"harmony_kda_fwd": 2}, {"harmony_kda_bwd": 2},
            {R.KDA_OUT: 2, R.KDA_STATE: 2, R.KDA_SOLVE: 2}),
    "ssd": (SSD,
            {"harmony_ssd_fwd": 2}, {"harmony_ssd_bwd": 2},
            {R.SSD_OUT: 2, R.SSD_STATE: 2}),
    "router": ({**DENSE, **EXPERTS, "attn": "blockwise", "max_seq": 32},
               {"harmony_top_k_rows": 2}, {},
               {R.ROUTER_LOGITS: 2, R.ROUTER_WEIGHT: 2, R.ROUTER_EXPERT: 2}),
}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The models' kernels in the Pallas interpreter: the ops the models
    look up at trace time, with ``interpret=True`` (the router's selection
    interprets itself off a TPU)."""
    flash = attention.flash_attention_lse

    def flash_lse(q, k, v, causal=False, block_q=None, block_k=None,
                  scale=None, interpret=False, window=None,
                  diffusion_block=None):
        return flash(q, k, v, causal, block_q, block_k, scale, True, window,
                     diffusion_block)

    monkeypatch.setattr(attention, "flash_attention_lse", flash_lse)
    monkeypatch.setattr(kda, "kda_attention", functools.partial(
        kda.kda_attention, interpret=True))
    monkeypatch.setattr(ssd, "ssd_scan", functools.partial(
        ssd.ssd_scan, interpret=True))


def _lm(app, remat):
    return TransformerLM(TransformerConfig(**app, remat=remat))


def _batch(app, seed=0):
    if app.get("objective") == "block_diffusion":
        return tuple(jnp.asarray(a) for a in block_diffusion_tokens.make(
            2, app["max_seq"], app["vocab_size"], app["diffusion_block"],
            seed=seed))
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, app["vocab_size"], (2, app["max_seq"] + 1)), jnp.int32)


def _bare(monkeypatch):
    """The parent's ``remat``: a bare ``jax.checkpoint`` of the same body."""
    monkeypatch.setattr(T, "_remat", lambda f, kept: jax.checkpoint(f))


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


def _program(app, remat):
    lm = _lm(app, remat)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    return _kernel_calls(jax.make_jaxpr(jax.grad(lm.loss))(
        params, _batch(app)).jaxpr)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_forward_kernel_runs_once_a_layer(monkeypatch, family):
    """In the loss-and-gradient program of a two-layer ``remat`` model each
    forward kernel appears once a layer — as without ``remat`` — and each
    backward kernel once; under a bare ``jax.checkpoint`` (the parent's) the
    forward kernels appear twice."""
    app, fwd, bwd, _ = FAMILIES[family]
    once = {**fwd, **bwd}
    for remat in (True, False):
        calls = _program(app, remat)
        assert {k: calls.get(k) for k in once} == once, (remat, calls)
    _bare(monkeypatch)
    calls = _program(app, True)
    twice = {**{k: 2 * n for k, n in fwd.items()}, **bwd}
    assert {k: calls.get(k) for k in twice} == twice, calls


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_loss_and_gradients_are_bit_equal(monkeypatch, family):
    """The kept arrays are the arrays the second forward would compute: loss
    and every gradient leaf are EQUAL under the policy, under a bare
    ``jax.checkpoint`` and without ``remat``."""
    app = FAMILIES[family][0]
    batch = _batch(app, seed=3)
    params = _lm(app, False).init(jax.random.PRNGKey(7))

    def run(remat):
        loss, grads = jax.value_and_grad(_lm(app, remat).loss)(params, batch)
        return [np.asarray(a) for a in jax.tree.leaves((loss, grads))]

    kept, plain = run(True), run(False)
    _bare(monkeypatch)
    bare = run(True)
    assert np.isfinite(kept[0]) and any(np.abs(g).max() > 0 for g in kept[1:])
    for a, b, c in zip(kept, bare, plain):
        assert np.array_equal(a, b) and np.array_equal(a, c)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_status_lists_what_is_kept(family):
    """STATUS ``remat_saved`` of a traced ``remat`` step: every name of the
    family with its arrays and bytes a step; of a step without ``remat``
    nothing — no residual is added, no record made."""
    app, _, _, names = FAMILIES[family]
    rows = {}
    for remat in (False, True):
        lm = _lm(app, remat)
        params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
        job = f"remat-{family}-{int(remat)}"
        with trace_span("job.build_step", job_id=job):
            jaxpr = jax.make_jaxpr(jax.grad(lm.loss))(params, _batch(app))
        rows[remat] = {r["name"]: r for r in
                       progcache.remat_saved().get(job, [])}
        if not remat:
            assert "checkpoint" not in str(jaxpr)
    assert rows[False] == {}
    kept = rows[True]
    assert set(names) <= set(kept) <= set(R.NAMES)
    for name, arrays in names.items():
        assert kept[name]["arrays"] == arrays and kept[name]["bytes"] > 0
    if family == "flash":  # out [2, 2, 256, 32] + lse [2, 2, 256] f32, twice
        assert kept[R.FLASH_OUT]["bytes"] == 2 * 2 * 2 * 256 * 32 * 4
        assert kept[R.FLASH_LSE]["bytes"] == 2 * 2 * 2 * 256 * 4


def test_one_policy_one_tuple():
    """The three wraps are one helper over one tuple of names, and a name is
    kept only where a ``fwd`` rule or the router says it."""
    import inspect

    source = inspect.getsource(T)
    assert source.count("jax.checkpoint(") == 1
    assert len(set(R.NAMES)) == len(R.NAMES) == 10
    with R.collecting() as kept:
        R.keep(jnp.zeros((3, 5), jnp.bfloat16), R.FLASH_OUT)
    assert kept == {R.FLASH_OUT: [1, 30]}
    with R.collecting() as kept:
        pass
    assert kept == {}
