"""MoE / expert parallelism: routing semantics, dense-vs-EP equivalence,
capacity drops, gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from harmony_tpu.models.moe import MoEConfig, init_moe_params, moe_ffn


def _setup(E=4, d=8, f=16, T=32, seed=0, cap=4.0):
    cfg = MoEConfig(num_experts=E, d_model=d, d_ff=f, capacity_factor=cap)
    params = init_moe_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, d), jnp.float32)
    return cfg, params, x


def _reference(params, x, cfg):
    """Per-token expert FFN, no capacity limit (valid when capacity >= T)."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    e = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, e[:, None], 1)[:, 0]
    w1, w2 = params["w1"][e], params["w2"][e]        # [T, d, f], [T, f, d]
    h = jax.nn.gelu(jnp.einsum("td,tdf->tf", x, w1))
    return gate[:, None] * jnp.einsum("tf,tfd->td", h, w2)


def test_moe_matches_per_token_reference():
    cfg, params, x = _setup()
    out, aux = moe_ffn(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_reference(params, x, cfg)),
                               atol=1e-5)
    assert float(aux) >= 1.0 - 1e-6  # Switch aux loss is minimized at 1


def test_capacity_drops_tokens():
    """With capacity 1 per expert, surplus tokens get zero output (callers
    keep the residual so they pass through)."""
    cfg, params, x = _setup(T=32, cap=0.125)  # C = 1
    out, _ = moe_ffn(params, x, cfg)
    zero_rows = np.isclose(np.abs(np.asarray(out)).sum(axis=1), 0.0)
    assert zero_rows.sum() >= 32 - 2 * cfg.num_experts  # most rows dropped
    assert (~zero_rows).sum() >= 1                      # but some got through


def test_expert_parallel_matches_reference(devices):
    """Realistic dp+ep: tokens sharded over the same axis as experts. With
    generous capacity (no drops) every token's output must equal the
    per-token reference."""
    from jax import lax

    cfg, params, x = _setup(E=8, T=64, cap=8.0)
    S = 4
    mesh = Mesh(np.asarray(devices[:S], dtype=object).reshape(S), ("expert",))

    def local_fn(p, xs):
        out, aux = moe_ffn(p, xs, cfg, axis_name="expert")
        return out, lax.pmean(aux, "expert")

    out_ep, aux_ep = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=({"router": P(), "w1": P("expert"), "w2": P("expert")},
                  P("expert")),
        out_specs=(P("expert"), P()),
    )(params, x)
    np.testing.assert_allclose(np.asarray(out_ep),
                               np.asarray(_reference(params, x, cfg)),
                               atol=1e-5)
    assert np.isfinite(float(aux_ep)) and float(aux_ep) >= 1.0 - 1e-6


def test_expert_parallel_gradients(devices):
    """EP gradients == single-device gradients (token-sharded loss term;
    generous capacity so routing is identical)."""
    from jax import lax

    cfg, params, x = _setup(E=4, T=32, cap=8.0)
    S = 4
    mesh = Mesh(np.asarray(devices[:S], dtype=object).reshape(S), ("expert",))
    specs = {"router": P(), "w1": P("expert"), "w2": P("expert")}

    def loss_ep(p, x):
        def local(p, xs):
            out, _ = moe_ffn(p, xs, cfg, axis_name="expert")
            return lax.psum((out * out).sum(), "expert")

        return jax.shard_map(local, mesh=mesh, in_specs=(specs, P("expert")),
                             out_specs=P())(p, x)

    def loss_local(p, x):
        out, _ = moe_ffn(p, x, cfg)
        return (out * out).sum()

    g1 = jax.grad(loss_ep)(params, x)
    g2 = jax.grad(loss_local)(params, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_aux_loss_pushes_toward_balance():
    """Training only on the aux loss should even out expert assignment."""
    cfg, params, x = _setup(E=4, T=256, seed=3)
    x = jnp.abs(x)  # positive inputs so a column shift acts as a true bias
    # bias the router hard toward expert 0
    params = dict(params)
    params["router"] = params["router"].at[:, 0].add(1.0)

    def frac_to_expert0(p):
        e = jnp.argmax(x @ p["router"], axis=-1)
        return float((e == 0).mean())

    before = frac_to_expert0(params)

    @jax.jit
    def step(p):
        g = jax.grad(lambda p: moe_ffn(p, x, cfg)[1])(p)
        return jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    for _ in range(80):
        params = step(params)
    after = frac_to_expert0(params)
    assert before > 0.9 and after < 0.5, (before, after)


class TestMoELM:
    """MoE FFN layers inside the transformer LM (TransformerConfig.moe_*)."""

    def _cfg(self, **kw):
        from harmony_tpu.models import TransformerConfig

        base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                    d_ff=64, max_seq=16, attn="blockwise",
                    moe_experts=2, moe_every=2, moe_capacity_factor=8.0)
        base.update(kw)
        return TransformerConfig(**base)

    def test_single_expert_equals_dense(self):
        """E=1 with ample capacity routes every token through the one
        expert at gate 1.0 — logits must equal the dense model with the
        same weights."""
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data

        moe_cfg = self._cfg(moe_experts=1, moe_every=1)
        dense_cfg = self._cfg(moe_experts=0)
        moe = TransformerLM(moe_cfg)
        dense = TransformerLM(dense_cfg)
        mp = moe.init(jax.random.PRNGKey(0))
        dp = dense.init(jax.random.PRNGKey(0))
        # graft the expert weights into the dense tree (and vice versa
        # shapes: moe w1 [1, d, f] -> dense w1 [d, f])
        for ml, dl in zip(mp["layers"], dp["layers"]):
            for k in ("ln1", "wqkv", "wo", "ln2"):
                dl[k] = ml[k]
            dl["w1"] = ml["moe"]["w1"][0]
            dl["w2"] = ml["moe"]["w2"][0]
        tokens = jnp.asarray(make_lm_data(3, 16, 64, seed=1))
        np.testing.assert_allclose(
            np.asarray(moe.apply(mp, tokens)),
            np.asarray(dense.apply(dp, tokens)),
            rtol=2e-5, atol=2e-5,
        )

    def test_moe_lm_learns_with_aux(self):
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data

        cfg = self._cfg()
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(2))
        tokens = jnp.asarray(make_lm_data(8, 16, cfg.vocab_size, seed=3))

        @jax.jit
        def step(p, t):
            loss, grads = jax.value_and_grad(model.loss)(p, t)
            return jax.tree.map(lambda w, g: w - 0.3 * g, p, grads), loss

        losses = []
        for _ in range(25):
            params, loss = step(params, tokens)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.8, losses
        # expert weights actually received gradient
        g = jax.grad(model.loss)(params, tokens)
        assert float(jnp.abs(g["layers"][1]["moe"]["w1"]).sum()) > 0

    def test_moe_cache_decode_matches_forward(self):
        """KV-cache decode with MoE layers reproduces the full forward when
        capacity is ample (no routing drops — granularity-independent)."""
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data
        from harmony_tpu.models.generate import decode_step, init_kv_cache

        cfg = self._cfg()
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(4))
        tokens = jnp.asarray(make_lm_data(2, 8, cfg.vocab_size, seed=5))
        full = model.apply(params, tokens)
        cache = init_kv_cache(cfg, 2)
        step = jax.jit(lambda c, t, p: decode_step(model, params, c, t, p))
        for pos in range(8):
            logits, cache = step(cache, tokens[:, pos], jnp.int32(pos))
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full[:, pos]),
                                       rtol=5e-4, atol=5e-4)

    def test_pp_rejects_moe(self, devices):
        from jax.sharding import Mesh

        from harmony_tpu.models import TransformerLM
        from harmony_tpu.models.transformer import make_pp_train_step

        mesh = Mesh(np.asarray(devices[:2], dtype=object).reshape(2),
                    ("stage",))
        with pytest.raises(ValueError, match="homogeneous"):
            make_pp_train_step(TransformerLM(self._cfg()), mesh)

    def test_sp_step_carries_aux(self, devices):
        """The sequence-parallel loss must include the weighted MoE aux —
        zeroing moe_aux_weight must measurably lower the SP loss (the aux
        is >= 1 for any router by Cauchy-Schwarz)."""
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data
        from harmony_tpu.models.transformer import make_sp_train_step
        from harmony_tpu.parallel import build_mesh

        mesh = build_mesh(devices[:8], data=2, seq=4, model=1)
        tokens = jnp.asarray(make_lm_data(4, 32, 64, seed=6))
        losses = {}
        for w in (0.01, 0.0):
            cfg = self._cfg(max_seq=32, moe_aux_weight=w)
            model = TransformerLM(cfg)
            params = model.init(jax.random.PRNGKey(7))  # same seed, same weights
            step = make_sp_train_step(model, mesh, learning_rate=0.0,
                                      donate=False)
            _, loss = step(params, tokens)
            losses[w] = float(np.asarray(loss.addressable_data(0)))
        assert losses[0.01] - losses[0.0] > 0.005, losses

    def test_ep_step_matches_single_device_ce(self, devices):
        """Expert-parallel training (experts sharded over the data axis,
        all_to_all token routing): with ample capacity and aux weight 0,
        the EP loss equals the single-device loss exactly — routing is
        per-token, so sharding the batch changes nothing."""
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data
        from harmony_tpu.models.transformer import make_ep_train_step
        from harmony_tpu.parallel import build_mesh

        cfg = self._cfg(moe_experts=4, moe_aux_weight=0.0,
                        moe_capacity_factor=8.0)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(8))
        mesh = build_mesh(devices[:4], data=4, model=1)
        step, shard = make_ep_train_step(model, mesh, learning_rate=0.0,
                                         donate=False)
        ep_params = shard(params)
        tokens = jnp.asarray(make_lm_data(8, 16, cfg.vocab_size, seed=9))
        _, loss_ep = step(ep_params, tokens)
        loss_ref = model.loss(params, tokens)
        np.testing.assert_allclose(
            float(np.asarray(loss_ep.addressable_data(0))),
            float(loss_ref), rtol=2e-4,
        )

    def test_ep_step_learns(self, devices):
        import jax.numpy as jnp

        from harmony_tpu.models import TransformerLM, make_lm_data
        from harmony_tpu.models.transformer import make_ep_train_step
        from harmony_tpu.parallel import build_mesh

        cfg = self._cfg(moe_experts=4)
        model = TransformerLM(cfg)
        mesh = build_mesh(devices[:4], data=4, model=1)
        step, shard = make_ep_train_step(model, mesh, learning_rate=0.3)
        params = shard(model.init(jax.random.PRNGKey(10)))
        tokens = jnp.asarray(make_lm_data(8, 16, cfg.vocab_size, seed=11))
        losses = []
        for _ in range(25):
            params, loss = step(params, tokens)
            losses.append(float(np.asarray(loss.addressable_data(0))))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.8, losses

    def test_ep_step_rejects_dense(self, devices):
        from harmony_tpu.models import TransformerLM
        from harmony_tpu.models.transformer import make_ep_train_step
        from harmony_tpu.parallel import build_mesh

        with pytest.raises(ValueError, match="moe_experts"):
            make_ep_train_step(TransformerLM(self._cfg(moe_experts=0)),
                               build_mesh(devices[:4], data=4, model=1))


# -- the dropless layer's held slots, in chunks of a static capacity ----------
#
# The reference is the full-length formulation (every T * k slot sorted,
# gathered, multiplied, masked and combined), written out here: the layer
# must equal it under ANY routing, at the tolerance of a float32 sum of at
# most k terms associated another way (fixed before the chip was used).

from harmony_tpu.models import moe as moe_mod  # noqa: E402
from harmony_tpu.models.moe import (  # noqa: E402
    DroplessConfig, chunk_plan, init_dropless_params, moe_ffn_dropless)

RTOL = 1e-6          # of the largest magnitude of what is compared
SIGMOID = dict(score="sigmoid", norm_topk=True, routed_scale=2.446,
               shared_experts=1)


def _full_length(params, x, cfg, seqs=1):
    """The layer as it stood before it was chunked, line for line."""
    from harmony_tpu.ops.grouped_matmul import grouped_matmul
    from harmony_tpu.tracing.stepscopes import step_scope

    T, d = x.shape
    k, H = cfg.top_k, cfg.experts_held
    gate, expert, slot_expert, tokens, stats = moe_mod._route(
        params, x, cfg, seqs)
    with step_scope("moe.dispatch"):
        order = jnp.argsort(slot_expert, stable=True)
        inv = jnp.argsort(order)
        sizes = tokens[:H]
        rows = moe_mod._slot_rows(x, order, inv, k)
    dtype = x.dtype
    with step_scope("moe.experts"):
        h = (jax.nn.silu(grouped_matmul(rows, params["wg"].astype(dtype),
                                        sizes))
             * grouped_matmul(rows, params["wu"].astype(dtype), sizes))
        y = grouped_matmul(h, params["wd"].astype(dtype), sizes)
    with step_scope("moe.combine"):
        weight = jnp.where(expert < H, gate, 0.0)
        y = moe_mod._slot_rows(y, inv, order, 1).reshape(T, k, d)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), weight)
        out = out.astype(dtype)
    if cfg.shared_experts:
        with step_scope("moe.shared"):
            hs = (jax.nn.silu(x @ params["shared_wg"].astype(dtype))
                  * (x @ params["shared_wu"].astype(dtype)))
            out = out + hs @ params["shared_wd"].astype(dtype)
    return out, stats


def _loss(layer, cfg, checkpoint=False):
    def loss(params, x):
        out, stats = layer(params, x, cfg)
        # the router's gradient through the gates AND the statistics
        return (out ** 2).sum() + 0.1 * (stats["prob_sum"] ** 2).sum(), out
    return jax.checkpoint(loss) if checkpoint else loss


def _assert_close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= RTOL * scale * 8, what


def _compare(cfg, params, x, checkpoint=False):
    """The layer against the full-length formulation: output and every
    gradient; returns the layer's held token-slots."""
    outs = []
    for layer in (moe_ffn_dropless, _full_length):
        (_, out), grads = jax.jit(jax.value_and_grad(
            _loss(layer, cfg, checkpoint), argnums=(0, 1), has_aux=True))(
                params, x)
        outs.append((out, grads))
    (out, (gp, gx)), (want, (wp, wx)) = outs
    _assert_close(out, want, "out")
    _assert_close(gx, wx, "d x")
    assert set(gp) == set(wp)
    for name in wp:
        _assert_close(gp[name], wp[name], f"d {name}")
    assert float(np.abs(np.asarray(gp["router"])).max()) > 0.0


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 8, so that shapes a CPU test can afford are chunked —
    and token tiles of 8, so that their row sums cross tiles."""
    from harmony_tpu.ops import sum_rows

    monkeypatch.setattr(moe_mod, "_ROW_TILE", 8)
    monkeypatch.setattr(sum_rows, "_TB", (8,))


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("router", [{}, SIGMOID], ids=["softmax", "sigmoid"])
@pytest.mark.parametrize("held", [1, 4, 8])  # of 32: 1/32, 1/8, 1/4
def test_chunked_layer_equals_the_full_length_formulation(
        small_tiles, held, router, checkpoint):
    cfg = DroplessConfig(32, 4, 16, 8, held, **router)
    T = 64
    C, chunks = chunk_plan(T * 4, held, 32)
    # a chunk of half the slots does not pay (PERF.md): 1/4 held runs plain
    assert (C, chunks) == {1: (16, 16), 4: (64, 4), 8: (256, 0)}[held]
    params = init_dropless_params(jax.random.PRNGKey(held), cfg)
    if router:  # a selection bias that is not all zeros
        params["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (32,))
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 16), jnp.float32)
    _compare(cfg, params, x, checkpoint)


def _routed(choices, E, H, k):
    """A softmax layer whose token ``t`` chooses exactly ``choices[t]`` (k
    experts each): the router is the identity and ``x`` holds the logits."""
    T = len(choices)
    cfg = DroplessConfig(E, k, E, 8, H)
    params = init_dropless_params(jax.random.PRNGKey(3), cfg)
    params["router"] = jnp.eye(E, dtype=jnp.float32)
    x = 0.1 * np.asarray(jax.random.normal(jax.random.PRNGKey(4), (T, E)))
    for t, chosen in enumerate(choices):
        for j, e in enumerate(chosen):
            x[t, e] = 3.0 + 0.25 * j
    return cfg, params, jnp.asarray(x, jnp.float32)


def _edge(name):
    """32 tokens x top-2 of 16 experts, 2 held: 64 slots in 4 chunks of
    16. Returns ``(choices, held slots, chunks that run)``."""
    T, away = 32, [[8, 9]]
    both, one, other = [[0, 1]], [[0, 8]], [[1, 9]]
    return {
        "no_held_slot": (away * T, 0, 0),
        "exactly_one_chunk": (one * 10 + other * 6 + away * 16, 16, 1),
        "one_past_the_chunk": (one * 10 + other * 7 + away * 15, 17, 2),
        "every_slot_held": (both * T, 64, 4),
        "a_run_across_a_boundary": (one * 24 + other * 4 + away * 4, 28, 2),
    }[name]


@pytest.mark.parametrize("name", [
    "no_held_slot", "exactly_one_chunk", "one_past_the_chunk",
    "every_slot_held", "a_run_across_a_boundary"])
def test_chunked_layer_is_exact_under_edge_routings(small_tiles, name):
    from harmony_tpu.metrics.moe import chunks_run

    choices, n_held, runs = _edge(name)
    cfg, params, x = _routed(choices, 16, 2, 2)
    assert chunk_plan(64, 2, 16) == (16, 4)
    out, stats = moe_ffn_dropless(params, x, cfg)
    tokens = np.asarray(stats["tokens"])
    assert tokens[:2].sum() == n_held and tokens.sum() == 64
    # the counter's host arithmetic says how many chunks this call ran
    assert chunks_run(tokens[None, None, :], 2).tolist() == [[runs]]
    if not n_held:
        assert float(np.abs(np.asarray(out)).max()) == 0.0
    _compare(cfg, params, x)


def test_layer_that_holds_every_expert_lowers_as_it_did():
    """``C >= T * k``: the parent's program, text for text (no knob decides
    it: the plan is a function of the shapes)."""
    import hashlib

    for cfg in (DroplessConfig(8, 2, 16, 8, 8), DroplessConfig(8, 2, 16, 8, 3),
                DroplessConfig(8, 2, 16, 8, 4, **SIGMOID)):
        assert chunk_plan(64 * 2, cfg.experts_held, 8)[1] == 0
        params = init_dropless_params(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((64, 16), jnp.float32)
        sha = [hashlib.sha256(jax.jit(jax.grad(
            lambda p, x, layer=layer: _loss(layer, cfg)(p, x)[0],
            argnums=(0, 1))).lower(params, x).as_text().encode()).hexdigest()
            for layer in (moe_ffn_dropless, _full_length)]
        assert sha[0] == sha[1]


def _gmm_call_sites(text):
    import re

    return (len(re.findall(r"call @_gmm(?:_\d+)?\(", text)),
            len(re.findall(r"call @_tgmm(?:_\d+)?\(", text)))


def _row_sums(text, d):
    """``(call sites of the row-sum kernel, XLA scatters whose updates are
    rows of ``d`` lanes)`` in a lowered program's text."""
    import re

    updates = [m.group(1) for m in re.finditer(
        r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*, (tensor<[^>]*>)\) -> ',
        text, re.S)]
    assert updates  # the pattern still reads this jax's scatters
    return (len(re.findall(r"call @sum_rows(?:_\d+)?\(", text)),
            [u for u in updates if re.fullmatch(rf"tensor<\d+x{d}x\w+>", u)])


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
def test_one_traced_body_a_layer_and_pass(monkeypatch, checkpoint):
    """The guard no CPU test gave PR 34: the gradient of one expert layer at
    a held share of 1/8, lowered for a TPU, calls the grouped matmuls as
    often as the full-length formulation does (3 forward + 3 dx + 3 dw, + 3
    forward under ``checkpoint``) and holds no conditional at all — a second
    capacity, or a full-length fallback behind a ``cond``, would be a second
    copy of the body to trace, differentiate and lower. The rows return to
    their tokens through ``harmony_sum_rows`` (ops/sum_rows.py, PR 37): one
    call site a pass, the same kernel at both, and no XLA scatter of
    ``[C, d]`` rows beside it (the scalar ``d_w.at[slots].add`` stays
    XLA's)."""
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    cfg = DroplessConfig(64, 4, 128, 64, 8)
    assert chunk_plan(1024 * 4, 8, 64) == (1024, 4)
    params = jax.eval_shape(
        lambda: init_dropless_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16)
    sites = []
    for layer in (moe_ffn_dropless, _full_length):
        text = jax.jit(jax.value_and_grad(
            _loss(layer, cfg, checkpoint), argnums=(0, 1), has_aux=True)
        ).trace(params, x).lower(lowering_platforms=("tpu",)).as_text()
        sites.append(_gmm_call_sites(text))
        if layer is moe_ffn_dropless:
            assert "stablehlo.case" not in text and "stablehlo.if" not in text
            assert text.count("stablehlo.while") == (3 if checkpoint else 2)
            assert _row_sums(text, 128) == (3 if checkpoint else 2, [])
    assert sites[0] == sites[1] == ((9, 3) if checkpoint else (6, 3))
    # the guard sees a row scatter where there is one: the parent's line
    rows = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    parent = jax.jit(lambda acc, tok, y: acc.at[tok].add(y)).trace(
        rows, jax.ShapeDtypeStruct((1024,), jnp.int32), rows).lower(
            lowering_platforms=("tpu",)).as_text()
    assert _row_sums(parent, 128) == (0, ["tensor<1024x128xf32>"])


def test_the_chunk_plan_reaches_kernel_plans():
    """What a compiled program runs is on STATUS: the capacity and the
    chunks the slot axis is cut into, and the kernels' tiles at ``M = C``."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    cfg = DroplessConfig(64, 4, 128, 64, 8)
    params = jax.eval_shape(
        lambda: init_dropless_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    with trace_span("job.build_step", job_id="plan-chunks"):
        jax.jit(jax.grad(lambda p, x: _loss(moe_ffn_dropless, cfg)(p, x)[0])
                ).trace(params, x)
    rows = progcache.kernel_plans()["plan-chunks"]
    plan, = [r for r in rows if r["kernel"] == "moe_held_chunks"]
    assert (plan["block_q"], plan["grid_steps"]) == (1024, 4)
    assert (plan["d"], plan["dv"]) == (128, 64)
    gmm = [r for r in rows if r["kernel"].startswith("harmony_gmm_")]
    assert {r["kernel"] for r in gmm} == {
        "harmony_gmm_fwd", "harmony_gmm_dx", "harmony_gmm_dw"}
    # tiles planned for the chunk's rows: 1024 / 512 row tiles + 8 groups - 1
    assert {r["grid_steps"] for r in gmm} == {2 + 8 - 1}


def test_the_row_sum_plan_reaches_kernel_plans():
    """... and the row-sum kernel's: the token tile its plan gives, the
    chunk's rows, the runs, and the tiles a call walks — noted by the
    forward and by the hand-written backward alike (one row: one plan)."""
    from harmony_tpu.ops.sum_rows import KERNEL_NAME, tile_plan
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    cfg = DroplessConfig(64, 4, 128, 64, 8)
    params = jax.eval_shape(
        lambda: init_dropless_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16)
    with trace_span("job.build_step", job_id="plan-row-sums"):
        jax.jit(jax.grad(lambda p, x: _loss(moe_ffn_dropless, cfg)(p, x)[0])
                ).trace(params, x)
    plan, = [r for r in progcache.kernel_plans()["plan-row-sums"]
             if r["kernel"] == KERNEL_NAME]
    assert KERNEL_NAME == "harmony_sum_rows"  # no grouped-matmul family's
    assert tile_plan(1024, 128, jnp.bfloat16) == 256
    assert (plan["block_q"], plan["block_k"], plan["sub"]) == (256, 1024, 8)
    assert (plan["grid_steps"], plan["d"], plan["planned"]) == (4, 128, True)
    # the cells' plans: 256 tokens a tile under the kernel's own VMEM limit
    for tokens, d in ((8192, 2304), (16384, 2048), (16384, 2560)):
        assert tile_plan(tokens, d, jnp.bfloat16) == 256
    assert tile_plan(16384, 8192, jnp.bfloat16) == 64  # wider rows: smaller
    assert tile_plan(60, 128, jnp.float32) == 60       # no divisor: one tile


# -- the router's selection is one op (ops/top_k_rows.py, PR 43) --------------

def _route_parent(params, x, cfg, seqs):
    """``_route`` as it stood before PR 43, line for line: ``lax.top_k``,
    ``take_along_axis``, and autodiff's scatter-add behind them."""
    from jax import lax

    T = x.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.dot(x.astype(jnp.float32), params["router"],
                     precision=lax.Precision.HIGHEST)
    if cfg.score == "softmax":
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
        gate, expert = lax.top_k(probs, k)
    else:
        score = jax.nn.sigmoid(logits)
        _, expert = lax.top_k(score + lax.stop_gradient(params["bias"]), k)
        gate = jnp.take_along_axis(score, expert, axis=1)
        probs = score / score.sum(axis=-1, keepdims=True)
    if cfg.norm_topk:
        gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-20)
    if cfg.routed_scale != 1.0:
        gate = gate * cfg.routed_scale
    slot_expert = expert.reshape(-1)
    if cfg.seq_aux:
        by_seq = jnp.sum(slot_expert.reshape(seqs, -1)[:, :, None]
                         == jnp.arange(E)[None, None, :], axis=1,
                         dtype=jnp.int32)
        tokens = by_seq.sum(axis=0)
        f = by_seq.astype(jnp.float32) * (E / (k * (T // seqs)))
        p = probs.reshape(seqs, T // seqs, E).mean(axis=1)
        seq_lb = jnp.sum(f * p, axis=-1).mean()
    else:
        tokens = jnp.sum(slot_expert[:, None] == jnp.arange(E)[None, :],
                         axis=0, dtype=jnp.int32)
    stats = {"tokens": tokens.astype(jnp.float32), "n": jnp.float32(T),
             "prob_sum": probs.sum(axis=0)}
    if cfg.score == "softmax":
        stats["z_sum"] = jnp.sum(lse * lse)
    if cfg.seq_aux:
        stats["seq_lb"] = seq_lb
    return gate, expert, slot_expert, tokens, stats


_ROUTERS = {
    "softmax": dict(),
    "sigmoid": dict(SIGMOID, seq_aux=True),
    # every third expert scores exactly 0.5 (a zero router column) and a
    # bias lifts those eleven above the rest: each row's top 6 is a tie
    "sigmoid-ties": dict(score="sigmoid", norm_topk=True),
}


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("router", list(_ROUTERS))
def test_route_equals_the_parents_formulation(router, checkpoint):
    """``_route`` — its selection one op with a compare-and-sum behind it —
    against ``lax.top_k`` + ``take_along_axis`` + their scatter-add, kept
    here: the gates, the experts slot by slot, the token counts, every
    statistic, and the gradients that reach the router and the tokens,
    all equal to the last bit."""
    cfg = DroplessConfig(32, 6, 16, 8, 4, **_ROUTERS[router])
    params = init_dropless_params(jax.random.PRNGKey(3), cfg)
    if cfg.score == "sigmoid":
        params["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (32,))
    if router == "sigmoid-ties":
        params["router"] = params["router"].at[:, ::3].set(0.0)
        params["bias"] = jnp.zeros((32,)).at[::3].set(1.0)
    seqs = 4
    x = jax.random.normal(jax.random.PRNGKey(5), (seqs * 24, 16))
    w = jax.random.normal(jax.random.PRNGKey(6), (seqs * 24, 6))

    def run(route):
        def loss(p, x):
            gate, expert, slot_expert, tokens, stats = route(p, x, cfg, seqs)
            total = (gate * w).sum() + (gate ** 2).sum() + sum(
                (v ** 2).sum() for name, v in stats.items()
                if name != "tokens")
            return total, (gate, expert, slot_expert, tokens, stats)
        if checkpoint:
            loss = jax.checkpoint(loss)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, x)

    (got_loss, got), (got_p, got_x) = run(moe_mod._route)
    (want_loss, want), (want_p, want_x) = run(_route_parent)
    assert got[1].dtype == want[1].dtype == jnp.int32
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(got[4]) == set(want[4])
    np.testing.assert_array_equal(np.asarray(got_loss), np.asarray(want_loss))
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
    np.testing.assert_array_equal(np.asarray(got_p["router"]),
                                  np.asarray(want_p["router"]))
    assert float(np.abs(np.asarray(got_p["router"])).max()) > 0.0
    if cfg.score == "sigmoid":  # the bias selects and carries no gradient
        assert not np.asarray(got_p["bias"]).any()
    if router == "sigmoid-ties":  # the lower lanes of the tie, in order
        assert (np.asarray(got[1]) == np.arange(0, 18, 3)).all()


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("router", [{}, SIGMOID], ids=["softmax", "sigmoid"])
def test_route_lowers_without_sort_gather_or_scalar_scatter(
        monkeypatch, router, checkpoint):
    """The gradient of one expert layer, lowered for a TPU: ``_route`` leaves
    no sort (the one sort left is ``moe.dispatch``'s argsort by expert), no
    gather and no scatter under ``moe.route``, and one ``top_k_rows`` call a
    layer and pass (+ 1 under ``checkpoint``); the only scatter with scalar
    updates is ``d_w.at[slots].add`` of the chunked backward, as before."""
    import re

    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    cfg = DroplessConfig(64, 4, 128, 64, 8, **router)
    params = jax.eval_shape(
        lambda: init_dropless_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16)
    texts = {}
    for layer in (moe_ffn_dropless, _full_length):
        texts[layer] = jax.jit(jax.value_and_grad(
            _loss(layer, cfg, checkpoint), argnums=(0, 1), has_aux=True)
        ).trace(params, x).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    text = texts[moe_ffn_dropless]
    calls = 2 if checkpoint else 1
    assert len(re.findall(r'kernel_name = "harmony_top_k_rows"', text)) \
        == calls, re.findall(r"kernel_name = \S+", text)
    named = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))

    def under_route(op):
        """Uses of ``op`` whose location names the ``moe.route`` scope."""
        out = []
        for m in re.finditer(rf'"?{re.escape(op)}"?[ (].*?loc\((#loc\d+)\)',
                             text):
            where = named.get(m.group(1), "")
            if "moe.route" in where:
                out.append(where)
        return out

    assert "moe.route" in " ".join(named.values())
    assert under_route("stablehlo.dot_general")  # the reader finds the scope
    for op in ("stablehlo.sort", "stablehlo.gather", "stablehlo.scatter",
               "chlo.top_k"):
        assert not under_route(op), (op, under_route(op))
    assert "chlo.top_k" not in text
    # scatters with scalar updates: the chunked backward's one, and none in
    # the full-length formulation, which has no other
    def scalar(t):
        """Scatters whose updates are scalars: one a row of the indices."""
        found = re.findall(
            r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*, tensor<([\dx]+)xi32>, '
            r'tensor<([\dx]+)xf32>\) -> ', t, re.S)
        return sum(at.rsplit("x", 1)[0] == updates for at, updates in found)

    assert (scalar(text), scalar(texts[_full_length])) == (1, 0)
    # the guard sees the parent's router where it stands
    parent = jax.jit(jax.grad(
        lambda p, x: (_route_parent(p, x, cfg, 1)[0] ** 2).sum())
    ).trace(params, x).lower(lowering_platforms=("tpu",)).as_text()
    assert scalar(parent) == 1 and "chlo.top_k" in parent


def test_the_top_k_plan_reaches_kernel_plans():
    """... and the selection kernel's: the token tile its plan gives, the
    experts, ``k``, and the tiles a call walks — one row a shape, whichever
    pass traced it."""
    from harmony_tpu.ops.top_k_rows import KERNEL_NAME, tile_plan
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    cfg = DroplessConfig(64, 4, 128, 64, 8, **SIGMOID)
    params = jax.eval_shape(
        lambda: init_dropless_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16)
    with trace_span("job.build_step", job_id="plan-top-k"):
        jax.jit(jax.grad(lambda p, x: _loss(moe_ffn_dropless, cfg)(p, x)[0])
                ).trace(params, x)
    plan, = [r for r in progcache.kernel_plans()["plan-top-k"]
             if r["kernel"] == KERNEL_NAME]
    assert KERNEL_NAME == "harmony_top_k_rows"  # no grouped-matmul family's
    assert (plan["block_q"], plan["block_k"], plan["sub"]) == (512, 64, 4)
    assert (plan["grid_steps"], plan["d"], plan["dv"]) == (2, 64, 4)
    assert plan["planned"] is True
    # the cells' plans: 512 tokens a tile at every router's width
    for tokens, experts, weighed in ((8192, 512, True), (8192, 256, True),
                                     (16384, 64, True), (16384, 64, False)):
        assert tile_plan(tokens, experts, weighed) == 512
    assert tile_plan(8192, 2048, True) == 256  # wider rows: a smaller tile
    assert tile_plan(60, 64, True) == 60       # no divisor: one tile
