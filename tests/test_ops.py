"""Kernel correctness: Pallas kernels (interpret mode) vs naive XLA math.

Mirrors the reference's test strategy of exact-semantics unit tests
(SURVEY.md §4): every kernel is validated against the obvious dense
implementation, including gradients and the distributed ring variant on the
8-virtual-device mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.ops import (
    blockwise_attention,
    flash_attention,
    ring_attention,
    weighted_histogram,
)
from harmony_tpu.ops.ring import ring_self_attention
from harmony_tpu.parallel import build_mesh


def naive_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        Sq, Sk = s.shape[-2:]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _qkv(B=2, H=2, S=128, D=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, S, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(causal):
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(out, naive_attention(q, k, v, causal), atol=2e-5)


def test_blockwise_ragged_kv_padding():
    q, k, v = _qkv(S=96)  # 96 % 64 != 0 -> pad path
    out = blockwise_attention(q, k, v, block_k=64)
    np.testing.assert_allclose(out, naive_attention(q, k, v), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_naive(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(out, naive_attention(q, k, v, causal), atol=2e-5)


def test_flash_gradients_match_naive():
    q, k, v = _qkv(S=64, D=16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                               interpret=True).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_weighted_histogram_kernel():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 24, 500).astype(np.int32)
    w = rng.normal(size=(500, 3)).astype(np.float32)
    out = weighted_histogram(jnp.asarray(ids), jnp.asarray(w), 24,
                             block_n=128, interpret=True)
    expect = np.zeros((24, 3), np.float32)
    np.add.at(expect, ids, w)
    np.testing.assert_allclose(out, expect, atol=1e-4)


def test_weighted_histogram_ignores_negative_ids():
    ids = jnp.asarray([0, -1, 1, -1], jnp.int32)
    w = jnp.ones((4, 1), jnp.float32)
    out = weighted_histogram(ids, w, 2, block_n=8, interpret=True)
    np.testing.assert_allclose(out[:, 0], [1.0, 1.0])


def test_weighted_histogram_single_column():
    data = jnp.asarray([1.0, 2.0, 3.0, 4.0])[:, None]
    ids = jnp.asarray([0, 1, 0, 2], jnp.int32)
    out = weighted_histogram(ids, data, 3, interpret=True)
    np.testing.assert_allclose(out[:, 0], [4.0, 2.0, 4.0])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_naive(devices, causal):
    mesh = build_mesh(devices, data=1, model=8)  # ring over "model"
    q, k, v = _qkv(B=1, H=2, S=64, D=16, seed=3)
    out = ring_self_attention(q, k, v, mesh, seq_axis="model", causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), naive_attention(q, k, v, causal), atol=2e-5
    )


def test_ring_attention_gradients(devices):
    mesh = build_mesh(devices, data=1, model=8)
    q, k, v = _qkv(B=1, H=1, S=32, D=8, seed=4)

    def loss_ring(q, k, v):
        return ring_self_attention(q, k, v, mesh, seq_axis="model",
                                   causal=True).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), b, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_inner_matches_einsum(devices, causal):
    """The Pallas flash inner (per-chunk kernel + LSE merge) must be exact
    against the einsum fold — forward AND all three gradients (the LSE
    cotangent folds into the backward kernels' delta term).
    check_vma=False: the pallas HLO interpreter trips shard_map's vma
    checker off-TPU (jax interpreter limitation)."""
    mesh = build_mesh(devices, data=2, seq=4, model=1)
    q, k, v = _qkv(B=2, H=2, S=64, D=16, seed=7)
    kw = dict(batch_axis="data", causal=causal)
    o_e = ring_self_attention(q, k, v, mesh, seq_axis="seq",
                              inner="einsum", **kw)
    o_f = ring_self_attention(q, k, v, mesh, seq_axis="seq",
                              inner="flash", check_vma=False,
                              interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_e), atol=2e-5)

    def loss_e(q, k, v):
        return (ring_self_attention(q, k, v, mesh, seq_axis="seq",
                                    inner="einsum", **kw) ** 2).sum()

    def loss_f(q, k, v):
        return (ring_self_attention(q, k, v, mesh, seq_axis="seq",
                                    inner="flash", check_vma=False,
                                    interpret=True, **kw) ** 2).sum()

    ge = jax.grad(loss_e, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ge, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_lse_matches_reference():
    """flash_attention_lse: the LSE output must equal the row logsumexp of
    the scaled scores, and gradients through BOTH outputs must match the
    direct computation."""
    from harmony_tpu.ops.attention import flash_attention_lse

    q, k, v = _qkv(B=1, H=2, S=32, D=8, seed=9)
    scale = q.shape[-1] ** -0.5
    out, lse = jax.jit(
        lambda q, k, v: flash_attention_lse(q, k, v, True, interpret=True)
    )(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((32, 32), bool))
    s = jnp.where(mask, s, -1e30)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=1e-4)

    def loss_flash(q, k, v):
        o, l = flash_attention_lse(q, k, v, True, interpret=True)
        return (o.astype(jnp.float32) ** 2).sum() + (l * 0.1).sum()

    def loss_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k).astype(jnp.float32)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        l = jax.scipy.special.logsumexp(s, axis=-1)
        return (o ** 2).sum() + (l * 0.1).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_weighted_histogram_bins_tiling():
    """num_bins > block_bins exercises the VMEM-bounded tiled grid."""
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 300, 1000).astype(np.int32)
    w = rng.normal(size=(1000, 2)).astype(np.float32)
    out = weighted_histogram(jnp.asarray(ids), jnp.asarray(w), 300,
                             block_n=256, block_bins=128, interpret=True)
    expect = np.zeros((300, 2), np.float32)
    np.add.at(expect, ids, w)
    assert out.shape == (300, 2)
    np.testing.assert_allclose(out, expect, atol=1e-4)


def test_weighted_histogram_w_tiling():
    """W > block_w exercises the third grid dimension (all three tiled:
    N, bins, W) with uneven padding on every axis."""
    rng = np.random.default_rng(11)
    ids = rng.integers(0, 70, 333).astype(np.int32)
    w = rng.normal(size=(333, 37)).astype(np.float32)
    out = weighted_histogram(jnp.asarray(ids), jnp.asarray(w), 70,
                             block_n=64, block_bins=32, block_w=16,
                             interpret=True)
    expect = np.zeros((70, 37), np.float32)
    np.add.at(expect, ids, w)
    assert out.shape == (70, 37)
    np.testing.assert_allclose(out, expect, atol=1e-4)


def test_histogram_tile_picker_respects_vmem_budget():
    """Any input size must yield a working set under the scoped-VMEM budget
    (the v5e limit is 16 MB; the kernel OOMed there before tiling W)."""
    from harmony_tpu.ops.histogram import _VMEM_BUDGET_WORDS, _pick_tiles

    for req in [(4096, 4096, 4096), (512, 2048, 256), (1024, 8192, 8192)]:
        bn, bb, bw = _pick_tiles(*req)
        words = bb * bn + 2 * bn * bw + 2 * bb * bw
        assert words <= _VMEM_BUDGET_WORDS, (req, (bn, bb, bw), words)
        assert min(bn, bb, bw) >= 8


def test_weighted_histogram_empty_input():
    out = weighted_histogram(jnp.zeros((0,), jnp.int32), jnp.zeros((0, 4)),
                             16, interpret=True)
    np.testing.assert_allclose(out, np.zeros((16, 4)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_matches_naive(causal):
    """The dedicated pallas backward kernels (dQ/dK/dV from saved LSE) must
    reproduce autodiff-of-naive gradients, including cotangent weighting."""
    q, k, v = _qkv(S=128, D=32, seed=9)
    w = jax.random.normal(jax.random.PRNGKey(10), q.shape)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=64,
                              interpret=True)
        return (out * w).sum()

    def loss_naive(q, k, v):
        return (naive_attention(q, k, v, causal=causal) * w).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4)


class TestMxuDot:
    def test_bf16_accumulates_f32(self):
        from harmony_tpu.ops import mxu_dot

        rng = np.random.default_rng(0)
        a = rng.standard_normal((64, 256), dtype=np.float32)
        b = rng.standard_normal((256, 32), dtype=np.float32)
        out = np.asarray(mxu_dot(jnp.asarray(a), jnp.asarray(b)))
        assert out.dtype == np.float32
        exact = a @ b
        # bf16 operands: ~2-3 decimal digits; accumulation stays f32 so the
        # error scales with operand rounding, not with the contraction depth.
        np.testing.assert_allclose(out, exact, rtol=3e-2, atol=3e-2 * np.abs(exact).max())

    def test_f32_precision_mode(self):
        from harmony_tpu.ops import mxu_dot

        rng = np.random.default_rng(1)
        a = rng.standard_normal((16, 64), dtype=np.float32)
        b = rng.standard_normal((64, 8), dtype=np.float32)
        out = np.asarray(mxu_dot(jnp.asarray(a), jnp.asarray(b), precision="f32"))
        np.testing.assert_allclose(out, a @ b, rtol=1e-5)

    def test_rejects_unknown_precision(self):
        from harmony_tpu.ops import mxu_dot

        with pytest.raises(ValueError):
            mxu_dot(jnp.ones((2, 2)), jnp.ones((2, 2)), precision="fp8")


class TestA2AAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, devices, causal):
        from harmony_tpu.ops import a2a_self_attention, blockwise_attention
        from harmony_tpu.parallel import build_mesh

        mesh = build_mesh(devices, data=1, seq=8, model=1)
        B, H, S, D = 2, 8, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
        out = a2a_self_attention(q, k, v, mesh, seq_axis="seq", causal=causal)
        ref = blockwise_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_rejects_indivisible_heads(self, devices):
        from harmony_tpu.ops import a2a_self_attention
        from harmony_tpu.parallel import build_mesh

        mesh = build_mesh(devices, data=1, seq=8, model=1)
        x = jnp.ones((2, 3, 64, 8))  # 3 heads, 8-way seq axis
        with pytest.raises(ValueError):
            a2a_self_attention(x, x, x, mesh, seq_axis="seq")


def test_flash_bf16_operands_match_f32_reference():
    """The kernel feeds the MXU in the OPERANDS' dtype (bf16 on hardware)
    with fp32 accumulation; on bf16 inputs it must track the fp32
    reference computed from the same (bf16-rounded) inputs within bf16
    tolerance — forward and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harmony_tpu.ops.attention import blockwise_attention, flash_attention

    b, h, s, d = 1, 2, 256, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(k2, (b, h, s, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(k3, (b, h, s, d), jnp.float32).astype(jnp.bfloat16)

    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    ref = blockwise_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.05)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=128,
                                       block_k=128, interpret=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=0.1, atol=0.1)


# -- the flash kernels' tile plan (ops/attention.py tile_plan) ---------------

_PLAN_LENGTHS = [(65, 65), (197, 197), (256, 256), (257, 257), (512, 512),
                 (1024, 1024), (2048, 2048), (256, 1024), (1024, 512),
                 (200, 200), (300, 300), (384, 768), (8192, 8192)]


@pytest.mark.parametrize("sq,sk", _PLAN_LENGTHS)
@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
def test_tile_plan_divides_fits_and_agrees_with_flash_ok(sq, sk, d, dtype):
    """The plan as a pure function: tiles divide both lengths, the sub-block
    divides the streamed tile, the VMEM estimate stays inside the budget
    (and a plan over Mosaic's default says so), and it is None exactly
    where flash_ok — the models' gate — is false."""
    from harmony_tpu.models.common import flash_ok
    from harmony_tpu.ops import attention as A

    plan = A.tile_plan(sq, sk, d, dtype, causal=True)
    tileable = all(s <= 256 or s % 128 == 0 for s in (sq, sk))
    assert (plan is not None) == tileable
    if sq == sk:
        assert flash_ok(sq, head_dim=d, dtype=dtype) == tileable
    if plan is None:
        return
    assert plan.planned
    shape = A._Shape(d, d, jnp.dtype(dtype).itemsize, sq, 0)
    for kern in ("fwd", "bwd"):
        t = getattr(plan, kern)
        assert sq % t.block_q == 0 and sk % t.block_k == 0
        streamed = t.block_q if kern == "bwd" else t.block_k
        assert streamed % t.sub == 0
        need = A._vmem_bytes(kern, t.block_q, t.block_k, t.sub, shape)
        assert need <= A._VMEM_CAP
        if t.vmem_limit_bytes is None:
            assert need <= A._VMEM_FREE < A._VMEM_DEFAULT
        else:
            assert need < t.vmem_limit_bytes
    if sq == sk == 1024 and d == 64:  # the gpt2 cell: 192 grid steps a call
        assert plan.fwd[:3] == (512, 1024, 1024)
        assert plan.bwd[:3] == (1024, 512, 512)


def test_tile_plan_explicit_blocks_win():
    from harmony_tpu.models.common import flash_ok
    from harmony_tpu.ops.attention import tile_plan

    plan = tile_plan(1024, 1024, 64, jnp.bfloat16, True,
                     block_q=128, block_k=256)
    assert not plan.planned
    assert plan.fwd[:3] == (128, 256, 256)     # one sub-block a grid step
    assert plan.bwd[:3] == (128, 256, 128)
    # blocks clamp to the length; a length they do not divide cannot tile
    assert tile_plan(64, 64, 16, jnp.float32, block_q=128).fwd[:2] == (64, 64)
    assert tile_plan(200, 200, 64, jnp.bfloat16, block_q=128) is None
    assert flash_ok(200, block=128) is False and flash_ok(200)
    q = jnp.zeros((1, 1, 200, 8))
    with pytest.raises(ValueError, match="cannot tile"):
        flash_attention(q, q, q, block_q=128, block_k=128, interpret=True)
    q = jnp.zeros((1, 1, 300, 8))
    with pytest.raises(ValueError, match="must divide by 128"):
        flash_attention(q, q, q, interpret=True)


def test_tile_plan_refuses_what_the_backward_cannot_hold():
    """The backward keeps a query head's WHOLE dQ (and, under grouped
    heads, the K/V head's whole dK and dV) in VMEM: where that cannot fit
    beside the smallest tiles there is no plan — by shape alone — and the
    models' gate says blockwise."""
    from harmony_tpu.models.common import flash_ok
    from harmony_tpu.ops import attention as A

    plan = A.tile_plan(65536, 65536, 128, jnp.bfloat16, True)
    shape = A._Shape(128, 128, 2, 65536, 0)
    assert A._vmem_bytes("bwd", *plan.bwd[:3], shape) <= A._VMEM_CAP
    assert plan.bwd.block_q < 65536          # the streamed tile gave way
    assert plan.bwd.vmem_limit_bytes <= 112 * 2**20 < 128 * 2**20
    assert A.tile_plan(65536, 65536, 128, jnp.bfloat16, True, group=8) is None
    assert A.tile_plan(131072, 131072, 128, jnp.bfloat16, True) is None
    assert flash_ok(65536) and not flash_ok(65536, group=8)
    assert flash_ok(32768, group=8) and not flash_ok(32768, group=8, streams=4)


def _assert_flash_matches_naive(q, k, v, causal, atol=2e-5, gtol=2e-4, **kw):
    from harmony_tpu.ops.attention import flash_attention_lse

    w = jax.random.normal(jax.random.PRNGKey(11), q.shape)
    out, lse = flash_attention_lse(q, k, v, causal, kw.get("block_q"),
                                   kw.get("block_k"), None, True)
    np.testing.assert_allclose(out, naive_attention(q, k, v, causal),
                               atol=atol)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        mask = jnp.arange(q.shape[2])[:, None] >= jnp.arange(k.shape[2])
        s = jnp.where(mask, s, -1e30)
    np.testing.assert_allclose(lse, jax.scipy.special.logsumexp(s, axis=-1),
                               atol=1e-4)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True, **kw)
                * w).sum()

    def loss_naive(q, k, v):
        return (naive_attention(q, k, v, causal) * w).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=gtol)


def _qkv_lens(sq, sk, d, bh=1, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, bh, sq, d), jnp.float32),
            jax.random.normal(ks[1], (1, bh, sk, d), jnp.float32),
            jax.random.normal(ks[2], (1, bh, sk, d), jnp.float32))


@pytest.mark.parametrize("sq,sk,causal", [
    (1024, 1024, True),    # the gpt2 cell's plan: the diagonal crosses the
                           # forward's one 512x1024 sub-block; dQ / dK/dV
                           # walk two, skipping the one above the diagonal
    (1024, 1024, False),   # every sub-block unmasked
    (512, 1024, False),    # the ring's inner: a q chunk against a longer kv
    (2048, 2048, True),    # the forward's loop runs 1 then 2 sub-blocks
    (197, 197, True),      # one block of a length no multiple of 8
])
def test_flash_under_the_plan_matches_naive(sq, sk, causal):
    """Forward, LSE and all three gradients under the tiles the kernels
    choose for themselves, against the dense reference."""
    q, k, v = _qkv_lens(sq, sk, 64, bh=2 if sq <= 1024 else 1)
    _assert_flash_matches_naive(q, k, v, causal)


@pytest.mark.parametrize("sq,sk,bq,bk", [
    (128, 128, 64, 32),   # q block 0 skips kv steps 2, 3: their index_map
                          # repeats step 1's block; the diagonal crosses
                          # two kv blocks of every q block
    (128, 256, 64, 64),   # more columns than rows: the LAST q block skips
                          # kv blocks too, and kv tiles 2, 3 meet no row
    (256, 128, 32, 64),   # more rows than columns: the late q blocks take
                          # every kv block unmasked
])
def test_flash_causal_skips_and_clamps(sq, sk, bq, bk):
    q, k, v = _qkv_lens(sq, sk, 16, bh=2, seed=6)
    _assert_flash_matches_naive(q, k, v, True, block_q=bq, block_k=bk)


@pytest.mark.parametrize("tiles", [(64, 256, 64), (128, 256, 128),
                                   (64, 128, 32), (256, 256, 64)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_sub_block_walk_matches_naive(tiles, causal):
    """The in-kernel walk at small sizes: a streamed tile of several
    sub-blocks (skipped / masked / unmasked by the loop bounds) gives what
    one sub-block a grid step gives, in both kernels."""
    from harmony_tpu.ops import attention as A

    q, k, v = _qkv_lens(256, 256, 16, bh=2, seed=7)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape)
    scale = 16 ** -0.5
    bq, bk, sub = tiles
    out, lse = A._flash_forward(q, k, v, causal, A.Tiles(bq, bk, sub), scale,
                                True)
    np.testing.assert_allclose(out, naive_attention(q, k, v, causal),
                               atol=2e-5)
    got = A._flash_backward(q, k, v, out, lse, do, None, causal,
                            A.Tiles(bk, bq, sub), scale, True)
    want = jax.vjp(lambda q, k, v: naive_attention(q, k, v, causal),
                   q, k, v)[1](do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4)


#: name -> (H, Hkv, Sq rows of one stream, Sk, D, Dv, causal, window,
#: diffusion_block, block_q, block_k, lse cotangent)
_FUSED_BWD_CASES = {
    "causal": (2, 2, 128, 128, 16, 16, True, None, None, 32, 64, False),
    "not-causal-longer-kv": (2, 2, 128, 256, 16, 16, False, None, None, 64,
                             64, False),
    "causal-planned": (2, 2, 1024, 1024, 16, 16, True, None, None, None,
                       None, False),
    "not-causal-planned": (1, 1, 512, 1024, 16, 16, False, None, None, None,
                           None, False),
    "widths-192-128": (2, 2, 128, 128, 192, 128, True, None, None, 64, 64,
                       False),
    "widths-192-128-planned": (1, 1, 512, 512, 192, 128, True, None, None,
                               None, None, True),
    "group-2": (4, 2, 128, 128, 16, 16, True, None, None, 64, 32, False),
    "group-7": (7, 1, 128, 128, 16, 16, True, None, None, 32, 32, False),
    "group-7-planned-not-causal": (7, 1, 512, 512, 16, 16, False, None, None,
                                   None, None, False),
    "window-across-tiles": (2, 2, 256, 256, 16, 16, True, 100, None, 64, 32,
                            False),
    "window-group-7": (7, 1, 256, 256, 16, 16, True, 100, None, 32, 64,
                       True),
    "window-group-2-planned": (4, 2, 1024, 1024, 16, 16, True, 300, None,
                               None, None, False),
    "diffusion-block": (2, 2, 64, 64, 16, 16, True, None, 4, 16, 16, False),
    "diffusion-block-group-4-planned": (4, 1, 128, 128, 16, 16, True, None,
                                        4, None, None, True),
    "lse-cotangent": (2, 2, 128, 128, 16, 16, True, None, None, 32, 64,
                      True),
    "lse-cotangent-group-2-not-causal": (4, 2, 128, 256, 16, 16, False, None,
                                         None, 64, 64, True),
}


@pytest.mark.parametrize("name", sorted(_FUSED_BWD_CASES))
def test_fused_flash_backward_matches_blockwise(name):
    """The ONE backward kernel (interpret mode): dQ — resident over the kv
    tiles, under explicit blocks assembled from several q tiles —, dK and
    dV — summed over a group's query heads, the query head the OUTER axis
    — against ``jax.grad`` of ``blockwise_attention``; the LSE's cotangent
    (the ring's path) folds into delta. Under ``diffusion_block`` q stacks
    both streams and block 0's noisy rows see no key."""
    from harmony_tpu.ops.attention import (
        blockwise_attention_lse, flash_attention_lse)

    (h, hkv, sq, sk, d, dv, causal, window, bd, bq, bk,
     lse_ct) = _FUSED_BWD_CASES[name]
    rows = sq * (2 if bd else 1)
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 5)
    q = jax.random.normal(ks[0], (1, h, rows, d))
    k = jax.random.normal(ks[1], (1, hkv, sk, d))
    v = jax.random.normal(ks[2], (1, hkv, sk, dv))
    w = jax.random.normal(ks[3], (1, h, rows, dv))
    w_lse = jax.random.normal(ks[4], (1, h, rows)) * float(lse_ct)

    def loss(attend):
        def fn(q, k, v):
            out, lse = attend(q, k, v)
            lse = jnp.where(lse > -1e29, lse, 0.0)  # a row that saw no key
            return (out * w).sum() + (lse * w_lse).sum()
        return fn

    got = jax.grad(loss(lambda q, k, v: flash_attention_lse(
        q, k, v, causal, bq, bk, None, True, window, bd)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: blockwise_attention_lse(
        q, k, v, causal, window=window, diffusion_block=bd)), (0, 1, 2))(
            q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4)
    if bd:  # block 0's noisy rows: no key seen, no gradient
        assert float(jnp.abs(got[0][:, :, sq:sq + bd]).max()) == 0.0


def test_flash_plan_lowers_for_tpu_at_the_gpt2_shape():
    """The forward and the backward kernel under the plan at the gpt2
    cell's own shape cross-lower through the Pallas TPU front end (block shapes,
    memory spaces, dynamic loop bounds) without a chip."""
    x = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("harmony_flash_fwd", "harmony_flash_bwd"):
        assert name in text
    assert "harmony_flash_bwd_d" not in text  # ONE backward kernel


def test_flash_plan_is_recorded_at_trace_time():
    """STATUS ``kernel_plans``: which tiles each kernel of a traced program
    runs and the grid steps a call takes under them."""
    from harmony_tpu.runtime import progcache
    from harmony_tpu.tracing import trace_span

    x = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.bfloat16)
    with trace_span("job.build_step", job_id="plan-rec"):
        jax.jit(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True).astype(jnp.float32).sum())).trace(x)
    rows = {r["kernel"]: r for r in progcache.kernel_plans()["plan-rec"]}
    assert rows["harmony_flash_fwd"] == {
        "kernel": "harmony_flash_fwd", "block_q": 512, "block_k": 1024,
        "sub": 1024, "planned": True, "d": 64, "dv": 64, "grid_steps": 192}
    assert set(rows) == {"harmony_flash_fwd", "harmony_flash_bwd"}
    assert rows["harmony_flash_bwd"] == {
        "kernel": "harmony_flash_bwd", "block_q": 1024, "block_k": 512,
        "sub": 512, "planned": True, "d": 64, "dv": 64, "grid_steps": 192}


# -- the router's selection: harmony_top_k_rows (ops/top_k_rows.py) ----------

def _scores(tokens, experts, ties, seed=0):
    """``[tokens, experts]`` float32 scores in (0, 1]: ``"none"`` distinct
    sigmoid scores; ``"ties"`` scores rounded to one decimal (every row
    repeats its values), rows saturated at exactly 1.0 in several lanes, and
    constant rows."""
    rng = np.random.default_rng(seed)
    s = 1.0 / (1.0 + np.exp(-3.0 * rng.normal(size=(tokens, experts))))
    if ties == "ties":
        s = np.round(s, 1)
        s[1::5, ::3] = 1.0
        s[2::5] = 0.5
        s[3::5] = 1.0
    return jnp.asarray(s.astype(np.float32))


@pytest.mark.parametrize("ties", ["none", "ties"])
@pytest.mark.parametrize("weighed", [False, True], ids=["sel", "sel+val"])
@pytest.mark.parametrize("tokens,experts,k", [
    (128, 64, 6), (96, 64, 8), (64, 256, 8), (32, 512, 22), (40, 8, 2),
    (60, 8, 2),   # a token count no tile divides: one block
])
def test_top_k_rows_equals_lax_top_k(tokens, experts, k, weighed, ties):
    """The kernel (interpret mode) against ``lax.top_k`` +
    ``take_along_axis``, bit for bit: the same experts in the same order —
    ties to the LOWER index — and the weights at those lanes."""
    from harmony_tpu.ops.top_k_rows import (tile_plan, top_k_rows,
                                            top_k_rows_ref)

    sel = _scores(tokens, experts, ties)
    val = _scores(tokens, experts, "none", seed=1) if weighed else None
    assert tile_plan(tokens, experts, weighed) == {
        128: 128, 96: 32, 64: 64, 32: 32, 40: 8, 60: 60}[tokens]
    weight, expert = jax.jit(
        lambda s, v: top_k_rows(s, v, k, interpret=True))(sel, val)
    want_w, want_e = top_k_rows_ref(sel, val, k)
    assert expert.dtype == jnp.int32 and weight.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(expert), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(weight), np.asarray(want_w))
    if ties == "ties":  # the constant rows: lanes 0 .. k-1, in order
        np.testing.assert_array_equal(np.asarray(expert)[2], np.arange(k))


def test_top_k_rows_orders_as_lax_top_k_orders():
    """The issue's row, and the total order of the bits behind it: ``+0.0``
    before ``-0.0`` (XLA's sort comparator), negative scores, an infinity."""
    from harmony_tpu.ops.top_k_rows import top_k_rows

    rows = jnp.asarray([[1, 3, 3, 2, 3, 1, 0, 0],
                        [0.0, -0.0, 0.0, -0.0, 1.0, -1.0, 0.0, -0.0],
                        [-3, -1, -2, -1, -5, -1, -4, -np.inf],
                        [np.inf, 2, np.inf, -2, 0, 1, -np.inf, 2]],
                       jnp.float32)
    _, expert = top_k_rows(rows, None, 4, interpret=True)
    assert np.asarray(expert)[0].tolist() == [1, 2, 4, 3]
    np.testing.assert_array_equal(np.asarray(expert),
                                  np.asarray(jax.lax.top_k(rows, 4)[1]))
    with pytest.raises(ValueError, match="top_k_rows"):
        top_k_rows(rows.astype(jnp.bfloat16), None, 4, interpret=True)
    with pytest.raises(ValueError, match="top_k_rows"):
        top_k_rows(rows, None, 9, interpret=True)


@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("weighed", [False, True], ids=["sel", "sel+val"])
@pytest.mark.parametrize("tokens,experts,k", [
    (96, 64, 6), (64, 256, 8), (32, 512, 22), (60, 8, 2)])
def test_top_k_rows_gradient_equals_the_scatter_adds(tokens, experts, k,
                                                     weighed, checkpoint):
    """``jax.grad`` through the op — a compare-and-sum — equals ``jax.grad``
    through the XLA formulation — a scatter-add — bit for bit; where ``val``
    is given the selecting scores get exactly zero."""
    from harmony_tpu.ops.top_k_rows import top_k_rows, top_k_rows_ref

    sel = _scores(tokens, experts, "ties")
    val = _scores(tokens, experts, "none", seed=1) if weighed else None
    g = jnp.asarray(np.random.default_rng(2).normal(size=(tokens, k)),
                    jnp.float32)
    grads = []
    for op in (lambda s, v: top_k_rows(s, v, k, interpret=True),
               lambda s, v: top_k_rows_ref(s, v, k)):
        def loss(s, v, op=op):
            weight, _ = op(s, v)
            return (weight * g).sum() + (weight ** 2).sum()
        if checkpoint:
            loss = jax.checkpoint(loss)
        grads.append(jax.jit(jax.grad(
            loss, argnums=(0, 1) if weighed else 0))(sel, val))
    got, want = (jax.tree_util.tree_leaves(t) for t in grads)
    assert len(got) == len(want) == 1 + weighed
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(np.abs(np.asarray(got[-1])).max()) > 0.0
    if weighed:
        assert not np.asarray(got[0]).any()


def test_top_k_rows_lowers_for_tpu_without_sort_gather_or_scatter():
    """Forward and backward at Nemotron-H's router cross-lower through the
    Pallas TPU front end as ONE kernel — whose name no expert-matmul pattern
    matches (``perf/layer_metrics/_moe_kernels.py``) — and plain elementwise
    work."""
    import re

    from harmony_tpu.ops.top_k_rows import KERNEL_NAME, top_k_rows

    x = jax.ShapeDtypeStruct((8192, 512), jnp.float32)
    text = jax.jit(jax.grad(
        lambda s, v: top_k_rows(s, v, 22)[0].sum(), argnums=(0, 1))
    ).trace(x, x).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1 and KERNEL_NAME in text
    assert KERNEL_NAME == "harmony_top_k_rows"
    assert not re.match(r"harmony_(gmm|moe)_", KERNEL_NAME)
    for op in ("stablehlo.sort", "stablehlo.scatter", "stablehlo.gather",
               "chlo.top_k"):
        assert op not in text, op
