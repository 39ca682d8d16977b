"""The flash kernels under their own tile plan, compiled by Mosaic for a v5e
that is described, not attached — what interpret mode and cross-lowering
cannot see: a slice Mosaic cannot prove aligned, a plan over the scoped
VMEM. Nothing runs; no time comes from here.

The topology is described inside a fixture, never at import (only one
process may load libtpu, and every xdist worker imports this file), and
the compiles stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from harmony_tpu.ops.attention import flash_attention_lse, tile_plan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,h,s,d,dtype,causal", [
    (8, 12, 1024, 64, jnp.bfloat16, True),    # the gpt2-124m cells' call
    (8, 12, 197, 64, jnp.bfloat16, False),    # ViT-B/16: one block of 197
    (2, 4, 197, 64, jnp.float32, True),       # ... under a dynamic guard
    (1, 8, 8192, 128, jnp.bfloat16, True),    # carries vmem_limit_bytes
    (2, 16, 4096, 128, jnp.bfloat16, True),   # the olmoe-1b-7b cell's call
])
def test_planned_flash_kernels_compile_for_v5e(one_chip, b, h, s, d, dtype,
                                               causal):
    plan = tile_plan(s, s, d, dtype, causal)
    assert (plan.bwd.vmem_limit_bytes is not None) == (s >= 4096)
    x = jax.ShapeDtypeStruct((b, h, s, d), dtype, sharding=one_chip)

    def loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal)
        return out.astype(jnp.float32).sum() + lse.sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("b,h,s,d,dv,dtype", [
    (2, 16, 8192, 192, 128, jnp.bfloat16),   # the moonlight-16b-a3b cell's
    (1, 4, 1024, 192, 128, jnp.float32),     # ... and its float32 twin
    (2, 4, 197, 24, 16, jnp.bfloat16),       # one block, widths off a lane
])
def test_flash_kernels_with_a_value_width_compile_for_v5e(one_chip, b, h, s, d,
                                                          dv, dtype):
    """q and k 192 wide (a lane tile and a half: VMEM pads it to 256), v,
    the output, dO and dV 128 wide: one lowering, no padded caller."""
    sd = lambda w: jax.ShapeDtypeStruct((b, h, s, w), dtype, sharding=one_chip)

    def loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, True)
        return out.astype(jnp.float32).sum() + lse.sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sd(d), sd(d), sd(dv)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    dq, dk, dvv = compiled.output_shardings  # three outputs came back
    assert [o.shape[-1] for o in jax.eval_shape(
        jax.grad(loss, argnums=(0, 1, 2)), sd(d), sd(d), sd(dv))] == [d, d, dv]


@pytest.mark.parametrize("h,hkv,s,block,dtype", [
    (32, 4, 8192, 4, jnp.bfloat16),    # the sdar-30b-a3b cell's stacked call
    (4, 1, 1024, 16, jnp.float32),     # ... a block the sub-blocks align to
])
def test_block_diffusion_flash_kernels_compile_for_v5e(one_chip, h, hkv, s,
                                                       block, dtype):
    """Both streams' queries stacked (2 s rows a query head) against the
    clean keys (s rows a K/V head) under the mask by block and stream: the
    two kernels under the plan of ONE stream's length, grouped heads
    through the index maps, with the own-block term and the merge by the
    two log-sum-exps behind them."""
    from harmony_tpu.ops.attention import merge_by_lse, own_block_attention

    sd = lambda heads, rows: jax.ShapeDtypeStruct(
        (1, heads, rows, 128), dtype, sharding=one_chip)

    def loss(q, k, v, kn, vn):
        out, lse = flash_attention_lse(q, k, v, True, diffusion_block=block)
        own, own_lse = own_block_attention(q[:, :, s:], kn, vn, block)
        noisy = merge_by_lse(out[:, :, s:], lse[:, :, s:], own, own_lse)
        return (out[:, :, :s].astype(jnp.float32).sum()
                + noisy.astype(jnp.float32).sum())

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sd(h, 2 * s), sd(hkv, s), sd(hkv, s), sd(hkv, s),
        sd(hkv, s)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("harmony_flash_bd_fwd", "harmony_flash_bd_bwd"):
        assert name in text, name
    assert "harmony_flash_bd_bwd_d" not in text  # ONE backward kernel


#: the flash call of every LM cell: (batch, query heads, K/V heads, positions,
#: q.k width, v width, window, diffusion block) -> the backward's tiles and
#: the MiB of VMEM its plan counts (``vmem_limit_bytes`` is 16 MiB over that)
_CELL_CALLS = {
    "gpt2-124m": ((8, 12, 12, 1024, 64, 64, None, None),
                  (1024, 512, 512), None),
    "olmoe-1b-7b": ((2, 16, 16, 4096, 128, 128, None, None),
                    (4096, 512, 512), 22.5),
    # sixteen block applications a step (4 layers x 4 passes), one call each
    "ouro-2.6b": ((1, 16, 16, 4096, 128, 128, None, None),
                  (4096, 512, 512), 22.5),
    "moonlight-16b-a3b": ((2, 16, 16, 8192, 192, 128, None, None),
                          (8192, 512, 512), 51.25),
    "kimi-linear-48b-a3b": ((1, 8, 8, 8192, 192, 128, None, None),
                            (8192, 512, 512), 51.25),
    "nemotron-3-super-120b-a12b": ((1, 4, 1, 8192, 128, 128, None, None),
                                   (8192, 512, 512), 46.0),
    "zaya1-8b": ((1, 8, 2, 8192, 128, 128, None, None),
                 (8192, 512, 512), 46.0),
    # a head's whole 16,384 rows one streamed tile: fetched once a head
    "smallthinker-21b-a3b-full": ((1, 28, 4, 16384, 128, 128, None, None),
                                  (16384, 512, 512), 86.0),
    "smallthinker-21b-a3b-window": ((1, 28, 4, 16384, 128, 128, 4096, None),
                                    (1024, 512, 512), 41.0),
    # two stacked streams cannot be one tile: parts of 2,048 rows
    "sdar-30b-a3b": ((1, 32, 4, 8192, 128, 128, None, 4),
                     (2048, 512, 512), 36.0),
    # 256-wide heads, eight query heads a K/V head: parts of 2,048 rows
    "qwen3-next-80b-a3b": ((1, 16, 2, 16384, 256, 256, None, None),
                           (2048, 512, 512), 79.0),
}


@pytest.mark.parametrize("cell", sorted(_CELL_CALLS))
def test_fused_flash_backward_compiles_at_each_cells_shape(one_chip, cell):
    """The ONE backward kernel under its own plan at every LM cell's call,
    compiled by Mosaic for a v5e: a query head's whole dQ resident beside
    the tiles (and, under grouped heads, the K/V head's whole dK and dV),
    under the VMEM limit the plan asks for — a plan that cannot lower or
    over-asks fails here, not at the driver. No shape takes two kernels."""
    from harmony_tpu.ops.attention import kernel_name

    (b, h, hkv, s, d, dv, window, block), tiles, mib = _CELL_CALLS[cell]
    streams = 2 if block else 1
    plan = tile_plan(s, s, d, jnp.bfloat16, True, dv=dv, window=window,
                     group=h // hkv, streams=streams)
    assert plan.bwd[:3] == tiles
    assert plan.bwd.vmem_limit_bytes == (
        None if mib is None else (mib + 16) * 2**20)
    sd = lambda heads, rows, w: jax.ShapeDtypeStruct(
        (b, heads, rows, w), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, True, window=window,
                                       diffusion_block=block)
        return out.astype(jnp.float32).sum() + lse.sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sd(h, streams * s, d), sd(hkv, s, d), sd(hkv, s, dv)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    name = kernel_name("bwd", window, block)
    assert name in text and name + "_d" not in text


@pytest.mark.parametrize("m,k,n,groups,dtype", [
    (65536, 2048, 1024, 16, jnp.bfloat16),  # olmoe-1b-7b.solo: gate / up
    (65536, 1024, 2048, 16, jnp.bfloat16),  # ... and down
    (98304, 2048, 1408, 8, jnp.bfloat16),   # moonlight-16b-a3b.solo: 1408
    (98304, 1408, 2048, 8, jnp.bfloat16),   # whole, as n and as k
    (300, 64, 32, 4, jnp.float32),          # rows and widths off every tile
])
def test_grouped_matmul_kernels_compile_for_v5e(one_chip, m, k, n, groups,
                                                dtype):
    """Forward and both backward products: a dynamic grid bound and
    scalar-prefetched index maps, which the interpreter cannot vouch for."""
    from harmony_tpu.ops.grouped_matmul import grouped_matmul

    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = jax.grad(lambda x, w, g: grouped_matmul(
        x, w, g, interpret=False).astype(jnp.float32).sum(), argnums=(0, 1))
    compiled = jax.jit(fn).lower(sd((m, k), dtype), sd((groups, k, n), dtype),
                                 sd((groups,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # dx and dw
    fwd = jax.jit(lambda x, w, g: grouped_matmul(x, w, g, interpret=False))
    assert fwd.lower(sd((m, k), dtype), sd((groups, k, n), dtype),
                     sd((groups,), jnp.int32)).compile().as_text().count(
        "tpu_custom_call") == 1


@pytest.mark.parametrize("tokens,experts,k,weighed", [
    (8192, 512, 22, True),    # nemotron-3-super-120b-a12b.solo
    (8192, 256, 8, True),     # kimi-linear-48b-a3b.solo
    (16384, 64, 6, True),     # moonlight-16b-a3b.solo, smallthinker's width
    (16384, 64, 8, False),    # olmoe-1b-7b.solo: softmax, no second operand
    (4096, 2048, 8, True),    # a width whose plan must shrink the tile
    (60, 8, 2, True),         # a token count no tile divides: one block
])
def test_router_selection_compiles_for_v5e(one_chip, tokens, experts, k,
                                           weighed):
    """``harmony_top_k_rows`` and its compare-and-sum backward at the four
    routers' shapes: two in-kernel transposes and a dynamic row store under
    the kernel's own VMEM limit; the backward holds no scatter and no second
    kernel."""
    from harmony_tpu.ops.top_k_rows import tile_plan, top_k_rows

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)

    def loss(sel, val, g):
        return (top_k_rows(sel, val if weighed else None, k)[0] * g).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sd(tokens, experts), sd(tokens, experts), sd(tokens, k)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "harmony_top_k_rows" in text and " scatter(" not in text
    assert tile_plan(tokens, experts, weighed) == {
        2048: 256 if weighed else 512, 8: 60}.get(experts, 512)


@pytest.mark.parametrize("n,d,v,tied", [
    (8192, 768, 50257, True),      # gpt2-124m.solo: 81 rows in the last tile
    (4096, 768, 50257, True),      # gpt2-124m.pair, a tenant
    (8192, 2048, 32784, True),     # zaya1-8b.solo: 256 x 128 + 16 rows
    (16384, 3072, 12544, False),   # laguna-s-2.1.solo
    (16384, 2048, 20480, False),   # moonlight-16b-a3b.solo
    (8192, 4096, 16384, False),    # nemotron-3-super-120b-a12b.solo: widest
    (8192, 2304, 20480, False),    # kimi-linear-48b-a3b.solo
    (16384, 2560, 18992, False),   # smallthinker-21b-a3b.solo: lanes ragged
    (8192, 2048, 12576, False),    # olmoe-1b-7b.solo
    (8192, 2048, 18992, False),    # sdar-30b-a3b.solo
    (4096, 2048, 49152, False),    # ouro-2.6b.solo: an exit, four a step
])
def test_readout_loss_kernels_compile_for_v5e(one_chip, n, d, v, tied):
    """``harmony_readout_fwd`` / ``_bwd_dx`` / ``_bwd_dw`` under their own
    plan at the LM cells' readouts: every plan inside the kernels' VMEM
    scope, ``dW``'s last block written as far as a ragged vocabulary goes,
    and no ``[N, V]`` array but the logits (the other temporaries — the
    head rounded to bfloat16, ``x`` transposed, the ``[N, 1]`` columns — are
    a third of them at most)."""
    from harmony_tpu.ops import readout_loss as R

    tiles = R.plan(n, d, v, tied, jnp.bfloat16)
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)

    def loss(x, head, targets, w):
        return (R.readout_nll(x, head, targets, tied=tied) * w).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sd((n, d), jnp.bfloat16),
        sd((v, d) if tied else (d, v), jnp.float32),
        sd((n,), jnp.int32), sd((n,), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3
    for name in (R.FWD_NAME, R.DX_NAME, R.DW_NAME):
        assert name in text
    logits = 4 * n * (-(-v // tiles.vocab_tile) * tiles.vocab_tile)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * logits


#: the rotary turn of every LM cell that has one a 128-wide head: (batch,
#: heads, positions, columns turned, by rows: the operand lies [B, S, H hd]);
#: SDAR's norm a head runs inside the kernel (PR 60)
_NORMED = {"sdar-30b-a3b-q", "sdar-30b-a3b-k"}
_ROTARY_CALLS = {
    "sdar-30b-a3b-q": (2, 32, 8192, 128, True),
    "sdar-30b-a3b-k": (2, 4, 8192, 128, True),
    "zaya1-8b-q": (1, 8, 8192, 64, False),         # behind CCA, half turned
    "smallthinker-21b-a3b-q": (1, 28, 16384, 128, True),
    "smallthinker-21b-a3b-k": (1, 4, 16384, 128, True),
    "olmoe-1b-7b": (2, 16, 4096, 128, True),
    "ouro-2.6b": (1, 16, 4096, 128, True),
    "laguna-s-2.1-full-q": (1, 6, 16384, 64, True),    # six heads a step
    "laguna-s-2.1-window-q": (1, 9, 16384, 128, True),  # nine
    "laguna-s-2.1-k": (1, 1, 16384, 128, True),
}


@pytest.mark.parametrize("call", sorted(_ROTARY_CALLS))
def test_rotary_kernel_compiles_at_each_cells_shape(one_chip, call):
    """``harmony_rotary`` forward and backward under its own plan: the lane
    rolls, a row tile of 2,048 x 128 with three tables inside the scoped
    VMEM, the ``[B, S, H hd]`` walk's blocks of several heads, and the
    normed turn's third stream and float32 temporaries beside them."""
    from harmony_tpu.ops import rotary as R

    b, h, s, turned, by_rows = _ROTARY_CALLS[call]
    x = jax.ShapeDtypeStruct((b, s, h * 128) if by_rows else (b, h, s, 128),
                             jnp.bfloat16, sharding=one_chip)
    assert R.plan(s, 128, jnp.bfloat16, h if by_rows else None) is not None

    def loss(x, w, offset):
        tab, shifts = R.tables(s, 128, 1e6, offset, turned)
        y = R.turn(x, tab, shifts, heads=h if by_rows else None,
                   norm=(w, 1e-6) if call in _NORMED else None)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        x, jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert R.KERNEL_NAME in text


@pytest.mark.parametrize("heads", [16, 2], ids=["qwen3-next-q", "qwen3-next-k"])
def test_rotary_kernel_compiles_at_256_wide_heads_a_quarter_turned(one_chip,
                                                                   heads):
    """Qwen3-Next's turn (PR 61): heads of 256 columns — two lane tiles —
    of which the first 64 turn, the norm a head (the ``1 + w`` weight is
    handed over as one vector) inside the kernel, q's 16 heads and k's 2 by
    rows at 16,384 positions, forward and backward."""
    from harmony_tpu.ops import rotary as R

    s, hd, turned = 16384, 256, 64
    assert R.plan(s, hd, jnp.bfloat16, heads) is not None
    x = jax.ShapeDtypeStruct((1, s, heads * hd), jnp.bfloat16,
                             sharding=one_chip)

    def loss(x, w, offset):
        tab, shifts = R.tables(s, hd, 1e7, offset, turned)
        y = R.turn(x, tab, shifts, heads=heads, norm=(1.0 + w, 1e-6))
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        x, jax.ShapeDtypeStruct((hd,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert R.KERNEL_NAME in text


#: the delta-rule cells' hand-over (PR 62): positions, the operand's
#: columns, the sections of a call
_CONV_CALLS = {
    "qwen3-next-80b-a3b-qkvz": (16384, 12288, (
        ("l2_scaled", 16), ("l2", 16), ("plain", 32))),
    "kimi-linear-48b-a3b-q": (8192, 1024, (("l2_scaled", 8),)),
    "kimi-linear-48b-a3b-k": (8192, 1024, (("l2", 8),)),
    "kimi-linear-48b-a3b-v": (8192, 1024, (("plain", 8),)),
}


@pytest.mark.parametrize("call", sorted(_CONV_CALLS))
def test_conv_heads_kernel_compiles_at_each_cells_shape(one_chip, call):
    """``harmony_conv_heads`` forward and backward under its own plan: the
    taps as sublane-offset reads of a float32 scratch, the 16-row views
    before and after a tile, a row tile of 1,024 x 4 heads with both
    scratches inside the scoped VMEM, ``dtaps`` a ``[K, 512]`` partial a
    step — a kernel a section, each way."""
    from harmony_tpu.ops import conv_heads as C

    s, columns, sections = _CONV_CALLS[call]
    conv = 128 * sum(h for _, h in sections)
    assert C.plan(s, 128, jnp.bfloat16, sections, 4) == (1024, 4)

    def loss(x, taps):
        return sum((y.astype(jnp.float32) ** 2).sum()
                   for y in C.conv_heads(x, taps, sections, 1e-6))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        jax.ShapeDtypeStruct((1, s, columns), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((4, conv), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2 * len(
        sections)
    assert C.KERNEL_NAME in text


def test_scalar_decay_delta_rule_kernels_compile_at_the_cells_shape(one_chip):
    """``harmony_gdn_fwd`` / ``harmony_gdn_bwd`` (ops/kda.py, PR 61) at
    ``qwen3-next-80b-a3b.solo``'s call: 32 value heads over 16 key heads of
    128, 16,384 positions in chunks of 64, eight chunks a grid step (PR 63:
    two chunks' solves a ``[128, 128]`` tile, its diagonal blocks sliced out
    at a lane offset of 64), ``g`` and ``beta`` a scalar a position — the
    ``[1, C]`` rows, their turn to columns and back, and the ``h // 2``
    index maps through Mosaic for a v5e; ONE forward and ONE backward
    kernel, and what the forward keeps is 1 / 64 of a state a position."""
    from harmony_tpu.ops import kda as K

    s, hk, hv, d = 16384, 16, 32, 128
    assert K.gdn_plan(hv, s // K.CHUNK) == (8 * K.CHUNK, hv * s // K.CHUNK // 8)
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)

    def loss(q, k, v, g, beta):
        o = K.gdn_attention(q, k, v, g, beta, interpret=False)
        return (o.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sd((1, hk, s, d), jnp.bfloat16), sd((1, hk, s, d), jnp.bfloat16),
        sd((1, hv, s, d), jnp.bfloat16), sd((1, hv, s), jnp.float32),
        sd((1, hv, s), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    for name in ("harmony_gdn_fwd", "harmony_gdn_bwd"):
        assert name in text, name
    assert "harmony_kda" not in text
    states = hv * (s // K.CHUNK) * (d * d + K.CHUNK * K.CHUNK) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * states


@pytest.mark.parametrize("tokens,cfg,checkpoint", [
    # kimi-linear-48b-a3b.solo: 65,536 slots in 16 chunks of 4,096, remat
    (8192, dict(num_experts=256, top_k=8, d_model=2304, d_ff=1024,
                experts_held=8, score="sigmoid", norm_topk=True,
                routed_scale=2.446, shared_experts=1), True),
    # moonlight-16b-a3b.solo: 98,304 slots in 4 chunks of 24,576
    (16384, dict(num_experts=64, top_k=6, d_model=2048, d_ff=1408,
                 experts_held=8, score="sigmoid", norm_topk=True,
                 routed_scale=2.446, shared_experts=2), False),
    # smallthinker-21b-a3b.solo: the same cut at 2,560 lanes, remat
    (16384, dict(num_experts=64, top_k=6, d_model=2560, d_ff=768,
                 experts_held=8, norm_topk=True, act="relu"), True),
], ids=["kimi-linear", "moonlight", "smallthinker"])
def test_chunked_expert_layer_compiles_for_v5e(one_chip, monkeypatch, tokens,
                                               cfg, checkpoint):
    """The expert layer's gradient at the cells' shapes, chunked (the plan
    the shapes give) against full-length (a row tile no shape reaches): as
    many grouped-matmul calls — one body a layer and pass, no second
    capacity — and no more temporary memory. The chunked form's row sums are
    ``harmony_sum_rows`` (one call a pass, at the tile its plan gives for
    2,048 / 2,304 / 2,560 lanes under its own VMEM limit), and no XLA
    scatter of ``[C, d]`` rows is left beside them."""
    import re

    from harmony_tpu.models import moe
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "trace_is_tpu", lambda: True)
    cfg = moe.DroplessConfig(**cfg)
    params = jax.eval_shape(
        lambda: moe.init_dropless_params(jax.random.PRNGKey(0), cfg))
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    x = jax.ShapeDtypeStruct((tokens, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    got = {}
    for form, tile in (("chunked", 512), ("full", 1 << 30)):
        monkeypatch.setattr(moe, "_ROW_TILE", tile)
        assert bool(moe.chunk_plan(tokens * cfg.top_k, cfg.experts_held,
                                   cfg.num_experts)[1]) == (form == "chunked")

        def loss(p, x):  # a new function each time: checkpoint caches traces
            out, stats = moe.moe_ffn_dropless(p, x, cfg)
            return (out.astype(jnp.float32) ** 2).sum() + stats["prob_sum"].sum()

        compiled = jax.jit(jax.value_and_grad(
            jax.checkpoint(loss) if checkpoint else loss, argnums=(0, 1))
        ).lower(jax.tree_util.tree_map(sd, params), x).compile()
        got[form] = (compiled.as_text().count(
            "custom_call_target=\"tpu_custom_call\""),
                     compiled.memory_analysis().temp_size_in_bytes)
        if form == "chunked":
            text = compiled.as_text()
    sums = 3 if checkpoint else 2
    # nine grouped matmuls and the router's selection (PR 43), the forward
    # ones again under remat
    assert got["full"][0] == (14 if checkpoint else 10)
    assert got["chunked"][0] == got["full"][0] + sums
    assert sum("harmony_top_k_rows" in line for line in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in line) == (
        2 if checkpoint else 1)
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sorts and not any("moe.route" in line for line in sorts)
    assert sum("harmony_sum_rows" in line for line in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in line) == sums
    wide = [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= \w+\[\d+,{cfg.d_model}\]\S* scatter\(", line)]
    assert not wide, wide
    assert got["chunked"][1] <= got["full"][1], got


@pytest.mark.parametrize("model,capacity", [
    (1, 1 << 24),   # criteo-fm.solo: 2^24 rows, 8.6 GB, on one chip
    (4, 1 << 26),   # criteo-fm-x4.solo: 2^26 rows, 8.6 GB a chip
])
def test_keyed_push_compiles_in_place_for_v5e(one_chip, model, capacity):
    """The scatter route of the keyed cells' push, 212,993 keys into
    float32 rows 128 wide, compiled by Mosaic and XLA for a v5e: the Pallas
    row scatter-add is there, the table is aliased onto the result whole,
    and nothing table-sized is copied — on one chip and row-sharded over
    four under ``shard_map``. (An unaliased table would not fit: 2 x 8.6 GB
    on a 16 GB chip.)"""
    import math
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harmony_tpu.config.params import TableConfig
    from harmony_tpu.table.table import TableSpec, block_sharding
    from harmony_tpu.utils.platform import traced_on

    from jax.experimental import topologies

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[:model]
    mesh = Mesh(np.array(devices).reshape(1, model), ("data", "model"))
    spec = TableSpec(TableConfig(table_id="keyed", capacity=capacity,
                                 value_shape=(128,), num_blocks=256))
    tsh = block_sharding(mesh, spec.num_blocks)
    flat = NamedSharding(mesh, P())
    n = 212_993
    compiled = jax.jit(
        traced_on(mesh, lambda a, k, d: spec.push(a, k, d, via="scatter")),
        out_shardings=tsh, donate_argnums=0).lower(
        jax.ShapeDtypeStruct(spec.storage_shape, spec.dtype, sharding=tsh),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=flat),
        jax.ShapeDtypeStruct((n, 128), jnp.float32, sharding=flat)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "harmony_scatter_add_rows" in text
    on_chip = math.prod(spec.storage_shape) // model
    assert compiled.memory_analysis().alias_size_in_bytes == on_chip * 4
    entry = text[text.index("\nENTRY "):]
    moved = [
        line.strip()[:120] for line in entry.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|scatter|add|fusion)\(",
                            line)]
        if m and math.prod(map(int, m.group(1).split(","))) == on_chip]
    assert not moved, moved


def _tiny_lm_step(mesh, optimizer, dtype=jnp.float32):
    """The small LM's step as the worker builds it (``pull_all_step``,
    ``_step_core``'s own body), compiled for ``mesh``: ``(trainer, spec,
    lowering, compiled)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harmony_tpu.dolphin.worker import pull_all_step, update_lowering
    from harmony_tpu.models import TransformerConfig, TransformerTrainer
    from harmony_tpu.table.table import TableSpec, block_sharding
    from harmony_tpu.utils.platform import traced_on

    trainer = TransformerTrainer(
        TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_seq=64, attn="blockwise", dtype=dtype),
        row_width=128, optimizer=optimizer)
    spec = TableSpec(trainer.model_table_config())
    tsh = block_sharding(mesh, spec.num_blocks)
    scalar = jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(traced_on(mesh, pull_all_step(spec, trainer, mesh)),
                       out_shardings=(tsh, None), donate_argnums=0).lower(
        jax.ShapeDtypeStruct(spec.storage_shape, spec.dtype, sharding=tsh),
        (jax.ShapeDtypeStruct((4, 33), jnp.int32,
                              sharding=NamedSharding(mesh, P("data"))),),
        {k: scalar for k in trainer.hyperparams()}).compile()
    return trainer, spec, update_lowering(spec, trainer, mesh), compiled


def _entry_results(text, sizes, ops):
    """Lines of the entry computation whose op is one of ``ops`` and whose
    result (any one array of it) has one of ``sizes`` elements."""
    import math
    import re

    entry = text[text.index("\nENTRY "):]
    hit = []
    for line in entry.splitlines():
        m = re.search(r"= (\(?\w+\[.*?) (%s)\(" % ops, line)
        if m and any(math.prod(map(int, dims.split(","))) in sizes
                     for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))):
            hit.append(line.strip()[:140])
    return hit


@pytest.mark.parametrize("optimizer,dtype", [
    ("adam", jnp.float32), ("momentum", jnp.float32),
    # bf16 activations: every weight is cast, and a cast commutes with a
    # STATIC slice of the pulled table — read that way the leaves cost one
    # cast of the whole [p | m | v] table a step (PERF.md, PR 42)
    ("adam", jnp.bfloat16)])
def test_lm_step_leaves_the_table_in_its_stored_layout(one_chip, optimizer,
                                                       dtype):
    """The small LM's fused PULL -> COMP -> PUSH step, from the worker's own
    function: the table enters in the default layout and no whole-table
    copy, reshape, pad or slice is among the ops the device runs — under
    blocks of 9 rows the compiler stored it ``{2,0,1}`` and relaid it out
    six times a step. And the update runs in PUSH on the stored rows: the
    table is aliased onto the result whole, and the ops whose result is the
    size of the table or of its sections together are the two that update
    it in place (the fold kernel, the counter's ``dynamic-update-slice``)
    — no concatenated delta, no whole-table add.
    (Not sgd: its table IS the parameter section, whose one relayout, rows
    to leaves, the model needs.)"""
    import math
    import re

    from harmony_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(list(one_chip.device_set), data=1)
    trainer, spec, lowering, compiled = _tiny_lm_step(mesh, optimizer, dtype)
    assert trainer.leaf_rows.record()["pad_rows"]  # leaves rounded up to tiles
    assert lowering == "row_ranges"
    text = compiled.as_text()

    stored = "f32[%s]{2,1,0:" % ",".join(map(str, spec.storage_shape))
    layout = re.search(r"entry_computation_layout=\{\((\S+),", text).group(1)
    assert layout.startswith(stored), layout
    table = math.prod(spec.storage_shape)
    moved = _entry_results(text, {table}, "copy|reshape|pad|slice")
    assert not moved, moved
    assert compiled.memory_analysis().alias_size_in_bytes == table * 4
    sections = ((1 + trainer.num_state_slots) * trainer.section_rows
                * trainer.row_width)
    wrote = _entry_results(text, {table, sections}, r"\S+")
    # not writes of the device's memory: views, and the prefetch into fast
    # memory that only a table this small gets
    wrote = [w for w in wrote if not re.search(
        r" (bitcast|parameter|tuple|get-tuple-element|copy-start|copy-done)"
        r"\(", w)]
    # both in place: the fold kernel, and the counter's +1 into 8 rows
    assert len(wrote) == 2, wrote
    assert "harmony_fold_row_sections" in wrote[0], wrote
    assert "dynamic-update-slice" in wrote[1].split(" = ")[0], wrote


def test_lm_step_on_a_row_sharded_table_keeps_the_whole_delta(one_chip):
    """Four chips, the table's blocks split over the model axis: a
    parameter's row and its m and v rows lie on different shards, so the
    update is no local fold — the predicate says ``whole_delta`` and the
    step compiles as before (the pull's all-gather, each shard's rows
    aliased). Adam's 64 blocks split four ways; momentum's 43 do not, and
    that table, whole on every chip, takes the row ranges."""
    import math

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"))
    trainer, spec, lowering, compiled = _tiny_lm_step(mesh, "adam")
    assert spec.num_blocks % 4 == 0 and lowering == "whole_delta"
    assert "all-gather" in compiled.as_text()
    table = math.prod(spec.storage_shape)
    assert compiled.memory_analysis().alias_size_in_bytes == table // 4 * 4
    trainer, spec, lowering, compiled = _tiny_lm_step(mesh, "momentum")
    assert spec.num_blocks % 4 and lowering == "row_ranges"
    assert "all-gather" not in compiled.as_text()


def _optimizer_rule(optimizer):
    """``optimizer`` as the fold's elementwise rule over its sections."""
    from harmony_tpu.dolphin import optim

    def rule(stored, g, consts):
        p, m, v = (*stored, g, g)[:3]
        new = optim.apply(optimizer, p, g, m, v, consts[0:1],
                          {"lr": consts[1:2], "beta2": consts[2:3]})
        return tuple(n - o for n, o in zip(new, stored))

    return rule


@pytest.mark.parametrize("rows,optimizer", [
    (121_424, "adam"),       # gpt2-124m: three sections of 0.498 GB
    (279_960, "adam"),       # olmoe-1b-7b
    (261_008, "adam"),       # moonlight-16b-a3b
    (121_424, "momentum"),   # two sections
    (121_424, "sgd"),        # the parameters alone
])
def test_section_fold_compiles_in_place_for_v5e(one_chip, rows, optimizer):
    """The optimizer inside ``harmony_fold_row_sections`` at the LM cells'
    section sizes (1024 lanes; the last block of each overlaps the one
    before it), compiled by Mosaic for a v5e: one custom call, the table
    aliased onto the result whole, no temporary."""
    from harmony_tpu.dolphin import optim
    from harmony_tpu.ops.sections import fold_row_sections

    sections = 1 + optim.num_slots(optimizer)
    rule = _optimizer_rule(optimizer)

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    table = sd(sections * rows + 8, 1024)
    compiled = jax.jit(
        lambda t, g, c: fold_row_sections(t, g, c, rule, rows=rows,
                                          sections=sections),
        donate_argnums=0).lower(table, sd(rows, 1024), sd(3, 1024)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == (sections * rows + 8) * 1024 * 4
    assert ma.temp_size_in_bytes == 0


@pytest.mark.parametrize("config", [
    "gpt2-124m",                    # 62 pieces, a dozen of 16 rows
    "zaya1-8b",                     # 26, four expert stacks of 32,768 rows
    "nemotron-3-super-120b-a12b",   # 61
])
def test_section_fold_reads_the_benchmark_pieces_for_v5e(one_chip, config):
    """The fold with the gradient in ``LeafRows``' pieces at a benchmark
    configuration's real shapes — every piece an operand in HBM, the walk
    in SMEM — compiled by Mosaic for a v5e: still ONE custom call, the
    table aliased onto the result whole, no temporary beside the few
    short pieces' blocks."""
    import json
    import os

    from harmony_tpu.models import TransformerTrainer
    from harmony_tpu.ops.sections import _BLOCK_ROWS, fold_row_sections

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", config + ".json")) as f:
        trainer = TransformerTrainer(**json.load(f)["job"]["app_params"])
    lr = trainer.leaf_rows
    pieces = jax.eval_shape(lr.to_pieces, jax.eval_shape(
        lambda: trainer.model.init(jax.random.PRNGKey(0))))

    rule = _optimizer_rule("adam")
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    rows = lr.rows
    compiled = jax.jit(
        lambda t, ps, c: fold_row_sections(
            t, list(zip(lr.piece_firsts, ps)), c, rule, rows=rows,
            sections=3),
        donate_argnums=0).lower(
            sd(3 * rows + 8, 1024), tuple(sd(*p.shape) for p in pieces),
            sd(3, 1024)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == (3 * rows + 8) * 1024 * 4
    short = sum(end - first < _BLOCK_ROWS for first, end in zip(
        lr.piece_firsts, [*lr.piece_firsts[1:], rows]))
    assert ma.temp_size_in_bytes <= short * _BLOCK_ROWS * 1024 * 4
