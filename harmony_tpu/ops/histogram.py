"""MXU one-hot histogram kernel.

The GBT trainer's hot op is building per-(node, feature, bin) gradient /
hessian / count histograms (ref: mlapps/gbt/GBTTrainer.java — the reference
does this with Java loops over instances; SURVEY.md §2.7). On TPU a scatter
serialises, but a histogram is also a matmul: ``one_hot(ids)^T @ weights``
— which runs on the 128x128 systolic array at full tilt.

:func:`weighted_histogram` is the Pallas kernel: grid over (W tiles, bin
tiles, N tiles); each step builds its tile's one-hot on the fly in VMEM
(never materialised in HBM) — *bins-major*, so the MXU contraction needs no
transposed operand copy — and accumulates the (bins, W) product into the
revisited output block. Tile sizes are clamped against a VMEM word budget
so the kernel fits the scoped-VMEM limit (16 MB on v5e) at any input size.

:func:`xla_histogram` is the pure-XLA one-hot matmul reference; off-TPU
callers take it by name (``interpret=True`` runs the kernel body in the
Pallas interpreter, for CPU tests).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_BINS = 512
DEFAULT_BLOCK_W = 512

# Budget for one grid step's VMEM working set, in f32 words. The step holds
# the one-hot (bn x bb), double-buffered weight blocks (2 x bn x bw) and the
# revisited output block (2 x bb x bw); ~6 MB keeps the whole set (plus
# Mosaic scratch) comfortably inside the 16 MB scoped-VMEM limit on v5e.
_VMEM_BUDGET_WORDS = 1_500_000
_MIN_TILE = 128


def _pick_tiles(bn: int, bb: int, bw: int) -> Tuple[int, int, int]:
    """Shrink tile sizes until the step's working set fits the budget."""

    def words(n: int, b: int, w: int) -> int:
        return b * n + 2 * n * w + 2 * b * w

    while words(bn, bb, bw) > _VMEM_BUDGET_WORDS:
        if bb >= max(bn, bw) and bb > _MIN_TILE:
            bb //= 2
        elif bn >= bw and bn > _MIN_TILE:
            bn //= 2
        elif bw > _MIN_TILE:
            bw //= 2
        else:
            break
    return bn, bb, bw


def _hist_kernel(ids_ref, w_ref, out_ref):
    """Grid (w_tiles, bins_tiles, n_tiles), n innermost: each step folds one
    tile of N into one (bin, W) output tile. The one-hot is built bins-major
    — rows are tile-local bins, columns are examples — so the MXU contraction
    is a plain (bb, bn) @ (bn, bw) with no transposed-operand copy (the
    transpose copy is what blew the scoped-VMEM limit on v5e)."""
    jb = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Tile-local ids: the bin-tile size is the output block's row count (one
    # source of truth — no kwarg that could drift from the BlockSpec).
    ids = ids_ref[:] - jb * out_ref.shape[0]           # (1, bn) int32, tile-local
    bins = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[:1] + ids.shape[1:], 0)
    onehot = (ids == bins).astype(jnp.float32)         # (bb, bn)
    # (bb, bn) @ (bn, bw) on the MXU, accumulated across n tiles.
    # HIGHEST precision: default MXU f32 truncates multiplicands to bf16 —
    # fine for attention logits, not for histogram sums that feed split-gain
    # ratios; full-f32 passes keep the histogram bit-comparable to scatter.
    out_ref[:] += jax.lax.dot_general(
        onehot, w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def xla_histogram(ids, weights, num_bins):
    """The reference: one-hot matmul in plain XLA, any backend."""
    onehot = jax.nn.one_hot(ids, num_bins, dtype=jnp.float32)
    return onehot.T @ weights.astype(jnp.float32)


def weighted_histogram(
    ids: jnp.ndarray,
    weights: jnp.ndarray,
    num_bins: int,
    block_n: int = DEFAULT_BLOCK_N,
    block_bins: int = DEFAULT_BLOCK_BINS,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[b, w] = sum over i with ids[i]==b of weights[i, w]``, as the
    Pallas kernel.

    ids [N] int32 (out-of-range / negative ids contribute nothing),
    weights [N, W] -> [num_bins, W] float32.
    """
    if ids.ndim != 1 or weights.ndim != 2 or ids.shape[0] != weights.shape[0]:
        raise ValueError(f"bad shapes ids={ids.shape} weights={weights.shape}")
    N, W = weights.shape
    if N == 0 or W == 0:
        # A zero-size grid would skip the kernel's i==0 init entirely and
        # return an uninitialized buffer (and W == 0 would zero the block
        # size the pads divide by).
        return jnp.zeros((num_bins, W), jnp.float32)
    block_n = min(block_n, max(N, 8))
    block_bins = min(block_bins, num_bins)
    block_w = min(block_w, W)
    block_n, block_bins, block_w = _pick_tiles(block_n, block_bins, block_w)
    pad = (-N) % block_n
    pad_w = (-W) % block_w
    if pad or pad_w:
        # one pad for both axes (a second pad would copy the array twice);
        # padded ids = -1: match no bin
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
        weights = jnp.pad(weights, ((0, pad), (0, pad_w)))
        N += pad
    Wp = W + pad_w
    pad_bins = (-num_bins) % block_bins
    nb = num_bins + pad_bins
    out = pl.pallas_call(
        _hist_kernel,
        grid=(Wp // block_w, nb // block_bins, N // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda jw, jb, i: (0, i)),
            pl.BlockSpec((block_n, block_w), lambda jw, jb, i: (i, jw)),
        ],
        out_specs=pl.BlockSpec((block_bins, block_w), lambda jw, jb, i: (jb, jw)),
        out_shape=jax.ShapeDtypeStruct((nb, Wp), jnp.float32),
        interpret=interpret,
        name="harmony_weighted_histogram",
    )(ids.astype(jnp.int32)[None, :], weights)
    return out[:num_bins, :W]
