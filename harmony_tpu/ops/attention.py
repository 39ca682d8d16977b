"""Flash attention for TPU: two Pallas kernels and a differentiable blockwise.

The reference has no attention models at all (SURVEY.md §5.7) — long-context
support is a first-class extension of this framework, not a port. Two tiers:

  * :func:`blockwise_attention` — pure-JAX streaming-softmax attention
    (lax.scan over KV blocks, O(S) memory). Differentiable by autodiff;
    numerically identical to flash attention. Works on any backend.
  * :func:`flash_attention` — Pallas TPU kernels forward AND backward
    (``harmony_flash_fwd``, ``harmony_flash_bwd``): online softmax state in
    VMEM scratch, QK^T and PV on the MXU in the operands' dtype with fp32
    accumulation; the forward saves only the per-row log-sum-exp and the
    backward recomputes each softmax tile from it (the flash-attention
    trade of FLOPs for HBM traffic) — ONCE: one backward kernel evaluates a
    score tile (``q k^T``, ``exp``, ``dO v^T``, ``ds``) and takes all three
    gradients from it (``p^T dO``, ``ds^T q``, ``ds k``: five products a
    tile), a query head's whole dQ resident in VMEM beside the K/V tile's
    dK and dV.

A grid step of a Pallas kernel costs ~0.35 us on a v5e whatever it
computes, so the kernels choose how much one step does from the shape
(:func:`tile_plan`): a step holds one RESIDENT tile (q rows for the
forward, kv rows for the backward) and one STREAMED tile of the other
operand — the whole sequence where VMEM allows (a windowed backward: the
band) — which an in-kernel loop walks in sub-blocks. Under a causal mask the
loop skips the sub-blocks above the diagonal and masks only those the
diagonal crosses.

Layout: (batch, heads, seq, head_dim). Any head_dim compiles; VMEM tiles pad
it to the 128-lane width, so 64 (GPT-2) fills half of each vector register
and half of the MXU's contraction depth. ``v`` (and with it the output, dO
and dV) may have a width of its own: latent attention multiplies 192-wide q
and k and sums 128-wide values, and pays for neither a padded v nor a
second lowering — equal widths trace the programs they always did.

Two more shapes of the same two kernels, each a branch taken in Python at
trace time, so a call without them traces the program it always did:

  * **grouped queries**: ``k`` / ``v`` may have fewer heads than ``q``, a
    divisor of its count; query head ``h`` reads K/V head ``h // (H //
    Hkv)`` through the BlockSpec index map (nothing is repeated in HBM), and
    the backward kernel walks the group's query heads on a grid axis OUTSIDE
    the K/V tiles — a head's dQ stays resident for its whole pass — with
    the K/V head's dK and dV summed over them in whole-length accumulators
    and written after the group's last head;
  * **a window** ``W`` (causal only): row ``i`` sees keys ``j`` with ``0 <=
    i - j < W``. The in-kernel loop gains a lower bound (sub-blocks behind
    the window are skipped, those its edge crosses masked), and the
    streamed grid axis is as long as the BAND's widest run of blocks, each
    resident tile starting at its own first needed block: grid steps
    outside the band do not exist, and a step past a tile's last needed
    block repeats that block's index and fetches nothing. Windowed calls
    carry their own kernel names (``harmony_flash_win_*``).

A third shape, the same way (``diffusion_block``; causal, no window):

  * **a mask by block and stream**, a block-diffusion step's (SDAR,
    arXiv:2510.06303): ``q`` holds the CLEAN rows and then the NOISY rows of
    each sequence, stacked along the sequence axis (``2 L`` rows), against
    the clean keys and values (``L`` columns). Stacked row ``r`` is position
    ``p = r mod L`` of stream ``s = r // L`` and sees column ``c`` iff ``c //
    B <= p // B - s``: a causal triangle whose edge is rounded UP to the
    block for clean rows and DOWN for noisy ones (``_seen_until`` /
    ``_seen_from``, the one pair every bound comes from). The sub-block
    loops keep their form with block-rounded bounds, only the sub-blocks the
    edge crosses are masked (``_apply_block_mask``, shared by the two
    kernels), tiles divide ONE stream's length so none straddles the two,
    and the index maps clamp by the same bounds. Rows that see no column
    (block 0's noisy rows) leave with output 0 and an LSE of ``-1e30``,
    which :func:`merge_by_lse` weighs at nothing, and get and give no
    gradient. The noisy rows' own-block term against the NOISY keys is
    :func:`own_block_attention` (``L / B`` tiles of ``B x B``, plain
    ``jnp``), merged in by the two LSEs: noisy keys never stream through a
    kernel. Kernel names ``harmony_flash_bd_*``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from harmony_tpu.ops.residuals import FLASH_LSE, FLASH_OUT, keep

DEFAULT_BLOCK_K = 256  # blockwise_attention's scan block
_NEG_INF = -1e30  # finite "-inf": keeps masked softmax NaN-free
_LANES = 128  # TPU lane width: per-row stats (LSE, delta) are stored
              # lane-replicated so their blocks are (8,128)-tileable


def _dot_f32(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot_f32_trans_a(a, b):
    """a^T @ b with fp32 accumulation (contracts the rows of both)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _apply_causal_mask(s, row0, col0):
    """Mask one score tile whose first row / column are global positions
    ``row0`` / ``col0``. Shared by the forward and the backward kernel —
    they MUST mask identically or gradients silently diverge from the
    forward."""
    # row0 + i >= col0 + j, with the tile-invariant i - j on one side so a
    # loop over sub-blocks pays one compare and one select per element
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    return jnp.where(ahead >= col0 - row0, s, _NEG_INF)


def _apply_band_mask(s, row0, col0, window):
    """:func:`_apply_causal_mask` with the window's edge beside the
    diagonal: keep ``0 <= row - col < window``."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    lo = col0 - row0
    return jnp.where((ahead >= lo) & (ahead < lo + window), s, _NEG_INF)


def _block_floor(x, block):
    """``x`` rounded down to a multiple of ``block`` (``x >= 0``; traced
    int32 vectors and scalars, or numpy for the static count)."""
    if block & (block - 1) == 0:
        return x - (x & (block - 1))
    return x - x % block


def _seen_until(p, block, stream):
    """One past the last column position ``p`` of ``stream`` sees under the
    mask by block and stream: its own block's end for a clean row (stream
    0), its own block's start for a noisy one (stream 1)."""
    return _block_floor(p, block) + (1 - stream) * block


def _seen_from(c, block, stream):
    """The first position of ``stream`` that sees column ``c``: the start of
    ``c``'s block for a clean row, of the block after it for a noisy one
    (``_seen_until``'s inverse: ``_seen_until(p) > c  <=>  p >=
    _seen_from(c)``)."""
    return _block_floor(c, block) + stream * block


def _apply_block_mask(s, row0, col0, block, stream):
    """:func:`_apply_causal_mask` with the diagonal's edge rounded to the
    diffusion block: the tile's first row is POSITION ``row0`` of
    ``stream`` (0 clean, 1 noisy) and sees column ``c`` iff ``c // block <=
    row // block - stream``. Shared by the forward and the backward
    kernel, as the causal mask is."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols < _seen_until(rows, block, stream), s, _NEG_INF)


def _check_diffusion(block, causal, window, sq, sk) -> None:
    if block is None:
        return
    if not causal or window is not None or block < 1 or sq != 2 * sk \
            or sk % block:
        raise ValueError(
            f"attention: diffusion_block {block} masks by block and stream: "
            "q holds the clean rows and then the noisy rows of each sequence "
            f"(2 x {sk} rows, got {sq}) against the clean keys, whose count "
            "the block must divide; causal=True and no window")


def _head_group(q, k, v) -> int:
    """Query heads a K/V head serves (1: as many K/V heads as query
    heads)."""
    h, hkv = q.shape[1], k.shape[1]
    if v.shape[1] != hkv or hkv < 1 or h % hkv:
        raise ValueError(f"attention: {h} query heads over {hkv} key and "
                         f"{v.shape[1]} value heads; the K/V head count "
                         "must divide the query head count")
    return h // hkv


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"attention: window {window} needs causal=True and "
                         "at least the key itself (window >= 1)")


def _resolve_scale(q, scale):
    """One source of truth for the scale default used by the primal
    forward, the VJP forward and the VJP backward."""
    return scale if scale is not None else q.shape[-1] ** -0.5


# ---------------------------------------------------------------------------
# Pure-JAX blockwise (differentiable reference path)
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_k: int = DEFAULT_BLOCK_K,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    diffusion_block: Optional[int] = None,
) -> jnp.ndarray:
    """Streaming-softmax attention: scan over KV blocks carrying (acc, m, l).

    q [B,H,Sq,D], k [B,H,Sk,D], v [B,H,Sk,Dv] -> [B,H,Sq,Dv] (``Dv`` may
    differ from ``D``: latent attention's 192-wide q.k beside a 128-wide v).
    O(Sq * block_k) live memory instead of O(Sq*Sk); autodiff through the
    scan gives the memory-efficient backward. ``k`` / ``v`` with fewer
    heads (a divisor of ``H``) are repeated, query head ``h`` reading K/V
    head ``h // group``; ``window`` (causal only) keeps ``0 <= i - j <
    window``; ``diffusion_block``: the mask by block and stream (module
    docstring; :func:`blockwise_attention_lse` also returns the LSE the
    own-block term merges by).
    """
    return _blockwise(q, k, v, causal, block_k, scale, window,
                      diffusion_block)[0]


def blockwise_attention_lse(q, k, v, causal=False, block_k=DEFAULT_BLOCK_K,
                            scale=None, window=None, diffusion_block=None):
    """:func:`blockwise_attention` with the per-row log-sum-exp ``[B, H,
    Sq]`` (float32) beside the output — :func:`flash_attention_lse`'s pair
    on any backend. A row that sees no key (block 0's noisy rows under
    ``diffusion_block``) has output 0 and an LSE of ``-1e30``, which merges
    to nothing."""
    return _blockwise(q, k, v, causal, block_k, scale, window,
                      diffusion_block, with_lse=True)


def _blockwise(q, k, v, causal, block_k, scale, window, diffusion_block,
               with_lse=False):
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    group = _head_group(q, k, v)
    _check_window(window, causal)
    _check_diffusion(diffusion_block, causal, window, Sq, Sk)
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, Sk)
    nk, rem = divmod(Sk, block_k)
    if rem:  # pad KV to a whole number of blocks; padded keys are masked out
        pad = block_k - rem
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nk += 1
    kb = k.reshape(B, H, nk, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nk, block_k, Dv).transpose(2, 0, 1, 3, 4)

    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(Sq)[:, None]
    if diffusion_block is not None:  # rows: Sk clean positions, then noisy
        stream, q_pos = q_pos // Sk, q_pos % Sk

    def step(carry, blk):
        acc, m, l = carry
        kblk, vblk, start = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        kv_pos = start + jnp.arange(block_k)[None, :]
        mask = kv_pos < Sk  # padding
        if diffusion_block is not None:
            mask = mask & (kv_pos < _seen_until(q_pos, diffusion_block,
                                                stream))
        elif causal:
            mask = mask & (q_pos >= kv_pos)
        if window is not None:
            mask = mask & (q_pos - kv_pos < window)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    # Derive the init carry from qf so its varying-axes type matches under
    # shard_map (plain zeros are "unvarying" and fail the scan's vma check
    # when attention runs inside a manual-axes region, e.g. a pipeline stage).
    acc0 = jnp.zeros_like(qf) if Dv == D else jnp.broadcast_to(
        jnp.zeros_like(qf[..., :1]), (B, H, Sq, Dv))
    m0 = jnp.full_like(qf[..., 0], _NEG_INF)
    l0 = jnp.zeros_like(qf[..., 0])
    starts = jnp.arange(nk) * block_k
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if diffusion_block is not None:  # a row no key reached: nothing
        dead = m <= _NEG_INF
        out = jnp.where(dead[..., None], 0.0, out)
    if not with_lse:
        return out.astype(q.dtype), None
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    if diffusion_block is not None:
        lse = jnp.where(dead, _NEG_INF, lse)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# The tile plan: how much work one grid step of each kernel does
# ---------------------------------------------------------------------------

class Tiles(NamedTuple):
    """One kernel's tiling. ``block_q`` x ``block_k`` is what a grid step
    holds in VMEM; ``sub`` is the length of the STREAMED tile (kv rows for
    the forward, q rows for the backward) one in-kernel loop iteration
    takes, so the score-sized temporaries are resident-block x ``sub``.
    ``vmem_limit_bytes`` is set only where the tiles need more than
    Mosaic's default scoped VMEM."""
    block_q: int
    block_k: int
    sub: int
    vmem_limit_bytes: Optional[int] = None


class TilePlan(NamedTuple):
    fwd: Tiles
    bwd: Tiles
    planned: bool  # False: the caller's explicit blocks, taken as given


#: the kernels' names in a device trace (perf/trace_reduce.py reads them) and
#: in STATUS ``kernel_plans``
_KERNEL_NAMES = {"fwd": "harmony_flash_fwd", "bwd": "harmony_flash_bwd"}
#: the same kernels under a window: told from the full-causal calls by name
_WIN_KERNEL_NAMES = {"fwd": "harmony_flash_win_fwd",
                     "bwd": "harmony_flash_win_bwd"}
#: ... and under the mask by block and stream (``diffusion_block``)
_BD_KERNEL_NAMES = {"fwd": "harmony_flash_bd_fwd",
                    "bwd": "harmony_flash_bd_bwd"}


def kernel_name(kernel: str, window: Optional[int],
                diffusion_block: Optional[int] = None) -> str:
    """The trace name of ``"fwd"`` / ``"bwd"``."""
    if diffusion_block is not None:
        return _BD_KERNEL_NAMES[kernel]
    return (_KERNEL_NAMES if window is None else _WIN_KERNEL_NAMES)[kernel]


_ONE_BLOCK = 256              # a whole length up to this is one block as it is
_RESIDENT = (512, 256, 128)   # resident-block lengths tried, largest first
_SUB = (1024, 512, 256, 128)  # sub-block lengths tried, largest first
_TEMPS_MAX = 6 * 2**20        # score-sized temporaries of one sub-block: past
                              # this a wider sub-block loses on the v5e (PERF.md)
_VMEM_DEFAULT = 16 * 2**20    # Mosaic's scoped-VMEM default on every TPU so far
_VMEM_FREE = 12 * 2**20       # estimates up to here run under that default
_VMEM_CAP = 96 * 2**20        # the most a plan may need: a v5e has 128 MiB, the
                              # limit asked for is this + ``_VMEM_DEFAULT``
_BAND_TILES = 4               # a windowed backward streams q tiles of at most
                              # window / this many rows (``_plan_kernel``)
_PART_ROWS = 2048             # ... and one whose q tile cannot be a head's
                              # whole rows, tiles of at most this many


class _Shape(NamedTuple):
    """What the VMEM budget reads of a call (all a trace can observe)."""
    d: int          # q / k head width
    dv: int         # v head width
    itemsize: int   # of the operands' dtype
    q_rows: int     # rows of ONE query head: the backward's resident dQ
    kv_rows: int    # rows the backward's dK / dV accumulators hold: the K/V
                    # tile's, or the whole K/V length under grouped heads


def _temp_bytes(kernel, resident, sub):
    """The score-sized f32 temporaries one sub-block keeps live: s, p and
    p's cast in the forward; s/p, dp, ds and two casts in the backward (the
    ``ds`` cast feeds both ``ds^T q`` and ``ds k``)."""
    return (3 if kernel == "fwd" else 5) * resident * sub * 4


def _vmem_bytes(kernel, block_q, block_k, sub, shape):
    """VMEM one grid step of ``kernel`` needs, in bytes: the BlockSpec tiles
    double-buffered (q/k/dq/dk ``d`` wide, v/o/do/dv ``dv`` wide, in the
    operands' dtype; the lane-replicated statistics in f32), the f32
    accumulators, and the temporaries of one sub-block. The backward holds
    beside its streamed q tile a query head's WHOLE dQ — its output block
    and an f32 accumulator of ``q_rows`` — and accumulators of ``kv_rows``
    for dK and dV. A tile's last dim pads to the lane width in VMEM: a
    192-wide tile counts as 256 lanes."""
    dl = -(-shape.d // _LANES) * _LANES
    dvl = -(-shape.dv // _LANES) * _LANES
    qk = lambda rows: rows * dl * shape.itemsize
    vo = lambda rows: rows * dvl * shape.itemsize
    stat = lambda rows: rows * _LANES * 4
    if kernel == "fwd":  # q, o | k, v
        tiles = (qk(block_q) + vo(block_q) + qk(block_k) + vo(block_k)
                 + stat(block_q))
        scratch = 2 * stat(block_q) + block_q * dvl * 4
        resident = block_q
    else:  # q, do, lse, delta | k, dk, v, dv | dq
        tiles = (qk(block_q) + vo(block_q) + 2 * stat(block_q)
                 + 2 * qk(block_k) + 2 * vo(block_k) + qk(shape.q_rows))
        scratch = (max(shape.kv_rows, block_k) * (dl + dvl) * 4
                   + shape.q_rows * dl * 4)
        resident = block_k
    return 2 * tiles + scratch + _temp_bytes(kernel, resident, sub)


def _with_limit(kernel, block_q, block_k, sub, shape):
    need = _vmem_bytes(kernel, block_q, block_k, sub, shape)
    limit = None if need <= _VMEM_FREE else need + _VMEM_DEFAULT
    return Tiles(block_q, block_k, sub, limit)


def _plan_kernel(kernel, sq, sk, shape, window=None):
    """Largest tiles that divide the lengths and fit the budget: the
    resident block first, then the widest sub-block whose temporaries stay
    under ``_TEMPS_MAX``, then as much of the streamed length as fits
    ``_VMEM_CAP`` (the whole of it at every shape a model here runs: 86
    MiB for 16,384 rows of 28 heads over 4) — or, for the backward under a
    window, as much as STREAMS THE BAND: at most ``window / _BAND_TILES``
    rows a tile. That kernel's streamed tile is one query head's q, dO and
    two lane-replicated statistics (1.5 KB a row), fetched ONCE a head
    where it is the head's whole rows and otherwise again for every K/V
    tile, so rows outside the band are worth not fetching — and, where the
    whole cannot be one tile (the two stacked streams of a block-diffusion
    call; a length past the cap), neither are rows above the diagonal: at
    most ``_PART_ROWS`` a tile (on the chip at 32 heads over 4 x 2 x 8,192
    x 128 under ``diffusion_block``: 22.1 / 20.2 / 19.3 / 19.8 ms a call
    with 8,192 / 4,096 / 2,048 / 1,024-row tiles: PERF.md, PR 50); the
    forward streams K and V, which every q tile and
    every query head of a group shares — whole, they are fetched once a K/V
    head, window or not (on the chip at 28 heads over 4 x 16,384 x 128,
    window 4,096: dK/dV 26.9 ms a call with 8,192-row tiles, 16.4 / 13.9 /
    12.9 / 13.1 with 4,096 / 2,048 / 1,024 / 512; the forward 8.4 ms whole
    against 9.1-9.3 in tiles of 1,024-4,096: PERF.md, PR 36)."""
    bwd = kernel == "bwd"
    res_len, str_len = (sk, sq) if bwd else (sq, sk)
    most = str_len if not bwd or window is None else \
        max(window // _BAND_TILES, 1)

    def divisors(length, sizes):
        if length <= _ONE_BLOCK:
            return (length,)
        return tuple(s for s in sizes if length % s == 0)

    for res in divisors(res_len, _RESIDENT):
        for sub in divisors(str_len, _SUB):
            if sub > _LANES and _temp_bytes(kernel, res, sub) > _TEMPS_MAX:
                continue
            for n in range(max(min(str_len, most) // sub, 1), 0, -1):
                if (str_len // sub) % n:
                    continue
                bq, bk = (n * sub, res) if bwd else (res, n * sub)
                if bwd and window is None and _PART_ROWS < bq < shape.q_rows:
                    continue
                if _vmem_bytes(kernel, bq, bk, sub, shape) <= _VMEM_CAP:
                    return _with_limit(kernel, bq, bk, sub, shape)
    return None


def tile_plan(sq, sk, d, dtype, causal=False, block_q=None, block_k=None,
              dv=None, window=None, group=1, streams=1):
    """The tiles of the two kernels for q [.., sq, d] against k
    [.., sk, d] and v [.., sk, dv] (``dv`` None: ``d``), or None where the
    kernels cannot tile the lengths: a length over ``_ONE_BLOCK`` must
    divide by 128, and the backward must fit a query head's whole dQ in
    VMEM beside its tiles. The ONE gate: the kernels raise where this
    returns None, and ``models.common.flash_ok`` asks here.

    Inputs are what a trace can observe — lengths, both head widths, operand
    dtype, the query heads a K/V head serves (``group``), the ``streams`` of
    ``sq`` rows a query head stacks (2 under ``diffusion_block``) — and the
    budget is VMEM (``_vmem_bytes``); a plan over Mosaic's default carries
    ``vmem_limit_bytes`` instead of shrinking. Explicit ``block_q`` /
    ``block_k`` win over the plan and are taken as given (one sub-block a
    grid step: the tiling of the interpreter tests and the ring's callers).
    ``causal`` does not change the tiles: the in-kernel loop bounds carry
    the causal skip at sub-block grain; a ``window`` shortens the backward's
    streamed tile to the band (``_plan_kernel``) and nothing else."""
    del causal
    shape = _Shape(d, d if dv is None else dv, jnp.dtype(dtype).itemsize,
                   streams * sq, sk if group > 1 else 0)
    if block_q is not None or block_k is not None:
        bq = min(block_q or block_k, sq)  # one given alone stands for both
        bk = min(block_k or block_q, sk)
        if sq % bq or sk % bk:
            return None
        return TilePlan(_with_limit("fwd", bq, bk, bk, shape),
                        _with_limit("bwd", bq, bk, bq, shape), False)
    tiles = [_plan_kernel(kern, sq, sk, shape, window)
             for kern in ("fwd", "bwd")]
    return None if None in tiles else TilePlan(*tiles, True)


def _require_plan(q, k, v, causal, block_q, block_k, window=None,
                  diffusion_block=None):
    sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
    if k.shape[3] != d:
        raise ValueError(f"flash attention: q is {d} wide and k "
                         f"{k.shape[3]}; only v may have a width of its own")
    if diffusion_block is not None:
        # the stacked rows are two streams of sk positions: tiles that
        # divide ONE stream's length never straddle the two
        sq = sk
    plan = tile_plan(sq, sk, d, q.dtype, causal, block_q, block_k,
                     dv=v.shape[3], window=window, group=_head_group(q, k, v),
                     streams=q.shape[2] // sq)
    if plan is None:
        raise ValueError(
            f"flash attention cannot tile seq lens ({sq},{sk})"
            + (f" by blocks ({block_q},{block_k})"
               if block_q is not None or block_k is not None else
               f": a length over {_ONE_BLOCK} must divide by {_LANES} (and "
               "a query head's whole dQ fit in VMEM)"))
    return plan


def _note_plan(plan, kernels, q, k, v, causal, window, diffusion_block=None):
    """Trace-time record of the tiling a compiled program runs — the plan is
    static per shape, so it engages always or never; STATUS ``kernel_plans``
    (beside ``compiles``) says which one a job got. A call with grouped
    heads or a window adds what the BAND needs of the plan
    (:func:`band_work`, summed over the call's heads) and sets
    ``harmony_flash_masked_share``; so does a call under
    ``diffusion_block``, counted from the block-rounded bounds. Never fails
    a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        bh, sq, sk = q.shape[0] * q.shape[1], q.shape[2], k.shape[2]
        for kern in kernels:
            t = getattr(plan, kern)
            band = None
            steps = (sq // t.block_q) * (sk // t.block_k)
            if (window is not None or k.shape[1] != q.shape[1]
                    or diffusion_block is not None):
                work = band_work(kern, t, sq, sk, causal, window,
                                 diffusion_block)
                steps = work["grid_steps"]
                band = {"window": window or 0, "kv_heads": k.shape[1],
                        "group": q.shape[1] // k.shape[1],
                        "band_grid_steps": bh * work["with_work"],
                        "sub_blocks": bh * work["sub_blocks"],
                        "masked_sub_blocks": bh * work["masked_sub_blocks"],
                        "computed": bh * work["computed"],
                        "masked_share": 1.0 - work["kept"] / work["computed"]}
            note_kernel_plan(
                kernel_name(kern, window, diffusion_block), t.block_q,
                t.block_k, t.sub,
                bh * steps, plan.planned, d=q.shape[3], dv=v.shape[3],
                band=band)
    except Exception:
        pass


def _compiler_params(tiles):
    if tiles.vmem_limit_bytes is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=tiles.vmem_limit_bytes)}


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _kv_sub_ranges(causal, q0, block_q, k0, sub, n_sub):
    """Split the ``n_sub`` sub-blocks of a streamed KV tile (first column
    ``k0``) against a resident q tile (rows ``q0 .. q0+block_q-1``):
    sub-blocks ``[0, n_full)`` lie at or below the diagonal for every row
    (no mask), ``[n_full, n_need)`` are crossed by it (masked), the rest lie
    above it and contribute nothing (skipped)."""
    if not causal:
        return n_sub, n_sub
    n_full = jnp.clip((q0 - k0 + 1) // sub, 0, n_sub)
    n_need = jnp.clip((q0 + block_q - k0 + sub - 1) // sub, 0, n_sub)
    return n_full, n_need


def _q_sub_ranges(causal, q0, k0, block_k, sub, n_sub):
    """The same split for a streamed q tile (first row ``q0``) against a
    resident KV tile (columns ``k0 .. k0+block_k-1``): sub-blocks
    ``[0, first)`` lie above the diagonal (skipped), ``[first, full_from)``
    are crossed by it (masked), ``[full_from, n_sub)`` lie below it."""
    if not causal:
        return 0, 0
    first = jnp.clip((k0 - q0) // sub, 0, n_sub)
    full_from = jnp.clip((k0 + block_k - q0 + sub - 2) // sub, 0, n_sub)
    return first, full_from


def _kv_block_ranges(p0, block_q, k0, sub, n_sub, block, stream, xp=jnp):
    """:func:`_kv_sub_ranges` under the mask by block and stream, for a
    resident q tile of positions ``p0 .. p0+block_q-1`` of ``stream``: the
    diagonal's edge is rounded UP to the diffusion block for clean rows
    and DOWN for noisy ones (``_seen_until``)."""
    n_full = xp.clip((_seen_until(p0, block, stream) - k0) // sub, 0, n_sub)
    n_need = xp.clip((_seen_until(p0 + block_q - 1, block, stream) - k0
                      + sub - 1) // sub, 0, n_sub)
    return n_full, n_need


def _q_block_ranges(p0, k0, block_k, sub, n_sub, block, stream, xp=jnp):
    """:func:`_q_sub_ranges` under the mask by block and stream, for a
    streamed q tile of positions ``p0 ..`` of ``stream`` (``_seen_from``)."""
    first = xp.clip((_seen_from(k0, block, stream) - p0) // sub, 0, n_sub)
    full_from = xp.clip((_seen_from(k0 + block_k - 1, block, stream) - p0
                         + sub - 1) // sub, 0, n_sub)
    return first, full_from


def _kv_band_ranges(q0, block_q, k0, sub, n_sub, window, xp=jnp):
    """:func:`_kv_sub_ranges` under a window, ``(a, b, c, d)``: sub-blocks
    ``[a, b)`` are crossed by the window's edge (masked), ``[b, c)`` lie
    inside the band for every row (no mask), ``[c, d)`` are crossed by the
    diagonal (masked); ``[0, a)`` lie behind the window and ``[d, n_sub)``
    above the diagonal (skipped). Where the window is narrower than a tile
    the edge and the diagonal share sub-blocks: ``[b, c)`` is then empty and
    every masked sub-block takes both tests (:func:`_apply_band_mask`).
    ``xp``: ``numpy`` for the static count (:func:`band_work`)."""
    clip = lambda x: xp.clip(x, 0, n_sub)
    d = clip((q0 + block_q - k0 + sub - 1) // sub)
    a = xp.minimum(clip((q0 - window + 1 - k0) // sub), d)
    b = xp.clip(-((k0 + window - q0 - block_q) // sub), a, d)
    c = xp.clip((q0 - k0 + 1) // sub, b, d)
    return a, b, c, d


def _q_band_ranges(q0, k0, block_k, sub, n_sub, window, xp=jnp):
    """:func:`_q_sub_ranges` under a window, ``(a, b, c, d)``: sub-blocks
    ``[a, b)`` are crossed by the diagonal (masked), ``[b, c)`` lie inside
    the band (no mask), ``[c, d)`` are crossed by the window's edge
    (masked); the rest are skipped."""
    clip = lambda x: xp.clip(x, 0, n_sub)
    a = clip((k0 - q0) // sub)
    d = xp.maximum(clip(-((q0 - k0 - block_k - window + 1) // sub)), a)
    b = xp.clip((k0 + block_k - q0 + sub - 2) // sub, a, d)
    c = xp.clip((k0 + window - q0) // sub, b, d)
    return a, b, c, d


def _kv_blocks_of(i, block_q, block_k, window, nk, xp=jnp):
    """``(first, last)`` KV block a q tile ``i`` needs under a window."""
    last = xp.minimum((i * block_q + block_q - 1) // block_k, nk - 1)
    first = xp.minimum(xp.maximum(i * block_q - window + 1, 0) // block_k,
                       last)
    return first, last


def _q_blocks_of(j, block_q, block_k, window, nq, xp=jnp):
    """``(first, last)`` q block a KV tile ``j`` needs under a window."""
    first = xp.minimum((j * block_k) // block_q, nq - 1)
    last = xp.minimum((j * block_k + block_k + window - 2) // block_q, nq - 1)
    return first, last


def _band_steps(kernel, tiles, sq, sk, window) -> int:
    """Length of the streamed grid axis under a window: the most blocks any
    resident tile needs (static)."""
    import numpy as np

    nq, nk = sq // tiles.block_q, sk // tiles.block_k
    if kernel == "bwd":
        first, last = _q_blocks_of(np.arange(nk), tiles.block_q,
                                   tiles.block_k, window, nq, np)
    else:
        first, last = _kv_blocks_of(np.arange(nq), tiles.block_q,
                                    tiles.block_k, window, nk, np)
    return int((last - first + 1).max())


def band_work(kernel, tiles, sq, sk, causal, window, diffusion_block=None):
    """What one (batch, q head) of a call of ``kernel`` under ``tiles`` runs
    and what the mask needs of it, counted from the same bounds the kernel
    loops by (static, a few thousand integer operations): ``grid_steps``
    the streamed axis has and those ``with_work``, the ``sub_blocks`` the
    in-kernel loops take and the ``masked`` ones among them, the score
    elements ``computed`` and those the mask ``kept``. A causal call
    without a window counts as one whose window reaches past every key;
    under ``diffusion_block`` (``sq`` the stacked ``2 sk`` rows) the bounds
    are the block-rounded ones and ``kept`` is ``sk ** 2`` exactly."""
    import numpy as np

    bq, bk, sub = tiles[:3]
    nq, nk = sq // bq, sk // bk
    bwd = kernel == "bwd"
    n_res, n_str, n_sub = (nk, nq, bq // sub) if bwd else (nq, nk, bk // sub)
    if diffusion_block is not None:
        B = diffusion_block
        pos = np.arange(sk)
        kept = int(sum(np.clip(_seen_until(pos, B, s), 0, sk).sum()
                       for s in (0, 1)))
        with_work = run = masked = 0
        for i in range(nq):
            stream, p0 = divmod(i * bq, sk)
            for j in range(nk):
                if bwd:
                    lo, full = (int(x) for x in _q_block_ranges(
                        p0, j * bk, bk, sub, n_sub, B, stream, np))
                    ran, cut = n_sub - lo, full - lo
                else:
                    full, need = (int(x) for x in _kv_block_ranges(
                        p0, bq, j * bk, sub, n_sub, B, stream, np))
                    ran, cut = need, need - full
                run += ran
                masked += cut
                with_work += ran > 0
        return {"grid_steps": nq * nk, "with_work": with_work,
                "sub_blocks": run, "masked_sub_blocks": masked,
                "computed": run * (bk if bwd else bq) * sub, "kept": kept}
    if not causal:
        run = n_res * n_str * n_sub
        return {"grid_steps": n_res * n_str, "with_work": n_res * n_str,
                "sub_blocks": run, "masked_sub_blocks": 0,
                "computed": sq * sk, "kept": sq * sk}
    reach = sq + sk if window is None else window
    rows = np.arange(sq)
    kept = int((np.minimum(rows + 1, sk)
                - np.clip(rows + 1 - reach, 0, sk)).sum())
    with_work = run = masked = widest = 0
    for r in range(n_res):
        first, last = (_q_blocks_of(r, bq, bk, reach, nq, np) if bwd else
                       _kv_blocks_of(r, bq, bk, reach, nk, np))
        widest = max(widest, int(last) - int(first) + 1)  # _band_steps'
        for s in range(int(first), int(last) + 1):
            a, b, c, d = (int(x) for x in (
                _q_band_ranges(s * bq, r * bk, bk, sub, n_sub, reach, np)
                if bwd else
                _kv_band_ranges(r * bq, bq, s * bk, sub, n_sub, reach, np)))
            run += d - a
            masked += (b - a) + (d - c)
            with_work += d > a
    steps = n_str if window is None else widest
    return {"grid_steps": steps * n_res, "with_work": with_work,
            "sub_blocks": run, "masked_sub_blocks": masked,
            "computed": run * (bk if bwd else bq) * sub, "kept": kept}


def _rows_at(start, n):
    """``n`` rows from ``start``, a multiple of ``n`` (static or traced)."""
    if isinstance(start, int):
        return pl.ds(start, n)
    return pl.ds(pl.multiple_of(start, n), n)


def _sub_slice(i, sub):
    return _rows_at(i * sub, sub)


def _for_sub_blocks(lo, hi, n_sub, body):
    """``body(i)`` for each sub-block ``i`` in ``[lo, hi)`` of ``n_sub``. A
    tile that is one sub-block takes it under a guard at a static offset
    (any length compiles, a multiple of 8 or not); more are a loop whose
    bounds may be traced values."""
    if n_sub > 1:
        jax.lax.fori_loop(lo, hi, lambda i, carry: (body(i), carry)[1], None)
    elif isinstance(lo, int) and isinstance(hi, int):
        if lo < hi:
            body(0)
    else:
        pl.when(jnp.logical_and(lo <= 0, hi > 0))(lambda: body(0))


def _for_band(ranges, has_work, n_sub, step, window):
    """The three runs of a windowed tile's sub-blocks (``_kv_band_ranges``
    / ``_q_band_ranges``): masked, plain, masked — none where the grid step
    lies past the tile's last needed block."""
    a, b, c, d = (jnp.where(has_work, x, 0) for x in ranges)
    band = functools.partial(_apply_band_mask, window=window)
    _for_sub_blocks(a, b, n_sub, step(band))
    _for_sub_blocks(b, c, n_sub, step(None))
    _for_sub_blocks(c, d, n_sub, step(band))


def _stream_of(q0, bd):
    """``(first position, stream, mask)`` of the q tile whose first STACKED
    row is ``q0`` under ``bd = (diffusion block, positions a stream)``: a
    tile lies in one stream (``_require_plan``)."""
    block, length = bd
    stream = q0 // length
    return q0 - stream * length, stream, functools.partial(
        _apply_block_mask, block=block, stream=stream)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
               scale, causal, block_q, block_k, sub, window=None, nk=None,
               bd=None):
    iq = pl.program_id(1)
    q0 = iq * block_q
    ik = pl.program_id(2)
    if bd is not None:  # rows are positions of a stream from here on
        q0, stream, block_mask = _stream_of(q0, bd)
    if window is None:
        k0 = ik * block_k
    else:  # the band's own grid axis: this q tile's ik-th needed KV block
        first, last = _kv_blocks_of(iq, block_q, block_k, window, nk)
        k0 = (first + ik) * block_k

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(mask):
        def body(j):
            cols = _sub_slice(j, sub)
            # NATIVE-dtype operand feeds: a bf16 q/k/v runs the MXU at bf16
            # throughput with fp32 accumulation (preferred_element_type).
            # The scale applies to the fp32 product, exactly.
            s = _dot_f32(q_ref[0], k_ref[0, cols, :], trans_b=True) * scale
            if mask is not None:
                s = mask(s, q0, k0 + j * sub)
            # (a row whose every column of its FIRST sub-block is masked — a
            # window's edge — sums p = 1 there; its first real score then
            # makes alpha exp(-1e30 - m) = 0 and wipes that, and every row
            # has one: its own key)
            m_prev = m_ref[:, :1]                        # (bq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
            l_ref[:] = l_ref[:] * alpha + p.sum(axis=1, keepdims=True)
            # p feeds the MXU in v's dtype (bf16 weights => bf16 p, the
            # standard flash trade; fp32 v keeps p fp32 so tests/CPU are
            # exact)
            acc_ref[:] = acc_ref[:] * alpha + _dot_f32(
                p.astype(v_ref.dtype), v_ref[0, cols, :])
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        return body

    n_sub = block_k // sub
    if bd is not None:
        n_full, n_need = _kv_block_ranges(q0, block_q, k0, sub, n_sub, bd[0],
                                          stream)
        _for_sub_blocks(0, n_full, n_sub, step(None))
        _for_sub_blocks(n_full, n_need, n_sub, step(block_mask))
    elif window is None:
        n_full, n_need = _kv_sub_ranges(causal, q0, block_q, k0, sub, n_sub)
        _for_sub_blocks(0, n_full, n_sub, step(None))
        if causal:
            _for_sub_blocks(n_full, n_need, n_sub, step(_apply_causal_mask))
    else:
        _for_band(_kv_band_ranges(q0, block_q, k0, sub, n_sub, window),
                  first + ik <= last, n_sub, step, window)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _write():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out = acc_ref[:] / l
        if bd is not None:
            # a row no column reached (block 0's noisy rows) kept m at the
            # mask's value and summed p = 1 over masked columns: it leaves
            # with output 0 and an LSE that merges to nothing
            dead = m_ref[:, :1] <= _NEG_INF
            out = jnp.where(dead, 0.0, out)
        o_ref[0] = out.astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l)
        if bd is not None:
            lse = jnp.where(dead, _NEG_INF, lse)
        # log-sum-exp per row, consumed by the backward kernel. Stored
        # broadcast across a 128-lane trailing dim: Mosaic requires the last
        # two block dims be (8,128)-tileable, and a (1, block_q) row block is
        # not — the lane-replicated layout is the canonical TPU shape for
        # per-row softmax stats (cf. jax.experimental.pallas.ops.tpu
        # flash_attention's l/m outputs).
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _out_struct(shape, dtype, *refs):
    """ShapeDtypeStruct whose varying-manual-axes (vma) is the union of the
    reference arrays' — required when a pallas_call runs INSIDE shard_map
    (the ring-attention inner): outputs vary over every axis an input
    does."""
    vma = frozenset()
    for r in refs:
        vma = vma | jax.typeof(r).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _last_needed_kv(causal, block_q, block_k, window=None, nk=None,
                    bd=None):
    """index_map clamp for a streamed KV tile: a grid step above the
    diagonal repeats the block index of the last needed one, so Pallas
    fetches nothing for it (its arithmetic is skipped in the kernel). Under
    a window grid step ``j`` is the q tile's ``j``-th needed block."""
    if not causal:
        return lambda i, j: j
    if bd is not None:
        def blocked(i, j):
            p0, stream, _ = _stream_of(i * block_q, bd)
            until = _seen_until(p0 + block_q - 1, bd[0], stream)
            return jnp.minimum(j, jnp.maximum(until - 1, 0) // block_k)
        return blocked
    if window is not None:
        def banded(i, j):
            first, last = _kv_blocks_of(i, block_q, block_k, window, nk)
            return jnp.minimum(first + j, last)
        return banded
    return lambda i, j: jnp.minimum(j, (i * block_q + block_q - 1) // block_k)


def _first_needed_q(causal, block_q, block_k, nq, window=None, bd=None):
    """The same clamp for the backward kernel's streamed q tile: steps before
    the first q tile that reaches this KV tile's columns fetch that one
    (the last one where none does: more columns than rows)."""
    if not causal:
        return lambda j, i: i
    if bd is not None:
        half = nq // 2  # q tiles a stream

        def blocked(j, i):
            stream = i // half
            first = _seen_from(j * block_k, bd[0], stream) // block_q
            return jnp.maximum(i, stream * half + jnp.minimum(first, half - 1))
        return blocked
    if window is not None:
        def banded(j, i):
            first, last = _q_blocks_of(j, block_q, block_k, window, nq)
            return jnp.minimum(first + i, last)
        return banded
    return lambda j, i: jnp.maximum(
        i, jnp.minimum((j * block_k) // block_q, nq - 1))


def _kv_head(group):
    """index_map of the K/V operand's leading (batch x head) axis: query
    head ``b`` of ``B * H`` reads K/V head ``b // group`` of ``B * Hkv``."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


# The kernel-calling functions below are jitted with everything but the
# arrays static: a model calls attention once per layer at one shape, and a
# jitted callee is traced and lowered ONCE per (shape, tiles) in a process —
# the layers of a step, and every later job's re-trace of it, reuse that
# (job.build_step: twelve layers' kernels cost one trace, not twelve).

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _flash_forward(q, k, v, causal, tiles, scale, interpret, window=None,
                   diffusion_block=None):
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    block_q, block_k, sub = tiles[:3]
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, Dv)
    nk = Sk // block_k
    grid = (B * H, Sq // block_q,
            nk if window is None else _band_steps("fwd", tiles, Sq, Sk,
                                                  window))
    band = {} if window is None else {"window": window, "nk": nk}
    if diffusion_block is not None:
        band = {"bd": (diffusion_block, Sk)}
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, sub=sub, **band,
    )
    kv_j = _last_needed_kv(causal, block_q, block_k, **band)
    kv_b = _kv_head(H // Hkv)
    out, lse = pl.pallas_call(
        kernel,
        name=kernel_name("fwd", window, diffusion_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j: (kv_b(b), kv_j(i, j), 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, i, j: (kv_b(b), kv_j(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Sq, Dv), q.dtype, q, k, v),
            _out_struct((B * H, Sq, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            _vmem((block_q, _LANES)),   # running row-max m
            _vmem((block_q, _LANES)),   # running normaliser l
            _vmem((block_q, Dv)),       # unnormalised output accumulator
        ],
        interpret=interpret,
        **_compiler_params(tiles),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, Dv), lse[:, :, 0].reshape(B, H, Sq)


def _bwd_p_ds(q, k, v, do, lse, delta, row0, col0, scale, mask):
    """Shared backward math for one score tile: returns (p, ds) with p the
    normalized softmax block. ``lse``/``delta`` arrive as (rows, 1) column
    tiles (lane 0 of the lane-replicated stats)."""
    # native-dtype MXU feeds with fp32 accumulation (see _fa_kernel)
    s = _dot_f32(q, k, trans_b=True) * scale
    if mask is not None:
        s = mask(s, row0, col0)
    p = jnp.exp(s - lse)                                      # normalized
    dp = _dot_f32(do, v, trans_b=True)
    ds = p * (dp - delta)
    return p, ds


def _fa_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                   scale, causal, block_q, block_k, sub, group, nq, nk,
                   window=None, bd=None):
    """dQ, dK and dV from ONE evaluation of each score tile. Grid (K/V head,
    query head of its group, kv tile, q tiles streaming by): ``dq_acc``
    holds the query head's whole dQ over its kv tiles; ``dk_acc`` /
    ``dv_acc`` hold this kv tile's rows (one K/V head a query head) or the
    K/V head's whole length, summed over the group's heads."""
    g = pl.program_id(1)              # query head of the K/V head's group
    jk = pl.program_id(2)             # kv tile (this dK / dV tile)
    k0 = 0 if nk == 1 else jk * block_k
    iq = pl.program_id(3)             # q tiles stream by
    step_i = iq
    if window is not None:  # the tile's own first needed q block onward
        first, last = _q_blocks_of(jk, block_q, block_k, window, nq)
        step_i = first + iq
    row0 = q0 = 0 if nq == 1 else step_i * block_q  # the tile's first q row
    if bd is not None:  # rows are positions of a stream from here on
        q0, stream, block_mask = _stream_of(q0, bd)
    kv_rows = _rows_at(0 if group == 1 else k0, block_k)
    n_sub = block_q // sub

    @pl.when(jnp.logical_and(jk == 0, iq == 0))
    def _init_dq():
        def zero(i):
            dq_acc[_sub_slice(i, sub), :] = jnp.zeros((sub, dq_acc.shape[1]),
                                                      dq_acc.dtype)
        _for_sub_blocks(0, nq * n_sub, nq * n_sub, zero)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init_dkv():
        dk_acc[kv_rows, :] = jnp.zeros((block_k, dk_acc.shape[1]),
                                       dk_acc.dtype)
        dv_acc[kv_rows, :] = jnp.zeros((block_k, dv_acc.shape[1]),
                                       dv_acc.dtype)

    def step(mask):
        def body(i):
            rows = _sub_slice(i, sub)
            q, do, k = q_ref[0, rows, :], do_ref[0, rows, :], k_ref[0]
            p, ds = _bwd_p_ds(
                q, k, v_ref[0], do,
                lse_ref[0, rows, :1], delta_ref[0, rows, :1],
                q0 + i * sub, k0, scale, mask)
            ds = ds.astype(q.dtype)
            dv_acc[kv_rows, :] += _dot_f32_trans_a(p.astype(do.dtype), do)
            dk_acc[kv_rows, :] += _dot_f32_trans_a(ds, q)           # (bk, d)
            dq_acc[_rows_at(row0 + i * sub, sub), :] += _dot_f32(ds, k)
        return body

    if bd is not None:
        first, full_from = _q_block_ranges(q0, k0, block_k, sub, n_sub, bd[0],
                                           stream)
        _for_sub_blocks(first, full_from, n_sub, step(block_mask))
        _for_sub_blocks(full_from, n_sub, n_sub, step(None))
    elif window is None:
        first, full_from = _q_sub_ranges(causal, q0, k0, block_k, sub, n_sub)
        if causal:
            _for_sub_blocks(first, full_from, n_sub, step(_apply_causal_mask))
        _for_sub_blocks(full_from, n_sub, n_sub, step(None))
    else:
        _for_band(_q_band_ranges(q0, k0, block_k, sub, n_sub, window),
                  step_i <= last, n_sub, step, window)

    last_q = iq == pl.num_programs(3) - 1

    @pl.when(jnp.logical_and(g == group - 1, last_q))
    def _write_dkv():
        dk_ref[0] = (dk_acc[kv_rows, :] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[kv_rows, :].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(jk == nk - 1, last_q))
    def _write_dq():
        def write(i):
            rows = _sub_slice(i, sub)
            dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)
        _for_sub_blocks(0, nq * n_sub, nq * n_sub, write)


def _bwd_row_stats(out, lse, do, lse_cotangent):
    """The backward kernel's per-row inputs, lane-replicated (see _LANES):
    the saved LSE and delta_i = dO_i . O_i, cheap enough to leave to XLA —
    which materializes the broadcasts; the kernels read lane 0.

    ``lse_cotangent`` supports callers that consume the LSE output (the
    ring-attention chunk merge): d lse_r / d s_rc = p_rc, so the extra term
    is ``g_lse_r * p_rc`` — algebraically it folds into the delta:
    ds = p * (dp - (delta - g_lse)). The kernel is unchanged."""
    B, H, Sq, D = out.shape  # the value width: out and do are v wide
    lsef = jnp.broadcast_to(lse.reshape(B * H, Sq)[:, :, None],
                            (B * H, Sq, _LANES))
    delta = jnp.einsum("bsd,bsd->bs",
                       do.reshape(B * H, Sq, D).astype(jnp.float32),
                       out.reshape(B * H, Sq, D).astype(jnp.float32))
    if lse_cotangent is not None:
        delta = delta - lse_cotangent.reshape(B * H, Sq).astype(jnp.float32)
    return lsef, jnp.broadcast_to(delta[:, :, None], (B * H, Sq, _LANES))


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12))
def _flash_backward(q, k, v, out, lse, do, lse_cotangent, causal, tiles,
                    scale, interpret, window=None, diffusion_block=None):
    """The flash backward, one kernel: grid (K/V head, query head of its
    group, kv tile, q tiles streaming by); each score tile's softmax is
    recomputed from the saved LSE once — the O(S) memory trade the forward
    made — and gives dV, dK and dQ. A query head's whole dQ is resident
    (f32) over its kv tiles and written once; under grouped heads dK and dV
    are resident whole, summed over the group, and each tile written after
    the group's last head (until then the output's block index stays put,
    so nothing is written back)."""
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, Dv)
    dof = do.reshape(B * H, Sq, Dv)
    if diffusion_block is not None:
        # a row that saw no key left with an LSE of -1e30: the backward's
        # p = exp(s - lse) would read 1 on its masked scores; +1e30 makes
        # them 0, so the row gives no gradient (and got none: its output
        # is a constant)
        lse = jnp.where(lse <= _NEG_INF / 2, -_NEG_INF, lse)
    lsef, delta = _bwd_row_stats(out, lse, do, lse_cotangent)
    block_q, block_k, sub = tiles[:3]
    nq, nk = Sq // block_q, Sk // block_k
    steps = nq if window is None else _band_steps("bwd", tiles, Sq, Sk,
                                                  window)
    bd = None if diffusion_block is None else (diffusion_block, Sk)
    q_i = _first_needed_q(causal, block_q, block_k, nq, window, bd)
    q_row = lambda b, g, j, i: (b * group + g, q_i(j, i), 0)
    kv_row = lambda b, g, j, i: (b, j, 0)
    # a K/V tile's gradient is whole after the group's LAST head
    dkv_row = kv_row if group == 1 else (
        lambda b, g, j, i: (b, jnp.where(g == group - 1, j, 0), 0))
    q_spec, do_spec = (pl.BlockSpec((1, block_q, w), q_row) for w in (D, Dv))
    k_spec, v_spec = (pl.BlockSpec((1, block_k, w), kv_row) for w in (D, Dv))
    row_spec = pl.BlockSpec((1, block_q, _LANES), q_row)
    kv_acc = block_k if group == 1 else Sk
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, sub=sub,
                          group=group, nq=nq, nk=nk, window=window, bd=bd),
        name=kernel_name("bwd", window, diffusion_block),
        grid=(B * Hkv, group, nk, steps),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, g, j, i: (b * group + g, 0, 0)),
            *(pl.BlockSpec((1, block_k, w), dkv_row) for w in (D, Dv)),
        ],
        out_shape=[
            _out_struct((B * H, Sq, D), q.dtype, qf, kf, vf, dof),
            _out_struct((B * Hkv, Sk, D), k.dtype, qf, kf, vf, dof),
            _out_struct((B * Hkv, Sk, Dv), v.dtype, qf, kf, vf, dof),
        ],
        scratch_shapes=[_vmem((Sq, D)), _vmem((kv_acc, D)),
                        _vmem((kv_acc, Dv))],
        interpret=interpret,
        **_compiler_params(tiles),
    )(qf, kf, vf, dof, lsef, delta)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, Hkv, Sk, D),
            dv.reshape(B, Hkv, Sk, Dv))


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    diffusion_block: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention. Forward AND backward are Pallas TPU kernels
    (``interpret=True`` runs them in the Pallas interpreter, for CPU
    tests; off-TPU callers that want speed take
    :func:`blockwise_attention` by name): the forward saves only O(S)
    softmax statistics (LSE) and the backward recomputes each softmax tile
    from them — flash attention's memory/FLOPs trade in both directions.
    The kernels tile themselves from the shape (:func:`tile_plan`);
    ``block_q`` / ``block_k`` override it. ``k`` / ``v`` may have fewer
    heads than ``q`` (grouped queries), ``window`` bounds how far back a
    row sees and ``diffusion_block`` masks by block and stream (module
    docstring).

    Thin wrapper over :func:`flash_attention_lse` (the kernel always writes
    the LSE output; discarding it costs nothing, and a zero LSE cotangent
    folds to the identical backward) — ONE custom_vjp to maintain."""
    out, _ = flash_attention_lse(q, k, v, causal, block_q, block_k, scale,
                                 interpret, window, diffusion_block)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    diffusion_block: Optional[int] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """:func:`flash_attention` that ALSO returns the per-row log-sum-exp
    ([B, H, Sq], fp32) — the composable form: outputs of independent KV
    chunks merge exactly via their LSEs (``ring_attention``'s flash inner;
    the noisy rows' own-block term under ``diffusion_block``).
    Differentiable in both outputs; the LSE cotangent folds into the
    backward kernel's delta term (see ``_bwd_row_stats``)."""
    return _fa_lse_call(q, k, v, causal, block_q, block_k, scale,
                        interpret, window, diffusion_block)


def _fa_lse_call(q, k, v, causal, block_q, block_k, scale, interpret,
                 window=None, diffusion_block=None):
    if not (q.dtype == k.dtype == v.dtype):
        raise TypeError(
            f"flash attention feeds the MXU in the operands' dtype, so "
            f"q/k/v must share one dtype (got {q.dtype}/{k.dtype}/"
            f"{v.dtype}); cast the operands before the call"
        )
    _head_group(q, k, v)
    _check_window(window, causal)
    _check_diffusion(diffusion_block, causal, window, q.shape[2], k.shape[2])
    plan = _require_plan(q, k, v, causal, block_q, block_k, window,
                         diffusion_block)
    _note_plan(plan, ("fwd",), q, k, v, causal, window, diffusion_block)
    return _flash_forward(q, k, v, causal, plan.fwd, _resolve_scale(q, scale),
                          interpret, window, diffusion_block)


def _fa_lse_fwd(q, k, v, causal, block_q, block_k, scale, interpret,
                window=None, diffusion_block=None):
    out, lse = _fa_lse_call(q, k, v, causal, block_q, block_k, scale,
                            interpret, window, diffusion_block)
    # named HERE, before they are both outputs and residuals: what a
    # rematerialised block keeps (ops/residuals.py)
    out, lse = keep(out, FLASH_OUT), keep(lse, FLASH_LSE)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(causal, block_q, block_k, scale, interpret, window,
                diffusion_block, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    plan = _require_plan(q, k, v, causal, block_q, block_k, window,
                         diffusion_block)
    _note_plan(plan, ("bwd",), q, k, v, causal, window, diffusion_block)
    return _flash_backward(q, k, v, out, lse, g_out, g_lse, causal, plan.bwd,
                           _resolve_scale(q, scale), interpret, window,
                           diffusion_block)


flash_attention_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


def own_block_attention(q, k, v, block: int, scale: Optional[float] = None):
    """The noisy stream's own-block term of a block-diffusion step: row
    ``p`` against the keys of its OWN block of ``block`` positions, both
    directions — ``S / block`` tiles of ``block x block``, ``S x block``
    pairs beside the kernels' ``S ** 2``, so plain ``jnp`` over ``[.., S /
    block, block, D]`` (grouped K/V heads repeated: a tile is tiny).
    ``(out [B, H, S, Dv], lse [B, H, S] float32)``, the pair
    :func:`merge_by_lse` takes."""
    B, H, S, D = q.shape
    group = _head_group(q, k, v)
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    tiles = lambda t: t.reshape(B, H, S // block, block, t.shape[-1])
    s = jnp.einsum("bhnqd,bhnkd->bhnqk", tiles(q), tiles(k),
                   preferred_element_type=jnp.float32) * _resolve_scale(
                       q, scale)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhnqk,bhnkd->bhnqd", (p / l).astype(v.dtype), tiles(v),
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, H, S, v.shape[-1]).astype(q.dtype),
            (m + jnp.log(l)).reshape(B, H, S))


def merge_by_lse(o_a, lse_a, o_b, lse_b):
    """Two attention outputs of the same rows over DISJOINT key sets, each
    normalised over its own keys, as one softmax over both: weights ``exp(lse
    - logaddexp(lse_a, lse_b))``. A side that saw no key (``lse <= -1e30``)
    weighs nothing and, through the weight, gets no gradient."""
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a, w_b = jnp.exp(lse_a - lse), jnp.exp(lse_b - lse)
    return (w_a[..., None] * o_a.astype(jnp.float32)
            + w_b[..., None] * o_b.astype(jnp.float32)).astype(o_a.dtype)
