"""The readout and the cross-entropy as ONE op: ``harmony_readout_*``.

A language model's step ends with

    logits = x @ head                      [N, V] float32
    nll    = logsumexp(logits) - logits[target]

and XLA walks the ``[N, V]`` float32 logits three times outside the
readout's matmuls: the exp-reduce of the log-softmax (one read), and its jvp
(one read, one write of ``dlogits``) — passes HBM pays for while the MXU
waits (PERF.md, PR 56: 7.1 ms of a 73 ms gpt2 step). Per logit the readout
does ``2 d`` FLOPs a product, so an exp, a subtract and a compare per element
ride free under the MXU if they happen on the tile a product just made or is
about to eat. Three kernels, one ``custom_vjp``:

- ``harmony_readout_fwd``, grid (row tiles, vocabulary tiles), vocabulary
  innermost: ``s = x_tile . head_tile`` in VMEM, the running maximum and
  sum of exponentials kept A LANE (``[TM, 128]``: elementwise work a tile,
  one cross-lane reduce a row tile), the target's logit picked by an iota
  compare; writes the float32 logits tile ONCE — the backward's residual,
  what the plain program keeps too — and ``lse``, ``nll`` a row.
- ``harmony_readout_bwd_dx``, same grid, the ``dx`` tile resident: reads the
  stored logits tile, forms ``g = (exp(s - lse) - onehot) d_nll`` in VMEM,
  ``dx += g . head_tile``.
- ``harmony_readout_bwd_dw``, grid (vocabulary tiles, row tiles), the ``dW``
  tile resident: the same ``g``, ``dW += x_tile^T . g`` (``x`` comes
  transposed, one small XLA pass, so that the product is a plain one; a
  tied table's ``[TV, d]`` tile is transposed in VMEM once a vocabulary
  tile; the rows' ``lse`` / ``d_nll`` / target columns come whole, once).

No ``[N, V]`` array but the logits exists; ``dlogits`` never does.

Numerical contract — the plain path's on a TPU: operands rounded to
bfloat16 as the MXU rounds float32 operands under default precision, float32
accumulation, float32 logits, float32 ``lse``, ``exp`` in float32, ``g``
rounded to bfloat16 only on entering the MXU. :func:`readout_nll_ref` is that
arithmetic in XLA.

Two layouts: ``tied`` — ``head`` is the embedding ``[V, d]``, ``s = x .
head^T`` — and a head of its own ``[d, V]``. ``head`` is rounded to bfloat16
by ONE XLA pass a step, which the kernels then re-read at half the bytes, and
is never grown: against a vocabulary ragged to the tile the last block is
read and ``dW``'s written as far as the array goes. The logits ARE grown to
whole tiles; the forward masks the columns past the vocabulary (``-inf``:
they carry it to the backward, where ``exp(-inf - lse)`` is 0) and ``dx``
zeroes the head's entries there (what lies past an array's end is no number).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_NAME = "harmony_readout_fwd"
DX_NAME = "harmony_readout_bwd_dx"
DW_NAME = "harmony_readout_bwd_dw"
_LANES = 128
#: the scoped VMEM the kernels ask for (a v5e has 128 MiB; Mosaic's default
#: scope is 16 MiB) and what a plan may fill of it
_VMEM_LIMIT, _VMEM_FREE = 96 * 2**20, 80 * 2**20
#: the smallest problem the kernels serve: a row tile of rows, and logits
#: whose passes cost something (64 MiB of float32: ~0.1 ms a pass of HBM)
_MIN_ROWS, _MIN_LOGITS = 256, 2**24


class Plan(NamedTuple):
    """Tiles of the three kernels: ``(rows, vocabulary)`` each."""
    fwd: Tuple[int, int]
    dx: Tuple[int, int]
    dw: Tuple[int, int]

    @property
    def row_tile(self) -> int:
        """What the rows are grown to a multiple of."""
        return max(self.fwd[0], self.dx[0], self.dw[0])

    @property
    def vocab_tile(self) -> int:
        """What the logits' columns are grown to a multiple of: the two
        kernels' that walk all of them (``dW`` reads its last block as far
        as they go)."""
        return max(self.fwd[1], self.dx[1])


_KERNELS = (FWD_NAME, DX_NAME, DW_NAME)
#: the tiles each kernel tries, best first (PERF.md, PR 56: STEP 0's sweep on
#: the chip). Rows: the more a step holds, the less often the head's tiles
#: are read again; ``dW`` wants the wider vocabulary tile, whose ``[d, TV]``
#: sum is what stays resident
_ROW_RESIDENT = ((2048, 256), (1024, 256), (1024, 512), (512, 512), (256, 512))
_TILES = {
    FWD_NAME: _ROW_RESIDENT,
    DX_NAME: _ROW_RESIDENT,
    DW_NAME: ((2048, 512), (1024, 512), (512, 512), (256, 512)),
}


def _vmem_bytes(kernel: str, tm: int, tv: int, d: int, n: int,
                tied: bool) -> int:
    """VMEM a grid step of ``kernel`` needs: the logits tile and the two
    operand tiles double-buffered, about three ``[TM, TV]`` float32
    temporaries, and what stays resident — the forward's three lane-wide
    sums; ``dx``'s float32 sum beside its output block; ``dW``'s sum, its
    output block, (tied) its transpose and all ``n`` rows' three columns,
    a lane-padded 512 bytes a row each."""
    step = (2 + 3) * 4 * tm * tv + 2 * 2 * tv * d + 2 * 2 * tm * d
    if kernel == FWD_NAME:
        return step + 3 * 4 * tm * _LANES
    if kernel == DX_NAME:
        return step + (4 + 4) * tm * d
    return step + (1 + 2 + tied) * 4 * tv * d + 3 * 4 * _LANES * n


def plan(n: int, d: int, v: int, tied: bool, dtype) -> Optional[Plan]:
    """The tiles for ``x [n, d]`` of ``dtype`` against a vocabulary of
    ``v``, or None where the kernels do not serve the shape: the plain
    readout is the path then. Pure: the shape decides, nothing else.

    Served: ``d`` whole lane tiles, at least a row tile of rows and 2^24
    logits, float32 or bfloat16 rows. A kernel takes the first of its
    tiles that the rows fill and VMEM holds."""
    if (d % _LANES or n < _MIN_ROWS or n * v < _MIN_LOGITS
            or jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                        jnp.dtype(jnp.bfloat16))):
        return None
    picked = [next(((tm, tv) for tm, tv in _TILES[kernel] if tm <= n
                    and _vmem_bytes(kernel, tm, tv, d, n, tied)
                    <= _VMEM_FREE),
                   None)
              for kernel in _KERNELS]
    return None if None in picked else Plan(*picked)


def note_plan(n: int, d: int, v: int, tiles: Plan) -> None:
    """Trace-time record of the kernels' tiling (STATUS ``kernel_plans``),
    a row a kernel: block_q = the row tile, block_k = the vocabulary tile,
    ``d`` the width, ``dv`` the vocabulary, grid_steps = the tiles a call
    walks. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        for name, (tm, tv) in zip(_KERNELS, tiles):
            note_kernel_plan(name, tm, tv, 0, -(-n // tm) * -(-v // tv),
                             True, d=d, dv=v)
    except Exception:
        pass


def readout_nll_ref(x, head, targets, tied: bool):
    """The XLA formulation the op stands for, at the MXU's rounding:
    ``logsumexp(x @ head) - (x @ head)[target]``, ``[N]`` float32."""
    logits = _dot(x.astype(jnp.bfloat16), head.astype(jnp.bfloat16), tied)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def _dot(a, b, trans_b: bool):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lane_chunks(tv: int):
    return [slice(c * _LANES, (c + 1) * _LANES) for c in range(tv // _LANES)]


def _make_fwd(tm: int, tv: int, v: int, vp: int, tied: bool):
    ragged = vp != v  # columns past the vocabulary: maybe whole tiles of them
    neg = float("-inf")

    def kernel(tgt, x, w, s_out, lse, nll, m_acc, l_acc, t_acc):
        """One (row tile, vocabulary tile): the product, then the running
        maximum, sum of exponentials and target logit, a lane each."""
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            m_acc[...] = jnp.full_like(m_acc, neg)
            l_acc[...] = jnp.zeros_like(l_acc)
            t_acc[...] = jnp.zeros_like(t_acc)

        s = _dot(x[...], w[...], tied)                         # [TM, TV]
        lane = lax.broadcasted_iota(jnp.int32, (tm, _LANES), 1)
        # the target's and the vocabulary's end relative to this tile
        rel = tgt[...] - j * tv                                # [TM, 1]
        chunks = []
        top = m_acc[...]
        for c, at in enumerate(_lane_chunks(tv)):
            sc = s[:, at]
            if ragged:
                sc = jnp.where(lane < v - j * tv - c * _LANES, sc, neg)
            s_out[:, at] = sc
            top = jnp.maximum(top, sc)
            chunks.append(sc)
        # a lane that has seen no column yet holds -inf: keep exp off nan
        safe = jnp.where(top == neg, 0.0, top)
        total = l_acc[...] * jnp.exp(m_acc[...] - safe)
        picked = t_acc[...]
        for c, sc in enumerate(chunks):
            total = total + jnp.exp(sc - safe)
            picked = picked + jnp.where(lane == rel - c * _LANES, sc, 0.0)
        m_acc[...] = top
        l_acc[...] = total
        t_acc[...] = picked

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            row_max = jnp.max(top, axis=1, keepdims=True)      # [TM, 1]
            row_sum = jnp.sum(total * jnp.exp(top - row_max), axis=1,
                              keepdims=True)
            out = row_max + jnp.log(row_sum)
            lse[...] = out
            nll[...] = out - jnp.sum(picked, axis=1, keepdims=True)

    return kernel


def _grad_tile(s, lse, dn, tgt, col0, tm: int, tv: int):
    """``g = (exp(s - lse) - onehot) d_nll`` of one logits tile, bfloat16;
    ``lse``, ``dn``, ``tgt``: the tile's rows' ``[TM, 1]`` columns."""
    lane = lax.broadcasted_iota(jnp.int32, (tm, _LANES), 1)
    rel = tgt - col0
    parts = []
    for c, at in enumerate(_lane_chunks(tv)):
        p = jnp.exp(s[:, at] - lse)
        p = jnp.where(lane == rel - c * _LANES, p - 1.0, p)
        parts.append((p * dn).astype(jnp.bfloat16))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _make_dx(tm: int, tv: int, v: int, vp: int, tied: bool):
    def kernel(lse, dn, tgt, s, w, dx, acc):
        """One (row tile, vocabulary tile): ``dx += g . head_tile``."""
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        g = _grad_tile(s, lse[...], dn[...], tgt[...], j * tv, tm, tv)
        tile = w[...]
        if vp != v:
            # the head's last block ends where the array does: what lies
            # past it is no number, and g's zeros there would not silence it
            at = lax.broadcasted_iota(jnp.int32, tile.shape, 0 if tied else 1)
            tile = jnp.where(at < v - j * tv, tile, jnp.zeros_like(tile))
        acc[...] += _dot(g, tile, not tied)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            dx[...] = acc[...].astype(dx.dtype)

    return kernel


def _make_dw(tm: int, tv: int, tied: bool):
    def kernel(lse, dn, tgt, s, xt, dw, acc):
        """One (vocabulary tile, row tile): ``dW^T += x_tile^T . g``. The
        three columns are here WHOLE, every row tile's, fetched once: a
        ``[TM, 1]`` block is ``TM / 8`` lane-padded tiles in HBM (1 MiB at
        2,048 rows) and this grid would fetch three a step — a third of the
        kernel's traffic at gpt2's shape."""
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        rows = pl.ds(pl.multiple_of(i * tm, tm), tm)
        g = _grad_tile(s, lse[rows, :], dn[rows, :], tgt[rows, :],
                       pl.program_id(0) * tv, tm, tv)
        acc[...] += _dot(xt[...], g, False)                    # [d, TV]

        @pl.when(i == pl.num_programs(1) - 1)
        def _():
            dw[...] = acc[...].T if tied else acc[...]

    return kernel


#: every grid: the resident tile's axis first, the walked axis innermost
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _head_spec(tv: int, d: int, tied: bool, vocab_axis: int):
    """The head's tile at the grid's ``vocab_axis``."""
    def at(*ids):
        return (ids[vocab_axis], 0) if tied else (0, ids[vocab_axis])
    return pl.BlockSpec((tv, d) if tied else (d, tv), at)


def _forward(x, w, tgt, tied: bool, tiles: Plan, interpret: bool):
    """``(logits [Np, Vp], lse [Np, 1], nll [Np, 1])`` of grown rows."""
    (n, d), (tm, tv), v = x.shape, tiles.fwd, w.shape[0 if tied else 1]
    vp = v + -v % tiles.vocab_tile
    col = pl.BlockSpec((tm, 1), lambda i, j: (i, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        _make_fwd(tm, tv, v, vp, tied),
        name=FWD_NAME,
        out_shape=(jax.ShapeDtypeStruct((n, vp), f32),
                   jax.ShapeDtypeStruct((n, 1), f32),
                   jax.ShapeDtypeStruct((n, 1), f32)),
        grid=(n // tm, vp // tv),
        in_specs=[col, pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
                  _head_spec(tv, d, tied, 1)],
        out_specs=(pl.BlockSpec((tm, tv), lambda i, j: (i, j)), col, col),
        scratch_shapes=[pltpu.VMEM((tm, _LANES), f32)] * 3,
        compiler_params=_PARAMS,
        interpret=interpret,
    )(tgt, x, w)


def _backward_dx(lse, dn, tgt, s, w, dtype, tied: bool, tiles: Plan,
                 interpret: bool):
    (n, vp), (tm, tv) = s.shape, tiles.dx
    v, d = w.shape if tied else w.shape[::-1]
    col = pl.BlockSpec((tm, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _make_dx(tm, tv, v, vp, tied),
        name=DX_NAME,
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        grid=(n // tm, vp // tv),
        in_specs=[col, col, col, pl.BlockSpec((tm, tv), lambda i, j: (i, j)),
                  _head_spec(tv, d, tied, 1)],
        out_specs=pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lse, dn, tgt, s, w)


def _backward_dw(lse, dn, tgt, s, xt, v: int, tied: bool, tiles: Plan,
                 interpret: bool):
    """``dW`` at the head's own ``v`` entries: the last vocabulary tile's
    block is written as far as the array goes."""
    n, (tm, tv), d = s.shape[0], tiles.dw, xt.shape[0]
    # whole and never fetched again: one buffer
    col = pl.BlockSpec((n, 1), lambda j, i: (0, 0),
                       pipeline_mode=pl.Buffered(1))
    return pl.pallas_call(
        _make_dw(tm, tv, tied),
        name=DW_NAME,
        out_shape=jax.ShapeDtypeStruct((v, d) if tied else (d, v),
                                       jnp.float32),
        grid=(pl.cdiv(v, tv), n // tm),
        in_specs=[col, col, col, pl.BlockSpec((tm, tv), lambda j, i: (i, j)),
                  pl.BlockSpec((d, tm), lambda j, i: (0, i))],
        out_specs=_head_spec(tv, d, tied, 0),
        scratch_shapes=[pltpu.VMEM((d, tv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lse, dn, tgt, s, xt)


def _grown(x, head, targets, tiles: Plan):
    """The operands as the kernels take them: ``x`` bfloat16 with its rows
    grown to whole tiles, ``head`` rounded to bfloat16 (ONE XLA pass a step;
    its last block is read as far as it goes), ``targets [Np, 1]`` int32."""
    rows = -x.shape[0] % tiles.row_tile
    xb = jnp.pad(x.astype(jnp.bfloat16), ((0, rows), (0, 0)))
    tgt = jnp.pad(targets.astype(jnp.int32), (0, rows))[:, None]
    return xb, head.astype(jnp.bfloat16), tgt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _readout_nll(x, head, targets, tied, tiles, interpret):
    return _fwd(x, head, targets, tied, tiles, interpret)[0]


def _fwd(x, head, targets, tied, tiles, interpret):
    xb, wb, tgt = _grown(x, head, targets, tiles)
    logits, lse, nll = _forward(xb, wb, tgt, tied, tiles, interpret)
    # zero-size carriers of the operands' dtypes
    like = (jnp.zeros((0,), x.dtype), jnp.zeros((0,), head.dtype))
    return nll[:x.shape[0], 0], (xb, wb, tgt, logits, lse, like)


def _bwd(tied, tiles, interpret, res, d_nll):
    xb, wb, tgt, logits, lse, (x_like, head_like) = res
    n, v = d_nll.shape[0], wb.shape[0 if tied else 1]
    dn = jnp.pad(d_nll.astype(jnp.float32), (0, xb.shape[0] - n))[:, None]
    dx = _backward_dx(lse, dn, tgt, logits, wb, x_like.dtype, tied, tiles,
                      interpret)
    dw = _backward_dw(lse, dn, tgt, logits, xb.T, v, tied, tiles, interpret)
    return dx[:n], dw.astype(head_like.dtype), None


_readout_nll.defvjp(_fwd, _bwd)


def readout_nll(x: jnp.ndarray, head: jnp.ndarray, targets: jnp.ndarray, *,
                tied: bool, tiles: Optional[Plan] = None,
                interpret: bool = False) -> jnp.ndarray:
    """``nll [N]`` float32, a row ``logsumexp(x . head) - (x . head)[target]``
    — ``x [N, d]`` float32 or bfloat16, ``head`` the embedding ``[V, d]``
    (``tied``) or a head of its own ``[d, V]``, ``targets [N]`` integers in
    ``[0, V)``. Differentiable in ``x`` and ``head``; the caller takes the
    mean or its weighted sum. ``tiles``: :func:`plan`'s unless given (the
    shape must be one it serves). Every trace notes the plan
    (:func:`note_plan`)."""
    (n, d), v = x.shape, head.shape[0 if tied else 1]
    if head.shape != ((v, d) if tied else (d, v)) or targets.shape != (n,):
        raise ValueError(f"readout_nll: x {x.shape}, head {head.shape} "
                         f"(tied={tied}), targets {targets.shape}")
    tiles = tiles or plan(n, d, v, tied, x.dtype)
    if tiles is None:
        raise ValueError(f"readout_nll: no plan serves x {x.shape} "
                         f"{x.dtype} against {v} columns")
    note_plan(n, d, v, tiles)
    return _readout_nll(x, head, targets, tied, tiles, interpret)
