"""The router's selection as one op: ``harmony_top_k_rows``.

A dropless router (models/moe.py ``_route``) ends with "which ``k`` of the
``E`` experts, and with what weight": ``lax.top_k(sel, k)`` and, where the
weights are not the selecting scores themselves (DeepSeek-V3's router selects
by ``score + bias`` and weighs by ``score``), ``take_along_axis(val, expert)``.
On a TPU the exact top-k is a SORT of every ``E``-wide row with its iota, the
``take_along_axis`` a gather of ``T k`` scalars and its transpose a serial
scatter-add of ``T k`` scalars: at Nemotron-H's router (8,192 tokens, top-22
of 512) 0.66 + 1.87 ms a forward call and 1.5 ms a backward one, where the
``[T, E]`` float32 scores are 16 MB — one pass of HBM is 0.02 ms (PERF.md,
PR 43).

The kernel's grid walks token tiles. A tile's ``[TB, E]`` block(s) come by
their BlockSpecs and are TRANSPOSED in VMEM, so that the tokens lie on the
lanes and the experts across sublanes and vregs: a row's maximum is then an
elementwise maximum across vregs and one sublane reduce a tile, where experts
on the lanes need a cross-lane reduce per eight tokens (measured 1.4-5 x
slower at every router's shape, PR 43). ``k`` rounds of: maximum over the
experts -> the FIRST expert that holds it -> emit its index and its weight ->
mask it. The outputs are gathered ``[k, TB]`` and transposed back once a tile.

Order: the scores are compared as ``lax.top_k`` compares them — by the total
order of their bits (``-0.0 < +0.0``, as XLA's sort comparator), ties to the
LOWER index — so ``expert`` is ``lax.top_k(sel, k)[1]`` bit for bit, and
``weight`` is ``val`` (``sel`` where ``val`` is None) at those lanes.

The backward is a compare-and-sum, not a scatter: ``d_val[t, e] = sum_j g[t,
j] (expert[t, j] == e)`` — at most one ``j`` matches, so the sum is exact and
equals the scatter-add bit for bit; ``d_sel`` is zero where ``val`` is given
(the selection alone carries no gradient). It stays XLA's: 0.02-0.12 ms a
call alone at the four routers' shapes (PR 43), a pass of its own where the
experts fill whole lane tiles and fused into the router's backward matmuls
where they do not (:func:`_bwd` has both measurements).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harmony_tpu.ops.residuals import ROUTER_EXPERT, ROUTER_WEIGHT, keep

KERNEL_NAME = "harmony_top_k_rows"
#: token tiles tried, largest first. On the chip (PERF.md, PR 43) a call at
#: Nemotron-H's router took 0.365 / 0.270 / 0.224 / 0.239 ms at 128 / 256 /
#: 512 / 1,024; the narrower routers read 0.060-0.067 at 512 and 0.052-0.063
#: at 1,024
_TB = (512, 256, 128, 64, 32, 16, 8)
#: the scoped VMEM the kernel asks for (Mosaic's default scope is 16 MiB), and
#: what a plan may fill of it
_VMEM_LIMIT, _VMEM_FREE = 32 * 2**20, 24 * 2**20
_LOWEST = np.int32(-2**31)


def _vmem_bytes(tb: int, experts: int, weighed: bool) -> int:
    """VMEM a grid step needs: each operand's block double-buffered and its
    transposed copy, and about six ``[E, TB]`` temporaries of a round."""
    return (3 * (1 + weighed) + 6) * tb * experts * 4


def tile_plan(tokens: int, experts: int, weighed: bool) -> int:
    """Tokens a grid step holds: the largest of 512..8 that divides
    ``tokens`` and fits the kernel's VMEM at this width; a token count that
    none divides is one tile."""
    fits = [tb for tb in _TB if tokens % tb == 0
            and _vmem_bytes(tb, experts, weighed) <= _VMEM_FREE]
    return fits[0] if fits else tokens


def note_plan(tokens: int, experts: int, k: int, weighed: bool) -> None:
    """Trace-time record of the kernel's tiling (STATUS ``kernel_plans``):
    block_q = the token tile, block_k = ``d`` = the experts, sub = ``dv`` =
    ``k``, grid_steps = the tiles a call walks. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        tb = tile_plan(tokens, experts, weighed)
        note_kernel_plan(KERNEL_NAME, tb, experts, k, tokens // tb, True,
                         d=experts, dv=k)
    except Exception:
        pass


def top_k_rows_ref(sel, val, k: int):
    """The XLA formulation the op stands for."""
    weight, expert = lax.top_k(sel, k)
    if val is not None:
        weight = jnp.take_along_axis(val, expert, axis=1)
    return weight, expert


def _ordered(bits):
    """Float32 bits <-> int32 keys whose signed order is the floats' total
    order (an involution)."""
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _make_kernel(tb: int, experts: int, k: int, weighed: bool):
    def kernel(*refs):
        """One token tile per grid step: transpose, ``k`` rounds, transpose
        the ``[k, TB]`` results back."""
        (sel, *val), (weight, expert, keys, wbuf, ebuf, *vals) = (
            refs[:1 + weighed], refs[1 + weighed:])
        keys[...] = _ordered(
            lax.bitcast_convert_type(sel[...], jnp.int32)).T    # [E, TB]
        if weighed:
            vals, = vals
            vals[...] = val[0][...].T

        def one(j, c):
            x = keys[...]
            lane = lax.broadcasted_iota(jnp.int32, (experts, tb), 0)
            top = jnp.max(x, axis=0, keepdims=True)              # [1, TB]
            first = jnp.min(jnp.where(x == top, lane, experts), axis=0,
                            keepdims=True)
            hit = lane == first
            if weighed:
                w = jnp.sum(jnp.where(hit, vals[...], 0.0), axis=0,
                            keepdims=True)
            else:
                w = lax.bitcast_convert_type(_ordered(top), jnp.float32)
            keys[...] = jnp.where(hit, _LOWEST, x)
            wbuf[pl.ds(j, 1), :] = w
            ebuf[pl.ds(j, 1), :] = first
            return c
        lax.fori_loop(0, k, one, 0)
        weight[...] = wbuf[...].T[:, :k]
        expert[...] = ebuf[...].T[:, :k]

    return kernel


def _select(sel, val, k: int, interpret: bool):
    (T, E), weighed = sel.shape, val is not None
    tb = tile_plan(T, E, weighed)
    kp = -(-k // 128) * 128  # whole lane tiles, for the transpose back
    block = pl.BlockSpec((tb, E), lambda i: (i, 0))
    out = pl.BlockSpec((tb, k), lambda i: (i, 0))
    return pl.pallas_call(
        _make_kernel(tb, E, k, weighed),
        name=KERNEL_NAME,
        out_shape=(jax.ShapeDtypeStruct((T, k), jnp.float32),
                   jax.ShapeDtypeStruct((T, k), jnp.int32)),
        grid=(T // tb,),
        in_specs=[block] * (1 + weighed),
        out_specs=(out, out),
        scratch_shapes=[pltpu.VMEM((E, tb), jnp.int32),
                        pltpu.VMEM((kp, tb), jnp.float32),
                        pltpu.VMEM((kp, tb), jnp.int32)]
        + [pltpu.VMEM((E, tb), jnp.float32)] * weighed,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*([sel, val] if weighed else [sel]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _top_k_rows(sel, val, k, weighed, interpret):
    return _select(sel, val, k, interpret)


def _fwd(sel, val, k, weighed, interpret):
    weight, expert = _select(sel, val, k, interpret)
    # what a rematerialised block keeps (ops/residuals.py)
    weight, expert = keep(weight, ROUTER_WEIGHT), keep(expert, ROUTER_EXPERT)
    # the lanes carry E to the backward: an iota, not a saved activation
    lanes = lax.broadcasted_iota(jnp.int32, (1, sel.shape[1]), 1)
    return (weight, expert), (expert, lanes)


def _bwd(k, weighed, interpret, res, cotangents):
    (expert, lanes), (g, _) = res, cotangents
    d = jnp.zeros((expert.shape[0], lanes.shape[1]), jnp.float32)
    for j in range(k):  # at most one j matches a lane: the sum is exact
        d = d + jnp.where(expert[:, j:j + 1] == lanes, g[:, j:j + 1], 0.0)
    # XLA fuses these k compares into the router's HIGHEST backward matmuls
    # as their operand's producer and runs them once a bf16 piece: at 512 / 22
    # that is +0.59 ms a layer where a pass of their own is 0.09, so whole
    # lane tiles of experts get the pass. Narrower rows stay fused: a
    # materialised [T, 64] operand made the same matmul 2.5-3 x slower (0.34
    # -> 0.87 ms, 0.41 -> 1.21), the k <= 8 compares cost it nothing (PR 43)
    if lanes.shape[1] % 128 == 0:
        d = lax.optimization_barrier(d)
    return (None, d) if weighed else (d, None)  # None: no cotangent


_top_k_rows.defvjp(_fwd, _bwd)


def top_k_rows(sel: jnp.ndarray, val: Optional[jnp.ndarray], k: int, *,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(weight [T, k] f32, expert [T, k] int32)``: ``expert[:, j]`` the
    lane of the ``j``-th largest of ``sel [T, E]`` float32, ties to the lower
    lane (``lax.top_k``'s order, bit for bit); ``weight[:, j]`` is ``val [T,
    E]`` float32 at that lane, or ``sel`` where ``val`` is None. The gradient
    reaches ``val`` alone where it is given. Every trace notes the tile plan
    (:func:`note_plan`)."""
    (T, E) = sel.shape
    if (sel.dtype != jnp.float32 or not 0 < k <= E
            or (val is not None and (val.shape, val.dtype)
                != (sel.shape, sel.dtype))):
        raise ValueError(
            f"top_k_rows: sel {sel.shape} {sel.dtype}, val "
            f"{None if val is None else (val.shape, val.dtype)}, k {k}")
    note_plan(T, E, k, val is not None)
    return _top_k_rows(sel, val, k, val is not None, interpret)
