"""Mixture-of-Experts FFNs: two routings, each with its own use.

**Dropless top-k** (:func:`moe_ffn_dropless` — the block of today's open
sparse models, OLMoE's in ``perf/configs/olmoe-1b-7b.json``): softmax over
all experts in float32, the top ``k`` of it per token, NO capacity and NO
drops, gate weights as the softmax gave them (never renormalised). The
``T x k`` token-slots are sorted by expert, the rows gathered, and the gated-SiLU experts run as three ragged grouped matmuls
(ops/grouped_matmul.py) over the sorted rows; the results return to their
tokens weighted by the gates. A device may hold only the first
``experts_held`` experts (its share of an expert-parallel deployment): the
router still scores all of them, slots routed to an absent expert sort last
and their tiles are never visited, and the layer's output is this device's
part of the sum. Returns the routing statistics the auxiliary losses and the
counters need. DeepSeek-V3's router (Moonlight's,
``perf/configs/moonlight-16b-a3b.json``) enters BEFORE that body and leaves
it as it is: ``score="sigmoid"`` scores each expert by a sigmoid, selects
the top ``k`` of ``score + bias`` (a per-expert selection bias that carries
no gradient and never reaches a weight), ``norm_topk`` divides the chosen
experts' scores by their sum (over all ``k`` chosen, held here or not) and
``routed_scale`` multiplies them; ``shared_experts`` adds one gated-SiLU MLP
of ``shared_experts x d_ff`` to every token BESIDE the routed sum;
``seq_aux`` adds the per-sequence balance statistic.

**Switch top-1 with capacity** (:func:`moe_ffn` — the older path, with
expert parallelism over a mesh axis): bounded per-expert capacity,
dispatch/combine as one-hot einsums (MXU-friendly, static shapes); with
expert parallelism the expert dimension is sharded over a mesh axis and
token buckets move to their expert's device — and back — via
``lax.all_to_all`` over ICI.

Switch semantics:
  * capacity C per (expert, source shard) = ceil(T_local * capacity_factor
    / num_experts); tokens routed beyond capacity are DROPPED by dispatch
    (their combine weight is 0) — callers keep a residual connection so a
    dropped token passes through unchanged (standard Switch behavior).
  * aux load-balance loss (mean over experts of fraction_dispatched *
    mean_router_prob * E) encourages uniform routing.

``moe_ffn`` is pure and runs anywhere; pass ``axis_name`` when the expert
leading dim of the params is sharded over that mesh axis (inside shard_map).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from harmony_tpu.tracing.stepscopes import step_scope


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.5

    def capacity(self, num_tokens: int) -> int:
        return max(1, -(-int(num_tokens * self.capacity_factor) // self.num_experts))


def init_moe_params(rng: jax.Array, cfg: MoEConfig) -> Dict[str, jnp.ndarray]:
    """Router + per-expert FFN weights (expert-stacked on the leading dim —
    shard that dim over the EP mesh axis)."""
    kr, k1, k2 = jax.random.split(rng, 3)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": jax.random.normal(kr, (d, E), jnp.float32) * (d ** -0.5),
        "w1": jax.random.normal(k1, (E, d, f), jnp.float32) * (d ** -0.5),
        "w2": jax.random.normal(k2, (E, f, d), jnp.float32) * (f ** -0.5),
    }


def _dispatch_combine(x, router, num_experts: int, capacity: int):
    """Top-1 routing tensors: dispatch [T, E, C] one-hot, combine = dispatch
    * router prob, plus the Switch aux loss."""
    T = x.shape[0]
    logits = x.astype(jnp.float32) @ router          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)  # [T, E]
    # Position of each token within its expert's bucket (stable by index).
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot             # [T, E]
    keep = (pos < capacity) * onehot
    pos_oh = jax.nn.one_hot(pos.sum(axis=-1), capacity, dtype=jnp.float32)
    dispatch = keep[:, :, None] * pos_oh[:, None, :]                 # [T,E,C]
    combine = dispatch * gate[:, None, None]
    # Switch aux loss: E * Σ_e (fraction of tokens to e) * (mean prob of e)
    frac = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                      # [T, d] local tokens
    cfg: MoEConfig,
    axis_name: Optional[str] = None,     # EP axis (params expert-sharded)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out [T, d], aux_loss). Without ``axis_name`` all experts are
    local; with it, params' leading expert dim holds E/S local experts and
    token buckets are exchanged with ``all_to_all``."""
    T, d = x.shape
    E = cfg.num_experts
    C = cfg.capacity(T)
    with step_scope("moe.route"):
        dispatch, combine, aux = _dispatch_combine(x, params["router"], E, C)
    with step_scope("moe.dispatch"):
        xe = jnp.einsum("tec,td->ecd", dispatch,
                        x.astype(jnp.float32))        # [E, C, d]

    if axis_name is None:
        with step_scope("moe.experts"):
            w1, w2 = params["w1"], params["w2"]       # [E, d, f], [E, f, d]
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w1))
            ye = jnp.einsum("ecf,efd->ecd", h, w2)    # [E, C, d]
    else:
        S = lax.psum(1, axis_name)
        E_loc = E // S
        # [E, C, d] -> exchange: each device keeps its E_loc experts but
        # receives every shard's buckets for them: [S*E_loc, C, d] ->
        # all_to_all splits the expert axis and concatenates source shards.
        with step_scope("moe.dispatch"):
            xe = xe.reshape(S, E_loc, C, d)
            xe = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)          # [S, E_loc, C, d] src-major
            xe = xe.transpose(1, 0, 2, 3).reshape(E_loc, S * C, d)
        with step_scope("moe.experts"):
            w1, w2 = params["w1"], params["w2"]       # [E_loc, d, f]
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, w1))
            ye = jnp.einsum("ecf,efd->ecd", h, w2)    # [E_loc, S*C, d]
        with step_scope("moe.combine"):
            ye = ye.reshape(E_loc, S, C, d).transpose(1, 0, 2, 3)  # [S, E_loc, C, d]
            ye = lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
            ye = ye.reshape(E, C, d)

    with step_scope("moe.combine"):
        out = jnp.einsum("tec,ecd->td", combine, ye).astype(x.dtype)
    return out, aux


# ---------------------------------------------------------------------------
# Dropless top-k routing over ragged grouped matmuls
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DroplessConfig:
    num_experts: int          # the router's width
    top_k: int
    d_model: int
    d_ff: int                 # one expert's width
    experts_held: int         # experts 0 .. experts_held-1 live here
    score: str = "softmax"    # "softmax" | "sigmoid" (+ the selection bias)
    norm_topk: bool = False   # chosen scores / their sum (+ 1e-20)
    routed_scale: float = 1.0
    shared_experts: int = 0   # one MLP of this many expert widths, all tokens
    seq_aux: bool = False     # stats carry the sequence-wise balance term


def init_dropless_params(rng: jax.Array, cfg: DroplessConfig
                         ) -> Dict[str, jnp.ndarray]:
    """Router over ALL experts; gate/up/down weights of the HELD experts,
    expert-stacked on the leading dim. A sigmoid router has its selection
    ``bias [E]`` (zeros: the optimizer leaves it there, its gradient is 0);
    shared experts are ``shared_wg`` / ``_wu`` / ``_wd``."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    E, H, d, f = cfg.num_experts, cfg.experts_held, cfg.d_model, cfg.d_ff
    params = {
        "router": jax.random.normal(kr, (d, E), jnp.float32) * (d ** -0.5),
        "wg": jax.random.normal(kg, (H, d, f), jnp.float32) * (d ** -0.5),
        "wu": jax.random.normal(ku, (H, d, f), jnp.float32) * (d ** -0.5),
        "wd": jax.random.normal(kd, (H, f, d), jnp.float32) * (f ** -0.5),
    }
    if cfg.score == "sigmoid":
        params["bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.shared_experts:
        fs = cfg.shared_experts * f
        ksg, ksu, ksd = jax.random.split(jax.random.fold_in(rng, 1), 3)
        params["shared_wg"] = jax.random.normal(ksg, (d, fs), jnp.float32) * (d ** -0.5)
        params["shared_wu"] = jax.random.normal(ksu, (d, fs), jnp.float32) * (d ** -0.5)
        params["shared_wd"] = jax.random.normal(ksd, (fs, d), jnp.float32) * (fs ** -0.5)
    return params


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _slot_rows(x, order, inv, k):
    """Row ``x[s // k]`` for each slot ``s`` of the PERMUTATION ``order``
    with inverse ``inv`` (slot ``s`` = token ``s // k``'s ``s % k``-th
    choice; ``k = 1``: plainly ``x[order]``). The cotangent gathers by
    ``inv`` and sums a token's ``k`` slots — autodiff's would be a
    scatter-add, which a TPU serialises."""
    return x[order // k]


def _slot_rows_bwd(k, res, g):
    inv, = res
    per_slot = g[inv].reshape(-1, k, g.shape[-1])
    return per_slot.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_slot_rows.defvjp(lambda x, order, inv, k: (x[order // k], (inv,)),
                  _slot_rows_bwd)


def _route(params, x, cfg: DroplessConfig, seqs: int):
    """``(gate [T, k], expert [T, k], slot_expert [T * k], tokens [E] int32,
    stats)``: each token's chosen experts (also slot by slot) and their
    weights, and the token-slots each expert was chosen for, by the
    configuration's router.
    ``stats`` are sums, so layers add before a mean is taken: ``tokens [E]``
    (token-slots each expert was chosen for), ``n`` (tokens), ``prob_sum
    [E]`` (sum over tokens of the router's probability; a sigmoid router's
    scores over their sum) and, softmax only, ``z_sum`` (sum over tokens of
    logsumexp(logits)^2). ``seq_aux`` adds ``seq_lb``: the mean over this
    layer's ``seqs`` sequences of ``sum_e f_e P_e`` (arXiv:2412.19437 eq.
    17-20: ``f_e = E / (k S) x`` the sequence's slots that chose ``e``, bias
    included, a count with no gradient; ``P_e`` the sequence's mean
    normalised score) — a mean already, so layers ADD it."""
    T = x.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    with step_scope("moe.route"):
        # a tiny matmul deciding discrete routes: full float32 passes on
        # the MXU
        logits = jnp.dot(x.astype(jnp.float32), params["router"],
                         precision=lax.Precision.HIGHEST)        # [T, E]
        if cfg.score == "softmax":
            lse = jax.nn.logsumexp(logits, axis=-1)
            probs = jnp.exp(logits - lse[:, None])
            gate, expert = lax.top_k(probs, k)                   # [T, k]
        else:
            score = jax.nn.sigmoid(logits)
            # the bias moves WHICH experts are chosen; weights are the
            # scores
            _, expert = lax.top_k(
                score + lax.stop_gradient(params["bias"]), k)
            gate = jnp.take_along_axis(score, expert, axis=1)
            probs = score / score.sum(axis=-1, keepdims=True)
        if cfg.norm_topk:  # over all k chosen, held here or not
            gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-20)
        if cfg.routed_scale != 1.0:
            gate = gate * cfg.routed_scale
        slot_expert = expert.reshape(-1)                         # [T * k]
    with step_scope("moe.aux"):
        # a compare-and-sum, not a scatter-add (which a TPU serialises)
        if cfg.seq_aux:
            by_seq = jnp.sum(slot_expert.reshape(seqs, -1)[:, :, None]
                             == jnp.arange(E)[None, None, :], axis=1,
                             dtype=jnp.int32)                    # [seqs, E]
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


def moe_ffn_dropless(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                     cfg: DroplessConfig, seqs: int = 1
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``(out [T, d], stats)`` for ``x [T, d]`` (``seqs`` sequences of
    ``T // seqs`` tokens, in order). ``out`` sums, per token,
    ``w_e * down_e(silu(gate_e x) * up_e x)`` over the token's top-k experts
    that are held here (``w_e``: :func:`_route`), plus the shared MLP where
    the configuration has one. ``stats``: :func:`_route`'s."""
    from harmony_tpu.ops.grouped_matmul import grouped_matmul

    T, d = x.shape
    k, H = cfg.top_k, cfg.experts_held
    gate, expert, slot_expert, tokens, stats = _route(params, x, cfg, seqs)
    # slots sorted by expert: the held experts' runs come first (they are
    # experts 0 .. H-1), absent experts' slots after them
    with step_scope("moe.dispatch"):
        order = jnp.argsort(slot_expert, stable=True)
        inv = jnp.argsort(order)  # a permutation's inverse, again by sorting
        sizes = tokens[:H]
        rows = _slot_rows(x, order, inv, k)                      # [T * k, d]
    dtype = x.dtype
    with step_scope("moe.experts"):
        h = (jax.nn.silu(grouped_matmul(rows, params["wg"].astype(dtype),
                                        sizes))
             * grouped_matmul(rows, params["wu"].astype(dtype), sizes))
        y = grouped_matmul(h, params["wd"].astype(dtype), sizes)  # [T * k, d]
    with step_scope("moe.combine"):
        # back to slot order; an absent expert's slot carries weight 0
        weight = jnp.where(expert < H, gate, 0.0)                # [T, k]
        y = _slot_rows(y, inv, order, 1).reshape(T, k, d)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), weight)
        out = out.astype(dtype)
    if cfg.shared_experts:  # plain matmuls on every token, beside the sum
        with step_scope("moe.shared"):
            hs = (jax.nn.silu(x @ params["shared_wg"].astype(dtype))
                  * (x @ params["shared_wu"].astype(dtype)))
            out = out + hs @ params["shared_wd"].astype(dtype)
    return out, stats
