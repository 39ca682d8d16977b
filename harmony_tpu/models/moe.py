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
counters need. **Only the held slots are moved** (PR 35): a chip that holds
``H`` of ``E`` experts computes about ``H / E`` of the slots, and gathering,
masking and combining all of them cost seven times the matmuls. The held
slots are the first ``sum(tokens[:H])`` entries of the sorted order, and the
layer walks them in chunks of a static capacity ``C`` (:func:`chunk_plan`:
twice the balanced held share, in 512-row tiles): chunk ``i`` gathers the
rows of sorted slots ``[i C, (i + 1) C)``, runs the three grouped matmuls
with the held runs clipped to that interval, and adds each held row times
its gate onto its token's row of a float32 ``[T, d]`` sum (PR 37: folded in
VMEM a token tile at a time, ops/sum_rows.py, where XLA's scatter-add took
the rows one after another). The router's selection itself — which ``k`` of
the ``E`` scores, and each one's weight — is one op too (PR 43,
ops/top_k_rows.py: ``lax.top_k``'s experts in its order, bit for bit, with a
compare-and-sum backward, where XLA sorted every row, gathered ``T k``
scalars and scatter-added them back one after another). The loop runs
while a chunk starts inside the held slots — one chunk while the router
stays inside the headroom, all ``ceil(T k / C)`` if every slot routes here:
nothing is dropped, there is no capacity factor. **There is ONE body**: a
``while_loop`` with a trip count the device decides, under a ``custom_vjp``
whose backward is the same loop written out (autodiff cannot reverse a loop
of unknown length). A ladder of capacities, or a ``cond`` with a full-length
fallback, is a second copy of the layer to trace, differentiate and lower
(each with nine Mosaic kernels): PR 34 measured that at +17 s of ``setup_s``
in Kimi Linear, and was refused for it. Where a chunk would pass a quarter
of the slots the layer is one full-length pass, as it always was
(:func:`_experts_plain`). DeepSeek-V3's router (Moonlight's,
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

from harmony_tpu.ops.residuals import ROUTER_LOGITS, keep
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
    act: str = "silu"         # the experts' activation (``_ACTS``)
    # Nemotron-H's LatentMoE: ``gated`` False drops the gate (down(act(up
    # x)): two grouped matmuls, not three; the shared MLP likewise);
    # ``latent`` > 0 is the width the ROUTED experts read and write — every
    # token through ``latent_down [d, latent]`` before the dispatch and
    # ``latent_up [latent, d]`` after the combine, the router and the shared
    # MLP on the full-width rows; ``shared_d_ff`` > 0 is the shared MLP's
    # width where that is no multiple of an expert's (the columns held here)
    gated: bool = True
    latent: int = 0
    shared_d_ff: int = 0
    # Qwen3-Next's shared expert: ``shared_gate`` multiplies the shared
    # MLP's output by ``sigmoid(x . shared_gate [d])``, one scalar a token
    shared_gate: bool = False
    # ZAYA1's router (arXiv:2511.17127; :func:`_mlp_logits`):
    # ``router_hidden`` > 0 is the width of an MLP where the other routers
    # have one matrix — its pre-norm rows also reach the next expert layer's
    # router —, selecting by ``softmax + bias`` and weighing by the softmax
    # alone; ``null_expert`` gives that softmax one more output, "no
    # expert": a slot that chose it is computed nowhere and adds nothing;
    # ``norm_eps`` is the router's RMSNorm's
    router_hidden: int = 0
    null_expert: bool = False
    norm_eps: float = 1e-6

    @property
    def router_out(self) -> int:
        """The router's outputs: an expert each, and "no expert"."""
        return self.num_experts + int(self.null_expert)

    @property
    def expert_d(self) -> int:
        """The routed experts' input and output width."""
        return self.latent or self.d_model

    @property
    def shared_width(self) -> int:
        return self.shared_d_ff or self.shared_experts * self.d_ff


def init_dropless_params(rng: jax.Array, cfg: DroplessConfig
                         ) -> Dict[str, jnp.ndarray]:
    """Router over ALL experts; gate/up/down weights of the HELD experts,
    expert-stacked on the leading dim. A sigmoid router has its selection
    ``bias [E]`` (zeros: the optimizer leaves it there, its gradient is 0);
    shared experts are ``shared_wg`` / ``_wu`` / ``_wd``. Ungated experts
    have no ``wg`` / ``shared_wg``; latent ones are ``expert_d`` wide
    between ``latent_down`` and ``latent_up``. An MLP router
    (``router_hidden``) has, where the others have ``router``,
    :func:`init_mlp_router`'s leaves."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    E, H, f = cfg.num_experts, cfg.experts_held, cfg.d_ff
    d, r = cfg.d_model, cfg.expert_d
    params = {
        "router": jax.random.normal(kr, (d, E), jnp.float32) * (d ** -0.5),
        "wu": jax.random.normal(ku, (H, r, f), jnp.float32) * (r ** -0.5),
        "wd": jax.random.normal(kd, (H, f, r), jnp.float32) * (f ** -0.5),
    }
    if cfg.router_hidden:
        del params["router"]
        params.update(init_mlp_router(kr, cfg))
    if cfg.gated:
        params["wg"] = jax.random.normal(kg, (H, r, f), jnp.float32) * (r ** -0.5)
    if cfg.score == "sigmoid":
        params["bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.shared_experts:
        fs = cfg.shared_width
        ksg, ksu, ksd = jax.random.split(jax.random.fold_in(rng, 1), 3)
        if cfg.gated:
            params["shared_wg"] = jax.random.normal(ksg, (d, fs), jnp.float32) * (d ** -0.5)
        params["shared_wu"] = jax.random.normal(ksu, (d, fs), jnp.float32) * (d ** -0.5)
        params["shared_wd"] = jax.random.normal(ksd, (fs, d), jnp.float32) * (fs ** -0.5)
        if cfg.shared_gate:
            params["shared_gate"] = jax.random.normal(
                jax.random.fold_in(rng, 3), (d,), jnp.float32) * (d ** -0.5)
    if cfg.latent:
        kld, klu = jax.random.split(jax.random.fold_in(rng, 2))
        params["latent_down"] = jax.random.normal(kld, (d, r), jnp.float32) * (d ** -0.5)
        params["latent_up"] = jax.random.normal(klu, (r, d), jnp.float32) * (r ** -0.5)
    return params


def init_mlp_router(rng: jax.Array, cfg: DroplessConfig
                    ) -> Dict[str, jnp.ndarray]:
    """An MLP router's leaves (:func:`_mlp_logits`), ``R = router_hidden``:
    the down-projection ``r_down [d, R]`` + ``r_down_b``, the scale
    ``r_eda [R]`` on the rows the router before left (ones), the norm's
    weight ``r_norm [R]``, ``r_w1`` / ``r_w2 [R, R]`` with biases, ``r_w3
    [R, outputs]`` without one, and the selection ``bias [outputs]``: zeros,
    -1 on "no expert" — held there by the optimizer (no gradient reaches
    it), so a router as initialised sends no token to no expert. ``r_w2``
    and ``r_w3`` are drawn fan-in and then CENTRED down their fan-in axis
    (each column less its mean): a GELU's outputs have a positive mean
    (0.28 for a unit normal), which a plain fan-in matrix turns into one
    constant offset an output — 0.2 against the 0.35 the logits spread
    over tokens, and the most loaded of 16 experts took 4 to 7 times the
    mean as initialised at d 512 on the CPU; at ZAYA1's widths on the chip
    2.4 to 2.9 times against 1.5 to 2.0 centred, the step's rate within
    half a percent either way (PERF.md section 6, PR 45). A trained router
    is balanced by its selection bias; this one starts nearer balance."""
    d, R, out = cfg.d_model, cfg.router_hidden, cfg.router_out
    kd, k1, k2, k3 = jax.random.split(rng, 4)
    dense = lambda key, a, b: jax.random.normal(key, (a, b), jnp.float32) \
        * (a ** -0.5)
    centred = lambda w: w - w.mean(axis=0)
    zeros, ones = (lambda: jnp.zeros((R,), jnp.float32),
                   lambda: jnp.ones((R,), jnp.float32))
    return {
        "r_down": dense(kd, d, R), "r_down_b": zeros(), "r_eda": ones(),
        "r_norm": ones(), "r_w1": dense(k1, R, R), "r_b1": zeros(),
        "r_w2": centred(dense(k2, R, R)), "r_b2": zeros(),
        "r_w3": centred(dense(k3, R, out)),
        "bias": jnp.zeros((out,), jnp.float32).at[cfg.num_experts:].set(-1.0),
    }


def _mlp_logits(params, x, cfg: DroplessConfig, state):
    """``(logits [T, outputs], rows [T, R])`` of ZAYA1's router, all in
    float32 at full precision: ``z = x W_down + b`` plus, where the expert
    layer before left its rows (``state``; None in the first), ``r_eda *
    state``; ``z`` is what this layer leaves for the next; ``logits =
    gelu(gelu(rmsnorm(z) W1 + b1) W2 + b2) W3`` (the exact GELU)."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    z = jnp.dot(x.astype(f32), params["r_down"], precision=hi) \
        + params["r_down_b"]
    if state is not None:
        z = z + params["r_eda"] * state
    u = z * lax.rsqrt(jnp.mean(z * z, axis=-1, keepdims=True)
                      + cfg.norm_eps) * params["r_norm"]
    for w, b in (("r_w1", "r_b1"), ("r_w2", "r_b2")):
        u = jax.nn.gelu(jnp.dot(u, params[w], precision=hi) + params[b],
                        approximate=False)
    return jnp.dot(u, params["r_w3"], precision=hi), z


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


def _route(params, x, cfg: DroplessConfig, seqs: int, state=None):
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
    normalised score) — a mean already, so layers ADD it. An MLP router
    (``router_hidden``; ``state``: :func:`_mlp_logits`) adds ``state``, its
    rows for the next layer's router, and with ``null_expert`` ``skipped``,
    the slots that chose no expert: those carry the index ``E``, which no
    device holds, and ``tokens`` / ``prob_sum`` keep to the ``E`` experts."""
    from harmony_tpu.ops.top_k_rows import top_k_rows
    from harmony_tpu.utils.platform import trace_is_tpu

    T = x.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    interpret = not trace_is_tpu()
    with step_scope("moe.route"):
        # a tiny matmul deciding discrete routes: full float32 passes on
        # the MXU
        if cfg.router_hidden:
            logits, rows = _mlp_logits(params, x, cfg, state)
        else:
            logits = jnp.dot(x.astype(jnp.float32), params["router"],
                             precision=lax.Precision.HIGHEST)    # [T, E]
        # kept by a rematerialised block (ops/residuals.py), like the
        # selection below: neither the matmul nor the rounds run again
        logits = keep(logits, ROUTER_LOGITS)
        # the selection is one op (ops/top_k_rows.py): lax.top_k's experts
        # in its order, no sort, no scalar gather, no scatter-add behind it
        if cfg.score == "softmax":
            lse = jax.nn.logsumexp(logits, axis=-1)
            probs = jnp.exp(logits - lse[:, None])
            if cfg.router_hidden:  # selected with the bias, weighed without
                gate, expert = top_k_rows(
                    probs + lax.stop_gradient(params["bias"]), probs, k,
                    interpret=interpret)
                probs = probs[:, :E]
            else:
                gate, expert = top_k_rows(probs, None, k,
                                          interpret=interpret)   # [T, k]
        else:
            score = jax.nn.sigmoid(logits)
            # the bias moves WHICH experts are chosen; weights are the
            # scores
            gate, expert = top_k_rows(
                score + lax.stop_gradient(params["bias"]), score, k,
                interpret=interpret)
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
        if cfg.router_hidden:
            stats["state"] = rows
        if cfg.null_expert:
            stats["skipped"] = jnp.sum(slot_expert == E, dtype=jnp.float32)
    return gate, expert, slot_expert, tokens, stats


# -- the held token-slots, in chunks of a static capacity --------------------

#: a chunk holds ``_HEADROOM`` times the slots a balanced router sends to the
#: held experts, in whole row tiles of the grouped matmul; the slots are
#: chunked where a chunk is at most ``1 / _LEAST_CUT`` of them (on the chip a
#: chunk of a quarter took 30% off the layer, a chunk of half ADDED 4% while
#: the rows were summed by XLA's scatter-add: PERF.md, PR 35. With the row-sum
#: kernel a chunk of half takes 15% OFF OLMoE's layer, 20.6 -> 17.5 ms: PR 37
#: read it and left the cut where it was, for an issue with that cell's own
#: measurement to move)
_HEADROOM, _ROW_TILE, _LEAST_CUT = 2, 512, 4


def chunk_plan(slots: int, held: int, experts: int) -> Tuple[int, int]:
    """``(capacity, chunks)`` of the expert layer for ``slots = T * k``
    token-slots where ``held`` of ``experts`` experts live. The capacity is
    the balanced held share with headroom, ``round_up(2 slots held /
    experts, 512)``, and the slot axis is cut into ``ceil(slots /
    capacity)`` chunks of it — of which a step runs those that hold a held
    slot, one while the router stays inside the headroom. ``(slots, 0)``
    where a chunk would pass a quarter of the slots (every expert held, a
    held share above 1/8, the small test models): the layer is then one
    full-length pass (:func:`_experts_plain`). A pure function of the three
    shapes: the program (:func:`moe_ffn_dropless`) and the counters
    (metrics/moe.py) both ask here and nothing else decides."""
    need = -(-_HEADROOM * slots * held // experts)
    C = -(-need // _ROW_TILE) * _ROW_TILE
    return (slots, 0) if _LEAST_CUT * C > slots else (C, -(-slots // C))


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
         "relu2": lambda t: jnp.square(jax.nn.relu(t))}


def _gated(g, u, act="silu"):
    """The experts' hidden rows: ``act(g) * u``, or ``act(u)`` where the
    experts are not gated (``g`` None)."""
    return _ACTS[act](u) if g is None else _ACTS[act](g) * u


def _d_act(t, act):
    """``act``'s derivative at ``t`` (float32)."""
    if act == "silu":
        s = jax.nn.sigmoid(t)
        return s * (1.0 + t * (1.0 - s))
    return jnp.where(t > 0, 2.0 * t if act == "relu2" else 1.0, 0.0)


def _chunk(C: int, i, order, offsets, starts, k: int):
    """Chunk ``i`` of the sorted slots: ``(lo, slots [C], tokens [C], sizes
    [H], bounds)`` — the held experts' runs, and their token tiles' ranges
    (``starts``: :func:`_tile_starts`), clipped to ``[i C, (i + 1) C)``."""
    lo = i * C
    slots = lax.dynamic_slice(order, (lo,), (C,))
    sizes = jnp.diff(jnp.clip(offsets - lo, 0, C))
    return lo, slots, slots // k, sizes, jnp.clip(starts - lo, 0, C)


def _tile_starts(slot_expert, offsets, k: int, tile: int):
    """``[T / tile + 1, H]`` int32: where, in the sorted slots, each held
    run's rows of each tile of ``tile`` consecutive tokens start (the last
    row: where the run ends). The sort by expert is stable and a token picks
    an expert once, so inside a run the tokens strictly ascend and a tile's
    rows are one contiguous range — what :func:`ops.sum_rows.sum_rows` folds
    (a chunk clips these to its interval). A compare-and-sum over the
    routing, not a scatter, with the slots on the lanes (experts there
    would fill 8 lanes of 128)."""
    H = offsets.shape[0] - 1
    held = slot_expert[None, :] == jnp.arange(H)[:, None]        # [H, T k]
    count = jnp.sum(held.reshape(H, -1, tile * k), axis=2, dtype=jnp.int32)
    return (offsets[:H, None] + jnp.concatenate(
        [jnp.zeros((H, 1), jnp.int32), jnp.cumsum(count, axis=1)], axis=1)).T


def _run_chunks(C: int, act: str, save: bool, x, weight, order, offsets,
                starts, wg, wu, wd):
    """``(out [T, d] f32, (g, u))``: the routed sum over the held slots,
    chunk by chunk while a chunk starts inside them; with ``save`` the
    gate and up products of every chunk that ran, stacked by sorted row
    (rows of a chunk that did not run stay 0). ``wg`` None: experts that
    are not gated — no gate product is formed or saved."""
    from harmony_tpu.ops.grouped_matmul import _gmm, _note_plans
    from harmony_tpu.ops.sum_rows import note_plan, sum_rows
    from harmony_tpu.utils.platform import trace_is_tpu

    (T, d), k, dtype = x.shape, weight.shape[1], x.dtype
    H, _, f = wu.shape
    interpret = not trace_is_tpu()
    _note_plans(("fwd",), C, d, f, H, dtype)
    _note_plans(("fwd",), C, f, d, H, dtype)
    note_plan(T, C, d, H, dtype)
    n_held = offsets[H]
    flat_w = weight.reshape(-1)

    def body(carry):
        i, acc, saved = carry
        with step_scope("moe.dispatch"):
            lo, slots, tok, sizes, bounds = _chunk(C, i, order, offsets,
                                                   starts, k)
            rows = x[tok]                                        # [C, d]
        with step_scope("moe.experts"):
            g = None if wg is None else _gmm(rows, wg, sizes, False,
                                             interpret)
            u = _gmm(rows, wu, sizes, False, interpret)
            y = _gmm(_gated(g, u, act), wd, sizes, False, interpret)  # [C, d]
            if save:
                saved = tuple(lax.dynamic_update_slice(s, v, (lo, 0))
                              for s, v in zip(saved, (u,) if g is None
                                              else (g, u)))
        with step_scope("moe.combine"):
            # each held row times its gate onto its token's row, folded in
            # VMEM a token tile at a time: XLA's scatter-add takes the C
            # rows one after another, and no XLA form of the sum tried on
            # the chip beat it (PERF.md, PR 35 / PR 37)
            acc = sum_rows(acc, y, tok, bounds, flat_w[slots], fresh=i == 0,
                           interpret=interpret)
        return i + 1, acc, saved

    saved = ((jnp.zeros((order.shape[0], f), dtype),)
             * (2 - (wg is None))) if save else ()
    _, out, saved = lax.while_loop(
        lambda carry: carry[0] * C < n_held, body,
        (jnp.int32(0), jnp.zeros((T, d), jnp.float32), saved))
    return out, saved


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts_chunked(C, act, x, weight, order, offsets, starts, wg, wu, wd):
    """``out [T, d]`` float32: per token, ``weight[t, j]`` times the held
    expert's gated MLP (``act``: :func:`_gated`) of ``x[t]``, summed over
    the token's slots
    routed to a held expert. ``order [chunks * C]`` are the slots sorted by
    expert (held runs first, padding after), ``offsets [H + 1]`` the held
    runs' bounds in it, ``starts`` their token tiles' (:func:`_tile_starts`,
    at the tile :func:`ops.sum_rows.tile_plan` gives). Only chunks that
    start inside the held slots run:
    ONE body, a dynamic trip count, so idle chunks cost nothing and a second
    capacity is never traced. The backward is written out, chunk by chunk
    the same way: autodiff cannot reverse a loop of unknown length, and the
    forward kernels must not run again in it (the benchmark pairs three
    forward, three dx and three dw calls a layer and step)."""
    return _run_chunks(C, act, False, x, weight, order, offsets, starts, wg,
                       wu, wd)[0]


def _experts_chunked_fwd(C, act, x, weight, order, offsets, starts, wg, wu,
                         wd):
    out, saved = _run_chunks(C, act, True, x, weight, order, offsets, starts,
                             wg, wu, wd)
    return out, (x, weight, order, offsets, starts, wg, wu, wd, saved)


def _experts_chunked_bwd(C, act, res, d_out):
    from harmony_tpu.ops.grouped_matmul import _gmm, _note_plans, _tgmm
    from harmony_tpu.ops.sum_rows import note_plan, sum_rows
    from harmony_tpu.utils.platform import trace_is_tpu

    x, weight, order, offsets, starts, wg, wu, wd, saved = res
    gated = wg is not None  # else down(act(up x)): a dx and a dw call fewer
    (T, d), k, dtype = x.shape, weight.shape[1], x.dtype
    H, _, f = wu.shape
    interpret = not trace_is_tpu()
    _note_plans(("dx", "dw"), C, d, f, H, dtype)
    _note_plans(("dx", "dw"), C, f, d, H, dtype)
    note_plan(T, C, d, H, dtype)
    n_held = offsets[H]
    flat_w = weight.reshape(-1)
    f32 = jnp.float32

    def body(carry):
        i, d_x, d_w, *d_ws = carry
        d_wu, d_wd = d_ws[-2:]
        with step_scope("moe.dispatch"):
            lo, slots, tok, sizes, bounds = _chunk(C, i, order, offsets,
                                                   starts, k)
            rows = x[tok]
        with step_scope("moe.combine"):
            w = flat_w[slots][:, None]
            d_rows_out = d_out[tok]                              # [C, d] f32
            d_y = (d_rows_out * w).astype(dtype)
        with step_scope("moe.experts"):
            g, u = ((None,) * (not gated) + tuple(
                lax.dynamic_slice(s, (lo, 0), (C, f)) for s in saved))
            h = _gated(g, u, act)
            # the down product's cotangent WITHOUT the gate's weight: the
            # weight's own cotangent is <h, it>, the hidden's is w times it
            d_hu = _gmm(d_rows_out.astype(dtype), wd, sizes, True,
                        interpret).astype(f32)                   # [C, f]
            d_wd = d_wd + _tgmm(h, d_y, sizes, interpret)
            d_h = d_hu * w
            if not gated:
                d_u = (d_h * _d_act(u.astype(f32), act)).astype(dtype)
                d_rows = _gmm(d_u, wu, sizes, True, interpret)
                d_wu = d_wu + _tgmm(rows, d_u, sizes, interpret)
                d_ws = (d_wu, d_wd)
            else:
                g32, u32 = g.astype(f32), u.astype(f32)
                if act == "silu":
                    sg = jax.nn.sigmoid(g32)
                    d_g = (d_h * u32 * sg * (1.0 + g32 * (1.0 - sg))
                           ).astype(dtype)
                    d_u = (d_h * g32 * sg).astype(dtype)
                else:  # relu: the gate passes where it is positive
                    d_g = jnp.where(g32 > 0, d_h * u32, 0.0).astype(dtype)
                    d_u = jnp.where(g32 > 0, d_h * g32, 0.0).astype(dtype)
                d_rows = (_gmm(d_g, wg, sizes, True, interpret)
                          + _gmm(d_u, wu, sizes, True, interpret))
                d_wg = d_ws[0] + _tgmm(rows, d_g, sizes, interpret)
                d_wu = d_wu + _tgmm(rows, d_u, sizes, interpret)
                d_ws = (d_wg, d_wu, d_wd)
        with step_scope("moe.combine"):
            d_w = d_w.at[slots].add(jnp.sum(h.astype(f32) * d_hu, axis=-1))
        with step_scope("moe.dispatch"):
            d_x = sum_rows(d_x, d_rows, tok, bounds, fresh=i == 0,
                           interpret=interpret)
        return (i + 1, d_x, d_w, *d_ws)

    held = (wg, wu, wd) if gated else (wu, wd)
    _, d_x, d_w, *d_ws = lax.while_loop(
        lambda carry: carry[0] * C < n_held, body,
        (jnp.int32(0), jnp.zeros((T, d), f32), jnp.zeros((T * k,), f32),
         *(jnp.zeros_like(t) for t in held)))
    return (d_x.astype(dtype), d_w.reshape(weight.shape), None, None, None,
            *(None,) * (not gated), *d_ws)


_experts_chunked.defvjp(_experts_chunked_fwd, _experts_chunked_bwd)


def _experts_plain(x, gate, expert, slot_expert, tokens, wg, wu, wd,
                   act="silu"):
    """The routed sum over ALL ``T * k`` sorted slots in one pass: where
    every expert is held, or the held share leaves nothing to cut."""
    from harmony_tpu.ops.grouped_matmul import grouped_matmul

    (T, d), k, H = x.shape, gate.shape[1], wu.shape[0]
    with step_scope("moe.dispatch"):
        order = jnp.argsort(slot_expert, stable=True)
        inv = jnp.argsort(order)  # a permutation's inverse, again by sorting
        sizes = tokens[:H]
        rows = _slot_rows(x, order, inv, k)                      # [T * k, d]
    dtype = x.dtype
    with step_scope("moe.experts"):
        if wg is None:  # experts that are not gated
            h = _ACTS[act](grouped_matmul(rows, wu.astype(dtype), sizes))
        else:
            h = (_ACTS[act](grouped_matmul(rows, wg.astype(dtype), sizes))
                 * grouped_matmul(rows, wu.astype(dtype), sizes))
        y = grouped_matmul(h, wd.astype(dtype), sizes)           # [T * k, d]
    with step_scope("moe.combine"):
        # back to slot order; an absent expert's slot carries weight 0
        weight = jnp.where(expert < H, gate, 0.0)                # [T, k]
        y = _slot_rows(y, inv, order, 1).reshape(T, k, d)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), weight)
        return out.astype(dtype)


def _note_chunk_plan(C: int, chunks: int, d: int, f: int) -> None:
    """Trace-time record of the layer's plan (STATUS ``kernel_plans``, row
    ``moe_held_chunks``): block_q = the capacity, grid_steps = the chunks
    the slot axis is cut into. Never fails a trace."""
    try:
        from harmony_tpu.runtime.progcache import note_kernel_plan

        note_kernel_plan("moe_held_chunks", C, 0, 0, chunks, True,
                         d=d, dv=f)
    except Exception:
        pass


def moe_ffn_dropless(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                     cfg: DroplessConfig, seqs: int = 1,
                     router_x: Optional[jnp.ndarray] = None,
                     state: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``(out [T, d], stats)`` for ``x [T, d]`` (``seqs`` sequences of
    ``T // seqs`` tokens, in order). ``out`` sums, per token,
    ``w_e * down_e(act(gate_e x) * up_e x)`` over the token's top-k experts
    that are held here (``w_e``: :func:`_route`), plus the shared MLP where
    the configuration has one. ``stats``: :func:`_route`'s. The router
    reads ``router_x [T, d]`` where given (a block that routes on its
    input), else the rows it dispatches. Latent experts (``cfg.latent``)
    read ``x latent_down`` and their sum passes ``latent_up``; the router
    and the shared MLP keep the full-width rows. ``state``: an MLP router's
    rows from the expert layer before (``stats["state"]`` of that call)."""
    from harmony_tpu.ops.sum_rows import tile_plan

    T, d = x.shape
    k, H = cfg.top_k, cfg.experts_held
    route = {} if state is None else {"state": state}
    gate, expert, slot_expert, tokens, stats = _route(
        params, x if router_x is None else router_x, cfg, seqs, **route)
    dtype = x.dtype
    full = x
    if cfg.latent:
        with step_scope("moe.latent"):
            x = x @ params["latent_down"].astype(dtype)
            d = cfg.latent
    C, chunks = chunk_plan(T * k, H, cfg.num_experts)
    if not chunks:
        out = _experts_plain(x, gate, expert, slot_expert, tokens,
                             *(params.get(w) for w in ("wg", "wu", "wd")),
                             cfg.act)
    else:
        _note_chunk_plan(C, chunks, d, cfg.d_ff)
        # slots sorted by expert: the held experts' runs come first (they
        # are experts 0 .. H-1) and only they are computed, C at a time
        with step_scope("moe.dispatch"):
            order = jnp.argsort(slot_expert, stable=True)
            order = jnp.pad(order, (0, chunks * C - T * k))
            offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                       jnp.cumsum(tokens[:H])])
            starts = _tile_starts(slot_expert, offsets, k,
                                  tile_plan(T, d, dtype))
        with step_scope("moe.experts"):
            weights = [params[w].astype(dtype) if w in params else None
                       for w in ("wg", "wu", "wd")]  # ungated: no wg
        out = _experts_chunked(C, cfg.act, x, gate, order, offsets, starts,
                               *weights)
        with step_scope("moe.combine"):
            out = out.astype(dtype)
    if cfg.latent:
        with step_scope("moe.latent"):
            out = out @ params["latent_up"].astype(dtype)
    if cfg.shared_experts:  # plain matmuls on every token, beside the sum
        with step_scope("moe.shared"):
            if cfg.gated:
                hs = (jax.nn.silu(full @ params["shared_wg"].astype(dtype))
                      * (full @ params["shared_wu"].astype(dtype)))
            else:
                hs = _ACTS[cfg.act](full @ params["shared_wu"].astype(dtype))
            shared = hs @ params["shared_wd"].astype(dtype)
            if cfg.shared_gate:  # a d-wide row a token: float32, no matmul
                gate = jax.nn.sigmoid(jnp.sum(
                    full.astype(jnp.float32) * params["shared_gate"],
                    axis=-1, keepdims=True))
                shared = shared * gate.astype(dtype)
                stats["shared_gate"] = lax.stop_gradient(gate.mean())
            out = out + shared
    return out, stats
