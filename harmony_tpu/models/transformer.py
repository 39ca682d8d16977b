"""Decoder-only transformer LM — the framework's flagship neural model.

Design (TPU-first, no reference counterpart — the reference has no attention
models, SURVEY.md §5.7):

  * **Functional params pytree** (dicts of arrays), f32 masters; activations
    run in ``config.dtype`` (bf16 on hardware) so matmuls hit the MXU at
    full rate.
  * **Attention tiers**: single-chip uses the Pallas flash kernel
    (harmony_tpu.ops.attention); sequence-parallel training uses ring
    attention (harmony_tpu.ops.ring) or the all-to-all head-scatter
    scheme (harmony_tpu.ops.ulysses, ``sp_attn="a2a"``) inside
    ``shard_map`` over the mesh's "seq" axis; the blockwise scan is the
    differentiable/any-backend tier.
  * **PS-table integration**: :class:`TransformerTrainer` flattens the
    pytree into a range-partitioned DenseTable ([rows, row_width]) so the
    LM trains through the same Trainer SPI / WorkerTasklet / elastic-table
    machinery as every classic app — checkpointing, live resharding and
    multi-tenant scheduling apply to the LM for free.
  * **make_sp_train_step**: the long-context path — batch sharded over
    "data", sequence sharded over "seq"; grads are psum'd over both axes and
    params stay replicated, so a step is ONE compiled SPMD program whose
    collectives (ring ppermute + grad psum) ride ICI.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harmony_tpu.ops.attention import blockwise_attention
from harmony_tpu.ops.residuals import NAMES, collecting
from harmony_tpu.ops.ring import ring_attention
from harmony_tpu.ops.ulysses import a2a_attention
from harmony_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
from harmony_tpu.tracing.stepscopes import step_scope


class Rotary(NamedTuple):
    """One kind of softmax block's rotary positions (``TransformerConfig.
    rotary``): rotate-half at base ``theta`` on the first ``fraction`` of a
    head's columns; under ``yarn`` = ``(factor, original positions,
    beta_fast, beta_slow)`` the frequencies are YaRN's (arXiv:2309.00071, as
    ``transformers``' ``_compute_yarn_parameters`` writes it:
    :meth:`inv_freq`) and cos and sin are scaled by ``attention_factor``."""
    theta: float
    fraction: float = 1.0
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: float = 1.0

    #: the published ``rope_parameters`` keys a spec may carry
    KEYS = ("rope_theta", "partial_rotary_factor", "rope_type", "factor",
            "original_max_position_embeddings", "beta_fast", "beta_slow",
            "attention_factor")

    @classmethod
    def of(cls, spec) -> Optional["Rotary"]:
        """A :class:`Rotary` from one entry of ``kind_rope``: None (no
        positions), a :class:`Rotary`, or the published keys."""
        if spec is None or isinstance(spec, cls):
            return spec
        spec = dict(spec)
        kind = spec.get("rope_type", "default")
        unknown = sorted(set(spec) - set(cls.KEYS))
        if unknown or kind not in ("default", "yarn") or (
                kind == "default" and set(spec) - set(cls.KEYS[:3])):
            raise ValueError(
                f"kind_rope: rope_type 'default' (rope_theta, "
                f"partial_rotary_factor) or 'yarn' (also {cls.KEYS[3:]}); "
                f"got {spec}")
        fraction = float(spec.get("partial_rotary_factor", 1.0))
        if kind == "default":
            return cls(float(spec["rope_theta"]), fraction)
        factor = float(spec["factor"])
        return cls(float(spec["rope_theta"]), fraction,
                   (factor, int(spec["original_max_position_embeddings"]),
                    float(spec.get("beta_fast", 32.0)),
                    float(spec.get("beta_slow", 1.0))),
                   float(spec.get("attention_factor",
                                  0.1 * np.log(factor) + 1.0)))

    def width(self, head_dim: int) -> int:
        """The columns of a ``head_dim``-wide head that turn."""
        return int(self.fraction * head_dim)

    def check(self, head_dim: int, kind: str) -> None:
        rot = self.fraction * head_dim
        if not 0.0 < self.fraction <= 1.0 or rot != int(rot) or int(rot) % 2 \
                or self.theta <= 0 or (self.yarn is not None and (
                    self.yarn[0] < 1.0 or self.yarn[1] < 1
                    or not self.yarn[2] > self.yarn[3] > 0)):
            raise ValueError(
                f"kind_rope[{kind!r}] = {self}: a positive base, a whole "
                f"even number of a {head_dim}-wide head's columns, and under "
                "YaRN factor >= 1, original positions >= 1 and beta_fast > "
                "beta_slow > 0")

    def inv_freq(self, dim: int):
        """The ``dim / 2`` frequencies (float32), or None for the plain
        ``theta ** (-2 i / dim)`` that :func:`rope` forms itself. YaRN:
        pair ``i`` keeps its frequency where it turns more than
        ``beta_fast`` times over the original positions, is slowed by
        ``factor`` where it turns fewer than ``beta_slow`` times, and a
        linear ramp over the pairs lies between."""
        if self.yarn is None:
            return None
        factor, original, fast, slow = self.yarn
        f32 = jnp.float32
        at = lambda turns: dim * np.log(original / (turns * 2 * np.pi)) / (
            2 * np.log(self.theta))
        low = max(int(np.floor(at(fast))), 0)
        high = min(int(np.ceil(at(slow))), dim - 1)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=f32) - low)
                        / ((high - low) or 1e-3), 0.0, 1.0)
        plain = f32(self.theta) ** (-jnp.arange(0, dim, 2, dtype=f32) / dim)
        return (1.0 - ramp) * plain + ramp * plain / f32(factor)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 256
    dtype: Any = jnp.float32        # activation dtype (bf16 on hardware)
    attn: str = "auto"              # "auto" | "flash" | "blockwise"
    sp_attn: str = "ring"           # sequence-parallel tier: "ring" | "a2a"
    remat: bool = False             # rematerialize each layer's activations
                                    # on the backward pass (HBM for FLOPs)
    # Mixture-of-Experts FFN (Switch-style, models/moe.py): 0 = dense.
    # Every ``moe_every``-th block swaps its FFN for a top-1-routed expert
    # bank; the Switch aux load-balance loss joins the CE at
    # ``moe_aux_weight``.
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.5
    moe_aux_weight: float = 0.01
    # -- the block's architecture (a model's published shape, not knobs):
    # the defaults are the GPT-2-era block above, bit for bit ------------
    pos: str = "learned"            # "learned" table | "rope" (rotate-half
    rope_theta: float = 10000.0     # rotary on q and k, no table) | "none"
                                    # (beside KDA blocks only, see below)
    qk_norm: bool = False           # RMSNorm over the whole d_model-wide q
                                    # and k before the head split (OLMoE)
    ffn: str = "gelu"               # "gelu" MLP | "swiglu" (gated SiLU)
    tie_embeddings: bool = True     # False: a separate [d, vocab] "head"
    norm_eps: float = 1e-6
    # Dropless top-k experts (models/moe.py moe_ffn_dropless): softmax over
    # ``moe_experts``, the top ``moe_top_k`` per token, no capacity, no
    # drops; 0 keeps the Switch top-1 capacity path. This device holds
    # experts 0 .. moe_experts_held-1 (None: all) — its share of an
    # expert-parallel deployment; the router keeps its full width. The
    # load-balance loss joins at ``moe_aux_weight``, the router z-loss at
    # ``moe_z_weight``.
    moe_top_k: int = 0
    moe_experts_held: Optional[int] = None
    moe_z_weight: float = 0.0
    # DeepSeek-V3's block (Moonlight's): latent attention — q heads of
    # [qk_nope | qk_rope], keys and values decompressed from ONE
    # ``kv_lora_rank``-wide normed latent a token plus ONE rotary key every
    # head shares, values ``v_head_dim`` wide (``d_model // n_heads`` plays
    # no part); ``moe_first_dense`` leading dense layers of width
    # ``dense_d_ff`` (``d_ff`` stays one expert's width); the router scores
    # by sigmoid, selects with a gradient-free bias, renormalises the
    # chosen scores and scales them (models/moe.py); ``moe_shared_experts``
    # experts' width of MLP on every token; the sequence-wise balance loss
    # joins at ``moe_aux_weight`` in place of the batch-wise one.
    attn_kind: str = "mha"          # "mha" | "mla"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_first_dense: int = 0
    dense_d_ff: int = 0
    moe_shared_experts: int = 0
    moe_score: str = "softmax"      # "softmax" | "sigmoid"
    moe_norm_topk: bool = False
    moe_routed_scale: float = 1.0
    moe_seq_aux: bool = False
    # Kimi Linear's hybrid (arXiv:2510.26692): the blocks listed in
    # ``linear_layers`` (0-based indices) mix tokens by gated delta-rule
    # linear attention with a per-channel decay (KDA, ops/kda.py) over
    # ``linear_heads`` heads of ``linear_head_dim`` (q, k and v alike), each
    # of q, k, v through a depthwise causal convolution of ``short_conv``
    # taps and a SiLU; every other block keeps ``attn_kind``'s softmax
    # attention. ``pos="none"``: no table and no rotary anywhere — the
    # convolutions and the decay carry the order.
    linear_layers: Tuple[int, ...] = ()
    linear_heads: int = 0
    linear_head_dim: int = 0
    short_conv: int = 0
    # SmallThinker's block (arXiv:2507.20984), ``attn_kind="mha"`` only:
    # ``n_kv_heads`` key/value heads serve the ``n_heads`` query heads in
    # groups (0: one each); heads are ``mha_head_dim`` wide whatever
    # ``d_model / n_heads`` is (0: that quotient); the blocks listed in
    # ``window_layers`` see the last ``window`` keys (the key itself
    # included) and carry the rotary positions, every other block sees the
    # whole causal past and carries NO positions (``pos="rope"`` then means
    # "in the windowed blocks"); ``moe_route_block_input``: the router of a
    # block's expert layer reads the block's INPUT, un-normed, not the
    # normed rows the experts are given; ``moe_act``: the experts' gate
    # activation, ``"silu"`` | ``"relu"``.
    n_kv_heads: int = 0
    mha_head_dim: int = 0
    window: int = 0
    window_layers: Tuple[int, ...] = ()
    moe_route_block_input: bool = False
    moe_act: str = "silu"
    # Nemotron-H's hybrid (``nemotron_h``; its Mamba-2 layers:
    # arXiv:2405.21060): ``layer_pattern`` has one letter a layer, and a
    # layer is ONE pre-norm sublayer — ``x + f(norm(x))`` — where every other
    # model's block is a mixer AND a feed-forward part: ``"M"`` a Mamba-2
    # state-space mixer (ops/ssd.py) of ``ssd_heads`` heads of
    # ``ssd_head_dim``, their ``ssd_groups`` groups of heads sharing one B
    # and one C of ``ssd_state``, a ``short_conv``-tap depthwise causal
    # convolution with a bias, scanned in chunks of ``ssd_chunk``; ``"*"``
    # softmax attention over the whole causal past (``attn_kind="mha"``,
    # grouped heads as above); ``"E"`` a dropless expert layer, which may be
    # ``moe_gated`` False (``down(act(up x))``, ``moe_act="relu2"``: the
    # squared ReLU), work in a ``moe_latent``-wide latent (every token
    # projected down before the dispatch and up after the combine, the
    # router and the shared MLP on the full-width rows) and hold
    # ``moe_shared_d_ff`` columns of its shared MLP. ``pos="none"``: the
    # family turns nothing. ``moe_every`` / ``moe_first_dense`` play no
    # part: the pattern says which layers route.
    layer_pattern: str = ""
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_groups: int = 0
    ssd_state: int = 0
    ssd_chunk: int = 0
    moe_gated: bool = True
    moe_latent: int = 0
    moe_shared_d_ff: int = 0
    # ZAYA1's block (arXiv:2511.17127; its attention is CCA,
    # arXiv:2510.04476), ``attn_kind="mha"`` with grouped heads: ``cca``:
    # attention runs INSIDE the q/k latent — ``wqkv``'s q and k columns pass
    # a depthwise causal convolution and then one ``head_dim x head_dim``
    # causal convolution a head (``CCA_TAPS`` taps each, with biases), the
    # mean of the unconvolved q and k is added back, each head is L2-normed
    # to ``sqrt(head_dim)`` with a learned temperature a key head, the LAST
    # half of the key/value heads read the previous position's value, and
    # ``wo`` projects from ``n_heads x head_dim``; ``rope_fraction``: rotary
    # turns the first that share of a head's columns, the rest pass;
    # ``moe_router_hidden`` > 0: the router is an MLP that wide (models/moe.py
    # ``_mlp_logits``: a down-projection, the router of the block before
    # added in under a learned scale, an RMSNorm, three matrices under
    # GELU, a selection bias outside the gradient, the softmax's own
    # probability as the weight) whose pre-norm rows pass from block to
    # block beside the residual stream; ``moe_null_expert``: that softmax has
    # one more output, "no expert", and a slot that chose it adds nothing;
    # ``merge_scaled``: a block's two residual adds are ``a_x (x + b_x) +
    # a_y (y + b_y)`` with four learned ``d_model``-wide vectors each.
    cca: bool = False
    rope_fraction: float = 1.0
    moe_router_hidden: int = 0
    moe_null_expert: bool = False
    merge_scaled: bool = False
    # SDAR's block and step (arXiv:2510.06303; the objective is block
    # diffusion, BD3-LMs, arXiv:2503.09573), ``attn_kind="mha"`` under
    # ``pos="rope"``: ``head_norm``: an RMSNorm over each HEAD's ``head_dim``
    # columns of q and of k (one weight vector each, shared by the heads)
    # after the head split and before the rotary — the Qwen3 block's, beside
    # grouped heads where the whole-width ``qk_norm`` cannot stand;
    # ``objective="block_diffusion"``: a step runs a CLEAN and a NOISED copy
    # of every sequence (``mask_token``, a row of the held vocabulary, where
    # the batch says "masked") through every layer, as rows ``n`` and ``N +
    # n`` of one ``[2N, L]`` batch; with positions in blocks of
    # ``diffusion_block`` a clean row sees the clean keys of its own and
    # earlier blocks, a noisy row the clean keys of EARLIER blocks and the
    # noisy keys of its own block (both directions); the readout takes the
    # noisy rows, and the loss is the cross-entropy at the masked positions
    # (no shift), each weighted by one over its block's masking rate, over
    # ``N L`` (``loss_and_metrics``).
    head_norm: bool = False
    objective: str = "next_token"   # "next_token" | "block_diffusion"
    diffusion_block: int = 0
    mask_token: int = -1
    # Laguna's block (``model_type`` "laguna": poolside's Laguna-S-2.1),
    # ``attn_kind="mha"`` with ``window_layers``: its two kinds of softmax
    # block (``layer_kinds()``'s ``"full"`` / ``"swa"``) differ in more than
    # the mask. ``kind_heads``: the QUERY heads of a kind, ``{"full": 48,
    # "swa": 72}`` (``num_attention_heads_per_layer``; a kind left out has
    # ``n_heads``) — ``wqkv``'s q columns, ``wo``'s rows and the queries a
    # K/V head serves are the kind's, the ``n_kv_heads`` K/V heads and the
    # head width the model's. ``kind_rope``: the rotary of EACH kind under
    # the published ``rope_parameters``' own keys, ``{"full": {"rope_theta":
    # 500000, "partial_rotary_factor": 0.5, "rope_type": "yarn", "factor":
    # 128, "original_max_position_embeddings": 8192, "beta_fast": 32,
    # "beta_slow": 1, "attention_factor": 1.485...}, "swa": {"rope_theta":
    # 10000}}`` (:class:`Rotary`; ``None`` for a kind: it carries no
    # positions); empty, ``window_layers`` reads as SmallThinker's — the
    # windowed blocks turn by ``rope_theta`` / ``rope_fraction``, the full
    # ones carry no positions. ``attn_gate="head"``: each head's attention
    # output times ``sigmoid`` of one scalar a head and position, a
    # projection ``wgate [heads, d]`` of the block's normed input, before
    # ``wo`` (headwise gating at the attention output, arXiv:2505.06708).
    kind_heads: Any = ()
    kind_rope: Any = ()
    attn_gate: str = "none"         # "none" | "head" | "element" (below)
    # Ouro's block and step (``model_type`` "ouro": ByteDance's Ouro-2.6B,
    # the looped language model of arXiv:2510.25741), ``attn_kind="mha"``
    # under ``pos="rope"``, dense: ``loop_steps`` (``total_ut_steps``): the
    # whole stack of layers runs that many times over ONE set of weights —
    # pass ``t + 1`` starts from pass ``t``'s rows AFTER the final norm,
    # which therefore closes every pass, and a weight's gradient is the sum
    # over its uses. ``sandwich_norm``: a block norms each sublayer's OUTPUT
    # as well as its input, ``x + norm(f(norm(x)))``, four norms a block
    # (``ln1_post`` / ``ln2_post`` beside ``ln1`` / ``ln2``).
    # ``exit_gate``: after every pass an exit — the pass's normed rows
    # through the ONE readout, and ``lam = sigmoid(rows . exit_w + exit_b)``,
    # one scalar a position; the exit distribution ``p(t) = lam(t) prod_{s <
    # t} (1 - lam(s))``, the last pass taking what is left, joins the passes'
    # cross-entropies into one loss, ``mean_n [sum_t p(t) nll(t) -
    # exit_entropy_weight H(p)]`` (the paper's entropy-regularised expected
    # loss over the exit step; ``loss_and_metrics``). Without the gate a
    # looped model's loss is the last pass's alone.
    loop_steps: int = 1
    sandwich_norm: bool = False
    exit_gate: bool = False
    exit_entropy_weight: float = 0.0
    # Qwen3-Next's block (``model_type`` "qwen3_next": Qwen3-Next-80B-A3B),
    # ``attn_kind="mha"`` under ``pos="rope"`` beside ``linear_layers``:
    # ``linear_kind="gdn"``: the blocks in ``linear_layers`` mix by Gated
    # DeltaNet (arXiv:2412.06464; ops/kda.py ``gdn_attention``) — ONE scalar
    # decay a head and position where KDA has one a channel, ``linear_heads``
    # KEY heads serving ``linear_value_heads`` value heads in groups (0: one
    # each; value head ``j`` reads key head ``j // group``), ONE input
    # projection ``w_qkvz`` and ONE ``short_conv``-tap convolution over its
    # ``q | k | v`` columns, the decay's and beta's scalars from one
    # ``w_ba``, and the output gate ``silu(z)`` AFTER the per-head norm;
    # rotary turns the softmax blocks only, the delta-rule blocks carry no
    # positions. ``attn_gate="element"``: ``wqkv``'s q block is ``2 x heads
    # x head_dim`` wide — a head's query columns, then its gate's — and the
    # head's attention output is multiplied element by element by
    # ``sigmoid`` of those (``head_norm`` and the rotary act on the query
    # half only). ``norm_offset``: the weight of every norm of the MODEL's
    # kind (``ln1``, ``ln2``, ``ln_f``, ``q_head_norm``, ``k_head_norm``)
    # enters as ``1 + w`` and starts at 0 (``norm_weight``); a delta-rule
    # block's output norm keeps the plain weight. ``moe_shared_gate``: an
    # expert layer's shared MLP times ``sigmoid(x . shared_gate)``, one
    # scalar a token (models/moe.py).
    linear_kind: str = "kda"        # "kda" | "gdn"
    linear_value_heads: int = 0
    norm_offset: bool = False
    moe_shared_gate: bool = False
    # Standard deviation the embedding rows are drawn with. GPT-2's 0.02
    # leaves a row at a fiftieth of what a block's fan-in projections add to
    # it, which nothing here divides by depth: attention's average over the
    # keys then IS the residual stream two blocks in, every token hands the
    # routers one direction, and a later block's router sends nearly all of
    # them to the same few experts (tests/test_smallthinker.py; PERF.md
    # section 6, PR 36). 1.0 is the fan-in rule read for a one-hot input,
    # and keeps the rows apart.
    embed_std: float = 0.02

    def __post_init__(self):
        from harmony_tpu.models.common import validate_attn

        if not self.mha_head_dim and self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")
        if self.sp_attn not in ("ring", "a2a"):
            raise ValueError(f"unknown sp_attn {self.sp_attn!r}")
        if self.moe_experts and self.moe_every < 1:
            raise ValueError("moe_every must be >= 1")
        # a job's app_params arrive as JSON: a list, held as a tuple
        object.__setattr__(self, "linear_layers",
                           tuple(int(i) for i in self.linear_layers))
        if self.pos not in ("learned", "rope", "none"):
            raise ValueError(f"unknown pos {self.pos!r}: 'learned', 'rope' "
                             "or 'none'")
        kda = (self.linear_heads, self.linear_head_dim, self.short_conv)
        if self.linear_layers and (
                min(kda) < 1 or sorted(set(self.linear_layers))
                != list(self.linear_layers)
                or not 0 <= self.linear_layers[0]
                or self.linear_layers[-1] >= self.n_layers):
            raise ValueError(
                "linear_layers lists blocks 0..n_layers-1 in order, once "
                "each, and needs linear_heads, linear_head_dim and "
                f"short_conv (got {self.linear_layers}, {kda})")
        ssd = (self.ssd_heads, self.ssd_head_dim, self.ssd_groups,
               self.ssd_state, self.ssd_chunk)
        scanned = "M" in self.layer_pattern
        if not self.linear_layers and (any(kda[:2]) or (
                self.short_conv and not scanned)):
            raise ValueError("linear_heads / linear_head_dim / short_conv "
                             "belong to KDA blocks: set linear_layers")
        if self.layer_pattern and (
                set(self.layer_pattern) - set("ME*")
                or len(self.layer_pattern) != self.n_layers
                or self.linear_layers or self.window_layers
                or self.attn_kind != "mha" or self.moe_first_dense
                or self.pos == "learned" or self.moe_route_block_input
                or ("E" in self.layer_pattern) != bool(self.moe_top_k)):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: one letter a layer "
                f"(n_layers {self.n_layers}), 'M' a Mamba-2 mixer, '*' "
                "softmax attention, 'E' dropless experts (moe_top_k, set if "
                "and only if an 'E' stands in it); no KDA, windowed, latent-"
                "attention or leading dense layers, no learned positions and "
                "no router on a block's input beside it")
        if scanned and (min(ssd) < 1 or self.short_conv < 1
                        or self.ssd_heads % self.ssd_groups):
            raise ValueError(
                "an 'M' layer needs ssd_heads, ssd_head_dim, ssd_groups, "
                "ssd_state, ssd_chunk and short_conv, and ssd_groups must "
                f"divide ssd_heads (got {ssd}, short_conv {self.short_conv})")
        if any(ssd) and not scanned:
            raise ValueError("ssd_heads / ssd_head_dim / ssd_groups / "
                             "ssd_state / ssd_chunk belong to the 'M' layers "
                             "of a layer_pattern")
        if (not self.moe_gated or self.moe_latent) \
                and "E" not in self.layer_pattern:
            raise ValueError("moe_gated / moe_latent belong to the 'E' "
                             "layers of a layer_pattern")
        if self.moe_shared_d_ff and "E" not in self.layer_pattern and (
                self.attn_kind != "mha" or not self.moe_top_k):
            raise ValueError(
                "moe_shared_d_ff is the held width of a dropless expert "
                "layer's shared MLP: the 'E' layers of a layer_pattern, or "
                "the expert blocks of an attn_kind='mha' model (moe_top_k)")
        if self.moe_shared_d_ff and self.moe_shared_experts != 1:
            raise ValueError("moe_shared_d_ff is the held width of ONE "
                             "shared MLP: set moe_shared_experts=1")
        object.__setattr__(self, "window_layers",
                           tuple(int(i) for i in self.window_layers))
        grouped = (self.n_kv_heads, self.mha_head_dim, self.window,
                   self.window_layers)
        if any(grouped) and self.attn_kind != "mha":
            raise ValueError("n_kv_heads / mha_head_dim / window / "
                             "window_layers belong to attn_kind='mha'")
        if self.n_kv_heads and (self.n_heads % self.n_kv_heads
                                or self.qk_norm):
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} must divide n_heads "
                f"{self.n_heads}, and qk_norm (a d_model-wide norm of q and "
                "k) needs as many key heads as query heads")
        if bool(self.window) != bool(self.window_layers) or self.window < 0 \
                or (self.window_layers and (
                    self.pos != "rope" or set(self.window_layers)
                    & set(self.linear_layers)
                    or sorted(set(self.window_layers))
                    != list(self.window_layers)
                    or not 0 <= self.window_layers[0]
                    or self.window_layers[-1] >= self.n_layers)):
            raise ValueError(
                "window_layers lists the windowed blocks 0..n_layers-1 in "
                "order, once each, none of them a KDA block, and needs "
                "window >= 1 and pos='rope' (rotary in those blocks, no "
                f"positions in the others); got {self.window_layers}, "
                f"window {self.window}, pos {self.pos!r}")
        if self.moe_act not in ("silu", "relu", "relu2"):
            raise ValueError(f"unknown moe_act {self.moe_act!r}: 'silu', "
                             "'relu' or 'relu2'")
        if self.moe_gated and self.moe_act == "relu2":
            raise ValueError("moe_act='relu2' is the activation of experts "
                             "that are not gated: set moe_gated=False")
        if not self.moe_top_k and (self.moe_route_block_input
                                   or self.moe_act != "silu"):
            raise ValueError("moe_route_block_input / moe_act belong to "
                             "dropless routing: set moe_top_k")
        if self.pos == "none" and not (self.linear_layers or scanned):
            raise ValueError(
                "pos='none' runs only beside KDA blocks (linear_layers) or "
                "state-space layers ('M' in layer_pattern): "
                "their convolutions and decay carry the order, and a latent "
                "(attn_kind='mla') block among them may then go without "
                "rotary; a model of softmax blocks alone cannot tell "
                "positions apart")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn {self.ffn!r}: 'gelu' or 'swiglu'")
        mla = (self.kv_lora_rank, self.qk_nope_head_dim,
               self.qk_rope_head_dim, self.v_head_dim)
        if self.attn_kind not in ("mha", "mla"):
            raise ValueError(f"unknown attn_kind {self.attn_kind!r}: 'mha' "
                             "or 'mla'")
        if self.attn_kind == "mha" and any(mla):
            raise ValueError("kv_lora_rank / qk_nope_head_dim / "
                             "qk_rope_head_dim / v_head_dim belong to latent "
                             "attention: set attn_kind='mla'")
        if self.attn_kind == "mla" and (
                min(mla) < 1 or self.pos == "learned" or self.qk_norm):
            raise ValueError(
                "attn_kind='mla' needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim, pos='rope' (rotary on the "
                "qk_rope parts; 'none' beside KDA blocks: the parts are "
                "carried and never turned) and no qk_norm (the latent has "
                "its own norm)")
        turned = (self.qk_rope_head_dim if self.attn_kind == "mla"
                  else self.head_dim)  # the width rotary positions turn
        if self.pos == "rope" and turned % 2:
            raise ValueError(f"rotary positions rotate pairs: head width "
                             f"{turned} is odd")
        if not 0 <= self.moe_top_k <= self.moe_experts:
            raise ValueError(f"moe_top_k {self.moe_top_k} must lie in 0.."
                             f"moe_experts ({self.moe_experts})")
        if self.moe_top_k and self.moe_gated and self.ffn != "swiglu":
            raise ValueError("dropless experts (moe_top_k > 0) are gated-SiLU:"
                             " set ffn='swiglu'")
        if not self.moe_top_k and (self.moe_experts_held is not None
                                   or self.moe_z_weight):
            raise ValueError("moe_experts_held / moe_z_weight belong to "
                             "dropless routing: set moe_top_k")
        if self.moe_experts_held is not None and not (
                1 <= self.moe_experts_held <= self.moe_experts):
            raise ValueError(f"moe_experts_held {self.moe_experts_held} must "
                             f"lie in 1..moe_experts ({self.moe_experts})")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_score {self.moe_score!r}: "
                             "'softmax' or 'sigmoid'")
        if not self.moe_top_k and (
                self.moe_shared_experts or self.moe_score != "softmax"
                or self.moe_norm_topk or self.moe_routed_scale != 1.0
                or self.moe_seq_aux):
            raise ValueError(
                "moe_shared_experts / moe_score / moe_norm_topk / "
                "moe_routed_scale / moe_seq_aux belong to dropless routing: "
                "set moe_top_k")
        if self.moe_score == "sigmoid" and not self.moe_seq_aux:
            raise ValueError("moe_score='sigmoid' is DeepSeek-V3's router: "
                             "its balance loss is the sequence-wise one "
                             "(moe_seq_aux)")
        if self.moe_seq_aux and self.moe_z_weight:
            raise ValueError("moe_seq_aux replaces the batch-wise balance "
                             "loss and has no router z-loss: moe_z_weight "
                             "must be 0")
        if not 0 <= self.moe_first_dense <= self.n_layers or (
                self.moe_first_dense and not self.moe_experts):
            raise ValueError(
                f"moe_first_dense {self.moe_first_dense}: leading dense "
                f"layers of an expert model, 0..n_layers ({self.n_layers})")
        if self.dense_d_ff and not self.moe_first_dense:
            raise ValueError("dense_d_ff is the width of the moe_first_dense "
                             "leading layers: set moe_first_dense")
        if self.cca and (self.attn_kind != "mha" or self.linear_layers
                         or self.layer_pattern or self.window_layers
                         or self.qk_norm):
            raise ValueError(
                "cca convolves and L2-norms the q and k columns of an "
                "attn_kind='mha' block's wqkv: a latent block (mla) has no "
                "such columns, KDA / layer_pattern layers have mixers of "
                "their own, qk_norm would norm the same heads twice, and a "
                "windowed CCA block turns with a theta of its own that no "
                "field carries (window_layers)")
        if self.cca and self.kv_heads % 2:
            raise ValueError(
                f"cca: the last half of the {self.kv_heads} key/value heads "
                "reads the previous position's value, so they must be even")
        rot = self.rope_fraction * self.head_dim
        if not 0.0 < self.rope_fraction <= 1.0 or rot != int(rot) \
                or int(rot) % 2:
            raise ValueError(
                f"rope_fraction {self.rope_fraction} of a {self.head_dim}-"
                "wide head must be a whole, even number of columns in "
                "(0, head]: rotary turns pairs")
        if self.rope_fraction != 1.0 and (self.attn_kind != "mha"
                                          or self.pos != "rope"):
            raise ValueError(
                "rope_fraction belongs to attn_kind='mha' under pos='rope': "
                "a latent block's turned width is qk_rope_head_dim")
        if self.moe_router_hidden and (
                not self.moe_top_k or self.moe_score != "softmax"
                or self.layer_pattern or self.moe_seq_aux):
            raise ValueError(
                "moe_router_hidden is the width of a dropless router's MLP "
                "(set moe_top_k), a softmax router (its selection bias is "
                "its own, not moe_score='sigmoid''s, and it has no "
                "sequence-wise balance term), and its state passes from "
                "block to block: a layer_pattern layer hands nothing on")
        if self.moe_null_expert and not self.moe_router_hidden:
            raise ValueError(
                "moe_null_expert is the MLP router's last output (set "
                "moe_router_hidden): the one-matrix routers have a column "
                "an expert and no more")
        if self.merge_scaled and self.layer_pattern:
            raise ValueError(
                "merge_scaled names the two residual merges of a block; a "
                "layer_pattern layer is one sublayer under one norm")
        if self.head_norm and (self.qk_norm or self.cca
                               or self.attn_kind != "mha"
                               or (self.linear_layers
                                   and self.linear_kind != "gdn")
                               or self.layer_pattern):
            raise ValueError(
                "head_norm norms each head's columns of an attn_kind='mha' "
                "block's q and k: qk_norm would norm the same columns over "
                "the whole width, cca L2-norms them itself, a latent block "
                "(mla) norms its latent, and KDA / layer_pattern layers "
                "carry no such leaves")
        if self.objective not in ("next_token", "block_diffusion"):
            raise ValueError(f"unknown objective {self.objective!r}: "
                             "'next_token' or 'block_diffusion'")
        diffusion = self.objective == "block_diffusion"
        if not diffusion and (self.diffusion_block or self.mask_token != -1):
            raise ValueError("diffusion_block / mask_token belong to "
                             "objective='block_diffusion'")
        if diffusion and (
                self.diffusion_block < 1
                or self.max_seq % self.diffusion_block
                or not 0 <= self.mask_token < self.vocab_size
                or self.pos != "rope" or self.attn_kind != "mha"
                or self.window_layers or self.linear_layers
                or self.layer_pattern or self.cca
                or self.moe_seq_aux or self.moe_null_expert
                or (self.moe_experts and not self.moe_top_k)):
            raise ValueError(
                "objective='block_diffusion' needs diffusion_block >= 1 "
                f"dividing max_seq ({self.max_seq}), a mask_token among the "
                f"{self.vocab_size} held rows, pos='rope' (each stream's "
                "positions 0..L-1; a learned table would be read twice) and "
                "attn_kind='mha' blocks without a window: the mask by block "
                "and stream is written for softmax attention over the whole "
                "past, not for windowed, latent (mla), CCA, KDA or "
                "state-space layers, and its loss adds the dropless experts' "
                "balance and z terms over both streams, not a per-sequence "
                "balance, a 'no expert' count or Switch experts' "
                f"(got block {self.diffusion_block}, "
                f"mask_token {self.mask_token}, pos {self.pos!r}, "
                f"attn_kind {self.attn_kind!r})")
        self._check_kinds()
        self._check_loop()
        self._check_gdn()
        validate_attn(self.attn)

    def _check_gdn(self) -> None:
        """``linear_kind`` / ``linear_value_heads`` / ``norm_offset`` /
        ``moe_shared_gate``: Qwen3-Next's, beside ``attn_kind="mha"`` blocks
        whose whole causal past one kind of softmax block sees."""
        if self.linear_kind not in ("kda", "gdn"):
            raise ValueError(f"unknown linear_kind {self.linear_kind!r}: "
                             "'kda' or 'gdn'")
        gdn = self.linear_kind == "gdn"
        if (gdn or self.linear_value_heads) and not (
                gdn and self.linear_layers
                and self.linear_value_heads >= 0
                and self.linear_value_heads % self.linear_heads == 0):
            raise ValueError(
                "linear_kind='gdn' names the mixer of the blocks in "
                "linear_layers, and linear_value_heads its value heads: "
                "whole groups of the linear_heads key heads (got "
                f"linear_layers {self.linear_layers}, linear_kind "
                f"{self.linear_kind!r}, heads {self.linear_heads} / "
                f"{self.linear_value_heads})")
        if gdn and (self.attn_kind != "mha" or self.window_layers
                    or self.pos == "learned"):
            raise ValueError(
                "linear_kind='gdn' runs beside attn_kind='mha' blocks that "
                "see the whole causal past, under pos='rope' (rotary in "
                "the softmax blocks only) or 'none': not beside latent "
                "(mla) or windowed blocks or a learned position table")
        if self.norm_offset and (
                self.qk_norm or self.attn_kind != "mha" or self.cca
                or self.layer_pattern or self.sandwich_norm
                or self.moe_router_hidden):
            raise ValueError(
                "norm_offset reads ln1 / ln2 / ln_f and the head norms as "
                "1 + w: a d_model-wide qk_norm, a latent's (mla), an MLP "
                "router's, a layer_pattern layer's and the ln*_post norms "
                "have no such form, and cca norms nothing by weight")
        if self.moe_shared_gate and not (self.moe_top_k
                                         and self.moe_shared_experts):
            raise ValueError("moe_shared_gate gates a dropless expert "
                             "layer's shared MLP: set moe_top_k and "
                             "moe_shared_experts")

    def _check_loop(self) -> None:
        """``loop_steps`` / ``sandwich_norm`` / ``exit_gate`` /
        ``exit_entropy_weight``: Ouro's, beside dense ``mha`` blocks under
        rotary positions and nothing else."""
        if self.loop_steps < 1 or self.exit_entropy_weight < 0:
            raise ValueError(
                f"loop_steps {self.loop_steps} counts the passes (>= 1) and "
                f"exit_entropy_weight {self.exit_entropy_weight} weighs an "
                "entropy (>= 0)")
        if self.exit_gate and self.loop_steps < 2:
            raise ValueError("exit_gate joins the exits of several passes: "
                             "set loop_steps > 1")
        if self.exit_entropy_weight and not self.exit_gate:
            raise ValueError("exit_entropy_weight weighs the entropy of the "
                             "gate's exit distribution: set exit_gate")
        if (self.loop_steps > 1 or self.sandwich_norm) and (
                self.attn_kind != "mha" or self.pos != "rope"
                or self.moe_experts or self.layer_pattern
                or self.linear_layers or self.window_layers or self.cca
                or self.objective != "next_token"):
            raise ValueError(
                "loop_steps > 1 / sandwich_norm run dense attn_kind='mha' "
                "blocks under pos='rope' and the next-token objective: a "
                "learned position table would be added once for all passes, "
                "an expert layer's balance loss and counts have no rule for "
                "a layer that routes several times a step, the recurrent "
                "(KDA, state-space) mixers' statistics are one a layer, the "
                "two kinds of a windowed model and CCA's blocks carry no "
                "ln*_post leaves, and block diffusion's two streams have no "
                "exit")

    def _check_kinds(self) -> None:
        """``kind_heads`` / ``kind_rope`` / ``attn_gate``: held as tuples of
        pairs (a job's ``app_params`` arrive as JSON objects), checked
        against the two kinds a model with ``window_layers`` has."""
        heads = tuple(sorted((str(k), int(v))
                             for k, v in dict(self.kind_heads).items()))
        ropes = tuple(sorted((str(k), Rotary.of(v))
                             for k, v in dict(self.kind_rope).items()))
        object.__setattr__(self, "kind_heads", heads)
        object.__setattr__(self, "kind_rope", ropes)
        if self.attn_gate not in ("none", "head", "element"):
            raise ValueError(f"unknown attn_gate {self.attn_gate!r}: 'none', "
                             "'head' or 'element'")
        if (heads or ropes) and (not self.window_layers or self.cca):
            raise ValueError(
                "kind_heads / kind_rope tell the 'full' and 'swa' blocks of "
                "a model with window_layers apart (attn_kind='mha', no cca): "
                "a model of one kind of softmax block says n_heads, "
                "rope_theta and rope_fraction")
        if self.attn_gate != "none" and (
                self.attn_kind != "mha" or self.cca
                or (self.linear_layers and self.linear_kind != "gdn")
                or self.objective != "next_token"
                or (self.attn_gate == "element" and self.window_layers)):
            raise ValueError(
                "attn_gate='head' gates the heads of an attn_kind='mha' "
                "block's softmax attention under the next-token objective: "
                "not beside latent (mla) or CCA attention, KDA blocks (their "
                "mixer has its own output gate) or the two streams of "
                "block diffusion; 'element' (a gate as wide as the head, out "
                "of wqkv's q block) not in a model of two kinds of softmax "
                "block (window_layers)")
        kinds = {"full", "swa"}
        if set(dict(heads)) - kinds or (ropes and set(dict(ropes)) != kinds):
            raise ValueError(
                "kind_heads names 'full' and / or 'swa'; kind_rope BOTH, a "
                f"kind without positions as null (got {sorted(dict(heads))}"
                f", {sorted(dict(ropes))})")
        for kind, h in heads:
            if h < 1 or h % self.kv_heads or self.qk_norm:
                raise ValueError(
                    f"kind_heads[{kind!r}] = {h}: whole groups of the "
                    f"{self.kv_heads} key/value heads (n_kv_heads), and no "
                    "d_model-wide qk_norm")
        if ropes and (self.rope_fraction != 1.0 or self.pos != "rope"):
            raise ValueError("kind_rope says each kind's rotary under "
                             "pos='rope': rope_fraction has no part beside it")
        for kind, r in ropes:
            if r is not None:
                r.check(self.head_dim, kind)

    def heads(self, kind: Optional[str] = None) -> int:
        """The heads a block of ``kind`` (``layer_kinds()``'s) mixes with:
        a softmax block's QUERY heads, a KDA or state-space mixer's own."""
        if kind in ("kda", "ssd"):
            return self.linear_heads if kind == "kda" else self.ssd_heads
        if kind == "gdn":  # its VALUE heads: the states it carries
            return self.linear_value_heads or self.linear_heads
        return dict(self.kind_heads).get(kind, self.n_heads)

    def norm_weight(self, w):
        """A norm's weight as the model's norm multiplies by it: the leaf,
        or under ``norm_offset`` ``1 + w`` (float32, before any cast)."""
        return w + 1.0 if self.norm_offset else w

    def rotary(self, kind: Optional[str] = None) -> Optional["Rotary"]:
        """How a softmax block of ``kind`` turns q and k — the ONE answer
        to "which positions does this block carry": None where it carries
        none (no ``pos="rope"``; a ``"full"`` block of a model with
        ``window_layers`` unless ``kind_rope`` gives it one)."""
        if self.pos != "rope":
            return None
        if self.kind_rope:
            return dict(self.kind_rope)[kind]
        if kind == "full" and self.window_layers:
            return None
        return Rotary(self.rope_theta, self.rope_fraction)

    def is_moe_layer(self, i: int) -> bool:
        """Block i uses the MoE FFN: past the ``moe_first_dense`` leading
        dense layers, the last of every ``moe_every`` group (Switch
        interleaves dense and expert blocks); in a ``layer_pattern`` model
        the layers lettered ``E``."""
        if self.layer_pattern:
            return self.layer_pattern[i] == "E"
        return (bool(self.moe_experts) and i >= self.moe_first_dense
                and i % self.moe_every == self.moe_every - 1)

    def moe_layers(self):
        """The indices of the blocks that are expert layers, in order — the
        ONE answer to "which layers route": the trainer's vectors
        (``moe_expert_tokens [len(moe_layers()), experts]``), the counters'
        ``layer`` label (metrics/moe.py) and the benchmark's work functions
        ask here, so a leading dense layer never shows as idle experts."""
        return tuple(i for i in range(self.n_layers) if self.is_moe_layer(i))

    def layer_kinds(self) -> Tuple[str, ...]:
        """Each block's token mixer, in order — the ONE answer to "which
        kind of layer is this": ``linear_kind`` (``"kda"`` | ``"gdn"``) for
        the blocks in ``linear_layers``, else ``attn_kind`` (``"mha"`` | ``"mla"``) — or,
        in a model with ``window_layers``, ``"swa"`` for those (windowed,
        rotary) and ``"full"`` for its other softmax blocks (whole causal
        past, no positions), and under ``objective="block_diffusion"``
        ``"full"`` for every block (no window: the kind the flash gauges'
        readers count unwindowed kernels by). A ``layer_pattern`` model's layers are one
        sublayer each: ``"ssd"`` (``M``), ``"attn"`` (``*``: attention and
        nothing after it), ``"moe"`` (``E``: experts and no mixer). The
        block, ``init``, the trainer's vectors (``kda_decay_mean [kda
        blocks]``, ``ssd_decay_mean [ssd layers]``), STATUS ``layer_kinds``
        and the benchmark's work functions ask here."""
        if self.layer_pattern:
            return tuple({"M": "ssd", "*": "attn", "E": "moe"}[c]
                         for c in self.layer_pattern)

        def kind(i):
            if i in self.linear_layers:
                return self.linear_kind
            if self.objective == "block_diffusion":
                return "full"  # the whole past of its stream, by block
            if not self.window_layers:
                return self.attn_kind
            return "swa" if i in self.window_layers else "full"
        return tuple(kind(i) for i in range(self.n_layers))

    def ffn_width(self, i: int) -> int:
        """The dense MLP's width in block i (an expert's, in an expert
        layer)."""
        return (self.dense_d_ff or self.d_ff) if i < self.moe_first_dense \
            else self.d_ff

    @property
    def moe_cfg(self):
        from harmony_tpu.models.moe import MoEConfig

        return MoEConfig(num_experts=self.moe_experts, d_model=self.d_model,
                         d_ff=self.d_ff,
                         capacity_factor=self.moe_capacity_factor)

    @property
    def dropless_cfg(self):
        from harmony_tpu.models.moe import DroplessConfig

        held = self.moe_experts_held
        return DroplessConfig(
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            d_model=self.d_model, d_ff=self.d_ff,
            experts_held=self.moe_experts if held is None else held,
            score=self.moe_score, norm_topk=self.moe_norm_topk,
            routed_scale=self.moe_routed_scale,
            shared_experts=self.moe_shared_experts, seq_aux=self.moe_seq_aux,
            act=self.moe_act, gated=self.moe_gated, latent=self.moe_latent,
            shared_d_ff=self.moe_shared_d_ff,
            shared_gate=self.moe_shared_gate,
            router_hidden=self.moe_router_hidden,
            null_expert=self.moe_null_expert, norm_eps=self.norm_eps)

    @property
    def head_dim(self) -> int:
        return self.mha_head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def qkv_widths(self) -> Tuple[int, int, int]:
        """The widths of ``wqkv``'s three column blocks (q, k, v)."""
        return self.qkv_widths_of(None)

    def qkv_widths_of(self, kind: Optional[str]) -> Tuple[int, int, int]:
        """``qkv_widths`` in a block of ``kind`` (``kind_heads``)."""
        hd = self.head_dim
        return self.heads(kind) * hd, self.kv_heads * hd, self.kv_heads * hd

    @property
    def gdn_widths(self) -> Tuple[int, int, int]:
        """A Gated DeltaNet mixer's widths: its key heads' channels ``Hk
        dh`` (q and k each), its value heads' ``Hv dh`` (v and z each), and
        what the convolution runs over (``q | k | v``)."""
        key = self.linear_heads * self.linear_head_dim
        value = self.heads("gdn") * self.linear_head_dim
        return key, value, 2 * key + value

    @property
    def ssd_widths(self) -> Tuple[int, int, int]:
        """An ``M`` layer's widths: its heads' channels ``H P``, what the
        convolution runs over (``x | B | C``: ``H P + 2 G N``) and its input
        projection's columns (``z | x | B | C | dt``)."""
        inner = self.ssd_heads * self.ssd_head_dim
        conv = inner + 2 * self.ssd_groups * self.ssd_state
        return inner, conv, inner + conv + self.ssd_heads

    def require_classic_block(self, who: str) -> None:
        """The side training steps and the decode path below still assume
        the GPT-2-era block (learned positions, GELU, tied readout, Switch
        experts if any): every field outside ``_CLASSIC_FIELDS`` must hold
        its default, so a field added later is refused here as it stands."""
        other = [f.name for f in dataclasses.fields(self)
                 if f.name not in _CLASSIC_FIELDS
                 and getattr(self, f.name) != f.default]
        if other:
            raise ValueError(
                f"{who} runs the GPT-2-era block only (learned positions, "
                "GELU, tied readout, Switch experts) and reads no "
                f"{' / '.join(other)}: such configs train through "
                "TransformerLM.loss and TransformerTrainer")


#: what the GPT-2-era side steps and the decode path read of a
#: ``TransformerConfig``: the sizes, the attention tiers, ``remat``, the
#: Switch experts, the norms' epsilon and the embedding's scale
#: (``rope_theta`` turns nothing under the learned positions they require)
_CLASSIC_FIELDS = frozenset({
    "vocab_size", "d_model", "n_heads", "n_layers", "d_ff", "max_seq",
    "dtype", "attn", "sp_attn", "remat", "moe_experts", "moe_every",
    "moe_capacity_factor", "moe_aux_weight", "norm_eps", "rope_theta",
    "embed_std"})


from harmony_tpu.models.common import rms_norm as _norm  # noqa: E402


def rope(x, theta: float, pos_offset=0, width: Optional[int] = None,
         scaled: Optional[Rotary] = None):
    """Rotate-half rotary positions on ``x [B, H, S, hd]`` (positions
    ``pos_offset .. pos_offset+S-1``): float32 angles, result in x's dtype.
    Since PR 58 this is the REFERENCE ``ops/rotary.py``'s kernel is held to
    and the fallback (``_turned``) wherever that kernel declines: off the
    TPU, heads that are not whole lane tiles, the latent blocks.
    ``width``: only the first that many columns of a head are turned, as a
    head that wide would be (``rope_fraction``); the others pass.
    ``scaled``: a :class:`Rotary` under YaRN — its frequencies in place of
    ``theta``'s, cos and sin times its ``attention_factor``."""
    if width is not None and width != x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :width], theta, pos_offset, scaled=scaled),
             x[..., width:]], axis=-1)
    hd = x.shape[-1]
    if scaled is None:
        inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    else:
        inv_freq = scaled.inv_freq(hd)
    ang = (pos_offset + jnp.arange(x.shape[2], dtype=jnp.float32)
           )[:, None] * inv_freq[None, :]                        # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    if scaled is not None:
        cos, sin = (t * scaled.attention_factor for t in (cos, sin))
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def _rotary_serves(positions, hd, dtype, heads=(None,)) -> bool:
    """Does ``ops.rotary``'s kernel take this trace's turn — a TPU's, and
    a shape its plan serves for every one of ``heads`` (a head count: the
    operand lies ``[B, S, heads hd]``; None: ``[B, heads, S, hd]``)?"""
    from harmony_tpu.ops import rotary
    from harmony_tpu.utils.platform import trace_is_tpu

    return trace_is_tpu() and all(
        rotary.plan(positions, hd, dtype, n) is not None for n in heads)


def _turned(q, k, theta, pos_offset, heads=None, norms=(None, None),
            **part):
    """``rope`` of a softmax block's ``q`` and ``k [B, heads, S, hd]`` —
    where ``_rotary_serves`` by ``ops.rotary``'s kernel, two calls over ONE
    pair of tables. ``heads`` = (query heads, key heads): both lie ``[B, S,
    heads hd]`` and the kernel turns them to heads as it reads (the caller
    has asked ``_rotary_serves``); only then ``norms`` = q's and k's
    ``(weight [hd], eps)``: the kernel norms each head before it turns it."""
    from harmony_tpu.ops import rotary

    if heads is None:
        S, hd = q.shape[2], q.shape[3]
        if not _rotary_serves(S, hd, q.dtype):
            return (rope(q, theta, pos_offset, **part),
                    rope(k, theta, pos_offset, **part))
        heads = (None, None)
    else:
        S, hd = q.shape[1], q.shape[2] // heads[0]
    tab, shifts = rotary.tables(S, hd, theta, pos_offset, **part)
    return tuple(rotary.turn(t, tab, shifts, heads=n, norm=m)
                 for t, n, m in zip((q, k), heads, norms))


#: a KDA block's initial decay, as flash-linear-attention's ``kda`` layer
#: draws it: ``a_log = log U(1, 16)`` a head, ``dt_bias`` the inverse
#: softplus of ``exp U(log 0.001, log 0.1)`` a channel; and the epsilon of
#: its l2 norm (under the root, beside the sum of squares)
KDA_A = (1.0, 16.0)
KDA_DT = (1e-3, 1e-1)
KDA_L2_EPS = 1e-6


def init_kda_params(k_in: jax.Array, k_out: jax.Array,
                    cfg: TransformerConfig) -> Dict[str, jnp.ndarray]:
    """A KDA mixer's parameters (``TransformerLM._kda_mixer``): q / k / v
    projections ``[d, H dh]`` with their convolution taps ``[K, H dh]``
    (uniform in ``+-K^-1/2``, a depthwise ``Conv1d``'s default), the decay's
    low-rank pair ``wf_a [d, dh]`` / ``wf_b [dh, H dh]`` with ``a_log [H]``
    and ``dt_bias [H dh]``, ``wb [H, d]`` for beta, the output gate's pair
    ``wg_a`` / ``wg_b``, the per-head norm ``o_norm [dh]`` (one weight for
    every head) and ``wo [H dh, d]``."""
    from harmony_tpu.models.common import dense_init as dense

    d, H, dh, K = (cfg.d_model, cfg.linear_heads, cfg.linear_head_dim,
                   cfg.short_conv)
    kq, kk, kv, kcq, kck, kcv, kfa, kfb, ka, kdt, kb, kga, kgb = \
        jax.random.split(k_in, 13)
    taps = lambda key: jax.random.uniform(
        key, (K, H * dh), jnp.float32, -K ** -0.5, K ** -0.5)
    dt = jnp.exp(jax.random.uniform(kdt, (H * dh,), jnp.float32,
                                    np.log(KDA_DT[0]), np.log(KDA_DT[1])))
    return {
        "wq": dense(kq, (d, H * dh)), "wk": dense(kk, (d, H * dh)),
        "wv": dense(kv, (d, H * dh)),
        "conv_q": taps(kcq), "conv_k": taps(kck), "conv_v": taps(kcv),
        "wf_a": dense(kfa, (d, dh)), "wf_b": dense(kfb, (dh, H * dh)),
        "a_log": jnp.log(jax.random.uniform(ka, (H,), jnp.float32, *KDA_A)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        # stored [H, d]: a leaf whose minor dimension is a handful of heads
        # makes XLA lay the whole flat parameter vector out 8 wide
        "wb": dense(kb, (d, H)).T,
        "wg_a": dense(kga, (d, dh)), "wg_b": dense(kgb, (dh, H * dh)),
        "o_norm": jnp.ones((dh,), jnp.float32),
        "wo": dense(k_out, (H * dh, d)),
    }


#: a Gated DeltaNet block's initial decay, as ``transformers``'
#: ``Qwen3NextGatedDeltaNet`` draws it: ``a_log = log U(0, 16)`` and
#: ``dt_bias = 1`` a value head
GDN_A = (0.0, 16.0)


def init_gdn_params(k_in: jax.Array, k_out: jax.Array,
                    cfg: TransformerConfig) -> Dict[str, jnp.ndarray]:
    """A Gated DeltaNet mixer's parameters (``TransformerLM._gdn_mixer``):
    ONE input projection ``w_qkvz [d, q | k | v | z]`` (``Hk dh`` columns
    each of q and k, ``Hv dh`` each of v and z), the taps ``conv [K, q | k |
    v]`` of its one convolution (uniform in ``+-K^-1/2``, no bias), ``w_ba
    [2 Hv, d]`` for beta's and the decay's scalars (rows ``b | a``; stored
    transposed, as KDA's ``wb``), ``a_log`` and ``dt_bias [Hv]``, the
    per-head output norm ``o_norm [dh]`` (a plain weight, one for every
    head) and ``wo [Hv dh, d]``."""
    from harmony_tpu.models.common import dense_init as dense

    d, K, Hv = cfg.d_model, cfg.short_conv, cfg.heads("gdn")
    key, value, conv = cfg.gdn_widths
    ki, kc, ka, kb = jax.random.split(k_in, 4)
    return {
        "w_qkvz": dense(ki, (d, conv + value)),
        "conv": jax.random.uniform(kc, (K, conv), jnp.float32,
                                   -K ** -0.5, K ** -0.5),
        "w_ba": dense(kb, (d, 2 * Hv)).T,
        "a_log": jnp.log(jax.random.uniform(ka, (Hv,), jnp.float32, *GDN_A)),
        "dt_bias": jnp.ones((Hv,), jnp.float32),
        "o_norm": jnp.ones((cfg.linear_head_dim,), jnp.float32),
        "wo": dense(k_out, (value, d)),
    }


def init_ssd_params(k_in: jax.Array, k_out: jax.Array,
                    cfg: TransformerConfig) -> Dict[str, jnp.ndarray]:
    """A Mamba-2 mixer's parameters (``TransformerLM._ssd_mixer``): the
    input projection ``w_in [d, z | x | B | C | dt]``, the convolution's taps
    ``conv [K, x | B | C]`` (uniform in ``+-K^-1/2``) and bias ``conv_b``,
    ``a_log`` / ``dt_bias`` / ``skip`` (the paper's ``D``) a head — drawn as
    the KDA block's decay is (``KDA_A``, ``KDA_DT``: Mamba-2's own ranges) —
    the gated norm's weight ``o_norm [H P]`` and ``w_out [H P, d]``."""
    from harmony_tpu.models.common import dense_init as dense

    H, K = cfg.ssd_heads, cfg.short_conv
    inner, conv, proj = cfg.ssd_widths
    ki, kc, ka, kdt = jax.random.split(k_in, 4)
    dt = jnp.exp(jax.random.uniform(kdt, (H,), jnp.float32,
                                    np.log(KDA_DT[0]), np.log(KDA_DT[1])))
    return {
        "w_in": dense(ki, (cfg.d_model, proj)),
        "conv": jax.random.uniform(kc, (K, conv), jnp.float32,
                                   -K ** -0.5, K ** -0.5),
        "conv_b": jnp.zeros((conv,), jnp.float32),
        "a_log": jnp.log(jax.random.uniform(ka, (H,), jnp.float32, *KDA_A)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "skip": jnp.ones((H,), jnp.float32),
        "o_norm": jnp.ones((inner,), jnp.float32),
        "w_out": dense(k_out, (inner, cfg.d_model)),
    }


#: taps of CCA's two causal convolutions (``cca_time0`` / ``cca_time1`` of
#: ZAYA1's config: position t sees t - 1 and t), and the epsilon beside the
#: sum of squares under its L2 norm's root
CCA_TAPS = 2
CCA_L2_EPS = 1e-12


def init_cca_params(key: jax.Array, cfg: TransformerConfig
                    ) -> Dict[str, jnp.ndarray]:
    """What CCA adds to an ``mha`` block's ``wqkv`` / ``wo``
    (``TransformerLM._cca_latent``), over the ``c = (n_heads + kv_heads)
    head_dim`` channels of ``[q | k]``: the depthwise taps ``conv0 [K, c]``
    (uniform in ``+-K^-1/2``, as KDA's) and bias ``conv0_b [c]``; the
    per-head taps ``conv1 [K, heads, hd, hd]`` (fan-in ``K hd``) and bias
    ``conv1_b [c]``; the keys' temperature ``temp [kv_heads]``, stored as
    itself, 1 at the start."""
    K, hd = CCA_TAPS, cfg.head_dim
    heads = cfg.n_heads + cfg.kv_heads
    k0, k1 = jax.random.split(key)
    return {
        "conv0": jax.random.uniform(k0, (K, heads * hd), jnp.float32,
                                    -K ** -0.5, K ** -0.5),
        "conv0_b": jnp.zeros((heads * hd,), jnp.float32),
        "conv1": jax.random.normal(k1, (K, heads, hd, hd), jnp.float32)
        * (K * hd) ** -0.5,
        "conv1_b": jnp.zeros((heads * hd,), jnp.float32),
        "temp": jnp.ones((cfg.kv_heads,), jnp.float32),
    }


def merge_init(d: int):
    """A scaled residual merge's ``[a_x, b_x, a_y, b_y]`` as rows of
    ``[4, d]``: ones and zeros, a plain sum at the start."""
    return jnp.stack([jnp.ones((d,), jnp.float32),
                      jnp.zeros((d,), jnp.float32)] * 2)


def _merge(x, y, m):
    """The residual merge of stream ``x`` and a sublayer's ``y``: their sum,
    or under ``merge_scaled`` (``m [4, d]``: :func:`merge_init`) ``a_x (x +
    b_x) + a_y (y + b_y)`` in float32."""
    if m is None:
        return x + y
    with step_scope("merge"):
        f32 = jnp.float32
        return (m[0] * (x.astype(f32) + m[1])
                + m[2] * (y.astype(f32) + m[3])).astype(x.dtype)


def _l2norm(t):
    """``t`` over its last axis' l2 norm (``KDA_L2_EPS`` under the root): a
    delta-rule block's q and k, a head each."""
    return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + KDA_L2_EPS)


def _causal_conv(t, taps):
    """The depthwise causal convolution of ``t [B, S, c]`` by ``taps [K,
    c]``, in float32: ``y_t = sum_j taps[j] t_{t - (K-1) + j}``. Who calls
    it since PR 62: ``_ssd_mixer`` (a bias, float32 out, ``B | C`` not by
    heads) and the CCA block's two convolutions, always; the delta-rule
    blocks only where ``ops/conv_heads.py``'s kernel
    declines — off the TPU, heads not 128 wide."""
    K, S = taps.shape[0], t.shape[1]
    tp = jnp.pad(t.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    return sum(tp[:, j:j + S] * taps[j] for j in range(K))


def _conv_to_heads(t, taps, sections, dh):
    """A delta-rule block's operands from its projection ``t [B, S, C]`` by
    ``ops.conv_heads``'s kernel — the first ``taps.shape[1]`` columns
    through the depthwise causal convolution and a SiLU, each of
    ``sections`` (``(kind, heads)`` in column order; ``l2``: ``_l2norm`` a
    head, ``l2_scaled``: times ``dh^-1/2`` too) as ``[B, heads, S, dh]`` in
    t's dtype, ONE pass over the rows as they lie — or None where the
    caller keeps its own lines: where the block's scan does not take its
    kernels either (off the TPU, a mesh of several chips), and where the
    kernel's plan declines the shape."""
    from harmony_tpu.ops import conv_heads
    from harmony_tpu.ops.kda import _kernel_route

    if not _kernel_route() or conv_heads.plan(
            t.shape[1], dh, t.dtype, sections, taps.shape[0]) is None:
        return None
    return conv_heads.conv_heads(t, taps, sections, KDA_L2_EPS)


def _remat(f, kept: Dict[str, list]):
    """``f`` (a block's body) rematerialised, the ONE place and policy:
    the backward recomputes the block's activations from its input instead
    of keeping them — activation HBM drops from O(n_layers * B * S * d) to
    O(B * S * d) — EXCEPT what the kernels' ``fwd`` rules and the router
    name (``ops/residuals.py`` ``NAMES``: a few hundred MB a step), so the
    norms, projections, rotary and glue run a second time and no Pallas
    forward, router matmul or selection does. Each call adds what it keeps
    to ``kept`` (``{name: [arrays, bytes]}``, STATUS ``remat_saved``);
    ``jax.checkpoint`` traces a signature once, so a call it has traced
    before counts what that trace named."""
    body = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(*NAMES))
    by_signature: Dict[Any, Dict[str, list]] = {}

    def call(*args, **kwargs):
        leaves, tree = jax.tree.flatten((args, kwargs))
        signature = (tree, tuple((jnp.shape(a), jnp.result_type(a))
                                 for a in leaves))
        with collecting() as named:
            out = body(*args, **kwargs)
        if named:
            by_signature[signature] = named
        for name, (arrays, nbytes) in by_signature.get(signature, {}).items():
            row = kept.setdefault(name, [0, 0])
            row[0] += arrays
            row[1] += nbytes
        return out

    return call


class TransformerLM:
    """Pure-functional decoder-only LM: ``init`` -> params, ``apply`` ->
    logits, ``loss`` -> mean next-token cross-entropy."""

    def __init__(self, config: TransformerConfig) -> None:
        self.config = config

    # -- params ----------------------------------------------------------

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        k_emb, k_pos, *k_layers = jax.random.split(rng, 2 + cfg.n_layers)
        d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff

        from harmony_tpu.models.common import dense_init as dense

        layers = []
        kinds = cfg.layer_kinds()
        # the model's norm starts as the identity: a weight of 1, or 0
        # where it enters as 1 + w
        unit = ((lambda n: jnp.zeros((n,), jnp.float32)) if cfg.norm_offset
                else (lambda n: jnp.ones((n,), jnp.float32)))
        for i, kl in enumerate(k_layers):
            ks = jax.random.split(kl, 4)
            f = cfg.ffn_width(i)
            if cfg.layer_pattern:  # one sublayer under one norm
                from harmony_tpu.models import moe

                layer = {"ln": jnp.ones((d,), jnp.float32)}
                if kinds[i] == "ssd":
                    layer["ssd"] = init_ssd_params(ks[0], ks[1], cfg)
                elif kinds[i] == "attn":
                    layer["wqkv"] = dense(ks[0], (d, sum(cfg.qkv_widths)))
                    layer["wo"] = dense(ks[1], (cfg.qkv_widths[0], d))
                else:
                    layer["moe"] = moe.init_dropless_params(
                        ks[2], cfg.dropless_cfg)
                layers.append(layer)
                continue
            if kinds[i] in ("kda", "gdn"):
                mixer = init_kda_params if kinds[i] == "kda" \
                    else init_gdn_params
                layer = {
                    "ln1": unit(d),
                    kinds[i]: mixer(ks[0], ks[1], cfg),
                    "ln2": unit(d),
                }
            elif cfg.attn_kind == "mla":
                kq, ka, kb = jax.random.split(ks[0], 3)
                nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
                r, vd = cfg.kv_lora_rank, cfg.v_head_dim
                layer = {
                    "ln1": jnp.ones((d,), jnp.float32),
                    "wq": dense(kq, (d, h * (nope + rot))),
                    "wkv_a": dense(ka, (d, r + rot)),
                    "kv_norm": jnp.ones((r,), jnp.float32),
                    "wkv_b": dense(kb, (r, h * (nope + vd))),
                    "wo": dense(ks[1], (h * vd, d)),
                    "ln2": jnp.ones((d,), jnp.float32),
                }
            else:
                widths = cfg.qkv_widths_of(kinds[i])
                # attn_gate="element": the q block carries the gate's columns
                gate = widths[0] if cfg.attn_gate == "element" else 0
                layer = {
                    "ln1": unit(d),
                    "wqkv": dense(ks[0], (d, sum(widths) + gate)),
                    "wo": dense(ks[1], (widths[0], d)),
                    "ln2": unit(d),
                }
                if cfg.attn_gate == "head":  # stored [heads, d], as KDA's wb
                    layer["wgate"] = dense(jax.random.fold_in(ks[0], 2),
                                           (d, cfg.heads(kinds[i]))).T
                if cfg.sandwich_norm:  # on the sublayers' outputs
                    layer["ln1_post"] = jnp.ones((d,), jnp.float32)
                    layer["ln2_post"] = jnp.ones((d,), jnp.float32)
            if cfg.qk_norm:
                layer["q_norm"] = jnp.ones((d,), jnp.float32)
                layer["k_norm"] = jnp.ones((d,), jnp.float32)
            if cfg.head_norm and kinds[i] not in ("kda", "gdn"):
                layer["q_head_norm"] = unit(cfg.head_dim)
                layer["k_head_norm"] = unit(cfg.head_dim)
            if cfg.cca:
                layer["cca"] = init_cca_params(
                    jax.random.fold_in(ks[0], 1), cfg)
            if cfg.merge_scaled:
                layer["merge1"], layer["merge2"] = merge_init(d), merge_init(d)
            if cfg.is_moe_layer(i):
                from harmony_tpu.models import moe

                layer["moe"] = (
                    moe.init_dropless_params(ks[2], cfg.dropless_cfg)
                    if cfg.moe_top_k else
                    moe.init_moe_params(ks[2], cfg.moe_cfg))
            else:
                layer["w1"] = dense(ks[2], (d, f))
                layer["w2"] = dense(ks[3], (f, d))
                if cfg.ffn == "swiglu":  # w1 gates, w3 is the up projection
                    layer["w3"] = dense(jax.random.fold_in(ks[2], 1), (d, f))
            layers.append(layer)
        params = {
            "embed": jax.random.normal(k_emb, (cfg.vocab_size, d), jnp.float32)
            * cfg.embed_std,
            "ln_f": unit(d),
            "layers": layers,
        }
        if cfg.pos == "learned":
            params["pos"] = jax.random.normal(
                k_pos, (cfg.max_seq, d), jnp.float32) * 0.02
        if not cfg.tie_embeddings:
            params["head"] = dense(jax.random.fold_in(k_emb, 1),
                                   (d, cfg.vocab_size))
        if cfg.exit_gate:  # fan-in rule: a logit of unit scale on normed rows
            params["exit_w"] = dense(jax.random.fold_in(k_emb, 2), (d, 1))[:, 0]
            params["exit_b"] = jnp.zeros((), jnp.float32)
        return params

    # -- forward ---------------------------------------------------------

    def _attention(self, q, k, v, axis_name: Optional[str],
                   window: Optional[int] = None):
        cfg = self.config
        if cfg.objective == "block_diffusion":
            if axis_name is not None:
                raise ValueError("the sequence-parallel attention tiers do "
                                 "not run the mask by block and stream")
            return self._stream_attention(q, k, v)
        if axis_name is not None:
            if window is not None or k.shape[1] != q.shape[1]:
                raise ValueError("the sequence-parallel attention tiers run "
                                 "neither a window nor grouped heads")
            sp = a2a_attention if cfg.sp_attn == "a2a" else ring_attention
            return sp(q, k, v, axis_name=axis_name, causal=True)
        S = q.shape[2]
        from harmony_tpu.models.common import flash_on_mesh, resolve_attn

        attn = resolve_attn(cfg.attn, S, head_dim=q.shape[3],
                            v_head_dim=v.shape[3], dtype=q.dtype,
                            group=q.shape[1] // k.shape[1])
        band = {} if window is None else {"window": window}
        if attn == "flash":  # the kernels tile themselves from the shape
            return flash_on_mesh(q, k, v, causal=True, **band)
        return blockwise_attention(q, k, v, causal=True, **band)

    def _stream_attention(self, q, k, v):
        """Attention of a block-diffusion step on ``[2N, heads, L, hd]``
        operands — rows ``n`` the clean and ``N + n`` the noisy copy of
        sequence ``n``, the ONE place the two streams meet. One call of the
        attention tier with both streams' queries stacked along the sequence
        axis against the CLEAN keys and values under the mask by block and
        stream (ops/attention.py ``diffusion_block``: a causal triangle
        whose edge is rounded up to the block for clean rows and down for
        noisy ones), then the noisy rows' own-block term — ``L / B`` tiles of
        ``B x B`` against the NOISY keys, plain ``jnp`` — merged in by the
        two log-sum-exps. Noisy keys never stream through a kernel, and no
        ``[L, L]`` array exists."""
        from harmony_tpu.models.common import flash_on_mesh, resolve_attn
        from harmony_tpu.ops.attention import (blockwise_attention_lse,
                                               merge_by_lse,
                                               own_block_attention)

        cfg = self.config
        N, L, B = q.shape[0] // 2, q.shape[2], cfg.diffusion_block
        attn = resolve_attn(cfg.attn, L, head_dim=q.shape[3],
                            v_head_dim=v.shape[3], dtype=q.dtype,
                            group=q.shape[1] // k.shape[1], streams=2)
        with step_scope("mixer.streams"):
            stacked = jnp.concatenate([q[:N], q[N:]], axis=2)  # [N, H, 2L, hd]
        if attn == "flash":  # the kernels tile themselves from the shape
            o, lse = flash_on_mesh(stacked, k[:N], v[:N], causal=True,
                                   diffusion_block=B, with_lse=True)
        else:
            o, lse = blockwise_attention_lse(stacked, k[:N], v[:N],
                                             causal=True, diffusion_block=B)
        with step_scope("mixer.streams"):
            own, own_lse = own_block_attention(q[N:], k[N:], v[N:], B)
            noisy = merge_by_lse(o[:, :, L:], lse[:, :, L:], own, own_lse)
            return jnp.concatenate([o[:, :, :L], noisy], axis=0)

    def _latent_qkv(self, xn, layer, pos_offset):
        """DeepSeek-V3's latent attention operands from the normed input
        ``xn [B, S, d]``: ``q [B, h, S, nope + rope]``, ``k`` the same width
        (each head's decompressed ``k_nope`` beside the ONE rotary key all
        heads share) and ``v [B, h, S, v_head_dim]``. Rotary turns only the
        ``rope``-wide parts, and is skipped under ``pos="none"`` (Kimi
        Linear's latent blocks, ``mla_use_nope``: the parts are carried as
        they are, the order comes from the KDA blocks around); the softmax
        scale is ``(nope + rope) ** -0.5`` either way, the kernels' default
        for a q that wide."""
        cfg = self.config
        B, S = xn.shape[0], xn.shape[1]
        h, nope, rot = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        r, vd = cfg.kv_lora_rank, cfg.v_head_dim
        to_heads = lambda t, w: t.reshape(B, S, h, w).transpose(0, 2, 1, 3)
        with step_scope("mixer.qkv"):
            q = to_heads(xn @ layer["wq"].astype(cfg.dtype), nope + rot)
            ckv = xn @ layer["wkv_a"].astype(cfg.dtype)      # [B, S, r + rot]
            c = _norm(ckv[..., :r], layer["kv_norm"].astype(cfg.dtype),
                      cfg.norm_eps)
            kv = to_heads(c @ layer["wkv_b"].astype(cfg.dtype), nope + vd)
        turn = ((lambda t: rope(t, cfg.rope_theta, pos_offset))
                if cfg.pos == "rope" else (lambda t: t))
        with step_scope("mixer.rope"):
            q_pe = turn(q[..., nope:])
            k_pe = turn(ckv[:, None, :, r:])
            q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (B, h, S, rot))],
                axis=-1)
        return q, k, kv[..., nope:]

    def _kda_mixer(self, xn, p):
        """Kimi Linear's KDA token mixer on the normed input ``xn [B, S,
        d]``: ``(y [B, S, d], {"decay", "beta"})``. q, k, v each through a
        depthwise causal convolution and a SiLU; per head ``q = l2norm(q)
        dk^-1/2``, ``k = l2norm(k)``; the per-channel log-decay ``g =
        -exp(a_log_h) softplus(W_f (x) + dt_bias)``, ``beta = sigmoid(w_b
        x)``; the gated delta rule (ops/kda.py); the output ``W_o [rmsnorm
        per head (o) * sigmoid(W_g (x))]``, both gates through a
        ``linear_head_dim``-wide bottleneck. The statistics are the layer's
        mean decay ``exp(g)`` and mean ``beta`` (no gradient)."""
        from harmony_tpu.ops.kda import kda_attention

        cfg = self.config
        B, S = xn.shape[0], xn.shape[1]
        H, dh, dt = cfg.linear_heads, cfg.linear_head_dim, cfg.dtype
        f32 = jnp.float32
        w = lambda name: p[name].astype(dt)
        heads = lambda t: t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)

        def proj(name):
            with step_scope("kda.proj"):
                return xn @ w(name)

        def conv(name, taps, kind):  # no bias; ONE section a projection
            t = proj(name)
            served = _conv_to_heads(t, taps, ((kind, H),), dh)
            if served is not None:
                return served[0]
            t = heads(jax.nn.silu(_causal_conv(t, taps)))
            if kind != "plain":
                t = _l2norm(t)
            return t * dh ** -0.5 if kind == "l2_scaled" else t

        with step_scope("kda.conv"):
            q = conv("wq", p["conv_q"], "l2_scaled")
            k = conv("wk", p["conv_k"], "l2")
            v = conv("wv", p["conv_v"], "plain")
        with step_scope("kda.gate"):
            f = heads(((xn @ w("wf_a")) @ w("wf_b")).astype(f32)
                      + p["dt_bias"])
            g = -jnp.exp(p["a_log"])[None, :, None, None] * jax.nn.softplus(f)
            beta = jax.nn.sigmoid(
                jnp.einsum("bsd,hd->bhs", xn, w("wb")).astype(f32))
        with step_scope("kda.scan"):
            o = kda_attention(q.astype(dt), k.astype(dt), v.astype(dt), g,
                              beta)
        with step_scope("kda.gate"):
            gate = jax.nn.sigmoid(
                heads((xn @ w("wg_a")) @ w("wg_b")).astype(f32)).astype(dt)
        with step_scope("kda.out"):
            o = _norm(o, w("o_norm"), cfg.norm_eps) * gate
            y = o.transpose(0, 2, 1, 3).reshape(B, S, H * dh) @ w("wo")
        with step_scope("kda.gate"):
            return y, {"decay": lax.stop_gradient(jnp.exp(g).mean()),
                       "beta": lax.stop_gradient(beta.mean())}

    def _gdn_mixer(self, xn, p):
        """Qwen3-Next's Gated DeltaNet token mixer on the normed input ``xn
        [B, S, d]``: ``(y [B, S, d], {"decay", "beta"})``, under
        ``_kda_mixer``'s scopes. ``[q | k | v | z] = xn W_qkvz``; ``q | k |
        v`` through ONE depthwise causal convolution and a SiLU; per key
        head ``q = l2norm(q) dh^-1/2``, ``k = l2norm(k)``; per VALUE head and
        position the scalars ``beta = sigmoid(b)`` and the log-decay ``g =
        -exp(a_log) softplus(a + dt_bias)`` (float32) from ``[b | a] = xn
        W_ba``; the gated delta rule with value head ``j`` on key head ``j
        // (Hv / Hk)`` (ops/kda.py ``gdn_attention``); the output ``W_o
        [rmsnorm per head (o) * silu(z)]`` — the norm first, a plain weight.
        The statistics are the layer's mean decay ``exp(g)`` and mean
        ``beta`` (no gradient)."""
        from harmony_tpu.ops.kda import gdn_attention

        cfg = self.config
        B, S = xn.shape[0], xn.shape[1]
        Hv, dh, dt = cfg.heads("gdn"), cfg.linear_head_dim, cfg.dtype
        key, value, conv = cfg.gdn_widths
        f32 = jnp.float32
        heads = lambda t: t.reshape(B, S, -1, dh).transpose(0, 2, 1, 3)

        with step_scope("kda.proj"):
            qkvz = xn @ p["w_qkvz"].astype(dt)
            ba = jnp.einsum("bsd,hd->bhs", xn, p["w_ba"].astype(dt))
        with step_scope("kda.conv"):  # z's columns stay where they are
            served = _conv_to_heads(qkvz, p["conv"], (
                ("l2_scaled", key // dh), ("l2", key // dh),
                ("plain", value // dh)), dh)
            if served is not None:
                q, k, v = served
            else:
                q, k, v = jnp.split(
                    jax.nn.silu(_causal_conv(qkvz[..., :conv], p["conv"])),
                    (key, 2 * key), axis=-1)
                q, k = _l2norm(heads(q)) * dh ** -0.5, _l2norm(heads(k))
                v = heads(v)
        with step_scope("kda.gate"):
            beta = jax.nn.sigmoid(ba[:, :Hv].astype(f32))
            g = -jnp.exp(p["a_log"])[None, :, None] * jax.nn.softplus(
                ba[:, Hv:].astype(f32) + p["dt_bias"][None, :, None])
        with step_scope("kda.scan"):
            o = gdn_attention(q.astype(dt), k.astype(dt), v.astype(dt), g,
                              beta)
        with step_scope("kda.gate"):
            gate = jax.nn.silu(heads(qkvz[..., conv:]).astype(f32)).astype(dt)
        with step_scope("kda.out"):
            o = _norm(o, p["o_norm"].astype(dt), cfg.norm_eps) * gate
            y = o.transpose(0, 2, 1, 3).reshape(B, S, value) \
                @ p["wo"].astype(dt)
        with step_scope("kda.gate"):
            return y, {"decay": lax.stop_gradient(jnp.exp(g).mean()),
                       "beta": lax.stop_gradient(beta.mean())}

    def _ssd_mixer(self, xn, p):
        """Nemotron-H's Mamba-2 token mixer on the normed input ``xn [B, S,
        d]``: ``(y [B, S, d], {"decay", "dt"})``. ``[z | xBC | dt] = xn
        W_in``; ``xBC`` through a depthwise causal convolution with a bias
        and a SiLU, split into ``x [H, P]``, ``B`` and ``C [G, N]``; per head
        the step ``dt = softplus(dt + dt_bias)`` and the decay ``exp(-dt
        exp(a_log))``; the recurrence ``S_t = a_t S_{t-1} + dt_t x_t B_t^T``,
        ``y_t = S_t C_t + skip x_t`` (ops/ssd.py; head ``h`` reads group ``h
        // (H / G)``); the output ``W_out [rmsnorm per group (y * silu(z))]``,
        a weight a channel. The statistics are the layer's mean decay and
        mean step (no gradient)."""
        from harmony_tpu.ops.ssd import ssd_scan

        cfg = self.config
        B, S = xn.shape[0], xn.shape[1]
        H, P, G, N = (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_groups,
                      cfg.ssd_state)
        inner, conv, _ = cfg.ssd_widths
        dt_, f32 = cfg.dtype, jnp.float32
        heads = lambda t, h: t.reshape(B, S, h, -1).transpose(0, 2, 1, 3)
        with step_scope("ssd.proj"):
            zxbcdt = xn @ p["w_in"].astype(dt_)
            z, xbc, dt = jnp.split(zxbcdt, (inner, inner + conv), axis=-1)
        with step_scope("ssd.conv"):
            xbc = jax.nn.silu(_causal_conv(xbc, p["conv"]) + p["conv_b"])
            x, b, c = jnp.split(xbc, (inner, inner + G * N), axis=-1)
            x = heads(x, H)                                  # [B, H, S, P] f32
        with step_scope("ssd.gate"):
            dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"]
                                 ).transpose(0, 2, 1)        # [B, H, S]
            g = -jnp.exp(p["a_log"])[None, :, None] * dt
        with step_scope("ssd.scan"):
            y = ssd_scan((x * dt[..., None]).astype(dt_),
                         heads(b, G).astype(dt_), heads(c, G).astype(dt_), g,
                         chunk=cfg.ssd_chunk)
        with step_scope("ssd.gate"):
            y = y.astype(f32) + p["skip"][None, :, None, None] * x
            y = y.transpose(0, 2, 1, 3).reshape(B, S, G, inner // G)
            y = (y * jax.nn.silu(z.astype(f32)).reshape(y.shape)).astype(dt_)
        with step_scope("ssd.out"):
            y = _norm(y, p["o_norm"].astype(dt_).reshape(G, -1),
                      cfg.norm_eps).reshape(B, S, inner)
            y = y @ p["w_out"].astype(dt_)
        with step_scope("ssd.gate"):
            return y, {"decay": lax.stop_gradient(jnp.exp(g).mean()),
                       "dt": lax.stop_gradient(dt.mean())}

    def _layer(self, x, layer, kind: str, axis_name: Optional[str] = None,
               pos_offset: Any = 0):
        """One layer of a ``layer_pattern`` model — ONE pre-norm sublayer,
        ``x + f(norm(x))`` with ``f`` by ``kind`` (``layer_kinds()``: the
        state-space mixer, softmax attention, or the expert layer).
        ``_block``'s triple: ``(x, aux, mix)``."""
        cfg = self.config
        aux, mix = jnp.asarray(0.0, jnp.float32), None
        with step_scope("norm"):
            xn = _norm(x, layer["ln"].astype(cfg.dtype), cfg.norm_eps)
        if kind == "ssd":
            y, mix = self._ssd_mixer(xn, layer["ssd"])
        elif kind == "attn":
            y = self._softmax_mixer(xn, layer, axis_name, pos_offset, kind)
        else:
            y, aux = ffn_apply(cfg, layer, xn)
        return x + y, aux, mix

    def _block(self, x, layer, axis_name: Optional[str],
               moe_axis: Optional[str] = None, pos_offset: Any = 0,
               kind: Optional[str] = None, route_state=None):
        """One pre-norm decoder block — the shared body of ``apply`` and
        the pipeline-parallel stage fn. Returns ``(x, aux, mix)``: aux is
        the Switch load-balance loss when the block carries a Switch MoE
        FFN, the dropless layer's routing statistics (a dict of sums,
        models/moe.py) when it carries that, 0 otherwise; mix is a KDA
        block's mixer statistics (``_kda_mixer``), None for a softmax block.
        ``moe_axis`` = expert-parallel mesh axis (see ffn_apply). The
        published q/k/v projections are the three column blocks of
        ``wqkv``. ``kind`` (``layer_kinds()``'s) matters in a model with
        ``window_layers`` only: ``"swa"`` blocks window and turn, ``"full"``
        ones do neither unless ``kind_rope`` says how they turn (and
        ``kind_heads`` how many query heads each has). ``route_state``: what
        the MLP router of the block before left for this one's
        (``moe_router_hidden``; None in the first block); this block's is
        ``aux["state"]``. Under ``sandwich_norm`` each sublayer's output
        passes a norm of its own (``ln1_post`` / ``ln2_post``) before its
        residual add."""
        cfg = self.config
        eps = cfg.norm_eps
        x_in = x
        weight = lambda name: cfg.norm_weight(layer[name]).astype(cfg.dtype)
        with step_scope("norm"):
            xn = _norm(x, weight("ln1"), eps)
        if "kda" in layer:
            y, mix = self._kda_mixer(xn, layer["kda"])
        elif "gdn" in layer:
            y, mix = self._gdn_mixer(xn, layer["gdn"])
        else:
            y, mix = self._softmax_mixer(xn, layer, axis_name, pos_offset,
                                         kind), None
        if cfg.sandwich_norm:
            with step_scope("norm"):
                y = _norm(y, layer["ln1_post"].astype(cfg.dtype), eps)
        x = _merge(x, y, layer.get("merge1"))
        with step_scope("norm"):
            xn = _norm(x, weight("ln2"), eps)
        route = {"router_x": x_in} if cfg.moe_route_block_input else {}
        if route_state is not None:
            route["route_state"] = route_state
        out, aux = ffn_apply(cfg, layer, xn, moe_axis=moe_axis, **route)
        if cfg.sandwich_norm:
            with step_scope("norm"):
                out = _norm(out, layer["ln2_post"].astype(cfg.dtype), eps)
        return _merge(x, out, layer.get("merge2")), aux, mix

    def _cca_latent(self, q, k, v, p):
        """CCA's q, k and v (arXiv:2510.04476) from ``wqkv``'s column blocks
        ``q [B, S, H hd]``, ``k`` and ``v [B, S, Hkv hd]``, all ``[B, S,
        heads, hd]``. Over ``c = [q | k]``: a depthwise causal convolution
        with a bias (float32), then a head at a time one ``[K hd, hd]``
        causal convolution with a bias (activation dtype operands, float32
        sums); the mean of the UNCONVOLVED q and k is added back — query
        head i gets ``(q_i + k_kv(i)) / 2``, key head j ``(mean of its
        query heads + k_j) / 2``; each head is L2-normed to ``sqrt(hd)``,
        a key head times its temperature (float32); the last half of the
        key/value heads read the previous position's value (zero at 0)."""
        cfg = self.config
        B, S = q.shape[0], q.shape[1]
        H, Hkv, hd, dt = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.dtype
        f32 = jnp.float32
        shift = lambda t: jnp.pad(t, ((0, 0), (1, 0)) + ((0, 0),) * (
            t.ndim - 2))[:, :S]
        c = jnp.concatenate([q, k], axis=-1)                  # [B, S, c]
        c1 = (_causal_conv(c, p["conv0"]) + p["conv0_b"]).astype(dt)
        c1 = c1.reshape(B, S, H + Hkv, hd)
        c2 = jnp.einsum("bsjgc,jgcd->bsgd", jnp.stack([shift(c1), c1], axis=2),
                        p["conv1"].astype(dt), preferred_element_type=f32)
        c2 = c2 + p["conv1_b"].reshape(H + Hkv, hd)
        qg = q.astype(f32).reshape(B, S, Hkv, H // Hkv, hd)
        k0 = k.astype(f32).reshape(B, S, Hkv, hd)
        q = c2[:, :, :H] + (0.5 * (qg + k0[:, :, :, None])).reshape(
            B, S, H, hd)
        k = c2[:, :, H:] + 0.5 * (qg.mean(axis=3) + k0)

        def l2(t):
            return t * (hd ** 0.5 * lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + CCA_L2_EPS))

        v = v.reshape(B, S, Hkv, hd)
        v = jnp.concatenate([v[:, :, :Hkv // 2], shift(v[:, :, Hkv // 2:])],
                            axis=2)
        return (l2(q).astype(dt), (l2(k) * p["temp"][:, None]).astype(dt), v)

    def _softmax_mixer(self, xn, layer, axis_name, pos_offset, kind=None):
        """Softmax attention (``attn_kind``) on the normed input ``xn [B,
        S, d]`` through its output projection. ``kind`` (``layer_kinds()``'s)
        says the block's window, its query heads (``heads``) and how it
        turns q and k (``rotary``); under ``attn_gate="head"`` each head's
        output is gated before ``wo``."""
        cfg = self.config
        B, S = xn.shape[0], xn.shape[1]
        h, hd, eps = cfg.heads(kind), cfg.head_dim, cfg.norm_eps
        window = cfg.window if kind == "swa" else None
        if cfg.attn_kind == "mla":
            q, k, v = self._latent_qkv(xn, layer, pos_offset)
        else:
            with step_scope("mixer.qkv"):
                qkv = xn @ layer["wqkv"].astype(cfg.dtype)      # [B, S, 3d]
                gate = None
                if cfg.attn_gate == "element":  # a head: query | gate
                    wq, wk, _ = cfg.qkv_widths_of(kind)
                    qg, k, v = jnp.split(qkv, (2 * wq, 2 * wq + wk), axis=-1)
                    qg = qg.reshape(B, S, h, 2 * hd)
                    q, gate = qg[..., :hd].reshape(B, S, wq), qg[..., hd:]
                elif cfg.qkv_widths_of(kind) == (cfg.d_model,) * 3:
                    q, k, v = jnp.split(qkv, 3, axis=-1)
                else:  # grouped queries: k and v are kv_heads heads wide
                    wq, wk, _ = cfg.qkv_widths_of(kind)
                    q, k, v = jnp.split(qkv, (wq, wq + wk), axis=-1)
                if cfg.qk_norm:
                    q = _norm(q, layer["q_norm"].astype(cfg.dtype), eps)
                    k = _norm(k, layer["k_norm"].astype(cfg.dtype), eps)
                to_heads = lambda t: t.reshape(B, S, -1, hd).transpose(
                    0, 2, 1, 3)
                rot = cfg.rotary(kind)
                # where the rotary kernel serves the shape and nothing but
                # a norm works on single heads before it, q and k stay as
                # the projection left them: the kernel's index map is their
                # transpose, and it norms each head as it turns it
                heads = (h, cfg.kv_heads)
                by_rows = (rot is not None and not cfg.cca
                           and _rotary_serves(S, hd, q.dtype, heads))
                heads = heads if by_rows else None
                if by_rows:
                    v = to_heads(v)
                elif not cfg.cca:
                    q, k, v = to_heads(q), to_heads(k), to_heads(v)
                norms = (None, None)
                head_w = lambda name: cfg.norm_weight(layer[name])
                if cfg.head_norm and by_rows:
                    norms = ((head_w("q_head_norm"), eps),
                             (head_w("k_head_norm"), eps))
                elif cfg.head_norm:  # a head at a time, one weight for all
                    q = _norm(q, head_w("q_head_norm").astype(cfg.dtype), eps)
                    k = _norm(k, head_w("k_head_norm").astype(cfg.dtype), eps)
            if cfg.cca:
                with step_scope("mixer.cca"):
                    q, k, v = (to_heads(t) for t in self._cca_latent(
                        q, k, v, layer["cca"]))
            if rot is not None:
                part = ({} if rot.fraction == 1.0 else
                        {"width": rot.width(hd)})
                if rot.yarn is not None:
                    part["scaled"] = rot
                with step_scope("mixer.rope"):
                    q, k = _turned(q, k, rot.theta, pos_offset, heads,
                                   norms, **part)
        with step_scope("mixer.core"):
            o = self._attention(q, k, v, axis_name, window)
        if cfg.attn_gate == "head":  # one scalar a head and position
            with step_scope("mixer.gate"):
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,hd->bhs", xn, layer["wgate"].astype(cfg.dtype)
                ).astype(jnp.float32))
                o = o * gate[..., None].astype(o.dtype)
        with step_scope("mixer.out"):
            o = o.transpose(0, 2, 1, 3).reshape(B, S, h * v.shape[3])
        if cfg.attn_gate == "element":  # one a column of the head, which
            # lies as the projection left it: no transpose of the gate
            with step_scope("mixer.gate"):
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    o.dtype).reshape(o.shape)
        with step_scope("mixer.out"):
            return o @ layer["wo"].astype(cfg.dtype)

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,              # [B, S] int32 (LOCAL shard under SP)
        axis_name: Optional[str] = None,  # seq-parallel ring axis (shard_map)
        pos_offset: Any = 0,              # global position of tokens[:, 0]
    ) -> jnp.ndarray:
        logits, _ = self._apply_with_aux(params, tokens, axis_name, pos_offset)
        return logits

    def _apply_with_aux(self, params, tokens, axis_name=None, pos_offset=0,
                        moe_axis=None):
        """apply + the MoE aux: the summed Switch loss (0 for dense
        configs), or for dropless configs the routing statistics summed
        over the layers plus ``tokens_by_layer [moe layers, E]``."""
        return self._forward(params, tokens, axis_name, pos_offset,
                             moe_axis)[:2]

    def _forward(self, params, tokens, axis_name=None, pos_offset=0,
                 moe_axis=None):
        """``(logits, aux, mixers)``: ``_apply_with_aux``'s pair and the
        recurrent layers' statistics — KDA blocks' ``{"decay", "beta"}``
        each ``[kda blocks]``, state-space layers' ``{"decay", "dt"}`` each
        ``[ssd layers]`` (None for a model without such layers)."""
        exits, aux, mixers = self._trunk(params, tokens, axis_name,
                                         pos_offset, moe_axis)
        with step_scope("head"):  # a looped model answers with its last pass
            return self._readout(params, exits[-1]), aux, mixers

    def _readout(self, params, x):
        """The plain readout: the normed rows' float32 logits (for a stable
        softmax). The readout is the embedding (weight-tied) unless the
        model has a head of its own."""
        head = (params["embed"].T if self.config.tie_embeddings
                else params["head"])
        return x.astype(jnp.float32) @ head

    def _readout_tiles(self, x):
        """The tiles of the ONE readout-and-cross-entropy op
        (ops/readout_loss.py) for the normed rows ``x``, or None where its
        ``plan`` leaves the shape to the plain readout."""
        from harmony_tpu.ops import readout_loss

        cfg = self.config
        return readout_loss.plan(x.size // x.shape[-1], x.shape[-1],
                                 cfg.vocab_size, cfg.tie_embeddings, x.dtype)

    def _fused_nll(self, params, x, targets, tiles):
        """``-log softmax(readout(x))[targets]``, float32 in ``targets``'
        shape, by that op: the logits' passes ride on the tiles of the
        readout's products."""
        from harmony_tpu.ops.readout_loss import readout_nll
        from harmony_tpu.utils.platform import trace_is_tpu

        tied = self.config.tie_embeddings
        with step_scope("head"):
            nll = readout_nll(
                x.reshape(-1, x.shape[-1]),
                params["embed" if tied else "head"], targets.reshape(-1),
                tied=tied, tiles=tiles, interpret=not trace_is_tpu())
        return nll.reshape(targets.shape)

    def _trunk(self, params, tokens, axis_name=None, pos_offset=0,
               moe_axis=None):
        """``(exits, aux, mixers)``: everything up to the final norm — its
        rows ``[B, S, d]`` in the activation dtype (block diffusion: the noisy
        half), what the readout reads, as a tuple of one — and ``_forward``'s
        other two. Under ``loop_steps`` = T > 1 the layers run T times over
        the same ``params["layers"]`` (a static loop: ``blk<i>`` stays the
        LAYER's, so a layer's T uses fold into one row of a scope table), the
        final norm closes every pass — its rows are that pass's exit AND the
        next pass's input — and ``exits`` holds all T passes' rows."""
        cfg = self.config
        with step_scope("embed"):
            x = _embed_in(cfg, params["embed"], params.get("pos"), tokens,
                          pos_offset)

        def block(x, layer, **state):
            return self._block(x, layer, axis_name, moe_axis=moe_axis,
                               pos_offset=pos_offset, **state)

        # cfg.remat: every body below is checkpointed under ONE policy
        # (``_remat``); ``kept`` sums what it keeps over the layers
        kept: Dict[str, list] = {}
        wrap = (functools.partial(_remat, kept=kept) if cfg.remat
                else (lambda f: f))
        block = wrap(block)
        # a model with window_layers has two kinds of softmax block, each
        # its own traced body (no other model traces a second one); a
        # layer_pattern model a body a kind of layer
        by_kind = {kind: wrap(functools.partial(
            self._block, axis_name=axis_name, moe_axis=moe_axis,
            pos_offset=pos_offset, kind=kind)) for kind in ("swa", "full")
        } if cfg.window_layers else {}
        kinds = cfg.layer_kinds()
        if cfg.layer_pattern:
            by_kind = {kind: wrap(functools.partial(
                self._layer, kind=kind, axis_name=axis_name,
                pos_offset=pos_offset)) for kind in set(kinds)}
        aux = jnp.asarray(0.0, jnp.float32)
        routed = []  # dropless layers' statistics
        mixers = []  # KDA blocks' statistics
        state = {}   # an MLP router's rows, for the next block's router
        exits = []   # a looped model's passes, each after the final norm
        for _ in range(cfg.loop_steps):
            for i, layer in enumerate(params["layers"]):
                with step_scope("blk", i):
                    x, a, mix = by_kind.get(kinds[i], block)(x, layer,
                                                             **state)
                if mix is not None:
                    mixers.append(mix)
                if isinstance(a, dict):
                    if "state" in a:
                        state = {"route_state": a.pop("state")}
                    routed.append(a)
                else:
                    aux = aux + a
            if cfg.loop_steps > 1:
                with step_scope("head"):
                    x = _norm(x, cfg.norm_weight(params["ln_f"]).astype(
                        cfg.dtype), cfg.norm_eps)
                exits.append(x)
        if kept:
            from harmony_tpu.runtime.progcache import note_remat_saved

            note_remat_saved(kept)
        if routed:
            aux = jax.tree.map(lambda *xs: sum(xs), *routed)
            aux["tokens_by_layer"] = jnp.stack([a["tokens"] for a in routed])
            if "skipped" in aux:
                aux["skipped_by_layer"] = jnp.stack(
                    [a["skipped"] for a in routed])
            if "shared_gate" in aux:
                aux["shared_gate_by_layer"] = jnp.stack(
                    [a["shared_gate"] for a in routed])
        if not exits:
            with step_scope("head"):
                if cfg.objective == "block_diffusion":
                    x = x[x.shape[0] // 2:]  # ONE readout: the noisy rows
                x = _norm(x, cfg.norm_weight(params["ln_f"]).astype(
                    cfg.dtype), cfg.norm_eps)
            exits = [x]
        mixers = (jax.tree.map(lambda *xs: jnp.stack(xs), *mixers)
                  if mixers else None)
        return tuple(exits), aux, mixers

    def _exit_gate(self, params, exits):
        """``lam [T, B, S]`` float32: each pass's exit gate on its normed
        rows, ``sigmoid(rows . exit_w + exit_b)`` — the products in float32
        on the vector unit (a ``d``-wide row a position: no matmul)."""
        f32 = jnp.float32
        return jnp.stack([jax.nn.sigmoid(
            jnp.sum(h.astype(f32) * params["exit_w"], axis=-1)
            + params["exit_b"]) for h in exits])

    def _exit_loss_and_metrics(self, params, exits, targets):
        """A gated looped model's ``(loss, metrics)`` from its T passes'
        normed rows: every pass read out by the ONE readout (the fused op
        where its plan serves the shape: ``nll(t)`` a row, whose row weights
        ``p(t) / N`` carry a gradient of their own — the gate learns through
        ``nll(t)``'s value, the trunk through the weights), joined by
        :func:`exit_distribution` under the scope ``exit.gate``. Metrics:
        ``ce`` the last pass's mean cross-entropy (what inference answers
        with), ``exit_entropy`` the mean entropy of ``p``, and the vectors
        ``ce_by_exit [T]`` and ``exit_mass [T]`` — the SUM over the step's
        positions of ``p(t)``, so its own sum counts the positions
        (metrics/loop.py)."""
        cfg = self.config
        tiles = self._readout_tiles(exits[-1])

        def nll_of(h):
            if tiles is not None:
                return self._fused_nll(params, h, targets, tiles)
            with step_scope("head"):
                logp = jax.nn.log_softmax(self._readout(params, h), axis=-1)
                return -jnp.take_along_axis(logp, targets[..., None],
                                            axis=-1)[..., 0]

        nll = [nll_of(h) for h in exits]
        with step_scope("exit.gate"):
            nll = jnp.stack(nll)                              # [T, B, S]
            p = exit_distribution(self._exit_gate(params, exits))
            entropy = exit_entropy(p)                         # [B, S]
            loss = jnp.mean(jnp.sum(p * nll, axis=0)
                            - cfg.exit_entropy_weight * entropy)
            return loss, {"ce": nll[-1].mean(),
                          "exit_entropy": entropy.mean(),
                          "ce_by_exit": nll.mean(axis=(1, 2)),
                          "exit_mass": p.sum(axis=(1, 2))}

    def exits(self, params, tokens):
        """``(logits [T, B, S, V], lam [T, B, S])`` of every pass of a
        looped model, through the plain readout: what a check against a
        reference reads (``apply`` answers with ``logits[-1]``)."""
        exits, _, _ = self._trunk(params, tokens)
        with step_scope("head"):
            logits = jnp.stack([self._readout(params, h) for h in exits])
        with step_scope("exit.gate"):
            return logits, self._exit_gate(params, exits)

    def loss(self, params, tokens, axis_name=None) -> jnp.ndarray:
        """Mean next-token cross-entropy over the (single-device) batch,
        plus the weighted MoE auxiliary losses for expert configs. Under
        ``objective="block_diffusion"`` ``tokens`` is the batch tuple
        ``(tokens, masked, rate)`` and the loss the weighted one
        (``loss_and_metrics``)."""
        return self.loss_and_metrics(params, tokens, axis_name)[0]

    def noised(self, tokens, masked):
        """The ``[2N, L]`` tokens a block-diffusion step runs: the clean
        rows ``tokens [N, L]``, then the same rows with ``mask_token`` where
        ``masked`` is set. ``apply`` of them gives the noisy rows' logits
        ``[N, L, V]``."""
        with step_scope("noise"):
            noisy = jnp.where(masked != 0, jnp.asarray(
                self.config.mask_token, tokens.dtype), tokens)
            return jnp.concatenate([tokens, noisy], axis=0)

    def _diffusion_loss_and_metrics(self, params, batch, axis_name):
        """Block diffusion's ``(loss, metrics)`` on the batch ``(tokens [N,
        L], masked [N, L], rate [N, L / B])``: ``(1 / (N L)) sum_b (1 /
        rate_b) sum_{p in b, masked} CE(logits_p, tokens_p)`` — the noisy
        rows' logits at their OWN positions, no shift — plus the routing
        losses over all ``2 N L`` positions computed. Metrics: the
        unweighted mean CE over the masked positions (``ce``), the masked
        share, ``diffusion_tokens [masked, all]`` (a vector: the counters'),
        and an expert configuration's routing terms."""
        cfg = self.config
        if axis_name is not None or not isinstance(batch, (tuple, list)) \
                or len(batch) != 3:
            raise ValueError(
                "objective='block_diffusion' trains on the batch tuple "
                "(tokens, masked, rate) and on no sequence-parallel axis")
        tokens, masked, rate = batch
        (x,), aux, _ = self._trunk(params, self.noised(tokens, masked))
        tiles = self._readout_tiles(x)
        if tiles is None:
            with step_scope("head"):
                logits = self._readout(params, x)
        else:
            nll = self._fused_nll(params, x, tokens, tiles)
        with step_scope("loss"):
            f32 = jnp.float32
            if tiles is None:
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, tokens[..., None],
                                           axis=-1)[..., 0]
            m = (masked != 0).astype(f32)
            weight = m / jnp.repeat(rate.astype(f32), cfg.diffusion_block,
                                    axis=1)
            count = m.sum()
            loss = (nll * weight).sum() / m.size
            out = {"ce": (nll * m).sum() / jnp.maximum(count, 1.0),
                   "masked_share": count / m.size,
                   "diffusion_tokens": jnp.stack(
                       [count, jnp.asarray(m.size, f32)])}
            if cfg.moe_top_k:
                lb, z = routing_losses(aux, cfg.moe_experts)
                loss = loss + cfg.moe_aux_weight * lb + cfg.moe_z_weight * z
                out.update(aux_lb=lb, aux_z=z,
                           moe_expert_tokens=aux["tokens_by_layer"])
            return loss, out

    def loss_and_metrics(self, params, tokens, axis_name=None):
        """``(loss, metrics)``: dropless expert configs report the loss's
        three terms and the token-slots each expert of each layer was
        chosen for (a vector per step); models with KDA blocks each such
        block's mean decay and mean beta (vectors ``[kda blocks]``)."""
        cfg = self.config
        if cfg.objective == "block_diffusion":
            return self._diffusion_loss_and_metrics(params, tokens, axis_name)
        exits, aux, mixers = self._trunk(params, tokens[:, :-1],
                                         axis_name=axis_name)
        if cfg.exit_gate:
            return self._exit_loss_and_metrics(params, exits, tokens[:, 1:])
        x = exits[-1]  # a looped model without the gate: the last pass alone
        tiles = self._readout_tiles(x)
        if tiles is None:
            with step_scope("head"):
                logits = self._readout(params, x)
        else:
            nll = self._fused_nll(params, x, tokens[:, 1:], tiles)
        kda = {}
        if mixers is not None:  # one recurrent kind a model
            kind = "ssd" if cfg.layer_pattern else cfg.linear_kind
            kda = {f"{kind}_{stat}_mean": v for stat, v in mixers.items()}
        with step_scope("loss"):
            ce = (_next_token_ce(logits, tokens[:, 1:]) if tiles is None
                  else nll.mean())
            if cfg.moe_seq_aux:  # each layer's mean over sequences, summed
                return ce + cfg.moe_aux_weight * aux["seq_lb"], {
                    "ce": ce, "aux_seq": aux["seq_lb"],
                    "moe_expert_tokens": aux["tokens_by_layer"], **kda}
            if cfg.moe_top_k:
                lb, z = routing_losses(aux, cfg.moe_experts)
                loss = ce + cfg.moe_aux_weight * lb + cfg.moe_z_weight * z
                if cfg.moe_null_expert:
                    kda["moe_null_slots"] = aux["skipped_by_layer"]
                if cfg.moe_shared_gate:
                    kda["moe_shared_gate_mean"] = aux["shared_gate_by_layer"]
                return loss, {"ce": ce, "aux_lb": lb, "aux_z": z,
                              "moe_expert_tokens": aux["tokens_by_layer"],
                              **kda}
            if cfg.moe_experts:
                return ce + cfg.moe_aux_weight * aux, kda
            return ce, kda


def routing_losses(stats, num_experts: int):
    """``(load_balance, router_z)`` from the dropless layers' summed
    statistics, over ALL the layers' tokens at once as ``transformers``'
    ``load_balancing_loss_func`` has it: ``E * sum_e f_e * P_e`` with
    ``f_e`` the share of tokens whose top-k holds expert ``e`` (so
    ``sum_e f_e = k``) and ``P_e`` the mean router probability of ``e``;
    and the mean of ``logsumexp(router logits)^2``. Both on all experts'
    logits whatever share of them is held here. ``f_e`` carries no
    gradient (it counts)."""
    n = stats["n"]  # layers x tokens
    lb = num_experts * jnp.sum(
        lax.stop_gradient(stats["tokens"]) / n * stats["prob_sum"] / n)
    return lb, stats["z_sum"] / n


def exit_distribution(lam):
    """The exit distribution ``p [T, ...]`` of the gates ``lam [T, ...]``:
    ``p(t) = lam(t) S(t-1)`` with ``S(0) = 1``, ``S(t) = S(t-1) (1 -
    lam(t))`` — the chance of not having left before pass ``t`` times that of
    leaving there — and the LAST pass takes what is left, ``p(T) = S(T-1)``
    (its own gate decides nothing), so ``p`` sums to 1."""
    stay, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p + [stay])


def exit_entropy(p):
    """``H(p) = -sum_t p(t) log p(t)`` over the leading axis, ``0 log 0``
    read as 0 (in the value and in the gradient)."""
    live = p > 0
    return -jnp.sum(jnp.where(live, p * jnp.log(jnp.where(live, p, 1.0)),
                              0.0), axis=0)


def _next_token_ce(logits, targets) -> jnp.ndarray:
    """Mean next-token cross-entropy — ONE implementation shared by the
    single-device loss and the pipeline-parallel loss (the SP path's
    _masked_ce differs: psum-reduced masked mean over sharded axes)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -ll.mean()


def ffn_apply(cfg, layer, xn, no_drop: bool = False,
              moe_axis: Optional[str] = None, router_x=None,
              route_state=None):
    """Dense or MoE FFN on [..., d] activations — the ONE dense/MoE
    dispatch shared by training blocks and the decode path. Returns
    ``(out, aux)``. ``no_drop`` lifts the expert capacity to cover every
    token (decode routes tiny per-step batches where the training
    capacity_factor would drop tokens whenever two rows share an expert,
    letting one sequence degrade another's output). ``moe_axis`` is the
    expert-parallel mesh axis: expert params are sharded on their leading
    dim and token buckets move over ICI via all_to_all (moe_ffn).
    ``router_x``: what a dropless router reads where that is not ``xn``
    (``moe_route_block_input``); ``route_state``: what the MLP router of the
    block before left for this one's (``moe_router_hidden``)."""
    if "moe" in layer and cfg.moe_top_k:
        from harmony_tpu.models.moe import moe_ffn_dropless

        route = {} if router_x is None else {
            "router_x": router_x.reshape(-1, cfg.d_model)}
        if route_state is not None:
            route["state"] = route_state
        out, stats = moe_ffn_dropless(layer["moe"],
                                      xn.reshape(-1, cfg.d_model),
                                      cfg.dropless_cfg, seqs=xn.shape[0],
                                      **route)
        return out.reshape(xn.shape), stats
    if "moe" in layer:
        import dataclasses as _dc

        from harmony_tpu.models.moe import moe_ffn

        mcfg = cfg.moe_cfg
        if no_drop:
            mcfg = _dc.replace(mcfg, capacity_factor=float(mcfg.num_experts))
        flat = xn.reshape(-1, cfg.d_model)
        out, aux = moe_ffn(layer["moe"], flat, mcfg, axis_name=moe_axis)
        return out.reshape(xn.shape), aux
    with step_scope("ffn"):
        hidden = xn @ layer["w1"].astype(cfg.dtype)
        if cfg.ffn == "swiglu":
            hidden = (jax.nn.silu(hidden)
                      * (xn @ layer["w3"].astype(cfg.dtype)))
        else:
            hidden = jax.nn.gelu(hidden)
        return (hidden @ layer["w2"].astype(cfg.dtype),
                jnp.asarray(0.0, jnp.float32))


def _embed_in(cfg, embed, pos, tokens, pos_offset=0) -> jnp.ndarray:
    """Token (+ learned position) embedding in activation dtype — shared by
    apply and the pipeline-parallel path. Rotary configs have no table:
    their positions enter in the block."""
    if cfg.pos != "learned":
        return embed[tokens].astype(cfg.dtype)
    idx = pos_offset + jnp.arange(tokens.shape[1])
    return (embed[tokens] + pos[idx]).astype(cfg.dtype)


# ---------------------------------------------------------------------------
# Sequence-parallel training step (the long-context path)
# ---------------------------------------------------------------------------

def _lm_targets_and_mask(tokens: jnp.ndarray):
    """Global next-token targets + loss mask, built BEFORE sharding so a
    shard's last position targets the next shard's first token."""
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1
    )
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:], jnp.float32),
         jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1
    )
    return targets, mask


def _masked_ce(logits, targets, mask, psum_axes):
    """Masked mean next-token cross-entropy, psum-reduced over the sharded
    batch/sequence axes."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    tot = lax.psum((-ll * mask).sum(), psum_axes)
    cnt = lax.psum(mask.sum(), psum_axes)
    return tot / cnt


def make_sp_train_step(
    model: TransformerLM,
    mesh,
    learning_rate: float = 0.1,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQ_AXIS,
    donate: bool = True,
):
    """Build a jitted SPMD train step: ``step(params, tokens) ->
    (new_params, loss)`` with batch over ``data_axis`` and sequence over
    ``seq_axis`` (ring attention). ``tokens`` is the GLOBAL [B, S] array;
    params are replicated and stay replicated (grad psum over both axes).
    """
    axes = (data_axis, seq_axis)
    model.config.require_classic_block("make_sp_train_step")

    def local_step(params, tokens, targets, mask):
        S_loc = tokens.shape[1]
        offset = lax.axis_index(seq_axis) * S_loc

        def loss_fn(p):
            logits, aux = model._apply_with_aux(
                p, tokens, axis_name=seq_axis, pos_offset=offset
            )
            loss = _masked_ce(logits, targets, mask, axes)
            if model.config.moe_experts:
                # aux is per-shard (each shard routes its local tokens):
                # mean over shards keeps the weight comparable to the
                # single-device objective
                loss = loss + model.config.moe_aux_weight \
                    * lax.pmean(aux, axes)
            return loss

        # Params enter replicated (unvarying) and the loss is psum-reduced,
        # so shard_map's typed autodiff already inserts the cross-device
        # gradient psum during transposition — grads come back replicated.
        # (An explicit psum here would multiply the gradient by the device
        # count.)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params = jax.tree.map(
            lambda p, g: p - learning_rate * g.astype(p.dtype), params, grads
        )
        return new_params, loss

    tok_spec = P(data_axis, seq_axis)

    # donate=True (default): the update aliases params in place instead of
    # holding old AND new parameter buffers live across the step (2x param
    # HBM on TPU). Callers needing the pre-step params pass donate=False.
    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(params, tokens):
        targets, mask = _lm_targets_and_mask(tokens)
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), tok_spec, tok_spec, tok_spec),
            out_specs=(P(), P()),
        )(params, tokens, targets, mask)

    return step


# ---------------------------------------------------------------------------
# Combined data x sequence x tensor parallel training step
# ---------------------------------------------------------------------------

def tp_param_specs(n_layers: int, model_axis: str) -> Dict[str, Any]:
    """PartitionSpec tree for the tensor-parallel param layout (wqkv split
    into wq/wk/wv): Megatron-style column-parallel in-projections
    (``P(None, model)``) and row-parallel out-projections
    (``P(model, None)``); everything else replicated."""
    layer = {
        "ln1": P(), "ln2": P(),
        "wq": P(None, model_axis), "wk": P(None, model_axis),
        "wv": P(None, model_axis),
        "wo": P(model_axis, None),
        "w1": P(None, model_axis), "w2": P(model_axis, None),
    }
    return {
        "embed": P(), "pos": P(), "ln_f": P(),
        "layers": [dict(layer) for _ in range(n_layers)],
    }


def to_tp_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Convert the LM's packed-wqkv param tree to the TP layout (wq/wk/wv
    separate so each can shard cleanly on its output dim)."""
    layers = []
    for layer in params["layers"]:
        wq, wk, wv = jnp.split(layer["wqkv"], 3, axis=-1)
        layers.append({
            "ln1": layer["ln1"], "ln2": layer["ln2"],
            "wq": wq, "wk": wk, "wv": wv,
            "wo": layer["wo"], "w1": layer["w1"], "w2": layer["w2"],
        })
    return {"embed": params["embed"], "pos": params["pos"],
            "ln_f": params["ln_f"], "layers": layers}


def make_parallel_train_step(
    model: TransformerLM,
    mesh,
    learning_rate: float = 0.1,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQ_AXIS,
    model_axis: str = "model",
    donate: bool = True,
):
    """Build the full 3-axis SPMD train step: batch over ``data_axis``,
    sequence over ``seq_axis`` (ring attention), and tensor parallelism
    over ``model_axis`` (column-parallel wq/wk/wv+w1 with heads split
    across shards, row-parallel wo/w2 with a psum back to replicated
    activations — the Megatron decomposition, expressed in shard_map so
    XLA schedules every collective on ICI).

    Returns ``(step, shard_params)``: ``shard_params(params)`` places a
    replicated param tree into the TP layout/sharding; ``step(tp_params,
    tokens) -> (new_tp_params, loss)`` takes the GLOBAL token matrix.

    Gradient flow: the loss is psum-reduced over (data, seq); TP-sharded
    leaves get their gradients locally (each shard owns its slice), while
    replicated leaves (embeddings, norms) are transposed through the
    forward psums, so shard_map's typed autodiff inserts the model-axis
    gradient psum exactly where the math needs it.
    """
    cfg = model.config
    from jax.sharding import NamedSharding

    cfg.require_classic_block("make_parallel_train_step")
    if cfg.moe_experts:
        raise ValueError(
            "make_parallel_train_step is dense-only (its Megatron sharding "
            "splits w1/w2 over the model axis; MoE layers have no w1/w2) — "
            "train MoE configs with the single-device or sp steps, or run "
            "moe_ffn under expert parallelism directly"
        )
    tp = mesh.shape.get(model_axis, 1)
    if cfg.n_heads % tp:
        raise ValueError(f"n_heads {cfg.n_heads} must divide by tensor "
                         f"parallelism {tp}")
    if cfg.d_ff % tp or cfg.d_model % tp:
        raise ValueError("d_model and d_ff must divide by tensor parallelism")
    h_loc, hd = cfg.n_heads // tp, cfg.head_dim
    sp = mesh.shape.get(seq_axis, 1)
    if cfg.sp_attn == "a2a" and h_loc % sp:
        raise ValueError(
            f"sp_attn='a2a' needs per-TP-shard heads ({h_loc}) divisible by "
            f"the sequence axis ({sp})"
        )
    sp_attn_fn = a2a_attention if cfg.sp_attn == "a2a" else ring_attention
    specs = tp_param_specs(cfg.n_layers, model_axis)
    # PartitionSpec subclasses tuple, hence the is_leaf guard.
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )

    def shard_params(params: Dict[str, Any]) -> Dict[str, Any]:
        # device_put validates the tree structures match, so a param leaf
        # missing from tp_param_specs errors instead of mis-pairing.
        return jax.device_put(to_tp_params(params), shardings)

    def local_apply(p, tokens, offset):
        B, S = tokens.shape
        dtype = cfg.dtype
        x = (p["embed"][tokens] + p["pos"][offset + jnp.arange(S)]).astype(dtype)
        for layer in p["layers"]:
            xn = _norm(x, layer["ln1"].astype(dtype))
            to_heads = lambda t: t.reshape(B, S, h_loc, hd).transpose(0, 2, 1, 3)
            o = sp_attn_fn(
                to_heads(xn @ layer["wq"].astype(dtype)),
                to_heads(xn @ layer["wk"].astype(dtype)),
                to_heads(xn @ layer["wv"].astype(dtype)),
                axis_name=seq_axis, causal=True,
            )
            o = o.transpose(0, 2, 1, 3).reshape(B, S, h_loc * hd)
            # row-parallel out-projection: partial sums -> replicated x
            x = x + lax.psum(o @ layer["wo"].astype(dtype), model_axis)
            xn = _norm(x, layer["ln2"].astype(dtype))
            hidden = jax.nn.gelu(xn @ layer["w1"].astype(dtype))
            x = x + lax.psum(hidden @ layer["w2"].astype(dtype), model_axis)
        x = _norm(x, p["ln_f"].astype(dtype))
        return x.astype(jnp.float32) @ p["embed"].T

    def local_step(p, tokens, targets, mask):
        S_loc = tokens.shape[1]
        offset = lax.axis_index(seq_axis) * S_loc

        def loss_fn(p):
            logits = local_apply(p, tokens, offset)
            return _masked_ce(logits, targets, mask, (data_axis, seq_axis))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p = jax.tree.map(
            lambda w, g: w - learning_rate * g.astype(w.dtype), p, grads
        )
        return new_p, loss

    tok_spec = P(data_axis, seq_axis)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())  # see make_sp_train_step
    def step(tp_params, tokens):
        targets, mask = _lm_targets_and_mask(tokens)
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, tok_spec, tok_spec, tok_spec),
            out_specs=(specs, P()),
        )(tp_params, tokens, targets, mask)

    return step, shard_params


def make_ep_train_step(
    model: TransformerLM,
    mesh,
    learning_rate: float = 0.1,
    data_axis: str = DATA_AXIS,
    donate: bool = True,
):
    """Expert+data-parallel train step for MoE configs: the batch shards
    over ``data_axis`` and the SAME axis carries expert parallelism — each
    shard owns ``moe_experts / shards`` experts (MoE params sharded on
    their leading expert dim) and token buckets move to their expert's
    device and back via ``all_to_all`` over ICI (models/moe.py). Dense
    layers and attention run data-parallel; non-expert params stay
    replicated with the gradient psum inserted by shard_map's typed
    autodiff. Returns ``(step, shard_params)``."""
    from jax.sharding import NamedSharding

    cfg = model.config
    cfg.require_classic_block("make_ep_train_step")
    ep = mesh.shape[data_axis]
    if not cfg.moe_experts:
        raise ValueError("make_ep_train_step needs an MoE config "
                         "(moe_experts > 0); use the dp/sp steps for dense")
    if cfg.moe_experts % ep:
        raise ValueError(f"moe_experts {cfg.moe_experts} must divide by the "
                         f"{data_axis} axis size {ep}")

    rep = NamedSharding(mesh, P())
    exp = NamedSharding(mesh, P(data_axis))

    def param_specs(params):
        """ONE spec tree drives both placement and the shard_map in/out
        specs — deriving them separately would let the two layouts drift."""
        specs = jax.tree.map(lambda _: P(), params)
        for spec_layer, layer in zip(specs["layers"], params["layers"]):
            if "moe" in layer:
                spec_layer["moe"]["w1"] = P(data_axis)
                spec_layer["moe"]["w2"] = P(data_axis)
        return specs

    def shard_params(params):
        shardings = jax.tree.map(
            lambda s: exp if s == P(data_axis) else rep, param_specs(params),
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.device_put(params, shardings)

    def local_step(params, tokens, targets, mask):
        def loss_fn(p):
            logits, aux = model._apply_with_aux(p, tokens,
                                                moe_axis=data_axis)
            loss = _masked_ce(logits, targets, mask, (data_axis,))
            return loss + cfg.moe_aux_weight * lax.pmean(aux, data_axis)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree.map(
            lambda p, g: p - learning_rate * g.astype(p.dtype), params, grads
        )
        return new, loss

    tok_spec = P(data_axis)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(params, tokens):
        targets, mask = _lm_targets_and_mask(tokens)
        specs = param_specs(params)
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, tok_spec, tok_spec, tok_spec),
            out_specs=(specs, P()),
        )(params, tokens, targets, mask)

    return step, shard_params


def make_pp_train_step(
    model: TransformerLM,
    mesh,
    learning_rate: float = 0.1,
    num_microbatches: Optional[int] = None,
    stage_axis: str = "stage",
    donate: bool = True,
):
    """Pipeline-parallel train step: the LM's blocks split into S
    contiguous stages over ``mesh``'s ``stage`` axis (GPipe microbatching,
    parallel/pipeline.py); embed/positions/final-norm stay replicated and
    run outside the pipeline. Returns ``(step, shard_params)``:
    ``shard_params(params)`` converts an ordinary init tree into the
    stage-stacked, stage-sharded layout; ``step(pp_params, tokens) ->
    (new_pp_params, loss)`` is one jitted SPMD program whose inter-stage
    activation transfers are ppermutes riding ICI."""
    from jax.sharding import NamedSharding

    from harmony_tpu.parallel.pipeline import make_pipeline_fn

    cfg = model.config
    cfg.require_classic_block("make_pp_train_step")
    if cfg.moe_experts:
        raise ValueError(
            "make_pp_train_step needs homogeneous layers to stage-stack; "
            "MoE configs interleave two layer structures — use the sp/dp "
            "steps (or set moe_experts=0)"
        )
    S = mesh.shape[stage_axis]
    if cfg.n_layers % S:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible into "
                         f"{S} pipeline stages")
    lps = cfg.n_layers // S

    def stage_fn(stage_layers, x):
        # stage_layers leaves are [layers_per_stage, ...]: apply in order
        def body(x, layer):
            return model._block(x, layer, None)[0], None

        x, _ = lax.scan(body, x, stage_layers)
        return x

    pipe = make_pipeline_fn(stage_fn, mesh, axis_name=stage_axis,
                            num_microbatches=num_microbatches)

    def to_pp(params):
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *params["layers"])
        stages = jax.tree.map(
            lambda a: a.reshape(S, lps, *a.shape[1:]), stacked
        )
        return {"embed": params["embed"], "pos": params["pos"],
                "ln_f": params["ln_f"], "stages": stages}

    rep = NamedSharding(mesh, P())
    staged = NamedSharding(mesh, P(stage_axis))

    def shard_params(params):
        pp = to_pp(params)
        # one device_put over a sharding pytree: structure mismatches error
        # instead of silently mis-pairing leaves
        shardings = {
            "embed": rep, "pos": rep, "ln_f": rep,
            "stages": jax.tree.map(lambda _: staged, pp["stages"]),
        }
        return jax.device_put(pp, shardings)

    def loss_fn(pp, tokens):
        inp, targets = tokens[:, :-1], tokens[:, 1:]
        x = _embed_in(cfg, pp["embed"], pp["pos"], inp)
        h = pipe(pp["stages"], x)
        h = _norm(h, pp["ln_f"].astype(cfg.dtype))
        logits = h.astype(jnp.float32) @ pp["embed"].T  # weight-tied readout
        return _next_token_ce(logits, targets)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(pp, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(pp, tokens)
        new = jax.tree.map(
            lambda p, g: p - learning_rate * g.astype(p.dtype), pp, grads
        )
        return new, loss

    return step, shard_params


def load_text_tokens(
    path: str, seq_len: int, num_seqs: int = 0, vocab_size: int = 256
) -> np.ndarray:
    """Real-file LM data: byte-level tokenization of a text file into a
    [num_seqs, seq_len] int32 matrix (the LM counterpart of the classic
    apps' file loaders — usable as a JobConfig ``data_fn`` with
    ``data_args={"path": ..., "seq_len": ...}``).

    Bytes >= vocab_size fold modulo (byte-level needs vocab_size 256; a
    smaller vocab still trains, just lossily). ``num_seqs=0`` takes every
    whole window the file provides."""
    if vocab_size < 2:
        raise ValueError("vocab_size must be >= 2")
    if seq_len < 2:  # a next-token example needs at least 2 tokens
        raise ValueError(f"seq_len must be >= 2, got {seq_len}")
    if num_seqs < 0:
        raise ValueError(f"num_seqs must be >= 0, got {num_seqs}")
    raw = np.fromfile(path, np.uint8)
    total = raw.shape[0] // seq_len
    if total == 0:
        raise ValueError(
            f"{path}: {raw.shape[0]} bytes cannot fill one {seq_len}-token "
            "sequence"
        )
    if num_seqs and total < num_seqs:
        raise ValueError(
            f"{path}: holds {total} windows of {seq_len}, wanted {num_seqs}"
        )
    n = num_seqs or total
    toks = raw[: n * seq_len].reshape(n, seq_len).astype(np.int32)
    return toks % vocab_size


def make_lm_data(
    num_seqs: int, seq_len: int, vocab_size: int, seed: int = 0
) -> np.ndarray:
    """Synthetic learnable corpus: orderly token walks with noise (next
    token is predictable from the current one ~80% of the time), so
    cross-entropy falls measurably within a few epochs."""
    rng = np.random.default_rng(seed)
    step = rng.integers(1, 7, size=(num_seqs, 1))
    start = rng.integers(0, vocab_size, size=(num_seqs, 1))
    walk = (start + step * np.arange(seq_len)[None, :]) % vocab_size
    noise = rng.integers(0, vocab_size, size=walk.shape)
    take_noise = rng.random(walk.shape) < 0.2
    return np.where(take_noise, noise, walk).astype(np.int32)


# ---------------------------------------------------------------------------
# Trainer SPI integration (LM in the elastic PS table)
# ---------------------------------------------------------------------------

from harmony_tpu.models.pytree_trainer import PyTreeTrainer  # noqa: E402


class TransformerTrainer(PyTreeTrainer):
    """Train the LM through the framework's elastic-table substrate (see
    PyTreeTrainer for the row layout and optimizer-state sections). Batch =
    [B, S] int32 token matrix."""

    default_table_id = "lm-model"
    config_cls = TransformerConfig

    def build_model(self, config: TransformerConfig) -> TransformerLM:
        return TransformerLM(config)

    def loss_on_batch(self, params, batch):
        return self.loss_and_metrics_on_batch(params, batch)[0]

    def loss_and_metrics_on_batch(self, params, batch):
        if self.config.objective == "block_diffusion":
            # the whole tuple (tokens, masked, rate): the noise is data
            return self.model.loss_and_metrics(params, tuple(batch))
        tokens = batch[0] if isinstance(batch, (tuple, list)) else batch
        return self.model.loss_and_metrics(params, tokens)

    def init_global_settings(self, ctx) -> None:
        super().init_global_settings(ctx)
        from harmony_tpu.metrics import kda
        from harmony_tpu.tracing.span import current_job

        cfg = self.config
        kinds = cfg.layer_kinds()
        kda.note_layer_kinds(current_job() or "-", kinds, heads={
            kind: cfg.heads(kind) for kind in set(kinds) if kind != "moe"},
            loop_steps=cfg.loop_steps)

    def observe_step_vectors(self, job_id: str, vectors) -> None:
        if "moe_expert_tokens" in vectors:
            from harmony_tpu.metrics import moe

            moe.observe(job_id, vectors["moe_expert_tokens"],
                        self.config.dropless_cfg.experts_held,
                        self.config.moe_layers(),
                        null_slots=vectors.get("moe_null_slots"),
                        shared_gate=vectors.get("moe_shared_gate_mean"))
        if "diffusion_tokens" in vectors:
            from harmony_tpu.metrics import diffusion

            diffusion.observe(job_id, vectors["diffusion_tokens"])
        if "exit_mass" in vectors:
            from harmony_tpu.metrics import loop

            loop.observe(job_id, vectors["exit_mass"], vectors["ce_by_exit"])
        from harmony_tpu.metrics import kda

        for kind, stats in kda.STATS.items():  # the recurrent layers' pairs
            first, second = (f"{kind}_{stat}_mean" for stat in stats)
            if first in vectors:
                kinds = self.config.layer_kinds()
                kda.observe(job_id, vectors[first], vectors[second],
                            [i for i, k in enumerate(kinds) if k == kind],
                            kind=kind)
