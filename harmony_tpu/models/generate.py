"""Incremental decoding for the transformer LM — KV-cache generation.

TPU-first inference: the cache is a STATIC [B, H, max_seq, head_dim]
buffer per layer (XLA wants fixed shapes), each step writes its keys/
values at the current position with `dynamic_update_slice` and attends
over the whole buffer under a position mask, and the generation loop is
one `lax.scan` — a single compiled program for the entire continuation,
no per-token host round-trips.

Decode is memory-bound (one query row), so attention here is a plain
masked softmax over the cache — the flash kernel's tiling buys nothing
at query length 1.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from harmony_tpu.models.common import rms_norm as _norm

_NEG_INF = -1e30


def init_kv_cache(cfg, batch: int) -> Dict[str, jnp.ndarray]:
    """Per-layer K/V buffers, stacked over layers: [L, B, H, max_seq, hd]."""
    shape = (cfg.n_layers, batch, cfg.n_heads, cfg.max_seq, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def decode_step(model, params, cache, token: jnp.ndarray, pos: jnp.ndarray):
    """One token for the whole batch: ``token`` [B] int32 at position
    ``pos`` (scalar int32). Returns (logits [B, vocab] f32, new cache)."""
    cfg = model.config
    B = token.shape[0]
    h, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    x = (params["embed"][token] + params["pos"][pos]).astype(cfg.dtype)  # [B,d]

    # mask over cache positions: attend to <= pos only
    valid = (jnp.arange(cfg.max_seq) <= pos)[None, None, :]      # [1,1,S]

    # The stacked cache buffers update IN PLACE (one position per layer per
    # step): under a scan carry XLA aliases the buffer, so per-token HBM
    # traffic is the attention reads plus one row write — NOT a rebuild of
    # the whole [L,B,H,S,hd] stack (slicing layers out and re-stacking
    # would copy the full cache every token and dominate the decode).
    cache_k, cache_v = cache["k"], cache["v"]
    for i, layer in enumerate(params["layers"]):
        xn = _norm(x, layer["ln1"].astype(cfg.dtype))
        qkv = xn @ layer["wqkv"].astype(cfg.dtype)               # [B, 3d]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, h, 1, hd)
        cache_k = lax.dynamic_update_slice(
            cache_k, k.reshape(1, B, h, 1, hd), (i, 0, 0, pos, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, v.reshape(1, B, h, 1, hd), (i, 0, 0, pos, 0))
        ck = cache_k[i]                                          # [B,h,S,hd]
        cv = cache_v[i]
        s = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * (hd ** -0.5)    # [B,h,1,S]
        s = jnp.where(valid[None], s, _NEG_INF)
        o = jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(s, axis=-1),
                       cv.astype(jnp.float32)).astype(cfg.dtype)
        x = x + o.reshape(B, d) @ layer["wo"].astype(cfg.dtype)
        xn = _norm(x, layer["ln2"].astype(cfg.dtype))
        x = x + _ffn(cfg, layer, xn)
    xf = _norm(x, params["ln_f"].astype(cfg.dtype))
    logits = xf.astype(jnp.float32) @ params["embed"].T          # [B, vocab]
    return logits, {"k": cache_k, "v": cache_v}


def _ffn(cfg, layer, xn):
    """Decode-side FFN: the shared dense/MoE dispatch with NO-DROP expert
    capacity (per-step batches are tiny; the training capacity factor
    would drop tokens whenever two rows pick one expert)."""
    from harmony_tpu.models.transformer import ffn_apply

    return ffn_apply(cfg, layer, xn, no_drop=True)[0]


def prefill(model, params, cache, prompt: jnp.ndarray):
    """Fill the cache from the whole prompt in ONE batched causal forward
    (per-token prefill would cost prompt_len sequential 1-query dispatches
    at ~zero MXU utilization). Mirrors TransformerLM.apply's block math but
    writes every layer's K/V into the cache and returns the LAST position's
    logits — the state generation continues from."""
    cfg = model.config
    B, P = prompt.shape
    h, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    x = (params["embed"][prompt]
         + params["pos"][jnp.arange(P)]).astype(cfg.dtype)        # [B,P,d]
    q_pos = jnp.arange(P)[:, None]
    causal = (q_pos >= jnp.arange(P)[None, :])[None, None]        # [1,1,P,P]
    cache_k, cache_v = cache["k"], cache["v"]
    for i, layer in enumerate(params["layers"]):
        xn = _norm(x, layer["ln1"].astype(cfg.dtype))
        qkv = xn @ layer["wqkv"].astype(cfg.dtype)                # [B,P,3d]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(B, P, h, hd).transpose(0, 2, 1, 3)
        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
        cache_k = lax.dynamic_update_slice(
            cache_k, kh[None], (i, 0, 0, 0, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, vh[None], (i, 0, 0, 0, 0))
        s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * (hd ** -0.5)
        s = jnp.where(causal, s, _NEG_INF)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                       vh.astype(jnp.float32)).astype(cfg.dtype)
        x = x + o.transpose(0, 2, 1, 3).reshape(B, P, d) \
            @ layer["wo"].astype(cfg.dtype)
        xn = _norm(x, layer["ln2"].astype(cfg.dtype))
        x = x + _ffn(cfg, layer, xn)
    xf = _norm(x[:, -1], params["ln_f"].astype(cfg.dtype))
    logits = xf.astype(jnp.float32) @ params["embed"].T           # [B,V]
    return logits, {"k": cache_k, "v": cache_v}


def make_generate_fn(model, prompt_len: int, num_new: int,
                     temperature: float = 0.0):
    """Build a jitted ``generate(params, prompt [B, prompt_len], key) ->
    tokens [B, prompt_len + num_new]``.

    One compiled program: a single batched prefill forward fills the cache
    from the prompt, then a decode scan samples ``num_new`` tokens (greedy
    at temperature 0). ``prompt_len + num_new`` must fit
    ``config.max_seq``."""
    cfg = model.config
    if cfg.objective == "block_diffusion":
        raise ValueError(
            "make_generate_fn samples left to right, one token a sequence a "
            "step; a block-diffusion model generates by denoising a whole "
            "block over several steps against a block-wise cache, a serving "
            "path this repo does not have (ROADMAP.md Queue 2)")
    cfg.require_classic_block("make_generate_fn")
    total = prompt_len + num_new
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt_len + num_new = {total} exceeds max_seq {cfg.max_seq}"
        )

    def pick(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1
        ).astype(jnp.int32)

    def generate(params, prompt, key: Optional[jax.Array] = None):
        B = prompt.shape[0]
        if key is None:
            key = jax.random.PRNGKey(0)
        cache = init_kv_cache(cfg, B)
        logits, cache = prefill(model, params, cache,
                                prompt.astype(jnp.int32))

        def decode(carry, step_key):
            cache, logits, pos = carry
            tok = pick(logits, step_key)
            new_logits, cache = decode_step(model, params, cache, tok, pos)
            return (cache, new_logits, pos + 1), tok

        keys = jax.random.split(key, num_new)
        (_, _, _), out = lax.scan(
            decode, (cache, logits, jnp.int32(prompt_len)), keys
        )
        return jnp.concatenate([prompt.astype(jnp.int32), out.T], axis=1)

    return jax.jit(generate)
