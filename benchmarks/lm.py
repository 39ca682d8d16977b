#!/usr/bin/env python
"""Flagship LM benchmarks: training tokens/sec + model FLOPs utilization.

The table apps carry the reference-parity headline (bench.py); this file
measures the framework's model path — the transformer LM whose attention
runs through the framework kernels (Pallas flash on TPU, blockwise
elsewhere):

  train   single-device train step: tokens/sec, model-FLOPs/sec, MFU
          (6*N*T approximation for the training FLOPs of an N-param
          decoder, + exact attention term).
  sp      sequence-parallel train step (ring attention over a data x seq
          mesh): tokens/sec on whatever devices are visible — the
          long-context path the reference has no counterpart for.

Prints one JSON line per section. Run on a chip, or
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python benchmarks/lm.py sp
for the virtual-mesh sanity pass (CPU numbers are not chip numbers).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np


from common import mfu as _mfu, on_tpu as _on_tpu, timed_chain  # noqa: E402 (shared helpers)


def _time_chain(step, state):
    dt, _ = timed_chain(step, state, repeats=5)
    return dt


def _param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def _train_flops(n_params: int, tokens: int, cfg) -> float:
    """~6*N per token (fwd 2N + bwd 4N) + the attention term 12*L*S*d per
    token (QK^T + AV fwd and bwd, causal-halved)."""
    return tokens * (6.0 * n_params
                     + 12.0 * cfg.n_layers * cfg.max_seq * cfg.d_model / 2)


def _model(on_tpu: bool, seq: int | None = None, layers: int | None = None):
    from harmony_tpu.models import TransformerConfig, TransformerLM

    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=8192, d_model=512, n_heads=8, n_layers=layers or 8,
            d_ff=2048, max_seq=seq or 1024, attn="auto", dtype=jnp.bfloat16,
        )
    else:
        # CPU sanity shapes: the chip-sized model needs >10s per step on a
        # laptop core — these validate the path, not the number
        cfg = TransformerConfig(
            vocab_size=1024, d_model=128, n_heads=4, n_layers=layers or 2,
            d_ff=512, max_seq=seq or 256, attn="auto", dtype=jnp.float32,
        )
    return cfg, TransformerLM(cfg)


def _run_train_bench(cfg, model, batch, inner, metric, on_tpu) -> dict:
    """Shared single-device train-step timing: one raw SGD step chained
    through timed_inner's fori_loop (the ONE compile is the timed program
    itself; the dependency chain keeps every iteration in the timed
    graph, and the fold amortizes per-program dispatch).
    Stderr markers separate compile time from run time in logs."""
    from harmony_tpu.models import make_lm_data

    from common import timed_inner

    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(make_lm_data(batch, cfg.max_seq, cfg.vocab_size))

    def raw_step(p):
        loss, grads = jax.value_and_grad(model.loss)(p, tokens)
        return jax.tree.map(lambda w, g: w - 0.1 * g.astype(w.dtype),
                            p, grads)

    n_params = _param_count(params)
    print(f"{metric}: compiling (params={n_params/1e6:.1f}M, "
          f"seq={cfg.max_seq}, batch={batch})...", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    dt, _ = timed_inner(raw_step, params, inner=inner, outer=3)
    print(f"{metric}: compiled+timed in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    n_tok = batch * cfg.max_seq
    flops = _train_flops(n_params, n_tok, cfg)
    out = {"metric": metric, "value": round(n_tok / dt),
           "unit": "tokens/sec", "params_m": round(n_params / 1e6, 1),
           "seq": cfg.max_seq, "batch": batch,
           "tflops": round(flops / dt / 1e12, 2), "mfu": _mfu(flops / dt)}
    if not on_tpu:
        out["note"] = "cpu sanity shapes — not a chip number"
    return out


def bench_train() -> dict:
    on_tpu = _on_tpu()
    cfg, model = _model(on_tpu)
    # realistic training batch: at batch 8 the 512-wide matmuls leave the
    # MXU mostly idle and the measured MFU reflects launch overhead, not
    # the model; 32x1024 tokens/step is a normal operating point
    return _run_train_bench(cfg, model, batch=32 if on_tpu else 2,
                            inner=8 if on_tpu else 1,
                            metric="lm train step", on_tpu=on_tpu)


def bench_train_100m() -> dict:
    """The SCALED flagship evidence (round-3): a ~190M-param decoder at
    seq 2048, bf16, head_dim 128, per-layer remat — the operating point
    where matmuls are large enough that MFU reflects the model, not
    launch overhead (the 29.9M/seq-1024 config measured 10.3%)."""
    from harmony_tpu.models import TransformerConfig, TransformerLM
    on_tpu = _on_tpu()
    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32768, d_model=1024, n_heads=8, n_layers=12,
            d_ff=4096, max_seq=2048, attn="auto", dtype=jnp.bfloat16,
            remat=True,
        )
        batch = 8
    else:
        # CPU sanity shape: validates the config path, not the number
        cfg = TransformerConfig(
            vocab_size=2048, d_model=256, n_heads=2, n_layers=2,
            d_ff=1024, max_seq=512, attn="auto", dtype=jnp.float32,
            remat=True,
        )
        batch = 2
    model = TransformerLM(cfg)
    out = _run_train_bench(cfg, model, batch=batch,
                           inner=4 if on_tpu else 1,
                           metric="lm train step (100M-class)",
                           on_tpu=on_tpu)
    out["remat"] = True
    return out


def bench_sp() -> dict:
    from harmony_tpu.models import make_lm_data
    from harmony_tpu.models.transformer import make_sp_train_step
    from harmony_tpu.parallel import build_mesh
    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return {"metric": "lm sp train step", "value": None,
                "unit": "tokens/sec", "note": "needs >=2 devices"}
    data_ax = 2 if n % 2 == 0 else 1
    seq_ax = n // data_ax
    on_tpu = _on_tpu()
    # long-context shape: sequence scales with the ring size
    per_shard = 1024 if on_tpu else 128
    cfg, model = _model(on_tpu, seq=per_shard * seq_ax, layers=4 if on_tpu else 1)
    mesh = build_mesh(devs, data=data_ax, seq=seq_ax, model=1)
    params = model.init(jax.random.PRNGKey(0))
    batch = (2 if on_tpu else 1) * data_ax
    tokens = jnp.asarray(make_lm_data(batch, cfg.max_seq, cfg.vocab_size))
    step = make_sp_train_step(model, mesh, learning_rate=0.1, donate=False)
    dt = _time_chain(lambda p: step(p, tokens)[0], params)
    n_tok = batch * cfg.max_seq
    out = {"metric": "lm sp train step", "value": round(n_tok / dt),
           "unit": "tokens/sec", "seq": cfg.max_seq, "batch": batch,
           "mesh": {"data": data_ax, "seq": seq_ax},
           "devices": n}
    if not on_tpu:
        out["note"] = "cpu sanity shapes — not a chip number"
    return out


def bench_decode() -> dict:
    """KV-cache generation throughput (models/generate.py): one compiled
    scan for the whole continuation, no per-token host round-trips."""
    from harmony_tpu.models import make_lm_data
    from harmony_tpu.models.generate import make_generate_fn
    on_tpu = _on_tpu()
    cfg, model = _model(on_tpu, seq=1024 if on_tpu else 128)
    params = model.init(jax.random.PRNGKey(0))
    batch = 8 if on_tpu else 2
    prompt_len = 32 if on_tpu else 8
    num_new = (cfg.max_seq - prompt_len) // 2
    prompt = jnp.asarray(make_lm_data(batch, prompt_len, cfg.vocab_size))
    gen = make_generate_fn(model, prompt_len, num_new)
    # chain: the next iteration's prompt is a slice of this one's output
    # (valid token ids, same shape) — keeps the loop in one device graph
    dt = _time_chain(lambda pr: gen(params, pr)[:, :prompt_len], prompt)
    # the prefill is per-token decode steps too, so the honest per-token
    # rate divides by ALL steps executed — not just the sampled ones
    # (num_new-only would skew with the prompt/continuation split)
    steps = prompt_len + num_new
    out = {"metric": "lm decode (kv cache)",
           "value": round(batch * steps / dt),
           "unit": "tokens/sec", "batch": batch, "prompt": prompt_len,
           "new_tokens": num_new,
           "ms_per_token": round(dt / steps * 1e3, 2)}
    if not on_tpu:
        out["note"] = "cpu sanity shapes — not a chip number"
    return out


def bench_pp() -> dict:
    """Pipeline-parallel train step (GPipe microbatching over a stage
    mesh, ppermute activations) — tokens/sec at 2 layers per stage."""
    from harmony_tpu.models import make_lm_data
    from harmony_tpu.models.transformer import make_pp_train_step
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return {"metric": "lm pp train step", "value": None,
                "unit": "tokens/sec", "note": "needs >=2 devices"}
    on_tpu = _on_tpu()
    # layers must split evenly into n stages
    cfg, model = _model(on_tpu, layers=2 * n)
    mesh = Mesh(np.asarray(devs, dtype=object).reshape(n), ("stage",))
    params = model.init(jax.random.PRNGKey(0))
    batch = (8 if on_tpu else 2) * n  # microbatch per stage
    tokens = jnp.asarray(make_lm_data(batch, cfg.max_seq, cfg.vocab_size))
    step, shard = make_pp_train_step(model, mesh, learning_rate=0.1,
                                     donate=False)
    pp_params = shard(params)
    dt = _time_chain(lambda p: step(p, tokens)[0], pp_params)
    n_tok = batch * cfg.max_seq
    out = {"metric": "lm pp train step", "value": round(n_tok / dt),
           "unit": "tokens/sec", "seq": cfg.max_seq, "batch": batch,
           "stages": n, "layers": cfg.n_layers}
    if not on_tpu:
        out["note"] = "cpu sanity shapes — not a chip number"
    return out


def bench_ep() -> dict:
    """Expert-parallel MoE train step (experts sharded over the data
    axis, all_to_all token routing) — tokens/sec."""
    from harmony_tpu.models import TransformerConfig, TransformerLM, make_lm_data
    from harmony_tpu.models.transformer import make_ep_train_step
    from harmony_tpu.parallel import build_mesh
    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return {"metric": "lm ep train step", "value": None,
                "unit": "tokens/sec", "note": "needs >=2 devices"}
    on_tpu = _on_tpu()
    base, _ = _model(on_tpu)
    import dataclasses

    # replace, not a field-by-field copy: ep must benchmark exactly the
    # model the other sections use, plus the MoE fields
    cfg = dataclasses.replace(base, moe_experts=2 * n, moe_every=2)
    model = TransformerLM(cfg)
    mesh = build_mesh(devs, data=n, model=1)
    step, shard = make_ep_train_step(model, mesh, learning_rate=0.1,
                                     donate=False)
    params = shard(model.init(jax.random.PRNGKey(0)))
    batch = (8 if on_tpu else 2) * n
    tokens = jnp.asarray(make_lm_data(batch, cfg.max_seq, cfg.vocab_size))
    dt = _time_chain(lambda p: step(p, tokens)[0], params)
    n_tok = batch * cfg.max_seq
    out = {"metric": "lm ep train step", "value": round(n_tok / dt),
           "unit": "tokens/sec", "seq": cfg.max_seq, "batch": batch,
           "experts": cfg.moe_experts, "devices": n}
    if not on_tpu:
        out["note"] = "cpu sanity shapes — not a chip number"
    return out


SECTIONS = {"train": bench_train, "train100m": bench_train_100m,
            "sp": bench_sp, "decode": bench_decode,
            "pp": bench_pp, "ep": bench_ep}


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which != "all" and which not in SECTIONS:
        sys.exit(f"unknown section {which!r}; have {sorted(SECTIONS)} or 'all'")
    names = list(SECTIONS) if which == "all" else [which]
    for name in names:
        print(json.dumps(SECTIONS[name]()))


if __name__ == "__main__":
    main()
