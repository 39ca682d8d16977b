#!/usr/bin/env python
"""Microbenchmarks for the framework's data-plane primitives.

BASELINE.md's north-star metrics are (a) aggregate multi-tenant throughput
(bench.py at the repo root) and (b) ET push/pull bandwidth — this file
measures (b) plus the other primitives a capacity-planning reader needs:

  table      pull (all-gather of the sharded model) and push (delta fold)
             bandwidth through DenseTable.apply_step — the analogue of the
             reference's per-batch multiGetOrInit/multiUpdate path
             (SURVEY.md §3.2 PULL/PUSH TaskUnits).
  reshard    live migration cost: DenseTable.reshard between two mesh
             layouts, reported as bytes moved per second (the reference's
             MoveInitMsg/DataMsg block transfer, SURVEY.md §3.4).
  attention  flash vs naive attention wall time (the framework's Pallas
             kernel path where supported, jittable fallback elsewhere).
  multiget   host-path random-key multi_get/multi_update ops/sec (the
             sparse/irregular access path, e.g. embedding lookups).
  sparse     DeviceHashTable fused pull/push keys/sec — the hash-backed
             embedding hot path (admission + gather + fold in one step).
  mxu        dense bf16 matmul achieved FLOP/s and MFU (fraction of the
             chip's peak) — the ceiling every MXU-shaped op is judged
             against (BASELINE.md measurement plan; per-batch analogue of
             the reference's metrics.avsc:164-201 compute records).
  ringflash  the ring-attention flash inner compiled under shard_map —
             correctness + speed vs the einsum inner (gates flipping
             ring_attention's inner='auto' to flash-on-TPU).
  stall      job stall during a live migration: an MLR job trains while
             an executor drains; reports the blocking move, the next
             epoch's relayout overhead, and bytes moved.
  chkp       two-stage checkpoint save/commit/restore throughput on a
             64 MB table (.blk v2 codec when the native lib is built;
             commit copies into staging then renames, so it is O(size)).

Attention also reports achieved FLOP/s + MFU. MFU is null off-TPU (no
meaningful peak). Run on the real chip and commit the JSON.

  roofline   ANALYTIC expected-performance model (v5e roofline) for every
             headline kernel at its bench shape — FLOPs, HBM bytes, AI,
             binding resource, expected-MFU range with stated basis. No
             device needed.

Run:  python benchmarks/micro.py [table|reshard|attention|multiget|sparse|mxu|ringflash|stall|chkp|roofline|all]

Each section prints one JSON line so results diff cleanly across rounds.
Uses whatever backend JAX is pointed at (set
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 for
the virtual multi-device mesh).
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

from harmony_tpu.config import TableConfig
from harmony_tpu.parallel import build_mesh
from harmony_tpu.table import DenseTable, TableSpec

from common import mfu, on_tpu, timed_chain, timed_inner  # noqa: E402 (shared helpers)

REPEATS = 10


def _mesh():
    devs = jax.devices()
    data = 2 if len(devs) % 2 == 0 and len(devs) > 1 else 1
    return build_mesh(devs, data=data)


def _time_chain(step, state):
    dt, _ = timed_chain(step, state, repeats=REPEATS)
    return dt


def _time_inner(body, state, inner: int = 32):
    # the inner fold amortizes per-program dispatch; off-TPU
    # interpret-mode kernels make big inner loops unaffordable — time
    # single programs there
    if not on_tpu():
        inner = 1
    dt, _ = timed_inner(body, state, inner=inner, outer=3)
    return dt


def bench_table() -> dict:
    """Pull+push bandwidth through one fused step over the job mesh."""
    mesh = _mesh()
    capacity, width = 16384, 256          # 16 MB model
    spec = TableSpec(TableConfig(
        table_id="bench", capacity=capacity, value_shape=(width,),
        num_blocks=64, update_fn="add",
    ))
    table = DenseTable(spec, mesh)
    model_bytes = capacity * width * 4

    def step(arr):
        model = spec.pull_all(arr)                 # PULL (all-gather)
        delta = model * 1e-6                       # touch every element
        return spec.push_all(arr, delta)           # PUSH (fold)

    dt = _time_inner(step, table.array)            # arr -> arr: chained
    gbps = 2 * model_bytes / dt / 1e9              # pulled + pushed
    return {"metric": "table pull+push bandwidth", "value": round(gbps, 2),
            "unit": "GB/s", "model_mb": model_bytes // 2**20,
            "devices": len(mesh.devices.flat)}


def bench_reshard() -> dict:
    """Live re-sharding cost between two mesh layouts."""
    devs = jax.devices()
    if len(devs) < 2:
        return {"metric": "reshard bandwidth", "value": None,
                "unit": "GB/s", "note": "needs >=2 devices"}
    capacity, width = 16384, 256
    spec = TableSpec(TableConfig(
        table_id="bench-rs", capacity=capacity, value_shape=(width,),
        num_blocks=64, update_fn="add",
    ))
    m1 = build_mesh(devs, data=1)
    m2 = build_mesh(devs, data=len(devs))
    table = DenseTable(spec, m1)
    model_bytes = capacity * width * 4
    t0 = time.perf_counter()
    n = 0
    for _ in range(REPEATS // 2):
        table.reshard(m2)
        table.reshard(m1)
        n += 2
    jax.block_until_ready(table.array)  # each reshard depends on the last: one chain
    dt = (time.perf_counter() - t0) / n
    return {"metric": "reshard bandwidth", "value": round(model_bytes / dt / 1e9, 2),
            "unit": "GB/s", "model_mb": model_bytes // 2**20,
            "devices": len(devs)}


def bench_attention() -> dict:
    """Framework attention kernel vs the naive O(S^2)-memory reference —
    bf16 operands at head_dim 128 (the MXU-native configuration the
    kernel is built for; the round-2 capture fed fp32 at d=64 and timed
    the casts, not the kernel), plus a (block_q, block_k) sweep so the
    reported number is the kernel's best config on THIS device."""
    from harmony_tpu.ops import flash_attention
    b, h, s, d = 4, 8, 2048, 128
    dt = jnp.bfloat16 if on_tpu() else jnp.float32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32).astype(dt)
    k = jax.random.normal(k2, (b, h, s, d), jnp.float32).astype(dt)
    v = jax.random.normal(k3, (b, h, s, d), jnp.float32).astype(dt)

    def naive(q, k, v):
        a = jnp.einsum("bhsd,bhtd->bhst", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        a = jnp.where(mask, a, -jnp.inf)
        p = jax.nn.softmax(a, -1).astype(v.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", p, v)

    # chain the query through the op (output shape == q shape): every
    # iteration is in the compiled loop's graph and q never re-uploads
    t_naive = _time_inner(lambda qq: naive(qq, k, v), q, inner=16)
    # causal attention FLOPs: QK^T + AV = 2 x 2bhs^2d, halved by the mask
    flops = 2 * b * h * s * s * d
    sweep = {}
    best_cfg, t_flash = None, None
    # off-TPU the kernel runs interpreted (python-level grid) — sweeping
    # 4 configs of meaningless numbers quadruples the CPU pass for nothing
    configs = ((256, 256), (256, 512), (512, 512), (512, 1024)) \
        if on_tpu() else ((256, 256),)
    for bq, bk in configs:
        if s % bq or s % bk:
            continue
        t = _time_inner(
            lambda qq, bq=bq, bk=bk: flash_attention(
                qq, k, v, causal=True, block_q=bq, block_k=bk,
                interpret=not on_tpu()),
            q, inner=16)
        sweep[f"{bq}x{bk}"] = {"ms": round(t * 1e3, 2),
                               "mfu": _mfu(flops / t)}
        if t_flash is None or t < t_flash:
            t_flash, best_cfg = t, (bq, bk)
    out = {"metric": "flash attention speedup vs naive", "seq": s,
           "head_dim": d, "dtype": str(dt.__name__),
           "value": round(t_naive / t_flash, 2), "unit": "x",
           "naive_ms": round(t_naive * 1e3, 1),
           "flash_ms": round(t_flash * 1e3, 1),
           "flash_tflops": round(flops / t_flash / 1e12, 2),
           "best_blocks": f"{best_cfg[0]}x{best_cfg[1]}",
           "block_sweep": sweep}
    out["flash_mfu"] = _mfu(flops / t_flash)
    return out


_mfu = mfu


def bench_ringflash() -> dict:
    """The ring-attention flash inner, COMPILED under shard_map.

    ring.py's inner='auto' stays on the einsum fold until this section has
    run green on a real chip (interpret mode is validated in tests; the
    compiled Mosaic-under-shard_map path is the open question). Runs on
    however many devices are visible — on the single chip it exercises the
    1-device ring (the kernel-under-shard_map mechanics without ppermute);
    on a virtual mesh it exercises the full rotation. Reports correctness
    vs the einsum inner plus both times."""
    from harmony_tpu.ops.ring import ring_self_attention

    devs = jax.devices()
    n = len(devs)
    mesh = build_mesh(devs, data=1, seq=n, model=1)
    b, h, d = 2, 4, 64
    s_loc = 512
    s = s_loc * n
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32)
    k = jax.random.normal(k2, (b, h, s, d), jnp.float32)
    v = jax.random.normal(k3, (b, h, s, d), jnp.float32)

    vma_kw = {} if on_tpu() else {"check_vma": False, "interpret": True}
    flash_fn = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, mesh, seq_axis="seq", causal=True, inner="flash", **vma_kw))
    einsum_fn = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, mesh, seq_axis="seq", causal=True, inner="einsum"))
    try:
        err = float(jnp.abs(flash_fn(q, k, v).astype(jnp.float32)
                            - einsum_fn(q, k, v).astype(jnp.float32)).max())
        if on_tpu():
            # fold 8 rings into one program: amortizes per-program
            # dispatch (separate compile from the err check)
            t_f = _time_inner(lambda qq: ring_self_attention(
                qq, k, v, mesh, seq_axis="seq", causal=True, inner="flash",
                **vma_kw), q, inner=8)
            t_e = _time_inner(lambda qq: ring_self_attention(
                qq, k, v, mesh, seq_axis="seq", causal=True, inner="einsum"),
                q, inner=8)
        else:
            # off-TPU reuse the fns the err check already compiled (the
            # interpret-mode flash compile is expensive)
            t_f, _ = timed_chain(lambda qq: flash_fn(qq, k, v), q, repeats=3)
            t_e, _ = timed_chain(lambda qq: einsum_fn(qq, k, v), q, repeats=3)
    except Exception as e:  # a red section must still be a JSON line
        return {"metric": "ring flash inner (compiled shard_map)",
                "value": None, "unit": "x vs einsum inner",
                "devices": n, "seq": s,
                "error": f"{type(e).__name__}: {e}"[:400]}
    return {"metric": "ring flash inner (compiled shard_map)",
            "value": round(t_e / t_f, 2), "unit": "x vs einsum inner",
            "devices": n, "seq": s, "max_abs_err": err,
            "flash_ms": round(t_f * 1e3, 1), "einsum_ms": round(t_e * 1e3, 1),
            "ok": err < 5e-3}


def bench_mxu() -> dict:
    """Dense bf16 matmul MFU — the roofline every MXU op is judged by."""
    n = 4096
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (n, n), jnp.bfloat16)
    b = jax.random.normal(k2, (n, n), jnp.bfloat16)
    # chain a through the product, rescaled so bf16 never overflows; the
    # elementwise scale fuses into the matmul epilogue (FLOPs still 2n^3)
    scale = jnp.bfloat16(1.0 / np.sqrt(n))
    dt = _time_inner(lambda aa: (aa @ b) * scale, a, inner=64)
    flops = 2 * n * n * n
    return {"metric": "mxu_dot bf16 achieved", "value": round(flops / dt / 1e12, 2),
            "unit": "TFLOP/s", "n": n, "mfu": _mfu(flops / dt)}


def bench_attnbwd() -> dict:
    """Flash attention BACKWARD — the Pallas dQ/dK/dV kernels
    (ops/attention.py custom_vjp) vs autodiff through the naive O(S^2)
    reference, same shape/dtype policy as the forward section. Times a
    full grad step (fwd + bwd) for both; the bwd-only cost is the grad
    time minus the matching forward time. Roofline expectation:
    benchmarks/micro.py roofline 'flash_bwd' (20-40% MFU)."""
    from harmony_tpu.ops import flash_attention
    b, h, s, d = 4, 8, 2048, 128
    if not on_tpu():
        # interpreted Pallas backward at s=2048 costs minutes of python
        # grid loops; keep the section runnable everywhere (numbers off
        # TPU are mechanics-smoke only — the bundle excludes them)
        b, h, s = 1, 2, 512
    dt = jnp.bfloat16 if on_tpu() else jnp.float32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32).astype(dt)
    k = jax.random.normal(k2, (b, h, s, d), jnp.float32).astype(dt)
    v = jax.random.normal(k3, (b, h, s, d), jnp.float32).astype(dt)

    def naive(q, k, v):
        a = jnp.einsum("bhsd,bhtd->bhst", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        a = jnp.where(mask, a, -jnp.inf)
        p = jax.nn.softmax(a, -1).astype(v.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", p, v)

    def loss_of(fn):
        # mean keeps the cotangent O(1) so bf16 grads stay in range
        return lambda qq, kk, vv: jnp.mean(
            fn(qq, kk, vv).astype(jnp.float32))

    grad_naive = jax.grad(loss_of(naive), argnums=(0, 1, 2))
    grad_flash = jax.grad(
        loss_of(lambda qq, kk, vv: flash_attention(
            qq, kk, vv, causal=True, interpret=not on_tpu())),
        argnums=(0, 1, 2))

    def chain(gfn):
        # chain q through its own grad so iterations stay in-graph
        return lambda qq: gfn(qq, k, v)[0].astype(dt)

    t_naive = _time_inner(chain(grad_naive), q, inner=8)
    t_flash = _time_inner(chain(grad_flash), q, inner=8)
    # grad step = fwd + bwd; standard accounting: bwd = 2.5x fwd FLOPs
    fwd_flops = 2 * b * h * s * s * d
    step_flops = int(3.5 * fwd_flops)
    return {"metric": "flash attention BACKWARD (grad step) vs naive",
            "seq": s, "head_dim": d, "dtype": str(dt.__name__),
            "value": round(t_naive / t_flash, 2), "unit": "x",
            "naive_grad_ms": round(t_naive * 1e3, 1),
            "flash_grad_ms": round(t_flash * 1e3, 1),
            "flash_grad_tflops": round(step_flops / t_flash / 1e12, 2),
            "flash_grad_mfu": _mfu(step_flops / t_flash)}


def bench_roofline() -> dict:
    """ANALYTIC roofline for every headline kernel at its bench shape —
    no device needed, so the expected numbers stand next to whatever
    has not been measured yet.

    Machine model (v5e, public spec): 197 bf16 TFLOP/s peak, 819 GB/s
    HBM — ridge at ~240 FLOP/byte. For each kernel: FLOPs, minimum HBM
    traffic, arithmetic intensity, the binding resource, the roofline
    wall time, and an expected-MFU RANGE whose basis is stated (pure
    roofline for clean matmuls; a derated range for kernels whose inner
    loop interleaves VPU work between MXU ops). When a chip capture
    exists, the measured section stands next to this model; until then
    THIS is the claim the kernels are built to."""
    PEAK = 197e12          # v5e dense bf16 FLOP/s (utils/platform._PEAK_BF16)
    BW = 819e9             # v5e HBM GB/s (public spec sheet)
    ridge = PEAK / BW

    def entry(flops, bytes_, eff_lo, eff_hi, basis):
        ai = flops / bytes_
        bound = "compute" if ai >= ridge else "memory"
        # roofline time at 100% efficiency of the binding resource
        t_roof = max(flops / PEAK, bytes_ / BW)
        # expected wall = roofline / efficiency; expected MFU uses the
        # FLOP clock even for memory-bound kernels (how MFU is reported)
        t_lo, t_hi = t_roof / eff_hi, t_roof / eff_lo
        return {
            "flops": round(flops / 1e9, 2), "gflops_unit": "GFLOP",
            "hbm_mb": round(bytes_ / 1e6, 1),
            "ai_flop_per_byte": round(ai, 1),
            "bound": bound,
            "roofline_ms": round(t_roof * 1e3, 3),
            "expected_ms": [round(t_lo * 1e3, 3), round(t_hi * 1e3, 3)],
            "expected_mfu": [round(flops / t_hi / PEAK, 3),
                             round(flops / t_lo / PEAK, 3)],
            "basis": basis,
        }

    kernels = {}
    # -- mxu: 4096^3 bf16 matmul (bench_mxu's shape) ---------------------
    n = 4096
    kernels["mxu_dot_4096"] = entry(
        2 * n**3, 3 * n * n * 2, 0.80, 0.95,
        "aligned 4096-cube bf16 matmul: MXU-tiled perfectly; large "
        "published XLA matmuls land 80-95% of peak")
    # -- flash attention fwd (bench_attention's shape) -------------------
    b, h, s, d = 4, 8, 2048, 128
    att_flops = 2 * b * h * s * s * d  # QK^T + AV, halved by causal mask
    att_bytes = 4 * b * h * s * d * 2  # q,k,v,o once each, bf16
    kernels["flash_fwd_b4h8_s2048_d128"] = entry(
        att_flops, att_bytes, 0.25, 0.50,
        "two MXU matmuls per tile with a VPU softmax (max/exp/rescale) "
        "between them; d=128 keeps the MXU fed. Public TPU flash "
        "kernels at this shape land 25-50% of peak; >=25% fwd MFU is "
        "the round-5 acceptance bar (3x+ over the measured r02 naive)")
    # -- flash attention bwd (ops/attention.py backward kernels) ---------
    kernels["flash_bwd_b4h8_s2048_d128"] = entry(
        int(2.5 * att_flops), int(1.75 * att_bytes), 0.20, 0.40,
        "dQ/dK/dV recompute-style backward = 2.5x fwd FLOPs (5 matmuls "
        "per tile vs 2), heavier VPU mixing -> derate below fwd")
    # -- 190M LM train step (benchmarks/lm.py train100m config) ----------
    params, seq, bsz = 190e6, 2048, 8
    lm_flops = 6 * params * seq * bsz  # fwd+bwd ~ 6*N per token
    lm_bytes = (2 * params * 2        # params read + grads written, bf16
                + 3 * bsz * seq * 512 * 2 * 24)  # rough activation traffic
    kernels["lm_190m_train_step"] = entry(
        lm_flops, int(lm_bytes), 0.25, 0.45,
        "transformer train step ~6N FLOPs/token; with remat + bf16 and "
        "d_model-scale matmuls the published XLA range on v5e is "
        "25-45% MFU; >=25% is the round-5 acceptance bar (r02 measured "
        "10.3% at 29.9M params - sub-MXU-size matmuls)")
    # -- table push: scatter vs MXU fold at bench_table's shape ----------
    cap, dim = 1 << 16, 256
    tbl_bytes = cap * dim * 4 * 3  # read + write table, read delta, fp32
    kernels["table_push_64k_x256"] = entry(
        2 * cap * dim, tbl_bytes, 0.50, 0.85,
        "pure streaming fold (1 MAC per element): memory-bound at "
        "AI<1; expected = 50-85% of HBM bandwidth")
    rows = {k: v for k, v in kernels.items()}
    return {"metric": "analytic roofline (v5e model)",
            "value": rows["flash_fwd_b4h8_s2048_d128"]["expected_mfu"][0],
            "unit": "min expected flash fwd MFU",
            "peak_bf16_tflops": PEAK / 1e12, "hbm_gbps": BW / 1e9,
            "ridge_flop_per_byte": round(ridge, 1),
            "kernels": rows,
            "note": ("analytic — carries the EXPECTED number for every "
                     "kernel not yet measured; measured sections "
                     "replace this as captures land")}


def bench_multiget() -> dict:
    """Host-path random-key access (sparse/irregular pulls)."""
    mesh = _mesh()
    capacity, width, nkeys = 65536, 64, 4096
    spec = TableSpec(TableConfig(
        table_id="bench-mg", capacity=capacity, value_shape=(width,),
        num_blocks=64, update_fn="add",
    ))
    table = DenseTable(spec, mesh)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, capacity, nkeys)
    deltas = rng.standard_normal((nkeys, width), dtype=np.float32)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        table.multi_get(keys)
        table.multi_update(keys, deltas)
    dt = (time.perf_counter() - t0) / REPEATS
    return {"metric": "host multi_get+multi_update", "value": round(2 * nkeys / dt),
            "unit": "keys/sec", "keys_per_call": nkeys}


def bench_sparse() -> dict:
    """Fused sparse pull/push on the DeviceHashTable — the embedding-table
    hot path (admission + gather + scatter-fold in ONE jitted step, keys
    from the full int32 domain)."""
    from harmony_tpu.table import DeviceHashTable, HashTableSpec

    mesh = _mesh()
    slots, width, nkeys = 262144, 64, 8192
    spec = HashTableSpec(TableConfig(
        table_id="bench-sp", capacity=slots, value_shape=(width,),
        num_blocks=64, is_ordered=False, update_fn="add", sparse=True,
    ))
    table = DeviceHashTable(spec, mesh)
    rng = np.random.default_rng(0)
    universe = rng.choice(2**31 - 3, size=4 * nkeys, replace=False) + 1
    keys = jnp.asarray(universe[rng.integers(0, 4 * nkeys, nkeys)], jnp.int32)
    deltas = jnp.asarray(
        rng.standard_normal((nkeys, width)), jnp.float32
    )

    def run(state):
        state, vals, token = spec.pull(state, keys)
        return spec.push(state, token, deltas + 0.0 * vals)

    dt = _time_inner(run, table.state, inner=16)
    row_bytes = width * 4
    return {"metric": "sparse table fused pull+push", "value": round(2 * nkeys / dt),
            "unit": "keys/sec", "keys_per_step": nkeys,
            "mb_per_step": round(2 * nkeys * row_bytes / 2**20, 1),
            "devices": len(mesh.devices.flat)}


def bench_stall() -> dict:
    """Job stall during a live migration (BASELINE.md measurement plan:
    're-sharding cost: blocks moved x bytes, job stall time during
    migration'). An MLR job trains over 2 executors; after a mid epoch,
    executor 0 DRAINS — all its blocks move to executor 1, shrinking the
    owning set so the table physically re-materializes on the new layout
    (a move that keeps the owning set is just an ownership-map edit; see
    TableHandle.move_blocks). Reported: the blocking move itself, the
    migrated-vs-clean epoch overhead (the next dispatch rebuilds for the
    new layout), and bytes moved."""
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.config.params import TrainerParams
    from harmony_tpu.dolphin import (
        TrainerContext, TrainingDataProvider, WorkerTasklet,
    )
    from harmony_tpu.metrics.collector import EpochMetrics, MetricCollector
    from harmony_tpu.parallel.mesh import DevicePool
    from harmony_tpu.runtime.master import ETMaster

    devs = jax.devices()
    if len(devs) < 2:
        return {"metric": "live migration stall", "value": None,
                "unit": "sec", "note": "needs >=2 devices"}
    master = ETMaster(DevicePool(devs[:2]))
    exs = master.add_executors(2)
    # the headline MLR shape (8 MB model) so the move transfers real bytes
    trainer = MLRTrainer(num_classes=256, num_features=8192,
                         features_per_partition=512)
    handle = master.create_table(trainer.model_table_config(),
                                 [e.id for e in exs])
    epochs, nb, mig_epoch = 9, 4, 4
    x, y = make_synthetic(512, num_features=8192, num_classes=256)
    spec = handle.table.spec
    row_bytes = int(np.prod(spec.value_shape)) * spec.dtype.itemsize
    moved = {}

    import threading

    def do_move():
        # drain ALL of ex0's blocks: the owning set shrinks, forcing the
        # physical re-materialization a partial move would skip. Runs on
        # its own thread — the production shape (the orchestrator moves
        # while workers train) — so the announce->prewarm->flip pipeline
        # overlaps training instead of being charged to the job.
            try:
            n_move = handle.block_manager.block_counts()[exs[0].id]
            t0 = time.perf_counter()
            handle.move_blocks(exs[0].id, exs[1].id, n_move)
            # sync INSIDE the timed region: device_put returns before bytes
            # move on async/lazy backends, and the transfer would otherwise
            # masquerade as the next epoch's relayout overhead
            jax.block_until_ready(handle.table.array)
            moved["sec"] = time.perf_counter() - t0
            moved["blocks"] = n_move
            moved["bytes"] = n_move * spec.block_size * row_bytes
            moved["owners_after"] = len(handle.owning_executors())
        except BaseException as e:  # noqa: BLE001 - surfaced below
            moved["error"] = f"{type(e).__name__}: {e}"

    mover = threading.Thread(target=do_move, name="stall-mover")

    def on_epoch(epoch):
        if epoch == mig_epoch:
            mover.start()

    walls: dict = {}
    collector = MetricCollector(
        sink=lambda m: walls.__setitem__(m.epoch_idx, m.epoch_time_sec)
        if isinstance(m, EpochMetrics) else None)
    worker = WorkerTasklet(
        "stall-bench",
        TrainerContext(params=TrainerParams(num_epochs=epochs,
                                            num_mini_batches=nb,
                                            comm_probe_period=0),
                       model_table=handle.table),
        trainer,
        TrainingDataProvider([x, y], nb),
        handle.table.mesh,
        collector=collector,
        epoch_callback=on_epoch,
    )
    worker.run()
    mover.join(timeout=120)
    if mover.is_alive():
        return {"metric": "live migration stall (job-observed excess wall)",
                "value": None, "unit": "sec", "error": "mover thread hung"}
    if "error" in moved:
        return {"metric": "live migration stall (job-observed excess wall)",
                "value": None, "unit": "sec",
                "error": f"move failed: {moved['error']}"}
    # JOB-OBSERVED stall: the excess wall time of the epochs overlapping
    # the migration (announce+prewarm+flip run on the mover thread; the
    # job pays only lock waits, the prewarm's device time, and whatever
    # relayout remains at the next rebuild). Clean epochs exclude epoch 0
    # (first-compile) and the migration-overlapped window.
    # every epoch from the trigger onward may overlap the mover thread;
    # clean epochs are strictly BEFORE it (minus the first-compile epoch)
    mig_window = tuple(range(mig_epoch, epochs))
    clean = [w for e, w in walls.items() if e not in (0, *mig_window)]
    clean_med = sorted(clean)[len(clean) // 2]
    stall = sum(max(walls[e] - clean_med, 0.0)
                for e in mig_window if e in walls)
    assert moved["owners_after"] == 1, "drain must shrink the owning set"
    return {
        "metric": "live migration stall (job-observed excess wall)",
        "value": round(stall, 3),
        "unit": "sec",
        "mover_wall_sec": round(moved["sec"], 3),
        "stall_vs_clean_epochs": round(stall / clean_med, 2),
        "blocks_moved": moved["blocks"],
        "bytes_moved": moved["bytes"],
        "clean_epoch_sec": round(clean_med, 3),
        "devices": 2,
    }


def bench_chkp() -> dict:
    """Two-stage checkpoint save/commit/restore throughput on a 64 MB
    table (the reference's ChkpManagerSlave temp->HDFS path; here the
    native .blk v2 codec + posix rename commit — SURVEY §3.5)."""
    import shutil
    import tempfile

    from harmony_tpu.checkpoint.manager import CheckpointManager
    from harmony_tpu.parallel.mesh import DevicePool
    from harmony_tpu.runtime.master import ETMaster

    devs = jax.devices()
    master = ETMaster(DevicePool(devs[: min(2, len(devs))]))
    exs = master.add_executors(min(2, len(devs)))
    capacity, width = 65536, 256                     # 64 MB fp32
    handle = master.create_table(
        TableConfig(table_id="bench-ck", capacity=capacity,
                    value_shape=(width,), num_blocks=64, update_fn="add"),
        [e.id for e in exs],
    )
    model_mb = capacity * width * 4 / 2**20
    from harmony_tpu import native
    # the table's device-side init must not bill to the stage timer
    jax.block_until_ready(handle.table.array)
    root = tempfile.mkdtemp(prefix="harmony-chkp-bench-")
    try:
        mgr = CheckpointManager(os.path.join(root, "temp"),
                                os.path.join(root, "commit"))
        t0 = time.perf_counter()
        cid = mgr.checkpoint(handle)                 # stage (device->disk)
        t_stage = time.perf_counter() - t0
        t0 = time.perf_counter()
        # durable commit: copies blocks into staging then renames — O(size)
        mgr.commit(cid)
        t_commit = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored = mgr.restore(master, cid, [e.id for e in exs],
                               table_id="bench-ck-r")
        np.asarray(restored.table.pull_array())      # force materialization
        t_restore = time.perf_counter() - t0
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {
        "metric": "checkpoint save/restore",
        "value": round(model_mb / t_stage, 1),
        "unit": "MB/s stage",
        "model_mb": round(model_mb),
        "codec": "blk" if native.available() else "npy",
        "stage_s": round(t_stage, 2),
        "commit_s": round(t_commit, 3),
        "restore_mbps": round(model_mb / t_restore, 1),
        "restore_s": round(t_restore, 2),
    }


SECTIONS = {
    "table": bench_table,
    "reshard": bench_reshard,
    "attention": bench_attention,
    "multiget": bench_multiget,
    "sparse": bench_sparse,
    "mxu": bench_mxu,
    "ringflash": bench_ringflash,
    "stall": bench_stall,
    "chkp": bench_chkp,
    "roofline": bench_roofline,
    "attnbwd": bench_attnbwd,
}
# reported metric name + unit per section, so ERROR lines land in the same
# metric series a success would (same keys a tracker would index on)
SECTION_METRICS = {
    "ringflash": ("ring flash inner (compiled shard_map)", "x vs einsum inner"),
    "table": ("table pull+push bandwidth", "GB/s"),
    "reshard": ("reshard bandwidth", "GB/s"),
    "attention": ("flash attention speedup vs naive", "x"),
    "multiget": ("host multi_get+multi_update", "keys/sec"),
    "sparse": ("sparse table fused pull+push", "keys/sec"),
    "mxu": ("mxu_dot bf16 achieved", "TFLOP/s"),
    "stall": ("live migration stall", "sec"),
    "chkp": ("checkpoint save/restore", "MB/s stage"),
    "roofline": ("analytic roofline (v5e model)", "min expected flash fwd MFU"),
    "attnbwd": ("flash attention BACKWARD (grad step) vs naive", "x"),
}


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which != "all" and which not in SECTIONS:
        sys.exit(f"unknown section {which!r}; have {sorted(SECTIONS)} or 'all'")
    names = list(SECTIONS) if which == "all" else [which]
    for name in names:
        print(json.dumps(SECTIONS[name]()))


if __name__ == "__main__":
    main()
