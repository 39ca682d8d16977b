#!/usr/bin/env python
"""On-chip smoke: the job path, end to end, on the TPU this machine has.

    python chip_smoke.py        # one process; uses every local TPU device

What it does, in ONE process (the process that owns the chips):

  1. device     platform / device_kind / count as JAX reports them, the chip's
                peaks row, whether the native layer is built, where the
                compile cache lives;
  2. sync       that ``jax.block_until_ready`` blocks (a chained run of large
                bf16 matmuls timed with it must not imply more than the
                chip's peak);
  3. kernels    every Pallas kernel COMPILED on the device (never
                ``interpret=True``) against its reference: ``gather_rows`` vs
                ``table[idx]``, ``scatter_add_rows`` vs ``.at[idx].add``,
                ``weighted_histogram`` vs ``xla_histogram``, flash attention
                forward and gradients vs ``blockwise_attention`` at the LM's
                shapes in bf16;
  4. jobs       starts the jobserver the way ``harmony-tpu start-jobserver``
                does, and submits over its TCP endpoint, through the jax-free
                client ``harmony-tpu submit`` uses: the BASELINE config-4
                trio (MLR + NMF + LDA) concurrently at widths whose
                per-sample work is large matmuls (MLR 8192 x 256, NMF rank
                256, LDA 8192 words x 64 topics), then the transformer LM
                (``LM_WIDTHS``; ``attn="auto"`` — which must trace the flash
                kernels) beside
                one keyed tenant whose 128-wide rows take the Pallas gather
                and the Pallas row scatter-add; WAIT, STATUS, SHUTDOWN. A few
                steps each; every tenant must step, stay finite and improve.

and, when the machine has four chips:

  5. sharded    an MLR tenant whose table spans all four devices: a quarter
                of the table's bytes on each, batches placed over the data
                axis, nothing on device 0 alone; then a LIVE 4 -> 2 -> 4
                reshard of the running tenant's table with values surviving;
  6. carve      two tenants at once under the ``carve`` scheduler on disjoint
                device pairs (the process-wide dispatch order under real
                concurrent collectives);
  7. dryrun     ``__graft_entry__``'s sections in-process on the real
                devices (ps / sp / dp x sp x tp / pp / ep). ``ps`` is the job
                path and must pass; the others are step factories beside it
                and are reported, not required.

Output: one JSON line per phase as it finishes, then — as the LAST line of
stdout — ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``. Exit 0 only if every required phase passed. When JAX
finds no TPU (``JAX_PLATFORMS=cpu`` included) it prints no result and exits
non-zero. Losses and timings here are smoke observations, not metrics.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO_ROOT)

#: per-job wall bound (compile included); the whole script must fit 1200 s
JOB_TIMEOUT_S = 600.0
#: sections of __graft_entry__ that are off the job path (reported only)
OPTIONAL_DRYRUN = ("sp", "dp_sp_tp", "pp", "ep")


# ---------------------------------------------------------------------------
# tenants
# ---------------------------------------------------------------------------

def _job(job_id: str, trainer: str, app_params: Dict[str, Any], data_fn: str,
         data_args: Dict[str, Any], *, epochs: int, batches: int,
         user: Optional[Dict[str, Any]] = None):
    from harmony_tpu.config.params import JobConfig, TrainerParams

    return JobConfig(
        job_id=job_id, app_type="dolphin", trainer=trainer,
        params=TrainerParams(num_epochs=epochs, num_mini_batches=batches,
                             comm_probe_period=6, app_params=app_params),
        num_workers=1,
        user={"data_fn": data_fn, "data_args": data_args, **(user or {})},
    )


def mlr_job(job_id: str = "smoke-mlr", *, n: int = 2048, features: int = 8192,
            classes: int = 256, fpp: int = 512, epochs: int = 3,
            batches: int = 4, user: Optional[Dict[str, Any]] = None):
    """The trio's MLR: 8192 features x 256 classes (dataset cut to n)."""
    return _job(
        job_id, "harmony_tpu.apps.mlr:MLRTrainer",
        {"num_classes": classes, "num_features": features,
         "features_per_partition": fpp, "step_size": 0.05},
        "harmony_tpu.apps.mlr:make_synthetic",
        {"n": n, "num_features": features, "num_classes": classes},
        epochs=epochs, batches=batches, user=user)


def nmf_job(job_id: str = "smoke-nmf", *, rows: int = 512, cols: int = 4096,
            rank: int = 256, epochs: int = 3, batches: int = 4,
            step_size: float = 1e-5, user: Optional[Dict[str, Any]] = None):
    """The trio's NMF: 4096 columns, rank 256 (rows cut). The step size is
    NOT the trainer's default 0.01: at this width that overshoots, the
    non-negativity clamp zeroes both factors within one epoch and the loss
    sits at ||X||^2 from then on (seen on the CPU and on the chip); 1e-5
    descends."""
    return _job(
        job_id, "harmony_tpu.apps.nmf:NMFTrainer",
        {"num_rows": rows, "num_cols": cols, "rank": rank,
         "step_size": step_size},
        "harmony_tpu.apps.nmf:make_synthetic",
        {"num_rows": rows, "num_cols": cols, "rank": rank},
        epochs=epochs, batches=batches, user=user)


def lda_job(job_id: str = "smoke-lda", *, docs: int = 512, vocab: int = 8192,
            topics: int = 64, doc_len: int = 128, epochs: int = 3,
            batches: int = 4):
    """The trio's LDA: 8192 words x 64 topics (docs cut)."""
    return _job(
        job_id, "harmony_tpu.apps.lda:LDATrainer",
        {"vocab_size": vocab, "num_topics": topics, "num_docs": docs,
         "max_doc_len": doc_len},
        "harmony_tpu.apps.lda:make_synthetic",
        {"num_docs": docs, "vocab_size": vocab, "num_topics": topics,
         "doc_len": doc_len},
        epochs=epochs, batches=batches)


LM_WIDTHS = dict(vocab_size=8192, d_model=512, n_heads=8, n_layers=8,
                 d_ff=2048, max_seq=1024)


def lm_job(job_id: str = "smoke-lm", *, widths: Dict[str, int] = LM_WIDTHS,
           batch: int = 8, epochs: int = 2, batches: int = 2,
           user: Optional[Dict[str, Any]] = None):
    """The smoke's LM (vocab 8192, d_model 512, 8 heads, 8 layers, d_ff
    2048, sequence 1024, bf16 activations: ``LM_WIDTHS``) as an ordinary
    job through the PS table. Sequences carry one extra token: the loss
    shifts by one, so the model sees exactly ``max_seq`` positions."""
    return _job(
        job_id, "harmony_tpu.models.transformer:TransformerTrainer",
        {**widths, "attn": "auto", "dtype": "bfloat16", "step_size": 0.1},
        "harmony_tpu.models.transformer:make_lm_data",
        {"num_seqs": batch * batches, "seq_len": widths["max_seq"] + 1,
         "vocab_size": widths["vocab_size"]},
        epochs=epochs, batches=batches, user=user)


def fm_job(job_id: str = "smoke-fm", *, vocab: int = 16383, slots: int = 8,
           emb_dim: int = 127, n: int = 8192, epochs: int = 3,
           batches: int = 4):
    """The keyed tenant: a factorization machine whose rows are
    1 + emb_dim = 128 floats wide — the width the Pallas gather and
    scatter-add take — pulled and pushed by key every step. With the bias
    row the table is 2^14 rows in 256 blocks of 64: whole 8-row tiles, which
    the in-place scatter-add needs (``TableSpec.push_lowering``)."""
    return _job(
        job_id, "harmony_tpu.apps.widedeep:FMTrainer",
        {"vocab_size": vocab, "num_slots": slots, "emb_dim": emb_dim,
         "step_size": 0.2},
        "harmony_tpu.apps.widedeep:make_synthetic",
        {"n": n, "vocab_size": vocab, "num_slots": slots},
        epochs=epochs, batches=batches)


#: tenants whose per-epoch progress figure rises as they learn (LDA reports
#: a log-likelihood); every other tenant reports a loss that must fall
HIGHER_IS_BETTER = ("lda",)


def summarize(job_id: str, result: Dict[str, Any], batches: int) -> Dict[str, Any]:
    """One tenant's outcome from its WAIT reply: steps taken, first and last
    per-epoch progress figure, and whether it stepped, stayed finite and
    improved."""
    import math

    worker = next(iter(result["workers"].values()))
    losses = [float(x) for x in worker["losses"]]
    steps = int(worker["epochs_run"]) * batches
    finite = bool(losses) and all(math.isfinite(x) for x in losses)
    rising = any(tag in job_id for tag in HIGHER_IS_BETTER)
    improved = len(losses) >= 2 and (
        losses[-1] > losses[0] if rising else losses[-1] < losses[0])
    return {"job": job_id, "steps": steps, "first": round(losses[0], 5),
            "last": round(losses[-1], 5),
            "progress": "rises" if rising else "falls",
            "ok": steps > 0 and finite and improved}


# ---------------------------------------------------------------------------
# the jobserver, driven the way the CLI drives it
# ---------------------------------------------------------------------------

class Server:
    """``harmony-tpu start-jobserver`` in this process — ``cli._make_server``
    plus the TCP endpoint — and the jax-free TCP client on the other side of
    the socket. ``scheduler`` overrides the default (share-all) for the
    carve phase."""

    def __init__(self, scheduler=None) -> None:
        import jax

        from harmony_tpu import cli
        from harmony_tpu.jobserver.client import CommandSender

        if scheduler is None:
            self.server = cli._make_server(0)
        else:
            from harmony_tpu.jobserver.server import JobServer

            self.server = JobServer(num_executors=len(jax.devices()),
                                    scheduler=scheduler)
            self.server.start()
        self.port = self.server.serve_tcp(0)
        self.client = CommandSender(self.port)

    def submit(self, config) -> None:
        reply = self.client.send_job_submit_command(config)
        if not reply.get("ok"):
            raise RuntimeError(f"SUBMIT {config.job_id}: {reply}")

    def wait(self, config) -> Dict[str, Any]:
        result = self.client.wait_result(config.job_id, timeout=JOB_TIMEOUT_S)
        return summarize(config.job_id, result,
                         config.params.num_mini_batches)

    def run(self, configs) -> List[Dict[str, Any]]:
        """Submit ``configs`` together, wait for all of them."""
        for c in configs:
            self.submit(c)
        return [self.wait(c) for c in configs]

    def status(self) -> Dict[str, Any]:
        reply = self.client.send_status_command()
        if not reply.get("ok"):
            raise RuntimeError(f"STATUS: {reply}")
        return reply

    def shutdown(self) -> None:
        """SHUTDOWN over TCP, then wait for the drain (the command only
        starts it)."""
        self.client.send_shutdown_command()
        deadline = time.monotonic() + 120.0
        while self.server.state != "CLOSED":
            if time.monotonic() > deadline:
                raise RuntimeError("jobserver did not close within 120 s")
            time.sleep(0.1)


def _say(phase: str, **fields: Any) -> None:
    """One JSON line of stdout."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _all_ok(tenants: List[Dict[str, Any]]) -> None:
    """Every tenant stepped, stayed finite and improved — after printing
    them, so a failing run still shows each tenant's figures."""
    _say("tenants", tenants=tenants)
    bad = [t["job"] for t in tenants if not t["ok"]]
    _require(not bad, f"tenants did not step/stay finite/improve: {bad}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(cache_dir: Optional[str]) -> Dict[str, Any]:
    import jax

    from harmony_tpu import native
    from harmony_tpu.utils.platform import chip_peaks

    d = jax.devices()[0]
    peaks = chip_peaks(d)  # an unknown device_kind raises here
    return {
        "platform": d.platform, "device_kind": d.device_kind,
        "count": len(jax.devices()),
        "peak_bf16_tflops": peaks.bf16_flops / 1e12,
        "native": "built" if native.available() else "python fallback",
        "compile_cache": cache_dir or "off",
        "jax": jax.__version__,
    }


def phase_sync() -> Dict[str, Any]:
    """Does ``block_until_ready`` block? 40 chained 8192^3 bf16 matmuls are
    4.4e13 FLOP; if the wait returned before the device finished, the
    implied rate would exceed the chip's peak."""
    import jax
    import jax.numpy as jnp

    from harmony_tpu.utils.platform import chip_peaks

    n, reps = 8192, 40
    dev = jax.devices()[0]
    key = jax.random.PRNGKey(0)
    x = jax.device_put(jax.random.normal(key, (n, n), jnp.bfloat16), dev)
    # scaled so the chain's values stay O(1): timing must not ride on infs
    w = jax.device_put(
        jax.random.normal(key, (n, n), jnp.bfloat16) * (n ** -0.5), dev)
    mm = jax.jit(lambda a, b: a @ b)
    jax.block_until_ready(mm(x, w))  # compile
    t0 = time.perf_counter()
    y = x
    for _ in range(reps):
        y = mm(y, w)
    jax.block_until_ready(y)
    blocked = time.perf_counter() - t0
    t1 = time.perf_counter()
    val = float(y[0, 0].astype(jnp.float32))  # a host read of the result
    read_after = time.perf_counter() - t1
    implied = 2.0 * n ** 3 * reps / blocked
    peak = chip_peaks(dev).bf16_flops
    _require(val == val, "matmul chain produced NaN")
    _require(implied <= 1.02 * peak,
             f"block_until_ready returned early: {implied / 1e12:.0f} "
             f"TFLOP/s implied on a {peak / 1e12:.0f} TFLOP/s chip")
    return {"implied_tflops": round(implied / 1e12, 1),
            "peak_tflops": peak / 1e12,
            "blocked_s": round(blocked, 4),
            "host_read_after_s": round(read_after, 4),
            "block_until_ready_blocks": True}


def phase_kernels() -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harmony_tpu.ops import sparse
    from harmony_tpu.ops.attention import (
        blockwise_attention,
        flash_attention,
        tile_plan,
    )
    from harmony_tpu.ops.histogram import weighted_histogram, xla_histogram

    out: Dict[str, Any] = {}
    rng = np.random.default_rng(0)

    # -- gather_rows vs table[idx] (the FM tenant's table and key count) --
    rows, width, nkeys = 16384, 128, 16385
    table = jnp.asarray(rng.standard_normal((rows, width), dtype=np.float32))
    idx = rng.integers(0, rows, nkeys).astype(np.int32)
    got = np.asarray(jax.jit(sparse.gather_rows)(table, jnp.asarray(idx)))
    _require(np.array_equal(got, np.asarray(table)[idx]),
             "gather_rows != table[idx]")
    oob = np.concatenate([idx[:64], [-1, -9, rows, rows + 100]]).astype(np.int32)
    _require(np.array_equal(
        np.asarray(jax.jit(sparse.gather_rows)(table, jnp.asarray(oob))),
        np.asarray(sparse.gather_rows_ref(table, jnp.asarray(oob)))),
        "gather_rows out-of-range clamp != reference")
    out["gather_rows"] = {"table": [rows, width], "keys": nkeys,
                          "max_abs_err": 0.0}

    # -- scatter_add_rows vs the XLA scatter: duplicates, two tiles, a tail
    # and dropped ids; float deltas, and still bit for bit (an in-order fold)
    sidx = np.concatenate([rng.integers(0, 64, nkeys // 2),  # hot duplicates
                           rng.integers(-2, rows + 2, nkeys - nkeys // 2)]
                          ).astype(np.int32)
    sdel = jnp.asarray(rng.standard_normal((nkeys, width), dtype=np.float32))
    _require(np.array_equal(
        np.asarray(jax.jit(sparse.scatter_add_rows)(
            table, jnp.asarray(sidx), sdel)),
        np.asarray(jax.jit(sparse.scatter_add_rows_ref)(
            table, jnp.asarray(sidx), sdel))),
        "scatter_add_rows != .at[idx].add(deltas)")
    out["scatter_add_rows"] = {"table": [rows, width], "keys": nkeys,
                               "max_abs_err": 0.0}

    # -- weighted_histogram vs xla_histogram -------------------------------
    # integer-valued weights: both routes are exact whatever precision the
    # backend's default matmul runs at, so the comparison is bit for bit
    ids = rng.integers(-2, rows + 2, nkeys).astype(np.int32)
    wts = rng.integers(-3, 4, (nkeys, width)).astype(np.float32)
    hist = jax.jit(lambda i, w: weighted_histogram(i, w, rows))
    href = jax.jit(lambda i, w: xla_histogram(i, w, rows))
    herr = float(np.abs(
        np.asarray(hist(jnp.asarray(ids), jnp.asarray(wts)))
        - np.asarray(href(jnp.asarray(ids), jnp.asarray(wts)))).max())
    _require(herr == 0.0, f"weighted_histogram != xla_histogram ({herr})")
    out["weighted_histogram"] = {"n": nkeys, "bins": rows, "w": width,
                                 "max_abs_err": herr}

    # -- flash attention, forward + gradients, at the LM's shapes in bf16 --
    # against the fp32 blockwise reference on the same bf16-rounded inputs,
    # to the tolerances tests/test_ops.py holds the kernel to in bf16
    S, D = LM_WIDTHS["max_seq"], LM_WIDTHS["d_model"] // LM_WIDTHS["n_heads"]

    def refa(q, k, v):
        return blockwise_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=True)

    def flash_parity(B, H, **blocks):
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        q, k, v, g = (jax.random.normal(kk, (B, H, S, D), jnp.float32)
                      .astype(jnp.bfloat16) for kk in ks)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, **blocks)

        def with_cotangent(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        o_f = np.asarray(jax.jit(flash)(q, k, v), np.float32)
        o_r = np.asarray(jax.jit(refa)(q, k, v), np.float32)
        np.testing.assert_allclose(o_f, o_r, rtol=0.05, atol=0.05)
        g_f = jax.jit(jax.grad(with_cotangent(flash),
                               argnums=(0, 1, 2)))(q, k, v)
        g_r = jax.jit(jax.grad(with_cotangent(refa),
                               argnums=(0, 1, 2)))(q, k, v)
        gerr = []
        for a, b in zip(g_f, g_r):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            np.testing.assert_allclose(a, b, rtol=0.1, atol=0.1)
            gerr.append(float(np.abs(a - b).max()))
        plan = tile_plan(S, S, D, q.dtype, True, **blocks)
        return {
            "shape": [B, H, S, D], "dtype": "bfloat16",
            "tiles": {kern: list(getattr(plan, kern)[:3])
                      for kern in ("fwd", "bwd")},
            "fwd_max_abs_err": float(np.abs(o_f - o_r).max()),
            "grad_max_abs_err": {"dq": gerr[0], "dk": gerr[1], "dv": gerr[2]}}

    # explicit 128 x 128 tiles (one sub-block a grid step) at the smoke LM's
    # heads, then the tiles the kernels choose themselves at the gpt2-124m
    # cell's own call (8 x 12 x 1024 x 64)
    out["flash_attention"] = flash_parity(8, LM_WIDTHS["n_heads"],
                                          block_q=128, block_k=128)
    out["flash_attention_planned"] = flash_parity(8, 12)
    return out


def _lm_traces_flash(mesh) -> int:
    """Pallas custom calls in the LM tenant's compute traced for ``mesh`` —
    ``attn="auto"`` must have resolved to the flash kernels: one forward and
    one backward kernel a layer."""
    import jax
    import jax.numpy as jnp

    from harmony_tpu.models.transformer import TransformerTrainer
    from harmony_tpu.utils.platform import traced_on

    cfg = lm_job()
    trainer = TransformerTrainer(**cfg.params.app_params)
    model = jax.ShapeDtypeStruct((trainer.capacity, trainer.row_width),
                                 jnp.float32)
    nseq = cfg.user["data_args"]["num_seqs"] // cfg.params.num_mini_batches
    batch = (jax.ShapeDtypeStruct(
        (nseq, cfg.user["data_args"]["seq_len"]), jnp.int32),)
    text = jax.jit(traced_on(mesh, trainer.compute)).lower(
        model, batch, {"lr": jnp.float32(0.1)}).as_text()
    if text.count("tpu_custom_call") < 2:
        return 0
    # the kernels' jitted callers (ops/attention.py) are lowered once and
    # CALLED once a layer: one kernel in the forward's, one in the backward's
    return (text.count("call @_flash_forward")
            + text.count("call @_flash_backward"))


def phase_jobs(srv: Server, ndev: int) -> Dict[str, Any]:
    import jax

    from harmony_tpu.parallel.mesh import build_mesh

    t0 = time.perf_counter()
    trio = srv.run([mlr_job(), nmf_job(), lda_job()])
    t_trio = time.perf_counter() - t0
    # on four chips the LM runs data x model = 2 x 2: its table split in
    # two, its batch split in two, flash under shard_map over the data axis
    lm_user = {"data_axis": 2} if ndev >= 4 else None
    t1 = time.perf_counter()
    pair = srv.run([lm_job(user=lm_user), fm_job()])
    t_pair = time.perf_counter() - t1
    status = srv.status()
    tenants = trio + pair
    _all_ok(tenants)
    _require(status["state"] == "INIT" and not status["running"],
             f"STATUS after the jobs: state={status['state']} "
             f"running={status['running']}")
    lm_mesh = build_mesh(jax.devices(), data=2 if ndev >= 4 else 1)
    kernels = _lm_traces_flash(lm_mesh)
    _require(kernels >= 2 * LM_WIDTHS["n_layers"],
             f"LM attn='auto' traced {kernels} Pallas calls, want "
             f">= {2 * LM_WIDTHS['n_layers']} (flash fwd + bwd a layer)")
    fm_layout = status["tenants"]["smoke-fm"]["table_layout"]
    _require(fm_layout["push_lowering"] == "pallas_rows",
             f"keyed tenant's push is not the Pallas row scatter-add: "
             f"{fm_layout}")
    return {"trio_wall_s": round(t_trio, 1),
            "lm_fm_wall_s": round(t_pair, 1),
            "lm_pallas_calls": kernels,
            "fm_push_lowering": fm_layout["push_lowering"],
            "status_ledger_tenants": sorted(status["tenants"])}


def _running_table(srv: Server, job_id: str, min_version: int):
    """The TableHandle of a RUNNING tenant's model table (the master's public
    registry), once the tenant has committed ``min_version`` writes."""
    deadline = time.monotonic() + JOB_TIMEOUT_S
    while time.monotonic() < deadline:
        ids = [t for t in srv.server.master.table_ids()
               if t.startswith(job_id + ":")]
        if ids:
            handle = srv.server.master.get_table(ids[0])
            if handle.table.data_version >= min_version:
                return handle
        time.sleep(0.005)
    raise TimeoutError(f"{job_id}: table never reached version {min_version}")


def _shard_report(arr) -> Dict[str, Any]:
    """Per-device bytes of ``arr`` from its addressable shards."""
    per = {}
    for s in arr.addressable_shards:
        per[s.device.id] = per.get(s.device.id, 0) + int(s.data.nbytes)
    return {"table_bytes": int(arr.nbytes), "per_device_bytes": per}


def _locked_reshard(handle, owners) -> Dict[str, Any]:
    """Rebalance a LIVE table onto ``owners`` and prove the values survived:
    the table lock (re-entrant; the one reshard itself takes — the access
    latch workers wait on) is held across before-copy, move and after-copy,
    so no training step lands in between."""
    import numpy as np

    table = handle.table
    with table._lock:
        before = np.asarray(table.array)
        handle.rebalance(list(owners))
        arr = table.array
        after = np.asarray(arr)
        report = _shard_report(arr)
    _require(np.array_equal(before, after), "values changed across reshard")
    n = len(owners)
    _require(len(report["per_device_bytes"]) == n
             and all(b * n == report["table_bytes"]
                     for b in report["per_device_bytes"].values()),
             f"table not split evenly over {n} devices: {report}")
    return report


def phase_sharded(srv: Server) -> Dict[str, Any]:
    """Four chips: an MLR tenant with its table over all four devices, then
    a live 4 -> 2 -> 4 reshard of that running tenant."""
    import jax
    from jax.sharding import PartitionSpec as P

    # long enough to still be training through both reshards
    cfg = mlr_job("smoke-mlr-sharded", epochs=400, batches=4)
    srv.submit(cfg)
    handle = _running_table(srv, cfg.job_id, min_version=4)
    table = handle.table
    owners = handle.owning_executors()
    _require(len(owners) == 4, f"table owned by {owners}, want 4 executors")
    with table._lock:  # a donating step must not delete the array mid-read
        start = _shard_report(table.array)
    _require(sorted(start["per_device_bytes"]) == sorted(
        d.id for d in jax.devices()), f"table not on every device: {start}")
    _require(all(4 * b == start["table_bytes"]
                 for b in start["per_device_bytes"].values()),
             f"a device does not hold a quarter of the table: {start}")
    # batches go where the worker puts them: over the mesh's data axis
    worker = srv.server._entities[cfg.job_id]._workers[0]
    x, y = worker._shard_batch(worker.data.batch_at(0))
    _require(x.sharding.spec == P("data") and x.sharding.mesh == table.mesh,
             f"batch sharding {x.sharding}")
    _require({s.device.id for s in x.addressable_shards}
             == {d.id for d in jax.devices()},
             "batch shards missing from some device")
    half = _locked_reshard(handle, owners[:2])
    # let the tenant train on two devices for a few steps
    _running_table(srv, cfg.job_id, min_version=table.data_version + 4)
    back = _locked_reshard(handle, owners)
    tenant = srv.wait(cfg)
    _all_ok([tenant])
    in_use = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()}
    return {"start": start, "after_4_to_2": half,
            "after_2_to_4": back, "values_survived": True,
            "batch_spec": str(x.sharding.spec),
            "batch_shard_shape": list(x.addressable_shards[0].data.shape),
            "peak_bytes_in_use": in_use,
            "mesh": {k: int(v) for k, v in table.mesh.shape.items()}}


def phase_carve() -> Dict[str, Any]:
    """Four chips: two tenants at once on disjoint device pairs — one
    data-parallel over its pair (a cross-chip delta reduction every step),
    one with its table split over its pair (an all-gather every step)."""
    from harmony_tpu.jobserver.scheduler import CarveScheduler

    scheduler = CarveScheduler(max_share=2)
    srv = Server(scheduler=scheduler)
    try:
        a = mlr_job("smoke-carve-a", epochs=6, batches=4,
                    user={"data_axis": 2})
        b = nmf_job("smoke-carve-b", epochs=6, batches=4,
                    user={"data_axis": 1})
        srv.submit(a)
        srv.submit(b)
        slices: Dict[str, List[str]] = {}
        together = False
        deadline = time.monotonic() + JOB_TIMEOUT_S
        while time.monotonic() < deadline and not together:
            sa = scheduler.slice_of(a.job_id)
            sb = scheduler.slice_of(b.job_id)
            if sa and sb:
                slices = {a.job_id: sa, b.job_id: sb}
                together = True
            elif not srv.status()["running"]:
                break
            time.sleep(0.02)
        tenants = [srv.wait(a), srv.wait(b)]
    finally:
        srv.shutdown()
    _all_ok(tenants)
    _require(together, "the two tenants never held slices at the same time")
    sa, sb = slices[a.job_id], slices[b.job_id]
    _require(len(sa) == 2 and len(sb) == 2 and not set(sa) & set(sb),
             f"slices not disjoint pairs: {slices}")
    return {"slices": slices}


def phase_dryrun() -> Dict[str, Any]:
    """__graft_entry__'s sections on the real devices, each on its own: a
    failing section does not stop the next."""
    import jax

    import __graft_entry__ as graft

    devices = jax.devices()[:4]
    sections: Dict[str, Any] = {}
    for name, section in graft.DRYRUN_SECTIONS:
        t0 = time.perf_counter()
        try:
            section(devices)
            sections[name] = {"ok": True}
        except Exception as e:  # reported per section (see OPTIONAL_DRYRUN)
            traceback.print_exc()
            sections[name] = {"ok": False,
                              "error": f"{type(e).__name__}: {e}"[:400]}
        sections[name]["wall_s"] = round(time.perf_counter() - t0, 1)
    required_bad = [n for n, s in sections.items()
                    if not s["ok"] and n not in OPTIONAL_DRYRUN]
    _require(not required_bad, f"job-path dry-run failed: {required_bad}")
    return {"sections": sections}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax.devices()[0].platform is "
              f"{devices[0].platform!r}. No result.", file=sys.stderr)
        return 1
    from harmony_tpu.utils.compcache import enable_compile_cache

    # None on a multi-chip host: cache-loaded sub-mesh executables halt the
    # chip there (harmony_tpu/utils/compcache.py), so every run is cold
    cache_dir = enable_compile_cache()
    cache_was_warm = bool(cache_dir and os.path.isdir(cache_dir)
                          and os.listdir(cache_dir))
    ndev = len(devices)
    failed: List[str] = []

    def phase(name: str, fn: Callable[[], Dict[str, Any]]) -> None:
        t0 = time.perf_counter()
        try:
            body, ok = fn(), True
        except Exception as e:  # the phase boundary: record, go on, exit 1
            traceback.print_exc()
            body, ok = {"error": f"{type(e).__name__}: {e}"[:800]}, False
            failed.append(name)
        _say(name, ok=ok, wall_s=round(time.perf_counter() - t0, 1), **body)

    phase("device", lambda: phase_device(cache_dir))
    phase("sync", phase_sync)
    phase("kernels", phase_kernels)
    srv: Optional[Server] = None
    try:
        srv = Server()
        phase("jobs", lambda: phase_jobs(srv, ndev))
        if ndev >= 4:
            phase("sharded", lambda: phase_sharded(srv))
    finally:
        if srv is not None:
            phase("shutdown", lambda: srv.shutdown() or {})
    if ndev >= 4:
        phase("carve", phase_carve)
        phase("dryrun", phase_dryrun)

    from harmony_tpu.runtime import progcache

    compile_s = sum(c.get("compile_seconds") or 0.0
                    for c in progcache.program_costs())
    stray = [t.name for t in threading.enumerate()
             if t is not threading.current_thread() and not t.daemon]
    _say("summary", ok=not failed, failed_phases=failed,
         wall_s=round(time.perf_counter() - t_start, 1),
         compile_cache={"dir": cache_dir or "off",
                        "warm_at_start": cache_was_warm},
         job_program_compile_s=round(compile_s, 1),
         non_daemon_threads_left=stray)
    # the result: the LAST line of stdout
    print(json.dumps({
        "ok": not failed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": ndev},
    }), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
