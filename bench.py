#!/usr/bin/env python
"""Headline benchmark — BASELINE.md config 4: aggregate training throughput
of CONCURRENT MLR + NMF + LDA jobs sharing one mesh under the JobServer
(the reference's north-star metric: aggregate samples/sec across concurrent
jobs on a shared multi-tenant substrate).

Runs in ONE process, which owns the accelerator. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N,
   "device": {"platform": "tpu", "kind": ..., "count": N}, ...}
and exits non-zero, printing no ``value``, when JAX finds no TPU: a rate
from another backend is never written under this metric's name.

The reference publishes no numbers (BASELINE.md: "published: {}"); its
north-star target is >=4x a CPU-cluster aggregate. ``vs_baseline`` is the
measured accelerator aggregate divided by the SAME three concurrent jobs run
on this host's CPU backend (in this process, beside the accelerator) — the
local proxy: >=4.0 meets the north star. Both backends run a 1-epoch WARMUP
pass first, so the recorded rate is steady-state training throughput (the
north-star quantity) rather than a compile-time race.
"""
import json
import os
import sys
import time

import jax

# Allow both the accelerator and CPU backends so the baseline runs in-process.
plats = jax.config.jax_platforms
if plats and "cpu" not in plats:
    jax.config.update("jax_platforms", plats + ",cpu")

from harmony_tpu.config.params import JobConfig, TrainerParams  # noqa: E402
from harmony_tpu.jobserver.server import JobServer  # noqa: E402
from harmony_tpu.parallel.mesh import DevicePool  # noqa: E402
from harmony_tpu.utils.compcache import enable_compile_cache  # noqa: E402

EPOCHS = 12
BATCHES = 8
METRIC = "aggregate throughput, concurrent MLR+NMF+LDA (multi-tenant jobserver)"


def job_configs(scale: float, epochs: int = EPOCHS):
    """The three BASELINE jobs, sized so per-sample compute lands on the
    MXU (large matmuls — MLR 8192x256, NMF rank-256); ``scale`` shrinks
    the CPU baseline run's DATASET only (per-sample compute is identical —
    rates, not totals, are compared)."""
    mlr_n = max(int(16384 * scale), BATCHES * 64)
    nmf_rows = max(int(4096 * scale), BATCHES * 8)
    lda_docs = max(int(2048 * scale), BATCHES * 8)
    mlr = JobConfig(
        job_id="bench-mlr", app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"num_classes": 256, "num_features": 8192,
                        "features_per_partition": 512, "step_size": 0.05},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
              "data_args": {"n": mlr_n, "num_features": 8192,
                            "num_classes": 256}},
    )
    nmf = JobConfig(
        job_id="bench-nmf", app_type="dolphin",
        trainer="harmony_tpu.apps.nmf:NMFTrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"num_rows": nmf_rows, "num_cols": 4096, "rank": 256,
                        "step_size": 0.01},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.nmf:make_synthetic",
              "data_args": {"num_rows": nmf_rows, "num_cols": 4096,
                            "rank": 256}},
    )
    lda = JobConfig(
        job_id="bench-lda", app_type="dolphin",
        trainer="harmony_tpu.apps.lda:LDATrainer",
        params=TrainerParams(
            num_epochs=epochs, num_mini_batches=BATCHES, comm_probe_period=6,
            app_params={"vocab_size": 8192, "num_topics": 64,
                        "num_docs": lda_docs, "max_doc_len": 128},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.apps.lda:make_synthetic",
              "data_args": {"num_docs": lda_docs, "vocab_size": 8192,
                            "num_topics": 64, "doc_len": 128}},
    )
    # examples processed per job = epochs * dataset size
    totals = {"bench-mlr": epochs * mlr_n, "bench-nmf": epochs * nmf_rows,
              "bench-lda": epochs * lda_docs}
    return [mlr, nmf, lda], totals


def submit_and_time(server, configs, timeout_s: float):
    """Submit ``configs`` together; wait for all; returns {job_id:
    seconds-from-common-start}, stamped by done-callbacks so a job
    finishing before an earlier-submitted one gets ITS OWN completion
    time. Shared by bench.py and benchmarks/fairness.py."""
    job_walls: dict = {}
    t0 = time.perf_counter()

    def stamp(job_id):
        return lambda _f: job_walls.setdefault(
            job_id, round(time.perf_counter() - t0, 2))

    futures = []
    for c in configs:
        f = server.submit(c)
        f.add_done_callback(stamp(c.job_id))
        futures.append(f)
    for f in futures:
        f.result(timeout=timeout_s)
    return job_walls


def run_concurrent(devices, scale: float, job_timeout: float = 900.0,
                   epochs: int = EPOCHS) -> "tuple[float, dict]":
    """Submit the three jobs concurrently to one JobServer over ``devices``;
    returns (aggregate samples/sec = total examples / wall, per-job wall
    seconds). ``job_timeout`` bounds each job: tight for the accelerator
    pass (a wedged chip must surface as an error line, not a stall),
    looser for the slow-but-healthy CPU reference pass."""
    configs, totals = job_configs(scale, epochs)
    server = JobServer(num_executors=len(devices),
                       device_pool=DevicePool(devices))
    server.start()
    try:
        t0 = time.perf_counter()
        job_walls = submit_and_time(server, configs, job_timeout)
        wall = time.perf_counter() - t0
    finally:
        server.shutdown(timeout=120)
    total = sum(totals.values())
    rate = total / wall
    # per-job completion: the aggregate is bounded by the LAST job, so
    # the straggler app is the next perf target — make it visible
    print(f"  {len(configs)} jobs, {total} examples, {wall:.1f}s "
          f"-> {rate:,.0f} samples/sec aggregate; per-job {job_walls}",
          file=sys.stderr)
    from harmony_tpu.data import devcache
    from harmony_tpu.runtime import progcache
    print(f"  progcache {progcache.stats()}  devcache {devcache.stats()}",
          file=sys.stderr)
    return rate, job_walls


def cpu_baseline_rate() -> float:
    """Best of two measured CPU passes (after a compile warmup).

    A single pass proved fragile: transient host contention once depressed
    it 5x, which INFLATES vs_baseline. Taking the best CPU rate is the
    conservative denominator — steady-state capability of this host, not
    its worst moment."""
    cpu = jax.devices("cpu")[:1]
    print("cpu warmup (compile) pass:", file=sys.stderr)
    run_concurrent(cpu, scale=0.125, job_timeout=3600.0, epochs=1)
    rates = []
    for i in range(2):
        print(f"concurrent MLR+NMF+LDA on cpu (reduced size, "
              f"pass {i + 1}/2):", file=sys.stderr)
        rates.append(run_concurrent(cpu, scale=0.125,
                                    job_timeout=3600.0)[0])
    return max(rates)


def measure_scrape_latency() -> dict:
    """Exporter-overhead probe (tracked round over round in BENCH json):
    serve the process registry — populated by the training passes that
    just ran — on an ephemeral port and time a few real HTTP scrapes.
    Returns {metrics_scrape_ms, scrape_bytes, families}."""
    import urllib.request

    from harmony_tpu.metrics.exporter import MetricsExporter
    from harmony_tpu.metrics.registry import parse_exposition

    exp = MetricsExporter(0).start()
    try:
        samples = []
        body = b""
        for _ in range(5):
            t0 = time.perf_counter()
            body = urllib.request.urlopen(exp.url + "/metrics",
                                          timeout=10).read()
            samples.append((time.perf_counter() - t0) * 1000.0)
        return {
            "metrics_scrape_ms": round(sorted(samples)[len(samples) // 2], 3),
            "scrape_bytes": len(body),
            "families": len(parse_exposition(body.decode())),
        }
    finally:
        exp.stop()


def measure_state_movement() -> dict:
    """State-movement latency probe (tracked round over round in BENCH
    json beside throughput): a small checkpoint restore and a small TCP
    block-migration exchange, both on the CPU backend so every round is
    comparable. Returns
    {"chkp.restore_ms", "move.exchange_ms", ...}."""
    import shutil
    import tempfile

    import numpy as np

    root = tempfile.mkdtemp(prefix="harmony-bench-sm-")
    try:
        from harmony_tpu.checkpoint import CheckpointManager
        from harmony_tpu.config.params import TableConfig
        from harmony_tpu.parallel import DevicePool
        from harmony_tpu.runtime import ETMaster
        from harmony_tpu.table import blockmove

        cpu = jax.devices("cpu")
        master = ETMaster(DevicePool(cpu[:1]))
        execs = [e.id for e in master.add_executors(1)]
        nb, rows, dim = 32, 256, 256  # 32 x 256 KB = 8 MB
        cfg = TableConfig(table_id="bench-sm", capacity=nb * rows,
                          value_shape=(dim,), num_blocks=nb)
        h = master.create_table(cfg, execs)
        vals = np.ones((nb * rows, dim), np.float32)
        h.table.multi_update(list(range(nb * rows)), vals)
        mgr = CheckpointManager(root + "/temp", root + "/commit")
        cid = mgr.checkpoint(h)
        samples = []
        for i in range(3):
            t0 = time.perf_counter()
            rh = mgr.restore(master, cid, execs, table_id=f"bench-sm-r{i}")
            samples.append((time.perf_counter() - t0) * 1000.0)
            rh.drop()
        restore_ms = sorted(samples)[len(samples) // 2]

        class _KV:
            def __init__(self):
                self.kv = {}

            def key_value_set(self, k, v):
                self.kv[k] = v

            def blocking_key_value_get(self, k, timeout_ms):
                return self.kv[k]

            def key_value_delete(self, k):
                self.kv.pop(k, None)

        block = np.ones((rows, dim), np.float32)
        plan = blockmove.MovePlan(
            sends={0: [(b, 0) for b in range(nb)]},
            recvs={0: set(range(nb))}, block_nbytes=block.nbytes)
        outgoing = {b: block for b in range(nb)}
        orig_kv = blockmove._kv_client
        blockmove._kv_client = lambda: _KV()
        try:
            samples = []
            for i in range(3):
                t0 = time.perf_counter()
                received, _ = blockmove._tcp_exchange(plan, outgoing,
                                                      900000 + i)
                samples.append((time.perf_counter() - t0) * 1000.0)
                assert len(received) == nb
        finally:
            blockmove._kv_client = orig_kv
        exchange_ms = sorted(samples)[len(samples) // 2]
        from harmony_tpu.checkpoint.manager import _chkp_io_threads

        return {
            "chkp.restore_ms": round(restore_ms, 1),
            "move.exchange_ms": round(exchange_ms, 1),
            "chkp_mb": round(nb * rows * dim * 4 / 1e6, 1),
            "move_parallel": blockmove._move_parallel(),
            "io_threads": _chkp_io_threads(),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def measure_sparse_hot_path() -> dict:
    """Sparse device-hot-path probe (tracked round over round in BENCH
    json): a small embedding-SGD table driven fused (FusedSparseStep,
    one donated-buffer program per batch) and unfused (ModelAccessor
    host round trip), interleaved, on the CPU backend. Returns fused/
    unfused samples-per-sec, the ratio, the unfused arm's measured
    per-phase pull/comp/push seconds; a loss-parity break raises."""
    import jax.numpy as jnp
    import numpy as np

    from harmony_tpu.config.params import TableConfig
    from harmony_tpu.dolphin import ModelAccessor
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices("cpu")[:1])
    rows, width, batch, nb = 2048, 32, 256, 30
    rng = np.random.default_rng(0)
    batches = [
        (rng.integers(0, rows, batch).astype(np.int32),
         rng.normal(size=(batch, width)).astype(np.float32))
        for _ in range(nb)
    ]

    def table():
        return DenseTable(
            TableSpec(TableConfig(table_id="bench-sparse",
                                  capacity=rows, value_shape=(width,),
                                  num_blocks=32)), mesh)

    def compute(r, t):
        err = r - t
        return -0.05 * err, {"loss": jnp.mean(jnp.sum(err * err, -1))}

    acc_f = ModelAccessor(table())
    fs = acc_f.fused_step(compute, signature=("bench-sparse-hook",))
    fs.run_batches(batches[:2])  # compile warmup
    t0 = time.perf_counter()
    l_f = [float(a["loss"]) for a in fs.run_batches(batches)]
    fused_s = time.perf_counter() - t0

    acc = ModelAccessor(table())
    comp = jax.jit(compute)

    def one(keys, tgt):
        rows_h = acc.pull(keys)
        delta, aux = jax.block_until_ready(
            comp(jnp.asarray(rows_h), jnp.asarray(tgt)))
        acc.push(keys, np.asarray(delta))
        return float(aux["loss"])

    for k, t in batches[:2]:
        one(k, t)
    acc.get_and_reset_times()
    t0 = time.perf_counter()
    l_u = [one(k, t) for k, t in batches]
    unfused_s = time.perf_counter() - t0
    pull_s, push_s = acc.get_and_reset_times()
    if l_f != l_u:
        raise RuntimeError("fused/unfused loss parity broke")
    n = nb * batch
    return {
        "fused_sps": round(n / fused_s, 1),
        "unfused_sps": round(n / unfused_s, 1),
        "ratio": round(unfused_s / fused_s, 2),
        "unfused_pull_ms": round(pull_s * 1000, 2),
        "unfused_push_ms": round(push_s * 1000, 2),
        "unfused_comp_ms": round(
            max(unfused_s - pull_s - push_s, 0.0) * 1000, 2),
        "loss_parity": "bit-identical",
    }


#: (key in the result line, probe). Host-side and control-plane probes
#: tracked round over round beside the headline; each runs in this process
#: (on the CPU backend or no backend at all) and a probe that raises fails
#: the run. (The autoscale and chaos probes left in PR 21: both need an
#: 8-virtual-device CPU backend, which a process that already holds the
#: accelerator cannot create; tests/test_chaos.py and benchmarks/autoscale.py
#: cover them.) Rebuilding this list into benchmark cells is ROADMAP S0/D3.
def _probes():
    return (
        ("obs", measure_scrape_latency),
        ("state_movement", measure_state_movement),
        ("sparse_hot_path", measure_sparse_hot_path),
        ("input_service", measure_input_service),
        ("lint", measure_lint),
        ("obs_doctor", measure_obs_doctor),
        ("ha", measure_ha),
        ("critpath", measure_critpath),
        ("policy", measure_policy),
        ("serving", measure_serving),
        ("obs_incidents", measure_obs_incidents),
    )


def emit(tpu_rate: float, cpu_rate: float, job_walls: dict,
         devices) -> None:
    line = {
        "metric": METRIC,
        "value": round(tpu_rate, 1),
        "unit": "samples/sec",
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
        "vs_baseline": round(tpu_rate / cpu_rate, 2),
        "cpu_rate": round(cpu_rate, 1),
        "mode": "3 concurrent jobs, num_workers=1 each; steady-state "
                "(compile warmed on both backends)",
        # the aggregate is bounded by the LAST job: the straggler app
        # named here is the next perf target
        "accel_job_walls_s": job_walls,
    }
    # the probes are CPU-backend work: their unplaced jits must not land on
    # the accelerator this process holds (a probe that compares a CPU-mesh
    # arm with an unplaced one would compare two backends' arithmetic)
    with jax.default_device(jax.devices("cpu")[0]):
        for key, probe in _probes():
            line[key] = probe()
    print(json.dumps(line))


def measure_input_service() -> dict:
    """Input-service probe (tracked round over round in the BENCH json,
    and by --compare via the dotted input_service.* series): a small
    multi-tenant-process service-vs-in-process A/B — 3 same-dataset
    tenant processes, standalone service, unpinned cores (the full
    pinned-budget capture is benchmarks/INPUT_SVC_r10.json). Returns
    {svc_sps, inproc_sps, speedup, parity}."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.bench_input_pipeline import run_service_bench

    r = run_service_bench(tenants=3, n=262144, epochs=2, rounds=1,
                          cores=0)
    if not r.get("losses_bit_identical"):
        raise RuntimeError("service/in-process loss parity broke")
    return {
        "svc_sps": r["service_sps"],
        "inproc_sps": r["inproc_sps"],
        "speedup": r["speedup"],
        "parity": "bit-identical",
    }


def measure_obs_doctor() -> dict:
    """Telemetry-history + doctor overhead probe (tracked round over
    round in the BENCH json): ingest of this process's REAL exposition
    (populated by the training passes that just ran) per scrape cycle,
    and one full rule evaluation over a store holding scenario-shaped
    tenant series. Returns {ingest_ms, diagnose_ms, series, points,
    rules, diagnoses}. Full sweep: benchmarks/obs_doctor.py
    (OBS_DOCTOR_r11.json)."""
    from harmony_tpu.metrics.doctor import Doctor, all_rules
    from harmony_tpu.metrics.history import HistoryStore
    from harmony_tpu.metrics.registry import get_registry

    text = get_registry().expose()
    store = HistoryStore(window_sec=900.0, resolution_sec=1.0)
    rounds = 20
    now = time.time()
    t0 = time.perf_counter()
    for i in range(rounds):
        store.ingest_exposition("leader", text,
                                ts=now - (rounds - i))
    ingest_ms = (time.perf_counter() - t0) * 1000.0 / rounds
    # scenario-shaped tenant series so every rule has real work
    for j in range(8):
        labels = {"job": f"bench-t{j}", "attempt": f"bench-t{j}"}
        for i in range(30):
            ts = now - 30 + i
            store.ingest("tenant.input_wait_frac", labels,
                         0.8 if j % 2 else 0.1, ts=ts)
            store.ingest("tenant.straggler_ratio", labels,
                         2.5 if j % 3 == 0 else 1.0, ts=ts)
            store.ingest("tenant.mfu", labels,
                         0.4 if i < 15 else 0.1, ts=ts)
    doc = Doctor(store, events_fn=dict)
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        doc.diagnose()  # dedupe suppresses re-EMISSION, not the work
        samples.append((time.perf_counter() - t0) * 1000.0)
    st = store.stats()
    return {
        "ingest_ms": round(ingest_ms, 3),
        "diagnose_ms": round(sorted(samples)[len(samples) // 2], 3),
        "series": st["series"],
        "points": st["points"],
        "rules": len(all_rules()),
        "diagnoses": len(doc.recent()),
        "scrape_bytes": len(text),
    }


def measure_critpath() -> dict:
    """Step-phase budget + critical-path overhead probe (tracked round
    over round in the BENCH json): windowed budget computation
    (PhaseBudgetStore.snapshot — runs on every ledger query and scrape
    cycle) and the full critical-path analysis (critpath.analyze —
    runs on every STATUS) over a scenario-shaped store. Returns
    {budget_ms, analyze_ms, tenants, workers, epochs}. Full sweep:
    benchmarks/critpath.py (CRITPATH_r13.json)."""
    from harmony_tpu.metrics import critpath
    from harmony_tpu.metrics.phases import PhaseBudgetStore

    store = PhaseBudgetStore()
    tenants, workers, epochs = 8, 4, 24
    for j in range(tenants):
        for e in range(epochs):
            for w in range(workers):
                store.observe_epoch(
                    f"bench-t{j}", f"bench-t{j}", f"w{w}", e,
                    0.1 + 0.01 * w,
                    {"input_wait": 0.01, "host_dispatch": 0.005,
                     "pull_comm": 0.01, "compute": 0.06,
                     "push_comm": 0.005})
    budget_samples = []
    for _ in range(10):
        t0 = time.perf_counter()
        snap = store.snapshot()
        budget_samples.append((time.perf_counter() - t0) * 1000.0)
    analyze_samples = []
    for _ in range(10):
        t0 = time.perf_counter()
        critpath.analyze(snap)
        analyze_samples.append((time.perf_counter() - t0) * 1000.0)
    return {
        "budget_ms": round(sorted(budget_samples)[5], 3),
        "analyze_ms": round(sorted(analyze_samples)[5], 3),
        "tenants": tenants, "workers": workers, "epochs": epochs,
    }


def measure_ha() -> dict:
    """Control-plane HA overhead probe (tracked round over round in
    the BENCH json): durable log-append cost (write+flush+fsync per
    control-plane transition — the tax every submission/dispatch/
    completion now pays on an HA leader) and warm-standby takeover
    latency (lease election + fenced replay + re-arm bookkeeping over
    a populated log; the server-boot share is excluded — it is the
    same cost a cold start pays). Returns {append_ms, appends_per_sec,
    takeover_ms, replayed_entries}."""
    import tempfile

    from harmony_tpu.jobserver.halog import DurableJobLog, ReplayState
    from harmony_tpu.jobserver.lease import LeaseManager

    root = tempfile.mkdtemp(prefix="harmony-bench-ha-")
    path = os.path.join(root, "job.walog")
    log = DurableJobLog(path)
    n = 256
    t0 = time.perf_counter()
    for i in range(n):
        kind = ("submission", "dispatch", "job_done")[i % 3]
        log.append(kind, job_id=f"bench-j{i % 8}",
                   config={"job_id": f"bench-j{i % 8}", "k": i})
    wall = time.perf_counter() - t0
    log.close()
    # takeover: election + reopen (torn-tail scan) + fenced replay
    samples = []
    replayed = 0
    for r in range(5):
        lease = LeaseManager(root, f"bench-rep-{r}", lease_s=30.0)
        t0 = time.perf_counter()
        if not lease.try_acquire():  # never assert: -O strips it,
            raise RuntimeError("bench lease acquire failed")
        relog = DurableJobLog(path)
        relog.set_epoch(lease.epoch)
        st = ReplayState.from_entries(relog.entries())
        samples.append((time.perf_counter() - t0) * 1000.0)
        replayed = st.entries_applied
        relog.close()
        lease.release()
    import shutil

    shutil.rmtree(root, ignore_errors=True)
    return {
        "append_ms": round(wall * 1000.0 / n, 4),
        "appends_per_sec": round(n / wall, 1),
        "takeover_ms": round(sorted(samples)[len(samples) // 2], 3),
        "replayed_entries": replayed,
    }


def measure_policy() -> dict:
    """Device-policy engine overhead probe (tracked round over round in
    the BENCH json): full plan evaluations over a synthetic 16-tenant
    contention window (queued claimant + growable/packable tenants) in
    ``act`` mode against a null fence. Returns {eval_ms, tenants,
    actions_planned, actions_per_window}."""
    from harmony_tpu.jobserver.policy import ActionGate, PolicyEngine

    n = 16
    rows = {}
    tenants = {}
    for i in range(n):
        jid = f"bench-pol-{i:02d}"
        rows[jid] = {
            "slo": {"attainment": 0.4 if i % 3 == 0 else 1.0},
            "phase_class": ("compute-bound" if i % 3 == 0
                            else "dispatch-bound" if i % 3 == 1
                            else "balanced"),
            "input_wait_frac": 0.1, "mfu": None,
            "samples_per_sec": 1000.0 + i,
        }
        tenants[jid] = {"executors": [f"e{2 * i}", f"e{2 * i + 1}"],
                        "attempt": 0, "priority": i % 2}

    class _Sched:
        def idle_executors(self):
            return ["idle0"]

        def queued_jobs(self):
            return []

        def plan_grant(self, job_id, executors, shared=False):
            pass

    import os as _os

    saved = _os.environ.get("HARMONY_POLICY")
    _os.environ["HARMONY_POLICY"] = "act"
    try:
        eng = PolicyEngine(
            scheduler=_Sched(), ledger_fn=lambda: rows,
            tenants_fn=lambda: tenants,
            fence_fn=lambda j, k: None,  # plans, never lands
            gate=ActionGate(cooldown_sec=0.0, confirm=1,
                            stale_after=999.0))
        samples = []
        planned = 0
        for _ in range(20):
            t0 = time.perf_counter()
            plan = eng.evaluate()
            samples.append((time.perf_counter() - t0) * 1000.0)
            planned = len(plan["actions"])
    finally:
        if saved is None:
            _os.environ.pop("HARMONY_POLICY", None)
        else:
            _os.environ["HARMONY_POLICY"] = saved
    return {
        "eval_ms": round(sorted(samples)[len(samples) // 2], 3),
        "tenants": n,
        "actions_per_window": planned,
    }


def measure_obs_incidents() -> dict:
    """Incident-correlation probe (tracked round over round in the
    BENCH json, and by --compare via obs_incidents.recall): a fixed
    synthetic episode set — 8 tenants, each a seeded trigger→diagnosis→
    action→resolution joblog sequence — through a standalone
    IncidentEngine, measuring correlation wall per cycle, the open
    count after folding, and recall (episodes that produced a resolved
    incident / episodes injected). Synthetic on purpose: the BENCH line
    must stay cheap; the chaos-ground-truth scorecard is
    benchmarks/OBS_INCIDENT_r19.json (benchmarks/obs_incidents.py).
    Returns {correlate_ms, open, recall, resolved}."""
    import time as _t

    from harmony_tpu.jobserver import joblog
    from harmony_tpu.metrics.incidents import IncidentEngine

    n = 8
    eng = IncidentEngine(window_sec=5.0, persist=False)
    t0 = _t.time()
    for i in range(n):
        job = f"bench-inc-{i}"
        joblog.record_event(job, "slo", attainment=0.4)
        joblog.record_event(job, "diagnosis", rule="slo_burn",
                            verdict="input_bound", confidence=0.9)
        joblog.record_event(job, "policy", action="grow",
                            outcome="advised", reason="under_slo")
        joblog.record_event(job, "elastic_restore", recovery="regrow")
    t1 = _t.monotonic()
    eng.correlate()
    correlate_ms = (_t.monotonic() - t1) * 1000.0
    st = eng.status()
    for i in range(n):
        joblog.clear_events(f"bench-inc-{i}")
    return {
        "correlate_ms": round(correlate_ms, 3),
        "open": st["open"],
        "resolved": st["resolved"],
        "recall": round(st["resolved"] / float(n), 3),
        "setup_s": round(_t.time() - t0, 3),
    }


def measure_serving() -> dict:
    """Online-serving probe (tracked round over round in the BENCH json,
    and by --compare via serving.qps / serving.p99_ms): a short
    closed-loop read storm — 4 client threads, skewed keys — against a
    small live DenseTable through the micro-batching ServingEndpoint
    (batch window + hot-row cache on, the production defaults).
    Returns {qps, p50_ms, p99_ms, cache_hit_rate, batch_occupancy}. The
    pinned batching×cache×training A/B grid is
    benchmarks/SERVING_r20.json (benchmarks/serving_bench.py)."""
    import threading as _th

    import numpy as np

    from harmony_tpu.config.params import TableConfig
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.serving import ServingEndpoint
    from harmony_tpu.serving import protocol as _sp
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices("cpu")[:1])
    cap, width = 1024, 32
    table = DenseTable(
        TableSpec(TableConfig(table_id="bench-serve", capacity=cap,
                              value_shape=(width,), num_blocks=8)),
        mesh)
    table.multi_put(np.arange(cap, dtype=np.int32),
                    np.ones((cap, width), np.float32))
    ep = ServingEndpoint(table_fn=lambda job: table, cache_mb=8,
                         window_ms=2.0)
    ep.start()
    lat_ms: "list[float]" = []
    lock = _th.Lock()
    threads_n, reads_per = 4, 40
    rng = np.random.default_rng(7)
    # skewed key draw: a hot head so the cache has something to do
    hot = rng.integers(0, 64, size=(threads_n, reads_per, 12))
    cold = rng.integers(0, cap, size=(threads_n, reads_per, 4))

    def client(i):
        sock = _sp.connect(("127.0.0.1", ep.port))
        try:
            mine = []
            for r in range(reads_per):
                keys = np.concatenate(
                    [hot[i, r], cold[i, r]]).astype(np.int32)
                t0 = time.perf_counter()
                _sp.send_arrays(sock, {"op": "lookup", "r": r,
                                       "job": "bench", "mode": "live"},
                                (keys,))
                frame = _sp.recv_frame(sock)
                dt = (time.perf_counter() - t0) * 1000.0
                if frame and frame.get("op") == "rows":
                    mine.append(dt)
            with lock:
                lat_ms.extend(mine)
        finally:
            sock.close()

    def storm():
        ths = [_th.Thread(target=client, args=(i,))
               for i in range(threads_n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)

    # warmup: a full concurrent pass, so the coalesced gather
    # shapes the measured storm will hit are already compiled
    storm()
    with lock:
        lat_ms.clear()
    t0 = time.perf_counter()
    storm()
    wall = time.perf_counter() - t0
    st = ep.stats()
    ep.stop()
    if not lat_ms:
        raise RuntimeError("serving probe: no lookup was answered")
    ordered = sorted(lat_ms)

    def pct(p):
        return ordered[min(len(ordered) - 1,
                           int(p * (len(ordered) - 1)))]

    cache = st.get("cache") or {}
    hits = cache.get("hits", 0)
    lookups = hits + cache.get("misses", 0)
    return {
        "qps": round(len(lat_ms) / wall, 1),
        "p50_ms": round(pct(0.50), 3),
        "p99_ms": round(pct(0.99), 3),
        "cache_hit_rate": (round(hits / lookups, 3)
                           if lookups else None),
        "batch_occupancy": st.get("batch_occupancy"),
    }


def measure_lint() -> dict:
    """harmonylint-suite runtime probe (tracked round over round in the
    BENCH json): one full run over harmony_tpu/. Returns {"lint.wall_ms",
    findings, suppressed, files, passes}."""
    from harmony_tpu.analysis import run_lint

    r = run_lint()
    return {
        "lint.wall_ms": r.wall_ms,
        "findings": len(r.findings),
        "suppressed": len(r.suppressed),
        "files": r.files_scanned,
        "passes": len(r.passes_run),
    }


# -- machine-checked perf history (bench.py --compare) ---------------------
#
# The committed BENCH_r*.json trajectory was prose-reviewed until now: a
# regression only surfaced if a human read two JSON blobs side by side.
# `--compare` diffs the newest two rounds on the named headline series
# and exits 1 on a >threshold drop, so the history is machine-checked
# (bin/bench_diff.sh wraps it; tests/test_bench_compare.py runs it as a
# tier-1 smoke over the committed rounds).

#: higher-is-better series checked by default. `value` is the headline
#: aggregate; `cpu_rate` is the always-measurable denominator that keeps
#: rounds comparable when the accelerator transport is wedged;
#: `input_service.svc_sps` (dotted = nested lookup) tracks the
#: disaggregated-input-service serving rate — absent in rounds before
#: PR 10, which --compare skips rather than fails; the `autoscale.*`
#: pair tracks the closed policy loop (aggregate samples/sec and SLO
#: attainment of the churning-mix act arm) — absent before PR 15,
#: skipped the same way; `chaos.scenarios_ok` tracks the seeded chaos
#: smoke pair — any drop means an invariant went red on a pinned
#: schedule (absent before PR 18, skipped the same way);
#: `obs_incidents.recall` tracks the
#: incident engine's synthetic correlation probe — a drop means seeded
#: fault→diagnosis→action→resolution episodes stopped folding into
#: resolved incidents (absent before PR 19, skipped the same way); the
#: `serving.*` pair tracks the online read path (absent before PR 20,
#: skipped the same way) — serving.qps is higher-is-better like the
#: rest, serving.p99_ms is in LOWER_IS_BETTER so --compare fails on a
#: latency RISE, not a drop.
HEADLINE_SERIES = ("value", "cpu_rate", "input_service.svc_sps",
                   "autoscale.agg_sps", "autoscale.slo_attainment",
                   "chaos.scenarios_ok", "obs_incidents.recall",
                   "serving.qps", "serving.p99_ms")
#: series where a smaller number is the good direction (latencies):
#: compare_bench inverts the regression test for these
LOWER_IS_BETTER = frozenset({"serving.p99_ms"})
COMPARE_THRESHOLD = 0.15


def _bench_line(path: str) -> dict:
    """The result line of one committed round — either the bare JSON
    line bench.py prints or the driver's wrapper with it under
    "parsed"."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        return data["parsed"]
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a bench line")
    return data


def _series_value(line: dict, name: str):
    """The measured number for one series, or (None, reason) when the
    round holds no measurement for it. Dotted names index nested dicts
    (``input_service.svc_sps``). 0.0 counts as a MEASUREMENT only
    when the line does not carry the unreachable-accelerator markers
    that rounds before PR 21 wrote beside a 0.0 for 'did not run'."""
    v: "object | None" = line
    for part in name.split("."):
        if not isinstance(v, dict):
            v = None
            break
        v = v.get(part)
    if v is None:
        return None, "series absent"
    try:
        v = float(v)
    except (TypeError, ValueError):
        return None, f"non-numeric {v!r}"
    unreachable = ("error" in line
                   or line.get("accelerator") == "unreachable")
    if v <= 0.0 and unreachable:
        return None, "unreachable-accelerator round (0.0 is not a measurement)"
    return v, None


def find_bench_rounds(root: "str | None" = None) -> "list[str]":
    """Committed BENCH_r*.json beside this file (or under ``root``),
    ordered oldest -> newest by round number."""
    import glob
    import re

    root = root or os.path.dirname(os.path.abspath(__file__))

    def round_of(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    files = [p for p in glob.glob(os.path.join(root, "BENCH_r*.json"))
             if round_of(p) >= 0]
    return sorted(files, key=round_of)


def compare_bench(old_path: str, new_path: str,
                  series=HEADLINE_SERIES,
                  threshold: float = COMPARE_THRESHOLD) -> dict:
    """Diff two committed rounds on the named headline series. A series
    REGRESSES when both rounds measured it and the new value moved more
    than ``threshold`` in the BAD direction — below the old for the
    default higher-is-better series, above it for LOWER_IS_BETTER ones
    (latencies); a series only one round measured is reported as
    skipped (with the reason), never failed — an unreachable
    accelerator is a transport state, not a code regression."""
    old_line, new_line = _bench_line(old_path), _bench_line(new_path)
    report = {
        "old": os.path.basename(old_path),
        "new": os.path.basename(new_path),
        "threshold": threshold,
        "series": {},
        "regressions": [],
    }
    for name in series:
        old_v, old_why = _series_value(old_line, name)
        new_v, new_why = _series_value(new_line, name)
        row: dict = {"old": old_v, "new": new_v}
        if old_v is None or new_v is None:
            row["status"] = "skipped"
            row["note"] = "; ".join(
                f"{side}: {why}" for side, why in
                (("old", old_why), ("new", new_why)) if why)
            report["series"][name] = row
            continue
        row["ratio"] = round(new_v / old_v, 4) if old_v else None
        if name in LOWER_IS_BETTER:
            row["direction"] = "lower-is-better"
            regressed = old_v > 0 and new_v > old_v * (1.0 + threshold)
        else:
            regressed = old_v > 0 and new_v < old_v * (1.0 - threshold)
        if regressed:
            row["status"] = "regression"
            report["regressions"].append(name)
        else:
            row["status"] = "ok"
        report["series"][name] = row
    report["ok"] = not report["regressions"]
    return report


def compare_main(argv) -> int:
    """`python bench.py --compare [--dir D] [--series a,b] [--threshold
    T] [OLD NEW]` — defaults to the newest two committed rounds. Exit:
    0 ok, 1 regression, 2 usage (fewer than two rounds / bad files)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --compare")
    ap.add_argument("--compare", action="store_true")  # the mode flag
    ap.add_argument("--dir", default=None,
                    help="where the committed BENCH_r*.json live "
                         "(default: beside bench.py)")
    ap.add_argument("--series", default=",".join(HEADLINE_SERIES),
                    help="comma-separated headline series (higher=better "
                         "unless listed in LOWER_IS_BETTER)")
    ap.add_argument("--threshold", type=float, default=COMPARE_THRESHOLD,
                    help="allowed fractional drop before failing")
    ap.add_argument("files", nargs="*",
                    help="explicit OLD NEW round files (default: the "
                         "newest two in --dir)")
    args = ap.parse_args(argv)
    if args.files and len(args.files) != 2:
        print("--compare takes exactly two files (OLD NEW) or none",
              file=sys.stderr)
        return 2
    if args.files:
        old_path, new_path = args.files
    else:
        rounds = find_bench_rounds(args.dir)
        if len(rounds) < 2:
            print(f"--compare needs two committed rounds; found "
                  f"{len(rounds)}", file=sys.stderr)
            return 2
        old_path, new_path = rounds[-2], rounds[-1]
    series = [s.strip() for s in args.series.split(",") if s.strip()]
    try:
        report = compare_bench(old_path, new_path, series=series,
                               threshold=args.threshold)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"--compare: {e}", file=sys.stderr)
        return 2
    print(json.dumps(report, indent=2))
    return 0 if report["ok"] else 1


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench.py measures the accelerator and found none "
              f"(jax.devices()[0].platform = {devices[0].platform!r}); "
              "no result", file=sys.stderr)
        return 1
    print(f"accelerator devices: {devices}", file=sys.stderr)
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    print("accelerator warmup (compile) pass:", file=sys.stderr)
    run_concurrent(devices, scale=1.0, epochs=1)
    print("concurrent MLR+NMF+LDA on accelerator:", file=sys.stderr)
    tpu_rate, tpu_walls = run_concurrent(devices, scale=1.0)
    emit(tpu_rate, cpu_baseline_rate(), tpu_walls, devices)
    return 0


if __name__ == "__main__":
    if "--compare" in sys.argv[1:]:
        sys.exit(compare_main(sys.argv[1:]))
    sys.exit(main())
