#!/usr/bin/env python
"""Online serving plane — latency/throughput A/B across the two serving
levers, idle and against live training (docs/SERVING.md).

One fixed closed-loop read storm (8 client threads, skewed keys, the
SAME pregenerated key streams for every arm — equal offered load by
construction) against a live DenseTable through the ServingEndpoint's
framed wire, across the lever grid:

  * ``unbatched``       — batch window 0, cache 0: every lookup is its
    own lock-held gather (the baseline the micro-batching claim is
    measured against);
  * ``batched``         — window 2 ms: concurrent lookups coalesce into
    ONE keyed gather (the leader waits out the window, so the win is
    queueing-delay removed minus window added);
  * ``cached``          — ByteLRU hot rows only (layout+data-version
    keyed), no coalescing;
  * ``batched_cached``  — both levers, the production default.

Then the two endpoint configs that bracket the grid rerun CONCURRENT
with a training loop (multi_update bursts on the same table) to measure
interference both ways: serving p99 under training, and training
updates/sec with and without the storm.

In-bench consistency gate (asserted before any number is reported):
during the concurrent-training arm, a dedicated reader does ``pinned``
lookups throughout and every response must be bit-identical to the
committed chain epoch's durable bytes and stamped with its epoch — a
torn or drifting pinned read fails the bench, it does not get averaged.

CPU-backend honesty note: gathers here cost ~ms on 1 host device, so
the batching win is lock-queueing removed; on a real TPU the gather is
µs but the dispatch+transfer fixed cost per lookup is proportionally
LARGER, which favors coalescing more, not less.

Writes benchmarks/SERVING_r20.json and prints ONE JSON line.
Run: python benchmarks/serving_bench.py
"""
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

ROUNDS = 2
CLIENTS = 8
READS_PER_CLIENT = 50
KEYS_PER_READ = 16
CAPACITY, WIDTH = 4096, 64
HOT_HEAD = 64  # skew: 3/4 of each read's keys land in this head

ARMS = (
    ("unbatched", 0.0, 0),
    ("batched", 2.0, 0),
    ("cached", 0.0, 64),
    ("batched_cached", 2.0, 64),
)
TRAIN_ARMS = ("unbatched", "batched_cached")
TRAIN_BATCH = 256


def _streams():
    """One fixed skewed key stream per (client, read) — identical for
    every arm, so offered load is equal by construction."""
    rng = np.random.default_rng(20)
    hot = rng.integers(0, HOT_HEAD,
                       size=(CLIENTS, READS_PER_CLIENT, 12))
    cold = rng.integers(0, CAPACITY,
                        size=(CLIENTS, READS_PER_CLIENT,
                              KEYS_PER_READ - 12))
    return np.concatenate([hot, cold], axis=-1).astype(np.int32)


def _make_table():
    from harmony_tpu.config.params import TableConfig
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table import DenseTable, TableSpec

    mesh = build_mesh(jax.devices("cpu")[:1])
    table = DenseTable(
        TableSpec(TableConfig(table_id="srv-bench", capacity=CAPACITY,
                              value_shape=(WIDTH,), num_blocks=8)),
        mesh)
    table.multi_put(np.arange(CAPACITY, dtype=np.int32),
                    np.ones((CAPACITY, WIDTH), np.float32))
    return table


def _make_chain(root):
    """A committed 2-epoch chain for the pinned-consistency gate:
    epoch 1's durable bytes are exactly 2.0 everywhere."""
    from harmony_tpu.checkpoint import CheckpointManager
    from harmony_tpu.parallel import DevicePool
    from harmony_tpu.runtime import ETMaster

    master = ETMaster(DevicePool(jax.devices("cpu")[:1]))
    mgr = CheckpointManager.for_job(root, "srv-bench-pin")
    exs = master.add_executors(1)
    from harmony_tpu.config.params import TableConfig

    h = master.create_table(
        TableConfig(table_id="srv-bench-pin:m", capacity=32,
                    value_shape=(2,), num_blocks=8),
        [e.id for e in exs])
    for e in range(2):
        h.table.multi_update(list(range(32)), np.ones((32, 2), np.float32))
        mgr.checkpoint(h, commit=True, app_meta={"epoch": float(e)})
    return np.full((KEYS_PER_READ, 2), 2.0, np.float32)


def _storm(port, keys, lat_out):
    """The closed loop: CLIENTS threads, persistent sockets, each
    draining its fixed stream back-to-back. Returns wall seconds."""
    from harmony_tpu.serving import protocol

    errs = []

    def client(i):
        sock = protocol.connect(("127.0.0.1", port))
        try:
            mine = []
            for r in range(READS_PER_CLIENT):
                t0 = time.perf_counter()
                protocol.send_arrays(
                    sock, {"op": "lookup", "r": r, "job": "srv-bench",
                           "mode": "live"}, (keys[i, r],))
                frame = protocol.recv_frame(sock)
                dt = (time.perf_counter() - t0) * 1000.0
                if not frame or frame.get("op") != "rows":
                    raise RuntimeError(f"client {i} read {r}: {frame!r}")
                mine.append(dt)
            lat_out.extend(mine)
        except Exception as e:
            errs.append(e)
        finally:
            sock.close()

    t0 = time.perf_counter()
    ths = [threading.Thread(target=client, args=(i,))
           for i in range(CLIENTS)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=300)
    if errs:
        raise errs[0]
    return time.perf_counter() - t0


def _pct(ordered, p):
    return ordered[min(len(ordered) - 1, int(p * (len(ordered) - 1)))]


def run_arm(window_ms, cache_mb, keys, *, table=None, training=False,
            chkp_root=None, pinned_want=None):
    """One arm: (optionally) a training loop + pinned reader alongside
    the measured storm. Returns the arm's result dict."""
    from harmony_tpu.serving import ServingEndpoint, protocol

    table = table if table is not None else _make_table()
    ep = ServingEndpoint(table_fn=lambda job: table, cache_mb=cache_mb,
                         window_ms=window_ms, chkp_root=chkp_root)
    ep.start()
    stop = threading.Event()
    train_count = [0]
    pinned_reads = [0]
    gate_errs = []
    try:
        warm: "list[float]" = []
        _storm(ep.port, keys, warm)  # compile the coalesced gather shapes

        def trainer():
            rng = np.random.default_rng(1)
            while not stop.is_set():
                k = rng.integers(0, CAPACITY, TRAIN_BATCH).astype(np.int32)
                table.multi_update(
                    k, np.full((TRAIN_BATCH, WIDTH), 0.001, np.float32))
                train_count[0] += 1

        def pinned_reader():
            sock = protocol.connect(("127.0.0.1", ep.port))
            try:
                pk = np.arange(KEYS_PER_READ, dtype=np.int32)
                r = 0
                while not stop.is_set():
                    protocol.send_arrays(
                        sock, {"op": "lookup", "r": r,
                               "job": "srv-bench-pin", "mode": "pinned"},
                        (pk,))
                    frame = protocol.recv_frame(sock)
                    r += 1
                    if (not frame or frame.get("op") != "rows"
                            or frame.get("epoch") != 1
                            or not np.array_equal(
                                np.asarray(frame["data"][0], np.float32),
                                pinned_want)):
                        gate_errs.append(
                            f"pinned read {r}: "
                            f"{(frame or {}).get('epoch')!r}")
                        return
                    pinned_reads[0] += 1
            finally:
                sock.close()

        side = []
        if training:
            side = [threading.Thread(target=trainer),
                    threading.Thread(target=pinned_reader)]
            for t in side:
                t.start()
            time.sleep(0.1)  # the loops reach steady state

        lat: "list[float]" = []
        t_train0 = train_count[0]
        wall = _storm(ep.port, keys, lat)
        train_steps = train_count[0] - t_train0
        stop.set()
        for t in side:
            t.join(timeout=60)
        if gate_errs:
            raise AssertionError(
                f"pinned consistency gate failed: {gate_errs[0]}")
        st = ep.stats()
        cache = st.get("cache") or {}
        hits = cache.get("hits", 0)
        looked = hits + cache.get("misses", 0)
        ordered = sorted(lat)
        out = {
            "qps": round(len(lat) / wall, 1),
            "p50_ms": round(_pct(ordered, 0.50), 3),
            "p95_ms": round(_pct(ordered, 0.95), 3),
            "p99_ms": round(_pct(ordered, 0.99), 3),
            "batch_occupancy": st.get("batch_occupancy"),
            "cache_hit_rate": round(hits / looked, 3) if looked else None,
        }
        if training:
            out["train_updates_per_sec"] = round(train_steps / wall, 1)
            out["train_samples_per_sec"] = round(
                train_steps * TRAIN_BATCH / wall, 1)
            out["pinned_reads_ok"] = pinned_reads[0]
        return out
    finally:
        stop.set()
        ep.stop()


def _train_alone(table, seconds=1.0):
    """The interference denominator: the same update loop, no storm."""
    rng = np.random.default_rng(1)
    # warm the push program
    table.multi_update(
        rng.integers(0, CAPACITY, TRAIN_BATCH).astype(np.int32),
        np.full((TRAIN_BATCH, WIDTH), 0.001, np.float32))
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        k = rng.integers(0, CAPACITY, TRAIN_BATCH).astype(np.int32)
        table.multi_update(
            k, np.full((TRAIN_BATCH, WIDTH), 0.001, np.float32))
        n += 1
    return n * TRAIN_BATCH / (time.perf_counter() - t0)


def main() -> None:
    keys = _streams()
    arms: "dict[str, dict]" = {}
    # idle grid: best-of-ROUNDS per arm on p99 (host throughput drifts;
    # interleaved so no arm owns a quiet stretch)
    for _ in range(ROUNDS):
        for name, window_ms, cache_mb in ARMS:
            r = run_arm(window_ms, cache_mb, keys)
            if name not in arms or r["p99_ms"] < arms[name]["p99_ms"]:
                arms[name] = r
    # the bench's claim, asserted in-bench: both levers on must beat the
    # unbatched baseline on tail latency at equal offered load
    assert arms["batched_cached"]["p99_ms"] < arms["unbatched"]["p99_ms"], (
        f"micro-batching+cache lost on p99: "
        f"{arms['batched_cached']['p99_ms']} vs "
        f"{arms['unbatched']['p99_ms']}")

    with tempfile.TemporaryDirectory() as root:
        pinned_want = _make_chain(root)
        grid = {n: (w, c) for n, w, c in ARMS}
        train_arms = {}
        train_alone_sps = None
        for name in TRAIN_ARMS:
            w, c = grid[name]
            table = _make_table()
            if train_alone_sps is None:
                train_alone_sps = round(_train_alone(table), 1)
            train_arms[name] = run_arm(
                w, c, keys, table=table, training=True, chkp_root=root,
                pinned_want=pinned_want)
            assert train_arms[name]["pinned_reads_ok"] > 0, (
                "pinned gate never exercised")

    out = {
        "metric": "serving",
        "unit": "lookup ms (client-measured, closed loop)",
        "rounds": ROUNDS,
        "mode": (f"{CLIENTS} closed-loop clients x {READS_PER_CLIENT} "
                 f"lookups x {KEYS_PER_READ} keys, identical skewed "
                 "streams per arm (equal offered load), best-of per arm "
                 "on p99"),
        "workload": {"capacity": CAPACITY, "width": WIDTH,
                     "hot_head": HOT_HEAD,
                     "train_batch": TRAIN_BATCH},
        "arms": arms,
        "concurrent_training": {
            "train_alone_samples_per_sec": train_alone_sps,
            "arms": train_arms,
            "note": "same storm with a multi_update loop on the same "
                    "table; train_samples_per_sec vs the alone row is "
                    "the interference cost, and the pinned reader's "
                    "bit-exact gate ran throughout",
        },
        "consistency_gate": {
            "mode": "pinned",
            "checked_reads": sum(a["pinned_reads_ok"]
                                 for a in train_arms.values()),
            "result": "bit-identical to the committed epoch throughout",
        },
        "claim": {
            "p99_unbatched_ms": arms["unbatched"]["p99_ms"],
            "p99_batched_cached_ms": arms["batched_cached"]["p99_ms"],
            "p99_win": round(
                arms["unbatched"]["p99_ms"]
                / arms["batched_cached"]["p99_ms"], 2),
            "note": "asserted in-bench: batched+cached < unbatched on "
                    "p99 at equal offered load",
        },
        "note": "CPU backend: gathers are ~ms and serialize on the "
                "table lock, so coalescing removes queueing delay; on "
                "TPU the per-lookup dispatch overhead batching removes "
                "is proportionally larger",
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SERVING_r20.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    # a CPU-backend benchmark: run standalone it opens no accelerator
    # (importers keep their own platform — nothing is set at import)
    jax.config.update("jax_platforms", "cpu")
    main()
