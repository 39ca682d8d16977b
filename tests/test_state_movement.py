"""Parallel state-movement data plane (HARMONY_MOVE_PARALLEL /
HARMONY_CHKP_IO_THREADS): serial-vs-parallel parity, leg splitting,
write-side backpressure, and fault-site semantics from pool threads —
retry counters and error classification must be thread-position
independent (a leg retried on a worker thread is the same leg retried
on the main thread)."""
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

import jax

from harmony_tpu import faults
from harmony_tpu.checkpoint import CheckpointManager
from harmony_tpu.checkpoint.manager import (
    CheckpointCorruptError,
    _InflightBudget,
    _recovery_put,
    drop_recovery_cache,
)
from harmony_tpu.config.params import TableConfig
from harmony_tpu.parallel import DevicePool
from harmony_tpu.runtime import ETMaster
from harmony_tpu.table import blockmove
from harmony_tpu.table.blockmove import (
    MovePlan,
    _leg_streams,
    _TcpReceiver,
    _tcp_exchange,
)


@pytest.fixture()
def master(devices):
    return ETMaster(DevicePool(devices))


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.disarm()
    drop_recovery_cache()


class _FakeKV:
    """In-process stand-in for the jax coordination KV store."""

    def __init__(self):
        self.kv = {}

    def key_value_set(self, k, v):
        self.kv[k] = v

    def blocking_key_value_get(self, k, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        while time.monotonic() < deadline:
            if k in self.kv:
                return self.kv[k]
            time.sleep(0.005)
        raise TimeoutError(k)

    def key_value_delete(self, k):
        self.kv.pop(k, None)


def _payload(b, rows=16, dim=8):
    return (np.arange(rows * dim, dtype=np.float32).reshape(rows, dim)
            + 31 * b)


def _multi_peer_exchange(monkeypatch, parallel, seq, nb=12):
    """pid 0 sends nb blocks striped to two fake peers whose receivers
    live in-process; returns ({dst: {block: arr}}, wire_sent)."""
    monkeypatch.setenv("HARMONY_MOVE_PARALLEL", str(parallel))
    kv = _FakeKV()
    monkeypatch.setattr(blockmove, "_kv_client", lambda: kv)
    expected = {1: {b for b in range(nb) if b % 2 == 0},
                2: {b for b in range(nb) if b % 2 == 1}}
    rxs = {dst: _TcpReceiver(blocks) for dst, blocks in expected.items()}
    for dst, rx in rxs.items():
        kv.key_value_set(f"harmony/blockmove/{seq}/{dst}",
                         f"127.0.0.1:{rx.port}")
    outgoing = {b: _payload(b) for b in range(nb)}
    plan = MovePlan(
        sends={0: [(b, 1 + (b % 2)) for b in range(nb)]},
        recvs=expected,  # pid 0 receives nothing; peers are the rxs
        block_nbytes=outgoing[0].nbytes,
    )
    try:
        _, wire_sent = _tcp_exchange(plan, outgoing, seq)
        got = {dst: dict(rx.wait(time.monotonic() + 20))
               for dst, rx in rxs.items()}
    finally:
        for rx in rxs.values():
            rx.close()
    return got, wire_sent


class TestTcpParallelLegs:
    def test_multi_peer_parallel_parity_with_serial(self, monkeypatch):
        """The acceptance parity check at the transport layer: parallel
        legs deliver byte-identical blocks and identical wire accounting
        vs the serial fallback."""
        serial, sent_1 = _multi_peer_exchange(monkeypatch, 1, seq=70001)
        parallel, sent_4 = _multi_peer_exchange(monkeypatch, 4, seq=70002)
        assert sent_1 == sent_4 == sum(
            _payload(b).nbytes for b in range(12))
        assert serial.keys() == parallel.keys()
        for dst in serial:
            assert serial[dst].keys() == parallel[dst].keys()
            for b in serial[dst]:
                np.testing.assert_array_equal(serial[dst][b],
                                              parallel[dst][b])
                np.testing.assert_array_equal(serial[dst][b], _payload(b))

    def test_oversized_leg_splits_into_striped_streams(self, monkeypatch):
        """With the split threshold forced tiny, one destination's leg
        fans out over multiple connections — the receiver reassembles by
        block id, bytes intact, wire accounting exact."""
        monkeypatch.setattr(blockmove, "_LEG_SPLIT_BYTES", 1)
        got, wire_sent = _multi_peer_exchange(monkeypatch, 4, seq=70003)
        for dst, blocks in got.items():
            for b, arr in blocks.items():
                np.testing.assert_array_equal(arr, _payload(b))
        assert wire_sent == sum(_payload(b).nbytes for b in range(12))

    def test_leg_streams_partition(self):
        outgoing = {b: np.zeros((4, 2), np.float32) for b in range(8)}
        by_dst = {2: [0, 1, 2, 3], 5: [4, 5, 6, 7]}
        # serial: exactly one stream per destination, destination order
        assert _leg_streams(by_dst, outgoing, 1) == [
            (2, [0, 1, 2, 3]), (5, [4, 5, 6, 7])]
        # parallel with a tiny split threshold: stripes partition each
        # destination's blocks exactly (no dup, no loss)
        old = blockmove._LEG_SPLIT_BYTES
        blockmove._LEG_SPLIT_BYTES = 1
        try:
            legs = _leg_streams(by_dst, outgoing, 3)
        finally:
            blockmove._LEG_SPLIT_BYTES = old
        for dst, blocks in by_dst.items():
            stripes = [bs for d, bs in legs if d == dst]
            assert 1 < len(stripes) <= 3
            assert sorted(b for s in stripes for b in s) == blocks

    def test_send_fault_from_pool_thread_retried(self, monkeypatch):
        """blockmove.send tripping on a pool thread retries the leg under
        the policy exactly like the serial path: migration completes,
        retry counters move, payload intact."""
        monkeypatch.setenv("HARMONY_RETRY_MAX_ATTEMPTS", "3")
        monkeypatch.setenv("HARMONY_RETRY_BASE_DELAY", "0.001")
        monkeypatch.setenv("HARMONY_RETRY_MAX_DELAY", "0.002")
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "blockmove.send", match={"block": 3}, count=1,
            exc="ConnectionResetError", message="injected link flap")]))
        blockmove._LEG_RETRIES[0] = 0
        got, wire_sent = _multi_peer_exchange(monkeypatch, 4, seq=70004)
        from harmony_tpu.faults.retry import retry_counters

        assert retry_counters()["blockmove.send.retries"] >= 1
        assert blockmove._LEG_RETRIES[0] >= 1
        for dst, blocks in got.items():
            for b, arr in blocks.items():
                np.testing.assert_array_equal(arr, _payload(b))
        # unique bytes, not retransmits
        assert wire_sent == sum(_payload(b).nbytes for b in range(12))

    def test_connect_giveup_from_pool_thread_escalates(self, monkeypatch):
        """Retry exhaustion on a worker thread still classifies as
        MigrationTransportError carrying infra_suspect — the pool must
        not swallow or rewrap the auto-resume evidence."""
        monkeypatch.setenv("HARMONY_MOVE_PARALLEL", "4")
        monkeypatch.setenv("HARMONY_RETRY_MAX_ATTEMPTS", "2")
        monkeypatch.setenv("HARMONY_RETRY_BASE_DELAY", "0.001")
        monkeypatch.setenv("HARMONY_RETRY_MAX_DELAY", "0.002")
        kv = _FakeKV()
        monkeypatch.setattr(blockmove, "_kv_client", lambda: kv)
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "blockmove.connect", count=-1, exc="ConnectionError",
            message="fabric down")]))
        payload = np.ones((2, 2), np.float32)
        plan = MovePlan(sends={0: [(0, 1), (1, 2)]}, recvs={},
                        block_nbytes=payload.nbytes)
        with pytest.raises(blockmove.MigrationTransportError) as ei:
            _tcp_exchange(plan, {0: payload, 1: payload}, 70005)
        assert ei.value.infra_suspect

    def test_large_frame_single_writev_roundtrip(self):
        """A payload past the coalesce threshold rides the sendmsg
        (writev) path; the recv_into reader reassembles it exactly."""
        rx = _TcpReceiver({9})
        try:
            big = np.arange(blockmove._IO_CHUNK // 4 + 777,
                            dtype=np.float32)
            with socket.create_connection(("127.0.0.1", rx.port)) as s:
                blockmove._send_frame(s, 9, big)
            got = rx.wait(time.monotonic() + 20)[9]
            np.testing.assert_array_equal(got, big)
        finally:
            rx.close()


class TestFileExchangeParallel:
    def test_parallel_parity_with_serial(self, tmp_path, monkeypatch):
        """Staged-file transport: pooled per-block write/read loops are
        byte-identical to the serial fallback."""
        from jax.sharding import Mesh

        from harmony_tpu.table.blockmove import _file_exchange

        devs = jax.devices()[:2]
        mesh = Mesh(np.array(devs), ("model",))
        outgoing = {b: _payload(b) for b in range(10)}
        plan = MovePlan(sends={0: [(b, 0) for b in range(10)]},
                        recvs={0: set(range(10))},
                        block_nbytes=outgoing[0].nbytes)
        results = {}
        for par, seq in ((1, 70101), (4, 70102)):
            monkeypatch.setenv("HARMONY_MOVE_PARALLEL", str(par))
            monkeypatch.setenv("HARMONY_POD_STAGE_ROOT",
                               str(tmp_path / f"p{par}"))
            os.makedirs(str(tmp_path / f"p{par}"), exist_ok=True)
            received, written = _file_exchange(plan, dict(outgoing), seq,
                                               mesh, mesh)
            assert written == sum(a.nbytes for a in outgoing.values())
            results[par] = received
        assert results[1].keys() == results[4].keys()
        for b in results[1]:
            np.testing.assert_array_equal(results[1][b], results[4][b])
            np.testing.assert_array_equal(results[1][b], outgoing[b])

    def test_stage_write_fault_from_pool_thread_escalates(
            self, tmp_path, monkeypatch):
        """A persistent stage-write failure on a pool thread still
        surfaces as MigrationTransportError with clean staging."""
        from jax.sharding import Mesh

        from harmony_tpu.table.blockmove import (
            MigrationTransportError,
            _file_exchange,
        )

        monkeypatch.setenv("HARMONY_MOVE_PARALLEL", "4")
        monkeypatch.setenv("HARMONY_RETRY_MAX_ATTEMPTS", "2")
        monkeypatch.setenv("HARMONY_RETRY_BASE_DELAY", "0.001")
        monkeypatch.setenv("HARMONY_RETRY_MAX_DELAY", "0.002")
        monkeypatch.setenv("HARMONY_POD_STAGE_ROOT", str(tmp_path))
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "blockmove.stage_write", count=-1, exc="OSError",
            message="participant killed before publish")]))
        devs = jax.devices()[:2]
        mesh = Mesh(np.array(devs), ("model",))
        outgoing = {b: _payload(b) for b in range(6)}
        plan = MovePlan(sends={0: [(b, 0) for b in range(6)]},
                        recvs={0: set(range(6))},
                        block_nbytes=outgoing[0].nbytes)
        with pytest.raises(MigrationTransportError, match="staging block"):
            _file_exchange(plan, outgoing, 70103, mesh, mesh)
        assert not [p for p in tmp_path.iterdir()
                    if p.name.startswith("harmony-move-70103")]


def _bench_table(master, tid, num_blocks=16, rows=8, dim=4):
    cfg = TableConfig(table_id=tid, capacity=num_blocks * rows,
                      value_shape=(dim,), num_blocks=num_blocks)
    h = master.create_table(cfg, master.executor_ids()[:2] or
                            [e.id for e in master.add_executors(2)])
    vals = (np.arange(cfg.capacity, dtype=np.float32)[:, None]
            * np.ones((dim,), np.float32))
    h.table.multi_update(list(range(cfg.capacity)), vals)
    return h, vals


class TestCheckpointParallelIO:
    def test_write_restore_parity_across_thread_counts(
            self, master, tmp_path, monkeypatch):
        """The acceptance parity check: checkpoints written and restored
        at HARMONY_CHKP_IO_THREADS 1 and 4 produce identical manifests
        (same per-block checksums) and byte-identical restored tables,
        in every write/restore thread-count combination."""
        h, vals = _bench_table(master, "par-io")
        infos, cids, mgrs = {}, {}, {}
        for t in (1, 4):
            monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", str(t))
            mgr = CheckpointManager(str(tmp_path / f"t{t}" / "temp"),
                                    str(tmp_path / f"t{t}" / "commit"))
            cids[t] = mgr.checkpoint(h)
            infos[t] = mgr.info(cids[t])
            mgrs[t] = mgr
        assert infos[1].block_checksums == infos[4].block_checksums
        for wt in (1, 4):
            for rt in (1, 4):
                monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", str(rt))
                rh = mgrs[wt].restore(master, cids[wt],
                                      master.executor_ids()[:2],
                                      table_id=f"par-io-r{wt}{rt}")
                got = np.asarray(rh.table.pull_array())
                np.testing.assert_array_equal(got, vals)
                rh.drop()

    def test_partial_restore_parity_and_accounting(
            self, master, tmp_path, monkeypatch):
        """restore_partial at 4 threads: byte parity with serial, cached
        blocks still never touch storage (the O(lost-bytes) contract is
        thread-count independent)."""
        from harmony_tpu.checkpoint import manager as mgr_mod

        h, vals = _bench_table(master, "par-partial")
        mgr = CheckpointManager(str(tmp_path / "temp"),
                                str(tmp_path / "commit"))
        cid = mgr.checkpoint(h)
        host = {b: np.asarray(a)
                for b, a in h.table.addressable_blocks().items()}
        cached = {b: a for b, a in host.items() if b % 2 == 0}
        for t in (1, 4):
            monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", str(t))
            _recovery_put("par-partial", cid, dict(cached))
            mgr_mod.reset_read_stats()
            rh, stats = mgr.restore_partial(
                master, cid, master.executor_ids()[:2],
                table_id=f"par-partial-r{t}")
            got = np.asarray(rh.table.pull_array())
            rh.drop()
            np.testing.assert_array_equal(got, vals)
            assert stats["blocks_local"] == len(cached)
            assert stats["blocks_read"] == len(host) - len(cached)
            assert mgr_mod.read_stats["blocks_read"] == stats["blocks_read"]
            drop_recovery_cache()

    def test_block_write_fault_retried_from_pool_thread(
            self, master, tmp_path, monkeypatch):
        """chkp.block_write tripping on an I/O pool thread retries under
        the policy (counters move) and the checkpoint lands restorable."""
        monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", "4")
        monkeypatch.setenv("HARMONY_RETRY_MAX_ATTEMPTS", "3")
        monkeypatch.setenv("HARMONY_RETRY_BASE_DELAY", "0.001")
        monkeypatch.setenv("HARMONY_RETRY_MAX_DELAY", "0.002")
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "chkp.block_write", count=2, exc="OSError",
            message="injected ENOSPC blip")]))
        from harmony_tpu.faults.retry import retry_counters

        before = retry_counters().get("chkp.block_write.retries", 0)
        h, vals = _bench_table(master, "par-wfault")
        mgr = CheckpointManager(str(tmp_path / "temp"),
                                str(tmp_path / "commit"))
        cid = mgr.checkpoint(h)
        assert retry_counters()["chkp.block_write.retries"] >= before + 2
        rh = mgr.restore(master, cid, master.executor_ids()[:2],
                         table_id="par-wfault-r")
        np.testing.assert_array_equal(np.asarray(rh.table.pull_array()),
                                      vals)
        rh.drop()

    def test_partial_read_fault_from_pool_thread_escalates(
            self, master, tmp_path, monkeypatch):
        """chkp.partial_read firing on a pool thread escalates exactly
        like the serial path: the injected OSError (not a corruption
        reclassification) reaches the caller and no orphan table is
        left behind."""
        monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", "4")
        h, _vals = _bench_table(master, "par-pfault")
        mgr = CheckpointManager(str(tmp_path / "temp"),
                                str(tmp_path / "commit"))
        cid = mgr.checkpoint(h)
        faults.arm(faults.FaultPlan([faults.FaultRule(
            "chkp.partial_read", count=-1, exc="OSError",
            message="second failure mid-restore")]))
        before = set(master.table_ids())
        with pytest.raises(OSError, match="mid-restore"):
            mgr.restore_partial(master, cid, master.executor_ids()[:2],
                                table_id="par-pfault-r")
        assert set(master.table_ids()) == before

    def test_corrupt_block_classified_from_pool_thread(
            self, master, tmp_path, monkeypatch):
        """Corruption found by a pool-thread read still classifies as
        CheckpointCorruptError (never retried into success, never a bare
        pool error) and the failed restore leaves no orphan."""
        monkeypatch.setenv("HARMONY_CHKP_IO_THREADS", "4")
        h, _vals = _bench_table(master, "par-corrupt")
        mgr = CheckpointManager(str(tmp_path / "temp"),
                                str(tmp_path / "commit"))
        cid = mgr.checkpoint(h)
        cdir = os.path.join(mgr.temp_root, cid)
        victim = next(f for f in sorted(os.listdir(cdir))
                      if f.startswith("3."))
        with open(os.path.join(cdir, victim), "r+b") as f:
            f.seek(12)
            f.write(b"\xff" * 8)
        before = set(master.table_ids())
        with pytest.raises(CheckpointCorruptError):
            mgr.restore(master, cid, master.executor_ids()[:2],
                        table_id="par-corrupt-r")
        assert set(master.table_ids()) == before


class TestInflightBudget:
    def test_backpressure_blocks_and_releases(self):
        budget = _InflightBudget(100)
        budget.acquire(60)
        acquired = threading.Event()

        def second():
            budget.acquire(60)  # 120 > 100: must wait for the release
            acquired.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        assert not acquired.wait(0.15)
        budget.release(60)
        assert acquired.wait(5)
        t.join()

    def test_oversized_single_block_admitted_alone(self):
        budget = _InflightBudget(10)
        budget.acquire(500)  # larger than the cap: admitted, no deadlock
        budget.release(500)


class TestChkpIoBenchSmoke:
    def test_chkp_io_bench_tiny(self, tmp_path):
        """Tier-1 smoke of benchmarks/chkp_io_bench.py at toy sizes: the
        sweep runs both profiles, parity holds (asserted inside), and
        every arm reports positive timings. Best of three (``repeats``): on
        a host loaded by the other test workers one 40 ms reading drowns in
        the scheduler's noise (0.28 against 0.26 s read, twice, PR 54)."""
        from benchmarks.chkp_io_bench import run_bench

        res = run_bench(num_blocks=8, block_rows=8, dim=4,
                        threads=(1, 4), repeats=3,
                        tmp_root=str(tmp_path))
        assert set(res["profiles"]) == {"local", "remote_5ms"}
        for profile, arm in res["profiles"].items():
            for t, row in arm.items():
                for op, v in row.items():
                    assert v > 0, (profile, t, op)
        # remote profile: 4 threads must beat serial on reads — storage
        # latency overlaps across the pool (8 blocks x 5ms vs ceil(8/4))
        remote = res["profiles"]["remote_5ms"]
        assert remote["4"]["restore_s"] < remote["1"]["restore_s"]
        assert res["speedups_at_4"]["remote_5ms"]["restore"] > 1.0
