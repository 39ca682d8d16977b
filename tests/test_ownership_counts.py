"""``BlockManager.block_counts()`` against a recount of the ownership map.

The manager keeps its per-executor counts as ownership changes, so the
per-epoch ServerMetrics hook (jobserver/entity.py
``_make_table_metrics_hook``) and the pod plan hook pay O(executors) an
epoch, not O(blocks) — PERF.md §6, PR 32. ``_owner`` stays the source of
truth for who owns what; every mutator must leave the counts equal to what
a pass over ``ownership_vector()`` finds.
"""
import random
from collections import Counter

import pytest

from harmony_tpu.table import BlockManager


def recount(bm: BlockManager):
    """What the pre-PR-32 ``block_counts()`` computed: one pass over the
    ownership vector."""
    execs = bm.executors
    seen = Counter(bm.ownership_vector())
    return {e: seen.get(i, 0) for i, e in enumerate(execs)}


def check(bm: BlockManager):
    counts = bm.block_counts()
    assert counts == recount(bm)
    assert list(counts) == bm.executors  # same key order as the recount's
    assert sum(counts.values()) == bm.num_blocks


# -- a seeded random walk over every mutator ---------------------------------

def _random_walk(bm: BlockManager, rng: random.Random, steps: int):
    spare = [f"x{i}" for i in range(6)]
    for _ in range(steps):
        execs = bm.executors
        counts = bm.block_counts()
        op = rng.choice(("move", "move", "move", "over", "associate",
                         "unassociate", "rebalance", "self"))
        if op == "move" and len(execs) > 1:
            src, dst = rng.sample(execs, 2)
            n = rng.randint(0, counts[src])
            moved = bm.move(src, dst, n)
            assert len(moved) == n
        elif op == "self":
            src = rng.choice(execs)
            bm.move(src, src, rng.randint(0, counts[src]))
        elif op == "over" and len(execs) > 1:
            # more blocks than the source owns: refused, nothing changes
            src, dst = rng.sample(execs, 2)
            before = bm.ownership_vector()
            with pytest.raises(ValueError, match="owns only"):
                bm.move(src, dst, counts[src] + 1 + rng.randint(0, 3))
            assert bm.ownership_vector() == before
        elif op == "associate":
            free = [e for e in spare if e not in execs]
            if free:
                bm.associate(rng.choice(free))
        elif op == "unassociate" and len(execs) > 1:
            victim = rng.choice(execs)
            if counts[victim]:
                with pytest.raises(ValueError, match="still owns"):
                    bm.unassociate(victim)
                # drain it to zero, then it may leave
                dst = rng.choice([e for e in execs if e != victim])
                bm.move(victim, dst, counts[victim])
                check(bm)
            bm.unassociate(victim)
            assert victim not in bm.block_counts()
        elif op == "rebalance":
            k = rng.randint(1, 5)
            bm.rebalance(rng.sample(spare + ["e0", "e1", "e2"], k))
        check(bm)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
@pytest.mark.parametrize("num_blocks,num_execs", [(1, 1), (7, 3), (64, 4),
                                                  (1000, 5)])
def test_counts_equal_a_recount_after_every_mutation(seed, num_blocks,
                                                     num_execs):
    rng = random.Random(1000 * seed + num_blocks)
    bm = BlockManager("t", num_blocks, [f"e{i}" for i in range(num_execs)])
    check(bm)
    _random_walk(bm, rng, steps=60)


# -- the named elastic sequences ----------------------------------------------

def _drain_middle(bm):
    """Drain a MIDDLE executor to zero, unassociate it (the indices above
    it shift down), then bring it back and hand it blocks again."""
    bm.move("e1", "e0", bm.block_counts()["e1"])
    check(bm)
    bm.unassociate("e1")
    assert bm.executors == ["e0", "e2", "e3"]
    check(bm)
    bm.move("e3", "e2", 2)  # the shifted indices still name the right rows
    check(bm)
    bm.associate("e1")
    assert bm.block_counts()["e1"] == 0
    bm.move("e0", "e1", 5)
    assert bm.block_counts()["e1"] == 5


def _grow_then_shrink(bm):
    bm.associate("n0")
    bm.associate("n1")
    check(bm)
    bm.move("e0", "n0", 3)
    bm.move("e2", "n1", 4)
    check(bm)
    bm.move("n0", "e3", 3)
    bm.unassociate("n0")
    check(bm)
    bm.rebalance(["e0", "n1"])  # wholesale shrink
    assert set(bm.block_counts()) == {"e0", "n1"}


def _rebalance_uneven(bm):
    """Round-robin over a count that does not divide the blocks."""
    bm.rebalance(["a", "b", "c", "d", "e", "f", "g"])
    check(bm)
    bm.rebalance(["only"])
    assert bm.block_counts() == {"only": bm.num_blocks}
    bm.rebalance([f"w{i}" for i in range(bm.num_blocks + 3)])  # > blocks
    assert sorted(bm.block_counts().values())[:3] == [0, 0, 0]


def _move_too_many(bm):
    owned = bm.block_counts()["e2"]
    with pytest.raises(ValueError, match="owns only"):
        bm.move("e2", "e0", owned + 1)
    check(bm)
    bm.move("e2", "e0", owned)  # exactly all of them is fine
    assert bm.block_counts()["e2"] == 0
    with pytest.raises(ValueError, match="owns only"):
        bm.move("e2", "e0", 1)


def _move_nothing_and_to_self(bm):
    before = bm.block_counts()
    assert bm.move("e0", "e1", 0) == []
    assert len(bm.move("e3", "e3", 2)) == 2
    assert bm.block_counts() == before


def _unknown_names_change_nothing(bm):
    before = bm.block_counts()
    with pytest.raises(ValueError):
        bm.move("nobody", "e0", 1)
    with pytest.raises(ValueError):
        bm.move("e0", "nobody", 1)
    with pytest.raises(ValueError):
        bm.unassociate("nobody")
    with pytest.raises(ValueError, match="already associated"):
        bm.associate("e0")
    with pytest.raises(ValueError):
        bm.rebalance([])
    assert bm.block_counts() == before


@pytest.mark.parametrize("scenario", [
    _drain_middle, _grow_then_shrink, _rebalance_uneven, _move_too_many,
    _move_nothing_and_to_self, _unknown_names_change_nothing,
], ids=lambda f: f.__name__.lstrip("_"))
def test_elastic_sequences(scenario):
    bm = BlockManager("t", 37, ["e0", "e1", "e2", "e3"])
    scenario(bm)
    check(bm)


# -- listeners see the same map the counts describe ---------------------------

def test_listener_snapshot_agrees_with_the_counts():
    bm = BlockManager("t", 24, ["e0", "e1", "e2"])
    seen = []

    def listener(table_id, owners):
        # re-entering the manager from a listener (RLock) must already see
        # the counts of the snapshot being announced
        want = Counter(owners)
        got = bm.block_counts()
        seen.append(got == {e: want.get(i, 0)
                            for i, e in enumerate(bm.executors)})

    bm.subscribe(listener)
    bm.move("e0", "e2", 3)
    bm.move("e1", "e0", 8)
    bm.unassociate("e1")
    bm.rebalance(["e0", "e9"])
    assert seen == [True] * 4


# -- the result is the caller's to mutate -------------------------------------

def test_the_returned_dict_is_a_copy():
    """runtime/master.py subtracts from the dict it gets."""
    bm = BlockManager("t", 10, ["e0", "e1"])
    counts = bm.block_counts()
    counts["e0"] -= 4
    counts["ghost"] = 99
    del counts["e1"]
    assert bm.block_counts() == {"e0": 5, "e1": 5}
    assert bm.block_counts() is not bm.block_counts()
    bm.move("e0", "e1", 2)
    assert bm.block_counts() == {"e0": 3, "e1": 7}


# -- the cost guard: no clock, no pass over the blocks ------------------------

class _NoWalk(list):
    """The ownership list, refusing to be walked."""

    def __iter__(self):
        raise AssertionError("block_counts() walked the ownership map")


@pytest.mark.parametrize("num_blocks", [16, 170_000])
def test_block_counts_never_walks_the_ownership_map(num_blocks):
    bm = BlockManager("t", num_blocks, ["e0", "e1", "e2"])
    bm.move("e0", "e1", 5)
    want = recount(bm)
    bm._owner = _NoWalk(bm._owner)
    with pytest.raises(AssertionError, match="walked"):
        recount(bm)  # the guard does bite a recount
    assert bm.block_counts() == want
    # owner_of indexes, it does not walk: still the source of truth
    assert bm.owner_of(0) in ("e0", "e1")


# -- the caller that asks every epoch: a job's ServerMetrics rows -------------

def test_server_metrics_rows_equal_the_recount_around_a_mid_job_move(
        devices, monkeypatch):
    """A windowed job (EPOCH_WINDOW = 8, probes off) on a table of many
    blocks: every per-epoch ServerMetrics row carries what a recount of the
    ownership map gives — before and after a block move lands mid-job
    through the plan hook (the window ends AT the plan epoch)."""
    from harmony_tpu.config.params import (JobConfig, TableConfig,
                                           TrainerParams)
    from harmony_tpu.dolphin import WorkerTasklet
    from harmony_tpu.jobserver import JobServer, podplan
    from harmony_tpu.optimizer.hetero import _largest_remainder
    from harmony_tpu.parallel import DevicePool

    assert WorkerTasklet.EPOCH_WINDOW == 8
    blocks, epochs, nb, move_at, moved = 512, 20, 4, 10, 37
    job = "counts-mlr"
    windows = []
    window_len = WorkerTasklet._epoch_window_len

    def noting(self, epoch, num_epochs):
        w = window_len(self, epoch, num_epochs)
        windows.append((epoch, w))
        return w

    monkeypatch.setattr(WorkerTasklet, "_epoch_window_len", noting)
    server = JobServer(2, device_pool=DevicePool(devices[:2]))
    server.start()
    try:
        execs = server.master.executor_ids()
        podplan.schedule(job, {"epoch": move_at, "src": execs[0],
                               "dst": execs[1], "num_blocks": moved})
        cfg = JobConfig(
            job_id=job, app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            # MLR's schema for the parameters below — 4 classes x 128
            # partitions of 4 features = 512 rows — one row a block
            tables=[TableConfig(table_id="counts-m", capacity=blocks,
                                value_shape=(4,), num_blocks=blocks)],
            params=TrainerParams(
                num_epochs=epochs, num_mini_batches=nb, comm_probe_period=0,
                app_params={"num_classes": 4, "num_features": 512,
                            "features_per_partition": 4, "step_size": 0.1}),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 64, "num_features": 512,
                                "num_classes": 4, "seed": 3}})
        result = server.submit(cfg).result(timeout=300)
        rows = server.metrics.server_metrics(job)
    finally:
        podplan.clear(job)
        server.shutdown(timeout=60)
    assert [(p["epoch"], p["moved"]) for p in result["applied_plans"]] == [
        (move_at, moved)]
    # the hook replayed eight times a drain, and the move ended a window
    assert windows == [(0, 8), (8, 3), (11, 8), (19, 1)]
    # the parent's recount, on a mirror of the job's ownership map
    mirror = BlockManager("mirror", blocks, execs)
    by_epoch = {}
    for m in rows:
        by_epoch.setdefault(m.window_idx, []).append(m)
    # one report an epoch + the end-of-job closing window
    assert sorted(by_epoch) == list(range(epochs + 1))
    for e in range(epochs + 1):
        counts = recount(mirror)
        owners = [(ex, n) for ex, n in counts.items() if n > 0]
        weights = [n for _, n in owners]
        got = by_epoch[e]
        assert [(m.executor_id, m.num_blocks) for m in got] == owners, e
        for field in ("pull_count", "push_count", "pull_bytes"):
            vals = [getattr(m, field) for m in got]
            assert vals == _largest_remainder(sum(vals), weights), (e, field)
        if e < epochs:  # each epoch's own ops, not lumped on a window's first
            assert sum(m.pull_count for m in got) == nb, e
            assert sum(m.push_count for m in got) == nb, e
            assert sum(m.pull_bytes for m in got) > 0, e
        if e == move_at:  # the plan hook runs AFTER this epoch's report
            mirror.move(execs[0], execs[1], moved)
    assert by_epoch[move_at][0].num_blocks == blocks // 2
    assert by_epoch[move_at + 1][0].num_blocks == blocks // 2 - moved
    assert by_epoch[epochs][1].num_blocks == blocks // 2 + moved
