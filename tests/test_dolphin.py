"""End-to-end Dolphin training tests on the virtual 8-device mesh.

The analogues of the reference's integration tests (SURVEY.md §4): run a
full app and assert exact values (AddVector/AddInteger) or learning progress
(MLR loss decreasing), as `ExampleTest`/`ValidatorTask` do on the REEF local
runtime.
"""
import numpy as np
import pytest

from harmony_tpu.apps.addvector import AddIntegerTrainer, AddVectorTrainer, make_marks
from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
from harmony_tpu.config.params import TrainerParams
from harmony_tpu.dolphin import TrainingDataProvider, TrainerContext, WorkerTasklet
from harmony_tpu.table import DenseTable, TableSpec


def run_job(trainer, data_arrays, mesh, params, job_id="job"):
    spec = TableSpec(trainer.model_table_config())
    table = DenseTable(spec, mesh)
    ctx = TrainerContext(params=params, model_table=table)
    data = TrainingDataProvider(data_arrays, params.num_mini_batches)
    worker = WorkerTasklet(job_id, ctx, trainer, data, mesh)
    result = worker.run()
    return table, worker, result


class TestAddVector:
    def test_exact_sums(self, mesh8):
        n, keys, dim = 256, 32, 4
        trainer = AddVectorTrainer(num_keys=keys, vector_dim=dim, delta=0.5)
        params = TrainerParams(num_epochs=3, num_mini_batches=8)
        table, _, result = run_job(trainer, list(make_marks(n)), mesh8, params)
        expected = trainer.expected_value(n * 3)
        vals = np.asarray(table.pull_array())
        np.testing.assert_allclose(vals, np.full((keys, dim), expected))
        assert result["epochs_run"] == 3

    def test_addinteger_exact(self, mesh_dp):
        # ref scale: 128 updates total (ExampleTest AddIntegerET).
        n = 128
        trainer = AddIntegerTrainer(num_keys=8, delta=1.0)
        params = TrainerParams(num_epochs=1, num_mini_batches=4)
        table, _, _ = run_job(trainer, list(make_marks(n)), mesh_dp, params)
        np.testing.assert_allclose(np.asarray(table.pull_array()), np.full(8, 128.0))


class TestMLR:
    def test_loss_decreases_and_learns(self, mesh8):
        x, y = make_synthetic(512, num_features=32, num_classes=4, seed=1)
        trainer = MLRTrainer(
            num_classes=4, num_features=32, features_per_partition=8, step_size=0.5
        )
        params = TrainerParams(num_epochs=8, num_mini_batches=8)
        table, worker, result = run_job(trainer, [x, y], mesh8, params)
        losses = result["losses"]
        assert losses[-1] < losses[0] * 0.7, losses
        ev = worker.evaluate((x, y))
        assert ev["accuracy"] > 0.8, ev

    def test_resume_from_starting_epoch(self, mesh8):
        x, y = make_synthetic(128, num_features=16, num_classes=2, seed=2)
        trainer = MLRTrainer(num_classes=2, num_features=16, features_per_partition=4)
        params = TrainerParams(num_epochs=4, num_mini_batches=4)
        spec = TableSpec(trainer.model_table_config())
        from harmony_tpu.table import DenseTable

        table = DenseTable(spec, mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        data = TrainingDataProvider([x, y], 4)
        w = WorkerTasklet("j", ctx, trainer, data, mesh8, starting_epoch=2)
        result = w.run()
        assert result["epochs_run"] == 2  # epochs 2,3 only (resume semantics)


class TestMetrics:
    def test_batch_metrics_emitted(self, mesh8):
        from harmony_tpu.metrics import MetricCollector, MetricManager

        manager = MetricManager()
        manager.start_collection()
        collector = MetricCollector(sink=manager.on_metric)
        x, y = make_synthetic(128, num_features=16, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=16, features_per_partition=4)
        params = TrainerParams(num_epochs=2, num_mini_batches=4)
        spec = TableSpec(trainer.model_table_config())
        table = DenseTable(spec, mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet(
            "j", ctx, trainer, TrainingDataProvider([x, y], 4), mesh8, collector=collector
        )
        w.run()
        batches = manager.worker_batch_metrics()
        assert len(batches) == 8  # 2 epochs x 4 batches
        assert all(b.num_examples == 32 for b in batches)
        assert manager.aggregate_throughput() > 0


class TestEpochWindow:
    """Multi-epoch fused dispatch windows (WorkerTasklet._run_fused_epochs):
    one drain per window must change NOTHING observable — same losses, same
    final model, same per-epoch metric stream — vs the one-drain-per-epoch
    loop, including epoch-indexed trainer hooks (MLR's LR decay)."""

    def _run(self, mesh8, window):
        from harmony_tpu.metrics import MetricCollector, MetricManager

        manager = MetricManager()
        manager.start_collection()
        x, y = make_synthetic(128, num_features=16, num_classes=2, seed=3)
        trainer = MLRTrainer(
            num_classes=2, num_features=16, features_per_partition=4,
            step_size=0.1, decay_rate=0.5, decay_period=2,
        )
        params = TrainerParams(num_epochs=6, num_mini_batches=4,
                               comm_probe_period=0)
        spec = TableSpec(trainer.model_table_config())
        table = DenseTable(spec, mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet(
            "j", ctx, trainer, TrainingDataProvider([x, y], 4), mesh8,
            collector=MetricCollector(sink=manager.on_metric),
        )
        w.EPOCH_WINDOW = window  # instance override of the class cap
        result = w.run()
        return result, manager, np.asarray(table.pull_array())

    def test_window_matches_unwindowed(self, mesh8):
        r1, m1, t1 = self._run(mesh8, window=1)
        rw, mw, tw = self._run(mesh8, window=8)
        np.testing.assert_allclose(r1["losses"], rw["losses"], rtol=0, atol=0)
        np.testing.assert_allclose(t1, tw, rtol=0, atol=0)
        assert len(m1.worker_batch_metrics()) == len(mw.worker_batch_metrics()) == 24
        e1 = sorted(e.epoch_idx for el in m1._epoch.values() for e in el)
        ew = sorted(e.epoch_idx for el in mw._epoch.values() for e in el)
        assert e1 == ew == list(range(6))

    def test_window_gating(self, mesh8):
        x, y = make_synthetic(64, num_features=8, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=8,
                             features_per_partition=4)
        spec = TableSpec(trainer.model_table_config())
        table = DenseTable(spec, mesh8)

        def worker(probe_period, **kw):
            params = TrainerParams(num_epochs=12, num_mini_batches=4,
                                   comm_probe_period=probe_period)
            ctx = TrainerContext(params=params, model_table=table)
            return WorkerTasklet("j", ctx, trainer,
                                 TrainingDataProvider([x, y], 4), mesh8, **kw)

        # a probe (re)build is due before the first probe ran: per-epoch
        assert worker(4)._epoch_window_len(0, 12) == 1
        # probes off: the class cap applies
        assert worker(0)._epoch_window_len(0, 12) == 8
        # after the first probe, windows open up to the drift-refresh
        # horizon (8x period), clamped by the class cap
        w = worker(4)
        w._probe_pull = object()  # probe ran
        w._next_probe = 8 * 4
        assert w._epoch_window_len(0, 12) == 8
        w._next_probe = 5  # drift refresh near: window must not cross it
        assert w._epoch_window_len(0, 12) == 5
        # resume: the horizon is relative to starting_epoch
        w = worker(4, starting_epoch=3)
        assert w._epoch_window_len(3, 12) == 1  # first probe still due
        # remaining epochs bound the window
        assert worker(0)._epoch_window_len(10, 12) == 2
        # non-deferrable epoch callback (checkpoint chains) disables windows
        w = worker(0, epoch_callback=lambda e: None)
        assert w._epoch_window_len(0, 12) == 1
        # deferrable (metrics-only) callback keeps them
        w = worker(0, epoch_callback=lambda e: None, defer_epoch_callback=True)
        assert w._epoch_window_len(0, 12) == 8
        # a trainer whose hook reads trained state opts out
        trainer.epoch_hook_windowable = False
        try:
            assert worker(0)._epoch_window_len(0, 12) == 1
        finally:
            del trainer.epoch_hook_windowable
        # a subclass overriding the hook WITHOUT opting in is excluded
        # even though its PARENT opted in — the flag describes the
        # parent's hook, not the override
        class PeekingMLR(MLRTrainer):
            def on_epoch_finished(self, ctx, epoch_idx):
                pass  # pretend it reads trained state

        trainer_peek = PeekingMLR(num_classes=2, num_features=8,
                                  features_per_partition=4)
        params = TrainerParams(num_epochs=12, num_mini_batches=4,
                               comm_probe_period=0)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet("j2", ctx, trainer_peek,
                          TrainingDataProvider([x, y], 4), mesh8)
        assert w._epoch_window_len(0, 12) == 1


    @pytest.mark.parametrize("raw, cap", [
        (None, 8), ("", 8), ("1", 1), ("3", 3), ("8", 8),
        ("64", 8),  # never above the class cap
        ("0", 1),   # never below one epoch
    ])
    def test_operator_window_cap(self, mesh8, monkeypatch, raw, cap):
        """HARMONY_EPOCH_WINDOW lowers the epochs a drain (the ledger feeds
        once a drain) and lifts nothing; the probe horizon and the job's end
        still bound a window under it."""
        x, y = make_synthetic(64, num_features=8, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=8,
                             features_per_partition=4)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
        params = TrainerParams(num_epochs=12, num_mini_batches=4,
                               comm_probe_period=0)
        w = WorkerTasklet("j", TrainerContext(params=params, model_table=table),
                          trainer, TrainingDataProvider([x, y], 4), mesh8)
        if raw is None:
            monkeypatch.delenv("HARMONY_EPOCH_WINDOW", raising=False)
        else:
            monkeypatch.setenv("HARMONY_EPOCH_WINDOW", raw)
        assert w._epoch_window_len(0, 12) == cap
        assert w._epoch_window_len(11, 12) == 1
        w.EPOCH_WINDOW = 2  # the instance's cap still holds over the knob
        assert w._epoch_window_len(0, 12) == min(cap, 2)


class TestCommProbe:
    def test_probe_feeds_pull_push_split(self, mesh8):
        """The per-epoch comm probe (WorkerTasklet._probe_comm) must emit a
        REAL pull/push split in BatchMetrics — not zeros — so the
        elasticity optimizer's comm_unit is measured, not degenerate (ref:
        ModelAccessor.java:33-49 pull/push timers feeding the optimizer)."""
        from harmony_tpu.metrics import MetricCollector, MetricManager

        manager = MetricManager()
        manager.start_collection()
        x, y = make_synthetic(128, num_features=16, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=16,
                             features_per_partition=4)
        params = TrainerParams(num_epochs=2, num_mini_batches=4)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet(
            "probe-j", ctx, trainer, TrainingDataProvider([x, y], 4), mesh8,
            collector=MetricCollector(sink=manager.on_metric),
        )
        w.run()
        batches = manager.worker_batch_metrics()
        assert batches
        for b in batches:
            # pull is the all-gather — always measurable; push can land at
            # the CPU timing noise floor (it's derived by subtraction), so
            # comm_unit = pull+push stays > 0 either way
            assert b.pull_time_sec > 0
            assert b.push_time_sec >= 0
            # the split actually subtracted comm out of the step time
            assert b.comp_time_sec < b.batch_time_sec
            assert abs((b.pull_time_sec + b.push_time_sec + b.comp_time_sec)
                       - max(b.batch_time_sec,
                             b.pull_time_sec + b.push_time_sec)) < 1e-6

    def test_failing_probe_backs_off_and_keeps_its_windows(self, mesh8,
                                                            caplog):
        """A probe that fails every time (on the chip: a pull-all table too
        large for the probe's non-donating copies) is built ONCE, logged
        once, retried twice as late after each failure in a row — and the
        epochs between attempts dispatch in whole windows, not one by one."""
        x, y = make_synthetic(64, num_features=8, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=8,
                             features_per_partition=4)
        params = TrainerParams(num_epochs=60, num_mini_batches=2,
                               comm_probe_period=1)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet("oom-j", ctx, trainer,
                          TrainingDataProvider([x, y], 2), mesh8)
        builds, attempts, windows = [], [], []

        def fail(*_):
            attempts.append(len(windows))
            raise RuntimeError("RESOURCE_EXHAUSTED: Error loading program")

        def build():
            builds.append(1)
            w._probe_pull = w._probe_pp = fail

        window_len = w._epoch_window_len

        def spy(epoch, num_epochs):
            windows.append((epoch, window_len(epoch, num_epochs)))
            return windows[-1][1]

        w._build_comm_probe = build
        w._epoch_window_len = spy
        with caplog.at_level("WARNING", logger="harmony_tpu.dolphin.worker"):
            result = w.run()
        assert len(result["losses"]) == 60
        assert len(builds) == 1
        # attempts at epochs 0, 16 (8 x 2), 48 (16 + 8 x 4): each found
        # that many windows dispatched before it
        starts = [e for e, _ in windows]
        assert [starts[n] for n in attempts] == [0, 16, 48]
        assert [n for _, n in windows[:3]] == [8, 8, 8]
        assert max(n for _, n in windows) == 8 and len(windows) <= 10
        assert sum("comm probe failed" in r.message
                   for r in caplog.records) == 1
        assert table.comm_split() is None

    def test_probe_disabled_degenerates_to_comp(self, mesh8):
        from harmony_tpu.metrics import MetricCollector, MetricManager

        manager = MetricManager()
        manager.start_collection()
        x, y = make_synthetic(64, num_features=8, num_classes=2)
        trainer = MLRTrainer(num_classes=2, num_features=8,
                             features_per_partition=4)
        params = TrainerParams(num_epochs=1, num_mini_batches=2)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        w = WorkerTasklet(
            "noprobe-j", ctx, trainer, TrainingDataProvider([x, y], 2), mesh8,
            collector=MetricCollector(sink=manager.on_metric),
        )
        w.comm_probe_every = 0
        w.run()
        for b in manager.worker_batch_metrics():
            assert b.pull_time_sec == 0 and b.push_time_sec == 0
            assert b.comp_time_sec == b.batch_time_sec


class TestAsyncBatchedDispatch:
    def test_empty_metrics_trainer(self, mesh8):
        """A trainer whose compute returns no metrics must not crash the
        async per-batch drain (regression: StopIteration on empty dict)."""

        class SilentTrainer(AddVectorTrainer):
            def compute(self, model, batch, hyper):
                delta, _ = super().compute(model, batch, hyper)
                return delta, {}

        n, keys, dim = 64, 8, 4
        trainer = SilentTrainer(num_keys=keys, vector_dim=dim, delta=1.0)
        params = TrainerParams(num_epochs=2, num_mini_batches=2)
        spec = TableSpec(trainer.model_table_config())
        table = DenseTable(spec, mesh8)
        ctx = TrainerContext(params=params, model_table=table)
        data = TrainingDataProvider(list(make_marks(n)), 2)
        # a barrier that never stops forces the per-batch async path
        w = WorkerTasklet(
            "j", ctx, trainer, data, mesh8, batch_barrier=lambda i: False
        )
        result = w.run()
        assert result["epochs_run"] == 2
        vals = np.asarray(table.pull_array())
        np.testing.assert_allclose(
            vals, np.full((keys, dim), trainer.expected_value(n * 2))
        )
