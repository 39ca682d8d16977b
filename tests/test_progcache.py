"""Process-level program cache: resubmitted identical jobs reuse compiled
steps (runtime/progcache) — the long-running JobServer's resubmit pattern
must not pay a recompile per submission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
from harmony_tpu.config.params import TableConfig, TrainerParams
from harmony_tpu.dolphin import TrainerContext, TrainingDataProvider, WorkerTasklet
from harmony_tpu.parallel import build_mesh
from harmony_tpu.runtime import progcache
from harmony_tpu.table import DenseTable, TableSpec
from harmony_tpu.table.update import UpdateFunction


def _mesh():
    return build_mesh(jax.devices(), data=2)


def _worker(mesh, *, num_classes=4, seed_data=None, table=None):
    trainer = MLRTrainer(
        num_classes=num_classes, num_features=8, features_per_partition=4
    )
    if table is None:
        table = DenseTable(
            TableSpec(trainer.model_table_config(num_blocks=8)), mesh
        )
    x, y = seed_data if seed_data is not None else make_synthetic(16, 8, num_classes)
    return WorkerTasklet(
        "pc",
        TrainerContext(params=TrainerParams(num_epochs=1, num_mini_batches=2),
                       model_table=table),
        trainer,
        TrainingDataProvider([x, y], 2),
        mesh,
    ), table


class TestProgramCache:
    def setup_method(self):
        progcache.clear()

    def test_identical_jobs_share_the_step_program(self):
        mesh = _mesh()
        data = make_synthetic(16, 8, 4)
        w1, _ = _worker(mesh, seed_data=data)
        r1 = w1.run()
        w2, _ = _worker(mesh, seed_data=data)
        r2 = w2.run()
        assert w2._step is w1._step
        assert progcache.stats()["hits"] >= 1
        # same program + same data -> identical training trajectory
        np.testing.assert_allclose(r1["losses"], r2["losses"], rtol=0, atol=0)

    def test_different_shape_misses(self):
        mesh = _mesh()
        w1, _ = _worker(mesh, num_classes=4)
        w1.run()
        w2, _ = _worker(mesh, num_classes=8)
        w2.run()
        assert w2._step is not w1._step

    def test_custom_update_fn_opts_out(self):
        mesh = _mesh()
        trainer = MLRTrainer(num_classes=4, num_features=8, features_per_partition=4)
        cfg = trainer.model_table_config(num_blocks=8)
        custom = UpdateFunction(
            name="custom-add",
            init=lambda k: jnp.float32(0),
            combine=lambda a, b: a + b,
            apply=lambda old, d: old + d,
            scatter_mode="add",
        )
        table = DenseTable(TableSpec(cfg, update_fn=custom), mesh)
        w1, _ = _worker(mesh, table=table)
        w1.run()
        assert w1._program_cache_key is None
        assert progcache.stats()["entries"] == 0

    def test_scalar_type_changes_the_signature(self):
        # True == 1 == 1.0 in Python: untagged keys would collide across
        # types while the BAKED trace constants differ
        a = MLRTrainer(num_classes=4, num_features=8, features_per_partition=4,
                       step_size=1)
        b = MLRTrainer(num_classes=4, num_features=8, features_per_partition=4,
                       step_size=1.0)
        assert a.jit_signature() != b.jit_signature()

    def test_reshard_drops_stale_device_buffers(self):
        from harmony_tpu.data import devcache
        devcache.clear()
        mesh = _mesh()
        data = make_synthetic(16, 8, 4)
        key = (("g", ()), 0, 16, 2)
        trainer = MLRTrainer(num_classes=4, num_features=8,
                             features_per_partition=4)
        table = DenseTable(
            TableSpec(trainer.model_table_config(num_blocks=8)), mesh)
        w = WorkerTasklet(
            "rd", TrainerContext(
                params=TrainerParams(num_epochs=1, num_mini_batches=2),
                model_table=table),
            trainer, TrainingDataProvider([*data], 2, dataset_key=key), mesh,
        )
        w.run()
        assert devcache.stats()["entries"] >= 1
        table.reshard(build_mesh(jax.devices(), data=4))
        w._build_step()
        assert devcache.stats()["entries"] == 0  # old-layout buffers freed

    def test_unnameable_trainer_opts_out(self):
        class ArrayTrainer(MLRTrainer):
            def __init__(self, **kw):
                super().__init__(**kw)
                self.bias = np.zeros(3)  # not structurally nameable

        t = ArrayTrainer(num_classes=4, num_features=8, features_per_partition=4)
        assert t.jit_signature() is None

    def test_reshard_changes_the_key(self):
        mesh = _mesh()
        w1, table = _worker(mesh)
        w1.run()
        key_before = w1._program_cache_key
        table.reshard(build_mesh(jax.devices(), data=4))
        w1._build_step()
        assert w1._program_cache_key != key_before

    def test_lru_bound_holds(self):
        mesh = _mesh()
        for i in range(3):
            w, _ = _worker(mesh, num_classes=4 * (i + 1))
            w.run()
        assert progcache.stats()["entries"] <= progcache._MAX_ENTRIES


class TestDeviceDataCache:
    def setup_method(self):
        from harmony_tpu.data import devcache
        devcache.clear()
        devcache.host_data.clear()

    def test_same_source_jobs_share_device_batches(self):
        from harmony_tpu.data import devcache
        mesh = _mesh()
        data = make_synthetic(16, 8, 4)
        key = (("f", ()), 0, 16, 2)
        for _ in range(2):
            trainer = MLRTrainer(num_classes=4, num_features=8,
                                 features_per_partition=4)
            table = DenseTable(
                TableSpec(trainer.model_table_config(num_blocks=8)), mesh)
            w = WorkerTasklet(
                "dc", TrainerContext(
                    params=TrainerParams(num_epochs=1, num_mini_batches=2),
                    model_table=table),
                trainer,
                TrainingDataProvider([*data], 2, dataset_key=key),
                mesh,
            )
            w.run()
        s = devcache.stats()
        # fused-epoch path: one stacked entry, reused by the second job
        assert s["hits"] >= 1 and s["entries"] == 1, s

    def test_shuffling_provider_never_keys(self):
        data = make_synthetic(16, 8, 4)
        p = TrainingDataProvider([*data], 2, shuffle_each_epoch=True,
                                 dataset_key=("k",))
        assert p.dataset_key is None

    def test_byte_bound_evicts(self):
        from harmony_tpu.data.devcache import ByteLRU
        lru = ByteLRU(max_bytes=100)
        a = np.zeros(10, np.float64)  # 80 bytes
        lru.put("a", a)
        lru.put("b", a)  # evicts "a"
        assert lru.get("a") is None and lru.get("b") is not None
        lru.put("huge", np.zeros(100, np.float64))  # over budget: rejected
        assert lru.get("huge") is None


class TestJobServerResubmit:
    def setup_method(self):
        from harmony_tpu.data import devcache
        devcache.clear()
        devcache.host_data.clear()
        progcache.clear()

    def test_resubmitted_job_reuses_programs(self):
        from harmony_tpu.config.params import JobConfig
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        cfg = JobConfig(
            job_id="pc-a", app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            params=TrainerParams(
                num_epochs=1, num_mini_batches=2,
                app_params={"num_classes": 4, "num_features": 8,
                            "features_per_partition": 4},
            ),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 16, "num_features": 8, "num_classes": 4}},
        )
        server = JobServer(num_executors=2,
                           device_pool=DevicePool(jax.devices()[:2]))
        server.start()
        try:
            server.submit(cfg).result(timeout=300)
            misses_after_first = progcache.stats()["misses"]
            cfg2 = cfg.replace(job_id="pc-b") if hasattr(cfg, "replace") else None
            if cfg2 is None:
                import dataclasses
                cfg2 = dataclasses.replace(cfg, job_id="pc-b")
            server.submit(cfg2).result(timeout=300)
        finally:
            server.shutdown(timeout=60)
        s = progcache.stats()
        assert s["misses"] == misses_after_first, (
            f"resubmit recompiled: {s}"
        )
        assert s["hits"] >= 1
        # the same-source dataset was reused at BOTH levels
        from harmony_tpu.data import devcache
        assert devcache.host_data.stats()["hits"] >= 1
        assert devcache.stats()["hits"] >= 1

    def test_concurrent_identical_jobs_share_mesh(self):
        """Concurrent jobs dispatching multi-device collective programs
        used to abort the process (in-process rendezvous inversion/
        starvation — parallel/dispatch.py); the global dispatch scope must
        keep N simultaneous identical submissions alive."""
        import dataclasses

        from harmony_tpu.config.params import JobConfig
        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.parallel.mesh import DevicePool

        cfg = JobConfig(
            job_id="cc-0", app_type="dolphin",
            trainer="harmony_tpu.apps.mlr:MLRTrainer",
            params=TrainerParams(
                num_epochs=2, num_mini_batches=2,
                app_params={"num_classes": 4, "num_features": 8,
                            "features_per_partition": 4},
            ),
            num_workers=1,
            user={"data_fn": "harmony_tpu.apps.mlr:make_synthetic",
                  "data_args": {"n": 16, "num_features": 8, "num_classes": 4}},
        )
        server = JobServer(num_executors=8,
                           device_pool=DevicePool(jax.devices()))
        server.start()
        try:
            futs = [
                server.submit(dataclasses.replace(cfg, job_id=f"cc-{i}"))
                for i in range(3)
            ]
            for f in futs:
                f.result(timeout=300)
        finally:
            server.shutdown(timeout=60)
