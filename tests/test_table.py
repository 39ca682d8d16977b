"""DenseTable semantics tests — op surface, sharding, resharding.

These are the TPU analogues of the reference's TableAccess suite
(services/et test `TableAccessSingleThreadTask` asserting op semantics) and
OwnershipCache/migration tests: exact-value assertions on get/update/put, and
value preservation across live re-sharding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.config import TableConfig
from harmony_tpu.parallel import build_mesh
from harmony_tpu.table import BlockManager, DenseTable, TableSpec


def make_table(mesh, *, capacity=64, vshape=(4,), num_blocks=16, ordered=True, update="add"):
    cfg = TableConfig(
        table_id="t",
        capacity=capacity,
        value_shape=vshape,
        num_blocks=num_blocks,
        is_ordered=ordered,
        update_fn=update,
    )
    return DenseTable(TableSpec(cfg), mesh)


class TestOps:
    def test_get_or_init_returns_init_value(self, mesh8):
        t = make_table(mesh8)
        np.testing.assert_array_equal(t.get_or_init(3), np.zeros(4, np.float32))

    def test_update_then_get(self, mesh8):
        t = make_table(mesh8)
        t.update(5, np.full(4, 2.5, np.float32))
        t.update(5, np.full(4, 1.0, np.float32))
        np.testing.assert_allclose(t.get(5), np.full(4, 3.5))

    def test_multi_update_duplicate_keys_fold(self, mesh8):
        t = make_table(mesh8)
        keys = [7, 7, 7, 9]
        deltas = np.stack([np.full(4, 1.0)] * 4).astype(np.float32)
        t.multi_update(keys, deltas)
        np.testing.assert_allclose(t.get(7), np.full(4, 3.0))
        np.testing.assert_allclose(t.get(9), np.full(4, 1.0))

    def test_put_returns_old(self, mesh8):
        t = make_table(mesh8)
        t.update(2, np.ones(4, np.float32))
        old = t.put(2, np.full(4, 9.0, np.float32))
        np.testing.assert_allclose(old, np.ones(4))
        np.testing.assert_allclose(t.get(2), np.full(4, 9.0))

    def test_remove_resets_to_init(self, mesh8):
        t = make_table(mesh8)
        t.update(2, np.ones(4, np.float32))
        removed = t.remove(2)
        np.testing.assert_allclose(removed, np.ones(4))
        np.testing.assert_allclose(t.get(2), np.zeros(4))

    def test_hash_partitioned_table(self, mesh8):
        t = make_table(mesh8, ordered=False)
        for k in (0, 1, 15, 16, 63):
            t.update(k, np.full(4, float(k), np.float32))
        for k in (0, 1, 15, 16, 63):
            np.testing.assert_allclose(t.get(k), np.full(4, float(k)))

    def test_pull_all_key_order(self, mesh8):
        t = make_table(mesh8, capacity=10, vshape=(), num_blocks=4, ordered=False)
        for k in range(10):
            t.update(k, np.asarray(float(k), np.float32))
        np.testing.assert_allclose(np.asarray(t.pull_array()), np.arange(10.0))

    def test_assign_update_fn(self, mesh8):
        t = make_table(mesh8, update="assign")
        t.update(1, np.full(4, 5.0, np.float32))
        t.update(1, np.full(4, 7.0, np.float32))
        np.testing.assert_allclose(t.get(1), np.full(4, 7.0))

    def test_min_update_fn(self, mesh8):
        t = make_table(mesh8, update="min", vshape=())
        assert t.get(0) == np.inf
        t.update(0, np.asarray(5.0, np.float32))
        t.update(0, np.asarray(9.0, np.float32))
        assert t.get(0) == 5.0

    def test_factory_update_fn_allowlist(self):
        """Durable factory names come from code-bearing input (checkpoint
        manifests): resolution outside the allowlisted prefixes must refuse,
        and allow_update_fn_prefix must admit."""
        import pytest

        from harmony_tpu.table.update import (
            _FACTORY_PREFIXES, allow_update_fn_prefix, get_update_fn,
        )

        with pytest.raises(PermissionError, match="allowlisted"):
            get_update_fn("os.path:join")
        allow_update_fn_prefix("tests.")
        try:
            with pytest.raises(ModuleNotFoundError):
                # admitted past the gate: fails on import, not on policy
                get_update_fn("tests.no_such_module:factory")
        finally:
            _FACTORY_PREFIXES.discard("tests.")

    def test_capacity_not_divisible_by_blocks(self, mesh8):
        t = make_table(mesh8, capacity=50, num_blocks=16)
        t.update(49, np.ones(4, np.float32))
        np.testing.assert_allclose(t.get(49), np.ones(4))
        assert t.pull_array().shape == (50, 4)


class TestSharding:
    def test_table_sharded_over_model_axis(self, mesh8):
        t = make_table(mesh8)
        # 16 blocks over model=4 -> 4 blocks per shard, replicated over data.
        shard_shapes = {s.data.shape for s in t.array.addressable_shards}
        assert shard_shapes == {(4, 4, 4)}

    def test_pure_ops_inside_jit(self, mesh8):
        t = make_table(mesh8)
        spec = t.spec

        @jax.jit
        def step(arr):
            keys = jnp.arange(8, dtype=jnp.int32)
            vals = spec.pull(arr, keys)
            return spec.push(arr, keys, vals + 1.0)

        t.commit(step(t.array))
        np.testing.assert_allclose(t.get(0), np.ones(4))


class TestResharding:
    def test_values_survive_mesh_change(self, devices):
        mesh_a = build_mesh(devices[:4], data=1, model=4)
        t = make_table(mesh_a)
        t.multi_update(list(range(64)), np.tile(np.arange(64, dtype=np.float32)[:, None], (1, 4)))
        before = np.asarray(t.pull_array())
        # Grow 4 -> 8 executors (ref: AddOneServerOptimizer-style reconfig).
        mesh_b = build_mesh(devices, data=1, model=8)
        t.reshard(mesh_b)
        np.testing.assert_allclose(np.asarray(t.pull_array()), before)
        shard_shapes = {s.data.shape for s in t.array.addressable_shards}
        assert shard_shapes == {(2, 4, 4)}
        # Shrink 8 -> 2.
        mesh_c = build_mesh(devices[:2], data=1, model=2)
        t.reshard(mesh_c)
        np.testing.assert_allclose(np.asarray(t.pull_array()), before)

    def test_pushes_after_reshard_apply(self, devices):
        t = make_table(build_mesh(devices[:2], data=1, model=2))
        t.update(0, np.ones(4, np.float32))
        t.reshard(build_mesh(devices[:8], data=2, model=4))
        t.update(0, np.ones(4, np.float32))
        np.testing.assert_allclose(t.get(0), np.full(4, 2.0))


class TestBlockIO:
    def test_export_import_roundtrip_different_topology(self, devices):
        mesh_a = build_mesh(devices[:4], data=1, model=4)
        t = make_table(mesh_a)
        t.multi_update(list(range(64)), np.tile(np.arange(64, dtype=np.float32)[:, None], (1, 4)))
        blocks = t.export_blocks()
        assert len(blocks) == 16
        mesh_b = build_mesh(devices, data=4, model=2)
        t2 = make_table(mesh_b)
        t2.import_blocks(blocks)
        np.testing.assert_allclose(np.asarray(t2.pull_array()), np.asarray(t.pull_array()))


class TestBlockManager:
    def test_even_partitioning(self):
        bm = BlockManager("t", 16, ["e0", "e1", "e2", "e3"])
        assert bm.block_counts() == {"e0": 4, "e1": 4, "e2": 4, "e3": 4}

    def test_move(self):
        bm = BlockManager("t", 16, ["e0", "e1"])
        moved = bm.move("e0", "e1", 3)
        assert len(moved) == 3
        assert bm.block_counts() == {"e0": 5, "e1": 11}
        assert all(bm.owner_of(b) == "e1" for b in moved)

    def test_unassociate_requires_empty(self):
        bm = BlockManager("t", 8, ["e0", "e1"])
        with pytest.raises(ValueError):
            bm.unassociate("e1")
        bm.move("e1", "e0", 4)
        bm.unassociate("e1")
        assert bm.executors == ["e0"]

    def test_listener_notified(self):
        bm = BlockManager("t", 8, ["e0", "e1"])
        events = []
        bm.subscribe(lambda tid, owners: events.append((tid, list(owners))))
        bm.move("e0", "e1", 1)
        assert events and events[0][0] == "t"


class TestKeyedPush:
    """``TableSpec.push``: one route, whose duplicates fold like a loop
    over the keys in the order they occur."""

    def _spec(self, update_fn="add"):
        from harmony_tpu.config import TableConfig
        from harmony_tpu.table import TableSpec

        return TableSpec(TableConfig(
            table_id="keyed-push", capacity=100, value_shape=(6,),
            num_blocks=8, update_fn=update_fn,
        ))

    @pytest.mark.parametrize("update_fn,fold", [
        ("add", np.add), ("min", np.minimum), ("max", np.maximum)])
    def test_duplicate_keys_fold_like_a_serial_loop(self, update_fn, fold):
        spec = self._spec(update_fn)
        rng = np.random.default_rng(0)
        arr = jnp.asarray(
            rng.standard_normal(spec.storage_shape, dtype=np.float32))
        keys = rng.integers(0, 100, 64).astype(np.int32)  # many repeats
        assert len(set(keys.tolist())) < 64
        deltas = rng.standard_normal((64, 6), dtype=np.float32)
        out = spec.push(arr, jnp.asarray(keys), jnp.asarray(deltas))
        want = np.array(spec.pull_all(arr))
        for k, d in zip(keys, deltas):  # float32 all the way
            want[k] = fold(want[k], d)
        got = np.asarray(spec.pull_all(out))
        if update_fn == "add":  # XLA may add a key's deltas in any order
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)

    def test_post_hook_after_a_duplicate_heavy_push(self):
        spec = self._spec("add_nonneg")  # post clamps touched entries >= 0
        arr = spec.init_array()
        keys = jnp.asarray([3, 3, 7, 3], jnp.int32)
        deltas = jnp.asarray(
            [[-5.0] * 6, [1.0] * 6, [2.0] * 6, [1.5] * 6], jnp.float32)
        assert spec.push_lowering(4) == "xla"
        out = spec.push(arr, keys, deltas)
        got = np.asarray(spec.pull(out, jnp.asarray([3, 7], jnp.int32)))
        # the clamp sees the folded row (-2.5), not each delta in turn
        np.testing.assert_allclose(got[0], np.zeros(6))
        np.testing.assert_allclose(got[1], np.full(6, 2.0))

    @pytest.mark.parametrize("via", ["mxu", "mxu_auto", "sparse"])
    def test_a_deleted_route_raises_and_names_the_one_there_is(self, via):
        spec = self._spec()
        with pytest.raises(ValueError, match="'scatter'"):
            spec.push(spec.init_array(), jnp.asarray([1], jnp.int32),
                      jnp.ones((1, 6), jnp.float32), via=via)

    def test_auto_is_scatter(self):
        spec = self._spec()
        args = (spec.init_array(), jnp.asarray([1, 1], jnp.int32),
                jnp.ones((2, 6), jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(spec.push(*args, via="auto")),
            np.asarray(spec.push(*args, via="scatter")))


class TestRandomizedOpEquivalence:
    def test_random_op_sequence_matches_dict_model(self, mesh8):
        """200 random put/update/remove/get ops against the sharded table
        must match a plain dict model exactly (the dense-table counterpart
        of the hash table's dict-equivalence sweep)."""
        rng = np.random.default_rng(42)
        capacity, vshape = 48, (3,)
        t = make_table(mesh8, capacity=capacity, vshape=vshape,
                       num_blocks=8, update="add")
        model = {}  # key -> np value; absent = init (zeros)

        def expect(k):
            return model.get(k, np.zeros(vshape, np.float32))

        for _ in range(200):
            op = rng.choice(["update", "put", "remove", "get", "multi_get",
                             "multi_update"])
            k = int(rng.integers(0, capacity))
            if op == "update":
                d = rng.standard_normal(vshape).astype(np.float32)
                t.update(k, d)
                model[k] = expect(k) + d
            elif op == "put":
                v = rng.standard_normal(vshape).astype(np.float32)
                t.put(k, v)
                model[k] = v
            elif op == "remove":
                got = t.remove(k)
                np.testing.assert_allclose(got, expect(k), rtol=1e-5,
                                           atol=1e-5)
                model.pop(k, None)
            elif op == "get":
                np.testing.assert_allclose(t.get(k), expect(k), rtol=1e-5,
                                           atol=1e-5)
            elif op == "multi_get":
                ks = rng.integers(0, capacity, 5).tolist()
                got = t.multi_get(ks)
                want = np.stack([expect(x) for x in ks])
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            else:  # multi_update with DUPLICATE keys (additive fold)
                ks = rng.integers(0, capacity, 6).tolist()
                ds = rng.standard_normal((6, *vshape)).astype(np.float32)
                t.multi_update(ks, ds)
                for x, dd in zip(ks, ds):
                    model[x] = expect(x) + dd
        # final full-table sweep
        final = np.asarray(t.pull_array())
        for k in range(capacity):
            np.testing.assert_allclose(final[k], expect(k), rtol=1e-4,
                                       atol=1e-5)


class TestKeyedStepBuild:
    """A keyed job's start: ``_build_step`` wraps programs and runs none,
    and nothing in the environment chooses how its push is lowered."""

    @staticmethod
    def _worker(mesh, job_id="ks-job"):
        from harmony_tpu.apps.mlr import make_synthetic
        from harmony_tpu.config.params import TrainerParams
        from harmony_tpu.dolphin import (
            TrainerContext, TrainingDataProvider, WorkerTasklet,
        )
        from harmony_tpu.dolphin.trainer import Trainer

        class KeyedTrainer(Trainer):
            pull_mode = "keys"

            def model_table_config(self, table_id="ks-model"):
                return TableConfig(table_id=table_id, capacity=64,
                                   value_shape=(4,), num_blocks=8,
                                   update_fn="add")

            def pull_keys(self, batch):
                return jnp.arange(32, dtype=jnp.int32) % 16  # repeats

            def compute(self, model, batch, hyper):
                return -0.1 * model, {"loss": jnp.sum(model * model)}

        trainer = KeyedTrainer()
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh)
        x, _ = make_synthetic(32, num_features=4, num_classes=2)
        return WorkerTasklet(
            job_id,
            TrainerContext(
                params=TrainerParams(num_epochs=1, num_mini_batches=2,
                                     comm_probe_period=0),
                model_table=table,
            ),
            trainer,
            TrainingDataProvider([x], 2),
            mesh,
        )

    @staticmethod
    def _step_text(w):
        table = w.ctx.model_table
        batch = tuple(
            jax.ShapeDtypeStruct((w.data.batch_size, *tail), dt)
            for tail, dt in w.data.array_specs())
        step = w._program_builders(table.sharding, None)[0]()
        return step.lower(table.array, batch, w._hyper()).as_text()

    def test_build_step_runs_nothing_on_the_device(self, mesh8, monkeypatch):
        """Also as a TPU mesh would be built for: a job's start may hold a
        table of half the chip, and any table-sized program beside it is one
        the chip cannot fit."""
        from harmony_tpu.dolphin import worker
        from harmony_tpu.parallel import dispatch
        from harmony_tpu.table import table as table_module
        from harmony_tpu.utils import platform

        monkeypatch.setattr(platform, "mesh_is_tpu", lambda mesh: True)
        w = self._worker(mesh8)
        version = w.ctx.model_table.data_version
        compiles, scopes = [], []

        def on_duration(name, *a, **kw):
            if name.endswith("backend_compile_duration"):
                compiles.append(name)

        real = dispatch.dispatch_scope
        for module in (dispatch, worker, table_module):
            monkeypatch.setattr(
                module, "dispatch_scope",
                lambda *a, **kw: scopes.append(a) or real(*a, **kw))
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            w._build_step()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        assert not compiles and not scopes
        assert w.ctx.model_table.data_version == version

    def test_program_key_has_no_route_slot(self, mesh8, monkeypatch):
        w = self._worker(mesh8)
        tsh = w.ctx.model_table.sharding
        key = w._program_key(tsh, None)
        assert len(key) == 6
        assert not {"scatter", "mxu", "mxu_auto", "sparse", "auto"} & {
            part for part in key if isinstance(part, str)}
        monkeypatch.setenv("HARMONY_PUSH_VIA", "mxu")
        assert w._program_key(tsh, None) == key

    @pytest.mark.parametrize("value", ["mxu", "mxu_auto", "sparse"])
    def test_the_old_knob_is_not_read(self, value, mesh8, monkeypatch):
        """``HARMONY_PUSH_VIA`` (the frozen benchmark configs still export
        it) changes neither the step's text nor what STATUS says of its
        push."""
        from harmony_tpu.metrics.accounting import ledger
        from harmony_tpu.runtime import progcache

        def build(job_id):
            progcache.clear()
            w = self._worker(mesh8, job_id)
            w._build_step()
            layout = ledger().snapshot()[job_id]["table_layout"]
            return self._step_text(w), layout["push_lowering"]

        monkeypatch.delenv("HARMONY_PUSH_VIA", raising=False)
        text, lowering = build("ks-unset")
        monkeypatch.setenv("HARMONY_PUSH_VIA", value)
        assert build("ks-set") == (text, lowering)
        assert lowering == "xla" and "scatter" in text
        progcache.clear()
