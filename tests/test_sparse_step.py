"""Fused device hot path: Pallas sparse kernels, FusedSparseStep, and the
fused-vs-unfused (TrainerParams.fused_step) parity contract.

Parity contract (docs/DEVICE_HOT_PATH.md): for a fixed seed, per-epoch
LOSSES are bit-identical with the knob on vs off — the phase boundaries
in the fused program (worker._phase_boundary) pin the same replicated
shardings the host-driven path materializes. Table state matches to float
tolerance (XLA may re-associate gradient-matmul accumulation differently
across program boundaries; NMF/LDA state is exactly equal, MLR differs in
final bits).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from harmony_tpu.config.params import TableConfig, TrainerParams
from harmony_tpu.dolphin import (
    FusedSparseStep,
    ModelAccessor,
    TrainerContext,
    TrainingDataProvider,
    WorkerTasklet,
)
from harmony_tpu.ops.sparse import (
    gather_rows,
    gather_rows_ref,
    segment_sum_rows,
    segment_sum_rows_ref,
)
from harmony_tpu.table import DenseTable, TableSpec


# ---------------------------------------------------------------------------
# ops/sparse.py: kernel (interpret mode) vs jnp reference
# ---------------------------------------------------------------------------


def test_gather_rows_kernel_matches_fallback():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 64, 40), jnp.int32)
    kernel = gather_rows(table, idx, interpret=True)
    fallback = gather_rows_ref(table, idx)
    # a gather copies bytes: the routes must agree EXACTLY
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(fallback))


def test_gather_rows_oob_clamps_like_jax_gather():
    table = jnp.asarray(np.arange(8 * 128, dtype=np.float32).reshape(8, 128))
    # 9/100 clamp to row 7; -1/-9 clamp to row 0 on BOTH routes (the jnp
    # route clamps explicitly — raw advanced indexing would wrap negatives
    # Python-style, which the kernel's clamp cannot reproduce)
    idx = jnp.asarray([0, 7, 9, 100, -1, -9], jnp.int32)
    kernel = gather_rows(table, idx, interpret=True)
    fallback = gather_rows_ref(table, idx)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(fallback))
    np.testing.assert_array_equal(np.asarray(fallback[4]), np.asarray(table[0]))
    np.testing.assert_array_equal(np.asarray(fallback[3]), np.asarray(table[7]))


def test_segment_sum_rows_kernel_matches_fallback_exact_counts():
    """Integer-valued folds are addition-order-insensitive: the kernel and
    the fallback must agree bit for bit (the LDA count-table shape)."""
    rng = np.random.default_rng(1)
    deltas = jnp.asarray(
        rng.integers(-3, 4, (200, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 16, 200), jnp.int32)
    kernel = segment_sum_rows(deltas, idx, 16, interpret=True)
    fallback = segment_sum_rows_ref(deltas, idx, 16)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(fallback))


def test_segment_sum_rows_kernel_matches_fallback_float():
    rng = np.random.default_rng(2)
    deltas = jnp.asarray(rng.normal(size=(100, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-2, 12, 100), jnp.int32)  # incl. OOB
    kernel = segment_sum_rows(deltas, idx, 10, interpret=True)
    fallback = segment_sum_rows_ref(deltas, idx, 10)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(fallback),
                               atol=1e-5, rtol=1e-5)
    # OOB ids (negative / >= num_rows) contribute nothing on either route
    ok = (np.asarray(idx) >= 0) & (np.asarray(idx) < 10)
    expect = np.zeros((10, 128), np.float32)
    np.add.at(expect, np.asarray(idx)[ok], np.asarray(deltas)[ok])
    np.testing.assert_allclose(np.asarray(kernel), expect, atol=1e-4)


def test_kernels_refuse_shapes_they_cannot_tile():
    """The kernels ARE the kernels: a shape they cannot take is an error
    naming the reference, never a quiet switch of route."""
    narrow = jnp.zeros((8, 3), jnp.float32)
    with pytest.raises(ValueError, match="gather_rows_ref"):
        gather_rows(narrow, jnp.zeros((4,), jnp.int32), interpret=True)
    half = jnp.zeros((8, 128), jnp.bfloat16)  # packed rows: no 1-row DMA
    with pytest.raises(ValueError, match="gather_rows_ref"):
        gather_rows(half, jnp.zeros((4,), jnp.int32), interpret=True)
    wide = jnp.zeros((8, 256), jnp.float32)  # tile-interleaved rows
    with pytest.raises(ValueError, match="gather_rows_ref"):
        gather_rows(wide, jnp.zeros((4,), jnp.int32), interpret=True)
    with pytest.raises(ValueError, match="segment_sum_rows_ref"):
        segment_sum_rows(jnp.zeros((4, 3), jnp.float32),
                         jnp.zeros((4,), jnp.int32), 8, interpret=True)


def _tpu_text(fn, *args):
    """StableHLO of ``fn`` cross-lowered for the TPU from this CPU host —
    runs the Pallas TPU front end (block-shape and memory-space checks)
    without a chip."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_sparse_kernels_lower_for_tpu():
    """A block shape or op the Pallas TPU lowering refuses (the old
    one-row gather block; pl.load/pl.store) can never again pass a
    CPU-only review: both kernels must cross-lower, at a plain shape and
    at the shapes that force padding."""
    table = jnp.zeros((1000, 128), jnp.float32)
    for n in (1, 8, 100, 4096):
        text = _tpu_text(gather_rows, table, jnp.zeros((n,), jnp.int32))
        assert "tpu_custom_call" in text
    for n, rows in ((5, 64), (256, 64), (1000, 16384)):
        text = _tpu_text(
            lambda d, i, rows=rows: segment_sum_rows(d, i, rows),
            jnp.zeros((n, 128), jnp.float32), jnp.zeros((n,), jnp.int32))
        assert "tpu_custom_call" in text


def test_spec_pull_matches_direct_gather(mesh8):
    spec = TableSpec(TableConfig(table_id="p", capacity=50,
                                 value_shape=(3,), num_blocks=10))
    t = DenseTable(spec, mesh8)
    t.multi_update(list(range(50)),
                   np.arange(150, dtype=np.float32).reshape(50, 3))
    keys = [0, 7, 49, 7]
    got = t.multi_get(keys)
    np.testing.assert_array_equal(
        got, np.arange(150, dtype=np.float32).reshape(50, 3)[keys])


def test_push_via_sparse_matches_scatter(mesh8):
    spec = TableSpec(TableConfig(table_id="ps", capacity=40,
                                 value_shape=(4,), num_blocks=8))
    arr = jax.jit(spec.init_array)()
    keys = jnp.asarray([1, 5, 1, 39], jnp.int32)  # duplicate key folds
    deltas = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, 4)).astype(np.float32))
    out_sc = spec.push(arr, keys, deltas, via="scatter")
    out_sp = spec.push(arr, keys, deltas, via="sparse")
    np.testing.assert_allclose(np.asarray(out_sc), np.asarray(out_sp),
                               atol=1e-6)


def test_push_via_sparse_requires_additive():
    spec = TableSpec(TableConfig(table_id="pa", capacity=8,
                                 value_shape=(2,), num_blocks=4,
                                 update_fn="assign"))
    arr = jax.jit(spec.init_array)()
    with pytest.raises(ValueError, match="additive"):
        spec.push(arr, jnp.asarray([1], jnp.int32),
                  jnp.ones((1, 2), jnp.float32), via="sparse")


# ---------------------------------------------------------------------------
# fused vs unfused WorkerTasklet parity (the knob's contract)
# ---------------------------------------------------------------------------


def _run_worker(trainer, arrays, mesh, fused, epochs=3, batches=4):
    spec = TableSpec(trainer.model_table_config())
    table = DenseTable(spec, mesh)
    ltable = (DenseTable(TableSpec(trainer.local_table_config()), mesh)
              if trainer.uses_local_table else None)
    params = TrainerParams(num_epochs=epochs, num_mini_batches=batches,
                           fused_step=fused)
    ctx = TrainerContext(params=params, model_table=table,
                         local_table=ltable)
    data = TrainingDataProvider(arrays, batches)
    w = WorkerTasklet(f"j-{fused}", ctx, trainer, data, mesh)
    result = w.run()
    return result, table, w


def test_mlr_fused_unfused_bit_identical_losses(mesh8):
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic

    def mk():
        return (MLRTrainer(num_classes=4, num_features=16,
                           features_per_partition=8),
                make_synthetic(64, 16, 4, seed=1))

    t, a = mk()
    r1, tb1, _ = _run_worker(t, a, mesh8, fused=True)
    t, a = mk()
    r0, tb0, _ = _run_worker(t, a, mesh8, fused=False)
    assert r1["losses"] == r0["losses"]  # bit-identical
    np.testing.assert_allclose(np.asarray(tb1.pull_array()),
                               np.asarray(tb0.pull_array()), atol=1e-6)


def test_nmf_fused_unfused_bit_identical(mesh8):
    from harmony_tpu.apps.nmf import NMFTrainer, make_synthetic

    def mk():
        return (NMFTrainer(num_rows=32, num_cols=24, rank=4, seed=2),
                make_synthetic(32, 24, 4, seed=2))

    t, a = mk()
    r1, tb1, _ = _run_worker(t, a, mesh8, fused=True)
    t, a = mk()
    r0, tb0, _ = _run_worker(t, a, mesh8, fused=False)
    assert r1["losses"] == r0["losses"]
    np.testing.assert_array_equal(np.asarray(tb1.pull_array()),
                                  np.asarray(tb0.pull_array()))


def test_lda_fused_unfused_bit_identical(mesh8):
    from harmony_tpu.apps.lda import LDATrainer, make_synthetic

    def mk():
        return (LDATrainer(vocab_size=50, num_topics=5, num_docs=32,
                           max_doc_len=10),
                make_synthetic(32, 50, 5, 10, seed=3))

    t, a = mk()
    r1, tb1, _ = _run_worker(t, a, mesh8, fused=True)
    t, a = mk()
    r0, tb0, _ = _run_worker(t, a, mesh8, fused=False)
    assert r1["losses"] == r0["losses"]
    np.testing.assert_array_equal(np.asarray(tb1.pull_array()),
                                  np.asarray(tb0.pull_array()))


def test_sparse_lda_fused_unfused_bit_identical(mesh8):
    """The hash-backed (DeviceHashTable) keyed path through the knob."""
    from harmony_tpu.apps.lda import LDATrainer, make_synthetic_sparse
    from harmony_tpu.table.hashtable import DeviceHashTable, HashTableSpec

    def run(fused):
        trainer = LDATrainer(vocab_size=50, num_topics=5, num_docs=32,
                             max_doc_len=10, sparse=True, slot_budget=256)
        table = DeviceHashTable(
            HashTableSpec(trainer.model_table_config()), mesh8)
        ltable = DenseTable(TableSpec(trainer.local_table_config()), mesh8)
        params = TrainerParams(num_epochs=2, num_mini_batches=4,
                               fused_step=fused)
        ctx = TrainerContext(params=params, model_table=table,
                             local_table=ltable)
        data = TrainingDataProvider(
            make_synthetic_sparse(32, 50, 5, 10, seed=3), 4)
        return WorkerTasklet("j", ctx, trainer, data, mesh8).run()

    assert run(True)["losses"] == run(False)["losses"]


def test_unfused_step_measures_phase_split(mesh8):
    """Knob OFF: the worker's phase split comes from direct measurement
    (no comm probe runs), and BatchMetrics carry a nonzero pull time."""
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.metrics.collector import MetricCollector

    trainer = MLRTrainer(num_classes=4, num_features=16,
                         features_per_partition=8)
    spec = TableSpec(trainer.model_table_config())
    table = DenseTable(spec, mesh8)
    params = TrainerParams(num_epochs=2, num_mini_batches=4,
                           fused_step=False)
    ctx = TrainerContext(params=params, model_table=table)
    data = TrainingDataProvider(make_synthetic(64, 16, 4, seed=1), 4)
    col = MetricCollector()
    w = WorkerTasklet("j", ctx, trainer, data, mesh8, collector=col)
    w.run()
    step = w._step
    assert step.steps == 8
    pull, comp, push = step.mean_phase_seconds()
    assert pull > 0 and push > 0
    assert w._probe_pull is None  # the comm probe never built/ran


def test_fused_step_env_override(mesh8, monkeypatch):
    """HARMONY_FUSED_STEP=0 forces the unfused path process-wide even
    when the config says fused."""
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic
    from harmony_tpu.dolphin.worker import _UnfusedStep

    monkeypatch.setenv("HARMONY_FUSED_STEP", "0")
    trainer = MLRTrainer(num_classes=4, num_features=16,
                         features_per_partition=8)
    table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
    params = TrainerParams(num_epochs=1, num_mini_batches=2,
                           fused_step=True)
    ctx = TrainerContext(params=params, model_table=table)
    data = TrainingDataProvider(make_synthetic(32, 16, 4, seed=1), 2)
    w = WorkerTasklet("j", ctx, trainer, data, mesh8)
    w._build_step()
    assert isinstance(w._step, _UnfusedStep)


# ---------------------------------------------------------------------------
# FusedSparseStep: the host-driven path's fused replacement
# ---------------------------------------------------------------------------


def _emb_table(mesh, rows=128, width=8):
    return DenseTable(
        TableSpec(TableConfig(table_id="emb", capacity=rows,
                              value_shape=(width,), num_blocks=16)),
        mesh,
    )


def _sgd_compute(rows, targets):
    err = rows - targets
    loss = jnp.mean(jnp.sum(err * err, -1))
    return -0.1 * err, {"loss": loss}


def _emb_batches(rows=128, width=8, n=12, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, rows, batch).astype(np.int32),
         rng.normal(size=(batch, width)).astype(np.float32))
        for _ in range(n)
    ]


def test_fused_sparse_step_matches_accessor_loop(mesh8):
    """The fused pull→compute→push program is bit-identical to the
    host-driven accessor round trip it replaces."""
    batches = _emb_batches()
    t1 = _emb_table(mesh8)
    fs = ModelAccessor(t1).fused_step(_sgd_compute)
    l_f = [float(a["loss"]) for a in fs.run_batches(batches)]

    t0 = _emb_table(mesh8)
    acc = ModelAccessor(t0)
    comp = jax.jit(_sgd_compute)
    l_u = []
    for keys, tgt in batches:
        rows = acc.pull(keys)
        delta, aux = comp(jnp.asarray(rows), jnp.asarray(tgt))
        acc.push(keys, np.asarray(delta))
        l_u.append(float(aux["loss"]))
    assert l_f == l_u
    np.testing.assert_array_equal(np.asarray(t1.pull_array()),
                                  np.asarray(t0.pull_array()))


def test_fused_sparse_step_charges_comp_only(mesh8):
    t = _emb_table(mesh8)
    acc = ModelAccessor(t)
    fs = acc.fused_step(_sgd_compute)
    keys, tgt = _emb_batches(n=1)[0]
    fs.step(keys, jnp.asarray(tgt))
    assert acc.get_and_reset_times() == (0.0, 0.0)  # no separable phases
    assert fs.comp_tracer.count == 1


def test_fused_step_donates_table_buffer(mesh8):
    """The pre-step storage buffer is genuinely invalidated by donation;
    with donate=False it survives."""
    t = _emb_table(mesh8)
    before = t.array
    fs = FusedSparseStep(t, _sgd_compute)
    keys, tgt = _emb_batches(n=1)[0]
    fs.step(keys, jnp.asarray(tgt))
    assert before.is_deleted()

    t2 = _emb_table(mesh8)
    before2 = t2.array
    fs2 = FusedSparseStep(t2, _sgd_compute, donate=False)
    fs2.step(keys, jnp.asarray(tgt))
    assert not before2.is_deleted()


def test_fused_step_never_donates_cached_operands(mesh8):
    """devcache contract: a cached device array passed as a step operand
    is read-only — donation is confined to the table buffer (argnum 0)."""
    from harmony_tpu.data import devcache

    t = _emb_table(mesh8)
    fs = FusedSparseStep(t, _sgd_compute)
    keys, tgt = _emb_batches(n=1)[0]
    staged = fs._stage((keys, tgt))
    devcache.put(("sparse-step-test", 0), staged)
    for _ in range(3):
        fs.step(*staged)
    cached = devcache.get(("sparse-step-test", 0))
    for a in cached:
        assert not a.is_deleted()
        np.asarray(a)  # still readable


def test_fused_step_progcache_participation(mesh8):
    """Equal (table signature, compute signature) builds share ONE
    compiled wrapper across rebuilds — and the hit shows up in the
    registry's harmony_progcache_events_total counter."""
    from harmony_tpu.metrics.registry import get_registry
    from harmony_tpu.runtime import progcache

    t = _emb_table(mesh8)
    sig = ("sparse-step-cache-test", 42)
    s0 = progcache.stats()
    fs1 = FusedSparseStep(t, _sgd_compute, signature=sig)
    fs2 = FusedSparseStep(t, _sgd_compute, signature=sig)
    assert fs1.cache_key is not None and fs1.cache_key == fs2.cache_key
    assert fs1._fn is fs2._fn
    s1 = progcache.stats()
    assert s1["hits"] >= s0["hits"] + 1
    assert s1["misses"] >= s0["misses"] + 1
    hit = get_registry().counter(
        "harmony_progcache_events_total",
        "Compiled-program cache lookups by result",
        ("result",),
    ).labels(result="hit")
    assert hit.value >= 1


def test_fused_step_rejects_hash_tables(mesh8):
    from harmony_tpu.table.hashtable import DeviceHashTable, HashTableSpec

    cfg = TableConfig(table_id="h", capacity=64, value_shape=(4,),
                      num_blocks=8, is_ordered=False, sparse=True)
    ht = DeviceHashTable(HashTableSpec(cfg), mesh8)
    with pytest.raises(TypeError, match="hash"):
        FusedSparseStep(ht, _sgd_compute)


def test_worker_program_key_carries_mode(mesh8):
    """A fused and an unfused build of the same job must not collide in
    the program cache."""
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic

    def key_for(fused):
        trainer = MLRTrainer(num_classes=4, num_features=16,
                             features_per_partition=8)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
        params = TrainerParams(num_epochs=1, num_mini_batches=2,
                               fused_step=fused)
        ctx = TrainerContext(params=params, model_table=table)
        data = TrainingDataProvider(make_synthetic(32, 16, 4, seed=1), 2)
        w = WorkerTasklet("j", ctx, trainer, data, mesh8)
        w._build_step()
        return w._program_cache_key

    kf, ku = key_for(True), key_for(False)
    assert kf is not None and ku is not None
    assert kf != ku
