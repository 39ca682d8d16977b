"""Fused device hot path: Pallas sparse kernels, FusedSparseStep, and the
step program's parity contract.

Parity contract (docs/DEVICE_HOT_PATH.md): for a fixed seed, the ONE step
program ``WorkerTasklet`` builds (pull -> compute -> push in one donated
jit) gives the per-epoch LOSSES of a per-phase loop over the public
accessor API — pull to the host, ``trainer.compute`` alone in a jit, push
from the host — kept HERE as the reference, bit for bit where the step's
phase boundaries (worker._phase_boundary) make that hold. Table state
matches to float tolerance where a gradient matmul feeds it (XLA may
re-associate its accumulation differently across program boundaries;
NMF/LDA/LM state is exactly equal, MLR and FM differ in final bits).
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
from harmony_tpu.ops import sparse
from harmony_tpu.ops.sparse import (
    gather_rows,
    gather_rows_ref,
    scatter_add_rows,
    scatter_add_rows_ref,
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


def _sequential_scatter_add(table, idx, deltas):
    """The serial scatter-add: every in-range key's delta added to its
    row in occurrence order, ``((row + d0) + d1) + …`` in float32."""
    out = np.array(table)
    for i, k in enumerate(np.asarray(idx)):
        if 0 <= k < out.shape[0]:
            out[k] = out[k] + np.asarray(deltas)[i]
    return out


def _criteo_like(rng, rows, n):
    """About a quarter of the keys distinct: a few hot ids, a long tail."""
    hot = rng.integers(0, 8, n)
    tail = rng.integers(0, rows, n)
    return np.where(rng.random(n) < 0.8, hot, tail)


_SCATTER_CASES = {
    # name: (table rows, keys(rng), integer-valued deltas?)
    "criteo_like_repeats": (512, lambda r: _criteo_like(r, 512, 700), False),
    "all_keys_equal": (64, lambda r: np.full(300, 7), False),
    "all_keys_distinct": (512, lambda r: r.permutation(512)[:300], False),
    "n_not_a_multiple_of_the_tile": (96, lambda r: r.integers(0, 96, 301),
                                     False),
    "n_smaller_than_one_tile": (96, lambda r: r.integers(0, 96, 5), False),
    "one_key": (16, lambda r: np.array([3]), False),
    "run_crossing_a_tile_boundary": (
        64, lambda r: np.concatenate([r.integers(0, 64, 120), np.full(16, 9),
                                      r.integers(0, 64, 120)]), False),
    "key_recurring_in_every_tile": (
        64, lambda r: np.where(np.arange(640) % 5 == 0, 11,
                               r.integers(0, 64, 640)), False),
    "out_of_range_ids_dropped": (
        64, lambda r: np.concatenate([r.integers(0, 64, 200),
                                      [64, 65, 1000, 2 ** 30]]), False),
    "negative_ids_dropped_not_clamped": (
        64, lambda r: np.concatenate([[-1, -64, -2 ** 30],
                                      r.integers(-5, 64, 200)]), False),
    "every_id_dropped": (64, lambda r: np.full(130, -1), False),
    "integer_valued_deltas": (64, lambda r: r.integers(0, 64, 400), True),
}


@pytest.mark.parametrize("case", list(_SCATTER_CASES))
def test_scatter_add_rows_kernel_is_the_serial_scatter_add(case, monkeypatch):
    """The kernel's fold is SEQUENTIAL and in occurrence order (a stable
    sort, then one add per slot on top of the table row), so it is held
    to the serial scatter-add bit for bit — float deltas included — and
    to XLA's own scatter, which on this backend folds the same way. Tiles
    of 128 slots, so a few hundred keys cross several boundaries."""
    monkeypatch.setattr(sparse, "_SCATTER_TILE", 128)
    rows, make_keys, integral = _SCATTER_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    idx = np.asarray(make_keys(rng), np.int32)
    table = rng.standard_normal((rows, 128)).astype(np.float32)
    deltas = (rng.integers(-4, 5, (idx.size, 128)) if integral
              else rng.standard_normal((idx.size, 128))).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda t, i, d: scatter_add_rows(t, i, d, interpret=True))(
            table, idx, deltas))
    np.testing.assert_array_equal(
        got, _sequential_scatter_add(table, idx, deltas))
    np.testing.assert_array_equal(
        got, np.asarray(scatter_add_rows_ref(jnp.asarray(table), idx, deltas)))


def test_scatter_add_rows_default_tile_takes_a_whole_batch():
    """One tile at the shipped size (no patched tile): keys, positions
    and deltas of one 4,096-slot block, with a tail."""
    rng = np.random.default_rng(5)
    idx = _criteo_like(rng, 2048, 5000).astype(np.int32)
    table = rng.standard_normal((2048, 128)).astype(np.float32)
    deltas = rng.standard_normal((5000, 128)).astype(np.float32)
    got = np.asarray(scatter_add_rows(table, idx, deltas, interpret=True))
    np.testing.assert_array_equal(
        got, _sequential_scatter_add(table, idx, deltas))


# -- ops/sum_rows.py: a chunk's rows summed into token rows -------------------

def _picks(T, k, choose):
    """``[T, k]`` expert choices: ``choose(t)`` gives token ``t``'s."""
    return np.array([choose(t) for t in range(T)], np.int32).reshape(T, k)


_AWAY = (6, 7)  # experts nobody holds (H <= 4 below)
_SUM_ROWS_CASES = {
    # name: (T, k, H, C, chunk, d, dtype, gated, choices(t))
    "a_token_in_several_runs": (
        32, 3, 4, 48, 0, 128, jnp.float32, True, lambda t: (0, 2, 3)),
    "an_empty_run": (
        32, 2, 4, 32, 0, 128, jnp.float32, True,
        lambda t: (0, 2) if t % 3 else (3, 6)),
    "a_run_cut_by_the_chunk_on_both_sides": (  # run 1 is slots 24 .. 71
        48, 2, 2, 16, 2, 128, jnp.float32, True,
        lambda t: (0, 1) if t < 24 else (1, 6)),
    "a_run_cut_by_the_chunks_end": (
        48, 2, 2, 32, 0, 128, jnp.bfloat16, True,
        lambda t: (0, 1) if t < 24 else (1, 6)),
    "no_held_row": (32, 2, 4, 32, 0, 128, jnp.float32, True, lambda t: _AWAY),
    "every_row_held": (
        32, 2, 2, 32, 1, 128, jnp.bfloat16, True, lambda t: (0, 1)),
    "tokens_on_both_edges_of_a_tile": (
        32, 2, 4, 32, 0, 128, jnp.float32, True,
        lambda t: (1, 3) if t in (0, 7, 8, 15, 16, 31) else _AWAY),
    "bfloat16_rows_no_gate": (
        40, 2, 4, 48, 0, 256, jnp.bfloat16, False,
        lambda t: ((t * 7) % 4, 4 + t % 3)),
    "float32_rows_no_gate": (
        40, 2, 4, 40, 1, 256, jnp.float32, False,
        lambda t: (t % 2, 2 + (t // 2) % 2)),
    "rows_not_a_multiple_of_a_sublane_group": (
        24, 3, 4, 24, 0, 128, jnp.bfloat16, True,
        lambda t: (t % 4, (t + 1) % 4, 5)),
    "moonlight_lanes": (16, 2, 4, 32, 0, 2048, jnp.bfloat16, False,
                        lambda t: (t % 4, 4 + t % 2)),
    "kimi_linear_lanes": (16, 2, 4, 32, 0, 2304, jnp.bfloat16, True,
                          lambda t: (t % 3, 3 + t % 4)),
    "smallthinker_lanes": (16, 2, 4, 32, 0, 2560, jnp.bfloat16, True,
                           lambda t: ((t // 2) % 4, 5)),
}


@pytest.mark.parametrize("case", list(_SUM_ROWS_CASES))
def test_sum_rows_kernel_is_the_serial_sum_in_chunk_order(case, monkeypatch):
    """One chunk of the expert layer's sorted slots, cut as the layer cuts
    it (``models/moe.py`` ``_tile_starts`` / ``_chunk``), folded by the
    kernel in token tiles of 8: equal BIT FOR BIT to a serial float32 loop
    over the chunk's held rows in order — each term rounded before it is
    added — and to XLA's scatter-add of the same terms."""
    from harmony_tpu.models import moe
    from harmony_tpu.ops import sum_rows as sr

    monkeypatch.setattr(sr, "_TB", (8,))
    T, k, H, C, chunk, d, dtype, gated, choose = _SUM_ROWS_CASES[case]
    expert = _picks(T, k, choose)
    rng = np.random.default_rng(sum(map(ord, case)))
    order = np.argsort(expert.reshape(-1), kind="stable")
    order = jnp.asarray(np.pad(order, (0, -order.size % C)), jnp.int32)
    offsets = jnp.asarray(np.concatenate(
        [[0], np.cumsum(np.bincount(expert.reshape(-1), minlength=8)[:H])]),
        jnp.int32)
    assert sr.tile_plan(T, d, dtype) == 8
    starts = moe._tile_starts(jnp.asarray(expert.reshape(-1)), offsets, k, 8)
    _, _, tok, sizes, bounds = moe._chunk(C, chunk, order, offsets, starts, k)
    held = int(sizes.sum())
    src = rng.standard_normal((C, d)).astype(np.float32)
    src[held:] = 0.0  # the grouped matmul's contract; nobody moves them
    src = jnp.asarray(src, dtype)
    gate = jnp.asarray(rng.random(C), jnp.float32) if gated else None
    acc = rng.standard_normal((T, d)).astype(np.float32)

    got = np.asarray(sr.sum_rows(jnp.asarray(acc), src, tok, bounds, gate,
                                 interpret=True))
    want, rows = acc.copy(), np.asarray(src.astype(jnp.float32))
    for i in range(held):
        term = rows[i] * np.asarray(gate)[i] if gated else rows[i]
        want[int(tok[i])] += term
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.asarray(sr.sum_rows_ref(
        jnp.asarray(acc), src, tok, held, gate)))
    # a sum the caller calls fresh is not read: zeros stand in for it
    fresh = np.asarray(sr.sum_rows(jnp.asarray(acc), src, tok, bounds, gate,
                                   True, interpret=True))
    np.testing.assert_array_equal(fresh, np.asarray(sr.sum_rows(
        jnp.zeros_like(acc), src, tok, bounds, gate, interpret=True)))
    if case == "no_held_row":
        assert held == 0 and not fresh.any()
    if case == "every_row_held":
        assert held == C


def test_scatter_kernel_refuses_shapes_it_cannot_tile():
    ids = jnp.zeros((4,), jnp.int32)
    for table, deltas in (
            (jnp.zeros((8, 64), jnp.float32), jnp.zeros((4, 64))),
            (jnp.zeros((8, 256), jnp.float32), jnp.zeros((4, 256))),
            (jnp.zeros((8, 128), jnp.bfloat16),
             jnp.zeros((4, 128), jnp.bfloat16)),
            (jnp.zeros((8, 128), jnp.float32),
             jnp.zeros((4, 128), jnp.bfloat16))):
        with pytest.raises(ValueError, match="scatter_add_rows_ref"):
            scatter_add_rows(table, ids, deltas, interpret=True)


def _tpu_text(fn, *args):
    """StableHLO of ``fn`` cross-lowered for the TPU from this CPU host —
    runs the Pallas TPU front end (block-shape and memory-space checks)
    without a chip."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_sparse_kernels_lower_for_tpu():
    """A block shape or op the Pallas TPU lowering refuses (the old
    one-row gather block; pl.load/pl.store) can never again pass a
    CPU-only review: the gather must cross-lower, at a plain shape and at
    the shapes that force padding (the scatter-add's turn is below)."""
    table = jnp.zeros((1000, 128), jnp.float32)
    for n in (1, 8, 100, 4096):
        text = _tpu_text(gather_rows, table, jnp.zeros((n,), jnp.int32))
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [1, 100, 5000, 212993])
def test_scatter_kernel_lowers_for_tpu(n):
    """SMEM index blocks, a tail block of deltas, the aliased table: the
    Pallas TPU front end takes them at one key, at a padded tile and at
    the keyed cells' 212,993 (an odd count: the last block holds one
    row)."""
    text = _tpu_text(scatter_add_rows, jnp.zeros((1000, 128), jnp.float32),
                     jnp.zeros((n,), jnp.int32),
                     jnp.zeros((n, 128), jnp.float32))
    assert "tpu_custom_call" in text


def _spec128(**kw):
    kw.setdefault("value_shape", (128,))
    return TableSpec(TableConfig(table_id="s", capacity=1024, num_blocks=16,
                                 **kw))


@pytest.fixture()
def as_tpu(monkeypatch):
    """Steer the route choice as on a TPU mesh, with the kernel body in
    the Pallas interpreter: every mesh "is" all-TPU, and the row kernels
    are called with ``interpret=True``."""
    import functools

    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "mesh_is_tpu", lambda mesh: True)
    for kernel in ("scatter_add_rows", "gather_rows"):
        monkeypatch.setattr(sparse, kernel, functools.partial(
            getattr(sparse, kernel), interpret=True))
    monkeypatch.setattr(sparse, "_SCATTER_TILE", 256)
    return platform.on_mesh


def test_scatter_route_keeps_xla_off_tpu(mesh8):
    """On a CPU mesh — and outside any mesh scope — the scatter route is
    XLA's scatter: no custom call in the lowered push."""
    from harmony_tpu.utils.platform import on_mesh

    spec = _spec128()
    args = (jnp.zeros(spec.storage_shape, jnp.float32),
            jnp.zeros((300,), jnp.int32), jnp.zeros((300, 128), jnp.float32))
    push = lambda a, k, d: spec.push(a, k, d, via="scatter")
    assert spec.push_lowering(300) == "xla"
    assert "custom_call" not in jax.jit(push).lower(*args).as_text()
    with on_mesh(mesh8):
        assert spec.push_lowering(300) == "xla"
        text = jax.jit(push).lower(*args).as_text()
    assert "custom_call" not in text and "scatter" in text


@pytest.mark.parametrize("why,kw", [
    ("narrower_rows", dict(value_shape=(64,))),
    ("wider_rows", dict(value_shape=(256,))),
    ("bf16_rows", dict(dtype="bfloat16")),
    ("min_mode", dict(update_fn="min")),
    ("max_mode", dict(update_fn="max")),
    ("set_mode", dict(update_fn="assign")),
])
def test_scatter_kernel_predicate_refuses(why, kw, as_tpu, mesh8):
    """Even traced for a TPU mesh the kernel is for additive float32 rows
    128 wide; everything else keeps XLA's scatter."""
    with as_tpu(mesh8):
        assert _spec128().push_lowering(300) == "pallas_rows"
        assert _spec128(**kw).push_lowering(300) == "xla"
        assert _spec128().push_lowering(0) == "xla"


def test_scatter_kernel_predicate_refuses_blocks_off_the_row_tile(as_tpu,
                                                                  mesh8):
    """Blocks that are not whole 8-row tiles: the flat row matrix the
    kernel needs would be a copy of the table each way."""
    spec = TableSpec(TableConfig(table_id="odd", capacity=1000,
                                 value_shape=(128,), num_blocks=8))
    assert spec.block_size % 8
    with as_tpu(mesh8):
        assert spec.push_lowering(300) == "xla"


@pytest.mark.parametrize("mesh_name", ["one_device", "mesh_2x4"])
def test_scatter_route_on_a_tpu_mesh_is_the_xla_scatter(mesh_name, as_tpu,
                                                        devices, mesh8):
    """The route as a TPU mesh takes it (rows ``P(model)``, ids made
    shard-local, foreign ids dropped, each shard updated in place) against
    the same push through XLA's scatter on unsharded rows: equal bit for
    bit, dropped and negative keys included (``.at[b, o]`` counts a
    negative block from the end)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table.table import block_sharding

    mesh = (mesh8 if mesh_name == "mesh_2x4"
            else build_mesh(devices[:1], data=1, model=1))
    spec = _spec128()
    rng = np.random.default_rng(11)
    arr = jnp.asarray(rng.standard_normal(spec.storage_shape), jnp.float32)
    keys = jnp.asarray(np.concatenate([
        _criteo_like(rng, 1024, 900), [-1, -3, 1024, 5000, -2000]]), jnp.int32)
    deltas = jnp.asarray(rng.standard_normal((keys.shape[0], 128)),
                         jnp.float32)
    want = spec.push(arr, keys, deltas, via="scatter")  # XLA: no mesh scope
    push = jax.jit(lambda a, k, d: spec.push(a, k, d, via="scatter"))
    with as_tpu(mesh):
        assert spec.push_lowering(keys.shape[0]) == "pallas_rows"
        got = push(jax.device_put(arr, block_sharding(mesh, spec.num_blocks)),
                   keys, deltas)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_kernel_route_still_applies_the_post_hook(as_tpu, mesh8):
    """An NMF-style non-negative table 128 wide: the clamp runs after the
    kernel as it does after XLA's scatter."""
    spec = _spec128(update_fn="add_nonneg")
    assert spec.update_fn.post is not None
    rng = np.random.default_rng(2)
    arr = jnp.asarray(np.abs(rng.standard_normal(spec.storage_shape)),
                      jnp.float32)
    keys = jnp.asarray(_criteo_like(rng, 1024, 600), jnp.int32)
    deltas = jnp.asarray(-2.0 * np.abs(rng.standard_normal((600, 128))),
                         jnp.float32)
    want = spec.push(arr, keys, deltas, via="scatter")
    with as_tpu(mesh8):
        assert spec.push_lowering(600) == "pallas_rows"
        got = jax.jit(lambda a, k, d: spec.push(a, k, d, via="scatter"))(
            arr, keys, deltas)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(np.asarray(got).min()) >= 0.0


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


# ---------------------------------------------------------------------------
# the step program against a per-phase reference over the public accessor
# ---------------------------------------------------------------------------


def _tables(trainer, mesh):
    from harmony_tpu.table.hashtable import DeviceHashTable, HashTableSpec

    cfg = trainer.model_table_config()
    table = (DeviceHashTable(HashTableSpec(cfg), mesh) if cfg.sparse
             else DenseTable(TableSpec(cfg), mesh))
    ltable = (DenseTable(TableSpec(trainer.local_table_config()), mesh)
              if trainer.uses_local_table else None)
    return table, ltable


def _run_worker(trainer, arrays, mesh, epochs, batches):
    table, ltable = _tables(trainer, mesh)
    params = TrainerParams(num_epochs=epochs, num_mini_batches=batches)
    ctx = TrainerContext(params=params, model_table=table,
                         local_table=ltable)
    data = TrainingDataProvider(arrays, batches)
    result = WorkerTasklet("j-step", ctx, trainer, data, mesh).run()
    return result["losses"], table


def _run_per_phase(trainer, arrays, mesh, epochs, batches):
    """The reference: the trainer's lifecycle driven phase by phase through
    the public host API. PULL reads rows to host numpy (``ModelAccessor``),
    COMP is ``trainer.compute`` alone in a jit, PUSH sends the delta back
    from the host. Operands are placed as the step's contract says — the
    pulled model replicated on the mesh, the batch split over the data
    axis, compute's outputs replicated — because that placement, not the
    arithmetic, is what GSPMD partitions the reductions by."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from harmony_tpu.parallel.mesh import DATA_AXIS

    table, ltable = _tables(trainer, mesh)
    ctx = TrainerContext(
        params=TrainerParams(num_epochs=epochs, num_mini_batches=batches),
        model_table=table, local_table=ltable)
    data = TrainingDataProvider(arrays, batches)
    acc = ModelAccessor(table)
    replicated = NamedSharding(mesh, P())
    by_data = NamedSharding(mesh, P(DATA_AXIS))
    local = trainer.uses_local_table
    comp = jax.jit(trainer.compute_with_local if local else trainer.compute,
                   out_shardings=replicated)
    keyed = trainer.pull_mode == "keys"
    keys_of = jax.jit(trainer.pull_keys) if keyed else None
    all_keys = None if keyed else np.arange(table.spec.config.capacity)

    trainer.init_global_settings(ctx)
    trainer.on_training_start(ctx, 0)
    losses = []
    for epoch in range(epochs):
        for batch in data.epoch_batches():
            hyper = {k: jnp.asarray(v)
                     for k, v in trainer.hyperparams().items()}
            keys = (np.asarray(keys_of(tuple(map(jnp.asarray, batch))))
                    if keyed else all_keys)
            model = jax.device_put(acc.pull(keys), replicated)     # PULL
            placed = tuple(jax.device_put(a, by_data) for a in batch)
            if local:
                lmodel = jax.device_put(np.asarray(ltable.pull_array()),
                                        replicated)
                delta, new_l, metrics = comp(model, lmodel, placed, hyper)
                ltable.write_all(np.asarray(new_l))
            else:
                delta, metrics = comp(model, placed, hyper)        # COMP
            acc.push(keys, np.asarray(delta))                      # PUSH
        # an epoch's figure is its last step's objective (_finish_epoch)
        losses.append(float(metrics[trainer.objective_metric or "loss"]))
        trainer.on_epoch_finished(ctx, epoch)
    return losses, table


def _state(table):
    items = getattr(table, "items", None)  # hash table: rows by key
    if items is not None:
        rows = items()
        return np.stack([rows[k] for k in sorted(rows)])
    return np.asarray(table.pull_array())


def _mlr():
    from harmony_tpu.apps.mlr import MLRTrainer, make_synthetic

    return (MLRTrainer(num_classes=4, num_features=16,
                       features_per_partition=8),
            make_synthetic(64, 16, 4, seed=1))


def _nmf():
    from harmony_tpu.apps.nmf import NMFTrainer, make_synthetic

    return (NMFTrainer(num_rows=32, num_cols=24, rank=4, seed=2),
            make_synthetic(32, 24, 4, seed=2))


def _lda(sparse=False):
    from harmony_tpu.apps import lda

    if sparse:  # hash-backed topic-word counts beside a dense local table
        return (lda.LDATrainer(vocab_size=50, num_topics=5, num_docs=32,
                               max_doc_len=10, sparse=True, slot_budget=256),
                lda.make_synthetic_sparse(32, 50, 5, 10, seed=3))
    return (lda.LDATrainer(vocab_size=50, num_topics=5, num_docs=32,
                           max_doc_len=10),
            lda.make_synthetic(32, 50, 5, 10, seed=3))


def _fm(sparse=False):
    """The keyed families of the benchmark's criteo cells: a dense keyed
    table and a DeviceHashTable."""
    from harmony_tpu.apps import widedeep

    data = (widedeep.make_synthetic_sparse if sparse
            else widedeep.make_synthetic)
    return (widedeep.FMTrainer(vocab_size=64, num_slots=4, emb_dim=7,
                               sparse=sparse),
            data(64, 64, 4, seed=4))


def _lm(**arch):
    """A tiny ``TransformerLM`` behind ``PyTreeTrainer`` (pull-all, Adam
    state in the table): gpt2's family, and with ``arch`` OLMoE's."""
    from harmony_tpu.models import TransformerTrainer, make_lm_data

    cfg = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_seq=16)
    cfg.update(arch)
    return (TransformerTrainer(row_width=128, step_size=1e-2,
                               optimizer="adam", **cfg),
            (make_lm_data(16, 17, cfg["vocab_size"], seed=5),))


def _olmoe():
    return _lm(vocab_size=96, d_model=64, n_heads=4, d_ff=32, pos="rope",
               qk_norm=True, ffn="swiglu", tie_embeddings=False,
               norm_eps=1e-5, moe_experts=8, moe_top_k=2, moe_every=1,
               moe_aux_weight=0.01, moe_z_weight=0.001, moe_experts_held=3)


#: family -> (make, epochs, batches a epoch, absolute tolerance of the
#: final table state). State tolerance: MLR's and FM's deltas come out of
#: a gradient matmul over the batch, whose accumulation XLA orders per
#: PROGRAM — the step's compute and a compute jitted alone differ in the
#: last bit (7e-9 / 1.5e-8 seen) although every loss along the way is
#: equal; the others' state is counts, or passes through no such matmul
#: on the way to the table, and is exact.
_FAMILIES = {
    "mlr": (_mlr, 3, 4, 1e-6),
    "nmf": (_nmf, 3, 4, 0.0),
    "lda": (_lda, 3, 4, 0.0),
    "lda-hash": (lambda: _lda(sparse=True), 2, 4, 0.0),
    "fm-scatter": (_fm, 3, 4, 1e-6),
    "fm-hash": (lambda: _fm(sparse=True), 3, 4, 0.0),
    "lm": (_lm, 2, 2, 0.0),
    "olmoe": (_olmoe, 2, 2, 0.0),
}
#: mesh -> (data, model): one device is what the benchmark's one-chip
#: cells run; 2 x 4 is where the boundaries' replication is a constraint
_MESHES = {"one-device": (1, 1), "data2-model4": (2, 4)}


@pytest.mark.parametrize("mesh_name", list(_MESHES))
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_step_matches_per_phase_accessor_loop(family, mesh_name, devices):
    """The one step program against the per-phase reference, family by
    family: the classic apps, and the families the benchmark's cells run."""
    from harmony_tpu.parallel import build_mesh

    make, epochs, batches, state_atol = _FAMILIES[family]
    data_ax, model_ax = _MESHES[mesh_name]
    mesh = build_mesh(devices[:data_ax * model_ax], data=data_ax,
                      model=model_ax)
    trainer, arrays = make()
    step_losses, step_table = _run_worker(trainer, arrays, mesh, epochs,
                                          batches)
    trainer, arrays = make()
    ref_losses, ref_table = _run_per_phase(trainer, arrays, mesh, epochs,
                                           batches)
    if (family, mesh_name) == ("lda", "one-device"):
        # LDA's objective is a float mean of per-token log-likelihoods;
        # on one device XLA's CPU backend vectorises that reduction
        # differently inside the whole step than in a compute jitted alone
        # (1 ulp: -2.0623443 against -2.0623441). The state below — the
        # counts every later step reads — is exact.
        np.testing.assert_allclose(step_losses, ref_losses, rtol=1e-6)
    else:
        assert step_losses == ref_losses  # bit-identical
    for table in (step_table, ref_table):
        # nothing dropped: the reference cannot see an admission mask
        assert getattr(table, "overflow_count", 0) == 0
    if state_atol:
        np.testing.assert_allclose(_state(step_table), _state(ref_table),
                                   rtol=0, atol=state_atol)
    else:
        np.testing.assert_array_equal(_state(step_table), _state(ref_table))


@pytest.mark.parametrize("mesh_name", list(_MESHES))
def test_worker_step_on_the_kernel_lowering(mesh_name, devices, monkeypatch,
                                            request):
    """A keyed FM tenant with 128-wide rows through ``WorkerTasklet``: as a CPU mesh lowers it (XLA's scatter) and as a
    TPU mesh does (the Pallas row scatter-add, interpreted) the losses and
    the table are equal bit for bit, and each run says which it took in
    the tenant ledger (STATUS ``table_layout.push_lowering``) and the
    gauge."""
    from harmony_tpu.apps import widedeep
    from harmony_tpu.metrics.accounting import ledger
    from harmony_tpu.metrics.registry import get_registry
    from harmony_tpu.parallel import build_mesh

    data_ax, model_ax = _MESHES[mesh_name]
    mesh = build_mesh(devices[:data_ax * model_ax], data=data_ax,
                      model=model_ax)

    def run():
        trainer = widedeep.FMTrainer(vocab_size=2047, num_slots=4,
                                     emb_dim=127)
        losses, table = _run_worker(
            trainer, widedeep.make_synthetic(64, 2047, 4, seed=4), mesh, 2, 4)
        layout = ledger().snapshot()["j-step"]["table_layout"]
        gauge = [line for line in get_registry().expose().splitlines()
                 if line.startswith("harmony_table_push_pallas_rows{")
                 and 'job="j-step"' in line]
        return losses, _state(table), layout["push_lowering"], gauge

    losses, state, lowering, gauge = run()
    assert lowering == "xla" and gauge[0].endswith(" 0"), (lowering, gauge)
    request.getfixturevalue("as_tpu")
    from harmony_tpu.runtime import progcache

    progcache.clear()  # the step's key does not name its lowering
    traced, kernel = [], sparse.scatter_add_rows
    monkeypatch.setattr(
        sparse, "scatter_add_rows",
        lambda *a, **kw: traced.append(a[1].shape) or kernel(*a, **kw))
    k_losses, k_state, lowering, gauge = run()
    assert traced  # the step really was built on the kernel
    assert lowering == "pallas_rows" and gauge[0].endswith(" 1"), (
        lowering, gauge)
    assert k_losses == losses
    np.testing.assert_array_equal(k_state, state)


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
