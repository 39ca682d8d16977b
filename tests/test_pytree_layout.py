"""The pytree model table's storage is its own row matrix.

Blocks are whole (8, 128) tiles with no tail block, every section starts a
tile, the counter has a block of its own, and ``compute`` runs the optimizer
on the sections as rows — against a frozen copy of the flat ``compute`` it
replaced (kept here only), to the last bit. And the step in two parts: the
gradient is COMP, the update rule runs in PUSH on the stored rows
(``pull_all_step``) — against ``compute`` + ``push_all``, to the last bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

import dataclasses

from jax.sharding import NamedSharding, PartitionSpec as P

from harmony_tpu.config.params import TableConfig, TrainerParams
from harmony_tpu.dolphin import TrainerContext, optim
from harmony_tpu.dolphin.worker import (
    _phase_boundary,
    pull_all_step,
    update_lowering,
)
from harmony_tpu.metrics import table_layout
from harmony_tpu.models import (
    TransformerConfig,
    TransformerTrainer,
    make_lm_data,
)
from harmony_tpu.models.pytree_trainer import PyTreeTrainer, _read_shape
from harmony_tpu.parallel import build_mesh
from harmony_tpu.table import DenseTable, TableSpec
from harmony_tpu.table.table import block_sharding, row_shards
from harmony_tpu.utils.platform import traced_on

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_seq=64, attn="blockwise")
BY_SLOTS = {0: "sgd", 1: "momentum", 2: "adam"}


class _Vector:
    """A model of one leaf of ``n`` parameters."""

    def __init__(self, n):
        self.n = n

    def init(self, key):
        return {"w": jax.random.normal(key, (self.n,), jnp.float32)}


class VectorTrainer(PyTreeTrainer):
    config_cls = int

    def build_model(self, config):
        return _Vector(config)

    def loss_on_batch(self, params, batch):
        return jnp.mean((params["w"][: batch.shape[-1]] - batch) ** 2)


# -- the flat layout and the flat compute, frozen (PRs 25-41) ----------------
#
# All leaves raveled end to end into ``num_params`` floats, cut into rows;
# sections ``flat_rows`` apart with the counter in the last row (before PR
# 26) or a whole number of tiles apart with a counter block (PRs 26-41).
# The program keeps this rule in ``rows_from_flat_chain`` alone.

def flat_rows(tr):
    return -(-tr.num_params // tr.row_width)


def flat_stride(tr):
    return -(-flat_rows(tr) // 8) * 8


def legacy_capacity(tr):
    return flat_rows(tr) * (1 + tr.num_state_slots) + bool(tr.num_state_slots)


def flat_capacity(tr):
    slots = tr.num_state_slots
    return (1 + slots) * flat_stride(tr) + (8 if slots else 0)


def flat_to_rows(tr, flat, rows):
    pad = rows * tr.row_width - tr.num_params
    return jnp.concatenate(
        [flat, jnp.zeros((pad,), flat.dtype)]).reshape(rows, tr.row_width)


def flat_unravel(tr):
    template = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    return ravel_pytree(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template))[1]


def flat_leaves(tr, table, i, stride):
    """Section ``i`` of a table in the flat layout, as the pytree."""
    rows = jnp.asarray(table)[i * stride:(i + 1) * stride]
    return flat_unravel(tr)(rows.reshape(-1)[: tr.num_params])


def legacy_compute(tr, model, batch, hyper):
    """PyTreeTrainer.compute as of PR 25: sections of ``flat_rows`` rows
    flattened to ``[num_params]``, the optimizer on flat vectors, every
    delta padded and reshaped back, the counter in the last row."""
    n_rows = flat_rows(tr)

    def section(i):
        rows = model[i * n_rows:(i + 1) * n_rows]
        return rows.reshape(-1)[: tr.num_params]

    def to_rows(flat):
        return flat_to_rows(tr, flat, n_rows)

    pflat = section(0)
    (loss, extra), grads = jax.value_and_grad(
        tr.loss_and_metrics_on_batch, has_aux=True)(
            flat_unravel(tr)(pflat), batch)
    gflat, _ = ravel_pytree(grads)
    slots = tr.num_state_slots
    m = section(1) if slots >= 1 else jnp.zeros_like(pflat)
    v = section(2) if slots >= 2 else jnp.zeros_like(pflat)
    t = model[-1, 0] + 1.0 if slots else jnp.asarray(1.0)
    new_p, new_m, new_v = optim.apply(tr.optimizer, pflat, gflat, m, v, t,
                                      hyper)
    sections = [to_rows(new_p - pflat)]
    if slots >= 1:
        sections.append(to_rows(new_m - m))
    if slots >= 2:
        sections.append(to_rows(new_v - v))
    delta = jnp.concatenate(sections)
    if slots:
        counter = jnp.zeros((1, tr.row_width), delta.dtype).at[0, 0].set(1.0)
        delta = jnp.concatenate([delta, counter])
    return delta, {"loss": loss, **extra}


def _lm(optimizer, row_width=256):
    return TransformerTrainer(CFG, row_width=row_width, step_size=3e-3,
                              optimizer=optimizer)


def _start(tr, rows):
    """The initial model in the trainer's own layout."""
    params = tr.model.init(jax.random.PRNGKey(tr.seed))
    model = jnp.zeros((rows, tr.row_width), jnp.float32)
    return model.at[: tr.section_rows].set(tr.leaf_rows.to_rows(params))


def _flat_start(tr, rows):
    """The same model in the flat layout."""
    flat, _ = ravel_pytree(tr.model.init(jax.random.PRNGKey(tr.seed)))
    model = jnp.zeros((rows, tr.row_width), jnp.float32)
    return model.at[: flat_rows(tr)].set(
        flat_to_rows(tr, flat, flat_rows(tr)))


def _train(compute, tr, model, steps):
    batch = (jnp.asarray(make_lm_data(4, 33, CFG.vocab_size, seed=7)),)
    hyper = {k: jnp.asarray(v, jnp.float32)
             for k, v in tr.hyperparams().items()}
    step = jax.jit(lambda model: compute(model, batch, hyper))
    losses = []
    for _ in range(steps):
        delta, metrics = step(model)
        model = model + delta
        losses.append(float(metrics["loss"]))
    return np.asarray(model), losses


def _assert_same_leaves(tr, new, old, old_stride):
    """Sections p, m, v of ``new`` (the trainer's layout) hold, leaf by
    leaf, the bits of ``old`` (flat, sections ``old_stride`` apart); pad
    rows and pad lanes of ``new`` are zero; every section moved."""
    for i in range(1 + tr.num_state_slots):
        section = jnp.asarray(tr.section(new, i))
        got = tr.leaf_rows.to_leaves(section)
        want = flat_leaves(tr, old, i, old_stride)
        jax.tree.map(np.testing.assert_array_equal, got, want)
        # the leaves are all a section holds: its pads are zero
        np.testing.assert_array_equal(tr.leaf_rows.to_rows(got), section)
        assert sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(got))


# -- (a) the schema ----------------------------------------------------------

@pytest.mark.parametrize("num_blocks", [0, 3])
@pytest.mark.parametrize("num_params", [16 * 128, 9 * 128 + 5, 14 * 128 + 1])
@pytest.mark.parametrize("slots", [0, 1, 2])
def test_storage_is_the_row_matrix(slots, num_params, num_blocks):
    tr = VectorTrainer(num_params, row_width=128, optimizer=BY_SLOTS[slots])
    assert flat_rows(tr) % 8 == {2048: 0, 1157: 2, 1793: 7}[num_params]
    assert tr.section_rows == 16  # one leaf: whole tiles of 8 x 128
    spec = TableSpec(tr.model_table_config(num_blocks=num_blocks))
    capacity = spec.config.capacity
    assert spec.block_size % 8 == 0
    assert spec.num_blocks * spec.block_size == capacity >= tr.capacity
    if num_blocks:
        assert spec.num_blocks == num_blocks
        assert capacity - tr.capacity < 8 * num_blocks  # no block to spare
    else:
        assert spec.block_size == 8 and capacity == tr.capacity
    stride = tr.section_stride(capacity)
    assert stride % 8 == 0 and stride >= flat_rows(tr)
    assert capacity >= (1 + slots) * stride + (8 if slots else 0)
    # sections and the counter through the accessors only
    model = np.arange(capacity * 128, dtype=np.float32).reshape(capacity, 128)
    for i in range(1 + slots):
        assert tr.section(model, i)[0, 0] == i * stride * 128
        assert tr.section(model, i).shape == (stride, 128)
    if slots:
        assert tr.counter(model) == (1 + slots) * stride * 128
    # pulling the whole model moves nothing; pushing pads nothing
    arr = jax.ShapeDtypeStruct(spec.storage_shape, jnp.float32)
    rows = jax.ShapeDtypeStruct((capacity, 128), jnp.float32)
    for jaxpr in (jax.make_jaxpr(spec.pull_all)(arr),
                  jax.make_jaxpr(spec.push_all)(arr, rows)):
        names = {e.primitive.name for e in jaxpr.eqns}
        assert not names & {"slice", "dynamic_slice", "pad", "concatenate",
                            "gather"}, names


# -- (b) rows against the flat compute, to the last bit ----------------------

@pytest.mark.parametrize("optimizer,steps", [
    ("adam", 3), ("sgd", 1), ("momentum", 1), ("adagrad", 1), ("rmsprop", 1)])
def test_row_compute_equals_flat_compute(optimizer, steps):
    tr = _lm(optimizer, row_width=128)  # 162 flat rows, 15 leaves in 200
    assert flat_rows(tr) % 8 and tr.num_params % tr.row_width
    new, new_losses = _train(tr.compute, tr, _start(tr, tr.capacity), steps)
    old, old_losses = _train(lambda *a: legacy_compute(tr, *a), tr,
                             _flat_start(tr, legacy_capacity(tr)), steps)
    assert new_losses == old_losses
    _assert_same_leaves(tr, new, old, flat_rows(tr))
    slots = tr.num_state_slots
    if slots:
        assert tr.counter(new) == old[-1, 0] == steps
        block = new[(1 + slots) * tr.section_rows:]
        assert block.shape[0] == 8 and block.sum() == steps


# -- (c) init and evaluate go through the same accessors --------------------

@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_init_and_evaluate_round_trip_the_parameters(mesh8, optimizer):
    tr = _lm(optimizer, row_width=128)
    table = DenseTable(TableSpec(tr.model_table_config()), mesh8)
    tr.init_global_settings(TrainerContext(
        params=TrainerParams(num_epochs=1, num_mini_batches=1),
        model_table=table))
    model = jnp.asarray(table.pull_array())
    assert model.shape == (tr.capacity, 128)
    params = tr.model.init(jax.random.PRNGKey(tr.seed))
    jax.tree.map(np.testing.assert_array_equal, tr._params(model), params)
    assert not np.asarray(model)[tr.section_rows:].any()
    np.testing.assert_array_equal(  # pad rows and lanes start at zero
        tr.leaf_rows.to_rows(params), tr.section(model, 0))
    batch = (jnp.asarray(make_lm_data(4, 33, CFG.vocab_size, seed=3)),)
    np.testing.assert_array_equal(
        tr.evaluate(model, batch)["loss"], tr.loss_on_batch(params, batch))


# -- (d) a table from an older chain -----------------------------------------

_FLAT_CHAINS = {
    # name: (capacity, rows between its sections)
    "unaligned": (legacy_capacity, flat_rows),   # before PR 26
    "tile_aligned": (flat_capacity, flat_stride),  # PRs 26-41
}


@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adam"])
def test_older_layout_trains_to_the_same_bits(optimizer):
    """``[params | m | v | counter row]`` at a stride of ``flat_rows``,
    trained there by the frozen flat compute: converted once
    (``rows_from_flat_chain``) it holds the same leaves and the same
    count, and two more steps on each side end on the same bits."""
    tr = _lm(optimizer, row_width=128)
    old, _ = _train(lambda *a: legacy_compute(tr, *a), tr,
                    _flat_start(tr, legacy_capacity(tr)), 2)
    new = tr.rows_from_flat_chain(old)
    assert new.shape == (tr.capacity, 128)
    _assert_same_leaves(tr, new, old, flat_rows(tr))
    new, new_losses = _train(tr.compute, tr, jnp.asarray(new), 2)
    old, old_losses = _train(lambda *a: legacy_compute(tr, *a), tr,
                             jnp.asarray(old), 2)
    assert new_losses == old_losses
    _assert_same_leaves(tr, new, old, flat_rows(tr))
    if tr.num_state_slots:
        assert tr.counter(new) == 4


@pytest.mark.parametrize("off", [-1, 1, 7])
def test_unknown_row_count_is_refused_by_name(off):
    tr = _lm("adam", row_width=128)
    rows = legacy_capacity(tr) + off
    with pytest.raises(ValueError) as e:
        tr.rows_from_flat_chain(np.zeros((rows, 128), np.float32))
    for n in (rows, tr.capacity, legacy_capacity(tr), flat_capacity(tr)):
        assert str(n) in str(e.value)
    assert "leaf_rows" in str(e.value) and "flat" in str(e.value)
    with pytest.raises(ValueError) as e:  # and the step's own accessors
        tr.section(np.zeros((rows, 128), np.float32), 1)
    assert str(rows) in str(e.value) and str(tr.capacity) in str(e.value)


# -- (e) the step in two parts: gradient | update rule + fold ----------------

def _whole_delta_step(spec, tr, mesh):
    """The step before the update moved into PUSH: ``compute``'s whole
    delta, fenced, through ``push_all``."""
    def step(arr, batch, hyper):
        model = _phase_boundary(spec.pull_all(arr), replicate_on=mesh)
        delta, metrics = _phase_boundary(tr.compute(model, batch, hyper),
                                         replicate_on=mesh)
        return spec.push_all(arr, delta), metrics
    return step


def _run_step(body, tr, spec, mesh, steps):
    tsh = block_sharding(mesh, spec.num_blocks)
    step = jax.jit(traced_on(mesh, body), out_shardings=(tsh, None),
                   donate_argnums=0)
    start = np.zeros((spec.num_blocks * spec.block_size, tr.row_width),
                     np.float32)
    start[: tr.section_rows] = np.asarray(tr.leaf_rows.to_rows(
        tr.model.init(jax.random.PRNGKey(tr.seed))))
    arr = jax.device_put(start.reshape(spec.storage_shape), tsh)
    batch = (jax.device_put(make_lm_data(4, 33, CFG.vocab_size, seed=7),
                            NamedSharding(mesh, P("data"))),)
    hyper = {k: jnp.asarray(v, jnp.float32)
             for k, v in tr.hyperparams().items()}
    losses = []
    for _ in range(steps):
        arr, metrics = step(arr, batch, hyper)
        losses.append(float(metrics["loss"]))
    return np.asarray(arr), losses


def _mesh(name, devices):
    return (build_mesh(devices[:1], data=1) if name == "one-device"
            else build_mesh(devices[:8], data=2, model=4))


@pytest.mark.parametrize("mesh_name", ["one-device", "data2-model4"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adam"])
def test_shipped_step_equals_compute_then_push_all(optimizer, mesh_name,
                                                   devices):
    """Final table and every loss, bit for bit, over four steps. The
    update runs on the stored rows wherever every device holds the table
    whole; the one table here that the 2 x 4 mesh shards by rows (adam's
    64 blocks) keeps the whole delta."""
    mesh = _mesh(mesh_name, devices)
    tr = _lm(optimizer, row_width=128)
    spec = TableSpec(tr.model_table_config())
    sharded = row_shards(mesh, spec.num_blocks) > 1
    assert sharded == (mesh_name == "data2-model4" and optimizer == "adam")
    assert update_lowering(spec, tr, mesh) == (
        "whole_delta" if sharded else "row_ranges")
    new, new_losses = _run_step(pull_all_step(spec, tr, mesh), tr, spec,
                                mesh, 4)
    old, old_losses = _run_step(_whole_delta_step(spec, tr, mesh), tr, spec,
                                mesh, 4)
    assert new_losses == old_losses and len(set(new_losses)) == 4
    np.testing.assert_array_equal(new, old)
    if tr.num_state_slots:
        assert tr.counter(new.reshape(-1, tr.row_width)) == 4


class _OnePart(TransformerTrainer):
    def row_update_parts(self, capacity):
        return None


_WHOLE_DELTA_CASES = {
    # name: (trainer class, table config of the trainer, mesh)
    "post_hook": (TransformerTrainer, lambda tr: dataclasses.replace(
        tr.model_table_config(), update_fn="add_nonneg"), "one-device"),
    "non_additive_fold": (TransformerTrainer, lambda tr: dataclasses.replace(
        tr.model_table_config(), update_fn="max"), "one-device"),
    "trainer_without_the_two_parts": (
        _OnePart, lambda tr: tr.model_table_config(), "one-device"),
    "rows_sharded_over_the_model_axis": (
        TransformerTrainer, lambda tr: tr.model_table_config(),
        "data2-model4"),
}


@pytest.mark.parametrize("case", list(_WHOLE_DELTA_CASES))
def test_predicate_keeps_the_whole_delta(case, devices):
    """Each thing the predicate looks at, one at a time: the step reads
    ``whole_delta`` and IS ``compute`` + ``push_all``."""
    cls, config, mesh_name = _WHOLE_DELTA_CASES[case]
    mesh = _mesh(mesh_name, devices)
    tr = cls(CFG, row_width=128, step_size=3e-3, optimizer="adam")
    spec = TableSpec(config(tr))
    assert update_lowering(spec, tr, mesh) == "whole_delta"
    new, new_losses = _run_step(pull_all_step(spec, tr, mesh), tr, spec,
                                mesh, 3)
    old, old_losses = _run_step(_whole_delta_step(spec, tr, mesh), tr, spec,
                                mesh, 3)
    assert new_losses == old_losses
    np.testing.assert_array_equal(new, old)


def _padded(ranges, capacity):
    delta = np.zeros((capacity, ranges[0][1].shape[1]), np.float32)
    for first, rows in ranges:
        delta[first:first + rows.shape[0]] = np.asarray(rows)
    return jnp.asarray(delta)


@pytest.mark.parametrize("firsts", [
    [(0, 16), (24, 40), (72, 8)],   # most rows named
    [(0, 16), (24, 8), (72, 8)],
    [(40, 8)],
])
@pytest.mark.parametrize("num_blocks", [10, 3])  # 3: a padded tail block
def test_range_push_equals_push_all_of_the_padded_delta(num_blocks, firsts):
    rng = np.random.default_rng(0)
    spec = TableSpec(TableConfig(table_id="r", capacity=80,
                                 num_blocks=num_blocks, value_shape=(128,),
                                 is_ordered=True))
    assert spec.takes_row_ranges
    arr = jnp.asarray(rng.normal(size=spec.storage_shape).astype(np.float32))
    ranges = [(f, jnp.asarray(rng.normal(size=(n, 128)).astype(np.float32)))
              for f, n in firsts]
    np.testing.assert_array_equal(
        spec.push_row_ranges(arr, ranges),
        spec.push_all(arr, _padded(ranges, 80)))
    names = {e.primitive.name for e in jax.make_jaxpr(
        lambda a: spec.push_row_ranges(a, ranges))(arr).eqns}
    assert "dynamic_update_slice" in names and not names & {
        "scatter-add", "scatter_add", "gather", "pad", "concatenate"}, names


@pytest.mark.parametrize("why,config,match", [
    ("min", dict(update_fn="min"), "'min'"),
    ("max", dict(update_fn="max"), "'max'"),
    ("post_hook", dict(update_fn="add_nonneg"), "'add_nonneg'"),
    ("hashed_keys", dict(is_ordered=False), "HashPartitioner"),
])
def test_range_push_refuses_by_name(why, config, match):
    """A delta that names some rows and one that pads the others with
    zeros fold to the same table only under the plain additive fold on
    keys in storage order: anything else is refused, never approximated."""
    spec = TableSpec(TableConfig(**{
        "table_id": "r", "capacity": 80, "num_blocks": 10,
        "value_shape": (128,), "is_ordered": True, **config}))
    assert not spec.takes_row_ranges
    arr = jnp.zeros(spec.storage_shape, jnp.float32)
    with pytest.raises(ValueError, match=match):
        spec.push_row_ranges(arr, [(0, jnp.ones((8, 128), jnp.float32))])
    with pytest.raises(ValueError, match=match):
        spec.fold_row_sections(arr, jnp.ones((8, 128), jnp.float32), {},
                               lambda stored, g, scalars: (g,), rows=8,
                               sections=1)


@pytest.mark.parametrize("first,n", [(76, 8), (-1, 8), (8, 8)])
def test_range_push_refuses_rows_outside_or_out_of_order(first, n):
    spec = TableSpec(TableConfig(table_id="r", capacity=80, num_blocks=10,
                                 value_shape=(128,), is_ordered=True))
    arr = jnp.zeros(spec.storage_shape, jnp.float32)
    ones = lambda k: jnp.ones((k, 128), jnp.float32)
    with pytest.raises(ValueError, match="push_row_ranges"):
        spec.push_row_ranges(arr, [(0, ones(16)), (first, ones(n))])


# -- (f) the fold as one in-place pass: ops/sections.py ----------------------

def _plain_rule(stored, g, consts):
    """Elementwise across the sections, with no product next to a sum:
    XLA's CPU backend contracts ``a * b + c`` where one fusion holds both,
    so only such a rule is bit-equal between two CPU programs."""
    p, m, v = (*stored, g, g)[:3]
    return ((g - m) - v, p - g,
            jnp.sqrt(jnp.abs(v)) / (consts[0:1] + jnp.abs(g)))[:len(stored)]


def _adam_rule(stored, g, consts):
    new = optim.apply("adam", *stored[:1], g, *stored[1:], consts[0:1],
                      {"lr": consts[1:2], "beta2": consts[2:3]})
    return tuple(n - o for n, o in zip(new, stored))


_FOLD_SHAPES = {
    # name: (rows of each piece of the gradient, row width, rows after the
    # sections); one piece is the gradient as one array
    "one_tile": ([8], 128, 8),
    "one_short_block": ([88], 128, 8),
    "whole_blocks": ([512], 128, 16),
    "last_block_overlaps": ([264], 128, 8),
    "last_block_of_odd_tiles": ([600], 256, 0),
    # pieces: every one an operand of its own, walked in blocks of 256 rows
    "ends_off_the_blocks": ([264, 600, 296], 128, 8),
    "a_piece_of_one_block": ([256, 512, 256], 128, 8),
    "small_between_two_large": ([1032, 24, 1040], 128, 8),
    "small_first_and_last": ([8, 520, 16], 128, 8),
    "all_short_of_a_block": ([8, 16, 8], 128, 0),
    "many": ([16, 256, 8, 1000, 24, 304, 8], 128, 8),
}


def _fold_operands(rows, width, extra, sections=3):
    rng = np.random.default_rng(rows)
    table = rng.normal(size=(sections * rows + extra, width)).astype(
        np.float32)
    table[(sections - 1) * rows:sections * rows] = np.abs(
        table[(sections - 1) * rows:sections * rows])  # v >= 0
    consts = np.broadcast_to(
        np.asarray([3.0, 1e-3, 0.95], np.float32)[:, None], (3, width))
    return (jnp.asarray(table),
            jnp.asarray(rng.normal(size=(rows, width)).astype(np.float32)),
            jnp.asarray(consts))


@pytest.mark.parametrize("sections", [1, 3])   # SGD's table, Adam's
@pytest.mark.parametrize("shape", list(_FOLD_SHAPES))
def test_fold_kernel_equals_its_reference(shape, sections):
    """Every section's every row once, whatever the pieces and the block
    count: a piece's last block overlaps the one before it and writes only
    its new rows, a piece shorter than a block is read inside a block
    pushed back into the section, and the rows after the sections are not
    touched. A piece may be longer than the rows it stands for (a leaf
    whose reshape ends inside a tile): the rest is not read."""
    from harmony_tpu.ops.sections import (
        fold_row_sections,
        fold_row_sections_ref,
    )

    pieces, width, extra = _FOLD_SHAPES[shape]
    rows = sum(pieces)
    table, g, consts = _fold_operands(rows, width, extra, sections)
    fold = dict(rows=rows, sections=sections)
    firsts = [0, *np.cumsum(pieces)[:-1].tolist()]
    junk = jnp.full((3, width), np.nan, jnp.float32)

    def side(g):
        if len(pieces) == 1:
            return g
        parts = [g[a:a + n] for a, n in zip(firsts, pieces)]
        # the longest piece handed over with rows after its own
        longest = int(np.argmax(pieces))
        parts[longest] = jnp.concatenate([parts[longest], junk])
        return list(zip(firsts, parts))

    want = jax.jit(lambda *a: fold_row_sections_ref(*a, _plain_rule, **fold))(
        table, g, consts)
    got = jax.jit(lambda t, g, c: fold_row_sections(
        t, side(g), c, _plain_rule, interpret=True, **fold))(table, g, consts)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        jax.jit(lambda t, g, c: fold_row_sections_ref(
            t, side(g), c, _plain_rule, **fold))(table, g, consts), want)
    assert np.abs(np.asarray(want) - np.asarray(table))[
        :sections * rows].max(axis=1).min() > 0  # every row moved
    np.testing.assert_array_equal(got[sections * rows:],
                                  table[sections * rows:])
    if sections < 3:
        return
    # Adam's rule: the same jnp ops, so at most the CPU's contraction of
    # ``b1 * m + (1 - b1) * g`` apart (the chip reads equal: PERF.md PR 30)
    want = jax.jit(lambda *a: fold_row_sections_ref(*a, _adam_rule, **fold))(
        table, g, consts)
    got = jax.jit(lambda t, g, c: fold_row_sections(
        t, side(g), c, _adam_rule, interpret=True, **fold))(table, g, consts)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-7)  # an ulp of 4


@pytest.mark.parametrize("pieces,rows", [
    ([16, 16], 32), ([8], 8), ([264, 8, 600], 872), ([8] * 5, 40),
    ([300 * 8, 8, 256, 264], 2928)])
def test_fold_schedule_writes_every_row_once(pieces, rows):
    """The kernel's walk, as numbers: every block lies inside the section
    and inside its piece, and the new rows of all blocks are the section's
    rows, each once, in order."""
    from harmony_tpu.ops import sections

    block = min(sections._BLOCK_ROWS, rows)
    at, piece, piece_at, new_at, new = sections._schedule(pieces, rows, block)
    assert (at >= 0).all() and (at + block <= rows).all()
    assert (at % 8 == 0).all() and (new_at % 8 == 0).all()
    assert (new > 0).all() and (new_at + new <= block).all()
    held = np.maximum(np.asarray(pieces)[piece], block)  # short ones padded
    assert (piece_at >= 0).all() and (piece_at + block <= held).all()
    starts = at + new_at
    np.testing.assert_array_equal(starts, np.cumsum([0, *new[:-1]]))
    assert starts[-1] + new[-1] == rows
    firsts = np.cumsum([0, *pieces[:-1]])
    # a block's new rows are its piece's rows, read at the same offset
    long = np.asarray(pieces)[piece] >= block
    np.testing.assert_array_equal((starts - firsts[piece])[long],
                                  (piece_at + new_at)[long])
    assert (piece_at[~long] == 0).all()


@pytest.mark.parametrize("bad,match", [
    (dict(rows=12), "8-row tiles"),
    (dict(width=100), "whole lanes"),
    (dict(dtype=jnp.bfloat16), "float32"),
    (dict(sections=4), "fold_row_sections_ref"),   # more rows than it has
    (dict(pieces=[0, 12]), "8-row tiles"),         # a piece of half tiles
    (dict(pieces=[0, 8], short=8), "fold_row_sections_ref"),  # rows missing
])
def test_fold_kernel_refuses_what_it_cannot_tile(bad, match):
    from harmony_tpu.ops.sections import fold_row_sections

    rows, width = bad.get("rows", 16), bad.get("width", 128)
    dtype = bad.get("dtype", jnp.float32)
    table = jnp.zeros((3 * rows + 8, width), dtype)
    side = jnp.zeros((rows - bad.get("short", 0), width), dtype)
    if "pieces" in bad:
        side = [(first, side[first:end]) for first, end in zip(
            bad["pieces"], [*bad["pieces"][1:], rows])]
    with pytest.raises(ValueError, match=match):
        fold_row_sections(table, side,
                          jnp.zeros((1, width), dtype), _plain_rule,
                          rows=rows, sections=bad.get("sections", 3),
                          interpret=True)


@pytest.fixture()
def as_tpu(monkeypatch):
    """Steer the lowering as on a TPU mesh, with the kernel body in the
    Pallas interpreter."""
    import functools

    from harmony_tpu.ops import sections
    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "mesh_is_tpu", lambda mesh: True)
    traced = []
    kernel = functools.partial(sections.fold_row_sections, interpret=True)
    monkeypatch.setattr(
        sections, "fold_row_sections",
        lambda *a, **kw: traced.append(a[0].shape) or kernel(*a, **kw))
    return traced


def test_fold_lowering_is_chosen_from_what_is_traced(as_tpu, devices):
    from harmony_tpu.utils.platform import on_mesh

    one = build_mesh(devices[:1], data=1)
    spec = TableSpec(_lm("adam", row_width=128).model_table_config())
    rows = spec.config.capacity // 3 // 8 * 8
    assert spec.fold_lowering(rows, 3) == "xla"  # no mesh named: XLA
    with on_mesh(one):
        assert spec.fold_lowering(rows, 3) == "pallas_sections"
        assert spec.fold_lowering(rows + 4, 3) == "xla"
        for field, value in (("value_shape", (100,)), ("dtype", "bfloat16"),
                             ("value_shape", (2, 128))):
            other = TableSpec(dataclasses.replace(spec.config,
                                                  **{field: value}))
            assert other.fold_lowering(rows, 3) == "xla", field
        nine = TableSpec(dataclasses.replace(
            spec.config, capacity=9 * 64, num_blocks=64))
        assert nine.block_size == 9 and nine.fold_lowering(8, 3) == "xla"
    with on_mesh(build_mesh(devices[:8], data=8)):  # replicas: XLA's
        assert spec.fold_lowering(rows, 3) == "xla"


@pytest.mark.parametrize("optimizer,vocab", [
    ("sgd", 64), ("momentum", 64), ("adam", 64),
    ("adam", 4100)])  # the embedding a piece of its own, the rest joined
def test_shipped_step_on_the_kernel_lowering(optimizer, vocab, as_tpu,
                                             devices):
    """The step as a one-chip TPU mesh lowers it — the optimizer inside
    ``harmony_fold_row_sections``, interpreted, the gradient handed to it
    in ``LeafRows``' pieces — against the step as the CPU mesh lowers it:
    every loss equal, the table equal to the CPU's contraction of a
    product into a sum (1 ulp of an element; on the chip kernel and XLA
    read equal to the last bit, PERF.md PR 30)."""
    from harmony_tpu.utils import platform

    mesh = build_mesh(devices[:1], data=1)
    tr = TransformerTrainer(dataclasses.replace(CFG, vocab_size=vocab),
                            row_width=128, step_size=3e-3,
                            optimizer=optimizer)
    assert tr.leaf_rows.record()["fold_pieces"] == (1 if vocab == 64 else 2)
    spec = TableSpec(tr.model_table_config())
    new, new_losses = _run_step(pull_all_step(spec, tr, mesh), tr, spec,
                                mesh, 4)
    assert as_tpu == [(spec.num_blocks * spec.block_size, 128)]
    platform.mesh_is_tpu = lambda mesh: False
    old, old_losses = _run_step(_whole_delta_step(spec, tr, mesh), tr, spec,
                                mesh, 4)
    assert len(as_tpu) == 1
    np.testing.assert_allclose(new_losses, old_losses, rtol=1e-6)
    np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-8)
    if tr.num_state_slots:
        assert tr.counter(new.reshape(-1, tr.row_width)) == 4


def test_worker_records_how_its_step_folds(as_tpu, devices):
    """Through ``WorkerTasklet``: the step it builds for a one-chip TPU
    mesh takes the kernel, and the tenant's ledger row says so."""
    from harmony_tpu.dolphin import TrainingDataProvider, WorkerTasklet
    from harmony_tpu.metrics.accounting import ledger
    from harmony_tpu.runtime import progcache

    mesh = build_mesh(devices[:1], data=1)
    tr = _lm("adam", row_width=128)
    table = DenseTable(TableSpec(tr.model_table_config()), mesh)
    ctx = TrainerContext(
        params=TrainerParams(num_epochs=2, num_mini_batches=2),
        model_table=table)
    data = TrainingDataProvider(
        (make_lm_data(8, 33, CFG.vocab_size, seed=5),), 2)
    progcache.clear()  # the step's key does not name its lowering
    try:
        losses = WorkerTasklet("j-fold", ctx, tr, data, mesh).run()["losses"]
    finally:
        progcache.clear()
    assert as_tpu and len(losses) == 2 and losses[1] < losses[0]
    layout = ledger().snapshot()["j-fold"]["table_layout"]
    assert layout["update_lowering"] == "row_ranges"
    assert layout["fold_lowering"] == "pallas_sections"
    from harmony_tpu.metrics.registry import get_registry

    assert 'harmony_table_fold_direct_row_share{job="j-fold"' in (
        get_registry().expose())


# -- (g) a leaf is a row range: models/pytree_trainer.py LeafRows -------------

class _Leaves:
    """A model of the given leaves."""

    def __init__(self, shapes):
        self.shapes = dict(shapes)

    def init(self, key):
        return {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
                for i, (name, shape) in enumerate(self.shapes.items())}


class LeavesTrainer(PyTreeTrainer):
    config_cls = dict

    def build_model(self, config):
        return _Leaves(config)

    def loss_on_batch(self, params, batch):
        return sum(jnp.mean((x - batch[0]) ** 2)
                   for x in jax.tree.leaves(params))


_LAYOUTS = {
    # name: (trainer, row width)
    "lm": (lambda: _lm("adam", row_width=128), 128),
    "vector_1": (lambda: VectorTrainer(1, row_width=128), 128),
    "vector_8191": (lambda: VectorTrainer(8191, row_width=1024), 1024),
    "vector_8192": (lambda: VectorTrainer(8192, row_width=1024), 1024),
    "vector_8193": (lambda: VectorTrainer(8193, row_width=1024), 1024),
    # gpt2's [50257, 768] embedding cut down: no whole number of rows, read
    # with its leading dimension grown to one ([1008, 24]: 189 rows)
    "embedding": (lambda: LeavesTrainer(
        {"emb": (1003, 24), "w": (16, 128), "b": (24,)}, row_width=128,
        optimizer="adam"), 128),
    # every form of a leaf: a scalar, whole tiles of row_width lanes (its
    # rows ARE the leaf), a grown leading dimension that would not fit
    # ([9, 129]: read as a vector), three dimensions, and one of no elements
    "forms": (lambda: LeavesTrainer(
        {"s": (), "stack": (3, 16, 128), "odd": (9, 129), "t": (5, 7, 48),
         "none": (0, 4)}, row_width=128, optimizer="momentum"), 128),
    # the fold's pieces (in flatten order): a5 and b5, leaves of PIECE_ROWS
    # rows or more, alone — a5 reads [5004, 96] = 3,753 rows of its 3,760,
    # so its last tile goes with the small leaves after it —, the runs
    # [a5's tile, a7, a8], [d0, d1] and [e0] each one piece, c5 alone
    "pieces": (lambda: LeavesTrainer(
        {"a5": (5003, 96), "a7": (24,), "a8": (40, 128), "b5": (8, 136, 128),
         "c5": (1024, 128), "d0": (96,), "d1": (3, 128), "d2": (0, 4),
         "e0": (2048, 128), "f0": (7,)}, row_width=128, optimizer="adam"),
        128),
}


@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_every_leaf_owns_whole_tiles(name):
    """Each leaf's first row is a multiple of TILE_ROWS, the ranges tile the
    section in flatten order with no gap and no overlap, and each holds its
    leaf with less than a tile to spare."""
    make, width = _LAYOUTS[name]
    tr = make()
    shapes = jax.tree.leaves(jax.eval_shape(
        lambda: tr.model.init(jax.random.PRNGKey(0))))
    at = 0
    assert len(tr.leaf_rows.leaves) == len(shapes)
    for f, leaf in zip(tr.leaf_rows.leaves, shapes):
        n = int(np.prod(leaf.shape))
        assert f.first == at and f.first % 8 == 0 and f.rows % 8 == 0
        assert f.shape == leaf.shape and f.size == n
        assert 0 <= f.rows * width - n < 8 * width
        # the shape its rows are read as: whole rows, inside its own tiles
        read = int(np.prod(f.read))
        assert read % width == 0 and n <= read <= f.rows * width
        at += f.rows
    assert tr.section_rows == tr.leaf_rows.rows == at
    slots = tr.num_state_slots
    assert tr.capacity == (1 + slots) * at + (8 if slots else 0)
    assert tr.num_params == sum(int(np.prod(x.shape)) for x in shapes)
    record = tr.leaf_rows.record()
    assert record["rows"] == at and record["leaves"] == len(shapes)
    assert record["pad_rows"] == at - sum(
        -(-int(np.prod(x.shape)) // width) for x in shapes)
    assert record["leaf_copies"] + record["leaf_bitcasts"] == len(shapes)
    assert record["leaf_bitcasts"] == {"forms": 1, "embedding": 1,
                                       "pieces": 4}.get(name, 0)


@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_pieces_tile_a_section_in_order(name):
    """``to_pieces``: whole tiles from ``piece_firsts[j]`` to the next
    piece's first, in order and with no gap, a large leaf alone and the
    small ones between two such joined; joined again they are ``to_rows``,
    which is the host's ``fill_rows``; and the record counts them."""
    from harmony_tpu.ops.sections import PIECE_ROWS

    tr = _LAYOUTS[name][0]()
    lr = tr.leaf_rows
    params = tr.model.init(jax.random.PRNGKey(3))
    pieces = jax.jit(lr.to_pieces)(params)
    ends = [*lr.piece_firsts[1:], lr.rows]
    assert lr.piece_firsts[0] == 0 and len(pieces) == len(lr.pieces)
    host = np.zeros((lr.rows, tr.row_width), np.float32)
    lr.fill_rows(host, jax.tree.leaves(params))
    direct = 0
    for p, run, first, end in zip(pieces, lr.pieces, lr.piece_firsts, ends):
        assert first % 8 == 0 and end > first and p.dtype == jnp.float32
        assert p.shape[0] >= end - first and p.shape[1] == tr.row_width
        np.testing.assert_array_equal(p[:end - first], host[first:end])
        assert sum(n for _, _, n in run) == end - first
        large = [lr.leaves[i].rows >= PIECE_ROWS and not at
                 for i, at, _ in run]
        assert large in ([True], [False] * len(run))
        direct += (end - first) * (len(run) == 1)
    np.testing.assert_array_equal(lr.join(pieces), host)
    np.testing.assert_array_equal(jax.jit(lr.to_rows)(params), host)
    record = lr.record()
    assert record["fold_pieces"] == len(pieces)
    assert record["direct_rows"] == direct <= lr.rows
    if name == "pieces":
        assert [[i for i, _, _ in run] for run in lr.pieces] == [
            [0], [0, 1, 2], [3], [4], [5, 6], [8], [9]]
        assert lr.pieces[0] == [(0, 0, 3752)] and pieces[0].shape[0] == 3753
        assert lr.pieces[1][0] == (0, 3752, 8)


def _benchmark_lm_jobs():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    jobs = {}
    for file in files:
        with open(os.path.join(root, file)) as f:
            job = json.load(f)["job"]
        if job["trainer"].endswith(":TransformerTrainer"):
            jobs[os.path.basename(file)[:-len(".json")]] = job["app_params"]
    return jobs


@pytest.mark.parametrize("config", sorted(_benchmark_lm_jobs()))
def test_benchmark_templates_come_back_in_pieces(config):
    """Each LM configuration of the benchmark at its real shapes (shapes
    only): ``to_rows`` is the concatenate of ``to_pieces``, the fold gets
    tens of operands, not hundreds, and over nine tenths of the gradient's
    rows are read from a leaf's own buffer."""
    from harmony_tpu.ops.sections import _BLOCK_ROWS

    tr = TransformerTrainer(**_benchmark_lm_jobs()[config])
    lr = tr.leaf_rows
    template = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    pieces = jax.eval_shape(lr.to_pieces, template)
    ends = [*lr.piece_firsts[1:], lr.rows]
    assert lr.piece_firsts[0] == 0
    for p, first, end in zip(pieces, lr.piece_firsts, ends):
        assert first % 8 == 0 and 0 < end - first <= p.shape[0] < end - first + 8
        assert p.shape[1] == tr.row_width and p.dtype == jnp.float32
    rows = jax.eval_shape(lr.to_rows, template)
    assert rows.shape == jax.eval_shape(lr.join, pieces).shape == (
        lr.rows, tr.row_width)
    record = lr.record()
    assert 1 <= record["fold_pieces"] == len(pieces) <= 128, record
    assert record["direct_rows"] / record["rows"] > 0.9, record
    # a piece short of a block costs the fold a block read for its few rows
    assert sum(end - first < _BLOCK_ROWS for first, end in zip(
        lr.piece_firsts, ends)) <= len(pieces) // 2


@pytest.mark.parametrize("shape,width,read,lead", [
    # gpt2's embedding: 37,692.75 rows of 1024; four rows of 768 are three
    ((50257, 768), 1024, (50260, 768), 50257),
    ((768,), 1024, (1024,), 768),               # a bias: one row of a tile
    ((), 128, (128,), 1),                       # a scalar
    ((16, 2048, 1024), 1024, (16, 2048, 1024), 16),  # its rows ARE the leaf
    # 1024 rows of 1025 would not fit 9,225 elements' 16 rows: a vector
    ((9, 1025), 1024, (10 * 1024,), 9 * 1025),
    ((0, 4), 128, (0, 4), 0),                   # no element, no row
])
def test_a_leaf_is_read_in_its_own_shape_where_that_fits(shape, width, read,
                                                         lead):
    """``_read_shape`` from the shape alone: the leading dimension grown to
    whole rows inside the leaf's own tiles, else the leaf as a vector."""
    n = int(np.prod(shape))
    rows = -(-n // (8 * width)) * 8
    assert _read_shape(shape, rows, width) == (read, lead)
    assert int(np.prod(read)) % width == 0
    assert n <= int(np.prod(read)) <= rows * width


@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_rows_and_leaves_round_trip_exactly(name):
    """leaves -> rows -> leaves and rows -> leaves -> rows, on the device
    and (``fill_rows``) on the host; the tails are zero."""
    tr = _LAYOUTS[name][0]()
    params = tr.model.init(jax.random.PRNGKey(3))
    rows = jax.jit(tr.leaf_rows.to_rows)(params)
    assert rows.shape == (tr.section_rows, tr.row_width)
    assert rows.dtype == jnp.float32
    back = jax.jit(tr.leaf_rows.to_leaves)(rows)
    jax.tree.map(np.testing.assert_array_equal, back, params)
    np.testing.assert_array_equal(tr.leaf_rows.to_rows(back), rows)
    host = np.zeros(rows.shape, np.float32)
    tr.leaf_rows.fill_rows(host, jax.tree.leaves(params))
    np.testing.assert_array_equal(host, rows)
    for f in tr.leaf_rows.leaves:  # what is no parameter is zero
        flat = host[f.first:f.first + f.rows].reshape(-1)
        assert not flat[f.size:].any()
        np.testing.assert_array_equal(
            flat[:f.size], np.asarray(
                jax.tree.leaves(params)[tr.leaf_rows.leaves.index(f)]
            ).reshape(-1))
    # a pulled model is read where its leaves lie, with no slice first
    model = jnp.concatenate([rows, jnp.ones(
        (tr.capacity - tr.section_rows + 8, tr.row_width))])
    jax.tree.map(np.testing.assert_array_equal, tr._params(model), params)


@pytest.mark.parametrize("name,optimizer", [
    ("lm", "adam"), ("lm", "momentum"), ("lm", "sgd"), ("embedding", "adam")])
def test_shipped_step_holds_the_bits_of_the_flat_layout(name, optimizer,
                                                        devices):
    """Four steps through ``pull_all_step`` on the leaf rows against the
    same steps of the frozen flat compute on the flat layout: every loss,
    and p, m and v leaf by leaf, to the last bit; pads still zero."""
    tr = (_lm(optimizer, row_width=128) if name == "lm"
          else _LAYOUTS[name][0]())
    assert tr.optimizer == optimizer
    mesh = build_mesh(devices[:1], data=1)
    spec = TableSpec(tr.model_table_config())
    assert update_lowering(spec, tr, mesh) == "row_ranges"
    if name == "lm":
        batch = (jnp.asarray(make_lm_data(4, 33, CFG.vocab_size, seed=7)),)
    else:
        batch = (jnp.float32(0.25),)
    hyper = {k: jnp.asarray(v, jnp.float32)
             for k, v in tr.hyperparams().items()}
    step = jax.jit(traced_on(mesh, pull_all_step(spec, tr, mesh)),
                   donate_argnums=0)

    def flat_body(table, batch, hyper):  # the same step, PR 25's layout
        model = _phase_boundary(table, replicate_on=mesh)
        delta, metrics = _phase_boundary(
            legacy_compute(tr, model, batch, hyper), replicate_on=mesh)
        return table + delta, metrics

    flat_step = jax.jit(traced_on(mesh, flat_body), donate_argnums=0)
    arr = jnp.asarray(_start(tr, tr.capacity)).reshape(spec.storage_shape)
    old = _flat_start(tr, legacy_capacity(tr))
    for _ in range(4):
        arr, metrics = step(arr, batch, hyper)
        old, old_metrics = flat_step(old, batch, hyper)
        assert float(metrics["loss"]) == float(old_metrics["loss"])
    new = np.asarray(arr).reshape(-1, tr.row_width)
    _assert_same_leaves(tr, new, old, flat_rows(tr))
    if tr.num_state_slots:
        assert tr.counter(new) == old[-1, 0] == 4


@pytest.mark.parametrize("optimizer,dtype", [
    ("sgd", jnp.float32), ("adam", jnp.float32), ("adam", jnp.bfloat16)])
def test_lowered_step_holds_no_flat_parameter_vector(optimizer, dtype,
                                                     devices):
    """The structural guard: in the step as lowered, no rank-1 array has
    ``num_params`` or a section's elements (nor any leaf's whole tiles) —
    rows go to leaves and back leaf by leaf, under bf16 activations too;
    and the parameter section is not sliced out of the pulled table as one
    array."""
    import re

    tr = TransformerTrainer(dataclasses.replace(CFG, dtype=dtype),
                            row_width=128, step_size=3e-3,
                            optimizer=optimizer)
    mesh = build_mesh(devices[:1], data=1)
    spec = TableSpec(tr.model_table_config())
    arr = jax.ShapeDtypeStruct(spec.storage_shape, jnp.float32)
    batch = (jax.ShapeDtypeStruct((4, 33), jnp.int32),)
    hyper = {k: jax.ShapeDtypeStruct((), jnp.float32)
             for k in tr.hyperparams()}
    text = jax.jit(traced_on(mesh, pull_all_step(spec, tr, mesh))).lower(
        arr, batch, hyper).as_text()
    rank1 = {int(n) for n in re.findall(r"tensor<(\d+)x(?:f32|bf16)>", text)}
    section = tr.section_rows * tr.row_width
    assert rank1, "the lowering names its vectors tensor<Nxf32>"
    assert not rank1 & {tr.num_params, section}, rank1
    assert max(rank1) < max(f.size for f in tr.leaf_rows.leaves), rank1
    if tr.num_state_slots:  # (sgd's table IS the section)
        assert f"tensor<{tr.section_rows}x{tr.row_width}xf32>" not in (
            text.split("stablehlo.optimization_barrier")[1])


def test_lowered_step_holds_no_section_of_gradient_rows(monkeypatch, devices):
    """The structural guard of the gradient's way back: the step as a
    one-chip TPU mesh lowers it (cross-lowered here, the fold a Mosaic
    custom call) builds no ``[section_rows, row_width]`` array — no
    concatenate, dynamic-update-slice or pad of that shape — between
    ``value_and_grad`` and the ONE ``harmony_fold_row_sections``, which
    takes the pieces as operands of its own."""
    import re

    from harmony_tpu.utils import platform

    monkeypatch.setattr(platform, "mesh_is_tpu", lambda mesh: True)
    tr = TransformerTrainer(
        dataclasses.replace(CFG, vocab_size=4100, dtype=jnp.bfloat16),
        row_width=128, step_size=3e-3, optimizer="adam")
    assert 1 < tr.leaf_rows.record()["fold_pieces"] < len(tr.leaf_rows.leaves)
    assert tr.leaf_rows.record()["direct_rows"]
    mesh = build_mesh(devices[:1], data=1)
    spec = TableSpec(tr.model_table_config())
    arr = jax.ShapeDtypeStruct(spec.storage_shape, jnp.float32)
    batch = (jax.ShapeDtypeStruct((4, 33), jnp.int32),)
    hyper = {k: jax.ShapeDtypeStruct((), jnp.float32)
             for k in tr.hyperparams()}
    text = jax.jit(traced_on(mesh, pull_all_step(spec, tr, mesh))).trace(
        arr, batch, hyper).lower(lowering_platforms=("tpu",)).as_text()
    folds = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "stablehlo.custom_call" in line]
    assert len(folds) == 1 and "harmony_fold_row_sections" in folds[0]
    # its operands: the walk, the rule's scalars, a piece each, the table
    operands = re.search(r"\((tensor<.*?)\) -> ", folds[0]).group(1)
    assert operands.count("tensor<") == 3 + tr.leaf_rows.record()[
        "fold_pieces"], operands
    section = f"tensor<{tr.section_rows}x{tr.row_width}xf32>"
    made = [line.strip()[:160] for line in text.splitlines()
            if f"-> {section}" in line or line.rstrip().endswith(
                f": {section}")]
    assert not made, made


# -- (h) a chain written before the leaves were row ranges -------------------

def _flat_chain_table(tr, which, steps=2):
    """A ``[p | m | v | counter]`` table in the flat layout ``which``
    after ``steps`` steps of the frozen flat compute."""
    capacity, stride = (fn(tr) for fn in _FLAT_CHAINS[which])
    trained, losses = _train(lambda *a: legacy_compute(tr, *a), tr,
                             _flat_start(tr, legacy_capacity(tr)), steps)
    n, slots = flat_rows(tr), tr.num_state_slots
    table = np.zeros((capacity, tr.row_width), np.float32)
    for i in range(1 + slots):
        table[i * stride:i * stride + n] = trained[i * n:(i + 1) * n]
    if slots:
        table[(1 + slots) * stride, 0] = trained[-1, 0]
    return table, stride, losses


@pytest.mark.parametrize("which", list(_FLAT_CHAINS))
@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_flat_chain_rows_convert_to_the_same_leaves(optimizer, which):
    tr = _lm(optimizer, row_width=128)
    table, stride, _ = _flat_chain_table(tr, which)
    new = tr.rows_from_flat_chain(table)
    assert new.shape == (tr.capacity, tr.row_width)
    _assert_same_leaves(tr, new, table, stride)
    if tr.num_state_slots:
        assert tr.counter(new) == 2
        assert new[(1 + tr.num_state_slots) * tr.section_rows:].sum() == 2


def test_capacity_cannot_tell_the_layouts_apart():
    """Two leaves of 1,000 elements in rows of 128: 16 rows either way,
    and the second leaf at element 1,000 or at row 8 — a flat table read
    as leaf rows (or the reverse) would be misread in silence, which is
    why a chain names its layout."""
    tr = LeavesTrainer({"a": (10, 100), "b": (1000,)}, row_width=128,
                       optimizer="sgd")
    assert tr.capacity == flat_capacity(tr) == 16
    params = tr.model.init(jax.random.PRNGKey(0))
    flat = np.asarray(flat_to_rows(tr, ravel_pytree(params)[0], 16))
    mine = np.asarray(tr.leaf_rows.to_rows(params))
    assert flat.shape == mine.shape and (flat != mine).any()
    np.testing.assert_array_equal(tr.rows_from_flat_chain(flat), mine)


def _write_chain(chkp_root, job, tr, table, epoch, layout=None):
    """One committed chain entry of ``job`` holding ``table``, as a job
    of the tree that wrote such a layout left it."""
    from harmony_tpu.checkpoint.manager import CheckpointManager
    from harmony_tpu.parallel import DevicePool
    from harmony_tpu.runtime import ETMaster

    master = ETMaster(DevicePool(jax.devices()[:1]))
    executors = [e.id for e in master.add_executors(1)]
    cfg = dataclasses.replace(
        tr.model_table_config(table_id=f"{job}:{tr.default_table_id}"),
        capacity=table.shape[0], num_blocks=table.shape[0] // 8
        if table.shape[0] % 8 == 0 else 1)
    handle = master.create_table(cfg, executors)
    handle.table.multi_put(list(range(table.shape[0])), table)
    meta = {"epoch": float(epoch)}
    if layout is not None:
        meta["layout"] = layout
    CheckpointManager.for_job(chkp_root, job).checkpoint(
        handle, commit=True, app_meta=meta)
    handle.drop()


_LM_APP = {"vocab_size": 64, "d_model": 32, "n_heads": 2, "n_layers": 2,
           "d_ff": 64, "max_seq": 64, "attn": "blockwise", "row_width": 128,
           "optimizer": "adam", "step_size": 3e-3}


def _resume(chkp_root, job, epochs):
    from harmony_tpu.config.params import JobConfig
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool

    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]),
                       chkp_root=chkp_root)
    server.start()
    try:
        cfg = JobConfig(
            job_id=job, app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=epochs, num_mini_batches=1,
                                 comm_probe_period=0, model_chkp_period=1,
                                 app_params=_LM_APP),
            num_workers=1,
            user={"data_fn": "harmony_tpu.models.transformer:make_lm_data",
                  "data_args": {"num_seqs": 4, "seq_len": 33,
                                "vocab_size": 64, "seed": 7},
                  "resume_from_chain": True})
        result = server.submit(cfg).result(timeout=300)
        layout = server._status()["tenants"][job]["table_layout"]
    finally:
        server.shutdown(timeout=60)
    return result, layout


@pytest.mark.parametrize("which", list(_FLAT_CHAINS))
def test_job_resumes_a_flat_chain_on_the_same_leaves(which, tmp_path):
    """A chain entry with no layout name (epoch 1, two flat steps in):
    the resumed job converts it, trains epochs 2 and 3 to the losses the
    frozen flat compute reads from the same state, records its own
    layout's name with the entries it writes — and those resume as they
    are."""
    from harmony_tpu.checkpoint.manager import CheckpointManager

    tr = TransformerTrainer(**_LM_APP)
    table, _, losses = _flat_chain_table(tr, which, steps=4)
    two_in, _, _ = _flat_chain_table(tr, which, steps=2)
    job = f"flat-{which}"
    _write_chain(str(tmp_path), job, tr, two_in, epoch=1)
    result, layout = _resume(str(tmp_path), job, epochs=4)
    got = next(iter(result["workers"].values()))["losses"]
    np.testing.assert_allclose(got, losses[2:], rtol=2e-6)
    assert layout["rows"] == tr.capacity and layout["tile_exact"] == 1
    assert layout["leaf_layout"] == tr.leaf_rows.record()
    mgr = CheckpointManager.for_job(str(tmp_path), job)
    metas = [mgr.info(cid).app_meta for cid in mgr.list_checkpoints()]
    assert sorted(m["epoch"] for m in metas) == [1.0, 2.0, 3.0]
    assert [m.get("layout") for m in sorted(
        metas, key=lambda m: m["epoch"])] == [None, "leaf_rows", "leaf_rows"]
    # the newest entry names this layout: restored as it is, one more epoch
    result, _ = _resume(str(tmp_path), job, epochs=5)
    assert len(next(iter(result["workers"].values()))["losses"]) == 1


def test_chain_in_an_unknown_layout_is_refused_with_both_named(tmp_path):
    tr = TransformerTrainer(**_LM_APP)
    _write_chain(str(tmp_path), "odd", tr,
                 np.zeros((tr.capacity, 128), np.float32), epoch=0,
                 layout="column_major")
    with pytest.raises(Exception) as e:
        _resume(str(tmp_path), "odd", epochs=2)
    assert "column_major" in str(e.value) and "leaf_rows" in str(e.value)


# -- the record that says it engaged -----------------------------------------

def test_table_layout_record():
    """An LM table reports tile_exact 1; a dense table of 9-row blocks 0."""
    from harmony_tpu.metrics.registry import get_registry

    tr = _lm("adam")
    lm = TableSpec(tr.model_table_config(table_id="lm"))
    row = table_layout.note("layout-lm", lm,
                            tr.section_stride(lm.config.capacity),
                            tr.leaf_rows.record())
    assert row == {"block_size": 8, "blocks": tr.capacity // 8,
                   "tail_rows": 0, "tile_exact": 1,
                   "section_stride": tr.section_rows, "rows": tr.capacity,
                   "leaf_layout": tr.leaf_rows.record()}
    nine = TableSpec(TableConfig(table_id="nine", capacity=65, num_blocks=8,
                                 value_shape=(128,)))
    assert table_layout.note("layout-nine", nine) == {
        "block_size": 9, "blocks": 8, "tail_rows": 7, "tile_exact": 0,
        "section_stride": None, "rows": 72}
    from harmony_tpu.metrics.accounting import ledger

    assert ledger().snapshot()["layout-lm"]["table_layout"] == row
    gauge = {labels: child.value
             for labels, child in table_layout._family().children()}
    assert gauge[("layout-lm", "lm")] == 1
    assert gauge[("layout-nine", "nine")] == 0
    assert "harmony_table_tile_exact{" in get_registry().expose()

    def exposed(name):
        return {line.split(",pid=")[0]: line.rsplit(" ", 1)[1]
                for line in get_registry().expose().splitlines()
                if line.startswith(name + "{")}

    blocks = exposed("harmony_table_blocks")
    assert float(blocks['harmony_table_blocks{job="layout-nine",'
                        'table="nine"']) == 8, blocks
    assert float(blocks['harmony_table_blocks{job="layout-lm",'
                        'table="lm"']) == tr.capacity // 8, blocks
    # how the step applies its update: a key of the same row, and a gauge
    table_layout.note_update("layout-lm", "lm", "row_ranges")
    record = _LAYOUTS["pieces"][0]().leaf_rows.record()
    table_layout.note_fold("layout-lm", "lm", "pallas_sections", record)
    table_layout.note_update("layout-nine", "nine", "whole_delta")
    assert ledger().snapshot()["layout-lm"]["table_layout"] == {
        **row, "update_lowering": "row_ranges",
        "fold_lowering": "pallas_sections"}
    assert ('harmony_table_fold_pallas_sections{job="layout-lm",table="lm",'
            in get_registry().expose())
    assert (ledger().snapshot()["layout-nine"]["table_layout"]
            ["update_lowering"]) == "whole_delta"
    # how much of the gradient that fold reads where the leaves left it
    assert 0 < record["direct_rows"] < record["rows"]
    assert record["fold_pieces"] == 7
    direct = exposed("harmony_table_fold_direct_row_share")
    key = 'harmony_table_fold_direct_row_share{job="layout-lm",table="lm"'
    assert float(direct[key]) == record["direct_rows"] / record["rows"]
    table_layout.note_fold("layout-lm", "lm", "xla", record)  # concatenated
    assert float(exposed("harmony_table_fold_direct_row_share")[key]) == 0
    ranges = exposed("harmony_table_update_row_ranges")
    assert ranges['harmony_table_update_row_ranges{job="layout-lm",'
                  'table="lm"'] == "1", ranges
    assert ranges['harmony_table_update_row_ranges{job="layout-nine",'
                  'table="nine"'] == "0", ranges


def test_status_carries_the_layout_of_a_submitted_lm_tenant():
    """SUBMIT -> table creation -> STATUS ``tenants.<job>.table_layout``."""
    from harmony_tpu.config.params import JobConfig
    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.parallel import DevicePool

    app = {"vocab_size": 64, "d_model": 32, "n_heads": 2, "n_layers": 1,
           "d_ff": 64, "max_seq": 64, "attn": "blockwise", "row_width": 128,
           "optimizer": "adam", "step_size": 1e-3}
    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    try:
        cfg = JobConfig(
            job_id="layout-job", app_type="dolphin",
            trainer="harmony_tpu.models.transformer:TransformerTrainer",
            params=TrainerParams(num_epochs=2, num_mini_batches=1,
                                 comm_probe_period=0, app_params=app),
            num_workers=1,
            user={"data_fn": "harmony_tpu.models.transformer:make_lm_data",
                  "data_args": {"num_seqs": 4, "seq_len": 33,
                                "vocab_size": 64, "seed": 1}})
        server.submit(cfg).result(timeout=300)
        row = server._status()["tenants"]["layout-job"]["table_layout"]
    finally:
        server.shutdown(timeout=60)
    tr = TransformerTrainer(**app)
    assert row == {"block_size": 8, "blocks": tr.capacity // 8,
                   "tail_rows": 0, "tile_exact": 1,
                   "section_stride": tr.section_rows, "rows": tr.capacity,
                   "update_lowering": "row_ranges", "fold_lowering": "xla",
                   "leaf_layout": tr.leaf_rows.record()}
    # where the leaves lie: 9 of them, none 128 wide, and what the tiles cost
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: tr.model.init(jax.random.PRNGKey(0))))
    used = sum(-(-int(np.prod(x.shape)) // 128) for x in leaves)
    assert row["leaf_layout"] == {
        "leaves": 9, "leaf_copies": 9, "leaf_bitcasts": 0,
        "pad_rows": tr.section_rows - used, "rows": tr.section_rows,
        "fold_pieces": 1, "direct_rows": 0}  # all small: one concatenate
    from harmony_tpu.metrics.registry import get_registry

    share = [line for line in get_registry().expose().splitlines()
             if line.startswith('harmony_table_leaf_pad_share{job="layout-job"')]
    assert len(share) == 1 and float(share[0].rsplit(" ", 1)[1]) == (
        (tr.section_rows - used) / tr.section_rows)
    direct = [line for line in get_registry().expose().splitlines() if
              line.startswith('harmony_table_fold_direct_row_share{job="layout-job"')]
    assert len(direct) == 1 and float(direct[0].rsplit(" ", 1)[1]) == 0
