"""The pytree model table's storage is its own row matrix.

Blocks are whole (8, 128) tiles with no tail block, every section starts a
tile, the counter has a block of its own, and ``compute`` runs the optimizer
on the sections as rows — against a frozen copy of the flat ``compute`` it
replaced (kept here only), to the last bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from harmony_tpu.config.params import TableConfig, TrainerParams
from harmony_tpu.dolphin import TrainerContext, optim
from harmony_tpu.metrics import table_layout
from harmony_tpu.models import (
    TransformerConfig,
    TransformerTrainer,
    make_lm_data,
)
from harmony_tpu.models.pytree_trainer import PyTreeTrainer
from harmony_tpu.table import DenseTable, TableSpec

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


# -- the flat compute this PR replaced, on the layout it ran on -------------

def legacy_capacity(tr):
    return tr.num_rows * (1 + tr.num_state_slots) + bool(tr.num_state_slots)


def legacy_compute(tr, model, batch, hyper):
    """PyTreeTrainer.compute as of PR 25: sections of ``num_rows`` rows
    flattened to ``[num_params]``, the optimizer on flat vectors, every
    delta padded and reshaped back, the counter in the last row."""
    def section(i):
        rows = model[i * tr.num_rows:(i + 1) * tr.num_rows]
        return rows.reshape(-1)[: tr.num_params]

    def to_rows(flat):
        return tr._to_rows(flat, tr.num_rows)

    pflat = section(0)
    (loss, extra), grads = jax.value_and_grad(
        tr.loss_and_metrics_on_batch, has_aux=True)(tr._unravel(pflat), batch)
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
    flat, _ = ravel_pytree(tr.model.init(jax.random.PRNGKey(tr.seed)))
    model = jnp.zeros((rows, tr.row_width), jnp.float32)
    return model.at[: tr.num_rows].set(tr._to_rows(flat, tr.num_rows))


def _train(compute, tr, rows, steps):
    batch = (jnp.asarray(make_lm_data(4, 33, CFG.vocab_size, seed=7)),)
    hyper = {k: jnp.asarray(v, jnp.float32)
             for k, v in tr.hyperparams().items()}
    step = jax.jit(lambda model: compute(model, batch, hyper))
    model, losses = _start(tr, rows), []
    for _ in range(steps):
        delta, metrics = step(model)
        model = model + delta
        losses.append(float(metrics["loss"]))
    return np.asarray(model), losses


# -- (a) the schema ----------------------------------------------------------

@pytest.mark.parametrize("num_blocks", [0, 3])
@pytest.mark.parametrize("num_params", [16 * 128, 9 * 128 + 5, 14 * 128 + 1])
@pytest.mark.parametrize("slots", [0, 1, 2])
def test_storage_is_the_row_matrix(slots, num_params, num_blocks):
    tr = VectorTrainer(num_params, row_width=128, optimizer=BY_SLOTS[slots])
    assert tr.num_rows % 8 == {2048: 0, 1157: 2, 1793: 7}[num_params]
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
    assert stride % 8 == 0 and stride >= tr.num_rows
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
    tr = _lm(optimizer, row_width=128)  # 84 rows: sections of 88
    assert tr.num_rows % 8 and tr.num_params % tr.row_width
    new, new_losses = _train(tr.compute, tr, tr.capacity, steps)
    old, old_losses = _train(lambda *a: legacy_compute(tr, *a), tr,
                             legacy_capacity(tr), steps)
    assert new_losses == old_losses
    n, slots = tr.num_params, tr.num_state_slots
    moved = 0
    for i in range(1 + slots):
        got = tr.section(new, i).reshape(-1)
        want = old[i * tr.num_rows:(i + 1) * tr.num_rows].reshape(-1)[:n]
        np.testing.assert_array_equal(got[:n], want)
        assert not got[n:].any()  # pad lanes and pad rows stay zero
        moved += bool(np.abs(want).sum())
    assert moved == 1 + slots
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
    assert not np.asarray(model)[tr.num_rows:].any()
    batch = (jnp.asarray(make_lm_data(4, 33, CFG.vocab_size, seed=3)),)
    np.testing.assert_array_equal(
        tr.evaluate(model, batch)["loss"], tr.loss_on_batch(params, batch))


# -- (d) a table from an older chain -----------------------------------------

@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adam"])
def test_older_layout_trains_to_the_same_bits(optimizer):
    """``[params | m | v | counter row]`` at a stride of ``num_rows``: the
    layout is read off the pulled model's row count."""
    tr = _lm(optimizer, row_width=128)
    rows = legacy_capacity(tr)
    assert tr.section_stride(rows) == tr.num_rows != tr.section_rows
    new, new_losses = _train(tr.compute, tr, rows, 2)
    old, old_losses = _train(lambda *a: legacy_compute(tr, *a), tr, rows, 2)
    assert new_losses == old_losses
    np.testing.assert_array_equal(new, old)
    if tr.num_state_slots:
        assert tr.counter(new) == 2


@pytest.mark.parametrize("off", [-1, 1, 7])
def test_unknown_row_count_is_refused_by_name(off):
    tr = _lm("adam", row_width=128)
    rows = legacy_capacity(tr) + off
    with pytest.raises(ValueError) as e:
        tr.section(np.zeros((rows, 128), np.float32), 1)
    for n in (rows, tr.capacity, legacy_capacity(tr)):
        assert str(n) in str(e.value)


# -- the record that says it engaged -----------------------------------------

def test_table_layout_record():
    """An LM table reports tile_exact 1; a dense table of 9-row blocks 0."""
    from harmony_tpu.metrics.registry import get_registry

    tr = _lm("adam")
    lm = TableSpec(tr.model_table_config(table_id="lm"))
    row = table_layout.note("layout-lm", lm,
                            tr.section_stride(lm.config.capacity))
    assert row == {"block_size": 8, "tail_rows": 0, "tile_exact": 1,
                   "section_stride": tr.section_rows, "rows": tr.capacity}
    nine = TableSpec(TableConfig(table_id="nine", capacity=65, num_blocks=8,
                                 value_shape=(128,)))
    assert table_layout.note("layout-nine", nine) == {
        "block_size": 9, "tail_rows": 7, "tile_exact": 0,
        "section_stride": None, "rows": 72}
    from harmony_tpu.metrics.accounting import ledger

    assert ledger().snapshot()["layout-lm"]["table_layout"] == row
    gauge = {labels: child.value
             for labels, child in table_layout._family().children()}
    assert gauge[("layout-lm", "lm")] == 1
    assert gauge[("layout-nine", "nine")] == 0
    assert "harmony_table_tile_exact{" in get_registry().expose()


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
    assert row == {"block_size": 8, "tail_rows": 0, "tile_exact": 1,
                   "section_stride": tr.section_rows, "rows": tr.capacity}
