"""Transformer LM: forward correctness, SP step vs single-device math,
and end-to-end training through the framework's worker loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.models import (
    TransformerConfig,
    TransformerLM,
    TransformerTrainer,
    make_lm_data,
)
from harmony_tpu.models.transformer import make_sp_train_step
from harmony_tpu.parallel import build_mesh

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_seq=64, attn="blockwise")


def test_forward_shapes_and_finite():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size))
    logits = model.apply(params, tokens)
    assert logits.shape == (4, 32, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_loss_decreases_plain_sgd():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(make_lm_data(16, 33, CFG.vocab_size))

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(model.loss)(p, tokens)
        return jax.tree.map(lambda a, b: a - 0.5 * b, p, g), loss

    losses = []
    for _ in range(20):
        params, loss = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, losses


def test_sp_step_matches_single_device(devices):
    """The sharded (data=2, seq=4) step computes the same loss and the same
    updated params as unsharded full-batch math."""
    mesh = build_mesh(devices, data=2, seq=4, model=1)
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size, seed=2))

    # donate=False: this parity test reuses the pre-step params below
    sp_step = make_sp_train_step(model, mesh, learning_rate=0.1, donate=False)
    new_sp, loss_sp = sp_step(params, tokens)

    def ref_loss(p):
        logits = model.apply(p, tokens)
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mask = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:], jnp.float32),
             jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (-ll * mask).sum() / mask.sum()

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)
    new_ref = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads_ref)

    np.testing.assert_allclose(float(loss_sp), float(loss_ref), atol=1e-5)
    for a, b in zip(jax.tree.leaves(new_sp), jax.tree.leaves(new_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_sp_training_loop_learns(devices):
    mesh = build_mesh(devices, data=1, seq=8, model=1)
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(3))
    tokens = jnp.asarray(make_lm_data(8, 64, CFG.vocab_size, seed=4))
    step = make_sp_train_step(model, mesh, learning_rate=0.5)
    first = last = None
    for i in range(15):
        params, loss = step(params, tokens)
        first = float(loss) if first is None else first
        last = float(loss)
    assert last < first - 0.3, (first, last)


def test_trainer_spi_through_worker_loop(mesh8):
    """The LM trains through WorkerTasklet + DenseTable like any app."""
    from harmony_tpu.config.params import TrainerParams
    from harmony_tpu.dolphin import TrainerContext, TrainingDataProvider, WorkerTasklet
    from harmony_tpu.table import DenseTable, TableSpec

    trainer = TransformerTrainer(CFG, row_width=256, step_size=0.5)
    table = DenseTable(TableSpec(trainer.model_table_config()), mesh8)
    tokens = make_lm_data(16, 33, CFG.vocab_size, seed=5)
    params = TrainerParams(num_epochs=4, num_mini_batches=2)
    ctx = TrainerContext(params=params, model_table=table)
    worker = WorkerTasklet(
        "lm", ctx, trainer, TrainingDataProvider([tokens], 2), mesh8
    )
    result = worker.run()
    losses = result["losses"]
    assert losses[-1] < losses[0], losses
    ev = worker.evaluate((tokens,))
    assert np.isfinite(float(ev["loss"]))


class TestStatefulOptimizers:
    def _train(self, optimizer, mesh, lr, epochs=5):
        from harmony_tpu.config.params import TrainerParams
        from harmony_tpu.dolphin import TrainerContext, TrainingDataProvider, WorkerTasklet
        from harmony_tpu.table import DenseTable, TableSpec

        trainer = TransformerTrainer(CFG, row_width=256, step_size=lr,
                                     optimizer=optimizer)
        table = DenseTable(TableSpec(trainer.model_table_config()), mesh)
        tokens = make_lm_data(16, 33, CFG.vocab_size, seed=7)
        params = TrainerParams(num_epochs=epochs, num_mini_batches=2)
        worker = WorkerTasklet(
            f"lm-{optimizer}", TrainerContext(params=params, model_table=table),
            trainer, TrainingDataProvider([tokens], 2), mesh,
        )
        return trainer, table, worker.run()

    def test_adam_learns_and_tracks_steps(self, mesh8):
        trainer, table, result = self._train("adam", mesh8, lr=3e-3)
        assert result["losses"][-1] < result["losses"][0], result["losses"]
        rows = np.asarray(table.pull_array())
        # counter row tallies exactly epochs x batches pushes
        assert trainer.counter(rows) == 5 * 2
        # second-moment section is strictly non-negative and non-trivial
        v = trainer.section(rows, 2).reshape(-1)
        assert (v >= -1e-12).all() and float(np.abs(v).sum()) > 0

    def test_momentum_learns(self, mesh8):
        _, _, result = self._train("momentum", mesh8, lr=0.05)
        assert result["losses"][-1] < result["losses"][0], result["losses"]

    def test_unknown_optimizer_rejected(self):
        import pytest as _pytest

        with _pytest.raises(ValueError, match="unknown optimizer"):
            TransformerTrainer(CFG, optimizer="lion")

    def test_optimizer_state_survives_checkpoint_restore(self, mesh8, tmp_path, devices):
        """Adam state rides the table: checkpoint -> restore -> keep
        training, counter and moments intact."""
        from harmony_tpu.checkpoint.manager import CheckpointManager
        from harmony_tpu.parallel import DevicePool
        from harmony_tpu.runtime.master import ETMaster

        trainer, table, _ = self._train("adam", mesh8, lr=3e-3, epochs=2)
        master = ETMaster(DevicePool(devices))
        execs = [e.id for e in master.add_executors(4)]
        handle = master.create_table(
            trainer.model_table_config(table_id="lm-chk"), execs)
        handle.table.commit(table.array)  # hand the trained state over
        mgr = CheckpointManager(str(tmp_path / "t"), str(tmp_path / "c"))
        cid = mgr.checkpoint(handle, commit=True)
        restored = mgr.restore(master, cid, execs[:2], table_id="lm-chk-2")
        rows = np.asarray(restored.table.pull_array())
        # step counter survived the round trip
        assert trainer.counter(rows) == 2 * 2


def test_parallel_step_matches_single_device(devices):
    """The full 3-axis step (data=2, seq=2, model=2: ring attention + Megatron
    column/row TP) computes the same loss and updated params as unsharded
    full-batch math — including replicated-leaf grads, which must be psum'd
    over the model axis through the forward psums."""
    from harmony_tpu.models.transformer import (
        make_parallel_train_step,
        to_tp_params,
    )

    mesh = build_mesh(devices, data=2, seq=2, model=2)
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(3))
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size, seed=4))

    # donate=False: this parity test reuses the pre-step params below
    step, shard_params = make_parallel_train_step(model, mesh, learning_rate=0.1,
                                                  donate=False)
    tp_params = shard_params(params)
    new_tp, loss_tp = step(tp_params, tokens)

    def ref_loss(p):
        logits = model.apply(p, tokens)
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mask = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:], jnp.float32),
             jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (-ll * mask).sum() / mask.sum()

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)
    new_ref = to_tp_params(
        jax.tree.map(lambda p, g: p - 0.1 * g, params, grads_ref)
    )

    np.testing.assert_allclose(float(loss_tp), float(loss_ref), atol=1e-5)
    flat_tp = jax.tree_util.tree_flatten_with_path(new_tp)[0]
    flat_ref = dict(jax.tree_util.tree_flatten_with_path(new_ref)[0])
    for path, a in flat_tp:
        b = flat_ref[path]
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4,
            err_msg=jax.tree_util.keystr(path),
        )


def test_parallel_step_rejects_bad_tp(devices):
    from harmony_tpu.models.transformer import make_parallel_train_step

    mesh = build_mesh(devices, data=1, seq=1, model=8)
    model = TransformerLM(CFG)  # n_heads=2 < tp=8
    with pytest.raises(ValueError):
        make_parallel_train_step(model, mesh)


def test_sp_step_a2a_matches_ring(devices):
    """The a2a sequence-parallel tier trains identically to ring (both are
    exact attention; same grads to f32 tolerance)."""
    import dataclasses

    mesh = build_mesh(devices, data=4, seq=2, model=1)
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size, seed=6))
    results = {}
    for impl in ("ring", "a2a"):
        cfg = dataclasses.replace(CFG, sp_attn=impl)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(7))
        step = make_sp_train_step(model, mesh, learning_rate=0.1)
        new_p, loss = step(params, tokens)
        results[impl] = (new_p, float(loss))
    np.testing.assert_allclose(results["ring"][1], results["a2a"][1], atol=1e-5)
    for a, b in zip(jax.tree.leaves(results["ring"][0]),
                    jax.tree.leaves(results["a2a"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_parallel_step_a2a_tier(devices):
    """sp_attn='a2a' is honored by the 3-axis step (heads-per-TP-shard must
    divide the seq axis) and trains to the same result as ring."""
    import dataclasses

    from harmony_tpu.models.transformer import make_parallel_train_step

    cfg4 = dataclasses.replace(CFG, n_heads=4, sp_attn="a2a")
    mesh = build_mesh(devices, data=2, seq=2, model=2)
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size, seed=8))
    outs = {}
    for impl in ("ring", "a2a"):
        cfg = dataclasses.replace(cfg4, sp_attn=impl)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(9))
        step, shard = make_parallel_train_step(model, mesh, learning_rate=0.1)
        new_p, loss = step(shard(params), tokens)
        outs[impl] = float(loss)
    np.testing.assert_allclose(outs["ring"], outs["a2a"], atol=1e-5)
    # indivisible: 2 heads / tp=2 -> 1 head per shard, seq axis 2
    bad = dataclasses.replace(CFG, sp_attn="a2a")
    with pytest.raises(ValueError, match="divisible"):
        make_parallel_train_step(TransformerLM(bad), mesh)


def test_config_rejects_unknown_sp_attn():
    import dataclasses

    with pytest.raises(ValueError, match="sp_attn"):
        dataclasses.replace(CFG, sp_attn="alltoall")


def test_remat_same_loss_and_grads():
    """remat=True changes memory scheduling, not math: identical loss and
    gradients to the plain forward."""
    import dataclasses

    model = TransformerLM(CFG)
    model_r = TransformerLM(dataclasses.replace(CFG, remat=True))
    params = model.init(jax.random.PRNGKey(11))
    tokens = jnp.asarray(make_lm_data(4, 32, CFG.vocab_size, seed=12))
    l0, g0 = jax.value_and_grad(model.loss)(params, tokens)
    l1, g1 = jax.value_and_grad(model_r.loss)(params, tokens)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_load_text_tokens_and_trains(tmp_path):
    """Real-file LM data: byte-level tokenization feeds the same training
    path as synthetic data, end to end through a jobserver job."""
    import jax

    from harmony_tpu.config.params import JobConfig, TrainerParams
    from harmony_tpu.jobserver import JobServer
    from harmony_tpu.models.transformer import load_text_tokens
    from harmony_tpu.parallel import DevicePool

    p = tmp_path / "corpus.txt"
    p.write_text("the quick brown fox jumps over the lazy dog. " * 200)
    toks = load_text_tokens(str(p), seq_len=33)
    assert toks.dtype == np.int32 and toks.shape[1] == 33
    assert toks.min() >= 0 and toks.max() < 256

    with pytest.raises(ValueError, match="windows"):
        load_text_tokens(str(p), seq_len=33, num_seqs=10**6)

    server = JobServer(1, device_pool=DevicePool(jax.devices()[:1]))
    server.start()
    cfg = JobConfig(
        job_id="lm-file", app_type="dolphin",
        trainer="harmony_tpu.models.transformer:TransformerTrainer",
        params=TrainerParams(
            num_epochs=4, num_mini_batches=2,
            app_params={"vocab_size": 256, "d_model": 32, "n_heads": 2,
                        "n_layers": 1, "d_ff": 64, "max_seq": 32,
                        "step_size": 0.3},
        ),
        num_workers=1,
        user={"data_fn": "harmony_tpu.models.transformer:load_text_tokens",
              "data_args": {"path": str(p), "seq_len": 33, "num_seqs": 64}},
    )
    result = server.submit(cfg).result(timeout=300)
    server.shutdown(timeout=60)
    losses = result["workers"]["lm-file/w0"]["losses"]
    assert losses[-1] < losses[0], losses  # real text is learnable


def test_init_traced_abstractly_has_inits_layout():
    """``jax.eval_shape`` of init (what the graft entry point fills in numpy:
    no jax op, no backend) has init's tree structure, shapes and dtypes
    exactly — for dense AND MoE configs."""
    import jax
    import jax.numpy as jnp

    from harmony_tpu.models import TransformerConfig, TransformerLM

    for kw in ({}, {"moe_experts": 2, "moe_every": 2}):
        cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=16, **kw)
        model = TransformerLM(cfg)
        a = model.init(jax.random.PRNGKey(0))
        b = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        assert (jax.tree_util.tree_structure(a)
                == jax.tree_util.tree_structure(b))
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert la.shape == lb.shape and la.dtype == lb.dtype


def test_a_field_the_side_steps_do_not_read_is_refused_without_an_edit():
    """``require_classic_block`` lists what the GPT-2-era side steps read,
    not what they do not: a field a later configuration adds is refused the
    day it holds anything but its default, and named."""
    import dataclasses

    from harmony_tpu.models import TransformerConfig

    @dataclasses.dataclass(frozen=True)
    class Later(TransformerConfig):
        a_later_field: int = 0

    read = dict(vocab_size=32, d_model=16, n_heads=2, d_ff=32, max_seq=16,
                attn="blockwise", sp_attn="a2a", remat=True, moe_experts=2,
                norm_eps=1e-5, embed_std=1.0)
    Later(**read).require_classic_block("make_sp_train_step")
    with pytest.raises(ValueError, match="make_sp_train_step runs the "
                       "GPT-2-era block only .* reads no a_later_field:"):
        Later(**read, a_later_field=1).require_classic_block(
            "make_sp_train_step")
