"""Importable helpers for jobserver tests (resolve_symbol needs real
module-level symbols, mirroring how users ship trainer classes)."""
from __future__ import annotations

from harmony_tpu.apps.addvector import AddIntegerTrainer, AddVectorTrainer


class CrashOnW0Trainer(AddVectorTrainer):
    """Fails during init on worker w0 only — exercises uneven worker death
    (the surviving workers must not deadlock in the TaskUnit quorum)."""

    def init_global_settings(self, ctx) -> None:
        if ctx.worker_id.endswith("/w0"):
            raise RuntimeError("synthetic failure on w0")


def slow_data(n: int = 32):
    """Blocks long enough to wedge a job past any test shutdown timeout."""
    import time

    import numpy as np

    time.sleep(15)
    return (np.ones(n, np.float32),)


class ExplodingTrainer(AddIntegerTrainer):
    """Dies during global init on EVERY worker — the §5.3 failure-injection
    stand-in for multi-tenant isolation tests."""

    def init_global_settings(self, ctx) -> None:
        raise RuntimeError("injected failure")


class LaggyMLRTrainer:
    """MLR with a host-side per-epoch sleep on ONE worker — the straggler
    for SSP gating tests (the sleep is pure host delay: identical on every
    pod process, no device dispatch)."""

    def __new__(cls, lag_sec: float = 0.0, lag_worker: str = "/w1", **kw):
        from harmony_tpu.apps.mlr import MLRTrainer

        class _Laggy(MLRTrainer):
            def on_epoch_finished(self, ctx, epoch) -> None:
                import time

                if lag_sec and ctx.worker_id.endswith(lag_worker):
                    time.sleep(lag_sec)
                super().on_epoch_finished(ctx, epoch)

        return _Laggy(**kw)


class MoveOncePodOptimizer:
    """Optimizer SPI impl that emits ONE move-only plan (drain half of
    executor-4 onto executor-0) as soon as worker metrics exist — the
    canned optimizer for pod elasticity tests (the SampleOptimizers
    analogue for the pod plan channel)."""

    def __init__(self) -> None:
        self.fired = False

    def optimize(self, params, num_available_evaluators):
        from harmony_tpu.optimizer.api import DolphinPlan, TransferStep

        if self.fired or not params.worker_metrics:
            return DolphinPlan()
        src = "executor-4"
        n = params.block_counts.get(src, 0)
        if not n:
            return DolphinPlan()
        self.fired = True
        return DolphinPlan(transfer_steps=[
            TransferStep(params.table_id, src, "executor-0", n)
        ])


from harmony_tpu.models.transformer import (  # noqa: E402
    TransformerLM, TransformerTrainer)


class _SeededBiasLM(TransformerLM):
    def init(self, rng):
        import jax

        params = super().init(rng)
        for i in self.config.moe_layers():
            moe = params["layers"][i]["moe"]
            moe["bias"] = 0.2 * jax.random.normal(
                jax.random.fold_in(rng, 7), moe["bias"].shape)
        return params


class SeededBiasLMTrainer(TransformerTrainer):
    """The LM trainer with a NON-ZERO seeded router selection bias,
    reporting the bias rows beside each step's loss: what the optimizer does
    to a leaf whose gradient is zero shows in ``seen[job]`` (``[steps,
    expert layers, experts]`` per drain)."""

    seen: dict = {}

    def build_model(self, config):
        return _SeededBiasLM(config)

    def loss_and_metrics_on_batch(self, params, batch):
        import jax.numpy as jnp

        loss, m = super().loss_and_metrics_on_batch(params, batch)
        return loss, {**m, "moe_bias": jnp.stack(
            [params["layers"][i]["moe"]["bias"]
             for i in self.config.moe_layers()])}

    def observe_step_vectors(self, job_id, vectors):
        import numpy as np

        self.seen.setdefault(job_id, []).append(
            np.asarray(vectors["moe_bias"]))
        super().observe_step_vectors(job_id, vectors)
