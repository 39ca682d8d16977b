"""Generic PS-table trainer for functional pytree models.

Any model exposing ``init(key) -> params`` and a pure loss over a batch
trains through the framework's elastic-table substrate with this one
Trainer: the flattened params pytree lives in a range-partitioned
DenseTable (rows of ``row_width`` f32), pull="all" re-assembles it each
batch, and the push folds the update through the table's additive fold —
so checkpointing, live migration and multi-tenancy apply to ANY model
family for free. The LM (models/transformer.py TransformerTrainer) and
ViT (models/vit.py ViTTrainer) are thin subclasses binding the model and
its batch->loss signature.

Stateful optimizers (harmony_tpu.dolphin.optim): momentum/Adam state
occupies extra row sections of the SAME table — ``[params | m | v |
counter row]`` — so optimizer state checkpoints, reshards and migrates
with the parameters (the reference has no shared-optimizer-state
mechanism at all; its trainers are plain SGD).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from harmony_tpu.config.params import TableConfig
from harmony_tpu.dolphin.trainer import Trainer, TrainerContext


class PyTreeTrainer(Trainer):
    pull_mode = "all"

    #: default table id; subclasses override
    default_table_id = "model"
    #: model config dataclass; subclasses set it and implement build_model,
    #: then inherit the config-vs-flat-kwargs constructor (flat kwargs keep
    #: JobConfig.app_params JSON-serializable for the TCP submit path)
    config_cls: Any = None

    def build_model(self, config: Any) -> Any:
        raise NotImplementedError

    def __init__(
        self,
        config: Any = None,
        row_width: int = 1024,
        step_size: float = 0.1,
        seed: int = 0,
        optimizer: str = "sgd",
        beta2: "float | None" = None,
        **config_kwargs,
    ) -> None:
        from harmony_tpu.dolphin import optim

        if config is None:
            config = self.config_cls(**config_kwargs)
        elif config_kwargs:
            raise TypeError("pass either config= or flat config kwargs, not both")
        self.config = config
        self.model = self.build_model(config)
        self.row_width = row_width
        self.step_size = step_size
        self.seed = seed
        self.optimizer = optimizer
        #: Adam's second-moment decay where a model's recipe departs from
        #: the optimizer's 0.999 (dolphin/optim.py reads hyper["beta2"])
        self.beta2 = beta2
        self.num_state_slots = optim.num_slots(optimizer)  # validates name
        template = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0))
        )
        flat, self._unravel = ravel_pytree(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)
        )
        self.num_params = flat.shape[0]
        self.num_rows = -(-self.num_params // row_width)

    # -- model binding (subclass hooks) -----------------------------------

    def loss_on_batch(self, params, batch) -> jnp.ndarray:
        """Pure scalar loss for one batch; subclasses bind the model's
        batch signature here."""
        raise NotImplementedError

    def loss_and_metrics_on_batch(self, params, batch):
        """``(loss, {name: array})``: what a step reports beside its loss
        (scalars, or vectors the drain hands to ``observe_step_vectors``).
        Default: nothing."""
        return self.loss_on_batch(params, batch), {}

    def eval_metrics(self, params, batch) -> Dict[str, jnp.ndarray]:
        return {"loss": self.loss_on_batch(params, batch)}

    # -- table schema -----------------------------------------------------

    @property
    def capacity(self) -> int:
        # param rows + one section per state slot + the step-counter row
        extra = 1 if self.num_state_slots else 0
        return self.num_rows * (1 + self.num_state_slots) + extra

    def model_table_config(
        self, table_id: str = "", num_blocks: int = 0
    ) -> TableConfig:
        return TableConfig(
            table_id=table_id or self.default_table_id,
            capacity=self.capacity,
            value_shape=(self.row_width,),
            num_blocks=num_blocks or max(self.capacity // 8, 1),
            is_ordered=True,
            update_fn="add",
        )

    # -- lifecycle --------------------------------------------------------

    def init_global_settings(self, ctx: TrainerContext) -> None:
        params = self.model.init(jax.random.PRNGKey(self.seed))
        flat, _ = ravel_pytree(params)
        ctx.model_table.multi_put(
            list(range(self.num_rows)), np.asarray(self._to_rows(flat))
        )
        # m/v sections and the counter row start (and stay, until the first
        # push) at the table's init value 0.

    # -- pure parts -------------------------------------------------------

    def _to_rows(self, flat: jnp.ndarray) -> jnp.ndarray:
        pad = self.num_rows * self.row_width - self.num_params
        return jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)]
        ).reshape(self.num_rows, self.row_width)

    def _section(self, model: jnp.ndarray, i: int) -> jnp.ndarray:
        """Flat [num_params] view of row section i (0=params, 1=m, 2=v)."""
        rows = model[i * self.num_rows:(i + 1) * self.num_rows]
        return rows.reshape(-1)[: self.num_params]

    def hyperparams(self) -> Dict[str, float]:
        if self.beta2 is None:
            return {"lr": self.step_size}
        return {"lr": self.step_size, "beta2": self.beta2}

    def compute(self, model, batch, hyper):
        from harmony_tpu.dolphin import optim

        pflat = self._section(model, 0)
        params = self._unravel(pflat)
        (loss, extra), grads = jax.value_and_grad(
            self.loss_and_metrics_on_batch, has_aux=True)(params, batch)
        gflat, _ = ravel_pytree(grads)
        slots = self.num_state_slots
        m = self._section(model, 1) if slots >= 1 else jnp.zeros_like(pflat)
        v = self._section(model, 2) if slots >= 2 else jnp.zeros_like(pflat)
        t = model[-1, 0] + 1.0 if slots else jnp.asarray(1.0)
        new_p, new_m, new_v = optim.apply(
            self.optimizer, pflat, gflat, m, v, t, hyper
        )
        sections = [self._to_rows(new_p - pflat)]
        if slots >= 1:
            sections.append(self._to_rows(new_m - m))
        if slots >= 2:
            sections.append(self._to_rows(new_v - v))
        delta = jnp.concatenate(sections)
        if slots:
            counter = jnp.zeros((1, self.row_width), delta.dtype).at[0, 0].set(1.0)
            delta = jnp.concatenate([delta, counter])
        return delta, {"loss": loss, **extra}

    def evaluate(self, model, batch) -> Dict[str, jnp.ndarray]:
        params = self._unravel(self._section(model, 0))
        return self.eval_metrics(params, batch)
