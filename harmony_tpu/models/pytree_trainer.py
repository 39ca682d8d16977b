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
counter block]`` — so optimizer state checkpoints, reshards and migrates
with the parameters (the reference has no shared-optimizer-state
mechanism at all; its trainers are plain SGD).

The table's storage IS the row matrix: every section starts on a multiple
of 8 rows, the counter has an 8-row block of its own, and blocks are whole
(8, 128) tiles with no tail block, so ``pull_all``'s reshape is a bitcast
and ``push_all`` pads nothing. A step changes layout twice, where the model
needs it (parameters rows -> leaves, gradients leaves -> rows); the
optimizer runs on row sections. Pad rows and pad lanes hold zeros and stay
zero under every optimizer (g = m = v = 0 -> update 0).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from harmony_tpu.config.params import TILE_ROWS, TableConfig
from harmony_tpu.dolphin.trainer import Trainer, TrainerContext
from harmony_tpu.tracing.stepscopes import step_scope


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
        #: rows from one section's start to the next (num_rows rounded up
        #: to whole tiles)
        self.section_rows = -(-self.num_rows // TILE_ROWS) * TILE_ROWS

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
        # one tile-aligned section per [params | state slot], then the
        # step counter's own block
        slots = self.num_state_slots
        return ((1 + slots) * self.section_rows
                + (TILE_ROWS if slots else 0))

    def model_table_config(
        self, table_id: str = "", num_blocks: int = 0
    ) -> TableConfig:
        capacity = self.capacity
        if num_blocks:
            # a caller's block count: blocks of whole tiles, no tail block
            block = -(-capacity // (num_blocks * TILE_ROWS)) * TILE_ROWS
            capacity = num_blocks * block
        return TableConfig(
            table_id=table_id or self.default_table_id,
            capacity=capacity,
            value_shape=(self.row_width,),
            num_blocks=num_blocks or capacity // TILE_ROWS,
            is_ordered=True,
            update_fn="add",
        )

    def section_stride(self, capacity: int) -> int:
        """Rows from one section's start to the next in a table of
        ``capacity`` rows. A table restored from a chain written before
        sections were tile-aligned (``[params | m | v | counter row]``,
        ``(1 + slots) * num_rows + 1`` rows) keeps its stride of
        ``num_rows``; any other row count is refused, never misread."""
        slots = self.num_state_slots
        if capacity >= self.capacity:
            return self.section_rows
        legacy = (1 + slots) * self.num_rows + (1 if slots else 0)
        if capacity == legacy:
            return self.num_rows
        raise ValueError(
            f"{type(self).__name__}: a model table of {capacity} rows fits "
            f"neither this trainer's layout (capacity {self.capacity}: "
            f"{1 + slots} sections of {self.section_rows} rows"
            f"{' + the counter block' if slots else ''}) nor the unaligned "
            f"one of older chains (capacity {legacy})")

    def section(self, model, i: int):
        """Rows ``[stride, row_width]`` of section i (0=params, 1=m, 2=v)
        of a pulled model (or its host copy)."""
        stride = self.section_stride(model.shape[0])
        return model[i * stride:(i + 1) * stride]

    def counter(self, model):
        """Pushes folded into a pulled model so far (stateful optimizers):
        the first cell after the sections."""
        stride = self.section_stride(model.shape[0])
        return model[(1 + self.num_state_slots) * stride, 0]

    # -- lifecycle --------------------------------------------------------

    def init_global_settings(self, ctx: TrainerContext) -> None:
        params = self.model.init(jax.random.PRNGKey(self.seed))
        flat, _ = ravel_pytree(params)
        rows = np.asarray(self._to_rows(flat, self.num_rows))
        # the put holds the table twice (nothing is donated) and the rows
        # once: the leaves and their flat copy, two more thirds of a
        # [params | m | v] table, go first — with them a 6.1 GB table did not
        # initialise on a 16 GB chip (PERF.md, PR 38)
        del params, flat
        ctx.model_table.multi_put(list(range(self.num_rows)), rows)
        # pad rows, m/v sections and the counter block start (and stay,
        # until the first push) at the table's init value 0.

    # -- pure parts -------------------------------------------------------

    def _to_rows(self, flat: jnp.ndarray, rows: int) -> jnp.ndarray:
        pad = rows * self.row_width - self.num_params
        return jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)]
        ).reshape(rows, self.row_width)

    def _leaves(self, p: jnp.ndarray) -> Any:
        """The parameter pytree of the parameter section's rows."""
        return self._unravel(p.reshape(-1)[: self.num_params])

    def _params(self, model: jnp.ndarray) -> Any:
        """The parameter pytree of a pulled model: rows -> leaves."""
        return self._leaves(self.section(model, 0))

    def hyperparams(self) -> Dict[str, float]:
        if self.beta2 is None:
            return {"lr": self.step_size}
        return {"lr": self.step_size, "beta2": self.beta2}

    # A step in two parts. ``gradient`` is COMP: it needs the parameter
    # section alone. ``push_update`` is PUSH: the optimizer, elementwise on
    # the stored sections, folded where the rows lie (``row_update_parts``;
    # dolphin/worker.py ``pull_all_step``). ``compute`` is the same
    # arithmetic as one whole-table delta.

    def row_update_parts(self, capacity: int):
        if self.section_stride(capacity) != self.section_rows:
            return None  # an older chain's sections start off a tile
        return (self.section_rows, 1 + self.num_state_slots, self.gradient,
                self.push_update)

    def gradient(self, p: jnp.ndarray, batch):
        """``(g, metrics)``: the gradient as rows ``[stride, row_width]``
        over the parameter section's rows ``p`` (rows -> leaves,
        value_and_grad, leaves -> rows), and what the step reports."""
        with step_scope("table.pull"):
            leaves = self._leaves(p)
        (loss, extra), grads = jax.value_and_grad(
            self.loss_and_metrics_on_batch, has_aux=True
        )(leaves, batch)
        with step_scope("table.grad_rows"):
            g = self._to_rows(ravel_pytree(grads)[0], p.shape[0])
        return g, {"loss": loss, **extra}

    def section_deltas(self, stored, g, scalars):
        """The optimizer as an elementwise rule: ``stored`` — the
        ``[params, m, v][: 1 + slots]`` sections, or equal blocks of them
        — and the gradient ``g`` in one shape, ``scalars`` the step count
        after this update (``"t"``) and the hyper-parameters, as scalars
        or anything that broadcasts -> ``new - stored`` per section."""
        from harmony_tpu.dolphin import optim

        p, m, v = (*stored, *[jnp.zeros_like(g)] * (3 - len(stored)))
        hyper = {k: x for k, x in scalars.items() if k != "t"}
        new = optim.apply(self.optimizer, p, g, m, v, scalars["t"], hyper)
        return tuple(n - o for n, o in zip(new, stored))

    def _counter_block(self, rows: int) -> jnp.ndarray:
        """The push's ``+1`` on the counter's first cell, as ``rows`` rows."""
        return jnp.zeros((rows, self.row_width), jnp.float32
                         ).at[0, 0].set(1.0)

    def push_update(self, spec, arr, model, g, hyper):
        """PUSH: the optimizer on the table's own sections under the
        gradient rows ``g`` (``model``: the pulled table, for the step
        count), each stored row read and written where it lies, then the
        counter's ``+1``. Tile-aligned sections only."""
        slots = self.num_state_slots
        t = self.counter(model) + 1.0 if slots else jnp.asarray(1.0)
        arr = spec.fold_row_sections(
            arr, g, {"t": t, **hyper}, self.section_deltas,
            rows=self.section_rows, sections=1 + slots)
        if slots:
            arr = spec.push_row_ranges(arr, [(
                (1 + slots) * self.section_rows,
                self._counter_block(TILE_ROWS))])
        return arr

    def compute(self, model, batch, hyper):
        slots = self.num_state_slots
        stored = tuple(self.section(model, i) for i in range(1 + slots))
        g, metrics = self.gradient(stored[0], batch)
        t = self.counter(model) + 1.0 if slots else jnp.asarray(1.0)
        sections = list(self.section_deltas(stored, g, {"t": t, **hyper}))
        tail = model.shape[0] - len(sections) * g.shape[0]
        if tail:  # the counter block (its first cell counts pushes)
            sections.append(self._counter_block(tail) if slots else
                            jnp.zeros((tail, self.row_width), g.dtype))
        return jnp.concatenate(sections), metrics

    def evaluate(self, model, batch) -> Dict[str, jnp.ndarray]:
        return self.eval_metrics(self._params(model), batch)
