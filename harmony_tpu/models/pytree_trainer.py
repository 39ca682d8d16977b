"""Generic PS-table trainer for functional pytree models.

Any model exposing ``init(key) -> params`` and a pure loss over a batch
trains through the framework's elastic-table substrate with this one
Trainer: the params pytree lives, leaf by leaf, in a range-partitioned
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
and ``push_all`` pads nothing.

Inside a section a leaf IS a row range (``LeafRows``, the layout
``"leaf_rows"``): leaf i, raveled, starts at row ``first_i`` — the sum of
the rows of the leaves before it in ``jax.tree.flatten`` order, always a
multiple of ``TILE_ROWS`` — and holds ``ceil(n_i / (TILE_ROWS *
row_width)) * TILE_ROWS`` rows, its tail zero. The offsets are static, from
the template's shapes. So a step changes layout twice, where the model
needs it, one copy a leaf each way: parameters ``p[first_i : first_i +
k]`` reshaped to the leaf, and each gradient leaf reshaped to its rows —
no ``[num_params]`` vector exists. The gradient's rows are never ONE array
either: they go to the fold in tile-aligned PIECES (``LeafRows.to_pieces``),
a large leaf's own rows a piece as its relayout left them and each run of
small leaves between two such joined by one concatenate, and
``ops.sections.fold_row_sections`` reads every piece where it lies — the
gradient is written once on its way to the optimizer. (``join`` makes one array of the
pieces for the whole-delta step; ``to_rows`` is the same rows built leaf by
leaf, for a job's start.)
The optimizer runs on row sections (m and v lie at the same offsets). Pad
rows and pad lanes hold zeros and stay zero under every optimizer (g = m =
v = 0 -> update 0). Chains written before this layout (leaves raveled end
to end) are converted once, on the host, at restore
(``rows_from_flat_chain``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harmony_tpu.config.params import TILE_ROWS, TableConfig
from harmony_tpu.dolphin.trainer import Trainer, TrainerContext
from harmony_tpu.ops.sections import PIECE_ROWS
from harmony_tpu.tracing.stepscopes import step_scope

#: this layout's name, as a chain's manifest records it
#: (``app_meta["layout"]``; jobserver/entity.py)
LEAF_ROWS = "leaf_rows"
#: what a chain without the key holds: all leaves raveled end to end
FLAT = "flat"


class _Leaf(NamedTuple):
    first: int                # its first row in a section, a tile's first
    rows: int                 # whole tiles
    shape: Tuple[int, ...]
    dtype: Any
    #: the shape its first ``prod(read) / row_width`` rows are read as, and
    #: how many entries of that shape's leading dimension are the leaf
    read: Tuple[int, ...]
    lead: int

    @property
    def size(self) -> int:
        return math.prod(self.shape)


def _read_shape(shape: Tuple[int, ...], rows: int, row_width: int):
    """``(read, lead)`` of a leaf of ``shape`` raveled into ``rows`` rows:
    its own shape with the leading dimension grown to a whole number of
    rows (gpt2's ``[50257, 768]`` reads ``[50260, 768]``, 37,695 rows;
    a ``[768]`` bias reads ``[1024]``), so that rows -> leaf is one reshape
    and a slice of the leading dimension — or, where that would not fit
    the leaf's rows (``[9, 1025]``), the leaf as a vector."""
    lead, *rest = shape or (1,)
    inner = math.prod(rest)
    whole = row_width // math.gcd(inner, row_width)
    grown = -(-lead // whole) * whole
    if grown * inner <= rows * row_width:
        return (grown, *rest), lead
    n = lead * inner
    return (-(-n // row_width) * row_width,), n


class LeafRows:
    """Where each leaf of a parameter pytree lies in a section's rows
    (the module's docstring has the rule), and the two relayouts."""

    def __init__(self, template: Any, row_width: int) -> None:
        shapes, self.treedef = jax.tree.flatten(template)
        self.row_width = row_width
        self.leaves = []
        first = 0
        for s in shapes:
            n = math.prod(s.shape)
            rows = -(-n // (TILE_ROWS * row_width)) * TILE_ROWS
            self.leaves.append(_Leaf(
                first, rows, tuple(s.shape), s.dtype,
                *_read_shape(tuple(s.shape), rows, row_width)))
            first += rows
        #: rows of a section: every leaf's tiles
        self.rows = first
        #: the fold's side operands (``to_pieces``): each a run of
        #: segments ``(leaf, its first row taken, rows)``. A leaf of
        #: ``PIECE_ROWS`` rows or more is a piece alone, as far as its
        #: reshape gives whole tiles (gpt2's embedding reads 37,695 rows:
        #: 37,688, its last tile goes with what follows); the leaves
        #: between two such are joined
        runs, run = [], []
        for i, f in enumerate(self.leaves):
            if f.rows >= PIECE_ROWS:
                whole = (math.prod(f.read) // row_width
                         // TILE_ROWS * TILE_ROWS)
                runs += [run, [(i, 0, whole)]]
                run = [(i, whole, f.rows - whole)] if whole < f.rows else []
            elif f.rows:
                run.append((i, 0, f.rows))
        self.pieces = [r for r in (*runs, run) if r]
        #: each piece's first row in a section
        self.piece_firsts = [self.leaves[r[0][0]].first + r[0][1]
                             for r in self.pieces]

    def record(self) -> Dict[str, int]:
        """STATUS ``table_layout.leaf_layout``: ``leaf_bitcasts`` counts
        the leaves whose rows ARE the leaf (whole tiles of ``row_width``
        lanes: merging leading dimensions moves nothing), ``leaf_copies``
        the others — one relayout copy each a direction; ``pad_rows`` the
        rows that hold no parameter; ``fold_pieces`` the operands the
        fold reads the gradient from and ``direct_rows`` the rows of those
        that are one leaf's own rows, not a concatenate of several."""
        w = self.row_width
        bitcasts = sum(
            1 for f in self.leaves
            if len(f.shape) >= 2 and f.shape[-1] == w
            and f.shape[-2] % TILE_ROWS == 0)
        return {"leaves": len(self.leaves),
                "leaf_copies": len(self.leaves) - bitcasts,
                "leaf_bitcasts": bitcasts,
                "pad_rows": sum(f.rows - -(-f.size // w)
                                for f in self.leaves),
                "rows": self.rows,
                "fold_pieces": len(self.pieces),
                "direct_rows": sum(r[0][2] for r in self.pieces
                                   if len(r) == 1)}

    def to_leaves(self, rows: jnp.ndarray) -> Any:
        """Rows ``[>= self.rows, row_width]`` whose first rows are a
        section (a pulled model, or one section of it) -> the pytree: each
        leaf its whole tiles, one reshape a leaf.

        The tiles are read with a DYNAMIC slice whose offset crosses an
        optimization barrier, although it is a constant: a static slice
        commutes with the model's casts, and the compiler then hoists every
        leaf's bf16 cast above its slice and casts the whole pulled table
        once, m and v with it (3.9 - 4.6 ms of gpt2's step; PERF.md PR 42);
        a dynamic slice of whole tiles is an address offset that fuses
        into the leaf's consumer."""
        tiles = rows.reshape(-1, TILE_ROWS, self.row_width)
        firsts = jax.lax.optimization_barrier(jnp.asarray(
            [f.first // TILE_ROWS for f in self.leaves], jnp.int32))
        out = []
        for f, first in zip(self.leaves, firsts):
            x = jax.lax.dynamic_slice_in_dim(tiles, first,
                                             f.rows // TILE_ROWS)
            k = math.prod(f.read) // self.row_width
            x = x.reshape(f.rows, self.row_width)[:k].reshape(f.read)
            out.append(x[:f.lead].reshape(f.shape).astype(f.dtype))
        return jax.tree.unflatten(self.treedef, out)

    def _leaf_rows(self, tree: Any):
        """Each leaf of the pytree as float32 rows of ``row_width``: its
        reshape, whole rows (its tail zero) and not yet whole tiles."""
        rows = []
        for f, x in zip(self.leaves, self.treedef.flatten_up_to(tree)):
            x = x.astype(jnp.float32).reshape(f.lead, *f.read[1:])
            rows.append(_grow(x, f.read[0]).reshape(-1, self.row_width))
        return rows

    def to_pieces(self, tree: Any) -> Tuple[jnp.ndarray, ...]:
        """The pytree -> the rows ``[self.rows, row_width]`` float32 as
        the tile-aligned pieces ``self.pieces``, in order: piece j holds
        the section's rows from ``piece_firsts[j]`` to the next piece's
        first (and, where it is a leaf whose reshape ends inside a tile,
        that tile's rows after them). Each leaf is reshaped to its own
        rows — the one pass over a large leaf, and none where its rows ARE
        the leaf — and every run of small leaves is one concatenate, their
        tails zero. What ``fold_row_sections`` reads, piece by piece: the
        rows as ONE array are never built."""
        rows = self._leaf_rows(tree)

        def segment(i, at, n):
            x = rows[i][at:]
            return x if x.shape[0] >= n else _grow(x, n)

        return tuple(segment(*r[0]) if len(r) == 1 else
                     jnp.concatenate([segment(*s) for s in r])
                     for r in self.pieces)

    def join(self, pieces) -> jnp.ndarray:
        """``to_pieces``' pieces -> the rows ``[self.rows, row_width]`` as
        one array (the whole-delta step's)."""
        ends = [*self.piece_firsts[1:], self.rows]
        return jnp.concatenate([p[:end - first] for p, first, end in zip(
            pieces, self.piece_firsts, ends)])

    def to_rows(self, tree: Any) -> jnp.ndarray:
        """The pytree -> rows ``[self.rows, row_width]`` float32: each leaf
        reshaped, its tail zero, to its own rows, and one concatenate —
        ``to_pieces`` joined, built leaf by leaf so that it costs a job's
        start, which runs it op by op, no copy of a piece."""
        return jnp.concatenate([_grow(x, f.rows) for f, x in zip(
            self.leaves, self._leaf_rows(tree))])

    def fill_rows(self, section: np.ndarray, leaves) -> None:
        """Host: write ``leaves`` (arrays, or flat vectors of each leaf's
        size, in ``jax.tree.flatten`` order) into a zeroed ``section``."""
        for f, x in zip(self.leaves, leaves):
            section[f.first:f.first + f.rows].reshape(-1)[:f.size] = (
                np.asarray(x).reshape(-1))


def _grow(x: jnp.ndarray, lead: int) -> jnp.ndarray:
    """``x`` with zeros after it along its leading dimension, to ``lead``."""
    if x.shape[0] == lead:
        return x
    return jnp.pad(x, [(0, lead - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


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
        #: where each leaf lies in a section: static, from the shapes
        self.leaf_rows = LeafRows(template, row_width)
        self.num_params = sum(f.size for f in self.leaf_rows.leaves)
        #: rows from one section's start to the next
        self.section_rows = self.leaf_rows.rows

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

    #: the layout's name, recorded with every chain entry this trainer's
    #: table writes and compared on restore (jobserver/entity.py)
    table_layout = LEAF_ROWS

    def section_stride(self, capacity: int) -> int:
        """Rows from one section's start to the next in a table of
        ``capacity`` rows; a table too small for the sections is refused,
        never misread."""
        if capacity >= self.capacity:
            return self.section_rows
        slots = self.num_state_slots
        raise ValueError(
            f"{type(self).__name__}: a model table of {capacity} rows does "
            f"not hold this trainer's layout (capacity {self.capacity}: "
            f"{1 + slots} sections of {self.section_rows} rows"
            f"{' + the counter block' if slots else ''})")

    def rows_from_flat_chain(self, old: np.ndarray) -> np.ndarray:
        """Host: the rows of this trainer's table from the rows ``old`` of
        one restored from a chain in the ``"flat"`` layout — every section
        all leaves raveled end to end in ``ceil(num_params / row_width)``
        rows, sections a whole number of tiles apart with the counter in a
        block of its own (PRs 26-41) or back to back with the counter in
        the last row (before). The frozen flat rule lives here alone; a
        row count that is neither is refused, never misread."""
        slots, w = self.num_state_slots, self.row_width
        flat_rows = -(-self.num_params // w)
        tiled = -(-flat_rows // TILE_ROWS) * TILE_ROWS
        aligned = (1 + slots) * tiled + (TILE_ROWS if slots else 0)
        unaligned = (1 + slots) * flat_rows + (1 if slots else 0)
        if old.shape[0] >= aligned:
            stride = tiled
        elif old.shape[0] == unaligned:
            stride = flat_rows
        else:
            raise ValueError(
                f"{type(self).__name__}: a model table of {old.shape[0]} "
                f"rows is neither flat layout of this model ({1 + slots} "
                f"sections of {self.num_params} parameters in rows of {w}: "
                f"capacity {aligned} tile-aligned, {unaligned} unaligned); "
                f"its own layout {LEAF_ROWS!r} has capacity {self.capacity}")
        new = np.zeros((self.capacity, w), np.float32)
        bounds = np.cumsum([0] + [f.size for f in self.leaf_rows.leaves])
        for i in range(1 + slots):
            flat = np.asarray(old[i * stride:(i + 1) * stride]).reshape(-1)
            self.leaf_rows.fill_rows(
                self.section(new, i),
                [flat[a:b] for a, b in zip(bounds[:-1], bounds[1:])])
        if slots:
            new[(1 + slots) * self.section_rows, 0] = old[
                (1 + slots) * stride, 0]
        return new

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
        # op by op, not one jit: a trainer is made anew for every job, and
        # the ops' executables are shared by shape where a jitted
        # closure's is not (2.5 s a job to trace and load it again;
        # PERF.md, PR 42)
        rows = np.asarray(self.leaf_rows.to_rows(params))
        # the put holds the table twice (nothing is donated) and the rows
        # once: the leaves, one more third of a [params | m | v] table, go
        # first — with them and a flat copy a 6.1 GB table did not
        # initialise on a 16 GB chip (PERF.md, PR 38)
        del params
        ctx.model_table.multi_put(list(range(self.section_rows)), rows)
        # pad rows, m/v sections and the counter block start (and stay,
        # until the first push) at the table's init value 0.

    # -- pure parts -------------------------------------------------------

    def _params(self, model: jnp.ndarray) -> Any:
        """The parameter pytree of a pulled model: rows -> leaves."""
        self.section_stride(model.shape[0])  # refuses what it cannot hold
        return self.leaf_rows.to_leaves(model)

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
        return (self.section_stride(capacity), 1 + self.num_state_slots,
                self.gradient, self.push_update)

    def gradient(self, model: jnp.ndarray, batch):
        """``(g, metrics)``: the gradient over the parameter section of
        the pulled ``model`` as the row pieces ``LeafRows.to_pieces`` makes
        of it (rows -> leaves, value_and_grad, leaves -> their rows), and
        what the step reports. The leaves are read where they lie in
        ``model``: a slice of its parameter section first would be a copy
        of the section."""
        with step_scope("table.pull"):
            leaves = self._params(model)
        (loss, extra), grads = jax.value_and_grad(
            self.loss_and_metrics_on_batch, has_aux=True
        )(leaves, batch)
        with step_scope("table.grad_rows"):
            g = self.leaf_rows.to_pieces(grads)
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
        gradient's row pieces ``g`` (``model``: the pulled table, for the
        step count), each stored row read and written where it lies and
        each piece read where ``gradient`` left it, then the counter's
        ``+1``. Tile-aligned sections only."""
        slots = self.num_state_slots
        t = self.counter(model) + 1.0 if slots else jnp.asarray(1.0)
        arr = spec.fold_row_sections(
            arr, list(zip(self.leaf_rows.piece_firsts, g)),
            {"t": t, **hyper}, self.section_deltas,
            rows=self.section_rows, sections=1 + slots)
        if slots:
            arr = spec.push_row_ranges(arr, [(
                (1 + slots) * self.section_rows,
                self._counter_block(TILE_ROWS))])
        return arr

    def compute(self, model, batch, hyper):
        slots = self.num_state_slots
        stored = tuple(self.section(model, i) for i in range(1 + slots))
        g, metrics = self.gradient(model, batch)
        g = self.leaf_rows.join(g)
        t = self.counter(model) + 1.0 if slots else jnp.asarray(1.0)
        sections = list(self.section_deltas(stored, g, {"t": t, **hyper}))
        tail = model.shape[0] - len(sections) * g.shape[0]
        if tail:  # the counter block (its first cell counts pushes)
            sections.append(self._counter_block(tail) if slots else
                            jnp.zeros((tail, self.row_width), g.dtype))
        return jnp.concatenate(sections), metrics

    def evaluate(self, model, batch) -> Dict[str, jnp.ndarray]:
        return self.eval_metrics(self._params(model), batch)
