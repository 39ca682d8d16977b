"""DenseTable — the elastic sharded model table, TPU-first.

This is the rebuild of the reference's Elastic Table (services/et): the
parameter-server role is played entirely by the table (SURVEY.md §1: servers
run a do-nothing tasklet while the table's UpdateFunction applies pushes,
dolphin/core/server/ServerTasklet.java:29-41). Capabilities reproduced:

  * key space partitioned into ``num_blocks`` blocks, hash- or range-based
    (ref: TableImpl routing, evaluator/impl/TableImpl.java:109-143);
  * pull = getOrInit/multiGetOrInit, push = update/multiUpdate with
    server-side UpdateFunction semantics (ref: ETModelAccessor.java:60-146);
  * live re-sharding across a changed executor/device set (ref:
    MigrationExecutor.java) — here an XLA resharding ``jax.device_put`` onto
    a new mesh, with a host-side latch standing in for the per-block
    ownership read-locks (OwnershipCache.java:140-153);
  * per-block export/import for two-stage checkpointing (ref:
    ChkpManagerSlave.java:50-63).

Architecture (deliberately NOT a translation):

  Storage is ONE dense jax array ``[num_blocks, block_size, *value_shape]``
  sharded over the mesh's "model" axis with NamedSharding (block axis ==
  placement axis, so a block maps to a chip the way a reference block maps to
  a server executor). Replication across the "data" axis gives every
  data-parallel worker a local copy to pull from; pushes are XLA scatters
  whose cross-shard traffic XLA lowers to collectives over ICI instead of
  per-key RPCs (SURVEY.md §5.8 TPU-native equivalent).

  All device state is functional: ops take the array, return a new array.
  The host-side DenseTable object serializes commits; in-flight jitted steps
  always see an immutable snapshot, which is what makes accesses racing with
  migration safe by construction (the role of the reference's retry/redirect
  protocol, RemoteAccessOpSender.java:132-163).
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harmony_tpu.config.params import TableConfig
from harmony_tpu.parallel.dispatch import dispatch_scope
from harmony_tpu.parallel.mesh import MODEL_AXIS
from harmony_tpu.table.partition import (
    BlockPartitioner,
    HashPartitioner,
    RangePartitioner,
)
from harmony_tpu.table.update import UpdateFunction, get_update_fn


def cross_set_reshard(arr: jax.Array, old_mesh: Mesh,
                      new_sharding: NamedSharding) -> jax.Array:
    """Reshard onto a DIFFERENT device set across hosts — the case
    multi-controller jax.device_put refuses ("input and target sharding
    should have the same set of devices"; direct transfers exist only
    experimentally on the TFRT TPU runtime).

    Block-granular and point-to-point (table/blockmove.py): each process
    stages only the blocks LEAVING it, moves them over the DCN host
    channel (TCP; KV-store rendezvous) or per-block staged files, and
    rebuilds its own new shards from local-plus-received blocks — the
    reference's O(moved bytes) cost model (MigrationExecutor.java:107-253,
    AllocatedTable.moveBlocks), with no full replica at any point. Works
    LIVE in either direction (shrink AND grow) on a running table; every
    participating process calls in lockstep."""
    from harmony_tpu.table.blockmove import migrate_blocks

    return migrate_blocks(arr, old_mesh, new_sharding)


def reshard_array(arr: jax.Array, old_mesh: Mesh,
                  new_sharding: NamedSharding) -> jax.Array:
    """Route an array onto a new sharding, choosing the transfer path UP
    FRONT (never by catching exceptions — a deleted/donated buffer must
    surface as itself, not vanish into a fallback):

      * same device set, or everything single-process -> jax.device_put
        (XLA moves bytes directly);
      * device set changes across processes -> cross_set_reshard (the
        case multi-controller device_put refuses)."""
    from harmony_tpu.parallel.mesh import mesh_spans_processes

    same_set = (
        {d.id for d in old_mesh.devices.flat}
        == {d.id for d in new_sharding.mesh.devices.flat}
    )
    multiproc = (mesh_spans_processes(old_mesh)
                 or mesh_spans_processes(new_sharding.mesh))
    if same_set or not multiproc:
        return jax.device_put(arr, new_sharding)
    return cross_set_reshard(arr, old_mesh, new_sharding)


def owned_addressable_blocks(arr: jax.Array) -> "Dict[int, np.ndarray]":
    """Blocks of a block-major global array whose bytes live on THIS
    process — deduped across replicas by the lowest-owner-process rule, so
    on a multi-process mesh every block is returned by exactly one process
    (the pod checkpoint's stage-1 contract: each process stages its own
    blocks from addressable shards, ref ChkpManagerSlave.java:50-63).
    Ownership comes from blockmove.block_owners — the ONE copy of the
    rule, so checkpoint staging and migration sourcing always agree on
    who holds a block's authoritative bytes."""
    from harmony_tpu.table.blockmove import axis0_bounds, block_owners

    pid = jax.process_index()
    nb = arr.shape[0]
    owners = block_owners(arr.sharding, arr.shape)
    out: Dict[int, np.ndarray] = {}
    for shard in arr.addressable_shards:
        start, stop = axis0_bounds(shard.index, nb)
        data = None
        for b in range(start, stop):
            if owners.get(b) == pid and b not in out:
                if data is None:
                    data = np.asarray(shard.data)  # one D2H per shard
                out[b] = data[b - start]
    return out


def block_sharding(mesh: Mesh, num_blocks: int) -> NamedSharding:
    """Placement policy for block-major table storage, shared by dense and
    hash tables: shard the leading (block) axis over the mesh model axis
    when divisible, else replicate (tiny tables / indivisible counts)."""
    model = mesh.shape.get(MODEL_AXIS, 1)
    if num_blocks % max(model, 1) == 0 and MODEL_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(MODEL_AXIS))
    return NamedSharding(mesh, P())


def row_shards(mesh: Mesh, num_blocks: int) -> int:
    """Shards :func:`block_sharding` splits a table's blocks into (1: every
    device holds the table whole)."""
    model = mesh.shape.get(MODEL_AXIS, 1)
    return model if model > 1 and num_blocks % model == 0 else 1


def _gather_on_mesh(mesh: Mesh, shards: int, flat: jnp.ndarray,
                    idx: jnp.ndarray) -> jnp.ndarray:
    """The Pallas gather partitioned by hand (a pallas_call is opaque to
    the GSPMD partitioner): each device gathers from the rows it holds;
    with the rows split over ``shards`` model-axis shards a key's row comes
    from the one shard that owns it (the others contribute zeros to a
    psum), so what crosses the model axis is the pulled rows — never the
    table."""
    from harmony_tpu.ops.sparse import gather_rows

    if mesh.devices.size == 1:
        return gather_rows(flat, idx)

    def local(rows, ids):
        if shards == 1:
            return gather_rows(rows, ids)
        lo = jax.lax.axis_index(MODEL_AXIS) * rows.shape[0]
        mine = (ids >= lo) & (ids < lo + rows.shape[0])
        got = gather_rows(rows, ids - lo)  # foreign ids clamp, then mask
        return jax.lax.psum(
            jnp.where(mine[:, None], got, jnp.zeros_like(got)), MODEL_AXIS)

    rows = P(MODEL_AXIS) if shards > 1 else P()
    return jax.shard_map(local, mesh=mesh, in_specs=(rows, P()),
                         out_specs=P(), check_vma=False)(flat, idx)


def _scatter_on_mesh(mesh: Mesh, shards: int, flat: jnp.ndarray,
                     idx: jnp.ndarray, deltas: jnp.ndarray) -> jnp.ndarray:
    """The Pallas scatter-add partitioned by hand, in place: each device
    updates the rows it holds (aliased shard by shard) from the keys that
    land in them — ids are made shard-local, and the kernel drops the
    foreign ones with every other id outside its rows. Nothing crosses
    the model axis."""
    from harmony_tpu.ops.sparse import scatter_add_rows

    if mesh.devices.size == 1:
        return scatter_add_rows(flat, idx, deltas)

    def local(rows, ids, d):
        if shards > 1:  # below this shard: negative; above, or -1: no row
            ids = ids - jax.lax.axis_index(MODEL_AXIS) * rows.shape[0]
        return scatter_add_rows(rows, ids, d)

    rows = P(MODEL_AXIS) if shards > 1 else P()
    return jax.shard_map(local, mesh=mesh, in_specs=(rows, P(), P()),
                         out_specs=rows, check_vma=False)(flat, idx, deltas)


class LayoutAnnouncerMixin:
    """Reshard announcements, shared by dense AND hash tables: the caller
    (TableHandle._announce_target) announces the TARGET mesh before the
    ownership flip so subscribers (workers) compile their programs for
    the target layout while the current one still trains — the stall then
    costs ~the move, not a recompile (the reference's access-latch-only
    stall, MigrationExecutor.java:163-253). Hosts must init
    ``self._layout_listeners = []`` and hold ``self._lock``."""

    def add_layout_listener(self, fn) -> None:
        with self._lock:
            self._layout_listeners.append(fn)

    def remove_layout_listener(self, fn) -> None:
        with self._lock:
            if fn in self._layout_listeners:
                self._layout_listeners.remove(fn)

    @property
    def layout_version(self) -> int:
        """Monotonic count of reshard announcements — an observability
        token for tests and dashboards ("did an announcement reach this
        table, and how many?"). Staleness of layout-derived state is
        decided by sharding comparison (StagedBatch.take, _maybe_rebuild),
        not by this counter."""
        with self._lock:
            return getattr(self, "_layout_version", 0)

    def set_comm_split(self, split) -> None:
        """Publish the comm probe's measured per-step (pull_sec,
        push_sec) device seconds for this table — chief-measured, read
        by every sibling worker sharing the table (the probe blocks the
        table lock for several round-trips; once per job per epoch is
        enough). A TYPED accessor on purpose: the split used to be a
        private-attr poke (``table._comm_split = ...``) that the
        thread-shared-state lint could not see and downstream consumers
        reached into; the lock here is the cross-thread publication
        fence."""
        with self._lock:
            self._comm_split = (float(split[0]), float(split[1]))

    def comm_split(self):
        """The last published (pull_sec, push_sec) probe split, or None
        before any probe ran — callers fall back to their own default
        rather than inventing zeros."""
        with self._lock:
            return getattr(self, "_comm_split", None)

    def announce_reshard(self, new_mesh: Mesh) -> None:
        """Run listeners with the target mesh (outside the table lock —
        listeners dispatch device programs). Best-effort: a failing
        listener never blocks the migration."""
        with self._lock:
            listeners = list(self._layout_listeners)
            self._layout_version = getattr(self, "_layout_version", 0) + 1
        try:  # the announcement count, scrapeable (metrics/registry.py)
            from harmony_tpu.metrics.registry import get_registry

            get_registry().counter(
                "harmony_table_layout_changes_total",
                "Reshard announcements across this process's tables",
            ).inc()
        except Exception:
            pass
        for fn in listeners:
            try:
                fn(new_mesh)
            except Exception:
                pass


class TableSpec:
    """Static description of a table + its pure on-device ops.

    Separating the pure functions from the stateful host object lets trainers
    inline ``pull``/``push`` into their own jitted train step (the fast path)
    while DenseTable uses the same functions for its host-level API.
    """

    def __init__(self, config: TableConfig, update_fn: Optional[UpdateFunction] = None):
        self.config = config
        # Caller-supplied update fns have no stable identity, so specs built
        # with one are excluded from program-cache keys (runtime/progcache).
        self.custom_update_fn = update_fn is not None
        self.update_fn = update_fn or get_update_fn(config.update_fn)
        part_cls = RangePartitioner if config.is_ordered else HashPartitioner
        self.partitioner: BlockPartitioner = part_cls(config.capacity, config.num_blocks)
        self.value_shape: Tuple[int, ...] = tuple(config.value_shape)
        self.dtype = jnp.dtype(config.dtype)

    @property
    def table_id(self) -> str:
        return self.config.table_id

    @property
    def num_blocks(self) -> int:
        return self.partitioner.num_blocks

    @property
    def block_size(self) -> int:
        return self.partitioner.block_size

    @property
    def storage_shape(self) -> Tuple[int, ...]:
        return (self.num_blocks, self.block_size, *self.value_shape)

    # -- pure ops (safe inside any jit) ---------------------------------

    def init_array(self) -> jnp.ndarray:
        """Materialize initial storage via the update fn's ``init(key)``
        (getOrInit semantics: every key starts at its init value)."""
        b = jnp.arange(self.num_blocks, dtype=jnp.int32)[:, None]
        o = jnp.arange(self.block_size, dtype=jnp.int32)[None, :]
        keys = self.partitioner.key_of(b, o).reshape(-1)
        vals = jax.vmap(self.update_fn.init)(keys)
        vals = jnp.broadcast_to(
            vals.reshape(vals.shape[0], *([1] * len(self.value_shape))),
            (keys.shape[0], *self.value_shape),
        ) if vals.ndim == 1 and self.value_shape else vals
        return vals.astype(self.dtype).reshape(self.storage_shape)

    def _kernel_layout(self) -> "Tuple[Optional[Mesh], int]":
        """``(mesh, row shards)`` when the op being traced may take the
        Pallas kernels — the traced mesh was named by the caller
        (utils.platform.on_mesh) and is all-TPU — else ``(None, 1)``: a
        pallas_call is opaque to the GSPMD partitioner, so without the
        mesh there is no way to keep a sharded table's rows where they
        are, and ops traced outside a scope take the XLA references.
        Rows split over the model axis by block_sharding's rule."""
        from harmony_tpu.utils.platform import mesh_is_tpu, trace_mesh

        mesh = trace_mesh()
        if mesh is None or not mesh_is_tpu(mesh):
            return None, 1
        return mesh, row_shards(mesh, self.num_blocks)

    def push_lowering(self, n_keys: int) -> str:
        """What a ``push`` of ``n_keys`` keys lowers to in the program
        being traced: ``"pallas_rows"`` —
        ops.sparse.scatter_add_rows, in place, per row shard — for an
        additive update fn on a TPU mesh over rows the kernel takes,
        stored in whole 8-row tiles (the kernel sees the storage as its
        flat row matrix, which any other block size would copy, twice);
        ``"xla"`` — one XLA scatter — for everything else."""
        from harmony_tpu.config.params import TILE_ROWS
        from harmony_tpu.ops import sparse

        mesh, shards = self._kernel_layout()
        rows = self.num_blocks * self.block_size // shards
        if (mesh is not None and self.update_fn.scatter_mode == "add"
                and self.block_size % TILE_ROWS == 0
                and sparse.scatter_kernel_ok(
                    (rows, sparse.value_width(self.value_shape)),
                    self.dtype, n_keys)):
            return "pallas_rows"
        return "xla"

    def pull(self, arr: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
        """multiGetOrInit: gather values for ``keys`` -> [n, *value_shape].

        Traced for a TPU mesh (utils.platform.on_mesh) with rows the
        kernel takes, this is ops.sparse.gather_rows — the Pallas batched
        embedding gather, run per row shard; everywhere else the
        value-identical XLA gather (gather_rows_ref)."""
        from harmony_tpu.ops import sparse

        b, o = self.partitioner.locate(keys)
        flat_idx = (b * self.block_size + o).astype(jnp.int32)
        num_rows = self.num_blocks * self.block_size
        flat = arr.reshape(num_rows, sparse.value_width(self.value_shape))
        idx = flat_idx.reshape(-1)
        mesh, shards = self._kernel_layout()
        if mesh is not None and sparse.gather_kernel_ok(
                (num_rows // shards, flat.shape[1]), flat.dtype,
                idx.shape[0]):
            # clamp against the WHOLE table before ids are made shard-local
            idx = jnp.clip(idx, 0, num_rows - 1)
            rows = _gather_on_mesh(mesh, shards, flat, idx)
        else:
            rows = sparse.gather_rows_ref(flat, idx)
        return rows.reshape(*flat_idx.shape, *self.value_shape)

    def pull_all(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Whole table as ``[capacity, *value_shape]`` in key order (the
        "pull the full model" fast path; only meaningful for range tables)."""
        flat = arr.reshape(self.num_blocks * self.block_size, *self.value_shape)
        if isinstance(self.partitioner, RangePartitioner):
            return flat[: self.config.capacity]
        keys = jnp.arange(self.config.capacity, dtype=jnp.int32)
        return self.pull(arr, keys)

    def push(
        self,
        arr: jnp.ndarray,
        keys: jnp.ndarray,
        deltas: jnp.ndarray,
        *,
        via: str = "auto",
    ) -> jnp.ndarray:
        """multiUpdate: fold ``deltas`` into the table; duplicate keys fold
        per the update fn's scatter_mode.

        An additive push reads, adds to and writes the touched rows alone,
        in place. Traced for a TPU mesh over float32 rows 128 wide it is
        ops.sparse.scatter_add_rows (:meth:`push_lowering` says when): the
        keys sorted a tile at a time, duplicates folded in VMEM in
        occurrence order, each of a tile's distinct rows read and written
        once by row DMA, run per row shard. Everywhere else, and for min /
        max / set, one XLA scatter (duplicate keys serialise on TPU, 76 ns
        a key whatever they repeat).

        ``via`` names the route and there is one, "scatter" ("auto" is its
        alias); anything else raises.
        """
        if via not in ("scatter", "auto"):
            raise ValueError(
                f"unknown push route {via!r}: a keyed push has one route, "
                "'scatter'"
            )
        b, o = self.partitioner.locate(keys)
        mode = self.update_fn.scatter_mode
        ref = arr.at[b, o]
        if self.push_lowering(keys.shape[0]) == "pallas_rows":
            # what ``.at[b, o].add`` does with an index outside its axis:
            # a negative one counts from the end, anything else is dropped
            nb, bs = self.num_blocks, self.block_size
            bb, oo = jnp.where(b < 0, b + nb, b), jnp.where(o < 0, o + bs, o)
            inside = (bb >= 0) & (bb < nb) & (oo >= 0) & (oo < bs)
            mesh, shards = self._kernel_layout()
            out = _scatter_on_mesh(
                mesh, shards, arr.reshape(nb * bs, -1),
                jnp.where(inside, bb * bs + oo, -1).astype(jnp.int32),
                deltas.reshape(keys.shape[0], -1).astype(arr.dtype),
            ).reshape(arr.shape)
        elif mode == "add":
            out = ref.add(deltas.astype(arr.dtype))
        elif mode == "min":
            out = ref.min(deltas.astype(arr.dtype))
        elif mode == "max":
            out = ref.max(deltas.astype(arr.dtype))
        elif mode == "set":
            out = ref.set(deltas.astype(arr.dtype))
        else:
            raise ValueError(f"unknown scatter_mode {mode!r}")
        if self.update_fn.post is not None:
            # Apply-time invariant on the touched entries only.
            out = out.at[b, o].set(self.update_fn.post(out[b, o]))
        return out

    def _pad_to_storage(self, values: jnp.ndarray, dtype) -> jnp.ndarray:
        """[capacity, *vshape] in key order -> storage layout (range tables
        only: pad the tail block, reshape to [num_blocks, block_size, ...])."""
        pad = self.num_blocks * self.block_size - self.config.capacity
        v = values.astype(dtype)
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad, *self.value_shape), dtype)])
        return v.reshape(self.storage_shape)

    def push_all(self, arr: jnp.ndarray, deltas: jnp.ndarray) -> jnp.ndarray:
        """Dense full-model push: fold a ``[capacity, *value_shape]`` delta
        into every key (the whole-model pushUpdate fast path — one fused
        XLA add instead of a scatter; cross-shard reduction of data-parallel
        contributions is inserted by XLA where the delta computation
        contracts over the batch axis)."""
        mode = self.update_fn.scatter_mode
        if isinstance(self.partitioner, RangePartitioner):
            if mode == "set":
                return self.write_all(arr, deltas)
            d = self._pad_to_storage(deltas, arr.dtype)
            if mode == "add":
                out = arr + d
            elif mode == "min":
                out = jnp.minimum(arr, d)
            elif mode == "max":
                out = jnp.maximum(arr, d)
            else:
                raise ValueError(f"unknown scatter_mode {mode!r}")
            if self.update_fn.post is not None:
                out = self.update_fn.post(out)  # every entry is touched here
            return out
        keys = jnp.arange(self.config.capacity, dtype=jnp.int32)
        return self.push(arr, keys, deltas)

    @property
    def takes_row_ranges(self) -> bool:
        """Whether :meth:`push_row_ranges` / :meth:`fold_row_sections`
        apply: keys lie in storage order (a range table) and the update
        fn is the plain additive fold — a delta that names some rows and
        one that pads the others with zeros then fold to the same table
        (``min`` / ``max`` / a ``post`` hook touch every row of a whole
        push)."""
        return (isinstance(self.partitioner, RangePartitioner)
                and self.update_fn.scatter_mode == "add"
                and self.update_fn.post is None)

    def _flat_rows(self, arr: jnp.ndarray, what: str) -> jnp.ndarray:
        if not self.takes_row_ranges:
            raise ValueError(
                f"{what}: table {self.table_id!r} "
                f"({type(self.partitioner).__name__}, update fn "
                f"{self.update_fn.name!r}) takes whole deltas only")
        return arr.reshape(self.num_blocks * self.block_size,
                           *self.value_shape)

    def push_row_ranges(self, arr: jnp.ndarray, ranges) -> jnp.ndarray:
        """Dense push of row ranges: fold ``[(first_row, rows), ...]``
        (static firsts, ascending and disjoint; ``rows`` is ``[n,
        *value_shape]`` for keys ``first_row .. first_row + n``) into the
        stored rows, read from ``arr`` itself and updated where they lie
        (``dynamic-update-slice``: in place in a donated table, at a cost
        that follows the rows named). No ``[capacity, ...]`` delta is
        built. Equal, bit for bit, to ``push_all`` of the zero-padded whole
        delta. The deltas must not read OTHER stored rows of ``arr`` — the
        compiler would copy the table to keep them: a rule across rows is
        :meth:`fold_row_sections`'s."""
        flat = self._flat_rows(arr, "push_row_ranges")
        at = 0
        for first, rows in ranges:
            end = first + rows.shape[0]
            if first < at or end > self.config.capacity:
                raise ValueError(
                    f"push_row_ranges: rows {first}..{end} overlap the "
                    f"range before them or lie outside table "
                    f"{self.table_id!r} ({self.config.capacity} rows)")
            flat = jax.lax.dynamic_update_slice_in_dim(
                flat, flat[first:end] + rows.astype(arr.dtype), first,
                axis=0)
            at = end
        return flat.reshape(arr.shape)

    def fold_lowering(self, rows: int, sections: int) -> str:
        """What :meth:`fold_row_sections` lowers to in the program being
        traced: ``"pallas_sections"`` — ops.sections.fold_row_sections,
        one pass in place — on a one-device TPU mesh over rows the kernel
        takes, stored in whole 8-row tiles; ``"xla"`` — the rule on whole
        sections, rewritten through fresh buffers — for everything else."""
        from harmony_tpu.config.params import TILE_ROWS
        from harmony_tpu.ops import sections as kernel

        mesh, _ = self._kernel_layout()
        if (mesh is not None and mesh.devices.size == 1
                and self.block_size % TILE_ROWS == 0
                and len(self.value_shape) == 1
                and kernel.sections_kernel_ok(
                    (self.num_blocks * self.block_size, *self.value_shape),
                    self.dtype, rows, sections)):
            return "pallas_sections"
        return "xla"

    def fold_row_sections(self, arr: jnp.ndarray, side: jnp.ndarray,
                          scalars, rule, *, rows: int,
                          sections: int) -> jnp.ndarray:
        """Fold an elementwise update rule over aligned row sections:
        keys ``k * rows .. (k + 1) * rows`` for k < ``sections`` hold
        state that updates together, row by row — ``stored[k][r] +=
        rule(stored, side, scalars)[k][r]``, where ``rule`` gets the
        ``sections`` stored blocks and ``side`` (``[rows, *value_shape]``,
        e.g. a gradient; or its rows in pieces ``[(first_row, piece),
        ...]``, which the one-pass lowering reads where they lie and the
        other concatenates: ops.sections.fold_row_sections) in one shape, ``scalars`` as a dict of
        values that broadcast against them, and returns one delta per
        section.
        The update fn's fold of the table, with the delta computed where
        the rows lie: in one in-place pass (:meth:`fold_lowering`), else
        the rule on whole sections and one rewrite of them
        (ops.sections.fold_row_sections_ref)."""
        from harmony_tpu.ops.sections import (
            fold_row_sections,
            fold_row_sections_ref,
        )

        flat = self._flat_rows(arr, "fold_row_sections")
        if isinstance(side, (tuple, list)):
            side = [(first, p.astype(arr.dtype)) for first, p in side]
        else:
            side = side.astype(arr.dtype)
        if self.fold_lowering(rows, sections) == "pallas_sections":
            keys = sorted(scalars)
            consts = jnp.stack([jnp.broadcast_to(
                jnp.asarray(scalars[k], arr.dtype), flat.shape[1:])
                for k in keys])
            return fold_row_sections(
                flat, side, consts,
                lambda stored, g, c: rule(stored, g, {
                    k: c[i:i + 1] for i, k in enumerate(keys)}),
                rows=rows, sections=sections).reshape(arr.shape)
        return fold_row_sections_ref(
            flat, side, scalars, rule, rows=rows,
            sections=sections).reshape(arr.shape)

    def write_all(self, arr: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
        """Overwrite the whole table from ``[capacity, *value_shape]`` in key
        order (bulk set for restores / assign-style updates)."""
        if isinstance(self.partitioner, RangePartitioner):
            return self._pad_to_storage(values, self.dtype)
        keys = jnp.arange(self.config.capacity, dtype=jnp.int32)
        b, o = self.partitioner.locate(keys)
        return arr.at[b, o].set(values.astype(self.dtype))


class DenseTable(LayoutAnnouncerMixin):
    """Host-side handle: stateful commits, sharding, re-sharding, checkpoint.

    Mirrors the union of the reference's ``Table`` (evaluator/api/Table.java:
    46-221, the op surface) and ``AllocatedTable`` (driver/api/
    AllocatedTable.java:38-154, the master-side lifecycle handle) — one
    object, because single-controller JAX has no evaluator/driver split.
    """

    def __init__(self, spec: TableSpec, mesh: Mesh, arr: Optional[jax.Array] = None):
        self.spec = spec
        self._lock = threading.RLock()
        self._mesh = mesh
        self._layout_listeners: list = []
        self._sharding = self._make_sharding(mesh)
        if arr is None:
            # Route the init program through the process-level program cache:
            # every table construction otherwise compiles a fresh closure,
            # and a multi-tenant server constructs tables per job submit.
            from harmony_tpu.runtime import progcache

            key = (
                None if spec.custom_update_fn
                else (progcache.table_signature(self), "table_init")
            )
            init = progcache.get_or_build(
                key,
                lambda: jax.jit(spec.init_array, out_shardings=self._sharding),
            )
            with dispatch_scope(mesh) as finish:
                arr = finish(init())
        else:
            arr = jax.device_put(arr, self._sharding)
        self._arr: jax.Array = arr
        self._data_version = 0
        self._jit_cache: Dict[str, Callable] = {}

    # -- layout ----------------------------------------------------------

    def _make_sharding(self, mesh: Mesh) -> NamedSharding:
        return block_sharding(mesh, self.spec.num_blocks)

    @property
    def mesh(self) -> Mesh:
        with self._lock:
            return self._mesh

    @property
    def sharding(self) -> NamedSharding:
        with self._lock:
            return self._sharding

    @property
    def array(self) -> jax.Array:
        """Snapshot of current storage.

        CAUTION: if any writer uses a *donating* step (apply_step with a
        donate_argnums jit), this handle may be invalidated the moment such a
        step dispatches — dereferencing it afterwards raises "Array has been
        deleted" on hardware that honors donation. Host-side readers must not
        hold this across writer activity; use the table's read methods
        (multi_get / pull_array / export_blocks), which dispatch their device
        ops *under the table lock* and hand back freshly-produced arrays that
        no later donation can invalidate."""
        with self._lock:
            return self._arr

    @property
    def data_version(self) -> int:
        """Monotonic count of storage writes (commit / push / put /
        write_all). External caches of gathered rows (the serving
        plane's hot-row cache) key on this so a training step can never
        leave a stale row servable — a write retires the whole cached
        generation. Reshards bump ``layout_version`` instead; both ride
        the cache key."""
        with self._lock:
            return self._data_version

    def _bump_data_version(self) -> None:
        # callers hold self._lock (RLock) at every write site
        self._data_version += 1

    def commit(self, new_arr: jax.Array) -> None:
        """Install the post-step storage (the trainer fast path: a jitted
        train step returns the updated table array; committing it is the
        moment the push becomes visible, like the reference's server-side
        update application).

        If a reshard happened while the step was in flight, the step's result
        still carries the OLD layout — re-home it so the table never holds
        devices that were released back to the pool.
        """
        with self._lock:
            if new_arr.sharding != self._sharding:
                # same routed transfer as reshard: an in-flight step's
                # result re-homes across whatever device-set change the
                # reshard made (raw device_put would refuse cross-process
                # set changes). Non-mesh shardings (single-device results)
                # are process-local by construction — plain device_put.
                src_mesh = getattr(new_arr.sharding, "mesh", None)
                if src_mesh is None:
                    new_arr = jax.device_put(new_arr, self._sharding)
                else:
                    new_arr = reshard_array(new_arr, src_mesh, self._sharding)
            self._arr = new_arr
            self._bump_data_version()

    @staticmethod
    def apply_step_multi(tables: Sequence["DenseTable"], step_fn, *extra):
        """Like :meth:`apply_step` for a step over SEVERAL tables:
        ``step_fn(arr0, arr1, ..., *extra) -> ((new0, new1, ...), aux)``.
        Locks are taken in the given order (callers must use a consistent
        table order to stay deadlock-free); used for jobs with a worker-local
        table next to the PS table (ref: DolphinJobEntity's optional
        local-model table)."""
        import contextlib

        with contextlib.ExitStack() as stack:
            for t in tables:
                stack.enter_context(t._lock)
            arrs = [t._step_state for t in tables]
            with dispatch_scope(tables[0]._mesh) as finish:
                new_arrs, aux = finish(step_fn(*arrs, *extra))
            for t, new in zip(tables, new_arrs):
                t.commit(new)
        return aux

    @property
    def _step_state(self):
        """Uniform state accessor for mixed-table steps (DeviceHashTable
        exposes the same property over its (keys, values) pair)."""
        return self._arr

    def apply_step(self, step_fn, *extra):
        """Dispatch a functional step ``step_fn(arr, *extra) -> (new_arr, aux)``
        and commit its result atomically w.r.t. every other table accessor.

        This is the ONLY safe way to run a step that *donates* the storage
        buffer: dispatch and commit happen under the table lock, so no host
        accessor (checkpoint export, multi_get, a concurrent update) can
        observe the window where the live buffer is donated-but-not-replaced.
        Dispatch is async — the lock is held for microseconds, not for the
        device computation.
        """
        with self._lock:
            # Global enqueue-order scope: concurrent JOBS (each under its own
            # table lock) must still enqueue multi-device programs in one
            # process-wide order — and on in-process-collective backends
            # execute one at a time — or the collective rendezvous aborts
            # the process. See parallel/dispatch.py.
            with dispatch_scope(self._mesh) as finish:
                new_arr, aux = finish(step_fn(self._arr, *extra))
            self.commit(new_arr)  # RLock: re-homes if resharded mid-flight
        return aux

    # -- op surface (host-level; parity with Table.java) ----------------

    def _jitted(self, name: str, fn: Callable,
                out_shardings=None) -> Callable:
        from harmony_tpu.utils.platform import traced_on

        with self._lock:
            if name not in self._jit_cache:
                mesh = self._mesh  # stable: cache cleared on reshard
                fn = traced_on(mesh, fn)  # table ops pick kernels by mesh
                jf = (jax.jit(fn) if out_shardings is None
                      else jax.jit(fn, out_shardings=out_shardings))

                def wrapped(*args, _jf=jf, _mesh=mesh, **kw):
                    # host ops dispatch multi-device programs too (gathers/
                    # all-gathers over the sharded storage): same global
                    # dispatch rule as apply_step
                    with dispatch_scope(_mesh) as finish:
                        return finish(_jf(*args, **kw))

                self._jit_cache[name] = wrapped
            return self._jit_cache[name]

    def multi_get(self, keys: Sequence[int]) -> np.ndarray:
        k = jnp.asarray(keys, dtype=jnp.int32)
        with self._lock:  # dispatch under lock: see `array` docstring
            out = self._jitted("pull", self.spec.pull)(self._arr, k)
        return np.asarray(out)

    def get(self, key: int) -> np.ndarray:
        return self.multi_get([key])[0]

    # getOrInit == get: storage is eagerly init'ed per key (see
    # TableSpec.init_array), so absent keys already hold init values.
    get_or_init = get
    multi_get_or_init = multi_get

    def multi_update(self, keys: Sequence[int], deltas: np.ndarray) -> None:
        k = jnp.asarray(keys, dtype=jnp.int32)
        d = jnp.asarray(deltas)
        with self._lock:
            self._arr = self._jitted("push", self.spec.push)(
                self._arr, k, d)
            self._bump_data_version()

    def update(self, key: int, delta: np.ndarray) -> None:
        self.multi_update([key], jnp.asarray(delta)[None])

    # Fire-and-forget variants: jax dispatch is already async; parity alias
    # (ref: Table.updateNoReply / multiUpdateNoReply).
    update_no_reply = update
    multi_update_no_reply = multi_update

    def write_all(self, values) -> None:
        """Whole-table key-order overwrite (host-level write_all).

        Routes through the table's jit cache (_jitted) like every other
        host op — callers used to wrap ``jax.jit(spec.write_all)`` in a
        fresh lambda per invocation, which built a new jit wrapper (and
        retraced) every call; the cache makes the program build
        once-per-table instead."""
        v = jnp.asarray(values)
        with self._lock:
            self._arr = self._jitted("write_all", self.spec.write_all)(
                self._arr, v
            )
            self._bump_data_version()

    def multi_put(self, keys: Sequence[int], values: np.ndarray) -> None:
        """Bulk set (no old-value return): the bulk-load insertion path
        (ref: BulkDataLoader -> table.multiPut, HdfsSplitFetcher.java:44)."""
        k = jnp.asarray(keys, dtype=jnp.int32)
        v = jnp.asarray(values)

        def _mput(a, kk, vv):
            b, o = self.spec.partitioner.locate(kk)
            return a.at[b, o].set(vv.astype(a.dtype))

        with self._lock:
            self._arr = self._jitted("multi_put", _mput)(self._arr, k, v)
            self._bump_data_version()

    def put(self, key: int, value: np.ndarray) -> np.ndarray:
        """Set, returning the previous value (ref: Table.put returns old).
        Read-old and write-new happen under one lock acquisition so a racing
        update can't fall between them."""
        k = jnp.asarray([key], dtype=jnp.int32)
        v = jnp.asarray(value)[None]

        def _put(a, kk, vv):
            b, o = self.spec.partitioner.locate(kk)
            return a[b, o], a.at[b, o].set(vv.astype(a.dtype))

        put_fn = self._jitted("put", _put)
        with self._lock:
            old, self._arr = put_fn(self._arr, k, v)
            self._bump_data_version()
        return np.asarray(old)[0]

    def remove(self, key: int) -> np.ndarray:
        """Reset a key to its init value, returning the removed value."""
        init_v = jax.vmap(self.spec.update_fn.init)(jnp.asarray([key], jnp.int32))
        init_v = jnp.broadcast_to(
            init_v.reshape(1, *([1] * len(self.spec.value_shape))),
            (1, *self.spec.value_shape),
        ) if init_v.ndim == 1 and self.spec.value_shape else init_v
        return self.put(key, np.asarray(init_v[0]))

    def pull_array(self, replicated: bool = False) -> jax.Array:
        """Full table in key order (device array; stays sharded until
        used). ``replicated=True`` all-gathers so EVERY process holds the
        full value addressable — the multi-process read path (a sharded
        result spans hosts and np.asarray refuses it); the collective is
        dispatched under the same lock/dispatch discipline as any other
        host op, so callers on pods must hold their dispatch unit."""
        with self._lock:  # dispatch under lock: see `array` docstring
            if replicated:
                return self._jitted(
                    "pull_all_rep", self.spec.pull_all,
                    out_shardings=NamedSharding(self._mesh, P()),
                )(self._arr)
            return self._jitted("pull_all", self.spec.pull_all)(self._arr)

    # -- re-sharding (the migration path) --------------------------------

    def reshard(self, new_mesh: Mesh) -> None:
        """Move the table onto a new mesh (executor add/remove / mesh carve).

        The reference's ownership-first migration (MigrationExecutor.java:
        163-253) exists to keep per-key RPCs correct while blocks move. Here
        the whole move is one XLA resharding: under the lock we (1) flip the
        layout ("ownership first"), (2) device_put — XLA moves bytes over
        ICI, (3) release the lock (the access latch). Host accessors block
        for the duration; in-flight jitted steps run on the pre-move snapshot
        and their commit lands on the new layout via sharding constraint at
        next dispatch.
        """
        from harmony_tpu.runtime import progcache

        with self._lock:
            old_sig = (
                None if self.spec.custom_update_fn
                else progcache.table_signature(self)
            )
            # transfer FIRST, mutate after: a rejected transfer (e.g. a
            # cross-process grow) must leave mesh/sharding/array
            # consistent, not a mesh pointing at a layout the array never
            # reached
            new_sharding = self._make_sharding(new_mesh)
            new_arr = reshard_array(self._arr, self._mesh, new_sharding)
            self._mesh = new_mesh
            self._sharding = new_sharding
            self._arr = new_arr
            self._jit_cache.clear()
            if old_sig is not None:
                # The departed layout's init executable can never hit again
                # under its old key; don't let it squat in the LRU.
                progcache.drop(lambda k: k == (old_sig, "table_init"))

    def install_array(self, arr: jax.Array) -> None:
        """Replace the table's storage with a pre-assembled global array
        on the CURRENT sharding (the elastic partial-restore path: each
        process builds its addressable shards from cached + checkpoint
        blocks and installs the jointly-constructed array — on a
        multi-process mesh no single process could materialize the whole
        payload that import_blocks' replicated-argument path needs)."""
        with self._lock:
            if arr.shape != self._arr.shape:
                raise ValueError(
                    f"install_array shape {arr.shape} != table "
                    f"{self._arr.shape}")
            if arr.sharding != self._sharding:
                raise ValueError(
                    "install_array: array sharding does not match the "
                    "table's current sharding")
            if arr.dtype != self._arr.dtype:
                raise ValueError(
                    f"install_array dtype {arr.dtype} != table "
                    f"{self._arr.dtype}")
            old, self._arr = self._arr, arr
            if old is not arr:  # same-sharding device_put may alias
                try:
                    old.delete()
                except RuntimeError:
                    pass  # already donated/deleted

    # -- per-block IO (checkpoint path) ----------------------------------

    def snapshot_blocks(
        self, block_ids: Optional[Sequence[int]] = None
    ) -> Dict[int, jax.Array]:
        """Atomic DEVICE-side snapshot of blocks: the per-block gathers are
        dispatched under the lock (one consistent ``_arr``; a concurrent
        donating step can't invalidate the source buffer), but nothing
        transfers to host — callers pull bytes when/where they want
        (e.g. a background checkpoint writer)."""
        ids = list(range(self.spec.num_blocks)) if block_ids is None else list(block_ids)
        with self._lock:
            return {int(b): self._arr[int(b)] for b in ids}

    def export_blocks(self, block_ids: Optional[Sequence[int]] = None) -> Dict[int, np.ndarray]:
        """Materialize blocks to host memory (ref: ChkpManagerSlave writes
        local blocks to per-block files, evaluator/impl/ChkpManagerSlave.java).
        Single-controller only — on a multi-process mesh use
        :meth:`addressable_blocks` (each process reads its own shards)."""
        return {b: np.asarray(a) for b, a in self.snapshot_blocks(block_ids).items()}

    def addressable_blocks(self) -> Dict[int, np.ndarray]:
        """THIS process's owned blocks as host arrays (the stage-1 pod
        checkpoint source; see owned_addressable_blocks)."""
        with self._lock:
            arr = self._arr
        return owned_addressable_blocks(arr)

    def import_blocks(self, blocks: Dict[int, np.ndarray]) -> None:
        """Install block payloads (restore path; tolerates any topology —
        data is re-inserted through normal table writes like the reference's
        restore, ChkpManagerMaster.java:49-61)."""
        if not blocks:
            return
        ids = jnp.asarray(sorted(blocks), dtype=jnp.int32)
        payload = jnp.asarray(np.stack([blocks[int(b)] for b in sorted(blocks)]))
        set_blocks = self._jitted(
            "import_blocks", lambda a, i, p: a.at[i].set(p.astype(a.dtype))
        )
        with self._lock:
            self._arr = set_blocks(self._arr, ids, payload)

    def drop(self) -> None:
        """Release storage (ref: AllocatedTable.drop)."""
        with self._lock:
            self._arr.delete()
            self._jit_cache.clear()
