"""Factorization Machine + Wide&Deep — sparse-embedding recommenders.

BASELINE.md config 5 ("Wide&Deep / factorization-machine — new app; sparse
embedding tables"): the workload class the reference's per-key getOrInit/
update semantics exist for (embedding rows pulled/pushed by key), and the
hard TPU case called out in SURVEY.md §7.3 — per-key access does not map to
collectives.

TPU realization: ``pull_mode = "keys"`` — each batch names exactly the
embedding rows it touches; inside the ONE fused step the pull is an XLA
gather on the hash-partitioned table, and the push is a scatter-add whose
duplicate keys (the same feature appearing in many examples) fold on-device.
Model layout (one PS table, width ``1 + k``):

  key 0..vocab-1   : [w_i, v_i[0..k-1]]   per-feature wide weight + embedding
  key vocab        : [w0, 0...]           global bias
  key vocab+1...   : raveled MLP params   (WideDeepTrainer only), stored in
                     rows of the same width so deep weights ride the same
                     sparse pull/push path.

FM score:  w0 + Σ_s w[id_s] + ½ Σ_f [(Σ_s v[id_s])² − Σ_s v[id_s]²]
Wide&Deep: wide term + MLP(concat of the S slot embeddings).
Data: (ids [B, S] int32 slot-feature ids, y [B] 0/1 labels).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harmony_tpu.config.params import TableConfig
from harmony_tpu.dolphin.trainer import Trainer
from harmony_tpu.table.update import UpdateFunction, get_update_fn
from harmony_tpu.tracing.stepscopes import step_scope

# Sparse mode reserves the TOP of the int32 key space for the non-embedding
# rows (bias / raveled MLP); feature ids must stay below this base.
SPARSE_EXTRA_BASE = 2**31 - 8192


def make_embed_init(width: int, scale: float, seed: int) -> UpdateFunction:
    """Update-fn factory for hash-sharded embedding tables: a key admitted
    by getOrInit derives its row deterministically from a hash of
    (key, column) — small uniform noise for embedding components, 0 for the
    wide weight, and 0 for reserved tail rows (bias/MLP, which the chief
    seeds explicitly). Lazy init without ever enumerating the vocabulary;
    referenced by durable name (see table.update.get_update_fn factories)."""

    def init(key):
        from harmony_tpu.table.hashtable import _mix32

        j = jnp.arange(width, dtype=jnp.uint32)
        h = _mix32(
            _mix32(jnp.uint32(key), 0x9E3779B9 ^ seed)
            ^ j * jnp.uint32(0x9E3779B9),
            0x85EBCA6B,
        )
        u = h.astype(jnp.float32) / jnp.float32(2**32) * 2.0 - 1.0
        row = (scale * u).at[0].set(0.0)
        return jnp.where(key >= SPARSE_EXTRA_BASE, jnp.zeros(width), row)

    base = get_update_fn("add")
    return UpdateFunction(
        name="embed-init",  # replaced with the durable name by the registry
        init=init,
        combine=base.combine,
        apply=base.apply,
        scatter_mode="add",
    )


class FMTrainer(Trainer):
    pull_mode = "keys"

    def __init__(
        self,
        vocab_size: int,
        num_slots: int,
        emb_dim: int = 8,
        step_size: float = 0.1,
        l2: float = 1e-4,
        sparse: bool = False,
        slot_budget: int = 0,
    ) -> None:
        """``sparse=True`` backs the model with a DeviceHashTable: feature
        ids come from the whole int32 domain (below SPARSE_EXTRA_BASE) and
        ``slot_budget`` bounds admitted rows (default 4x vocab_size, which
        then only scales the budget — ids are NOT limited to it). Embedding
        rows initialize LAZILY at first touch via a deterministic per-key
        update-fn init (no vocab-wide bulk init is possible or needed)."""
        self.vocab_size = vocab_size
        self.num_slots = num_slots
        self.k = emb_dim
        self.step_size = step_size
        self.l2 = l2
        self.sparse = sparse
        self.slot_budget = slot_budget or 4 * vocab_size

    # -- table schema ----------------------------------------------------

    @property
    def width(self) -> int:
        return 1 + self.k

    @property
    def num_extra_rows(self) -> int:
        return 1  # the bias row

    def model_table_config(self, table_id: str = "fm-model", num_blocks: int = 0) -> TableConfig:
        if self.sparse:
            cap = self.slot_budget + self.num_extra_rows
            return TableConfig(
                table_id=table_id,
                capacity=cap,
                value_shape=(self.width,),
                num_blocks=num_blocks or min(cap, 256),
                is_ordered=False,
                update_fn=self._register_sparse_init(),
                sparse=True,
            )
        cap = self.vocab_size + self.num_extra_rows
        return TableConfig(
            table_id=table_id,
            capacity=cap,
            value_shape=(self.width,),
            num_blocks=num_blocks or min(cap, 256),
            is_ordered=False,          # hash-partitioned: the sparse case
            update_fn="add",
        )

    def _register_sparse_init(self) -> str:
        """Durable name of the lazy per-key init fn — a factory reference
        the update-fn registry can resolve IN ANY PROCESS (checkpoint
        manifests persist this string; restore must not depend on a live
        FMTrainer having registered anything)."""
        return (
            "harmony_tpu.apps.widedeep:make_embed_init"
            f"?width={self.width}&scale={self.init_scale}&seed={self.seed}"
        )

    def hyperparams(self) -> Dict[str, float]:
        return {"lr": self.step_size}

    # -- lifecycle -------------------------------------------------------

    init_scale: float = 0.05
    seed: int = 0

    @property
    def extra_base(self) -> int:
        """First reserved (non-embedding) key: right after the vocab for
        dense tables, the top of the int32 space for sparse ones."""
        return SPARSE_EXTRA_BASE if self.sparse else self.vocab_size

    def init_global_settings(self, ctx) -> None:
        """Seed embedding vectors with small noise (zero embeddings make the
        FM interaction term identically zero — nothing to learn from); wide
        weights and bias start at 0. Chief-only, through the normal
        multi_put path (ref: initial model values pushed into the table).
        Sparse mode: embeddings init LAZILY per key (the table's update-fn
        init) — only the reserved tail rows are seeded here."""
        if self.sparse:
            # reserved keys must stay <= MAX_KEY (2^31 - 3): base + n - 1
            assert self.num_extra_rows <= 2**31 - 2 - SPARSE_EXTRA_BASE
        if self.init_scale <= 0:
            return
        rng = np.random.default_rng(self.seed)
        if not self.sparse:
            rows = np.zeros((self.vocab_size, self.width), np.float32)
            rows[:, 1:] = rng.normal(scale=self.init_scale,
                                     size=(self.vocab_size, self.k))
            ctx.model_table.multi_put(list(range(self.vocab_size)), rows)
        extra = self._init_extra_rows(rng)
        if extra is not None:
            keys = list(range(self.extra_base, self.extra_base + len(extra)))
            dropped = ctx.model_table.multi_put(keys, extra)
            if self.sparse and dropped:
                # the model's OWN parameters (bias/MLP rows) failed
                # admission — training would silently pin them to zero
                raise RuntimeError(
                    f"{dropped} reserved model rows not admitted; raise "
                    f"slot_budget (currently {self.slot_budget})"
                )

    def _init_extra_rows(self, rng) -> np.ndarray | None:
        return None  # FM: bias row stays zero

    # -- pure parts ------------------------------------------------------

    def pull_keys(self, batch) -> jnp.ndarray:
        """The batch's embedding rows + the tail rows (bias / MLP): exactly
        the per-key pull the reference's multiGetOrInit does, as one gather."""
        ids = batch[0]
        B = ids.shape[0]
        extra = self.extra_base + jnp.arange(self.num_extra_rows, dtype=jnp.int32)
        return jnp.concatenate([ids.reshape(-1), extra])

    def _split(self, rows: jnp.ndarray, B: int):
        """rows [B*S + extra, width] -> (w [B,S], v [B,S,k], tail rows)."""
        n = B * self.num_slots
        emb = rows[:n].reshape(B, self.num_slots, self.width)
        return emb[..., 0], emb[..., 1:], rows[n:]

    def _scores(self, w, v, tail):
        lin = w.sum(axis=1) + tail[0, 0]                     # [B]
        sv = v.sum(axis=1)                                   # [B, k]
        inter = 0.5 * (sv * sv - (v * v).sum(axis=1)).sum(axis=-1)
        return lin + inter

    def compute(self, model, batch, hyper):
        ids, y = batch
        B = ids.shape[0]

        def loss_fn(rows):
            with step_scope("fm.interact"):
                w, v, tail = self._split(rows, B)
                logits = self._scores(w, v, tail)
            with step_scope("fm.loss"):
                ce = jnp.mean(
                    jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
                )
                return ce + self.l2 * (rows * rows).mean(), ce

        (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(model)
        # Duplicate ids: jax.grad of the gather already accumulated their
        # cotangents per occurrence; the table's scatter-add push folds the
        # per-occurrence deltas — same result as the reference's server-side
        # per-key update application.
        return -hyper["lr"] * grads, {"loss": ce}

    def _gather_rows(self, model: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
        """Assemble the same row layout the fused step's keyed pull produces,
        from the full [capacity, width] table (evaluation path)."""
        tail = model[self.vocab_size:self.vocab_size + self.num_extra_rows]
        return jnp.concatenate([model[ids.reshape(-1)], tail])

    def evaluate(self, model, batch) -> Dict[str, jnp.ndarray]:
        ids, y = batch
        B = ids.shape[0]
        w, v, tail = self._split(self._gather_rows(model, ids), B)
        logits = self._scores(w, v, tail)
        ce = jnp.mean(
            jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        )
        acc = jnp.mean(((logits > 0).astype(jnp.float32) == y).astype(jnp.float32))
        return {"loss": ce, "accuracy": acc}

    def predict(self, model: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
        w, v, tail = self._split(self._gather_rows(model, ids), ids.shape[0])
        return jax.nn.sigmoid(self._scores(w, v, tail))

    def evaluate_sparse(self, table, batch) -> Dict[str, jnp.ndarray]:
        """Offline evaluation against a hash-backed model: pull exactly the
        rows the test batch names (read-only lookup — evaluation must not
        admit keys) and reuse the dense metric math on the row layout."""
        ids, y = batch
        B = np.asarray(ids).shape[0]
        keys = np.concatenate([
            np.asarray(ids).reshape(-1),
            self.extra_base + np.arange(self.num_extra_rows, dtype=np.int64),
        ])
        rows = jnp.asarray(table.multi_get(keys))
        w, v, tail = self._split(rows, B)
        logits = self._scores(w, v, tail)
        y = jnp.asarray(y)
        ce = jnp.mean(
            jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        )
        acc = jnp.mean(((logits > 0).astype(jnp.float32) == y).astype(jnp.float32))
        return {"loss": ce, "accuracy": acc}


class WideDeepTrainer(FMTrainer):
    """FM wide term + a one-hidden-layer MLP over the concatenated slot
    embeddings (the deep tower), deep weights stored as extra table rows."""

    def __init__(
        self,
        vocab_size: int,
        num_slots: int,
        emb_dim: int = 8,
        hidden: int = 32,
        step_size: float = 0.1,
        l2: float = 1e-4,
        sparse: bool = False,
        slot_budget: int = 0,
    ) -> None:
        super().__init__(vocab_size, num_slots, emb_dim, step_size, l2,
                         sparse=sparse, slot_budget=slot_budget)
        self.hidden = hidden
        d_in = num_slots * emb_dim
        # raveled [W1 (d_in x h), b1 (h), W2 (h), b2 (1)]
        self._n_mlp = d_in * hidden + hidden + hidden + 1

    @property
    def num_extra_rows(self) -> int:
        return 1 + -(-self._n_mlp // self.width)  # bias row + MLP rows

    def _init_extra_rows(self, rng) -> np.ndarray:
        """Bias row (zeros) + He-init W1 / small W2, raveled into rows."""
        d_in, h = self.num_slots * self.k, self.hidden
        flat = np.zeros((self._n_mlp,), np.float32)
        flat[: d_in * h] = rng.normal(scale=(2.0 / d_in) ** 0.5, size=d_in * h)
        o = d_in * h + h
        flat[o:o + h] = rng.normal(scale=h ** -0.5, size=h)
        n_rows = self.num_extra_rows - 1
        padded = np.zeros((n_rows * self.width,), np.float32)
        padded[: self._n_mlp] = flat
        rows = np.concatenate(
            [np.zeros((1, self.width), np.float32),      # bias row
             padded.reshape(n_rows, self.width)]
        )
        return rows

    def _mlp(self, flat: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
        d_in, h = self.num_slots * self.k, self.hidden
        o = 0
        W1 = flat[o:o + d_in * h].reshape(d_in, h); o += d_in * h
        b1 = flat[o:o + h]; o += h
        W2 = flat[o:o + h]; o += h
        b2 = flat[o]
        z = jax.nn.relu(x @ W1 + b1)
        return z @ W2 + b2

    def _scores(self, w, v, tail):
        B = w.shape[0]
        wide = w.sum(axis=1) + tail[0, 0]
        flat = tail[1:].reshape(-1)[: self._n_mlp]
        deep = self._mlp(flat, v.reshape(B, -1))
        return wide + deep


def make_synthetic(
    n: int, vocab_size: int, num_slots: int, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """Synthetic CTR data: each slot draws a feature id from its own Zipf-ish
    range; the label depends on a hidden per-feature affinity plus a pairwise
    interaction, so FM (and the deep tower) have real signal to learn."""
    rng = np.random.default_rng(seed)
    per = vocab_size // num_slots
    ids = np.stack(
        [s * per + rng.integers(0, per, size=n) for s in range(num_slots)], axis=1
    ).astype(np.int32)
    affinity = rng.normal(scale=1.0, size=vocab_size)
    hidden = rng.normal(scale=0.7, size=(vocab_size, 4))
    lin = affinity[ids].sum(axis=1)
    sv = hidden[ids].sum(axis=1)
    inter = 0.5 * ((sv * sv).sum(-1) - (hidden[ids] ** 2).sum(axis=(1, 2)))
    logits = 0.8 * lin + 0.3 * inter - np.median(0.8 * lin + 0.3 * inter)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return ids, y


def make_synthetic_sparse(
    n: int, vocab_size: int, num_slots: int, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """Same CTR task, but ids spread (injectively up to rare collisions)
    over the whole admissible int32 domain — the workload only a hash-backed
    table can hold (sparse=True trainers)."""
    ids, y = make_synthetic(n, vocab_size, num_slots, seed)
    # ids land in [1, SPARSE_EXTRA_BASE-1]: key 0 is reserved by the hash
    # table (XLA's pad value must be an invalid key)
    spread = (
        (ids.astype(np.int64) * 2654435761 + 99991) % (SPARSE_EXTRA_BASE - 2)
    ).astype(np.int32) + 1
    return spread, y
