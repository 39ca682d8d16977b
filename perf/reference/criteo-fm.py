"""Plain reference for the keyed configurations (``criteo-fm``,
``criteo-fm-x4``): gather -> score -> gradient -> scatter-add SGD in
straight ``jax.numpy`` float32 under ``jax.default_matmul_precision(
"highest")``, holding only the rows the replayed batches touch — no
kernels, no table, no jobserver.

The model is the repo's factorization machine (apps/widedeep.py FMTrainer):
rows ``[w_i, v_i[0..k-1]]`` per feature id and one bias row ``[w0, 0...]``
at key ``vocab_size``;

    score = w0 + sum_s w[id_s] + 1/2 sum_f [(sum_s v[id_s])^2 - sum_s v[id_s]^2]
    loss  = mean logistic loss(score, y) + l2 * mean(pulled rows^2)

and one SGD step adds ``-lr * d loss / d row`` per OCCURRENCE of a row in
the batch (duplicates fold by addition). The reported figure is the
logistic loss alone, as the trainer reports it. ``l2`` (1e-4) and the
initial scale (0.05) are FMTrainer's defaults, which the job does not set.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perf.trainers.seeded_fm import seeded_rows

L2 = 1e-4
INIT_SCALE = 0.05


def replay(app: Dict[str, Any], data: Sequence[np.ndarray], batch: int,
           steps: int, seed: int, ablate: Optional[str] = None) -> List[float]:
    """Loss of each of the first ``steps`` steps (batch ``i`` is rows
    ``[i * batch, (i + 1) * batch)`` of the data set, cycling per epoch).
    ``ablate`` breaks the arithmetic on purpose — ``"no_interaction"`` /
    ``"no_l2"`` — so that perf/tests can show what the tolerance sees."""
    ids_all, y_all = np.asarray(data[0]), np.asarray(data[1])
    nb = ids_all.shape[0] // batch
    vocab, S = int(app["vocab_size"]), int(app["num_slots"])
    width = 1 + int(app["emb_dim"])
    lr = float(app["step_size"])
    l2 = 0.0 if ablate == "no_l2" else L2

    def keys_of(i: int) -> np.ndarray:
        ids = ids_all[(i % nb) * batch:(i % nb + 1) * batch]
        return np.concatenate([ids.reshape(-1), [vocab]]).astype(np.int64)

    touched = np.unique(np.concatenate([keys_of(i) for i in range(min(steps, nb))]))
    # pad to a multiple of 2^18 rows with keys nothing names, so that the
    # programs below have the same shapes whatever the seed drew and the
    # compile cache finds them again
    pad = -len(touched) % (1 << 18)
    touched = np.concatenate([touched, vocab + 1 + np.arange(pad)])

    @jax.jit
    def init(keys, seed):
        rows = seeded_rows(keys, width, seed, INIT_SCALE)
        return jnp.where((keys < vocab)[:, None], rows, 0.0)  # bias row: 0

    rows = init(jnp.asarray(touched, jnp.int32),
                jnp.uint32(seed & 0xFFFFFFFF))

    def loss_fn(pulled, y):
        emb = pulled[:-1].reshape(batch, S, width)
        w, v, w0 = emb[..., 0], emb[..., 1:], pulled[-1, 0]
        score = w0 + w.sum(axis=1)
        if ablate != "no_interaction":
            sv = v.sum(axis=1)
            score = score + 0.5 * (sv * sv - (v * v).sum(axis=1)).sum(axis=-1)
        ce = jnp.mean(jnp.maximum(score, 0) - score * y
                      + jnp.log1p(jnp.exp(-jnp.abs(score))))
        return ce + l2 * jnp.mean(pulled * pulled), ce

    @jax.jit
    def step(rows, idx, y):
        (_, ce), g = jax.value_and_grad(loss_fn, has_aux=True)(rows[idx], y)
        return rows.at[idx].add(-lr * g), ce

    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            idx = jnp.asarray(np.searchsorted(touched, keys_of(i)), jnp.int32)
            y = jnp.asarray(y_all[(i % nb) * batch:(i % nb + 1) * batch])
            rows, ce = step(rows, idx, y)
            losses.append(float(ce))
    return losses
