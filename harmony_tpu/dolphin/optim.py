"""Stateful training optimizers over PS-table storage.

The reference's trainers are plain SGD-family updates applied through the
table's UpdateFunction (server-side fold). Momentum/Adam need per-parameter
STATE shared exactly like the parameters — so the state lives in the same
elastic table, as extra row sections:

    rows = [ params | m (slot 1) | v (slot 2) | counter block ]

Every section reshards, checkpoints and migrates with the table for free.
The update math is pure (jit-safe) and elementwise, over arrays of any one
shape; trainers split their pulled rows into sections, call :func:`apply`
on them as they lie (models/pytree_trainer.py: ``[rows, row_width]``), and
push back per-section deltas (additive fold — delta = new - old).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

SLOTS = {"sgd": 0, "momentum": 1, "adagrad": 1, "rmsprop": 1, "adam": 2}


def num_slots(name: str) -> int:
    try:
        return SLOTS[name]
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}; have {sorted(SLOTS)}") from None


def apply(
    name: str,
    params: jnp.ndarray,       # any shape (a row section, a flat vector)
    grads: jnp.ndarray,        # params' shape
    m: jnp.ndarray,            # params' shape: slot-1 state (ignored for sgd)
    v: jnp.ndarray,            # params' shape: slot-2 state (adam only)
    t: jnp.ndarray,            # scalar step count AFTER this update (>= 1)
    hyper: Dict[str, jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (new_params, new_m, new_v). ``hyper``: lr (required),
    beta1/beta2/eps (adam, defaulted), mu (momentum), rho (rmsprop).
    Slot-1 meaning per optimizer: momentum=velocity, adagrad=sum of
    squared grads, rmsprop=EMA of squared grads."""
    lr = hyper["lr"]
    if name == "sgd":
        return params - lr * grads, m, v
    if name == "momentum":
        mu = hyper.get("mu", 0.9)
        new_m = mu * m + grads
        return params - lr * new_m, new_m, v
    if name == "adagrad":
        eps = hyper.get("eps", 1e-8)
        new_m = m + grads * grads
        return params - lr * grads / (jnp.sqrt(new_m) + eps), new_m, v
    if name == "rmsprop":
        rho = hyper.get("rho", 0.9)
        eps = hyper.get("eps", 1e-8)
        new_m = rho * m + (1 - rho) * grads * grads
        return params - lr * grads / (jnp.sqrt(new_m) + eps), new_m, v
    if name == "adam":
        b1 = hyper.get("beta1", 0.9)
        b2 = hyper.get("beta2", 0.999)
        eps = hyper.get("eps", 1e-8)
        new_m = b1 * m + (1 - b1) * grads
        new_v = b2 * v + (1 - b2) * grads * grads
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        return params - lr * mhat / (jnp.sqrt(vhat) + eps), new_m, new_v
    raise ValueError(f"unknown optimizer {name!r}")
