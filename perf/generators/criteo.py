"""Criteo-shaped click data from a seed: one categorical id per field, the
fields' id ranges as the source publishes them, heavy-tailed inside each.

``field_sizes`` is the source's ``num_embeddings_per_feature`` (Criteo 1TB:
six fields of 40 M ids, thirteen of under 20 k, two of under 5). The table
has ``vocab_size`` rows, fewer than the fields' sum, so the large fields are
cut as the source itself cuts them — DLRM's ``--max-ind-range`` takes an id
modulo the range: field ``f`` owns ``min(field_sizes[f], cap)`` consecutive
ids, with ``cap`` (:func:`max_ind_range`) the largest range under which all
fields fit. The small fields keep their published size, and with it their
duplicates: a batch of 8,192 examples names each of a 36-id field's ids
some hundreds of times, whatever the skew.

Inside a field the id's rank follows a truncated power law with exponent
``zipf_a`` (inverse CDF of the continuous law, floored), rank 0 the
hottest. The label is a Bernoulli of a hidden per-id affinity (a hash, so
no vocabulary-sized array is ever made) around a base click rate of about
a quarter.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def max_ind_range(field_sizes: Sequence[int], vocab_size: int) -> int:
    """The largest ``cap`` with ``sum(min(size, cap)) <= vocab_size``."""
    sizes = np.asarray(field_sizes, np.int64)
    lo, hi = 1, int(sizes.max())
    if int(np.minimum(sizes, lo).sum()) > vocab_size:
        raise ValueError(f"{len(sizes)} fields do not fit {vocab_size} rows")
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if int(np.minimum(sizes, mid).sum()) <= vocab_size:
            lo = mid
        else:
            hi = mid - 1
    return lo


def field_ranges(field_sizes: Sequence[int], vocab_size: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``(first id, number of ids)`` of each field, ``[num_fields]`` int64."""
    cap = max_ind_range(field_sizes, vocab_size)
    per = np.minimum(np.asarray(field_sizes, np.int64), cap)
    return np.cumsum(per) - per, per


def _affinity(ids: np.ndarray) -> np.ndarray:
    """Per-id hidden affinity in [-1, 1): a multiplicative hash of the id."""
    h = (ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(40)
    return h.astype(np.float64) * 2.0 ** -23 - 1.0


def make(n: int, vocab_size: int, field_sizes: Sequence[int],
         zipf_a: float = 1.05, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """``(ids [n, num_fields] int32, y [n] float32)``."""
    if not zipf_a > 1.0:
        raise ValueError("zipf_a must exceed 1")
    rng = np.random.default_rng(seed)
    first, per = field_ranges(field_sizes, vocab_size)
    u = rng.random((n, len(per)))
    e = 1.0 - zipf_a
    # inverse CDF of p(x) ~ x^-a on [1, per + 1), per field
    top = (per + 1).astype(np.float64)[None, :] ** e
    rank = np.floor(((top - 1.0) * u + 1.0) ** (1.0 / e)) - 1.0
    rank = np.clip(rank, 0, per[None, :] - 1).astype(np.int64)
    ids = first[None, :] + rank
    logits = -1.1 + 1.5 * _affinity(ids).sum(axis=1) / np.sqrt(len(per))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return ids.astype(np.int32), y
