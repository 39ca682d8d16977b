"""Token sequences drawn uniformly over the vocabulary from a seed.

The content of the data does not change a training step's arithmetic; the
loss starts near ``ln(vocab_size)`` and falls as the epoch's few sequences
are memorised, which is all the benchmark's progress check needs.
"""
from __future__ import annotations

import numpy as np


def make(num_seqs: int, seq_len: int, vocab_size: int,
         seed: int = 0) -> np.ndarray:
    """``[num_seqs, seq_len] int32``. The loss shifts by one, so a model of
    ``max_seq`` positions takes ``seq_len = max_seq + 1``."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab_size, size=(num_seqs, seq_len),
                        dtype=np.int32)
