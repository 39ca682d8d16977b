"""A block-diffusion step's batches, drawn from a seed: token sequences, the
positions the step masks and each block's masking rate.

A sequence of ``seq_len`` tokens lies in blocks of ``block``; each block
draws a rate ``t ~ U(EPS, 1]`` and masks each of its tokens with probability
``t`` (SDAR, arXiv:2510.06303; BD3-LMs, arXiv:2503.09573: the loss weighs a
masked position by ``1 / t`` of its block). The tokens are uniform over the
held vocabulary rows but the LAST one, which stands for the model's mask
token and never occurs as data. The noise is DATA here, not drawn in the
step: the same seed gives the same bytes, so the plain reference replays the
very batches the program trained on.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

#: the least masking rate a block may draw: ``1 / t`` stays under 1,000
EPS = 1e-3


def make(num_seqs: int, seq_len: int, vocab_size: int, block: int,
         seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(tokens [n, L] int32, masked [n, L] int8, rate [n, L / block]
    float32)``. There is no shift: a model of ``max_seq`` positions takes
    ``seq_len = max_seq``."""
    if seq_len % block:
        raise ValueError(f"seq_len {seq_len} is not whole blocks of {block}")
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab_size - 1, size=(num_seqs, seq_len),
                          dtype=np.int32)
    rate = (EPS + (1.0 - EPS) * (1.0 - rng.random(
        (num_seqs, seq_len // block)))).astype(np.float32)
    masked = rng.random((num_seqs, seq_len)) < np.repeat(rate, block, axis=1)
    return tokens, masked.astype(np.int8), rate
