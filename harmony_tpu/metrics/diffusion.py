"""What a block-diffusion tenant (``TransformerConfig.objective =
"block_diffusion"``) tells an operator: how much of its corpus each step
noised.

A step of such a tenant reports, beside its scalars, the vector
``diffusion_tokens [masked, all]`` — the positions of the batch that were
masked (the only ones the loss reads) and the positions there were — which
the worker's metric drain hands to the trainer
(``Trainer.observe_step_vectors``) and the trainer hands here:

  * ``harmony_diffusion_masked_tokens_total{job}`` — corpus tokens masked,
    over the drained steps;
  * ``harmony_diffusion_tokens_total{job}`` — corpus tokens stepped over.

Their ratio is the mean masking rate the job has trained under (0.5 under
rates drawn uniformly; a loader that stopped drawing reads 0 or 1).
"""
from __future__ import annotations

import numpy as np


def _counters():
    """The two counters (the names stand as literals: the lint pairs them
    with docs/OBSERVABILITY.md)."""
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.counter("harmony_diffusion_masked_tokens_total",
                        "Corpus tokens a block-diffusion job masked, over "
                        "the drained steps", ("job",)),
            reg.counter("harmony_diffusion_tokens_total",
                        "Corpus tokens a block-diffusion job stepped over",
                        ("job",)))


def observe(job: str, tokens: np.ndarray) -> None:
    """Add the drained steps' ``diffusion_tokens [steps, 2]``."""
    sums = np.asarray(tokens, np.float64).reshape(-1, 2).sum(axis=0)
    for counter, n in zip(_counters(), sums):
        counter.labels(job=job).inc(float(n))
