"""What a looped tenant with an exit gate (``TransformerConfig.loop_steps`` >
1 under ``exit_gate``) tells an operator: where the exit mass lies.

A step of such a tenant reports, beside its scalars, the vectors ``exit_mass
[T]`` — the sum over the step's positions of the exit distribution ``p(t)``,
so its own sum is the positions stepped over — and ``ce_by_exit [T]``, each
pass's mean cross-entropy, which the worker's metric drain hands to the
trainer (``Trainer.observe_step_vectors``) and the trainer hands here:

  * ``harmony_loop_exit_mass_total{job,step}`` — the sum over the drained
    steps' positions of ``p(step)`` (``step`` 1-based: the pass);
  * ``harmony_loop_exit_positions_total{job}`` — positions stepped over;
  * ``harmony_loop_exit_ce{job,step}`` — pass ``step``'s mean cross-entropy
    in the newest drained step (a later pass that reads no better than an
    earlier one is a loop that does no work).

``mass / positions`` is the job's mean exit distribution: ``(1/2, 1/4, 1/8,
1/8)`` under a fresh gate of four passes (``lam`` = 0.5), one pass near 1
when the gate has collapsed onto it.
"""
from __future__ import annotations

import numpy as np


def _families():
    """The two counters and the gauge (the names stand as literals: the lint
    pairs them with docs/OBSERVABILITY.md)."""
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.counter("harmony_loop_exit_mass_total",
                        "Sum over a looped job's drained positions of the "
                        "exit distribution's mass on each pass",
                        ("job", "step")),
            reg.counter("harmony_loop_exit_positions_total",
                        "Positions a looped job with an exit gate stepped "
                        "over", ("job",)),
            reg.gauge("harmony_loop_exit_ce",
                      "Mean cross-entropy of each pass's exit in a looped "
                      "job's newest drained step", ("job", "step")))


def observe(job: str, mass: np.ndarray, ce: np.ndarray) -> None:
    """Add the drained steps' ``exit_mass [steps, T]``; set the newest
    step's ``ce_by_exit [steps, T]``."""
    mass = np.asarray(mass, np.float64)
    mass = mass.reshape(-1, mass.shape[-1]).sum(axis=0)
    newest = np.asarray(ce, np.float64).reshape(-1, mass.shape[0])[-1]
    by_step, positions, ce_gauge = _families()
    for t, (m, c) in enumerate(zip(mass, newest), start=1):
        by_step.labels(job=job, step=str(t)).inc(float(m))
        ce_gauge.labels(job=job, step=str(t)).set(float(c))
    positions.labels(job=job).inc(float(mass.sum()))
