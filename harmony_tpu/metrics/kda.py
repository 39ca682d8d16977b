"""What a model with KDA blocks (gated delta-rule linear attention,
ops/kda.py) tells an operator, and which kinds of block a tenant runs.

A step of such a tenant reports, beside its scalars, each KDA block's mean
decay ``exp(g)`` and mean ``beta`` — vectors ``[kda blocks]``, which the
worker's metric drain hands to the trainer (``Trainer.observe_step_vectors``)
and the trainer hands here:

  * ``harmony_kda_decay_mean{job,layer}`` — the mean over tokens, heads and
    channels of the per-channel decay at the newest drained step; ``layer``
    is the block's index in the model. A decay pinned at 1 never forgets (the
    state saturates), one pinned at 0 remembers one token;
  * ``harmony_kda_beta_mean{job,layer}`` — the mean write strength;
  * ``harmony_model_layers{job,kind}`` — how many blocks of each kind
    (``TransformerConfig.layer_kinds()``: ``kda`` | ``mha`` | ``mla`` |
    ``swa`` | ``full``) the
    job's model has, set when the job initialises its table.

Under a profiler session the light span ``kda.observe`` marks each drain.
STATUS shows, per tenant, ``layer_kinds`` (:func:`kinds_by_job`) and ``kda:
{decay_mean, beta_mean}`` over the blocks (:func:`stats_by_job`).
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def _families():
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.gauge("harmony_kda_decay_mean",
                      "Mean per-channel decay exp(g) of a KDA block at the "
                      "newest drained step", ("job", "layer")),
            reg.gauge("harmony_kda_beta_mean",
                      "Mean write strength beta of a KDA block at the "
                      "newest drained step", ("job", "layer")),
            reg.gauge("harmony_model_layers",
                      "Blocks of each token-mixer kind in the job's model",
                      ("job", "kind")))


def note_layer_kinds(job: str, kinds: Sequence[str]) -> None:
    """Record the job's blocks by kind (``layer_kinds()``)."""
    gauge = _families()[2]
    for kind in sorted(set(kinds)):
        gauge.labels(job=job, kind=kind).set(list(kinds).count(kind))


def observe(job: str, decay: np.ndarray, beta: np.ndarray,
            layers: Sequence[int]) -> None:
    """Set the gauges from ``decay`` / ``beta [steps, kda blocks]``
    (the newest step stands); ``layers`` are those blocks' indices."""
    from harmony_tpu.tracing import trace_span

    decay, beta = np.asarray(decay, np.float64), np.asarray(beta, np.float64)
    with trace_span("kda.observe", record=False, job=job, steps=len(decay)):
        g_decay, g_beta, _ = _families()
        for i, layer in enumerate(layers):
            g_decay.labels(job=job, layer=str(layer)).set(float(decay[-1, i]))
            g_beta.labels(job=job, layer=str(layer)).set(float(beta[-1, i]))


def kinds_by_job() -> Dict[str, Dict[str, int]]:
    """``{job: {kind: blocks}}`` from the gauge."""
    out: Dict[str, Dict[str, int]] = {}
    try:
        for (job, kind), c in _families()[2].children():
            out.setdefault(job, {})[kind] = int(c.value)
    except Exception:
        return {}
    return out


def stats_by_job() -> Dict[str, Dict[str, float]]:
    """``{job: {decay_mean, beta_mean}}``: the gauges' mean over the job's
    KDA blocks."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        g_decay, g_beta, _ = _families()
        for name, gauge in (("decay_mean", g_decay), ("beta_mean", g_beta)):
            rows: Dict[str, list] = {}
            for (job, _layer), c in gauge.children():
                rows.setdefault(job, []).append(c.value)
            for job, values in rows.items():
                out.setdefault(job, {})[name] = sum(values) / len(values)
    except Exception:
        return {}
    return out
