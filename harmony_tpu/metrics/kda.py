"""What a model with recurrent layers — KDA or Gated DeltaNet blocks (gated
delta-rule linear attention with a decay a channel / a head, ops/kda.py) or
Mamba-2 state-space layers (ops/ssd.py) — tells an operator, and which kinds of layer a tenant runs.

A step of such a tenant reports, beside its scalars, two statistics of each
recurrent layer (``STATS``) — vectors ``[layers of the kind]``, which the
worker's metric drain hands to the trainer (``Trainer.observe_step_vectors``)
and the trainer hands here:

  * ``harmony_kda_decay_mean{job,layer}`` — the mean over tokens, heads and
    channels of the per-channel decay at the newest drained step; ``layer``
    is the block's index in the model. A decay pinned at 1 never forgets (the
    state saturates), one pinned at 0 remembers one token;
  * ``harmony_kda_beta_mean{job,layer}`` — the mean write strength;
  * ``harmony_gdn_decay_mean{job,layer}`` / ``harmony_gdn_beta_mean{job,
    layer}`` — a Gated DeltaNet block's mean decay ``exp(g)`` over tokens
    and VALUE heads (one scalar a head) and its mean write strength, read
    as KDA's;
  * ``harmony_ssd_decay_mean{job,layer}`` — a state-space layer's mean
    per-head decay ``exp(-dt exp(a_log))``, read the same way;
  * ``harmony_ssd_dt_mean{job,layer}`` — its mean step ``dt = softplus(dt +
    dt_bias)``: what a token writes with and forgets by;
  * ``harmony_model_layers{job,kind}`` — how many layers of each kind
    (``TransformerConfig.layer_kinds()``: ``kda`` | ``gdn`` | ``mha`` |
    ``mla`` | ``swa`` | ``full``, and a ``layer_pattern`` model's ``ssd`` | ``attn`` |
    ``moe``) the job's model has, set when the job initialises its table;
  * ``harmony_model_heads{job,kind}`` — the heads a block of each kind
    mixes with (a softmax block's QUERY heads, a ``gdn`` block's VALUE
    heads — the states it carries —, ``TransformerConfig.heads``:
    a model whose ``full`` and ``swa`` blocks differ in them reads two
    values), set at the same place.

Under a profiler session the light span ``kda.observe`` marks each drain.
STATUS shows, per tenant, ``layer_kinds`` (:func:`kinds_by_job`) and ``kda:
{decay_mean, beta_mean}`` / ``gdn: {decay_mean, beta_mean}`` / ``ssd:
{decay_mean, dt_mean}`` over the layers
(:func:`stats_by_job`).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np


#: a recurrent layer's two step statistics, by kind
STATS = {"kda": ("decay", "beta"), "gdn": ("decay", "beta"),
         "ssd": ("decay", "dt")}


def _gauges(kind: str):
    """The two gauges of ``kind``'s layers, in ``STATS``' order (the names
    stand as literals: the lint pairs them with docs/OBSERVABILITY.md)."""
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    if kind == "ssd":
        return (reg.gauge("harmony_ssd_decay_mean",
                          "Mean per-head decay of a Mamba-2 state-space "
                          "layer at the newest drained step",
                          ("job", "layer")),
                reg.gauge("harmony_ssd_dt_mean",
                          "Mean step dt of a Mamba-2 state-space layer at "
                          "the newest drained step", ("job", "layer")))
    if kind == "gdn":
        return (reg.gauge("harmony_gdn_decay_mean",
                          "Mean per-head decay exp(g) of a Gated DeltaNet "
                          "block at the newest drained step",
                          ("job", "layer")),
                reg.gauge("harmony_gdn_beta_mean",
                          "Mean write strength beta of a Gated DeltaNet "
                          "block at the newest drained step",
                          ("job", "layer")))
    return (reg.gauge("harmony_kda_decay_mean",
                      "Mean per-channel decay exp(g) of a KDA block at the "
                      "newest drained step", ("job", "layer")),
            reg.gauge("harmony_kda_beta_mean",
                      "Mean write strength beta of a KDA block at the "
                      "newest drained step", ("job", "layer")))


def _layers_gauge():
    from harmony_tpu.metrics.registry import get_registry

    return get_registry().gauge(
        "harmony_model_layers",
        "Blocks of each token-mixer kind in the job's model", ("job", "kind"))


def note_layer_kinds(job: str, kinds: Sequence[str],
                     heads: Optional[Dict[str, int]] = None,
                     loop_steps: int = 1) -> None:
    """Record the job's blocks by kind (``layer_kinds()``), for the kinds in
    ``heads`` the heads a block of that kind mixes with, and how many times
    a step passes the blocks (``loop_steps``: 1 but for a looped model)."""
    from harmony_tpu.metrics.registry import get_registry

    gauge = _layers_gauge()
    for kind in sorted(set(kinds)):
        gauge.labels(job=job, kind=kind).set(list(kinds).count(kind))
    get_registry().gauge(
        "harmony_model_loop_steps",
        "Times a step of the job's model passes its layers over ONE set of "
        "weights (1: an ordinary model)", ("job",)
    ).labels(job=job).set(loop_steps)
    if heads:
        by_kind = get_registry().gauge(
            "harmony_model_heads",
            "Heads of a block of each token-mixer kind in the job's model "
            "(query heads for softmax attention)", ("job", "kind"))
        for kind, n in sorted(heads.items()):
            by_kind.labels(job=job, kind=kind).set(n)


def observe(job: str, first: np.ndarray, second: np.ndarray,
            layers: Sequence[int], kind: str = "kda") -> None:
    """Set ``kind``'s gauges from its two statistics ``[steps, layers of
    the kind]`` (the newest step stands); ``layers`` are those layers'
    indices in the model."""
    from harmony_tpu.tracing import trace_span

    rows = [np.asarray(v, np.float64) for v in (first, second)]
    with trace_span("kda.observe", record=False, job=job, steps=len(rows[0])):
        for gauge, values in zip(_gauges(kind), rows):
            for i, layer in enumerate(layers):
                gauge.labels(job=job, layer=str(layer)).set(
                    float(values[-1, i]))


def kinds_by_job() -> Dict[str, Dict[str, int]]:
    """``{job: {kind: blocks}}`` from the gauge."""
    out: Dict[str, Dict[str, int]] = {}
    try:
        for (job, kind), c in _layers_gauge().children():
            out.setdefault(job, {})[kind] = int(c.value)
    except Exception:
        return {}
    return out


def stats_by_job(kind: str = "kda") -> Dict[str, Dict[str, float]]:
    """``{job: {<stat>_mean: ...}}``: ``kind``'s gauges' mean over the
    job's layers of that kind."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        for stat, gauge in zip(STATS[kind], _gauges(kind)):
            rows: Dict[str, list] = {}
            for (job, _layer), c in gauge.children():
                rows.setdefault(job, []).append(c.value)
            for job, values in rows.items():
                out.setdefault(job, {})[f"{stat}_mean"] = (
                    sum(values) / len(values))
    except Exception:
        return {}
    return out
