"""Expert-routing counters of dropless MoE tenants (models/moe.py).

A step of such a tenant reports, beside its scalars, the token-slots each
expert of each expert layer was chosen for — a vector, which the worker's
metric drain hands to the trainer (``Trainer.observe_step_vectors``) and the
trainer hands here:

  * ``harmony_moe_expert_tokens_total{job,layer,expert}`` — token-slots routed
    to every expert the ROUTER scores (held on this device or not); ``layer``
    is the block's index in the model, expert layers only;
  * ``harmony_moe_held_slots_total{job}`` — those of them routed to experts
    this device holds (the rows its grouped matmuls computed);
  * ``harmony_moe_absent_slots_total{job}`` — the rest of them: slots whose
    expert is held on another device; and, of a router with a "no expert"
    output (``moe_null_expert``), ``harmony_moe_null_slots_total{job}`` —
    token-slots that chose no expert, which no device computes (a job
    without that output has no such child). Apart, because the first is the
    deployment's cut and the second the model's own saving;
  * ``harmony_moe_experts_held{job}`` — how many experts (0 .. n-1) it holds;
  * ``harmony_moe_layer_calls_total{job}`` / ``harmony_moe_chunks_total{job}``
    — expert-layer calls (layers x steps), and the chunks of the layer's
    static capacity they ran (models/moe.py ``chunk_plan``: ``ceil(held /
    C)`` a call, host arithmetic on the same vector; 0 a call where the
    plain full-length path runs). Their ratio is 1.0 while every call's held
    slots fit one chunk;
  * ``harmony_moe_shared_gate_mean{job,layer}`` — of a layer whose shared
    MLP is gated (``moe_shared_gate``), the mean over tokens of ``sigmoid(x .
    shared_gate)`` at the newest drained step: pinned at 0 the shared expert
    is switched off, at 1 it is an ungated one.

Under a profiler session the span ``moe.observe`` (light; opened at each
drain) carries the drained steps' held token-slots one by one
(``held_slots="n/n/..."``, summed over the layers): the only per-STEP record
of the rows the grouped matmuls computed, which a trace reader lays over the
kernels' own events.

STATUS shows, per tenant, ``moe: {held_slot_share, load_max_over_mean}``
(:func:`stats_by_job`): the share of all token-slots this device computed,
and the most loaded held expert's tokens over the held experts' mean.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np


def _families():
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.counter(
                "harmony_moe_expert_tokens_total",
                "Token-slots routed to each expert of each expert layer",
                ("job", "layer", "expert")),
            reg.counter(
                "harmony_moe_held_slots_total",
                "Token-slots routed to experts this device holds", ("job",)),
            reg.gauge(
                "harmony_moe_experts_held",
                "Experts (0 .. n-1) of each expert layer held on this device",
                ("job",)),
            reg.counter(
                "harmony_moe_layer_calls_total",
                "Expert-layer calls (expert layers x steps)", ("job",)),
            reg.counter(
                "harmony_moe_chunks_total",
                "Chunks of the static capacity those calls ran (0 a call "
                "on the plain path)", ("job",)))


def _dropped_families():
    """Slots this device computed nothing for, by why."""
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.counter(
                "harmony_moe_absent_slots_total",
                "Token-slots routed to experts held on another device",
                ("job",)),
            reg.counter(
                "harmony_moe_null_slots_total",
                "Token-slots whose router chose no expert", ("job",)))


def _shared_gate_gauge():
    from harmony_tpu.metrics.registry import get_registry

    return get_registry().gauge(
        "harmony_moe_shared_gate_mean",
        "Mean sigmoid gate on an expert layer's shared MLP at the newest "
        "drained step (moe_shared_gate)", ("job", "layer"))


def chunks_run(expert_tokens: np.ndarray, experts_held: int) -> np.ndarray:
    """Chunks each layer call ran, ``[steps, layers]``, from its token
    counts ``[steps, layers, experts]``: the plan is the program's own
    (``models.moe.chunk_plan`` of the call's slots), the held slots over its
    capacity rounded up; 0 where the plan is the plain path."""
    from harmony_tpu.models.moe import chunk_plan

    by_step = np.asarray(expert_tokens, np.float64)
    slots = int(round(by_step[0, 0].sum())) if by_step.size else 0
    capacity, chunks = chunk_plan(slots, experts_held, by_step.shape[-1])
    held = by_step[:, :, :experts_held].sum(axis=2)
    return np.ceil(held / capacity) if chunks else np.zeros_like(held)


#: a job's grid of counter children, row-major over (layer, expert), kept
#: with the family it belongs to: ``labels()`` costs ~20 us a call, and a
#: drain of Kimi Linear's 4 x 256 grid paid it 1,024 times. Third: the sum
#: of everything added to the grid, so that STATUS (:func:`stats_by_job`,
#: five times a second under the benchmark) reads the held experts' cells
#: and this one number instead of walking 5 x 512 children a job
_grids: Dict[tuple, tuple] = {}


def _grid(tokens, job: str, layers: Sequence[int], experts: int):
    key = (job, tuple(layers), experts)
    found = _grids.get(key)
    if found is None or found[0] is not tokens:
        found = _grids[key] = (tokens, [
            tokens.labels(job=job, layer=str(layer), expert=str(expert))
            for layer in layers for expert in range(experts)], [0.0])
    return found


def observe(job: str, expert_tokens: np.ndarray, experts_held: int,
            layers: Optional[Sequence[int]] = None,
            null_slots: Optional[np.ndarray] = None,
            shared_gate: Optional[np.ndarray] = None) -> None:
    """Add ``expert_tokens [steps, expert layers, experts]`` to the counters;
    ``layers`` are those layers' block indices (``TransformerConfig.
    moe_layers()``: the ``layer`` label is the block's index, so a leading
    dense block has no row at all; None: every block is an expert layer);
    ``null_slots [steps, expert layers]``: the slots that chose no expert,
    of a router that has that output; ``shared_gate [steps, expert layers]``:
    the mean gate on a gated shared expert (the newest step stands)."""
    from harmony_tpu.tracing import trace_span

    by_step = np.asarray(expert_tokens, np.float64)
    held_by_step = by_step[:, :, :experts_held].sum(axis=(1, 2))
    with trace_span("moe.observe", record=False, job=job,
                    steps=len(held_by_step),
                    held_slots="/".join(str(int(n)) for n in held_by_step)):
        per = by_step.sum(axis=0)  # [layers, E]
        tokens, held_slots, held, calls, chunks = _families()
        layers = tuple(range(len(per)) if layers is None else layers)
        if len(layers) != len(per):
            raise ValueError(f"moe.observe: {len(layers)} layer labels for "
                             f"{len(per)} expert layers")
        _, children, seen = _grid(tokens, job, layers, per.shape[1])
        for child, n in zip(children, per.ravel().tolist()):
            child.inc(n)
        seen[0] += float(per.sum())
        held_slots.labels(job=job).inc(float(held_by_step.sum()))
        absent, null = _dropped_families()
        absent.labels(job=job).inc(float(per.sum() - held_by_step.sum()))
        if null_slots is not None:
            null.labels(job=job).inc(float(np.asarray(null_slots).sum()))
        if shared_gate is not None:
            gauge = _shared_gate_gauge()
            for layer, value in zip(layers, np.asarray(shared_gate)[-1]):
                gauge.labels(job=job, layer=str(layer)).set(float(value))
        held.labels(job=job).set(experts_held)
        calls.labels(job=job).inc(by_step.shape[0] * by_step.shape[1])
        chunks.labels(job=job).inc(float(chunks_run(by_step,
                                                    experts_held).sum()))


def stats_by_job() -> Dict[str, Dict[str, float]]:
    """``{job: {held_slot_share, load_max_over_mean}}`` from the counters."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        tokens, held_slots, held, _calls, _chunks = _families()
        n_held = {job: int(c.value) for (job,), c in held.children()}
        by_job: Dict[str, list] = {}  # job -> [all slots, held experts']
        for (job, layers, experts), (family, children, seen) in list(
                _grids.items()):
            if family is not tokens:
                continue  # a registry since replaced
            row = by_job.setdefault(job, [0.0, [0.0] * n_held.get(job, 0)])
            row[0] += seen[0]
            for at in range(len(layers)):
                for e in range(min(len(row[1]), experts)):
                    row[1][e] += children[at * experts + e].value
        slots = {job: c.value for (job,), c in held_slots.children()}
        for job, (total, mine) in by_job.items():
            if total <= 0 or not mine or sum(mine) <= 0:
                continue
            out[job] = {
                "held_slot_share": slots.get(job, 0.0) / total,
                "load_max_over_mean": max(mine) / (sum(mine) / len(mine))}
    except Exception:
        return {}
    return out
