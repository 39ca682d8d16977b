"""Shared by the expert-layer readers: device seconds and calls of the
program's expert kernels (``harmony_gmm_fwd`` / ``_dx`` / ``_dw``, and any
``harmony_moe_*``), summed by kernel name over ALL their events in the trace
the run just wrote, and the load the program counted per expert.

The reduction's ``device_ops`` is a top-10 and folds nothing by kernel, so
the readers open the run's own xplane file (``_host_spans.trace_path``) and
use ``perf/trace_reduce.py`` ``device_ops`` / ``parse_op`` as a library. A
kernel's event is named by its whole HLO line; its name is the HLO name
without XLA's ``.<n>`` suffix. A program without the kernels (the parent of
the PR that added them) has no such event: ``of_this_run`` returns None and
the readers report nothing.

``traced_steps`` pairs the kernels' events with the rows they computed. The
DEVICE says which calls are one step's: every executed program is an event of
the first device's line ``XLA Modules`` (the line ``_step_scopes.py`` reads
the step modules from, through the program's own reader), and the grouped-
matmul calls that start inside one such event are that execution's — a step's,
if they are as many as the configuration's shapes say a step makes
(``per_step``). The HOST says only the rows, in order: at each drain the
program hands the drained steps' held token-slots, one by one, to the span
``moe.observe`` (harmony_tpu/metrics/moe.py), which it opens once the NEXT
window's steps are enqueued (dolphin/worker.py ``_observe_vector_backlog``) —
so the span lies somewhere in the next window's first step, and the steps that
ENDED before it, since the one before, are its rows' steps, in order. (Until PR
48 the span's time cut the CALLS: with ``table.pull`` at 0.6 ms it came to lie
among the next step's first calls, 143 / 145 between two spans where 144 were
due, and the reader refused from PR 42 on.) Steps before the trace's first
span are its LAST steps. Steps after the trace's last span (their rows are
reported after the trace ended), and a step the trace's edge cut (fewer calls
than a step's, or no module event around them), are left out of both sides of
any ratio. Where a whole execution holds another number of calls than
``per_step``, or other than a span's number of steps ended between two spans,
nothing is paired: a guess is worse than nothing.

    python perf/layer_metrics/_moe_kernels.py <file.xplane.pb | cell name>
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402
from perf.layer_metrics import _host_spans  # noqa: E402

KERNEL = re.compile(r"^(harmony_(?:gmm|moe)_[a-z_]+?)(?:\.\d+)?$")
TOKENS = "harmony_moe_expert_tokens_total"
EXPERTS_HELD = "harmony_moe_experts_held"
OBSERVE = _host_spans.PREFIX + "moe.observe"
MODULES_LINE = "XLA Modules"

Call = Tuple[str, float, float]  # kernel, start_ns, end_ns
Run = Tuple[float, float]  # one executed program: start_ns, end_ns

_cache: Dict[str, Any] = {}


def kernel_calls(profile) -> Optional[Tuple[float, List[Call]]]:
    """``(busy seconds, [(kernel, start_ns, end_ns)] by start)`` of the
    first device, or None when no expert kernel ran."""
    per_dev = {d: ops for d, ops in trace_reduce.device_ops(profile).items()
               if ops}
    if not per_dev:
        return None
    ops = per_dev[min(per_dev)]
    busy, _ = trace_reduce.union_seconds((s, e) for _, s, e in ops)
    calls: List[Call] = []
    for text, s, e in ops:
        if trace_reduce.classify(text) != "kernel":
            continue
        m = KERNEL.match(trace_reduce.parse_op(text)[0])
        if m:
            calls.append((m.group(1), s, e))
    if not calls:
        return None
    return busy * 1e-9, sorted(calls, key=lambda c: c[1])


def kernel_seconds(profile) -> Optional[Dict[str, Any]]:
    """``{busy_s, kernels: {name: {seconds, calls}}}`` of the first device,
    or None when no expert kernel ran."""
    found = kernel_calls(profile)
    if found is None:
        return None
    kernels: Dict[str, Dict[str, float]] = {}
    for name, s, e in found[1]:
        row = kernels.setdefault(name, {"seconds": 0.0, "calls": 0})
        row["seconds"] += (e - s) * 1e-9
        row["calls"] += 1
    return {"busy_s": found[0], "kernels": kernels}


def module_runs(profile) -> List[Run]:
    """``[(start_ns, end_ns)]`` of every program the first device executed
    (the device ``kernel_calls`` reads), by start."""
    live = [d for d, ops in trace_reduce.device_ops(profile).items() if ops]
    if not live:
        return []
    first = f"/device:TPU:{min(live)}"
    return sorted((float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                  for plane in profile.planes if plane.name == first
                  for line in plane.lines if line.name == MODULES_LINE
                  for e in line.events)


def drains(profile, jobs: Sequence[str]) -> List[Tuple[float, List[float]]]:
    """``[(start_ns, [held token-slots of each drained step])]`` of the
    ``moe.observe`` spans of ``jobs``, by time."""
    out = []
    for plane in profile.planes:
        if plane.name != _host_spans.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != OBSERVE:
                    continue
                stats = dict(e.stats)
                if stats.get("job") in jobs and stats.get("held_slots"):
                    out.append((float(e.start_ns), [
                        float(n) for n in str(stats["held_slots"]).split("/")]))
    return sorted(out)


def pair(calls: Sequence[Call], runs: Sequence[Run],
         drained: Sequence[Tuple[float, List[float]]],
         per_step: int) -> Optional[List[Tuple[str, float, float]]]:
    """``[(kernel, seconds, held token-slots of the call's STEP)]`` for the
    calls of every whole step whose rows a span in the trace reports (module
    docstring): ``runs`` cut the calls into steps, ``drained`` gives the
    rows in order. None where an execution's calls are not a step's, or the
    steps that ended between two spans are not the later one's: the pairing
    would be a guess."""
    runs = sorted(runs)
    inside: List[List[Call]] = [[] for _ in runs]
    at = 0
    for c in sorted(calls, key=lambda c: c[1]):
        while at < len(runs) and runs[at][1] <= c[1]:
            at += 1
        if at < len(runs) and runs[at][0] <= c[1]:
            inside[at].append(c)
    # the executions that called the kernels at all; only the trace's edge
    # may have cut one short (its event then covers what the trace saw of it)
    steps = [(r[1], cs) for r, cs in zip(runs, inside) if cs]
    for n, (_, cs) in enumerate(steps):
        if len(cs) != per_step and (
                len(cs) > per_step or 0 < n < len(steps) - 1):
            return None
    steps = [st for st in steps if len(st[1]) == per_step]
    # the worker replays a drained window epoch by epoch: its spans come in
    # a burst with no step ending between them, and count as ONE drain
    bursts: List[List[Any]] = []  # [first start, last start, held slots]
    for t, held in sorted(drained):
        if bursts and not any(bursts[-1][1] < end <= t for end, _ in steps):
            bursts[-1][1] = t
            bursts[-1][2] = bursts[-1][2] + list(held)
        else:
            bursts.append([t, t, list(held)])
    out: List[Tuple[str, float, float]] = []
    after = float("-inf")
    for n, (first, last, held) in enumerate(bursts):
        ended = [cs for end, cs in steps if after < end <= first]
        if n and len(ended) != len(held):
            return None
        k = min(len(held), len(ended))
        for cs, slots in zip(ended[len(ended) - k:], held[len(held) - k:]):
            out.extend((name, (e - s) * 1e-9, slots) for name, s, e in cs)
        after = last
    return out


def cell_of(jobs: Sequence[str]):
    """The ``perf.run.Cell`` whose name the measured jobs' ids start with
    (``<cell>-run-<tenant>``), or None."""
    from perf.run import Cell, load_json

    names = [w["name"] for w in load_json(ROOT, "BENCHMARK.json")["workloads"]]
    mine = {max((n for n in names if j.startswith(n + "-")), key=len,
                default=None) for j in jobs}
    if len(mine) != 1 or None in mine:
        return None
    return Cell(mine.pop(), "--rehearse" in sys.argv)


def _load():
    path = _host_spans.trace_path()
    if path is None:
        return None
    if path not in _cache:
        try:
            _cache[path] = trace_reduce.load(path)
        except Exception:  # an unreadable trace reports nothing
            _cache[path] = None
    return _cache[path]


def of_this_run() -> Optional[Dict[str, Any]]:
    """``kernel_seconds`` of the trace this process's cell just wrote (read
    once), or None."""
    profile = _load()
    return None if profile is None else kernel_seconds(profile)


def traced_steps(obs, work: str = "olmoe") -> Optional[Dict[str, Any]]:
    """``{cell, work, layers, calls: pair(...)}`` for the measured jobs (the
    keys of ``obs["phases"]``) in the trace this process's cell just wrote:
    the configuration comes from the jobs' own ids, the calls a step from its
    shapes through the benchmark's work file ``perf/work/<work>.py``. None
    without the kernels, the spans or a whole step."""
    jobs = list(obs.get("phases") or {})
    profile = _load()
    if not jobs or profile is None:
        return None
    try:
        from perf.run import load_by_path

        found, cell = kernel_calls(profile), cell_of(jobs)
        if found is None or cell is None:
            return None
        module = load_by_path("work", work)
        layers = module.moe_layers(cell.job["app_params"])
        per_step = sum(module.CALLS_PER_LAYER.values()) * layers
        gmm = [c for c in found[1] if c[0] in module.CALLS_PER_LAYER]
        calls = pair(gmm, module_runs(profile), drains(profile, jobs),
                     per_step)
    except Exception:
        return None
    if not calls:
        return None
    return {"cell": cell, "work": module, "layers": layers, "calls": calls}


def roofline_share(obs, work: str, line: str) -> Optional[float]:
    """FLOPs the grouped matmuls of the traced steps NEEDED over what the
    chip could have done in the device time they took, percent:

        sum over calls (held rows of the call's step x 2 d f)  /  (seconds x bf16 peak)

    with both sides from the same steps of the same trace
    (``traced_steps``): a call counts its step's mean expert layer, a whole
    step counts exactly. Each kernel's own share goes to the printed
    ``line``. None with nothing to read, never 0."""
    if not obs.get("trace"):
        return None
    found = traced_steps(obs, work)
    if not found:
        return None
    try:
        import jax

        with open(os.path.join(PERF, "peaks.json")) as f:
            peak = json.load(f)[str(jax.devices()[0].device_kind)]["bf16_flops"]
    except Exception:
        return None
    cell, module, layers = found["cell"], found["work"], found["layers"]
    app = cell.job["app_params"]
    by_kernel: Dict[str, Dict[str, float]] = {}
    for name, seconds, step_slots in found["calls"]:
        row = by_kernel.setdefault(name, {"calls": 0, "seconds": 0.0,
                                          "flops": 0.0, "rows": 0.0})
        row["calls"] += 1
        row["seconds"] += seconds
        row["rows"] += step_slots / layers
        row["flops"] += module.gmm_flops_per_call(app, step_slots / layers)
    total = {k: sum(r[k] for r in by_kernel.values())
             for k in ("calls", "seconds", "flops", "rows")}
    if total["seconds"] <= 0:
        return None
    rows_per_call = total["rows"] / total["calls"]
    print(json.dumps({
        "line": line, "bound": "bf16 MXU peak",
        "expert_layers": layers, "calls_paired": total["calls"],
        "held_rows_per_call": rows_per_call,
        "held_slot_share": rows_per_call / module.slots_per_step(app, cell.batch),
        "kernels": {name: {"calls": r["calls"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "roofline_share": 100.0 * r["flops"]
                           / (r["seconds"] * peak)}
                    for name, r in sorted(by_kernel.items())}}), flush=True)
    return 100.0 * total["flops"] / (total["seconds"] * peak)


def load_max_over_mean(obs) -> Optional[float]:
    """The most loaded held expert's token-slots over the held experts'
    mean, for the measured jobs (the keys of ``obs["phases"]``), from the
    program's counters, read in this process through the registry's own
    exposition; None without them."""
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        fams = parse_exposition(get_registry().expose())
        tokens, held = fams.get(TOKENS), fams.get(EXPERTS_HELD)
    except Exception:
        return None
    if not tokens or not held:
        return None
    n_held = max((int(v) for _, labels, v in held["samples"]
                  if labels.get("job") in jobs), default=0)
    per_expert: Dict[int, float] = {}
    for _name, labels, value in tokens["samples"]:
        if labels.get("job") in jobs:
            e = int(labels["expert"])
            per_expert[e] = per_expert.get(e, 0.0) + float(value)
    mine = [per_expert.get(e, 0.0) for e in range(n_held)]
    if not mine or sum(mine) <= 0:
        return None
    return max(mine) / (sum(mine) / len(mine))


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0] if os.path.exists(argv[0]) else _host_spans.trace_path(argv[0])
    if path is None:
        print(f"no trace for {argv[0]!r}", file=sys.stderr)
        return 1
    print(json.dumps(kernel_seconds(trace_reduce.load(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
