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

``traced_steps`` pairs the kernels' events with the rows they computed IN
THE SAME SPAN: at each drain the program opens the span ``moe.observe``
(harmony_tpu/metrics/moe.py), whose annotation lists the drained steps' held
token-slots one by one. The device stands idle while the host drains, so the
kernel calls between two such events are the later one's steps, in order,
and the calls before the trace's first are its LAST steps. Calls after the
trace's last drain (their rows are reported after the trace ended) and a
step the trace's start cut are left out of both sides of any ratio.

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

Call = Tuple[str, float, float]  # kernel, start_ns, end_ns

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


def pair(calls: Sequence[Call], drained: Sequence[Tuple[float, List[float]]],
         per_step: int) -> Optional[List[Tuple[str, float, float]]]:
    """``[(kernel, seconds, held token-slots of the call's STEP)]`` for the
    calls of every whole step whose drain lies in the trace (module
    docstring). None where the calls between two drains are not the later
    one's steps (a program that dispatches across its drain, or another
    count of calls a step): the pairing would be a guess."""
    # the worker replays a drained window epoch by epoch: its spans come in
    # a burst with no kernel call between them, and count as ONE drain
    bursts: List[List[Any]] = []  # [first start, last start, held slots]
    for t, held in drained:
        if bursts and not any(bursts[-1][1] < c[1] and c[2] <= t
                              for c in calls):
            bursts[-1][1] = t
            bursts[-1][2] = bursts[-1][2] + list(held)
        else:
            bursts.append([t, t, list(held)])
    out: List[Tuple[str, float, float]] = []
    after = float("-inf")
    for n, (first, last, held) in enumerate(bursts):
        span = [c for c in calls if c[1] > after and c[2] <= first]
        if n and len(span) != len(held) * per_step:
            return None
        k = min(len(held), len(span) // per_step)
        span = span[len(span) - k * per_step:]
        for i, slots in enumerate(held[len(held) - k:]):
            for name, s, e in span[i * per_step:(i + 1) * per_step]:
                out.append((name, (e - s) * 1e-9, slots))
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


def traced_steps(obs) -> Optional[Dict[str, Any]]:
    """``{cell, calls: pair(...)}`` for the measured jobs (the keys of
    ``obs["phases"]``) in the trace this process's cell just wrote: the
    configuration comes from the jobs' own ids, the calls a step from its
    shapes through the benchmark's work function. None without the kernels,
    the spans or a whole step."""
    jobs = list(obs.get("phases") or {})
    profile = _load()
    if not jobs or profile is None:
        return None
    try:
        from perf.run import load_by_path

        found, cell = kernel_calls(profile), cell_of(jobs)
        if found is None or cell is None:
            return None
        work = load_by_path("work", "olmoe")
        app = cell.job["app_params"]
        per_step = sum(work.CALLS_PER_LAYER.values()) * work.moe_layers(app)
        gmm = [c for c in found[1] if c[0] in work.CALLS_PER_LAYER]
        calls = pair(gmm, drains(profile, jobs), per_step)
    except Exception:
        return None
    if not calls:
        return None
    return {"cell": cell, "work": work, "calls": calls}


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
