"""Shared by the window-ledger readers: what the program itself recorded of
every drained window of the measured jobs (harmony_tpu/metrics/phases.py,
"The window ledger"), read in this process — the one that ran the jobserver.

    python perf/layer_metrics/_windows.py <file.xplane.pb | cell name>

* the measured jobs are the keys of ``obs["phases"]``;
* the seconds late windows lost come from the program's counter
  ``harmony_window_stall_seconds_total{job,cause}``, through the registry's
  own exposition (as ``_stage_seconds.py`` reads its counter);
* the records (``{window, epoch, epochs, wall_s, spans, unnamed_s, first,
  late, ...}``) and the verdicts on the late ones (``{window, epoch, epochs,
  lost_s, cause, excess, start_sec}``) come from the program's store,
  ``harmony_tpu.metrics.phases.budget().window_ledger(job)``;
* whether a late window STRADDLES the trace's start is read off the trace
  the run just wrote (``_host_spans.trace_path``): the container span
  ``dolphin.epoch_window`` (``dolphin.epoch`` for a one-epoch window) is an
  event ``harmony/dolphin.epoch_window`` of the ``/host:CPU`` plane that
  carries ``job_id`` and ``epoch``; an annotation that was open when the
  profiler started leaves no event, so the window that straddled the start
  is the one BEFORE the job's earliest event. A late window straddles the
  start when its epoch is that earliest event's or the one before's. The
  window that held the stop is the one after the job's latest event; how
  many windows a late one lies from either is ``from_trace_start`` /
  ``from_trace_stop`` (the stop's collection outlasts its window: PERF.md
  section 6, PR 52).

A program without the ledger (the parent of the PR that added it) has no
``window_ledger`` on its store: ``summary`` returns None and every reader
built on it reports nothing. The first call of a process prints one line,
``{"line": "window_stalls", ...}``: every late window of the measured jobs
with its loss, its cause and the three largest excesses, and — late or not —
the windows that held the profiler's start and stop with their walls.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402
from perf.layer_metrics import _host_spans  # noqa: E402

FAMILY = "harmony_window_stall_seconds_total"
UNNAMED = "unnamed"
#: the container spans that carry a window's ``job_id`` and ``epoch``
WINDOW_EVENTS = frozenset({_host_spans.PREFIX + "dolphin.epoch_window",
                           _host_spans.PREFIX + "dolphin.epoch"})

_cache: Dict[Tuple[str, ...], Any] = {}


def stall_seconds(jobs: List[str]) -> Dict[str, Dict[str, float]]:
    """``{job: {cause: seconds}}`` from the program's counter; a job with
    no late window has no sample and reads ``{}``."""
    out: Dict[str, Dict[str, float]] = {j: {} for j in jobs}
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        family = parse_exposition(get_registry().expose()).get(FAMILY)
    except Exception:
        return out
    for _name, labels, value in (family or {}).get("samples", []):
        by = out.get(labels.get("job"))
        if by is not None:
            cause = labels.get("cause", "")
            by[cause] = by.get(cause, 0.0) + float(value)
    return out


def traced_epochs(profile) -> Dict[str, Tuple[int, int, int]]:
    """``{job: (first epoch, last epoch, last window's epochs)}`` of the
    window events the capture holds."""
    found: Dict[str, List[Tuple[float, int, int]]] = {}
    for plane in profile.planes:
        if plane.name != _host_spans.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name not in WINDOW_EVENTS:
                    continue
                stats = dict(e.stats)
                if "job_id" not in stats or "epoch" not in stats:
                    continue
                found.setdefault(str(stats["job_id"]), []).append(
                    (float(e.start_ns), int(stats["epoch"]),
                     int(stats.get("epochs", 1))))
    out = {}
    for job, events in found.items():
        events.sort()
        out[job] = (events[0][1], events[-1][1], events[-1][2])
    return out


def straddles_start(stall: Dict[str, Any],
                    traced: Optional[Tuple[int, int, int]]) -> Optional[bool]:
    """Whether the late window of ``stall`` holds the profiler's start
    (module docstring); None without a capture of the job."""
    if traced is None:
        return None
    return traced[0] in (stall["epoch"], stall["epoch"] + stall["epochs"])


def _at_trace(windows, traced, median) -> Optional[Dict[str, Any]]:
    """The windows that held the profiler's start and its stop, late or
    not: ``{start|stop: {window, wall_s, over_median_s}}``."""
    if traced is None or median is None:
        return None
    first, last, last_k = traced
    out = {}
    for w in windows:
        for end, held in (("start", w["epoch"] + w["epochs"] == first),
                          ("stop", w["epoch"] == last + last_k)):
            if held:
                out[end] = {"window": w["window"], "wall_s": w["wall_s"],
                            "over_median_s": w["wall_s"]
                            - median * w["epochs"]}
    return out


def _usual(regular) -> Dict[str, float]:
    """Median self seconds A WINDOW under each span (and under none) over
    the regular windows: where a window's host time usually goes."""
    names = sorted({n for w in regular for n in w["spans"]})
    out = {n: statistics.median(w["spans"].get(n, 0.0) for w in regular)
           for n in names}
    if regular:
        out[UNNAMED] = statistics.median(w["unnamed_s"] for w in regular)
    return out


def _spread(walls: List[float]) -> Optional[float]:
    if len(walls) < 4:
        return None
    q1, med, q3 = statistics.quantiles(walls, n=4)
    return 100.0 * (q3 - q1) / med if med > 0 else None


def of_jobs(jobs: List[str], profile=None) -> Optional[Dict[str, Any]]:
    """Per measured job: its stall seconds by cause, its regular windows'
    median wall an epoch and their spread, its late windows. None from a
    program without the window ledger."""
    try:
        from harmony_tpu.metrics.phases import budget

        ledger_of = getattr(budget(), "window_ledger", None)
    except Exception:
        return None
    if ledger_of is None or not jobs:
        return None
    traced = traced_epochs(profile) if profile is not None else {}
    seconds = stall_seconds(jobs)
    out: Dict[str, Any] = {}
    for job in jobs:
        ledger = ledger_of(job)
        windows = ledger["windows"]
        if not windows:
            return None  # a measured job that fed no record: nothing to say
        regular = [w for w in windows if not w["first"] and not w["late"]]
        walls = [w["wall_s"] / w["epochs"] for w in regular]
        t0 = windows[0]["end_ns"]
        by_window = {w["window"]: w for w in windows}
        median = statistics.median(walls) if walls else None
        at_trace = _at_trace(windows, traced.get(job), median)
        late = []
        for s in ledger["stalls"]:
            w = by_window.get(s["window"], {})
            late.append({
                "window": s["window"], "epoch": s["epoch"],
                "epochs": s["epochs"], "lost_s": s["lost_s"],
                "cause": s["cause"],
                "excess": dict(list(s["excess"].items())[:3]),
                # from the end of the job's first window, about where the
                # harness opens its own
                "at_s": (None if "start_ns" not in w
                         else (w["start_ns"] - t0) * 1e-9),
                "straddles_trace_start": straddles_start(s, traced.get(job)),
                # in windows, from the one that held the profiler's start
                # and from the one that held its stop (0: that window)
                **{"from_trace_" + end: s["window"] - held["window"]
                   for end, held in (at_trace or {}).items()},
            })
        out[job] = {
            "windows": len(windows), "regular": len(regular),
            "median_wall_s": median,
            "epochs_per_window": windows[-1]["epochs"],
            # the records lie end to end, so nothing of the job's life is
            # outside them: the longest one bounds any stall it had
            "elapsed_s": (windows[-1]["end_ns"] - t0) * 1e-9,
            "longest_window_s": max((w["wall_s"] for w in windows
                                     if not w["first"]), default=None),
            "wall_spread": _spread(walls),
            "usual_s": _usual(regular),
            "stall_s": seconds[job], "late": late,
            "at_trace": at_trace,
        }
    return out


def summary(obs) -> Optional[Dict[str, Any]]:
    """``of_jobs`` of this run's measured jobs against this run's trace,
    computed (and its line printed) once a process."""
    jobs = tuple((obs.get("phases") or {}))
    if not jobs:
        return None
    if jobs not in _cache:
        profile = None
        try:
            path = _host_spans.trace_path()
            if path is not None:
                profile = trace_reduce.load(path)
        except Exception:  # an unreadable trace: no straddle test
            profile = None
        try:
            found = of_jobs(list(jobs), profile)
        except Exception:
            found = None
        _cache[jobs] = found
        if found is not None:
            print(json.dumps({"line": "window_stalls", "jobs": found}),
                  flush=True)
    return _cache[jobs]


def mean_over_tenants(obs, key) -> Optional[float]:
    """The mean over the measured jobs of ``key(job's row)``; None when
    the program has no ledger or a job's row gives None."""
    found = summary(obs)
    if not found:
        return None
    values = [key(row) for row in found.values()]
    if any(v is None for v in values):
        return None
    return sum(values) / len(values)


def main(argv: List[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = (argv[0] if os.path.exists(argv[0])
            else _host_spans.trace_path(argv[0]))
    if path is None:
        print(f"no trace for {argv[0]!r}", file=sys.stderr)
        return 1
    traced = traced_epochs(trace_reduce.load(path))
    if not traced:
        print("no harmony/dolphin.epoch_window event in this trace")
        return 1
    for job, (first, last, k) in sorted(traced.items()):
        print(f"{job}: windows of epochs {first}..{last} (+{k}) lie whole in "
              f"the capture; the one before epoch {first} straddles its "
              f"start, the one from epoch {last + k} its stop")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
