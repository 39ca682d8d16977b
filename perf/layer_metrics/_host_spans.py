"""Shared by the idle-cause readers: the program's own spans, read from the
trace the run just wrote, laid over the device's idle time.

    python perf/layer_metrics/_host_spans.py <file.xplane.pb | cell name>

``trace_span`` (harmony_tpu/tracing/span.py) opens a
``jax.profiler.TraceAnnotation`` named ``harmony/<description>``; under a
profiler session it is an event on the calling thread's line of the plane
``/host:CPU``, on the same clock as the ``/device:TPU:<n>`` planes. Device
busy intervals come from ``perf.trace_reduce`` (``device_ops``,
``union_seconds``); the idle time is the first device's, inside the window
from the first to the last device operation, as ``trace_reduce`` has it.

* every instant of a thread has an innermost open span; the spans that only
  enclose (``CONTAINERS``: a worker's whole run, an epoch window) name
  nothing and do not count;
* the CAUSE of an instant, over all threads: the innermost span of a thread
  that is doing something, the latest-started first; else a span that only
  waits (``WAITS``); else a bystander's (``jobserver.status``); else none;
* an idle interval is cut at every change of cause and each piece booked to
  its cause — the metrics are sums of such pieces. A listed gap is named
  after the cause that holds most of it, not the one at its start: a gap
  starts when the device finishes its last operation, which is while the
  host still stands in whatever waited for that (``drain.d2h``, a
  ``block_until_ready``) — on the chip a 62 ms gap lay 1 ms under the
  wait and 59 ms under what the host did next.

A program without the annotations (the parent of the PR that added them)
has no ``harmony/`` event: ``attribute`` returns None and every reader built
on it reports nothing. ``obs`` carries no path, so the cell comes from the
process's ``--workload`` argument.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import trace_reduce  # noqa: E402

PREFIX = "harmony/"
HOST_PLANE = "/host:CPU"
#: spans that enclose a run or a window: open all the time, name nothing
CONTAINERS = frozenset({"dolphin.worker", "dolphin.epoch_window",
                        "dolphin.epoch", "jobserver.dispatch",
                        "job.first_window", "profile_session"})
#: spans in which a thread stands still for something else
WAITS = frozenset({"taskunit.wait", "step.backpressure"})
#: spans of threads that serve the client, not a tenant
BYSTANDERS = frozenset({"jobserver.status", "jobserver.submit"})
#: what ``drain_idle_share`` books: the window's drain and what follows it
DRAIN = frozenset({"dolphin.metric_drain", "drain.stack", "drain.d2h",
                   "drain.emit", "window.bookkeeping"})
GRANT = "taskunit.wait"

Segment = Tuple[float, float, str]

_cache: Dict[str, Any] = {}


def trace_path(cell: Optional[str] = None) -> Optional[str]:
    """The xplane file of ``cell``'s newest trace under
    ``chiprun_out/trace/<cell>/``; the cell defaults to the process's
    ``--workload`` argument."""
    if cell is None:
        argv = sys.argv
        for i, a in enumerate(argv):
            if a == "--workload" and i + 1 < len(argv):
                cell = argv[i + 1]
            elif a.startswith("--workload="):
                cell = a.split("=", 1)[1]
    if not cell:
        return None
    found = sorted(glob.glob(os.path.join(
        ROOT, "chiprun_out", "trace", cell, "plugins", "profile", "*",
        "*.xplane.pb")))
    return found[-1] if found else None


def thread_segments(profile) -> Dict[str, List[Segment]]:
    """``{thread: [(start_ns, end_ns, innermost span)]}`` of every host
    thread that opened a program span: its nested ``harmony/`` events
    flattened so that every instant belongs to the innermost one, the
    enclosing spans of ``CONTAINERS`` left out."""
    out: Dict[str, List[Segment]] = {}
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for n, line in enumerate(plane.lines):
            events = sorted(
                ((float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
                  e.name[len(PREFIX):])
                 for e in line.events if e.name.startswith(PREFIX)),
                key=lambda ev: (ev[0], -ev[1]))
            events = [ev for ev in events if ev[2] not in CONTAINERS]
            if not events:
                continue
            segs: List[Segment] = []
            stack: List[Segment] = []
            at = events[0][0]  # every instant before it is booked

            def book(until: float, name: str) -> None:
                nonlocal at
                if until > at:
                    segs.append((at, until, name))
                    at = until

            for ev in events:
                while stack and stack[-1][1] <= ev[0]:
                    top = stack.pop()
                    book(top[1], top[2])
                if stack:
                    book(ev[0], stack[-1][2])
                at = max(at, ev[0])
                stack.append(ev)
            while stack:
                top = stack.pop()
                book(top[1], top[2])
            out[f"{line.name}#{n}"] = segs
    return out


def _rank(name: str) -> int:
    return 2 if name in BYSTANDERS else 1 if name in WAITS else 0


def cause_timeline(threads: Dict[str, List[Segment]]
                   ) -> List[Tuple[float, Optional[str], bool]]:
    """``[(from_ns, cause, every worker waits for a grant)]``, each entry
    holding until the next: the cause rule of the module docstring over all
    threads. Worker threads are those that opened anything but a
    bystander's span."""
    workers = {t for t, segs in threads.items()
               if any(s[2] not in BYSTANDERS for s in segs)}
    edges: List[Tuple[float, int, str, str, float]] = []
    for t, segs in threads.items():
        for s, e, name in segs:
            edges.append((e, 0, t, name, s))
            edges.append((s, 1, t, name, s))
    edges.sort(key=lambda x: (x[0], x[1]))
    now: Dict[str, Tuple[str, float]] = {}
    line: List[Tuple[float, Optional[str], bool]] = []
    for t_ns, opening, thread, name, started in edges:
        if opening:
            now[thread] = (name, started)
        elif now.get(thread, (None, 0.0))[1] == started:
            del now[thread]
        cause = None
        if now:
            # doing before waiting before bystanding; the latest start first
            cause = min(now.values(), key=lambda v: (_rank(v[0]), -v[1]))[0]
        granted = bool(workers) and all(
            now.get(w, (None, 0.0))[0] == GRANT for w in workers)
        if line and line[-1][0] == t_ns:
            line[-1] = (t_ns, cause, granted)
        elif not line or line[-1][1:] != (cause, granted):
            line.append((t_ns, cause, granted))
    return line


def attribute(profile, top: int = 10) -> Optional[Dict[str, Any]]:
    """The first device's idle seconds by cause, or None when the trace
    holds no device operation or no program span::

        {window_s, idle_s, by_cause: {span or "unnamed": s}, drain_idle_s,
         grant_idle_s, unnamed_idle_s, gaps: [[start_s, length_s, cause]],
         unnamed: [[start_s, length_s, span before, span after]],
         span_s: {span: total seconds open, over threads}}
    """
    per_dev = {d: ops for d, ops in trace_reduce.device_ops(profile).items()
               if ops}
    threads = thread_segments(profile)
    if not per_dev or not threads:
        return None
    w0 = min(s for ops in per_dev.values() for _, s, _ in ops)
    w1 = max(e for ops in per_dev.values() for _, _, e in ops)
    _, busy = trace_reduce.union_seconds(
        (s, e) for _, s, e in per_dev[min(per_dev)])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    line = [(float("-inf"), None, False)] + cause_timeline(threads)
    by_cause: Dict[str, float] = {}
    grant = 0.0
    gaps: List[Tuple[float, float, str]] = []
    holes: List[Tuple[float, float, Optional[str], Optional[str]]] = []
    i = 0  # line[i] holds at the gap's start; both lists are in time order
    for g0, g1 in idle:
        while i + 1 < len(line) and line[i + 1][0] <= g0:
            i += 1
        j, at = i, g0
        within: Dict[str, float] = {}
        while at < g1:
            nxt = line[j + 1][0] if j + 1 < len(line) else float("inf")
            end = min(g1, nxt)
            cause = line[j][1] or "unnamed"
            within[cause] = within.get(cause, 0.0) + (end - at)
            if cause == "unnamed":  # between which spans did nobody look
                holes.append((end - at, at, line[j - 1][1] if j else None,
                              line[j + 1][1] if j + 1 < len(line) else None))
            if line[j][2]:
                grant += end - at
            at = end
            if end == nxt:
                j += 1
        for cause, length in within.items():
            by_cause[cause] = by_cause.get(cause, 0.0) + length
        gaps.append((g0, g1 - g0, max(within, key=within.get)))
    ns = 1e-9
    span_s: Dict[str, float] = {}
    for segs in threads.values():
        for s, e, name in segs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                span_s[name] = span_s.get(name, 0.0) + (e - s) * ns
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) * ns,
        "idle_s": sum(b - a for a, b in idle) * ns,
        "by_cause": {k: v * ns for k, v in
                     sorted(by_cause.items(), key=lambda kv: -kv[1])},
        "drain_idle_s": sum(v for k, v in by_cause.items() if k in DRAIN) * ns,
        "grant_idle_s": grant * ns,
        "unnamed_idle_s": by_cause.get("unnamed", 0.0) * ns,
        "gaps": [[(s - w0) * ns, length * ns, cause]
                 for s, length, cause in gaps[:top]],
        "unnamed": [[(at - w0) * ns, length * ns, before, after]
                    for length, at, before, after in
                    sorted(holes, reverse=True)[:top]],
        "span_s": dict(sorted(span_s.items(), key=lambda kv: -kv[1])),
    }


def of_this_run() -> Optional[Dict[str, Any]]:
    """``attribute`` of the trace this process's cell just wrote (read
    once), or None — no trace, no device operation, no program span."""
    path = trace_path()
    if path is None:
        return None
    if path not in _cache:
        try:
            _cache[path] = attribute(trace_reduce.load(path))
        except Exception:  # an unreadable trace reports nothing
            _cache[path] = None
    return _cache[path]


def idle_share(key: str) -> Optional[float]:
    """``100 * <key> / window_s`` of this run's trace, for the readers."""
    found = of_this_run()
    if not found or found["window_s"] <= 0:
        return None
    return 100.0 * found[key] / found["window_s"]


def main(argv: List[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0] if os.path.exists(argv[0]) else trace_path(argv[0])
    if path is None:
        print(f"no trace for {argv[0]!r}", file=sys.stderr)
        return 1
    found = attribute(trace_reduce.load(path))
    if found is None:
        print("no device operation or no harmony/ span in this trace")
        return 1
    print(f"window {found['window_s']:.4f} s, device 0 idle "
          f"{found['idle_s']:.4f} s "
          f"({100 * found['idle_s'] / found['window_s']:.2f}%)")
    print("idle seconds by cause:")
    for cause, s in found["by_cause"].items():
        print(f"  {cause:<24} {s:.6f}")
    print("longest gaps (start in window, length, the cause of most of it):")
    for start, length, cause in found["gaps"]:
        print(f"  dev0+{start:.4f}s  {1e3 * length:9.3f} ms  {cause}")
    print("longest idle stretches under no span (the spans before and after):")
    for start, length, before, after in found["unnamed"]:
        print(f"  dev0+{start:.4f}s  {1e3 * length:9.3f} ms  "
              f"{before} .. {after}")
    print("seconds inside each span, summed over threads:")
    for name, s in found["span_s"].items():
        print(f"  {name:<24} {s:.6f}")
    print(json.dumps({k: found[k] for k in (
        "window_s", "idle_s", "drain_idle_s", "grant_idle_s",
        "unnamed_idle_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
