"""From a profiler trace (``.xplane.pb``) to device busy / idle time, the
time of kernel and collective operations, and a breakdown.

    python perf/trace_reduce.py <file.xplane.pb> [--dump]

Read with nothing but ``jax.profiler.ProfileData``. What a v5e trace holds
(looked at by hand, PR 22): one plane per chip, ``/device:TPU:<n>``, whose
line ``XLA Ops`` carries one event per executed HLO operation (start and
duration in nanoseconds on the trace's clock), beside ``XLA Modules`` (one
event per executed program) and ``Steps``; host threads are the lines of
the plane ``/host:CPU``. All planes share one clock.

* busy: the union of the ``XLA Ops`` intervals of a device (operations of
  one core do not overlap, but the union makes that an observation rather
  than an assumption);
* idle share: 1 - busy / window, mean over the devices;
* an operation's class comes from its own name and opcode as the trace
  prints them (``classify``): kernels are custom calls to
  ``tpu_custom_call``, collectives are named or typed after one;
* an idle gap is the time between two busy intervals of a device. The
  harness traces with the Python tracer off (the host stays as it is), so
  the host's lines say nothing a gap could be named after: the longest
  gaps of the first device are listed by where in the window they start
  (``dev0+1.2345s`` - a period shows as equal distances), the rest as one
  sum. Naming them after what the host did waits for spans in the program
  (PERF.md, Open questions).
"""
from __future__ import annotations

import json
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: an event of ``XLA Ops`` is named by its whole HLO line,
#: ``%<name> = <shape> <opcode>(<operands>), <attributes>``; shapes carry
#: only upper-case letters before a parenthesis (``T(8,128)``, ``S(1)``), so
#: the opcode is the first lower-case word followed by one
HLO = re.compile(r"^%(?P<name>\S+) = .*?(?<![\w%.])(?P<opcode>[a-z][a-z0-9\-]*)\(")
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")

Interval = Tuple[float, float]


def union_seconds(intervals: Iterable[Interval]) -> Tuple[float, List[Interval]]:
    """Total length of the union of ``(start, end)`` intervals (any unit),
    and the merged intervals in order."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def parse_op(text: str) -> Tuple[str, str]:
    """``(name, opcode)`` of an ``XLA Ops`` event; an event that is not an
    HLO line keeps its text as the name and has no opcode."""
    m = HLO.match(text)
    return (m.group("name"), m.group("opcode")) if m else (text, "")


def classify(text: str) -> str:
    """``collective`` | ``kernel`` | ``xla`` for an ``XLA Ops`` event. The
    operation's own name and opcode decide, never its operands (a fusion
    that consumes ``%all-reduce.1`` is not a collective). A kernel is a
    custom call to ``tpu_custom_call``, which is how Mosaic (Pallas)
    kernels reach XLA; a collective is named or typed after one — with its
    ``-start`` / ``-done`` halves and the fusions XLA names after them."""
    name, opcode = parse_op(text)
    if COLLECTIVE.search(opcode) or COLLECTIVE.search(name):
        return "collective"
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return "kernel"
    return "xla"


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def device_ops(profile) -> Dict[int, List[Tuple[str, float, float]]]:
    """``{device ordinal: [(op name, start_ns, end_ns)]}`` from each TPU
    plane's ``XLA Ops`` line."""
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out.setdefault(int(m.group(1)), []).extend(_events(line))
    return out


def reduce(profile, top: int = 10) -> Optional[Dict[str, Any]]:
    """The trace's reduction, or None when no operation ran on a device.
    The window is the span from the first to the last device operation."""
    per_dev = device_ops(profile)
    per_dev = {d: ops for d, ops in per_dev.items() if ops}
    if not per_dev:
        return None
    w0 = min(s for ops in per_dev.values() for _, s, _ in ops)
    w1 = max(e for ops in per_dev.values() for _, _, e in ops)
    window = w1 - w0
    ns = 1e-9
    busy, kernel, collective = [], [], []
    op_time: Dict[str, float] = {}
    idle_gaps: List[List[Any]] = []
    for dev, ops in sorted(per_dev.items()):
        total, merged = union_seconds((s, e) for _, s, e in ops)
        busy.append(total)
        k_time = c_time = 0.0
        for text, s, e in ops:
            kind = classify(text)
            if kind == "kernel":
                k_time += e - s
            elif kind == "collective":
                c_time += e - s
            name, opcode = parse_op(text)
            key = f"{name} ({opcode})" if opcode else name[:120]
            op_time[key] = op_time.get(key, 0.0) + (e - s)
        kernel.append(k_time)
        collective.append(c_time)
        if dev != min(per_dev):
            continue  # the first device's gaps stand for the others'
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]), reverse=True)
        idle_gaps = [[f"dev{dev}+{(start - w0) * ns:.4f}s", length * ns]
                     for length, start in gaps[:top - 1]]
        if gaps[top - 1:]:
            idle_gaps.append([f"dev{dev}:{len(gaps) - top + 1}_shorter_gaps",
                              sum(g[0] for g in gaps[top - 1:]) * ns])
    n = len(per_dev)
    return {
        "devices": n,
        "window_s": window * ns,
        "busy_s": sum(busy) / n * ns,
        "kernel_s": sum(kernel) / n * ns,
        "collective_s": sum(collective) / n * ns,
        # seconds per device, so four chips read like one
        "device_ops": [[k, v * ns / n] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps,
    }


def dump(profile, limit: int = 12) -> None:
    """Planes, lines and the first events with their stats: what to look at
    by hand before trusting the reduction on a new trace."""
    for plane in profile.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:limit]:
                stats = {k: (v if not isinstance(v, (bytes, str)) else str(v)[:80])
                         for k, v in list(e.stats)[:8]}
                print(f"    {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")


def main(argv: List[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    profile = load(argv[0])
    if "--dump" in argv:
        dump(profile)
    print(json.dumps(reduce(profile), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
