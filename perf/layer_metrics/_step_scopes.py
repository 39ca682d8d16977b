"""Shared by the step-scope readers: the scope table of the trace the run
just wrote, from the program's own reader
(``harmony_tpu/tracing/stepscopes.py``, used as a library).

The program names its step's layers with ``step_scope`` (a vocabulary of
``jax.named_scope``s); a profiler capture carries every executed module's
HLO with those names in the plane ``/host:metadata``, and the reader joins
them to the ``XLA Ops`` events of a device. Here: device 0, the STEP modules
only (a module with a ``table.push`` scope: a training step; the comm
probe's and the start-up's programs name nothing and stay out), blocks
folded (``blk3/ffn`` -> ``blk*/ffn``). Shares are percent of those modules'
own device seconds, so the groups below partition 100:

    table_path + mixer + ffn + moe_routing + expert_kernels + head_loss
    + other_model + unscoped = 100

A program without the scopes (the parent of the PR that added them), a
trace without the metadata plane, or no trace: ``table`` returns None and
every reader reports nothing.

    python perf/layer_metrics/_step_scopes.py <file.xplane.pb | cell name>
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable, Dict, Optional

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.layer_metrics import _host_spans  # noqa: E402

TABLE = ("table.pull", "table.grad_rows", "table.push")
ROUTING = ("blk*/moe.route", "blk*/moe.dispatch", "blk*/moe.combine",
           "blk*/moe.aux")
EXPERTS = "blk*/moe.experts"

#: which group a row ``(scope, pass, class)`` belongs to; the first match
GROUPS: Dict[str, Callable[[Any], bool]] = {
    "unscoped": lambda r: r.scope.startswith("unscoped:"),
    "table_path": lambda r: r.scope in TABLE,
    "mixer": lambda r: r.scope.startswith(("blk*/mixer.", "blk*/kda.")),
    "ffn": lambda r: r.scope in ("blk*/ffn", "blk*/moe.shared"),
    "moe_routing": lambda r: r.scope in ROUTING or (
        r.scope == EXPERTS and r.klass != "kernel"),
    "expert_kernels": lambda r: r.scope == EXPERTS,
    "head_loss": lambda r: r.scope in ("head", "loss"),
    "other_model": lambda r: True,
}

_cache: Dict[str, Any] = {}
_printed = set()


def group_of(row) -> str:
    return next(g for g, test in GROUPS.items() if test(row))


def table(path: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """``{rows, seconds, steps, groups: {group: seconds}, read_s}`` of
    device 0's step modules in ``path`` (default: the trace this process's
    cell just wrote), read once; None when there is nothing to read."""
    path = path or _host_spans.trace_path()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = _read(path)
    return _cache[path]


def _read(path: str) -> Optional[Dict[str, Any]]:
    try:
        from harmony_tpu.tracing import stepscopes

        t0 = time.perf_counter()
        modules, devices = stepscopes.load(path)
        live = [d for d in sorted(devices) if devices[d]["ops"]]
        if not modules or not live:
            return None
        rows, seconds, steps = stepscopes.step_rows(
            stepscopes.reduce_device(modules, devices[live[0]]))
        read_s = time.perf_counter() - t0
    except Exception:  # a program without the reader, an unreadable trace
        return None
    if not rows or seconds <= 0:
        return None
    groups = {g: 0.0 for g in GROUPS}
    for r in rows:
        groups[group_of(r)] += r.seconds
    return {"rows": rows, "seconds": seconds, "steps": steps,
            "groups": groups, "read_s": read_s}


def line(found: Dict[str, Any], top: int = 24) -> Dict[str, Any]:
    """The printed ``step_scopes`` line: the partition, the table's ``top``
    rows (ms a step, percent), what is left by opcode."""
    total, n = found["seconds"], max(1e-9, found["steps"])
    rows = found["rows"]
    return {
        "line": "step_scopes", "step_modules_s": total,
        "steps": n, "ms_per_step": 1e3 * total / n,
        "reader_s": found["read_s"],
        "inherited_share": 100.0 * sum(r.inherited_s for r in rows) / total,
        "partition": {g: 100.0 * s / total
                      for g, s in found["groups"].items()},
        "rows": [[r.scope, r.which, r.klass, 1e3 * r.seconds / n,
                  100.0 * r.seconds / total] for r in rows[:top]],
        "rest": 100.0 * sum(r.seconds for r in rows[top:]) / total,
        "unscoped": {r.scope.split(":", 1)[1]: 100.0 * r.seconds / total
                     for r in rows if r.scope.startswith("unscoped:")},
    }


def share(obs, group: str) -> Optional[float]:
    """Percent of the step modules' device seconds in ``group``; prints the
    ``step_scopes`` line once a process."""
    if not obs.get("trace"):
        return None
    found = table()
    if found is None:
        return None
    if "line" not in _printed:
        _printed.add("line")
        print(json.dumps(line(found)), flush=True)
    return 100.0 * found["groups"][group] / found["seconds"]


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0] if os.path.exists(argv[0]) else _host_spans.trace_path(argv[0])
    found = None if path is None else table(path)
    if found is None:
        print(f"no scope table for {argv[0]!r}", file=sys.stderr)
        return 1
    print(json.dumps(line(found, top=60), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
