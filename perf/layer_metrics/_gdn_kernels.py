"""Shared by the Gated DeltaNet readers: device seconds and calls of the
program's delta-rule kernels under ONE scalar decay a head —
``harmony_gdn_fwd`` / ``harmony_gdn_bwd`` (harmony_tpu/ops/kda.py), or
``harmony_kda_*`` where the channel route is what a scalar-decay cell runs —
summed by kernel name over ALL their events in the trace the run just wrote,
as ``_kda_kernels.py`` does for KDA's (same libraries). A trace without such
kernels (the parent of the PR that added them included), or no trace,
reports nothing."""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

from perf import trace_reduce
from perf.layer_metrics import _moe_kernels

KERNEL = re.compile(r"^(harmony_(?:gdn|kda)_[a-z_]+?)(?:\.\d+)?$")


def kernel_seconds(profile) -> Optional[Dict[str, Any]]:
    """``{busy_s, kernels: {name: {seconds, calls}}}`` of the first device,
    or None when no delta-rule kernel ran."""
    per_dev = {d: ops for d, ops in trace_reduce.device_ops(profile).items()
               if ops}
    if not per_dev:
        return None
    ops = per_dev[min(per_dev)]
    busy, _ = trace_reduce.union_seconds((s, e) for _, s, e in ops)
    kernels: Dict[str, Dict[str, float]] = {}
    for text, s, e in ops:
        if trace_reduce.classify(text) != "kernel":
            continue
        m = KERNEL.match(trace_reduce.parse_op(text)[0])
        if m:
            row = kernels.setdefault(m.group(1), {"seconds": 0.0, "calls": 0})
            row["seconds"] += (e - s) * 1e-9
            row["calls"] += 1
    return {"busy_s": busy * 1e-9, "kernels": kernels} if kernels else None


def of_this_run() -> Optional[Dict[str, Any]]:
    """``kernel_seconds`` of the trace this process's cell just wrote."""
    try:
        profile = _moe_kernels._load()
        return None if profile is None else kernel_seconds(profile)
    except Exception:
        return None
