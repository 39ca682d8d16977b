"""Shared by the KDA readers: device seconds and calls of the program's
gated delta-rule kernels (``harmony_kda_fwd`` / ``harmony_kda_bwd``,
harmony_tpu/ops/kda.py), summed by kernel name over ALL their events in the
trace the run just wrote — as ``_flash_kernels.py`` does for the flash
kernels, with the same libraries (``_moe_kernels.py``, ``perf/trace_reduce.py``).
A trace without the kernels (every cell without KDA blocks, and the parent of
the PR that added them), or no trace, reports nothing."""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

from perf import trace_reduce
from perf.layer_metrics import _moe_kernels

KERNEL = re.compile(r"^(harmony_kda_[a-z_]+?)(?:\.\d+)?$")


def kernel_seconds(profile) -> Optional[Dict[str, Any]]:
    """``{busy_s, kernels: {name: {seconds, calls}}}`` of the first device,
    or None when no KDA kernel ran."""
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
