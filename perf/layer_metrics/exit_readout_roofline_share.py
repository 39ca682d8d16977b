"""``exit_readout_roofline_share`` — the time the traced calls of the readout
op (``harmony_readout_fwd`` / ``_bwd_dx`` / ``_bwd_dw``,
harmony_tpu/ops/readout_loss.py) NEEDED at the binding peak over the device
time they took, each kernel credited by the CELL's own work file:

    sum over calls max(FLOPs / bf16 peak, bytes / HBM peak)
        /  sum of the calls' device seconds

Seconds are ALL the ``harmony_readout_*`` events of the run's trace, by kernel
name (a looped model with an exit a pass calls each kernel ``loop_steps``
times a step). What a call needs is the answer of the work file the cell's
configuration names under ``job.flops_fn`` (``"<sibling>:<function>"``:
``perf/work/<sibling>.py``), which has to export ``READOUT_KERNELS`` (kernel
name in a trace -> which product) and ``readout_bound_seconds(app, batch,
kernel, peaks)`` — ``hetero_flash_roofline_share``'s pattern: the next
configuration brings a work file, not a reader. Each kernel's own share, its
seconds a call and the peak that binds it go to a printed line
(``exit_readout_roofline``).

No trace, a trace without the kernels (a shape the op's ``plan`` leaves to the
plain readout; the parent of the PR that added the op), or a cell whose work
file has no ``READOUT_KERNELS``: nothing. A ``harmony_readout_*`` kernel in
the trace that the work file has NO row for is named in the printed line
(``uncounted``) and the metric is left out of the run's line."""
import json
import os
import re

from perf import trace_reduce
from perf.layer_metrics._moe_kernels import PERF, _load, cell_of

KERNEL = re.compile(r"^(harmony_readout_[a-z_]+?)(?:\.\d+)?$")
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def kernel_seconds(profile):
    """``{name: {seconds, calls}}`` of the first device's readout kernels."""
    per_dev = {d: ops for d, ops in trace_reduce.device_ops(profile).items()
               if ops}
    kernels = {}
    for text, s, e in (per_dev[min(per_dev)] if per_dev else ()):
        if trace_reduce.classify(text) != "kernel":
            continue
        m = KERNEL.match(trace_reduce.parse_op(text)[0])
        if m:
            row = kernels.setdefault(m.group(1), {"seconds": 0.0, "calls": 0})
            row["seconds"] += (e - s) * 1e-9
            row["calls"] += 1
    return kernels


def work_of(cell):
    """The cell's work file, if it counts the readout kernels; else None."""
    from perf.run import load_by_path

    sibling, colon, _ = str(cell.job.get("flops_fn") or "").rpartition(":")
    if not colon or not os.path.exists(
            os.path.join(PERF, "work", sibling + ".py")):
        return None
    work = load_by_path("work", sibling)
    return work if all(hasattr(work, name) for name in (
        "READOUT_KERNELS", "readout_bound_seconds")) else None


def read(obs):
    if not obs.get("trace"):
        return None
    try:
        profile = _load()
        found = None if profile is None else kernel_seconds(profile)
    except Exception:  # an unreadable trace reports nothing
        return None
    cell = cell_of(list(obs.get("phases") or {}))
    work = None if cell is None else work_of(cell)
    if not found or work is None:
        return None
    import jax

    with open(os.path.join(PERF, "peaks.json")) as f:
        peaks = json.load(f).get(str(jax.devices()[0].device_kind))
    if peaks is None:  # a device the yardstick has no peaks for
        return None
    app = cell.job["app_params"]
    uncounted = sorted(set(found) - set(work.READOUT_KERNELS))
    rows = {name: {**k, **work.readout_bound_seconds(app, cell.batch, name,
                                                     peaks)}
            for name, k in found.items() if name in work.READOUT_KERNELS}
    seconds = sum(r["seconds"] for r in rows.values())
    print(json.dumps({
        "line": "exit_readout_roofline", "work": work.__file__[len(PERF) + 1:],
        "uncounted": uncounted,
        "kernels": {name: {"calls": r["calls"], "binds": r["binds"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "bound_ms_per_call": 1e3 * r["seconds_bound"],
                           "gflop_per_call": 1e-9 * r["flops"],
                           "mbyte_per_call": 1e-6 * r["bytes"],
                           "roofline_share": 100.0 * r["calls"]
                           * r["seconds_bound"] / r["seconds"]}
                    for name, r in sorted(rows.items())}}), flush=True)
    if uncounted or seconds <= 0:
        return None
    return 100.0 * sum(r["calls"] * r["seconds_bound"]
                       for r in rows.values()) / seconds
