"""``hetero_flash_roofline_share`` — the time the traced flash attention calls
NEEDED at the binding peak over the device time they took, each kernel
credited by the CELL's own work file:

    sum over calls max(FLOPs / bf16 peak, bytes / HBM peak)
        /  sum of the calls' device seconds

Seconds are ALL the ``harmony_flash_*`` events of the run's trace, by kernel
name. What a call of each kernel needs is the answer of the work file the
cell's configuration names under ``job.flops_fn`` (``"<sibling>:<function>"``:
``perf/work/<sibling>.py``), which has to export ``KERNELS`` (kernel name in a
trace -> (forward or backward, the kind of block that calls it)),
``bound_seconds(app, batch, kernel, peaks)`` and ``heads(app, kind)`` — so a
model whose kinds of block differ in their HEAD COUNT as well as their mask
(``perf/work/laguna.py``: the ``full`` blocks' heads x the triangle, the
``swa`` blocks' heads x the band, the fused backward at five products a pair)
is read by the file that counts its step, and the next configuration brings a
work file, not a reader. Each kernel's own share, its seconds a call and the
peak that binds it go to a printed line (``hetero_flash_roofline``).

No trace, a trace without flash kernels, or a cell whose work file has no
``KERNELS``: nothing. A ``harmony_flash_*`` kernel in the trace that the work
file has NO row for is named in the printed line (``uncounted``) and the
metric is left out of the run's line — a share over the kernels that happen
to be known would be a share of something else, and the older per-model
readers fell silent over exactly that without a word."""
import json
import os

from perf.layer_metrics._flash_kernels import of_this_run
from perf.layer_metrics._moe_kernels import PERF, cell_of

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def work_of(cell):
    """The cell's work file, if it counts flash kernels by kind; else None."""
    from perf.run import load_by_path

    sibling, colon, _ = str(cell.job.get("flops_fn") or "").rpartition(":")
    if not colon or not os.path.exists(
            os.path.join(PERF, "work", sibling + ".py")):
        return None
    work = load_by_path("work", sibling)
    return work if all(hasattr(work, name) for name in (
        "KERNELS", "bound_seconds", "heads")) else None


def read(obs):
    if not obs.get("trace"):
        return None
    found = of_this_run()
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
    uncounted = sorted(set(found["kernels"]) - set(work.KERNELS))
    rows = {name: {"calls": k["calls"], "seconds": k["seconds"],
                   **work.bound_seconds(app, cell.batch, name, peaks)}
            for name, k in found["kernels"].items() if name in work.KERNELS}
    seconds = sum(r["seconds"] for r in rows.values())
    print(json.dumps({
        "line": "hetero_flash_roofline", "work": work.__file__[len(PERF) + 1:],
        "uncounted": uncounted,
        "kernels": {name: {"calls": r["calls"], "binds": r["binds"],
                           "heads": work.heads(app, work.KERNELS[name][1]),
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
