"""``ssd_roofline_share`` — the time the traced Mamba-2 scan kernel calls
NEEDED at the chip's binding peak over the device time they took:

    sum over calls max(FLOPs / bf16 peak, bytes / HBM peak)  /  seconds

Seconds are ALL the ``harmony_ssd_*`` events of the run's trace, by kernel;
a call's FLOPs and bytes come from the configuration of the cell the
measured job's id names through the benchmark's own work functions
(``perf/work/nemotron_h.py``: the chunked algorithm's products at the
configuration's chunk, ``c b^T`` once a group, and x, b, c, the log-decay, y
and the boundary states as traffic). At 128 positions a chunk, heads of 64
and a state of 128 a call needs ~75 FLOPs a byte, so the bound is HBM's;
which peak binds is printed with each kernel's own share on the line
``ssd_roofline``."""
import json
import os

from perf.layer_metrics._moe_kernels import PERF, cell_of
from perf.layer_metrics._ssd_kernels import of_this_run

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = of_this_run()
    if not found:
        return None
    try:
        import jax

        from perf.run import load_by_path

        with open(os.path.join(PERF, "peaks.json")) as f:
            peaks = json.load(f)[str(jax.devices()[0].device_kind)]
        cell = cell_of(list(obs.get("phases") or {}))
        work = load_by_path("work", "nemotron_h")
        app = cell.job["app_params"]
        rows = {name: {"calls": k["calls"], "seconds": k["seconds"],
                       **work.bound_seconds(app, cell.batch, name, peaks)}
                for name, k in found["kernels"].items()}
    except Exception:
        return None
    seconds = sum(r["seconds"] for r in rows.values())
    if seconds <= 0:
        return None
    print(json.dumps({
        "line": "ssd_roofline",
        "kernels": {name: {"calls": r["calls"], "binds": r["binds"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "bound_ms_per_call": 1e3 * r["seconds_bound"],
                           "gflop_per_call": 1e-9 * r["flops"],
                           "mbyte_per_call": 1e-6 * r["bytes"],
                           "roofline_share": 100.0 * r["calls"]
                           * r["seconds_bound"] / r["seconds"]}
                    for name, r in sorted(rows.items())}}), flush=True)
    return 100.0 * sum(r["calls"] * r["seconds_bound"]
                       for r in rows.values()) / seconds
