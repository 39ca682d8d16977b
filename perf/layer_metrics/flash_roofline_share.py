"""``flash_roofline_share`` — FLOPs the traced flash attention calls NEEDED
over what the chip could have done in the device time they took:

    sum over calls (heads x S^2 / 2 x 2 x the widths of the kernel's products)
        /  (seconds x bf16 peak)

Seconds are ALL the ``harmony_flash_*`` events of the run's trace, by kernel;
the FLOPs of a call come from the configuration of the cell the measured
job's id names through the benchmark's own work function
(``perf/work/moonlight.py`` ``flash_flops_per_call``: causal, so half the
pairs; the backward kernels' recomputed scores count, they are what each is
defined to do). At 8,192 positions the kernels are compute-bound (a 512-row
q tile against the whole K and V: over 1,000 FLOPs a byte of HBM traffic),
so the bound is the MXU's. Each kernel's own share goes to a printed line
(``flash_roofline``)."""
import json
import os

from perf.layer_metrics._flash_kernels import of_this_run
from perf.layer_metrics._moe_kernels import PERF, cell_of

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
            peak = json.load(f)[str(jax.devices()[0].device_kind)]["bf16_flops"]
        cell = cell_of(list(obs.get("phases") or {}))
        work = load_by_path("work", "moonlight")
        app = cell.job["app_params"]
        rows = {name: {"calls": k["calls"], "seconds": k["seconds"],
                       "flops": k["calls"] * work.flash_flops_per_call(
                           app, cell.batch, name)}
                for name, k in found["kernels"].items()}
    except Exception:
        return None
    seconds = sum(r["seconds"] for r in rows.values())
    if seconds <= 0:
        return None
    print(json.dumps({
        "line": "flash_roofline", "bound": "bf16 MXU peak",
        "kernels": {name: {"calls": r["calls"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "gflop_per_call": 1e-9 * r["flops"] / r["calls"],
                           "roofline_share": 100.0 * r["flops"]
                           / (r["seconds"] * peak)}
                    for name, r in sorted(rows.items())}}), flush=True)
    return 100.0 * sum(r["flops"] for r in rows.values()) / (seconds * peak)
