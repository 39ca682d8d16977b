"""``gmm_roofline_share`` — FLOPs the grouped matmuls of the traced steps
NEEDED over what the chip could have done in the device time they took:

    sum over calls (held rows of the call's step x 2 d f)  /  (seconds x bf16 peak)

Both sides come from the same steps of the same trace
(``_moe_kernels.traced_steps``): seconds are the ``harmony_gmm_*`` events of
every whole step whose drain lies in the trace, rows are what the program
reported for exactly those steps (the ``moe.observe`` span's annotation,
summed over the layers — so a call counts its step's mean layer, and a whole
step counts exactly). Shapes come from the configuration of the cell the
measured job's id names, through the benchmark's own work function
(``perf/work/olmoe.py``). These matmuls are compute-bound at every tile the
plan picks (a 512-row tile against a [1024, 1024] weight tile: 256 FLOPs a
byte), so the bound is the MXU's. Each kernel's own share goes to a printed
line (``gmm_roofline``)."""
import json
import os

from perf.layer_metrics._moe_kernels import PERF, traced_steps

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = traced_steps(obs)
    if not found:
        return None
    try:
        import jax

        with open(os.path.join(PERF, "peaks.json")) as f:
            peak = json.load(f)[str(jax.devices()[0].device_kind)]["bf16_flops"]
    except Exception:
        return None
    cell, work = found["cell"], found["work"]
    app = cell.job["app_params"]
    layers = work.moe_layers(app)
    by_kernel = {}
    for name, seconds, step_slots in found["calls"]:
        row = by_kernel.setdefault(name, {"calls": 0, "seconds": 0.0,
                                          "flops": 0.0, "rows": 0.0})
        row["calls"] += 1
        row["seconds"] += seconds
        row["rows"] += step_slots / layers
        row["flops"] += work.gmm_flops_per_call(app, step_slots / layers)
    total = {k: sum(r[k] for r in by_kernel.values())
             for k in ("calls", "seconds", "flops", "rows")}
    if total["seconds"] <= 0:
        return None
    rows_per_call = total["rows"] / total["calls"]
    print(json.dumps({
        "line": "gmm_roofline", "bound": "bf16 MXU peak",
        "calls_paired": total["calls"],
        "held_rows_per_call": rows_per_call,
        "held_slot_share": rows_per_call / work.slots_per_step(app, cell.batch),
        "kernels": {name: {"calls": r["calls"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "roofline_share": 100.0 * r["flops"]
                           / (r["seconds"] * peak)}
                    for name, r in sorted(by_kernel.items())}}), flush=True)
    return 100.0 * total["flops"] / (total["seconds"] * peak)
