"""``gdn_roofline_share`` — the time the traced delta-rule kernel calls of a
Gated DeltaNet cell NEEDED at the chip's binding peak over the device time
they took:

    sum over calls max(FLOPs / bf16 peak, bytes / HBM peak)  /  seconds

Seconds are ALL the ``harmony_gdn_*`` (or ``harmony_kda_*``) events of the
run's trace, by kernel; a call's FLOPs and bytes come from the work file the
cell's configuration names under ``job.flops_fn`` (``perf/work/
qwen3_next.py``: ``GDN_KERNELS`` / ``gdn_bound_seconds`` — the chunked
algorithm's products, and the traffic of ONE scalar decay a head: q and k
once a key head, v and o a value head, ``g`` and ``beta`` a scalar a
position, the boundary states), whichever kernel runs: the yardstick reads
the same work whatever implements it. Which peak binds is printed with each
kernel's own share on the line ``gdn_roofline``. A cell whose work file has
no such table, a trace without the kernels, or no trace: nothing."""
import json
import os

from perf.layer_metrics._gdn_kernels import of_this_run
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
            peaks = json.load(f)[str(jax.devices()[0].device_kind)]
        cell = cell_of(list(obs.get("phases") or {}))
        sibling, _, _ = str(cell.job.get("flops_fn") or "").rpartition(":")
        work = load_by_path("work", sibling)
        app = cell.job["app_params"]
        rows = {name: {"calls": k["calls"], "seconds": k["seconds"],
                       **work.gdn_bound_seconds(app, cell.batch, name, peaks)}
                for name, k in found["kernels"].items()
                if name in work.GDN_KERNELS}
    except Exception:
        return None
    seconds = sum(r["seconds"] for r in rows.values())
    if seconds <= 0:
        return None
    print(json.dumps({
        "line": "gdn_roofline",
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
