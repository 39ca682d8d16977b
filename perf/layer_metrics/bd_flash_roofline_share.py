"""``bd_flash_roofline_share`` — the time the traced flash attention calls of
a block-diffusion step NEEDED at the binding peak over the device time they
took (``perf/work/sdar.py``: both streams' queries stacked against the clean
keys under the mask by block and stream, ``L^2`` pairs a head and sequence):

    sum over calls max(FLOPs / bf16 peak, bytes / HBM peak)
        /  sum of the calls' device seconds

Seconds are the ``harmony_flash_bd_*`` events of the run's trace, by kernel
name (``_flash_kernels.py``). Each kernel's own share, its seconds a call and
the peak that binds it go to a printed line (``bd_flash_roofline``). A trace
without those kernels (every configuration with another objective, and the
parent of the PR that added them) reports nothing."""
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
            peaks = json.load(f)[str(jax.devices()[0].device_kind)]
        cell = cell_of(list(obs.get("phases") or {}))
        work = load_by_path("work", "sdar")
        app = cell.job["app_params"]
        rows = {name: {"calls": k["calls"], "seconds": k["seconds"],
                       **work.bound_seconds(app, cell.batch, name, peaks)}
                for name, k in found["kernels"].items()
                if name in work.KERNELS}
    except Exception:
        return None
    seconds = sum(r["seconds"] for r in rows.values())
    if seconds <= 0:
        return None
    print(json.dumps({
        "line": "bd_flash_roofline",
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
