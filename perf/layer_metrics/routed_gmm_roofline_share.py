"""``routed_gmm_roofline_share`` — ``gmm_roofline_share``'s ratio for a model
whose blocks are not all expert layers: FLOPs the grouped matmuls of the
traced steps NEEDED over what the chip could have done in the device time
they took,

    sum over calls (held rows of the call's step x 2 d f)  /  (seconds x bf16 peak)

with both sides from the same steps of the same trace, paired as
``_moe_kernels.pair`` pairs them (the ``moe.observe`` span lists each drained
step's held token-slots; the calls before a drain are its steps). What
differs is the work file: ``perf/work/moonlight.py`` asks the program which
blocks route (``TransformerConfig.moe_layers()``), so a leading dense layer
is neither counted in the calls a step nor given a share of the rows. Shapes
``[rows, 2048] x [8, 2048, 1408]`` and back: compute-bound at the plan's
tiles (512 x 512 x 1408: over 250 FLOPs a byte), so the bound is the MXU's.
Each kernel's own share goes to a printed line (``routed_gmm_roofline``)."""
import json
import os

from perf.layer_metrics import _moe_kernels as mk

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def traced_steps(obs):
    """``_moe_kernels.traced_steps`` with this configuration's work file."""
    jobs = list(obs.get("phases") or {})
    profile = mk._load()
    if not jobs or profile is None:
        return None
    try:
        from perf.run import load_by_path

        found, cell = mk.kernel_calls(profile), mk.cell_of(jobs)
        if found is None or cell is None:
            return None
        work = load_by_path("work", "moonlight")
        layers = work.moe_layers(cell.job["app_params"])
        per_step = sum(work.CALLS_PER_LAYER.values()) * layers
        gmm = [c for c in found[1] if c[0] in work.CALLS_PER_LAYER]
        calls = mk.pair(gmm, mk.drains(profile, jobs), per_step)
    except Exception:
        return None
    if not calls:
        return None
    return {"cell": cell, "work": work, "layers": layers, "calls": calls}


def read(obs):
    if not obs.get("trace"):
        return None
    found = traced_steps(obs)
    if not found:
        return None
    try:
        import jax

        with open(os.path.join(mk.PERF, "peaks.json")) as f:
            peak = json.load(f)[str(jax.devices()[0].device_kind)]["bf16_flops"]
    except Exception:
        return None
    cell, work, layers = found["cell"], found["work"], found["layers"]
    app = cell.job["app_params"]
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
        "line": "routed_gmm_roofline", "bound": "bf16 MXU peak",
        "expert_layers": layers, "calls_paired": total["calls"],
        "held_rows_per_call": rows_per_call,
        "held_slot_share": rows_per_call / work.slots_per_step(app, cell.batch),
        "kernels": {name: {"calls": r["calls"],
                           "ms_per_call": 1e3 * r["seconds"] / r["calls"],
                           "roofline_share": 100.0 * r["flops"]
                           / (r["seconds"] * peak)}
                    for name, r in sorted(by_kernel.items())}}), flush=True)
    return 100.0 * total["flops"] / (total["seconds"] * peak)
