"""``dense_matmul_roofline_share`` — FLOPs of the matmuls XLA compiled (the
``dot`` / ``convolution`` inside each fusion of class ``matmul`` under a model
scope: 2 x output elements x contracted elements, from the module's own
shapes) over what the chip could have done in those fusions' device seconds
at its bf16 peak (``perf/peaks.json``). The grouped matmuls, flash and KDA
are kernels and have rooflines of their own; the table path and unscoped
instructions hold no model matmul. An event counts its instruction's FLOPs
once, so a fusion run from a loop counts once a trip. The printed line
``dense_matmul_roofline`` gives each scope's share and the highest share of
any single fusion: over 100 there means a miscount."""
import json
import os

from perf.layer_metrics._step_scopes import PERF, TABLE, table

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = table()
    if found is None:
        return None
    try:
        import jax

        with open(os.path.join(PERF, "peaks.json")) as f:
            peak = json.load(f)[str(jax.devices()[0].device_kind)]["bf16_flops"]
    except Exception:
        return None
    rows = [r for r in found["rows"]
            if r.klass == "matmul" and r.flops > 0 and r.seconds > 0
            and r.scope not in TABLE and r.scope != "compute"
            and not r.scope.startswith("unscoped:")]
    seconds = sum(r.seconds for r in rows)
    if seconds <= 0:
        return None
    by_scope = {}
    worst = ("", 0.0)
    for r in rows:
        acc = by_scope.setdefault(f"{r.scope} {r.which}", [0.0, 0.0])
        acc[0] += r.seconds
        acc[1] += r.flops
        for name, (s, f) in r.instrs.items():
            if s > 0 and f / (s * peak) > worst[1]:
                worst = (name, f / (s * peak))
    n = max(1e-9, found["steps"])
    print(json.dumps({
        "line": "dense_matmul_roofline", "bound": "bf16 MXU peak",
        "ms_per_step": 1e3 * seconds / n,
        "tflop_per_step": sum(r.flops for r in rows) / n / 1e12,
        "highest_single_fusion": [worst[0], 100.0 * worst[1]],
        "scopes": {k: {"ms_per_step": 1e3 * s / n,
                       "roofline_share": 100.0 * f / (s * peak)}
                   for k, (s, f) in sorted(by_scope.items(),
                                           key=lambda kv: -kv[1][0])}}),
        flush=True)
    return 100.0 * sum(r.flops for r in rows) / (seconds * peak)
