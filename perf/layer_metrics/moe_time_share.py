"""``moe_time_share`` — device time of the expert layer's own kernels
(``harmony_gmm_fwd`` / ``_dx`` / ``_dw`` and any ``harmony_moe_*`` custom
call) over device busy time, from the run's trace (``_moe_kernels.py``). The
routing around them (softmax, top-k, the two sorts, the gathers) is XLA's
and is not in this share."""
from perf.layer_metrics._moe_kernels import of_this_run

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = of_this_run()
    if not found or found["busy_s"] <= 0:
        return None
    return 100.0 * sum(k["seconds"] for k in found["kernels"].values()
                       ) / found["busy_s"]
