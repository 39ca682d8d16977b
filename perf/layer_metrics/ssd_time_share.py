"""``ssd_time_share`` — device time of the Mamba-2 scan kernels
(``harmony_ssd_fwd`` / ``harmony_ssd_bwd``) over device busy time, from the
run's trace (``_ssd_kernels.py``). The projections, the convolution, the
gate and the norm around them, and the chunk's running sum of the log-decay,
are XLA's and are not in this share."""
from perf.layer_metrics._ssd_kernels import of_this_run

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
