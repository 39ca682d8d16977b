"""``gdn_time_share`` — device time of the scalar-decay delta-rule kernels
(``harmony_gdn_fwd`` / ``harmony_gdn_bwd``; ``harmony_kda_*`` where those are
what the cell's Gated DeltaNet blocks run) over device busy time, from the
run's trace (``_gdn_kernels.py``). The projection, the convolution, the
gates and norms around them, and the chunk's running sum of the log-decay,
are XLA's and are not in this share."""
from perf.layer_metrics._gdn_kernels import of_this_run

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
