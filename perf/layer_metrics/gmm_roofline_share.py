"""``gmm_roofline_share`` — FLOPs the grouped matmuls of the traced steps
NEEDED over what the chip could have done in the device time they took:

    sum over calls (held rows of the call's step x 2 d f)  /  (seconds x bf16 peak)

Both sides come from the same steps of the same trace
(``_moe_kernels.traced_steps``): seconds are the ``harmony_gmm_*`` events
inside every whole execution of the step program (the device's ``XLA
Modules`` events cut the calls into steps) whose rows a ``moe.observe`` span
in the trace reports, rows are what the program reported for exactly those
steps, in order (the span's annotation, summed over the layers — so a call
counts its step's mean layer, and a whole step counts exactly). Shapes come
from the configuration of the cell the measured job's id names, through the
benchmark's own work function (``perf/work/olmoe.py``). These matmuls are
compute-bound at every tile the plan picks (a 512-row tile against a
[1024, 1024] weight tile: 256 FLOPs a byte), so the bound is the MXU's. Each
kernel's own share goes to a printed line (``gmm_roofline``)."""
from perf.layer_metrics._moe_kernels import roofline_share

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return roofline_share(obs, "olmoe", "gmm_roofline")
