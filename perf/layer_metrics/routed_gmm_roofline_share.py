"""``routed_gmm_roofline_share`` — ``gmm_roofline_share``'s ratio for a model
whose blocks are not all expert layers: FLOPs the grouped matmuls of the
traced steps NEEDED over what the chip could have done in the device time
they took,

    sum over calls (held rows of the call's step x 2 d f)  /  (seconds x bf16 peak)

with both sides from the same steps of the same trace, paired as
``_moe_kernels.pair`` pairs them (the device's ``XLA Modules`` events cut the
calls into steps; the ``moe.observe`` span lists each drained step's held
token-slots, in order). What differs is the work file:
``perf/work/moonlight.py`` asks the program which blocks route
(``TransformerConfig.moe_layers()``), so a leading dense layer is neither
counted in the calls a step nor given a share of the rows. Shapes
``[rows, 2048] x [8, 2048, 1408]`` and back: compute-bound at the plan's
tiles (512 x 512 x 1408: over 250 FLOPs a byte), so the bound is the MXU's.
Each kernel's own share goes to a printed line (``routed_gmm_roofline``)."""
from perf.layer_metrics import _moe_kernels as mk

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def traced_steps(obs):
    """``_moe_kernels.traced_steps`` with this configuration's work file."""
    return mk.traced_steps(obs, "moonlight")


def read(obs):
    return mk.roofline_share(obs, "moonlight", "routed_gmm_roofline")
