"""``moe_routing_time_share`` — device time of what surrounds the grouped matmuls in an expert layer —
``blk*/moe.route``, ``.dispatch`` (sorts, permutation gathers), ``.combine``,
``.aux``, and what of ``.experts`` is not a ``harmony_gmm_*`` kernel (the SiLU
and product between them, the ``where``s),
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "moe_routing")
