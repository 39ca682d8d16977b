"""``ffn_time_share`` — device time of the dense MLPs — ``blk*/ffn`` and the shared experts
``blk*/moe.shared``,
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "ffn")
