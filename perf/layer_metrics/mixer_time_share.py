"""``mixer_time_share`` — device time of the token mixers — ``blk*/mixer.*`` (projections, rotary, the flash
kernels with their lse / delta broadcasts, the output projection) and
``blk*/kda.*`` (projections, convolutions, gates, the scan kernels),
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "mixer")
