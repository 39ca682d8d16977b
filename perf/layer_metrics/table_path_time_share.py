"""``table_path_time_share`` — device time of the table path — ``table.pull`` (the pull and rows -> leaves),
``table.grad_rows`` (gradient leaves -> flat vector -> rows) and
``table.push`` (the fold kernel, the keyed sort and scatter-add), kernels
included,
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "table"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "table_path")
