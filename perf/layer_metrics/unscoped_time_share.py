"""``unscoped_time_share`` — device time of instructions no scope reached, after inheritance
(``unscoped:<opcode>``): the coverage guard — a refactor that drops a
scope shows here; the printed ``step_scopes`` line lists them by opcode,
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "unscoped")
