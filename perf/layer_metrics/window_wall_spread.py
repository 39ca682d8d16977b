"""``window_wall_spread`` — how far the step drifts inside a run: the
interquartile distance over the median of the wall an epoch of the measured
job's regular windows (not its first, not a late one), from the program's
window records (harmony_tpu/metrics/phases.py), mean over the tenants
(``perf/layer_metrics/_windows.py``)."""
from perf.layer_metrics import _windows

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return _windows.mean_over_tenants(obs, lambda row: row["wall_spread"])
