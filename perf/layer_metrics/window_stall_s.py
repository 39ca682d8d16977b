"""``window_stall_s`` — seconds the measured jobs' late windows lost, by the
program's own record: the sum over causes of
``harmony_window_stall_seconds_total{job,cause}`` (a drained window whose wall
an epoch exceeded 1.5 x the median of the regular windows before it loses its
wall less that median; harmony_tpu/metrics/phases.py), mean over the tenants,
0 in a run with no late window. ``stall_s``'s twin from inside: that one
reads the same breaks off the client's polls, over the harness's window; this
one over the measured job's whole life, and with a cause
(``perf/layer_metrics/_windows.py`` prints them)."""
from perf.layer_metrics import _windows

LAYER = "step driver"
UNIT = "s"
SOURCE = "program_counter"


def read(obs):
    return _windows.mean_over_tenants(
        obs, lambda row: sum(row["stall_s"].values()))
