"""``window_stall_unnamed_share`` — of the seconds the measured jobs' late
windows lost (``window_stall_s``), the share whose cause is ``unnamed``: the
window's wall grew under no span of the ledger's vocabulary. The coverage
guard: 0 in a run with no late window, and over 50 says the program needs a
span where that time went (``perf/layer_metrics/_windows.py``)."""
from perf.layer_metrics import _windows

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def _share(row):
    total = sum(row["stall_s"].values())
    return (100.0 * row["stall_s"].get(_windows.UNNAMED, 0.0) / total
            if total > 0 else 0.0)


def read(obs):
    return _windows.mean_over_tenants(obs, _share)
