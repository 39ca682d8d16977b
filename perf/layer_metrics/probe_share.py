"""``probe_share`` — the phase ``probe`` over the tenants' wall in the
window: the blocking comm probe (``dolphin.comm_probe``), admission
excluded."""
from perf.layer_metrics._named_phase import share_if_known

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share_if_known(obs, "probe")
