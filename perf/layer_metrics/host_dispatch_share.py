"""``host_dispatch_share`` — host seconds between batch-ready and device
dispatch over the tenants' wall in the window."""
from perf.layer_metrics._phase_share import share

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share(obs, ("host_dispatch",))
