"""``input_wait_share`` — prefetch consumer-stall seconds over the tenants'
wall in the window. Expected near 0 everywhere (the data set is resident on
the device after the first epoch): a guard."""
from perf.layer_metrics._phase_share import share

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share(obs, ("input_wait",))
