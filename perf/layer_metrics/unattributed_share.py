"""``unattributed_share`` — (``residual`` + ``barrier_wait``) over the
tenants' wall in the window: admission waits and the process-wide dispatch
order's queueing land here today, beside the drains' host share."""
from perf.layer_metrics._phase_share import share

LAYER = "control"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share(obs, ("residual", "barrier_wait"))
