"""``grant_wait_share`` — the phase ``grant_wait`` over the tenants' wall in
the window: every ``taskunit.wait`` (COMP / NET / CPU units) and
dispatch-turn admission of the training thread, measured by the span's own
clock reads. Carved out of ``residual``: ``unattributed_share`` lost what
this names."""
from perf.layer_metrics._named_phase import share_if_known

LAYER = "control"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share_if_known(obs, "grant_wait")
