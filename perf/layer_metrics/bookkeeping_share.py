"""``bookkeeping_share`` — the phase ``bookkeeping`` over the tenants' wall
in the window: the post-drain replay of ``_account_ops`` + ``_finish_epoch``
(span ``window.bookkeeping``)."""
from perf.layer_metrics._named_phase import share_if_known

LAYER = "step driver"
UNIT = "%"
SOURCE = "program_span"


def read(obs):
    return share_if_known(obs, "bookkeeping")
