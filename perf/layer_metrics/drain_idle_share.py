"""``drain_idle_share`` — idle seconds of the first device whose cause is
``dolphin.metric_drain``, ``drain.stack`` / ``drain.d2h`` / ``drain.emit`` or
``window.bookkeeping``, over the traced window
(perf/layer_metrics/_host_spans.py)."""
from perf.layer_metrics import _host_spans

LAYER = "step driver"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return _host_spans.idle_share("drain_idle_s")
