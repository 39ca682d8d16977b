"""``unnamed_idle_share`` — idle seconds of the first device under no
program span, over the traced window: the coverage guard. Expected near 0;
what it reads is host activity that still has no span."""
from perf.layer_metrics import _host_spans

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return _host_spans.idle_share("unnamed_idle_s")
