"""``grant_idle_share`` — idle seconds of the first device during which
every worker thread is inside ``taskunit.wait``, over the traced window: the
device stood still because the scheduler granted nobody."""
from perf.layer_metrics import _host_spans

LAYER = "control"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return _host_spans.idle_share("grant_idle_s")
