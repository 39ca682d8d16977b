"""``head_loss_time_share`` — device time of the readout — ``head`` (final norm, the logits matmul and its
two backward matmuls) and ``loss`` (log-softmax over the f32 logits, the
cross-entropy, the auxiliary sums),
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO)."""
from perf.layer_metrics._step_scopes import share

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    return share(obs, "head_loss")
