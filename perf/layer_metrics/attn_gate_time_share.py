"""``attn_gate_time_share`` — device time of the attention output's gate —
``blk*/mixer.gate``: the ``d x heads`` projection of the block's normed input,
its sigmoid and the product with each head's attention output, between the
flash kernels and ``wo`` — over the device seconds of the step modules of
device 0 in the traced window (``_step_scopes.py``: the program's scope table,
read from the profiler capture's own HLO; in the benchmark's partition these
seconds lie in ``mixer``). A program without the scope (every configuration
without ``attn_gate``, and the parent of the PR that added it) reports
nothing."""
from perf.layer_metrics._step_scopes import table

SCOPE = "blk*/mixer.gate"
LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = table()
    if found is None:
        return None
    seconds = sum(r.seconds for r in found["rows"] if r.scope == SCOPE)
    return 100.0 * seconds / found["seconds"] if seconds > 0 else None
