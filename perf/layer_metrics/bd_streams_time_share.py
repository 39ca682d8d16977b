"""``bd_streams_time_share`` — device time of what a block-diffusion step
adds AROUND its flash kernels — ``blk*/mixer.streams``: stacking both
streams' queries along the sequence axis, the noisy rows' own-block term
(``L / B`` tiles of ``B x B``, plain XLA), its merge with the kernels' output
by the two log-sum-exps, and splitting the streams back onto the batch axis —
over the device seconds of the step modules of device 0 in the traced window
(``_step_scopes.py``: the program's scope table, read from the profiler
capture's own HLO; in the benchmark's partition these seconds lie in
``mixer``). A program without the scope (every configuration with another
objective, and the parent of the PR that added it) reports nothing."""
from perf.layer_metrics._step_scopes import table

SCOPE = "blk*/mixer.streams"
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
