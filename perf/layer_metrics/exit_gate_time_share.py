"""``exit_gate_time_share`` — device time of a looped model's exit gate — the
scope ``exit.gate``: every pass's ``d``-wide gate product and sigmoid, the
exit distribution, its entropy and the weighted sum of the passes'
cross-entropies, forward and backward — over the device seconds of the step
modules of device 0 in the traced window (``_step_scopes.py``: the program's
scope table, read from the profiler capture's own HLO; in the benchmark's
partition these seconds lie in ``other_model``). A program without the scope
(every configuration without ``exit_gate``, and the parent of the PR that
added it) reports nothing."""
from perf.layer_metrics._step_scopes import table

SCOPE = "exit.gate"
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
