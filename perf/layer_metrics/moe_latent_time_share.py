"""``moe_latent_time_share`` — device time of the expert layers' two latent
projections — ``blk*/moe.latent``: every token down to the experts' width
before the dispatch and the routed sum back up after the combine, both
replicated and both for every token whatever share of the experts is held —
over the device seconds of the step modules of device 0 in the traced
window (``_step_scopes.py``: the program's scope table, read from the
profiler capture's own HLO; in the benchmark's partition these seconds lie in
``other_model``). A program without the scope (every configuration whose
experts read the full-width rows, and the parent of the PR that added it)
reports nothing."""
from perf.layer_metrics._step_scopes import table

SCOPE = "blk*/moe.latent"
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
