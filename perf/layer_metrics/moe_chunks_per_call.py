"""``moe_chunks_per_call`` — over the measured job, the chunks of the expert
layer's static capacity its layer calls ran, per call: the program's counters
``harmony_moe_chunks_total{job}`` over ``harmony_moe_layer_calls_total{job}``
(harmony_tpu/metrics/moe.py). 1.0: every call's held token-slots fitted one
chunk; above it the router drifted past the capacity's headroom and calls ran
a second chunk; 0: the plain full-length path runs (no chunking at this held
share). A program without the counters (the parent of the PR that added
them) reports nothing."""
CHUNKS = "harmony_moe_chunks_total"
CALLS = "harmony_moe_layer_calls_total"
LAYER = "model"
UNIT = "ratio"
SOURCE = "program_counter"


def read(obs):
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        fams = parse_exposition(get_registry().expose())
        total = {name: sum(float(v) for _, labels, v in fams[name]["samples"]
                           if labels.get("job") in jobs)
                 for name in (CHUNKS, CALLS)}
    except Exception:  # no such counter: nothing to read
        return None
    return total[CHUNKS] / total[CALLS] if total[CALLS] > 0 else None
