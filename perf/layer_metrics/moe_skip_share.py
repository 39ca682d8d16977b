"""``moe_skip_share`` — over the measured job, the share of token-slots whose
router chose "no expert" (``moe_null_expert``): the program's counter
``harmony_moe_null_slots_total{job}`` over that plus every slot an expert was
chosen for, ``harmony_moe_expert_tokens_total{job,layer,expert}``
(harmony_tpu/metrics/moe.py). Such a slot is computed on no device: the
model's own saving, apart from ``harmony_moe_absent_slots_total`` (slots
whose expert another device holds: the deployment's cut). 0 while the
selection bias of "no expert" stands where it starts (-1, held). A program
without the counter (a job whose router has no such output, and the parent of
the PR that added it) reports nothing."""
NULL = "harmony_moe_null_slots_total"
TOKENS = "harmony_moe_expert_tokens_total"
LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(obs):
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        fams = parse_exposition(get_registry().expose())
        total = {name: [float(v) for _, labels, v in fams[name]["samples"]
                        if labels.get("job") in jobs]
                 for name in (NULL, TOKENS)}
    except Exception:  # no such counter: nothing to read
        return None
    slots = sum(total[NULL]) + sum(total[TOKENS])
    if not total[NULL] or slots <= 0:
        return None
    return 100.0 * sum(total[NULL]) / slots
