"""``step_mfu_share`` — the share of the chips' bf16 peak that the model's own
arithmetic amounts to at the rate this run measured: 100 x the
``share_of_peak`` of the line ``model_flops_utilisation`` that ``perf/run.py``
prints in every run of a cell whose configuration names a ``job.flops_fn``
(``work_model_shares`` there: the tenants' fitted tokens/s, summed, x the
FLOPs a token of the corpus by the count the configuration names —
``perf/work_models.py`` ``lm_train_flops_per_token``, or its own
``"<sibling>:<function>"`` in ``perf/work/<sibling>.py``, found by
``work_models.resolve`` — / (``perf/peaks.json`` ``bf16_flops`` x the cell's
chips)). One count a configuration, one quotient: the harness hands it over
in ``obs``.

A fixed multiple of ``lm_tokens_per_s``: it finds nothing the rate does not.
It is here to BOUND claims: a kernel's roofline may fall silent (the kernel
left the path, a span it pairs with moved) and the whole step's share still
says what the chip did with the time. So it depends on NO trace event, span
or counter of the program. None only where the harness computed no share: no
tenant's rate was fitted, or the device has no row of peaks (a CPU rehearsal:
never a device number from a CPU)."""

LAYER = "model"
UNIT = "%"
SOURCE = "host_clock"


def read(obs):
    share = obs.get("model_flops_share")
    return None if share is None else 100.0 * share
