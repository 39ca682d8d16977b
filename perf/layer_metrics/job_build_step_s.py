"""``job_build_step_s`` — seconds the measured jobs spent under the span
``job.build_step``: ``_build_step`` plus the first dispatch of each new step
program, where it is traced, lowered and compiled or loaded (both lazy). Mean
over the tenants. What a resubmitted job pays for a program the process
already ran."""
from perf.layer_metrics._stage_seconds import mean_seconds

LAYER = "program cache"
UNIT = "s"
SOURCE = "program_counter"


def read(obs):
    return mean_seconds(obs, ("build_step",))
