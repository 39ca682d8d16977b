"""``job_init_s`` — seconds the measured jobs spent under the spans
``job.table_create``, ``job.init`` (``trainer.init_global_settings``) and
``job.data_load``, summed; mean over the tenants."""
from perf.layer_metrics._stage_seconds import mean_seconds

LAYER = "job"
UNIT = "s"
SOURCE = "program_counter"


def read(obs):
    return mean_seconds(obs, ("table_create", "init", "data_load"))
