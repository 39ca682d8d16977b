"""``expert_load_max_over_mean`` — over the measured job, the most loaded
held expert's token-slots over the held experts' mean, from the program's
counter ``harmony_moe_expert_tokens_total{job,layer,expert}`` and the gauge
``harmony_moe_experts_held{job}`` (harmony_tpu/metrics/moe.py; summed over
the layers). 1.0 is perfect balance; the grouped matmuls' ragged groups and
an expert-parallel deployment's slowest chip both follow it."""
from perf.layer_metrics._moe_kernels import load_max_over_mean

LAYER = "model"
UNIT = "ratio"
SOURCE = "program_counter"


def read(obs):
    return load_max_over_mean(obs)
