"""harmony_tpu.models — neural model families (beyond the reference's apps).

The reference ships classic PS workloads only (SURVEY.md §2.7); this package
adds the model families a TPU framework is actually judged on — starting
with a decoder-only transformer LM whose attention runs on the
harmony_tpu.ops kernels (flash single-chip, ring for sequence parallelism)
and whose parameters live in the same elastic DenseTable substrate as every
other app (so checkpointing, migration and multi-tenancy apply unchanged).
"""
from harmony_tpu.models.generate import make_generate_fn
from harmony_tpu.models.moe import (
    DroplessConfig,
    MoEConfig,
    init_dropless_params,
    init_moe_params,
    moe_ffn,
    moe_ffn_dropless,
)
from harmony_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    TransformerTrainer,
    make_lm_data,
)
from harmony_tpu.models.pytree_trainer import PyTreeTrainer
from harmony_tpu.models.vit import ViT, ViTConfig, ViTTrainer

__all__ = [
    "DroplessConfig",
    "MoEConfig",
    "TransformerConfig",
    "TransformerLM",
    "TransformerTrainer",
    "PyTreeTrainer",
    "ViT",
    "ViTConfig",
    "ViTTrainer",
    "init_dropless_params",
    "init_moe_params",
    "make_generate_fn",
    "make_lm_data",
    "moe_ffn",
    "moe_ffn_dropless",
]
