"""harmony_tpu.ops — Pallas TPU kernels + jittable fallbacks for hot ops.

The reference reaches native compute through Breeze -> netlib JNI -> BLAS
(SURVEY.md §5.9 item 1); the TPU rebuild's equivalent is XLA for everything
fusible plus hand-written Pallas kernels where a custom schedule beats the
compiler: streaming-softmax attention (flash), MXU one-hot histograms
(GBT's hot op), ragged grouped matmuls (experts) and the chunked gated
delta-rule scan (KDA).
"""
from harmony_tpu.ops.attention import blockwise_attention, flash_attention
from harmony_tpu.ops.histogram import weighted_histogram
from harmony_tpu.ops.kda import kda_attention
from harmony_tpu.ops.mxu import mxu_dot
from harmony_tpu.ops.ring import ring_attention
from harmony_tpu.ops.sparse import gather_rows
from harmony_tpu.ops.ulysses import a2a_attention, a2a_self_attention

__all__ = [
    "a2a_attention",
    "a2a_self_attention",
    "blockwise_attention",
    "flash_attention",
    "gather_rows",
    "kda_attention",
    "mxu_dot",
    "ring_attention",
    "weighted_histogram",
]
