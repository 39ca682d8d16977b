"""Operations and bytes a step needs, computed from the configuration's
shapes — the yardstick's own arithmetic, found by the name a configuration
file gives under ``job.flops_fn`` / ``job.bytes_fn``."""
from __future__ import annotations

from typing import Any, Dict


def lm_train_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of a dense decoder needs:
    6 x (matmul parameters) + the causal attention term. Matmul parameters
    are the 12 d^2 of each block (qkv 3, out 1, ffn 8 at d_ff = 4 d — taken
    from the shapes, not assumed) and the d x V readout (tied, but the
    readout matmul is still done); the embedding lookup is not a matmul.
    Attention scores and values cost 4 s d a token forward over all s keys;
    a causal mask needs half of them, so forward + backward need 6 L s d,
    not the 12 L s d of the unmasked convention. Recomputation counts
    nothing."""
    d, L, s = app["d_model"], app["n_layers"], app["max_seq"]
    block = 4 * d * d + 2 * d * app["d_ff"]
    matmul_params = L * block + d * app["vocab_size"]
    return 6.0 * matmul_params + 6.0 * L * s * d


def keyed_table_bytes_per_example(app: Dict[str, Any]) -> float:
    """Table bytes one example of the keyed tenant has to move: each of its
    ``num_slots`` rows read once by the pull, read and written once by the
    push (float32 rows of 1 + emb_dim)."""
    row = 4 * (1 + app["emb_dim"])
    return 3.0 * row * app["num_slots"]
