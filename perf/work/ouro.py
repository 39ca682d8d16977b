"""Operations and bytes a training step of Ouro-2.6B NEEDS, computed from the
configuration's shapes — the yardstick's own arithmetic for ``step_mfu_share``
(``train_flops_per_token``, named by the configuration's ``job.flops_fn`` as
``"ouro:..."``), for ``hetero_flash_roofline_share`` and for
``exit_readout_roofline_share`` (perf/layer_metrics/).

**The model** (``perf/reference/ouro-2.6b.py``): ``n_layers`` dense blocks —
softmax attention of ``n_heads`` heads of ``d_model / n_heads`` columns over the
whole causal past, a SwiGLU MLP of ``d_ff`` columns, four norms — run
``loop_steps`` = T times a step over ONE set of weights, and after every pass
an exit: the untied ``d x V`` readout and a ``d``-wide gate.

**FLOPs a token of the CORPUS** (``perf/work_models.py``'s contract; a
multiply-add is 2 FLOPs, forward 2, backward 4; recomputation — ``remat``,
the flash kernels' scores — counts nothing). A weight that is USED T times a
step is passed T times by every token, whatever the table holds of it once:

  dense            6 x T x n_layers x (attention's ``d 3d + d d`` + the MLP's
                   ``3 d d_ff``)
  routed           nothing: no experts
  attention_pairs  3 x T x n_layers x ``H x 2 (hd + hd) x pairs / S``, ``pairs
                   = S (S + 1) / 2`` (the triangle) a head and sequence
  scans            nothing: no layer scans
  readout          6 x T x ``d V``: every pass is read out over the whole
                   vocabulary

Embedding lookups, the norms, the rotary, the gate (a ``d``-wide product a
position and pass on the vector unit: 6 x T x d = 49 k of 8.15 G), the exit
distribution, the optimizer and the table path count nothing: no matmul.

**The flash kernels** as THIS tree names them (``ops/attention.py``):
``harmony_flash_fwd`` / ``harmony_flash_bwd``, one forward and one fused
backward a block APPLICATION (T x n_layers of each a step), counted as
``perf/work/laguna.py`` counts its full-causal kind: the forward's two
products a pair ``4 hd``, the fused backward's five ``10 hd``, the triangle;
bytes every operand row once.

**The readout kernels** (``ops/readout_loss.py``): ``harmony_readout_fwd``,
``harmony_readout_bwd_dx``, ``harmony_readout_bwd_dw``, each T calls a step
over ``N = batch x S`` rows. A call needs ``2 N V d`` FLOPs (one product: ``x
head``, ``g head^T``, ``x^T g``) and must move at least the float32 logits
ONCE (the forward writes them, each backward kernel reads them), the rows
``x`` (or ``dx``) in the activation dtype and the head in bfloat16 (``dW``
leaves in float32), every array once whatever the tiling re-reads. At 2,048
columns of ``d`` a call needs ~800 FLOPs a byte against the chip's 240: the
MXU binds all three (4.2 ms a call at the bf16 peak, 1.3 ms of HBM); the
logits' one pass each is what the op exists for, and it shows as a call's time
over its bound, not as the bound.
"""
from __future__ import annotations

from typing import Any, Dict

FLOAT32, BFLOAT16 = 4, 2
#: kernel name in a device trace -> (forward or the fused backward, the kind
#: of block that calls it): one kind, the whole causal past
KERNELS = {"harmony_flash_fwd": ("fwd", "mha"),
           "harmony_flash_bwd": ("bwd", "mha")}
#: products of head width a pair costs, by kernel (perf/work/laguna.py's)
PRODUCTS = {"fwd": 2, "bwd": 5}
#: the readout op's kernels in a device trace -> which of its three products
READOUT_KERNELS = {"harmony_readout_fwd": "fwd",
                   "harmony_readout_bwd_dx": "dx",
                   "harmony_readout_bwd_dw": "dw"}
#: every key of ``app_params`` the count has a rule for (most: "counts
#: nothing"); another raises
KNOWN = frozenset((
    "vocab_size", "d_model", "n_heads", "n_layers", "d_ff", "max_seq", "pos",
    "rope_theta", "ffn", "tie_embeddings", "norm_eps", "loop_steps",
    "sandwich_norm", "exit_gate", "exit_entropy_weight", "embed_std", "remat",
    "attn", "dtype", "optimizer", "step_size", "beta2", "seed"))


def _checked(app: Dict[str, Any]) -> None:
    """This file counts next-token training of a looped dense SwiGLU model
    with an exit a pass and an untied readout, and nothing else."""
    unknown = sorted(set(app) - KNOWN)
    if unknown or app.get("ffn") != "swiglu" or app.get("pos") != "rope" \
            or app.get("tie_embeddings", True) \
            or int(app.get("loop_steps", 1)) < 2 or not app.get("exit_gate"):
        raise ValueError(
            f"not counted here: unknown keys {unknown}, ffn "
            f"{app.get('ffn')!r}, pos {app.get('pos')!r}, tie_embeddings "
            f"{app.get('tie_embeddings', True)!r}, loop_steps "
            f"{app.get('loop_steps', 1)!r}, exit_gate {app.get('exit_gate')!r}")


def heads(app: Dict[str, Any], kind: str = "mha") -> int:
    """The query heads of a block (one kind)."""
    return int(app["n_heads"])


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def _shape(app: Dict[str, Any]):
    """``(H, hd, S)``."""
    h = int(app["n_heads"])
    return h, int(app["d_model"]) // h, int(app["max_seq"])


def train_flops_split(app: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs one token of the corpus needs forward + backward, by
    ``perf/work_models.py`` ``PARTS`` (module docstring)."""
    _checked(app)
    h, hd, s = _shape(app)
    d, f = int(app["d_model"]), int(app["d_ff"])
    applications = int(app["loop_steps"]) * int(app["n_layers"])
    return {
        "dense": 6.0 * applications * (d * 3 * d + d * d + 3 * d * f),
        "routed": 0.0,
        "attention_pairs": 3.0 * applications * h * 2 * (hd + hd)
        * causal_pairs(s) / s,
        "scans": 0.0,
        "readout": 6.0 * int(app["loop_steps"]) * d * int(app["vocab_size"]),
    }


def train_flops_per_token(app: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token of the corpus needs
    (perf/tests/test_ouro.py ``HAND`` holds the configuration to a count by
    hand)."""
    return float(sum(train_flops_split(app).values()))


def _bound(flops: float, nbytes: float, peaks: Dict[str, float]
           ) -> Dict[str, Any]:
    """``{flops, bytes, seconds_bound, binds}``: the larger of a call's
    compute time at the bf16 peak and its traffic time at the HBM peak."""
    t_mxu, t_hbm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds_bound": max(t_mxu, t_hbm),
            "binds": "bf16 MXU peak" if t_mxu >= t_hbm else "HBM peak"}


def _act(app: Dict[str, Any]) -> int:
    return BFLOAT16 if str(app.get("dtype", "float32")) == "bfloat16" \
        else FLOAT32


def bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                  peaks: Dict[str, float]) -> Dict[str, Any]:
    """One call of the flash ``kernel`` (a trace name of ``KERNELS``) over
    ``batch`` sequences: ``H`` heads x the triangle x 2 x the widths of its
    products; q, k, v, o and a float32 statistic a row forward, q, dO, k, v,
    two statistics in and dq, dk, dv out backward."""
    which, _ = KERNELS[kernel]
    h, hd, s = _shape(app)
    flops = 2.0 * PRODUCTS[which] * hd * int(batch) * h * causal_pairs(s)
    rows, stat = h * s * hd * _act(app), h * s * FLOAT32
    per_seq = {"fwd": 4 * rows + stat, "bwd": 7 * rows + 2 * stat}[which]
    return _bound(flops, float(int(batch) * per_seq), peaks)


def readout_bound_seconds(app: Dict[str, Any], batch: int, kernel: str,
                          peaks: Dict[str, float]) -> Dict[str, Any]:
    """One call of the readout ``kernel`` (a trace name of
    ``READOUT_KERNELS``) over ``batch`` sequences (module docstring)."""
    which = READOUT_KERNELS[kernel]
    n = int(batch) * int(app["max_seq"])
    d, v = int(app["d_model"]), int(app["vocab_size"])
    logits, rows = n * v * FLOAT32, n * d * _act(app)
    head = d * v * (FLOAT32 if which == "dw" else BFLOAT16)
    return _bound(2.0 * n * v * d, float(logits + rows + head), peaks)
