"""What a rematerialised block keeps: the kernels' residuals, by name.

``jax.checkpoint`` re-runs a block's whole forward in the backward pass, the
Pallas kernels with it, unless a policy tells it which values to keep. A
value can be kept only where it has a name, and a ``custom_vjp``'s outputs
take one only INSIDE its ``fwd`` rule, before they are returned both as
primal outputs and as residuals (named outside the ``custom_vjp`` the
kernel runs again; so it does with ``optimize_remat=True``). So each kernel
family's ``fwd`` rule passes what the backward kernel and the ops after it
read through :func:`keep`, under a name of :data:`NAMES`, and
``models/transformer.py`` checkpoints a block under ``save_only_these_names(
*NAMES)``: the kernels' forwards run once a layer, the block's cheap work
(norms, projections, rotary, the glue) is recomputed from the block's input
as before. The kernels' INPUTS carry no name. Without a policy a name is
the identity and lowers to nothing.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, List

from jax.ad_checkpoint import checkpoint_name

#: flash attention's output and per-row log-sum-exp (``ops/attention.py``
#: ``_fa_lse_fwd``: the plain, windowed and block-diffusion kernels' one rule)
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"
#: KDA's output, the state each chunk starts from and each chunk's solve
KDA_OUT, KDA_STATE, KDA_SOLVE = "kda_out", "kda_state", "kda_solve"
#: the state-space scan's output and the state each chunk starts from
SSD_OUT, SSD_STATE = "ssd_out", "ssd_state"
#: the router's ``[T, E]`` float32 logits (``models/moe.py`` ``_route``) and
#: its selection (``ops/top_k_rows.py``)
ROUTER_LOGITS = "router_logits"
ROUTER_WEIGHT, ROUTER_EXPERT = "router_weight", "router_expert"

#: every name a rematerialised block keeps — the ONE tuple the policy reads
NAMES = (FLASH_OUT, FLASH_LSE, KDA_OUT, KDA_STATE, KDA_SOLVE, SSD_OUT,
         SSD_STATE, ROUTER_LOGITS, ROUTER_WEIGHT, ROUTER_EXPERT)

_tracing = threading.local()


@contextlib.contextmanager
def collecting() -> Iterator[Dict[str, List[int]]]:
    """While a block is traced under the policy: ``{name: [arrays, bytes]}``
    of what :func:`keep` named on this thread (empty where the trace was
    JAX's cached one, or nothing was differentiated)."""
    outer, kept = getattr(_tracing, "kept", None), {}
    _tracing.kept = kept
    try:
        yield kept
    finally:
        _tracing.kept = outer


def keep(x, name: str):
    """``x`` under ``name`` (one of :data:`NAMES`), counted where a policy is
    in force (:func:`collecting`)."""
    kept = getattr(_tracing, "kept", None)
    if kept is not None:
        row = kept.setdefault(name, [0, 0])
        row[0] += 1
        row[1] += x.size * x.dtype.itemsize
    return checkpoint_name(x, name)
