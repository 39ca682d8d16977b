"""Operations and bytes the flash attention calls of a ZAYA1 step NEED,
computed from the configuration's shapes — the yardstick's own arithmetic for
``cca_flash_roofline_share`` (perf/layer_metrics/). The grouped matmuls of
this configuration are counted by ``perf/work/moonlight.py`` (same kernels,
same field names). CCA's preamble (two short convolutions, a mean, an L2
norm) and the router's MLP are XLA's: no kernel, so no roofline; their time
is ``cca_prep_time_share`` and ``moe_routing_time_share``.

Every layer attends causally INSIDE the latent: 8 query heads over 2
key/value heads, 128 wide for q, k and v alike, ``S (S + 1) / 2`` pairs a
query head — the full-causal kernels of ``perf/work/smallthinker.py``, whose
arithmetic this file uses as it stands (FLOPs a pair by kernel, the bytes of
every operand row once with K and V once a K/V head, the larger of the two
times; its docstring has the rules), without a window: a configuration with
no ``window`` key counts the triangle. At 8,192 positions a forward call needs
~1,700 FLOPs a byte against the chip's 240, so the MXU binds every call here.
"""
from __future__ import annotations

from perf.run import load_by_path

_flash = load_by_path("work", "smallthinker")

#: the kernels a ZAYA1 step calls (trace names): the three full-causal ones
KERNELS = tuple(name for name, (_, windowed) in _flash.KERNELS.items()
                if not windowed)
causal_pairs = _flash.causal_pairs
flash_flops_per_call = _flash.flash_flops_per_call
flash_bytes_per_call = _flash.flash_bytes_per_call
bound_seconds = _flash.bound_seconds
