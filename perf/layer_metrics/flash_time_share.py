"""``flash_time_share`` — device time of the flash attention kernels
(``harmony_flash_fwd`` / ``_bwd_dkv`` / ``_bwd_dq``) over device busy time,
from the run's trace (``_flash_kernels.py``). The projections around them
(latent attention's ``Wq``, ``Wkv_a``, ``Wkv_b``, ``Wo``), the rotary and the
lse/delta broadcasts are XLA's and are not in this share."""
from perf.layer_metrics._flash_kernels import of_this_run

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    if not obs.get("trace"):
        return None
    found = of_this_run()
    if not found or found["busy_s"] <= 0:
        return None
    return 100.0 * sum(k["seconds"] for k in found["kernels"].values()
                       ) / found["busy_s"]
