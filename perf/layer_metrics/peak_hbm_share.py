"""``peak_hbm_share`` — ``memory_stats()["peak_bytes_in_use"]`` after the
window, max over the devices, over the chip's HBM (perf/peaks.json). Shows
the cell is not a toy."""
LAYER = "device"
UNIT = "%"
SOURCE = "program_counter"


def read(obs):
    if not obs.get("memory_peak_bytes") or not obs.get("hbm_bytes"):
        return None
    return 100.0 * obs["memory_peak_bytes"] / obs["hbm_bytes"]
