"""``pallas_time_share`` — device time of Mosaic custom calls over device
busy time, from the trace (perf/trace_reduce.py ``classify``)."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    tr = obs.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]
