"""``device_idle_share`` — 1 - (union of device-operation intervals) /
traced window, mean over the devices."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
