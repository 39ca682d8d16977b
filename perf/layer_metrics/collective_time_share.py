"""``collective_time_share`` — device time of all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all operations over the traced
window, mean over the devices. Nothing to read on one device."""
LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"


def read(obs):
    tr = obs.get("trace")
    if not tr or tr["devices"] < 2 or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
