"""``stall_s`` — seconds of the window the tenants' feeds lost to breaks: the
sum, over the gaps between feeds that ``perf/rates.py`` ``steady`` leaves out
of the rate, of gap - median gap; the mean over the tenants, 0 in a run whose
feeds were regular. The rate says how fast the program runs while it runs;
this says how long it stood still."""
LAYER = "control"
UNIT = "s"
SOURCE = "host_clock"


def read(obs):
    fits = obs.get("fits")
    if not fits:
        return None
    return sum(f["stall_s"] for f in fits) / len(fits)
