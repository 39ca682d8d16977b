"""``exit_entropy_share`` — over the measured job, the entropy of its MEAN exit
distribution as a share of the most it can be, ``log T``: with ``m_t`` the
program's counter ``harmony_loop_exit_mass_total{job,step}`` (the sum over the
drained positions of the exit distribution's mass on pass ``t``) over
``harmony_loop_exit_positions_total{job}`` (harmony_tpu/metrics/loop.py),

    100 x ( - sum_t m_t log m_t ) / log T

87.5 under a fresh gate of four passes (``lam`` = 0.5: ``m`` = 1/2, 1/4, 1/8,
1/8), 100 where every pass takes the same mass, 0 when the gate has collapsed
onto one pass — a looped model that trains as a model of one depth. A program
without the counters (a job without an exit gate, and the parent of the PR
that added them) reports nothing."""
import math

MASS = "harmony_loop_exit_mass_total"
POSITIONS = "harmony_loop_exit_positions_total"
LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(obs):
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        fams = parse_exposition(get_registry().expose())
        mass = {}
        for _, labels, v in fams[MASS]["samples"]:
            if labels.get("job") in jobs:
                step = labels["step"]
                mass[step] = mass.get(step, 0.0) + float(v)
        positions = sum(float(v) for _, labels, v in fams[POSITIONS]["samples"]
                        if labels.get("job") in jobs)
    except Exception:  # no such counter: nothing to read
        return None
    if len(mass) < 2 or positions <= 0:
        return None
    shares = [m / positions for m in mass.values() if m > 0]
    return 100.0 * -sum(s * math.log(s) for s in shares) / math.log(len(mass))
