"""Shared by the phase-budget readers: a phase's share of the tenants' wall
over the window, from STATUS ``phase_budget`` (metrics/phases.py). Only the
host-clock phases are read — ``input_wait``, ``host_dispatch``,
``barrier_wait``, ``residual``; in the fused step ``pull_comm`` /
``compute`` / ``push_comm`` are a model, not a measurement."""


def share(obs, phases):
    rows = [r for r in (obs.get("phases") or {}).values() if r]
    if not rows:
        return None
    # mean over tenants of the tenant's own share
    return 100.0 * sum(sum(p.get(k, 0.0) for k in phases) / wall
                       for wall, p in rows) / len(rows)
