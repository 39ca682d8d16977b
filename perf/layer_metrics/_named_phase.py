"""Shared by the readers of the phases a later PR carved out of
``residual`` (``grant_wait``, ``probe``, ``bookkeeping``): the phase's share
of the tenants' wall (``_phase_share.share``), or nothing — not 0 — from a
program whose budget does not know the phase yet."""
from perf.layer_metrics._phase_share import share


def share_if_known(obs, phase):
    rows = [r for r in (obs.get("phases") or {}).values() if r]
    if not any(phase in p for _wall, p in rows):
        return None
    return share(obs, (phase,))
