"""Shared by the job-stage readers: seconds of the measured jobs' start by
stage, from the program's counter ``harmony_job_stage_seconds_total{job,
stage}`` (harmony_tpu/tracing/span.py ``job_stage``: the spans
``job.<stage>`` add their seconds to it). Read in this process — the one
that ran the jobserver — through the registry's own exposition; the
measured jobs are the keys of ``obs["phases"]``. A program without the
counter (the parent of the PR that added it) reports nothing."""

FAMILY = "harmony_job_stage_seconds_total"


def mean_seconds(obs, stages):
    jobs = list((obs.get("phases") or {}))
    if not jobs:
        return None
    try:
        from harmony_tpu.metrics.registry import get_registry, parse_exposition

        family = parse_exposition(get_registry().expose()).get(FAMILY)
    except Exception:
        return None
    if not family:
        return None
    per_job = {}
    for _name, labels, value in family["samples"]:
        if labels.get("job") in jobs and labels.get("stage") in stages:
            per_job[labels["job"]] = per_job.get(labels["job"], 0.0) + float(value)
    if not per_job:
        return None
    # mean over the measured tenants
    return sum(per_job.values()) / len(jobs)
