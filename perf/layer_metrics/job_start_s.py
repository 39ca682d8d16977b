"""``job_start_s`` — SUBMIT sent -> first change of the MEASURED job's
counters, on the harness's clock. Its programs are already in the process
(the warm-up ran them), so this is what a tenant who resubmits pays: grant,
table create, init, data load, trace and lowering, and the first drained
window of epochs."""
LAYER = "job"
UNIT = "s"
SOURCE = "host_clock"


def read(obs):
    return obs.get("job_start_s")
