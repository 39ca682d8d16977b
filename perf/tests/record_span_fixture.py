#!/usr/bin/env python
"""Record the small trace ``perf/tests/test_span_metrics.py`` checks the
span readers on (``perf/layer_metrics/_host_spans.py``).

    chiprun --chips 1 -- python perf/tests/record_span_fixture.py

Run by hand on the chip; writes ``chiprun_out/fixture_spans.xplane.pb``,
which is copied to ``perf/tests/fixture_spans.xplane.pb``. The traced
program is known, so the test knows what the readers must find: four rounds
of ``harmony/work`` (a chain of matmuls dispatched and blocked on) each
followed by ``harmony/sleep`` (20 ms of host sleep) — the four ~20 ms idle
gaps of the device lie under ``sleep``, none under ``work``, and nothing is
unnamed. The profiler runs with the harness's own options (Python tracer
off): the events are TraceMe's, not the Python tracer's.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harmony_tpu.tracing import trace_span

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    w = jax.device_put(jnp.ones((2048, 2048), jnp.bfloat16), devices[0])

    @jax.jit
    def chain(w):
        for _ in range(16):
            w = (w @ w) * 0.0001
        return w

    jax.block_until_ready(chain(w))  # compile outside the trace
    out_dir = os.path.join(ROOT, "chiprun_out", "fixture_spans_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with trace_span("dolphin.worker", job_id="fixture"):
        for i in range(4):
            with trace_span("work", record=False, round=i):
                jax.block_until_ready(chain(w))
            with trace_span("sleep", round=i):
                time.sleep(0.02)
        with trace_span("work", record=False, round=4):
            jax.block_until_ready(chain(w))  # the last sleep is a gap too
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    dst = os.path.join(ROOT, "chiprun_out", "fixture_spans.xplane.pb")
    shutil.copy(found, dst)
    shutil.rmtree(out_dir)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
