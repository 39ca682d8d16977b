#!/usr/bin/env python
"""Record the small capture ``perf/tests/test_window_metrics.py`` checks the
window readers' straddle test on (``perf/layer_metrics/_windows.py``).

    JAX_PLATFORMS=cpu python perf/tests/record_window_fixture.py

Needs no chip: only the host plane is read. Writes
``perf/tests/fixture_windows.xplane.pb``. The traced program is known: two
jobs' container spans, as ``dolphin/worker.py`` opens them (``job_id``,
``epoch``, ``epochs``, ``window``), with the profiler (the harness's own
options, Python tracer off) started INSIDE ``fixture-a``'s window 2 (epoch
16) and stopped inside its window 6 (epoch 48). An annotation open at either
end leaves no event, so the capture holds ``fixture-a``'s windows 3..5
(epochs 24..40) and ``fixture-b``'s 2..4 (epochs 16..32): the windows that
straddled the start are ``fixture-a``'s of epoch 16 and the stop its of
epoch 48.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harmony_tpu.tracing import trace_span

    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()  # compile outside the trace

    def window(job, n, **more):
        return trace_span("dolphin.epoch_window", job_id=job, worker_id="w0",
                          epoch=8 * n, epochs=8, window=n, fused=False,
                          **more)

    out_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with trace_span("dolphin.worker", job_id="fixture-a"):
        with window("fixture-a", 2):
            jax.profiler.start_trace(out_dir, profiler_options=options)
            f(x).block_until_ready()
        for n in (3, 4, 5):
            for job, m in (("fixture-a", n), ("fixture-b", n - 1)):
                with window(job, m):
                    with trace_span("step.dispatch", record=False):
                        f(x).block_until_ready()
        with window("fixture-a", 6):
            jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    dst = os.path.join(HERE, "fixture_windows.xplane.pb")
    shutil.copy(found, dst)
    shutil.rmtree(out_dir)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
