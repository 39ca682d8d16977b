#!/usr/bin/env python
"""Record the small trace ``perf/tests`` checks ``trace_reduce`` on.

    chiprun --chips <1|4> -- python perf/tests/record_fixture.py

Run by hand on the chip; writes ``chiprun_out/fixture_<n>chip.xplane.pb``,
which is copied to ``perf/tests/fixture_<n>chip.xplane.pb``. The traced
program is known, so the test knows what the reduction must find: four
rounds of (a chain of matmuls, the Pallas row gather, on several chips a
psum), each round followed by a host sleep of 20 ms — so busy time is well
under the window, there are idle gaps of about 20 ms, one kernel and (on
four chips) one collective.
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
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harmony_tpu.ops.sparse import gather_rows

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    table = jax.device_put(jnp.ones((4096, 128), jnp.float32), devices[0])
    idx = jax.device_put(jnp.arange(1024, dtype=jnp.int32) * 3, devices[0])
    w = jax.device_put(jnp.ones((n * 512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("x")))

    @jax.jit
    def chain(w):
        for _ in range(4):
            w = (w @ w[:512].T.astype(w.dtype)[:, :512]) * 0.001
        return w

    gather = jax.jit(gather_rows)
    psum = jax.jit(jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                                 in_specs=P("x"), out_specs=P()))

    def round_():
        out = [chain(w), gather(table, idx)]
        if n > 1:
            out.append(psum(w))
        jax.block_until_ready(out)

    round_()  # compile outside the trace
    out_dir = os.path.join(ROOT, "chiprun_out", f"fixture_{n}chip_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for _ in range(4):
        round_()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    dst = os.path.join(ROOT, "chiprun_out", f"fixture_{n}chip.xplane.pb")
    shutil.copy(found, dst)
    shutil.rmtree(out_dir)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
