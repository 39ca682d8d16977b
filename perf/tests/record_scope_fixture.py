#!/usr/bin/env python
"""Record the small trace the step-scope readers are checked on
(``harmony_tpu/tracing/stepscopes.py``, ``perf/layer_metrics/_step_scopes.py``;
``tests/test_step_scopes.py``, ``perf/tests/test_step_scopes.py``).

    chiprun --chips 1 -- python perf/tests/record_scope_fixture.py

Run by hand on the chip; writes ``chiprun_out/fixture_scopes.xplane.pb``,
which is copied to ``perf/tests/fixture_scopes.xplane.pb``. The traced
programs are known, so the tests know what the reader must find:

* ``jit__step`` — a step in the worker's shape, four executions: PULL a
  ``[ROWS, 128]`` float32 table into two bf16 weights (``table.pull``), one
  block (``blk0``: ``norm``, a ``D -> F -> D`` ``ffn``), a ``head`` that
  reuses ``w2`` as ``[D, F]`` logits, a log-softmax ``loss``, the gradient
  laid back into rows (``table.grad_rows``) and an SGD ``table.push``.
  Matmuls of ``2 B D F`` FLOPs each: ``blk0/ffn`` two forward and three
  backward (the input carries no gradient), ``head`` one forward and two
  backward;
* ``jit_pull_fn`` — a probe that names nothing (a copy of the table),
  twice: a second module in the same trace, not a step.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

D, F, B = 256, 1024, 512
ROWS = 2 * D * F // 128


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from harmony_tpu.tracing.stepscopes import step_scope

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1

    def loss_fn(params, x):
        w1, w2 = params
        with step_scope("blk", 0):
            with step_scope("norm"):
                xn = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
            with step_scope("ffn"):
                h = jax.nn.gelu(xn @ w1) @ w2
            x = x + h
        with step_scope("head"):
            logits = x.astype(jnp.float32) @ w2.T.astype(jnp.float32)
        with step_scope("loss"):
            return -jax.nn.log_softmax(logits).mean()

    def _step(arr, x):
        with step_scope("table.pull"):
            flat = arr.reshape(-1)
            w1 = flat[:D * F].reshape(D, F).astype(jnp.bfloat16)
            w2 = flat[D * F:].reshape(F, D).astype(jnp.bfloat16)
        with step_scope("compute"):
            loss, (g1, g2) = jax.value_and_grad(loss_fn)((w1, w2), x)
        with step_scope("table.grad_rows"):
            g = jnp.concatenate([g1.reshape(-1), g2.reshape(-1)]).astype(
                jnp.float32).reshape(arr.shape)
        with step_scope("table.push"):
            return arr - 0.01 * g, loss

    def pull_fn(arr):
        return arr * 1.0

    step = jax.jit(_step, donate_argnums=0)
    probe = jax.jit(pull_fn)
    key = jax.random.PRNGKey(0)
    arr = jax.device_put(
        0.02 * jax.random.normal(key, (ROWS, 128), jnp.float32), devices[0])
    x = jax.device_put(jax.random.normal(key, (B, D), jnp.bfloat16),
                       devices[0])
    arr, loss = step(arr, x)  # compile outside the trace
    jax.block_until_ready((loss, probe(arr)))
    out_dir = os.path.join(ROOT, "chiprun_out", "fixture_scopes_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for i in range(4):
        arr, loss = step(arr, x)
        if i % 2:
            jax.block_until_ready(probe(arr))
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    dst = os.path.join(ROOT, "chiprun_out", "fixture_scopes.xplane.pb")
    shutil.copy(found, dst)
    shutil.rmtree(out_dir)
    print(dst, os.path.getsize(dst), float(loss))
    return 0


if __name__ == "__main__":
    sys.exit(main())
