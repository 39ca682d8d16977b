"""Driver entry-point coverage: entry() hands the caller numpy arrays in
``init``'s layout and opens no backend; at cluster width: dryrun_multichip — the
full framework training-step suite (PS step, sparse FM, SP ring, dp x sp
x tp, pipeline, expert-parallel) — must compile AND execute on a
32-virtual-device mesh (the driver itself runs it at 8; this pins the
wider dp x sp x tp regime the reference's cluster scheduler served,
SchedulerImpl.java:28-66). The dryrun spawns its own sanitized
subprocess, so ambient accelerator health is irrelevant."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_ENTRY_CHILD = """
import inspect, json, sys
sys.path.insert(0, sys.argv[1])
import __graft_entry__ as g
fn, args = g.entry()
import jax, numpy as np
from jax._src import xla_bridge
said = {"opened_by_entry": xla_bridge.backends_are_initialized()}
params, tokens = args
said["numpy"] = all(type(a) is np.ndarray
                    for a in jax.tree.leaves(params) + [tokens])
out = jax.eval_shape(fn, *args)
said["traced"] = [list(out.shape), str(out.dtype)]
of = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
model = inspect.getclosurevars(fn).nonlocals["model"]
said["inits_leaves"] = of(params) == of(model.init(jax.random.PRNGKey(0)))
said["loss"] = float(jax.jit(fn)(*args))
print(json.dumps(said))
"""


def test_entry_returns_inits_leaves_in_numpy_and_opens_no_backend():
    """entry()'s promise, in a child of its own (this process has long
    opened a backend): the arrays are numpy's, in ``init``'s structure,
    shapes and dtypes; no backend is open when it returns; the caller's
    trace goes through and its jit reads a finite loss."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _ENTRY_CHILD, REPO], env=env,
                          text=True, capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    said = json.loads(proc.stdout.splitlines()[-1])
    loss = said.pop("loss")
    assert said == {"opened_by_entry": False, "numpy": True,
                    "traced": [[], "float32"], "inits_leaves": True}
    assert 0.0 < loss < 20.0


@pytest.mark.slow
def test_dryrun_multichip_32_devices():
    import __graft_entry__ as g

    g.dryrun_multichip(32, timeout_s=900.0)
