#!/usr/bin/env python
"""Scratch: compile each configuration's fused step at its real shapes for
a v5e that is described, not attached, and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python perf/aot_compile.py [config ...] [--share 0.5]

Run by hand before chip minutes are spent: it says whether the table and
the step's temporaries fit (the batch in ``gpt2-124m``, 2^24 / 2^26 rows
beside the scatter), and whether the kernels are in the program
(``tpu_custom_call``). Nothing runs; no time or rate comes from here.

The step is the worker's fused PULL -> COMP -> PUSH body
(dolphin/worker.py ``_step_core``) rebuilt from the same public parts —
``TableSpec.pull/push``, the trainer's ``compute``, the phase boundaries —
because a ``WorkerTasklet`` wants a live table on real devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def compile_config(name: str, share: float, chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harmony_tpu.config.base import resolve_symbol
    from harmony_tpu.dolphin.worker import _phase_boundary
    from harmony_tpu.parallel.mesh import build_mesh
    from harmony_tpu.table.table import TableSpec, block_sharding
    from harmony_tpu.utils.platform import traced_on

    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        job = json.load(f)["job"]
    for k, v in job["env"].items():
        os.environ[k] = v
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = build_mesh(list(topo.devices)[:chips], data=1)
    app = dict(job["app_params"])
    if job.get("seed_param"):
        app[job["seed_param"]] = 0
    trainer = resolve_symbol(job["trainer"])(**app)
    spec = TableSpec(trainer.model_table_config())
    tsh = block_sharding(mesh, spec.num_blocks)
    bsh = NamedSharding(mesh, P("data"))
    batch = max(1, int(round(job["batch"] * share)))
    data = resolve_symbol(job["data_fn"])(
        **{**job["data_args"], job["data_count_arg"]: 2, "seed": 0})
    arrays = data if isinstance(data, (tuple, list)) else (data,)
    batch_shapes = tuple(
        jax.ShapeDtypeStruct((batch, *np.asarray(a).shape[1:]),
                             np.asarray(a).dtype, sharding=bsh)
        for a in arrays)
    route = os.environ.get("HARMONY_PUSH_VIA", "scatter")

    def step(arr, batch, hyper):
        if trainer.pull_mode == "all":
            model = _phase_boundary(spec.pull_all(arr), replicate_on=mesh)
            delta, metrics = _phase_boundary(
                trainer.compute(model, batch, hyper), replicate_on=mesh)
            return spec.push_all(arr, delta), metrics
        keys = trainer.pull_keys(batch)
        model = _phase_boundary(spec.pull(arr, keys), replicate_on=mesh)
        delta, metrics = _phase_boundary(
            trainer.compute(model, batch, hyper), replicate_on=mesh)
        return spec.push(arr, keys, delta, via=route), metrics

    arr = jax.ShapeDtypeStruct(spec.storage_shape, spec.dtype, sharding=tsh)
    hyper = {k: jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=NamedSharding(mesh, P()))
             for k in trainer.hyperparams()}
    compiled = jax.jit(traced_on(mesh, step), out_shardings=(tsh, None),
                       donate_argnums=0).lower(
        arr, batch_shapes, hyper).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    gb = 1e9
    init = None
    if hasattr(trainer, "fill_program"):  # the benchmark's own device init
        fa = trainer.fill_program(spec, tsh).lower(
            arr, jax.ShapeDtypeStruct((), jnp.uint32)).compile(
            ).memory_analysis()
        init = {"temp_gb_per_chip": round(fa.temp_size_in_bytes / gb, 3),
                "alias_gb_per_chip": round(fa.alias_size_in_bytes / gb, 3)}
    return {
        "init_fill": init,
        "config": name, "chips": chips, "batch": batch,
        "table_gb_per_chip": round(ma.argument_size_in_bytes / gb, 3),
        "temp_gb_per_chip": round(ma.temp_size_in_bytes / gb, 3),
        "output_gb_per_chip": round(ma.output_size_in_bytes / gb, 3),
        "alias_gb_per_chip": round(ma.alias_size_in_bytes / gb, 3),
        "live_gb_per_chip": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / gb, 3),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "collectives": {op: text.count(op + "(") + text.count(op + "-start(")
                        for op in ("all-reduce", "all-gather",
                                   "reduce-scatter", "collective-permute",
                                   "all-to-all")},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--share", type=float, default=1.0,
                    help="the traffic mix's batch_share")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()
    for name in args.configs or ["criteo-fm", "gpt2-124m"]:
        print(json.dumps(compile_config(name, args.share, args.chips)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
