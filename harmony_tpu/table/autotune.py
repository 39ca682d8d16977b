"""Measurement-driven push-route selection.

The keyed additive push has two routes (TableSpec.push): "scatter" — on a
TPU mesh over float32 rows 128 wide the in-place Pallas row scatter-add
(ops.sparse.scatter_add_rows, PR 28; every measurement below predates it),
elsewhere XLA's scatter (duplicate keys serialise on TPU) — and the MXU
duplicate-fold (one-hot segment-sum matmul + one dense add). Which wins
depends on (capacity,
value width, dtype, key count, device) in ways a static heuristic gets
wrong — the round-2 on-chip capture measured scatter 1.3x FASTER at the
very shape the old ``capacity // 256`` gate routed to the MXU. So the
gate is now a one-time MEASUREMENT per shape signature: both routes run
on the table's actual mesh with representative operands, the faster one
is cached process-wide, and the chosen route is never the one the
measurement says is slower. ``HARMONY_PUSH_VIA`` still force-overrides
upstream (DenseTable.push_via) as the operator rollback.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_LOCK = threading.Lock()
_ROUTES: Dict[Tuple, str] = {}
_MEASUREMENTS: Dict[Tuple, Dict[str, float]] = {}  # observability/tests


def _signature(spec, mesh, nkeys: int) -> Tuple:
    devs = list(mesh.devices.flat)
    return (
        spec.config.capacity,
        spec.block_size,
        tuple(spec.value_shape),
        str(spec.dtype),
        int(nkeys),
        len(devs),
        devs[0].platform,
        tuple(mesh.shape.items()),
    )


def _measure(fn, args, mesh) -> float:
    """min-of-3 after a compile dispatch, each dispatch inside the global
    order scope and timed to ``block_until_ready``."""
    from harmony_tpu.parallel.dispatch import dispatch_scope

    def once() -> float:
        t0 = time.perf_counter()
        with dispatch_scope(mesh) as fin:
            out = fin(fn(*args))
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    once()  # compile
    return min(once() for _ in range(3))


def reset() -> None:
    with _LOCK:
        _ROUTES.clear()
        _MEASUREMENTS.clear()


def measurements() -> Dict[Tuple, Dict[str, float]]:
    with _LOCK:
        return dict(_MEASUREMENTS)


def choose_push_route(spec, mesh, nkeys: int, table=None) -> str:
    """The measured-faster keyed-push route for this shape on this mesh
    ("scatter" | "mxu"), cached per signature for the process lifetime.

    Non-additive update fns are always "scatter" (the fold needs
    commutative adds). When ``table`` (a DenseTable living on ``mesh``)
    is given, measurement runs NON-DONATING against its live array —
    no second table-sized allocation; without it a zero array is
    device-allocated. A measurement that raises — a route that does not
    compile on this mesh included — propagates into the step build:
    there is no unmeasured route to hide it behind.
    """
    if spec.update_fn.scatter_mode != "add":
        return "scatter"
    sig = _signature(spec, mesh, nkeys)
    with _LOCK:
        hit = _ROUTES.get(sig)
    if hit is not None:
        return hit
    from harmony_tpu.utils.platform import traced_on

    if table is not None:
        with table._lock:
            arr = table._arr
    else:
        from harmony_tpu.table.table import block_sharding

        sharding = block_sharding(mesh, spec.num_blocks)
        # lint: allow(jit-hygiene) one-shot push-route measurement at
        # job-build time (never per batch) — a cached wrapper would
        # only pin a program nothing ever reuses
        arr = jax.jit(
            lambda: jnp.zeros(spec.storage_shape, spec.dtype),
            out_shardings=sharding,
        )()
    rng = np.random.default_rng(0)
    keys = jnp.asarray(
        rng.integers(0, spec.config.capacity, int(nkeys)), jnp.int32
    )
    deltas = jnp.zeros((int(nkeys), *spec.value_shape), spec.dtype)

    def route_fn(via):
        # deltas depend on the array so neither XLA nor a cached
        # constant can fold the push away; non-donating (the live
        # table array must survive). Traced for the table's mesh, so
        # each route is measured as the lowering the step will bake.
        return jax.jit(traced_on(
            mesh,
            lambda a, k, d: spec.push(
                a, k, d + 0.0 * jnp.ravel(a)[0], via=via
            ),
        ))

    t_scatter = _measure(route_fn("scatter"), (arr, keys, deltas), mesh)
    t_mxu = _measure(route_fn("mxu"), (arr, keys, deltas), mesh)
    route = "mxu" if t_mxu < t_scatter else "scatter"
    meas = {"scatter_sec": t_scatter, "mxu_sec": t_mxu}
    with _LOCK:
        _ROUTES[sig] = route
        _MEASUREMENTS[sig] = meas
        while len(_ROUTES) > 1024:
            _ROUTES.pop(next(iter(_ROUTES)))
        while len(_MEASUREMENTS) > 1024:
            _MEASUREMENTS.pop(next(iter(_MEASUREMENTS)))
    return route
