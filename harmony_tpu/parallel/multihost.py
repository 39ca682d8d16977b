"""Multi-host wiring — the DCN side of the communication backend.

SURVEY.md §5.8 prescribes the split this framework implements: the DATA
plane is XLA collectives over ICI inside jitted steps (no counterpart of
the reference's per-key Netty RPCs needed), and the reference's
NameServer-based process bootstrap maps to JAX's distributed runtime:
``jax.distributed.initialize`` connects every host process to a
coordinator over DCN, after which ``jax.devices()`` is the GLOBAL device
list and a mesh built over it spans the pod — the same program text runs
single-host (this repo's tests, one chip or 8 virtual CPUs) and
multi-host (a pod slice) unchanged.

Single-host safe: every function degrades to a no-op/local equivalent, so
the framework never needs an "am I distributed?" fork in app code.
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
import numpy as np

from harmony_tpu.parallel.mesh import build_mesh

_initialized = False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the multi-host job (ref analogue: REEF NameServer registration,
    JobServerClient binding NameServerConfiguration — SURVEY.md §2.10).

    Arguments default from the standard env vars (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID). Returns True if a multi-process
    runtime was (or already is) initialized, False for the single-process
    no-op path.
    """
    global _initialized
    if _initialized:
        return True
    # IMPORTANT: decide from config BEFORE touching any jax API that could
    # initialize the XLA backend (jax.process_count() does) —
    # jax.distributed.initialize refuses to run after backend init.
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    num_processes = num_processes or int(os.environ.get("JAX_NUM_PROCESSES", 0))
    if not coordinator_address and num_processes <= 1:
        # No multi-host config of our own; report whether an external
        # launcher already initialized a multi-process runtime (safe to
        # query the backend here — we will not initialize).
        multi = jax.process_count() > 1
        _initialized = multi
        return multi
    # Half-configured launches must fail loudly: proceeding single-host
    # while peers block in jax.distributed.initialize is a silent hang plus
    # wrong-topology training. That includes a missing process id — every
    # host defaulting to id 0 conflicts at the coordinator.
    if not coordinator_address or num_processes <= 1:
        raise ValueError(
            "incomplete multi-host config: need BOTH a coordinator address "
            f"and num_processes > 1 (got coordinator={coordinator_address!r}, "
            f"num_processes={num_processes})"
        )
    pid_env = os.environ.get("JAX_PROCESS_ID")
    if process_id is None and pid_env is None:
        raise ValueError(
            "incomplete multi-host config: JAX_PROCESS_ID (or process_id=) "
            "is required when a coordinator is configured"
        )
    process_id = process_id if process_id is not None else int(pid_env)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError:
        # jax 0.9 raises 'distributed.initialize should only be called once.'
        # or 'must be called before any JAX computations...' — message text
        # is unstable across versions, so decide from the OUTCOME: if a
        # multi-process runtime is in fact up, an external launcher beat us
        # to it and the documented contract is satisfied; otherwise the
        # failure is real (e.g. backend initialized too early single-host).
        if jax.process_count() > 1:
            _initialized = True
            return True
        raise
    _initialized = True
    return True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_multihost() -> bool:
    return jax.process_count() > 1


def global_devices() -> List[jax.Device]:
    """All devices across all hosts (== jax.devices(); addressable subset
    is jax.local_devices())."""
    return list(jax.devices())


def global_mesh(data=None, model=None, seq=None):
    """Mesh over the GLOBAL device list. On a pod slice JAX orders devices
    so that adjacent ids share ICI links; the (data, [seq,] model) reshape
    keeps each model/seq group intra-host where possible."""
    return build_mesh(global_devices(), data=data, model=model, seq=seq)


_MESH_SUM_CACHE: dict = {}


def mesh_sum(mesh, value: float, tag: str = "") -> float:
    """Sum a per-PROCESS scalar over ONLY the processes holding devices of
    ``mesh`` (each process contributes its value once, via its first
    addressable mesh device; the rest contribute zero). Doubles as the
    mesh-scoped barrier: the psum completes only when every participating
    process has dispatched it — unlike sync_global_devices this is safe
    for a CARVED mesh (the global barrier would wait on processes that
    never call it). ``tag`` is documentation/trace only: collectives match
    by the deterministic call sequence, not by name.

    Single-process meshes return ``value`` immediately."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harmony_tpu.parallel.mesh import mesh_spans_processes

    if not mesh_spans_processes(mesh):
        return value
    axes = tuple(mesh.axis_names)
    fn = _MESH_SUM_CACHE.get(mesh)
    if fn is None:
        fn = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum(v, axes), mesh=mesh,
            in_specs=P(axes), out_specs=P(),
        ))
        _MESH_SUM_CACHE[mesh] = fn
        while len(_MESH_SUM_CACHE) > 64:  # long-lived servers, many meshes
            _MESH_SUM_CACHE.pop(next(iter(_MESH_SUM_CACHE)))
    sharding = NamedSharding(mesh, P(axes))
    imap = sharding.addressable_devices_indices_map((mesh.devices.size,))
    shards = []
    first = True
    for d, idx in sorted(imap.items(), key=lambda kv: kv[1][0].start or 0):
        v = float(value) if first else 0.0
        first = False
        shards.append(jax.device_put(np.asarray([v], np.float32), d))
    arr = jax.make_array_from_single_device_arrays(
        (mesh.devices.size,), sharding, shards
    )
    return float(np.asarray(fn(arr)))  # replicated out: addressable D2H


def mesh_barrier(mesh, tag: str = "barrier") -> None:
    """Mesh-scoped barrier (see mesh_sum)."""
    mesh_sum(mesh, 0.0, tag)


def sync_global_devices(tag: str = "barrier") -> None:
    """Cross-host barrier: a tiny psum over every device; returns when all
    processes reached it (the analogue of the reference's driver-mediated
    sync acks). Single-host it is a trivially fast all-device reduction."""
    from jax.experimental import multihost_utils

    if is_multihost():
        multihost_utils.sync_global_devices(tag)
    else:
        # Single process: dispatch + block on a trivial all-device op so the
        # call still orders against in-flight work on every local device.
        x = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
            np.ones((len(jax.local_devices()),), np.float32)
        )
        jax.block_until_ready(x)
