"""Process-level cache of compiled training-step programs.

A long-running JobServer repeatedly runs structurally identical jobs (the
reference's standing use case: resubmitting the same Dolphin app to the same
resource pool, DolphinJobLauncher -> JobServerDriver SUBMIT). Every submit
builds a fresh ``WorkerTasklet``, whose ``jax.jit(step)`` closure is a new
Python object — so the in-memory executable from the previous run is
unreachable and the step is traced and compiled again, which for a short
job can cost more than the training.

This cache keys the jitted callable on a STRUCTURAL signature of everything
the trace depends on — trainer behavior (Trainer.jit_signature), table
schema, current sharding/mesh layout, batch shapes, hyper-parameter keys,
dispatch shape (per-batch vs fused-epoch) — and returns the same callable
for equal keys, so resubmitted jobs reuse the compiled executable.

Opt-out is the default at the trainer level: ``Trainer.jit_signature``
returns None unless every instance attribute is a plain scalar (see its
docstring for the contract), and tables with caller-supplied update
functions never cache (no stable identity for arbitrary callables).

The cached callable closes over the FIRST job's trainer/spec instances;
the signature contract is exactly the guarantee that any other job with
the same key would have traced the identical program. Entries are LRU,
bounded — compiled TPU executables hold device memory for constants, so
the bound is deliberately small.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.monitoring
from jax.sharding import Mesh

_MAX_ENTRIES = 32
_lock = threading.Lock()
_cache: "OrderedDict[Hashable, Callable]" = OrderedDict()
_stats = {"hits": 0, "misses": 0}


def mesh_signature(mesh: Mesh) -> Tuple:
    """Value identity of a mesh: axis layout + the concrete device list.
    Two Mesh objects over the same devices in the same arrangement produce
    interchangeable programs (jax compares meshes by value the same way)."""
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple((d.platform, d.process_index, d.id) for d in mesh.devices.flat),
    )


def sharding_signature(sharding) -> Tuple:
    """Hash tables expose a (keys, vals) sharding tuple; recurse."""
    if isinstance(sharding, tuple):
        return tuple(sharding_signature(s) for s in sharding)
    return (mesh_signature(sharding.mesh), str(sharding.spec))


def table_signature(table: Any, sharding=None) -> Optional[Tuple]:
    """Structural identity of a table's traced ops, or None when the spec
    carries behavior the config string cannot name (custom update fn).

    ``sharding`` lets the caller pass a SNAPSHOT of the table's layout: a
    live reshard can land between reading the layout for the key and
    reading it again for jit out_shardings, and a key/executable layout
    mismatch poisons the cache — callers that also compile must read the
    sharding once and pass it here."""
    spec = table.spec
    if getattr(spec, "custom_update_fn", True):
        return None
    cfg = spec.config
    return (
        type(table).__name__,
        cfg.capacity,
        tuple(cfg.value_shape),
        cfg.dtype,
        spec.num_blocks,
        cfg.is_ordered,
        cfg.is_mutable,
        cfg.sparse,
        cfg.update_fn,
        getattr(spec, "max_probes", None),  # hash tables: probing depth is
                                            # constructor state, not config
        sharding_signature(table.sharding if sharding is None else sharding),
    )


_inflight: dict = {}


# -- compile telemetry ------------------------------------------------------
#
# Every cached-eligible build is wrapped in an _InstrumentedProgram: the
# FIRST call AOT-lowers and compiles (jit's own laziness would hide the
# compile inside an arbitrary later dispatch), the wall time of that
# compile is observed into harmony_compile_seconds{program}, and the
# executable's XLA cost_analysis()/memory_analysis() land in a bounded
# per-program cost table keyed by the structural program key — the
# FLOP/byte denominators the tenant ledger (metrics/accounting.py) turns
# into per-job MFU. Backends that expose neither analysis (or reject AOT
# entirely) walk the SAME code path and record explicit Nones: the CPU
# tier-1 run and a TPU pod differ only in which fields are filled.

_COST_MAX_ENTRIES = 128
_costs: "OrderedDict[Hashable, ProgramCost]" = OrderedDict()


@dataclass
class ProgramCost:
    """One compiled program's measured build cost. ``flops`` is the XLA
    cost-analysis model count for ONE invocation of the program (a fused
    epoch program's figure covers every step it scans over — callers
    divide by their step count); None = the backend exposed no analysis,
    which consumers must keep distinct from a measured 0.0."""

    tag: str                       # "step" / "epoch" / "table_init" / ...
    compile_seconds: float
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    created_at: float = field(default_factory=time.time)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "tag": self.tag,
            "compile_seconds": round(self.compile_seconds, 6),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
        }


def _key_tag(key: Hashable) -> str:
    """Human tag of a structural key: the step-kind string the call sites
    append — ("...", "step") / ("...", "epoch") / (sig, "table_init") /
    (tsig, "fused_sparse", ...). Bounded vocabulary by construction, so
    it is safe as a metric label."""
    if isinstance(key, tuple) and len(key) >= 2 and isinstance(key[1], str):
        return key[1]
    return "program"


def _extract_cost(tag: str, seconds: float, compiled) -> "ProgramCost":
    """Pull flops/bytes out of a jax.stages.Compiled, tolerating every
    backend shape: list-of-dicts, dict, None, or a raising method."""
    cost = ProgramCost(tag=tag, compile_seconds=seconds)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict) and ca:
            flops = ca.get("flops")
            cost.flops = float(flops) if flops is not None else None
            ba = ca.get("bytes accessed")
            cost.bytes_accessed = float(ba) if ba is not None else None
    except Exception:
        pass  # no cost model on this backend: explicit Nones
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            cost.argument_bytes = int(
                getattr(ma, "argument_size_in_bytes", 0))
            cost.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
            cost.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
            cost.generated_code_bytes = int(
                getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception:
        pass
    return cost


def _record_cost(key: Hashable, cost: "ProgramCost") -> None:
    with _lock:
        _costs[key] = cost
        _costs.move_to_end(key)
        while len(_costs) > _COST_MAX_ENTRIES:
            _costs.popitem(last=False)
    try:  # scrapeable compile wall time; the registry must never fail a build
        from harmony_tpu.metrics.registry import get_registry

        get_registry().histogram(
            "harmony_compile_seconds",
            "Wall seconds to build one cached program (trace + XLA compile)",
            ("program",),
        ).labels(program=cost.tag).observe(cost.compile_seconds)
    except Exception:
        pass


def program_cost(key: Hashable) -> Optional["ProgramCost"]:
    """The recorded build cost of ``key``'s program, or None when it has
    not compiled (or was evicted). Read-only; the ledger's FLOP source."""
    with _lock:
        return _costs.get(key)


def program_costs() -> List[Dict[str, Any]]:
    """Cost-table snapshot (newest last) for STATUS / obs tooling. Keys
    are structural tuples, unreadable raw — rows carry the tag + a short
    stable digest so operators can join rows across scrapes."""
    with _lock:
        items = list(_costs.items())
    out = []
    for key, cost in items:
        row = cost.to_dict()
        row["key_digest"] = f"{abs(hash(key)) & 0xFFFFFFFF:08x}"
        out.append(row)
    return out


class _InstrumentedProgram:
    """Callable wrapper adding compile telemetry to one cached program.

    First call: AOT ``lower(*args).compile()`` — the compile wall time is
    measured EXPLICITLY instead of hiding inside jit's lazy first
    dispatch — then the call executes through the compiled object.
    Steady state: calls dispatch straight through the compiled
    executable — no per-call argument inspection; a Python-level guard
    measured ~22us/call, swamping the ~2us the executable's dispatch
    costs over jit's C++ fast path, in the per-batch hot loop this
    wrapper sits on. The executable itself validates shapes/dtypes/
    PLACEMENTS at dispatch time, BEFORE executing (and therefore before
    donating), raising TypeError/ValueError; catching exactly those
    flips the wrapper PERMANENTLY onto the plain jit path, which
    recompiles per new signature — the uninstrumented behavior. (Args
    that are genuinely broken — e.g. an already-donated buffer — fail
    the jit path with the same error, so error parity holds.) Builders
    that return a non-stage callable (no ``.lower``) or a backend that
    rejects AOT get first-call wall-time-only telemetry the same way.

    The wrapper object itself is what the cache stores, so the identity
    contract (equal keys -> the same callable) is preserved."""

    __slots__ = ("_key", "_tag", "_fn", "_compiled", "_lock",
                 "_fallback", "_time_plain")

    def __init__(self, key: Hashable, fn: Callable) -> None:
        self._key = key
        self._tag = _key_tag(key)
        self._fn = fn
        self._compiled = None
        self._lock = threading.Lock()
        self._fallback = False   # True = permanently on the plain jit path
        self._time_plain = False  # one timed jit first-dispatch still owed

    def _instrument_first_call(self, args, kwargs) -> None:
        """One thread AOT-compiles and records; concurrent callers wait
        (same once-per-program semantics jit's own cache gives). A
        builder without ``.lower`` (plain callable) or a backend that
        rejects AOT degrades to timing the first jit dispatch —
        trace+compile+run, the best available compile-time proxy — with
        analyses left as explicit Nones."""
        with self._lock:
            if self._compiled is not None or self._fallback:
                return
            lower = getattr(self._fn, "lower", None)
            if lower is not None:
                try:
                    t0 = time.perf_counter()
                    compiled = lower(*args, **kwargs).compile()
                    seconds = time.perf_counter() - t0
                    _record_cost(self._key,
                                 _extract_cost(self._tag, seconds, compiled))
                    self._compiled = compiled
                    return
                except Exception:
                    pass
            self._fallback = True
            self._time_plain = True

    def __call__(self, *args, **kwargs):
        if not self._fallback:
            if self._compiled is None:
                self._instrument_first_call(args, kwargs)
            if self._compiled is not None:
                try:
                    return self._compiled(*args, **kwargs)
                except (TypeError, ValueError):
                    # dispatch-time validation (raised BEFORE execution,
                    # so nothing was donated): shapes/dtypes/placements
                    # the lowering did not see. Should not happen — the
                    # structural key pins them — but a caller-supplied
                    # signature could lie: permanent fallback to the jit
                    # path, which recompiles per signature exactly as
                    # the uninstrumented wrapper would (and re-raises
                    # identically if the args are genuinely broken)
                    self._fallback = True
        if self._time_plain:
            self._time_plain = False
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            _record_cost(self._key, ProgramCost(
                tag=self._tag, compile_seconds=time.perf_counter() - t0))
            return out
        return self._fn(*args, **kwargs)


def _record_event(result: str) -> None:
    """Scrapeable hit/miss counter beside the in-process _stats dict
    (metrics/registry.py): recompiles of cached-eligible programs —
    WorkerTasklet step rebuilds, FusedSparseStep builds, table inits —
    become visible in /metrics as harmony_progcache_events_total. Guarded: the
    cache must never fail (or slow) a build on registry trouble."""
    try:
        from harmony_tpu.metrics.registry import get_registry

        get_registry().counter(
            "harmony_progcache_events_total",
            "Compiled-program cache lookups by result",
            ("result",),
        ).labels(result=result).inc()
    except Exception:
        pass


def get_or_build(key: Optional[Hashable], build: Callable[[], Callable]) -> Callable:
    """Return the cached callable for ``key``, building (and caching) on
    miss. ``key=None`` bypasses the cache entirely.

    Concurrent misses on one key are deduplicated: the first caller builds,
    the rest wait on its completion — a multi-worker job's N simultaneous
    ``_build_step`` calls must compile once, not N times."""
    if key is None:
        return build()
    while True:
        with _lock:
            fn = _cache.get(key)
            if fn is not None:
                _cache.move_to_end(key)
                _stats["hits"] += 1
            else:
                ev = _inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    _inflight[key] = ev
                    break  # this thread builds
        if fn is not None:
            _record_event("hit")  # outside the lock (registry has its own)
            return fn
        ev.wait()
        # builder finished (or failed): loop re-checks the cache; on builder
        # failure the entry is absent and THIS thread takes over the build.
    try:
        # Build OUTSIDE the lock: tracing can be slow and may itself dispatch.
        # Cached-eligible programs are wrapped for compile telemetry: the
        # wrapper IS the cached object, so the identity contract (equal
        # keys -> the same callable) and every existing call shape hold.
        fn = _InstrumentedProgram(key, build())
        with _lock:
            _stats["misses"] += 1
            _cache[key] = fn
            _cache.move_to_end(key)
            while len(_cache) > _MAX_ENTRIES:
                _cache.popitem(last=False)
        _record_event("miss")
        return fn
    finally:
        with _lock:
            _inflight.pop(key, None)
        ev.set()


def drop(predicate) -> int:
    """Forget every entry whose key matches; returns the count. Used by the
    reshard path: executables whose out_shardings bind released devices can
    never hit again under their old key, and each holds device memory for
    its constants. Dropping is always SAFE — workers keep direct references
    to callables in use, so a drop only affects future lookups."""
    with _lock:
        stale = [k for k in _cache if predicate(k)]
        for k in stale:
            del _cache[k]
        # matching cost rows go with their executables: program_costs()
        # must not keep reporting programs the reshard path discarded
        for k in [k for k in _costs if predicate(k)]:
            del _costs[k]
        return len(stale)


def stats() -> dict:
    with _lock:
        return dict(_stats, entries=len(_cache))


# -- JAX's own compile durations, by job -------------------------------------
#
# A trainer whose ``jit_signature()`` is None (the LM's) bypasses the cache
# above, so nothing there sees its step compile. JAX reports every trace,
# lowering and backend compile (or persistent-cache load) through
# ``jax.monitoring`` on the compiling thread; the job is the one of the span
# open there (tracing/span.py ``current_job``).

_COMPILE_EVENTS = "/jax/core/compile/"
_BACKEND_COMPILE = "backend_compile_duration"


def _compile_families():
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.counter(
                "harmony_compile_seconds_total",
                "Seconds of JAX trace / lowering / backend compile by job",
                ("job", "stage")),
            reg.counter(
                "harmony_compiles_total",
                "Backend compiles (or persistent-cache loads) by job",
                ("job",)))


def _on_jax_duration(name: str, seconds: float, **_: Any) -> None:
    if not name.startswith(_COMPILE_EVENTS):
        return
    from harmony_tpu.tracing.span import current_job

    job = current_job() or "-"
    stage = name[len(_COMPILE_EVENTS):]
    try:  # the registry must never fail a compile
        seconds_by, compiles_by = _compile_families()
        seconds_by.labels(job=job, stage=stage).inc(float(seconds))
        if stage == _BACKEND_COMPILE:
            compiles_by.labels(job=job).inc()
    except Exception:
        pass


def compiles_by_job() -> Dict[str, Dict[str, float]]:
    """``{job: {compiles, seconds}}`` of every compile JAX made in this
    process under a span of that job (``-``: under none), read back from
    the two counters — STATUS ``compiles``."""
    out: Dict[str, Dict[str, float]] = {}
    try:
        seconds_by, compiles_by = _compile_families()
        for (job, _stage), child in seconds_by.children():
            row = out.setdefault(job, {"compiles": 0, "seconds": 0.0})
            row["seconds"] = round(row["seconds"] + child.value, 6)
        for (job,), child in compiles_by.children():
            out.setdefault(job, {"compiles": 0, "seconds": 0.0})[
                "compiles"] = int(child.value)
    except Exception:
        return {}
    return out


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# -- the tiles a traced kernel chose, by job ----------------------------------
#
# The flash kernels tile themselves from the shape (ops/attention.py
# ``tile_plan``): static per compiled program, so the record is made once, at
# trace time, under the job whose span is open on the tracing thread.

def _kernel_plan_family():
    from harmony_tpu.metrics.registry import get_registry

    return get_registry().gauge(
        "harmony_kernel_grid_steps",
        "Grid steps a call of a Pallas kernel under the tiles it was traced "
        "with, by job (planned=0: the caller's explicit blocks)",
        ("job", "kernel", "block_q", "block_k", "sub", "planned", "d", "dv"))


#: (the plan gauge's label values) -> columns of the STATUS row that are no
#: labels of the gauge: what a flash call with grouped heads or a window
#: needs of its plan (ops/attention.py ``_note_plan``: ``band``) and what
#: another kernel adds to its row (``extra``)
_plan_columns: Dict[tuple, Dict[str, Any]] = {}


def _masked_share_families():
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.gauge(
        "harmony_flash_masked_share",
        "Share of the score elements a traced flash kernel computes that its "
        "mask discards (static: from the tiles and the band), by job",
        ("job", "kernel")),
            reg.gauge(
        "harmony_flash_score_elements",
        "Score elements one call of a traced flash kernel computes under "
        "its tiles, masked ones included, by job", ("job", "kernel")))


def note_kernel_plan(kernel: str, block_q: int, block_k: int, sub: int,
                     grid_steps: int, planned: bool, *, d: int,
                     dv: int, band: Optional[Dict[str, Any]] = None,
                     extra: Optional[Dict[str, Any]] = None) -> None:
    """``d`` / ``dv``: the two widths the tiles were planned for — a flash
    kernel's q.k and v head widths, a grouped matmul's k and n. ``band``:
    what a flash call with grouped heads or a window adds to its row
    (``window``, ``kv_heads``, ``group`` — the query heads a K/V head
    serves —, ``band_grid_steps``, ``sub_blocks``,
    ``masked_sub_blocks``, ``computed``, ``masked_share``). ``extra``:
    further columns of the row, as they come (the rotary kernel's
    ``normed``: does the call norm each head before it turns it)."""
    from harmony_tpu.tracing.span import current_job

    job = current_job() or "-"
    labels = dict(
        job=job, kernel=kernel, block_q=str(block_q),
        block_k=str(block_k), sub=str(sub), planned=str(int(planned)),
        d=str(d), dv=str(dv))
    _kernel_plan_family().labels(**labels).set(grid_steps)
    if band is not None or extra is not None:
        _plan_columns[tuple(labels.values())] = {**(band or {}),
                                                 **(extra or {})}
    if band is not None:
        share, elements = _masked_share_families()
        share.labels(job=job, kernel=kernel).set(band["masked_share"])
        elements.labels(job=job, kernel=kernel).set(band["computed"])


def kernel_plans() -> Dict[str, list]:
    """``{job: [{kernel, block_q, block_k, sub, planned, d, dv,
    grid_steps}]}`` of
    every kernel traced in this process — STATUS ``kernel_plans``. A flash
    kernel traced with grouped heads or a window carries ``band``'s columns
    too, any kernel its ``extra`` ones (``note_kernel_plan``)."""
    out: Dict[str, list] = {}
    try:
        for key, child in _kernel_plan_family().children():
            job, kernel, bq, bk, sub, planned, d, dv = key
            out.setdefault(job, []).append({
                "kernel": kernel, "block_q": int(bq), "block_k": int(bk),
                "sub": int(sub), "planned": planned == "1",
                "d": int(d), "dv": int(dv),
                "grid_steps": int(child.value),
                **_plan_columns.get(tuple(key), {})})
    except Exception:
        return {}
    return out


# -- what a rematerialised block keeps, by job --------------------------------
#
# ``TransformerConfig.remat`` checkpoints each block under ONE policy that
# keeps the kernels' named residuals (ops/residuals.py); like a kernel plan
# the record is static per traced program and made at trace time.

def _remat_saved_families():
    from harmony_tpu.metrics.registry import get_registry

    reg = get_registry()
    return (reg.gauge(
        "harmony_remat_saved_arrays",
        "Arrays a step keeps across its rematerialised blocks under a "
        "residual's name instead of computing them again, by job",
        ("job", "name")),
            reg.gauge(
        "harmony_remat_saved_bytes",
        "Bytes of the arrays a step keeps across its rematerialised blocks "
        "under a residual's name, by job", ("job", "name")))


def note_remat_saved(kept: Dict[str, Any]) -> None:
    """``kept``: ``{name: (arrays, bytes)}`` one traced forward keeps under
    its ``remat`` policy (models/transformer.py ``_remat``), over all its
    layers. Never fails a trace."""
    try:
        from harmony_tpu.tracing.span import current_job

        job = current_job() or "-"
        arrays_by, bytes_by = _remat_saved_families()
        for name, (arrays, nbytes) in kept.items():
            arrays_by.labels(job=job, name=name).set(arrays)
            bytes_by.labels(job=job, name=name).set(nbytes)
    except Exception:
        pass


def remat_saved() -> Dict[str, list]:
    """``{job: [{name, arrays, bytes}]}`` of every rematerialised step traced
    in this process — STATUS ``remat_saved``; a job whose model has no
    ``remat`` is absent."""
    out: Dict[str, list] = {}
    try:
        arrays_by, bytes_by = _remat_saved_families()
        nbytes = {key: int(child.value) for key, child in bytes_by.children()}
        for (job, name), child in arrays_by.children():
            out.setdefault(job, []).append({
                "name": name, "arrays": int(child.value),
                "bytes": nbytes.get((job, name), 0)})
    except Exception:
        return {}
    return out


def clear() -> None:
    with _lock:
        _cache.clear()
        _costs.clear()
        _stats.update(hits=0, misses=0)
