#!/usr/bin/env python
"""One run of one benchmark cell.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``perf/configs/<config>.json``) under a traffic mix
(``perf/traffic/<traffic>.json``). This file has no branch per cell, model
or metric: tenants, trainer, ``app_params``, data, batch, epoch size and
the work model all come from those two files; a per-layer metric is a
reader under ``perf/layer_metrics/`` found by its name. The work model is
the function the configuration names under ``job.flops_fn`` / ``job.bytes_fn``:
a bare name lives in ``perf/work_models.py``, ``"<sibling>:<function>"`` in
``perf/work/<sibling>.py`` beside the configuration's other work functions
(``work_models.resolve``; that file's docstring says what such a count owes).
PERF.md sections 2-4 say what is measured and why.

One process: it starts the jobserver here (the one process that holds the
chips) and drives it only as a user can — ``JobConfig``s sent with the
jax-free ``CommandSender`` over the TCP endpoint (SUBMIT / STATUS / WAIT /
SHUTDOWN). The order of a run:

  1. a short WARM-UP job per tenant, with the measured job's own shapes,
     submitted together — compiles or loads exactly the programs the window
     will use, and gives a rate;
  2. the CHECK: the warm-up's first per-epoch losses against the plain
     reference under ``perf/reference/`` replaying the same steps;
  3. the MEASURED job per tenant, sized from the warm-up's rate to outlast
     the window; the window opens at the first change of its counters and
     lasts ``--seconds``. Rates are counted from the client's side
     (perf/rates.py). With ``--trace 1`` about three seconds in the middle
     are captured with ``jax.profiler`` and reduced (perf/trace_reduce.py);
  4. WAIT, SHUTDOWN, the last line.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result. ``--rehearse`` runs the configuration's tiny
preset on the CPU for control flow; its last line carries no metric.
``--debug-dir <dir>`` (a builder's aid) keeps every poll of the measured job
and dumps all threads' stacks whenever a feed is late (PERF.md section 7).
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf import rates  # noqa: E402

#: STATUS walks the ledger under the process's GIL, beside the dispatching
#: thread: no more than 5 Hz
POLL_PERIOD_S = 0.2
#: ... except just where a watched job's next feed is due, from this long
#: before it (and twice the last stamp's own width: perf/rates.py ``feeds``)
#: until it is seen — the worker is waiting for the device then —, or until
#: it is this late: a stall, and the grid again. Three to five more polls a
#: feed, and a feed's time known to 25 ms where the grid knew it to 200
FINE_PERIOD_S = 0.025
FINE_LEAD_S = 0.03
FINE_GIVE_UP_S = 0.5
#: the worker drains (and feeds the ledger) once per window of up to 8
#: epochs (dolphin/worker.py EPOCH_WINDOW); jobs are sized in whole windows
#: so that every drain stacks the same number of steps — the shape the
#: warm-up compiled
EPOCH_WINDOW = 8
WARMUP_EPOCHS = 2 * EPOCH_WINDOW
#: the measured job lasts about this many windows' worth of --seconds
OUTLAST = 1.15
TRACE_SECONDS = 3.0
JOB_TIMEOUT_S = 900.0
#: deployment settings of the program the harness sets in its own
#: environment: ledger and phase windows long enough that STATUS's phase
#: seconds are cumulative over a run (the harness takes differences)
PROGRAM_ENV = {"HARMONY_LEDGER_WINDOW": "100000",
               "HARMONY_PHASE_WINDOW": "100000"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(kind: str, **fields: Any) -> None:
    """One JSON line of stdout (never the last: that is the result)."""
    print(json.dumps({"line": kind, **fields}), flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_by_path(kind: str, name: str):
    """``perf/<kind>/<name>.py`` as a module (names carry ``-`` and ``.``,
    so they are loaded by path, not imported by name)."""
    path = os.path.join(PERF, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# the cell: BENCHMARK.json + one configuration file + one traffic file
# ---------------------------------------------------------------------------

class Cell:
    def __init__(self, name: str, rehearse: bool) -> None:
        self.bench = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = int(entry["chips"])
        conf = next(c for c in self.bench["configs"]
                    if c["name"] == entry["config"])
        self.config = load_json(ROOT, conf["file"])
        self.traffic = load_json(PERF, "traffic", entry["traffic"] + ".json")
        # a mix may set fields of the job (what a deployment would set
        # differently when tenants share), each with its reason in the file
        self.job = {**self.config["job"], **self.traffic.get("job", {})}
        if rehearse:
            tiny = self.config["rehearse"]
            self.job["app_params"] = {**self.job["app_params"],
                                      **tiny["app_params"]}
            self.job["data_args"] = {**self.job["data_args"],
                                     **tiny["data_args"]}
            self.job["batch"] = tiny["batch"]
        self.tenants = self.traffic["tenants"]
        self.batch = max(1, int(round(self.job["batch"]
                                      * self.traffic["batch_share"])))
        self.nb = int(self.job["num_mini_batches"])

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def tenant_seed(self, seed: int, tenant: Dict[str, Any]) -> int:
        return seed * 16 + int(tenant["seed_offset"])

    def app_params(self, seed: int, tenant: Dict[str, Any]) -> Dict[str, Any]:
        app = dict(self.job["app_params"])
        for key, scale in tenant["param_scale"].items():
            app[key] = app[key] * scale
        if self.job.get("seed_param"):
            app[self.job["seed_param"]] = self.tenant_seed(seed, tenant)
        return app

    def data_args(self, seed: int, tenant: Dict[str, Any]) -> Dict[str, Any]:
        return {**self.job["data_args"],
                self.job["data_count_arg"]: self.batch * self.nb,
                "seed": self.tenant_seed(seed, tenant)}

    def job_config(self, seed: int, tenant: Dict[str, Any], tag: str,
                   num_epochs: int):
        from harmony_tpu.config.params import JobConfig, TrainerParams

        return JobConfig(
            job_id=f"{self.name}-{tag}-{tenant['name']}",
            app_type="dolphin", trainer=self.job["trainer"],
            # every other TrainerParams field keeps the program's default
            params=TrainerParams(
                num_epochs=num_epochs, num_mini_batches=self.nb,
                comm_probe_period=int(self.job["comm_probe_period"]),
                app_params=self.app_params(seed, tenant)),
            num_workers=int(self.job["num_workers"]),
            user={"data_fn": self.job["data_fn"],
                  "data_args": self.data_args(seed, tenant)},
        )


# ---------------------------------------------------------------------------
# observing the program from the client's side
# ---------------------------------------------------------------------------

class Poller:
    """STATUS at ``POLL_PERIOD_S``, and at ``FINE_PERIOD_S`` where a feed of
    a job in ``watched`` is due: per job the cumulative counters and phase
    seconds, each poll stamped on the harness's monotonic clock."""

    def __init__(self, server) -> None:
        self.server = server
        self.client = server.client()
        #: job -> [(t, examples_total)]
        self.counters: Dict[str, List[Tuple[float, float]]] = {}
        #: job -> [(t, wall_sec, {phase: seconds})]
        self.phases: Dict[str, List[Tuple[float, float, Dict[str, float]]]] = {}
        #: job -> {epoch: {worker: wall seconds}}, as last reported
        self.epoch_walls: Dict[str, Dict[str, Dict[str, float]]] = {}
        self.status_seconds: List[float] = []
        #: the jobs whose feeds are met with fine polls
        self.watched: List[str] = []
        #: job -> its feeds so far, [(stamp, value, width)] (perf/rates.py)
        self.feeds: Dict[str, List[Tuple[float, float, float]]] = {}
        self._next = self._last = time.monotonic()

    def _wake(self) -> float:
        """When to ask next: at the grid's tick, or sooner where a watched
        job's feed is due. Its last feed happened no earlier than its stamp
        less half its width, and the period is no shorter than the least of
        the last three gaps less the two stamps' half widths."""
        wake, now = self._next, time.monotonic()
        for job in self.watched:
            last = self.feeds.get(job, [])[-4:]
            if len(last) < 2:
                continue
            gap = min(b[0] - a[0] for a, b in zip(last, last[1:]))
            due = last[-1][0] + gap
            # feeds as fast as the grid are many, and stay on it
            if gap < 2.0 * POLL_PERIOD_S or now > due + FINE_GIVE_UP_S:
                continue
            start = due - 2.0 * max(last[-1][2], last[-2][2]) - FINE_LEAD_S
            wake = min(wake, start)
        return max(wake, self._last + FINE_PERIOD_S)

    def poll(self) -> float:
        """Sleep to the next tick, poll once, return the poll's time."""
        delay = self._wake() - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t0 = self._last = time.monotonic()
        reply = self.server.status(self.client)
        t1 = time.monotonic()
        if t0 >= self._next:
            self._next += POLL_PERIOD_S
        self._next = max(self._next, t1)
        self.status_seconds.append(t1 - t0)
        t = 0.5 * (t0 + t1)
        for job, row in reply["tenants"].items():
            polls = self.counters.setdefault(job, [])
            polls.append((t, float(row["examples_total"])))
            self.feeds.setdefault(job, []).extend(rates.feeds(polls[-2:]))
        for job, row in reply["phase_budget"].items():
            self.phases.setdefault(job, []).append(
                (t, float(row["wall_sec"]), dict(row["phases"])))
            self.epoch_walls[job] = row["epoch_walls"]
        return t

    def changes(self, job: str) -> List[Tuple[float, float]]:
        """The job's change points so far (perf/rates.py)."""
        return rates.change_points(self.counters.get(job, []))

    def phase_delta(self, job: str, t0: float, t1: float
                    ) -> Optional[Tuple[float, Dict[str, float]]]:
        """``(wall seconds, {phase: seconds})`` the job's budget grew by
        between the polls nearest ``t0`` and ``t1``."""
        rows = [r for r in self.phases.get(job, []) if t0 <= r[0] <= t1]
        if len(rows) < 2:
            return None
        (_, w0, p0), (_, w1, p1) = rows[0], rows[-1]
        if w1 <= w0:
            return None
        return w1 - w0, {k: p1[k] - p0.get(k, 0.0) for k in p1}


class CompileLog:
    """Every compile JAX makes in this process, on the harness's clock
    (``jax.monitoring``). The program's own cache (runtime/progcache.py)
    sees only programs it keys — a trainer whose ``jit_signature()`` is
    None, the LM's, bypasses it — so the count that guards the window is
    taken here; the program's is printed beside it."""

    def __init__(self) -> None:
        import jax.monitoring

        self.events: List[Tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, seconds: float, **_: Any) -> None:
        if name.startswith("/jax/core/compile/"):
            self.events.append((time.monotonic(), name, float(seconds)))

    def seconds_before(self, t: float) -> float:
        return sum(s for at, _, s in self.events if at <= t)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, name, _ in self.events
                   if name == COMPILE_EVENT and t0 < at <= t1)


def run_jobs(server, poller: Poller, configs, *, until=None):
    """SUBMIT ``configs`` together, poll while they run, WAIT for each.
    ``until(t)`` is called after every poll. Returns ``(t_submit, {job id:
    WAIT result or the exception that ended it}, {job id: when its WAIT was
    seen to have returned})``."""
    with concurrent.futures.ThreadPoolExecutor(len(configs)) as pool:
        t_submit = time.monotonic()
        for c in configs:
            server.submit(c)
        waits = {c.job_id: pool.submit(server.client().wait_result,
                                       c.job_id, JOB_TIMEOUT_S)
                 for c in configs}
        done_at: Dict[str, float] = {}
        while len(done_at) < len(waits):
            t = poller.poll()
            for job, f in waits.items():
                if f.done():
                    done_at.setdefault(job, t)
            if until is not None:
                until(t)
        poller.poll()  # the counters' last feed
        results: Dict[str, Any] = {}
        for job, f in waits.items():
            try:
                results[job] = f.result()
            except Exception as e:  # a tenant that broke counts as failed
                results[job] = e
    return t_submit, results, done_at


def worker_result(result: Any) -> Optional[Dict[str, Any]]:
    if isinstance(result, Exception) or not result.get("workers"):
        return None
    return next(iter(result["workers"].values()))


def tenant_ok(result: Any) -> bool:
    """(a): stepped, stayed finite, ended better than it started (every
    configuration's progress figure so far is a loss; one whose figure
    rises would say so in its file)."""
    w = worker_result(result)
    if w is None:
        return False
    losses = [float(x) for x in w["losses"]]
    return (int(w["epochs_run"]) > 0 and len(losses) >= 2
            and all(math.isfinite(x) for x in losses)
            and losses[-1] < losses[0])


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def reference_check(cell: Cell, seed: int, warm: Dict[str, Any]
                    ) -> Tuple[bool, List[Dict[str, Any]]]:
    """(c): each tenant's first ``check_epochs`` per-epoch figures against
    the plain reference replaying the same steps. An epoch's figure is the
    loss of its LAST step (dolphin/worker.py ``_finish_epoch`` takes the
    final batch's metrics), computed on the parameters before that step's
    update. Tolerance: ``loss_rtol`` of the configuration file, whose
    reason sits beside it there."""
    from harmony_tpu.config.base import resolve_symbol

    ref = load_by_path("reference", cell.job["reference"])
    epochs = int(cell.job["check_epochs"])
    rtol = float(cell.job["loss_rtol"])
    ok, rows = True, []
    for tenant in cell.tenants:
        job = f"{cell.name}-warm-{tenant['name']}"
        w = worker_result(warm[job])
        if w is None:
            ok = False
            rows.append({"tenant": tenant["name"], "error": str(warm[job])[:300]})
            continue
        t_ref = time.monotonic()
        data = resolve_symbol(cell.job["data_fn"])(
            **cell.data_args(seed, tenant))
        data = data if isinstance(data, (tuple, list)) else (data,)
        steps = ref.replay(cell.app_params(seed, tenant), data, cell.batch,
                           epochs * cell.nb, cell.tenant_seed(seed, tenant))
        want = [steps[(e + 1) * cell.nb - 1] for e in range(epochs)]
        got = [float(x) for x in w["losses"][:epochs]]
        err = [abs(g - r) / abs(r) for g, r in zip(got, want)]
        good = len(got) == epochs and all(e <= rtol for e in err)
        ok = ok and good
        rows.append({"tenant": tenant["name"], "program": got,
                     "reference": want, "rel_err": err, "rtol": rtol,
                     "ok": good, "seconds": time.monotonic() - t_ref})
    return ok, rows


def work_model_shares(cell: Cell, peaks: Dict[str, float], aggregate: float
                      ) -> Dict[str, float]:
    """What the configuration's own arithmetic (the functions it names under
    ``job.flops_fn`` / ``job.bytes_fn``, found by ``perf/work_models.py``
    ``resolve``: that file's, or ``perf/work/<sibling>.py``'s) amounts to at
    the run's ``aggregate`` rate, as shares of the chips' peaks: a line each,
    traced run or not, and the shares by the lines' names. A name that does
    not resolve, or a function that counts nothing, prints
    ``work_model_failed`` and leaves its share out: no other count stands in."""
    from perf import work_models

    def counted(key: str) -> Optional[float]:
        if not cell.job.get(key):
            return None
        try:
            return work_models.count(cell.job, key)
        except Exception as e:
            say("work_model_failed", key=key, name=cell.job[key],
                error=f"{type(e).__name__}: {e}"[:300])
            return None

    shares = {}
    per_unit, per_ex = counted("flops_fn"), counted("bytes_fn")
    if per_unit is not None:
        shares["model_flops_utilisation"] = (
            aggregate * per_unit / (peaks["bf16_flops"] * cell.chips))
        say("model_flops_utilisation", flops_per_unit=per_unit,
            share_of_peak=shares["model_flops_utilisation"])
    if per_ex is not None:
        shares["table_bandwidth"] = (
            aggregate / float(cell.job["units_per_example"]) * per_ex
            / (peaks["hbm_bytes_per_s"] * cell.chips))
        say("table_bandwidth", bytes_per_example=per_ex,
            share_of_peak=shares["table_bandwidth"])
    return shares


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--debug-dir", default=None,
                    help="a builder's aid, not part of a check: write "
                         "every poll of the measured job there, and every "
                         "thread's stack whenever its feeds stop")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU: control flow only, "
                         "no metric")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = Cell(args.workload, args.rehearse)
    os.environ.update(PROGRAM_ENV)
    os.environ.update(cell.job["env"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu" or len(devices) < cell.chips):
        print(f"perf/run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"reports {len(devices)} x {platform!r}. No result.",
              file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    kind = str(devices[0].device_kind)
    peaks = load_json(PERF, "peaks.json").get(kind)
    if peaks is None and not args.rehearse:
        print(f"perf/run.py: no peaks for device_kind {kind!r} in "
              "perf/peaks.json; add it with its source.", file=sys.stderr)
        return 1

    from perf.jobserver_client import Server
    from harmony_tpu.runtime import progcache

    compiles = CompileLog()
    marks = {"imports": time.monotonic()}  # where set-up's seconds go
    sched = cell.traffic.get("scheduler") or {}
    server = Server(cell.chips, sched.get("class"), sched.get("args"))
    poller = Poller(server)
    marks["jobserver"] = time.monotonic()
    try:
        # 1. warm-up
        warm_cfgs = [cell.job_config(args.seed, t, "warm", WARMUP_EPOCHS)
                     for t in cell.tenants]
        _, warm, _ = run_jobs(server, poller, warm_cfgs)
        marks["warmup"] = time.monotonic()
        # epochs a second, per tenant, from the warm-up's second window
        # (the first holds the compile): what sizes the measured job
        sizes = {}
        for t, c in zip(cell.tenants, warm_cfgs):
            walls = poller.epoch_walls.get(c.job_id, {})
            last = [max(walls[str(e)].values())
                    for e in range(EPOCH_WINDOW, WARMUP_EPOCHS)
                    if str(e) in walls]
            if not last:
                raise RuntimeError(f"warm-up {c.job_id} reported no epoch "
                                   f"walls: {str(warm[c.job_id])[:300]}")
            eps = len(last) / sum(last)
            windows = 1 + math.ceil(OUTLAST * args.seconds * eps / EPOCH_WINDOW)
            sizes[t["name"]] = {"epochs_per_s": eps,
                                "num_epochs": EPOCH_WINDOW * windows}
        say("warmup", sizes=sizes,
            tenants={j: (None if worker_result(r) is None else
                         worker_result(r)["losses"][:4]) for j, r in warm.items()})
        # 2. the check, outside the window
        check_ok, check_rows = reference_check(cell, args.seed, warm)
        say("reference_check", ok=check_ok, tenants=check_rows)
        marks["check"] = time.monotonic()
        # 3. the measured job
        cfgs = [cell.job_config(args.seed, t, "run", sizes[t["name"]]["num_epochs"])
                for t in cell.tenants]
        jobs = poller.watched = [c.job_id for c in cfgs]
        window: Dict[str, Any] = {}
        # one trace per cell is kept, inside the checkout (git-ignored)
        trace_dir = os.path.join(ROOT, "chiprun_out", "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        stacks = None
        if args.debug_dir:
            os.makedirs(args.debug_dir, exist_ok=True)
            stacks = open(os.path.join(args.debug_dir, "stall_stacks.txt"), "w")

        def watch_for_stall(t: float) -> None:
            """Re-arm faulthandler's timer (its own thread, no GIL) at each
            feed: it fires when the next is STALL_FACTOR median gaps late."""
            pts = [p for p in poller.changes(jobs[0]) if p[0] >= window["t0"] - 1e-9]
            if t >= window["t1"]:
                faulthandler.cancel_dump_traceback_later()
            elif len(pts) >= 3 and len(pts) != window.get("feeds"):
                window["feeds"] = len(pts)
                gaps = sorted(b[0] - a[0] for a, b in zip(pts, pts[1:]))
                late = rates.STALL_FACTOR * gaps[len(gaps) // 2]
                stacks.write(f"--- armed at +{t - window['t0']:.2f}s for {late:.2f}s\n")
                stacks.flush()
                faulthandler.dump_traceback_later(late, file=stacks)

        def on_poll(t: float) -> None:
            if stacks is not None and "t0" in window:
                watch_for_stall(t)
            if "t0" not in window:
                firsts = [poller.changes(j) for j in jobs]
                if all(firsts):  # every tenant's counters have moved
                    window["t0"] = max(p[0][0] for p in firsts)
                    window["t1"] = window["t0"] + args.seconds
                    window["cache0"] = progcache.stats()
                return
            if not args.trace:
                return
            mid = 0.5 * (window["t0"] + window["t1"])
            if "trace0" not in window and t >= mid - TRACE_SECONDS / 2:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # the host stays as it is
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                window["trace0"] = time.monotonic()
            elif ("trace0" in window and "trace1" not in window
                  and t >= window["trace0"] + TRACE_SECONDS):
                window["trace1"] = time.monotonic()
                jax.profiler.stop_trace()
            if "cache1" not in window and t >= window["t1"]:
                window["cache1"] = progcache.stats()

        t_submit, results, done_at = run_jobs(server, poller, cfgs,
                                              until=on_poll)
        window.setdefault("cache1", progcache.stats())
        if "trace0" in window and "trace1" not in window:
            window["trace1"] = time.monotonic()
            jax.profiler.stop_trace()
        peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
    finally:
        faulthandler.cancel_dump_traceback_later()
        server.shutdown()

    # ---- reduction --------------------------------------------------------
    if "t0" not in window:
        print("perf/run.py: the measured job's counters never moved. "
              "No result.", file=sys.stderr)
        return 1
    t0, t1 = window["t0"], window["t1"]
    per_tenant: Dict[str, Dict[str, float]] = {}
    for t, job in zip(cell.tenants, jobs):
        # the feeds seen by a poll inside the window (stamp + half width)
        seen = [f for f in poller.feeds.get(job, [])
                if t0 - 1e-9 <= f[0] + 0.5 * f[2] <= t1]
        fit = rates.steady([f[:2] for f in seen], POLL_PERIOD_S,
                           [f[2] for f in seen])
        if fit is not None:
            fit["fine"] = sum(f[2] <= 2.0 * FINE_PERIOD_S for f in seen)
            per_tenant[t["name"]] = fit
    # a measured job that ended inside the window was sized too short
    ended_early = [j for j in jobs if done_at[j] < t1]
    compiles_in_window = compiles.compiles_between(t0, t1)
    cache_grew = (window["cache1"]["misses"] - window["cache0"]["misses"])
    failed = [j for j, r in results.items() if not tenant_ok(r)]
    failed += [j for j, r in warm.items()
               if isinstance(r, Exception) and j not in failed]
    correct = (not failed and check_ok and compiles_in_window == 0
               and cache_grew == 0 and not ended_early
               and len(per_tenant) == len(jobs))

    units = float(cell.job["units_per_example"])
    tenant_rates = {n: f["rate"] * units for n, f in per_tenant.items()}
    aggregate = sum(tenant_rates.values())
    e2e: Dict[str, float] = {"setup_s": t0 - T_PROCESS_START}
    if tenant_rates:
        e2e[cell.job["rate_metric"]] = aggregate
        e2e["min_tenant_share"] = (100.0 * min(tenant_rates.values())
                                   / (aggregate / len(tenant_rates)))
    if args.debug_dir:
        stacks.close()
        with open(os.path.join(args.debug_dir, "polls.json"), "w") as f:
            json.dump({"t0": t0, "t1": t1, "jobs": jobs,
                       "counters": {j: poller.counters.get(j) for j in jobs},
                       "phases": {j: poller.phases.get(j) for j in jobs}}, f)
    marks["job_start"] = t0
    edges = [T_PROCESS_START] + list(marks.values())
    say("setup", seconds={k: b - a for k, a, b in
                          zip(marks, edges, edges[1:])},
        compile_events=len(compiles.events),
        compile_s_before_window=compiles.seconds_before(t0))
    say("window", seconds=args.seconds, fits=per_tenant,
        tenant_rates=tenant_rates, ended_before_window_end=ended_early,
        compiles_in_window=compiles_in_window,
        progcache_misses_in_window=cache_grew,
        status_ms_median=1e3 * sorted(poller.status_seconds)[
            len(poller.status_seconds) // 2],
        polls=len(poller.status_seconds),
        losses={j: (None if worker_result(r) is None else
                    [worker_result(r)["losses"][0], worker_result(r)["losses"][-1]])
                for j, r in results.items()})
    shares = (work_model_shares(cell, peaks, aggregate)
              if peaks is not None and tenant_rates else {})

    device: Dict[str, Any] = {"platform": platform, "kind": kind,
                              "count": len(devices),
                              "memory_peak_bytes": peak_bytes}
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": len(jobs), "failed": len(failed)}
    if args.trace:
        from perf import trace_reduce

        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        reduction = (trace_reduce.reduce(trace_reduce.load(found[0]))
                     if found else None)
        obs = {
            "job_start_s": t0 - t_submit,
            "compile_s": compiles.seconds_before(t0),
            "phases": {j: poller.phase_delta(j, t0, t1) for j in jobs},
            "fits": list(per_tenant.values()),
            "trace": reduction,
            "memory_peak_bytes": peak_bytes,
            "hbm_bytes": None if peaks is None else peaks["hbm_bytes"],
            "model_flops_share": shares.get("model_flops_utilisation"),
        }
        say("progcache", costs_s=sum(c.get("compile_seconds") or 0.0
                                     for c in progcache.program_costs()),
            stats=progcache.stats(), jax_compile_s=obs["compile_s"])
        values = {}
        for m in cell.metrics("per_layer"):
            if m["moves"] not in e2e:
                continue
            reader = load_by_path("layer_metrics", m["name"].split(".")[0])
            value = reader.read(obs)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduction is not None:
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
            out["breakdown"] = {"device_ops": reduction["device_ops"],
                                "idle_gaps": reduction["idle_gaps"]}
    else:
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in cell.metrics("end_to_end") if m["name"] in e2e}
    if args.rehearse:
        say("rehearsal", note="CPU rehearsal: control flow only; the "
            "numbers below are not device metrics", would_report=values)
        values = {}
    out["metrics"] = values
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
