"""Command-line entry points — parity with the reference's ``bin/`` scripts.

Reference mapping (SURVEY.md appendix: entry-point index):

  start_jobserver.sh      -> ``harmony-tpu start-jobserver``
  submit_<app>.sh         -> ``harmony-tpu submit <app> [overrides]``
  run_<app>.sh (standalone)-> ``harmony-tpu run <app> [overrides]``
  (SHUTDOWN command)      -> ``harmony-tpu shutdown``
  (status)                -> ``harmony-tpu status``
  dashboard.py            -> ``harmony-tpu dashboard``

Every app ships a synthetic-data preset (the reference's submit scripts
likewise bake in example scales, e.g. submit_mlr.sh's 10x784) overridable
with ``--set key=value`` (app hyper-params), ``--data key=value`` (data/graph
args) and the common flags. ``submit`` talks to a running JobServer over the
TCP control plane; ``run`` is the standalone ETDolphinLauncher analogue
(in-process server, one job, exit).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List

from harmony_tpu.config.params import JobConfig, TrainerParams

# -- app presets ------------------------------------------------------------
# Scales chosen to finish in seconds on one chip while exercising the real
# code paths; override any field via --set / --data.

# Parameters of models.transformer:load_text_tokens — kept STATIC so the
# thin TCP submit path never imports jax; pinned against the real signature
# by tests/test_cli.py.
FILE_CORPUS_KEYS = frozenset({"path", "seq_len", "num_seqs", "vocab_size"})

PRESETS: Dict[str, Dict[str, Any]] = {
    "mlr": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.mlr:MLRTrainer",
        app_params={"num_classes": 10, "num_features": 784,
                    "features_per_partition": 98, "step_size": 0.1},
        data_fn="harmony_tpu.apps.mlr:make_synthetic",
        data_args={"n": 4096, "num_features": 784, "num_classes": 10},
    ),
    "nmf": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.nmf:NMFTrainer",
        app_params={"num_rows": 256, "num_cols": 256, "rank": 16,
                    "step_size": 0.05},
        data_fn="harmony_tpu.apps.nmf:make_synthetic",
        data_args={"num_rows": 256, "num_cols": 256, "rank": 16},
    ),
    "lda": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.lda:LDATrainer",
        app_params={"vocab_size": 500, "num_topics": 10, "num_docs": 256,
                    "max_doc_len": 64},
        data_fn="harmony_tpu.apps.lda:make_synthetic",
        data_args={"num_docs": 256, "vocab_size": 500, "doc_len": 64,
                   "num_topics": 10},
    ),
    "lasso": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.lasso:LassoTrainer",
        app_params={"num_features": 256, "lam": 0.05},
        data_fn="harmony_tpu.apps.lasso:make_synthetic",
        data_args={"n": 2048, "num_features": 256},
    ),
    "gbt": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.gbt:GBTTrainer",
        app_params={"num_features": 16, "num_examples": 2048,
                    "num_rounds": 16, "loss": "squared", "max_depth": 4},
        data_fn="harmony_tpu.apps.gbt:make_binned_synthetic",
        data_args={"n": 2048, "num_features": 16},
    ),
    "addvector": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.addvector:AddVectorTrainer",
        app_params={"num_keys": 32, "vector_dim": 8},
        data_fn="harmony_tpu.apps.addvector:make_marks",
        data_args={"n": 1024},
    ),
    "addinteger": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.addvector:AddIntegerTrainer",
        app_params={"num_keys": 16},
        data_fn="harmony_tpu.apps.addvector:make_marks",
        data_args={"n": 1024},
    ),
    "lm": dict(
        app_type="dolphin",
        trainer="harmony_tpu.models.transformer:TransformerTrainer",
        app_params={"vocab_size": 128, "d_model": 64, "n_heads": 4,
                    "n_layers": 2, "d_ff": 256, "max_seq": 64,
                    "step_size": 0.2},
        data_fn="harmony_tpu.models.transformer:make_lm_data",
        data_args={"num_seqs": 64, "seq_len": 65, "vocab_size": 128},
    ),
    "vit": dict(
        app_type="dolphin",
        trainer="harmony_tpu.models.vit:ViTTrainer",
        app_params={"image_size": 16, "patch_size": 4, "num_classes": 4,
                    "channels": 3, "d_model": 64, "n_heads": 4,
                    "n_layers": 2, "d_ff": 128, "row_width": 512,
                    "step_size": 0.05},
        data_fn="harmony_tpu.models.vit:make_synthetic",
        data_args={"n": 128, "image_size": 16, "patch_size": 4,
                   "num_classes": 4, "channels": 3},
    ),
    "fm": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.widedeep:FMTrainer",
        app_params={"vocab_size": 10000, "num_slots": 8, "emb_dim": 8,
                    "step_size": 0.2},
        data_fn="harmony_tpu.apps.widedeep:make_synthetic",
        data_args={"n": 8192, "vocab_size": 10000, "num_slots": 8},
    ),
    "widedeep": dict(
        app_type="dolphin",
        trainer="harmony_tpu.apps.widedeep:WideDeepTrainer",
        app_params={"vocab_size": 10000, "num_slots": 8, "emb_dim": 8,
                    "hidden": 64, "step_size": 0.2},
        data_fn="harmony_tpu.apps.widedeep:make_synthetic",
        data_args={"n": 8192, "vocab_size": 10000, "num_slots": 8},
    ),
    "pagerank": dict(
        app_type="pregel",
        trainer="harmony_tpu.apps.pagerank:PageRankComputation",
        app_params={"num_iterations": 10},
        graph_fn="harmony_tpu.pregel.graph:random_graph",
        graph_args={"num_vertices": 1000, "avg_degree": 5},
    ),
    "connected-components": dict(
        app_type="pregel",
        trainer="harmony_tpu.apps.concomp:ConnectedComponentsComputation",
        app_params={},
        graph_fn="harmony_tpu.pregel.graph:random_graph",
        graph_args={"num_vertices": 1000, "avg_degree": 5},
    ),
    "shortest-path": dict(
        app_type="pregel",
        trainer="harmony_tpu.apps.sssp:ShortestPathComputation",
        app_params={"source": 0},
        graph_fn="harmony_tpu.pregel.graph:random_graph",
        graph_args={"num_vertices": 1000, "avg_degree": 5, "weighted": True},
    ),
}


def _parse_kv(pairs: List[str]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for p in pairs or []:
        if "=" not in p:
            raise SystemExit(f"bad override {p!r}: expected key=value")
        k, v = p.split("=", 1)
        try:
            out[k] = json.loads(v)   # numbers, bools, lists, quoted strings
        except json.JSONDecodeError:
            out[k] = v               # bare string
    return out


def build_config(app: str, args: argparse.Namespace) -> JobConfig:
    if app not in PRESETS:
        raise SystemExit(f"unknown app {app!r}; available: {sorted(PRESETS)}")
    preset = {k: (dict(v) if isinstance(v, dict) else v)
              for k, v in PRESETS[app].items()}
    preset["app_params"].update(_parse_kv(args.set))
    user: Dict[str, Any] = {}
    if preset["app_type"] == "pregel":
        if args.graph_file:
            user["graph_fn"] = "harmony_tpu.pregel.graph:load_edge_list"
            user["graph_args"] = {"path": args.graph_file}
        else:
            user["graph_fn"] = preset["graph_fn"]
            user["graph_args"] = preset["graph_args"]
        user["graph_args"].update(_parse_kv(args.data))
        user["max_supersteps"] = args.max_supersteps
    else:
        user["data_fn"] = preset["data_fn"]
        user["data_args"] = {**preset["data_args"], **_parse_kv(args.data)}
    if app == "lm" and "path" in user.get("data_args", {}):
        # real-file corpus: byte-level tokenization replaces the synthetic
        # generator; the preset's seq_len/num_seqs/vocab_size args carry
        # over (load_text_tokens shares those names). Args the file loader
        # does NOT take (e.g. seed) fail HERE, not mid-job. STATIC key set:
        # importing the models package (jax) into this otherwise-thin TCP
        # submit path would cost seconds and open the accelerator the
        # jobserver process owns; a test pins the set against the real
        # signature.
        user["data_fn"] = "harmony_tpu.models.transformer:load_text_tokens"
        stray = set(user["data_args"]) - FILE_CORPUS_KEYS
        if stray:
            raise SystemExit(
                f"--data keys {sorted(stray)} do not apply to file corpora "
                f"(load_text_tokens takes {sorted(FILE_CORPUS_KEYS)})"
            )
    # Model/data-coupled keys must match between --set and --data: an
    # explicit override on either side wins over the preset default, a
    # conflicting pair is an error at submit time (not silently-wrong
    # training or a mid-job shape crash).
    _COUPLED = {"lm": ("vocab_size",),
                "vit": ("image_size", "patch_size", "num_classes", "channels")}
    for key in _COUPLED.get(app, ()):
        set_v = _parse_kv(args.set).get(key)
        data_v = _parse_kv(args.data).get(key)
        if set_v is not None and data_v is not None and set_v != data_v:
            raise SystemExit(
                f"conflicting {key}: --set {set_v} vs --data {data_v}")
        v = set_v if set_v is not None else user["data_args"].get(
            key, data_v if data_v is not None else preset["app_params"][key])
        preset["app_params"][key] = v
        user["data_args"][key] = v
    # Dolphin-only flags must fail LOUDLY on graph apps and before any jax
    # work (same client-side validation stance as the --set overrides).
    if preset["app_type"] == "pregel" and (
        args.optimizer or args.model_chkp_period or args.offline_eval
        or getattr(args, "auto_resume", False)
    ):
        raise SystemExit(
            "--optimizer / --model-chkp-period / --offline-eval / "
            "--auto-resume apply to dolphin (training) apps only; pregel "
            "jobs have no model table or checkpoint chain"
        )
    if args.offline_eval and args.model_chkp_period <= 0:
        raise SystemExit(
            "--offline-eval needs --model-chkp-period > 0: deferred "
            "evaluation replays the checkpoint chain, and 0 chains nothing"
        )
    if getattr(args, "auto_resume", False):
        if args.model_chkp_period <= 0:
            raise SystemExit(
                "--auto-resume needs --model-chkp-period > 0: resume "
                "restores the last chain checkpoint, and 0 chains nothing"
            )
        user["auto_resume"] = True
    if getattr(args, "pod_isolated", False):
        user["pod_isolated"] = True
    if args.optimizer:
        from harmony_tpu.config.base import resolve_symbol
        from harmony_tpu.jobserver.entity import DolphinJobEntity

        ref = DolphinJobEntity._OPTIMIZERS.get(args.optimizer, args.optimizer)
        try:
            resolve_symbol(ref)
        except Exception as e:  # typo'd names fail at submit, not mid-job
            raise SystemExit(
                f"unknown --optimizer {args.optimizer!r} "
                f"(registry: {sorted(DolphinJobEntity._OPTIMIZERS)}): {e}"
            )
    job_id = args.job_id or f"{app}-job"
    return JobConfig(
        job_id=job_id,
        app_type=preset["app_type"],
        trainer=preset["trainer"],
        optimizer=args.optimizer,
        optimizer_period=args.optimizer_period,
        params=TrainerParams(
            num_epochs=args.epochs,
            num_mini_batches=args.batches,
            clock_slack=args.slack,
            model_chkp_period=args.model_chkp_period,
            offline_model_eval=args.offline_eval,
            app_params=preset["app_params"],
        ),
        num_workers=args.workers,
        user=user,
    )


def _common_job_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--job-id", default=None)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batches", type=int, default=4,
                   help="mini-batches per epoch")
    p.add_argument("--workers", type=int, default=0,
                   help="0 = one worker per executor")
    p.add_argument("--slack", type=int, default=0,
                   help="SSP clock slack (0 = BSP)")
    p.add_argument("--set", action="append", metavar="K=V", default=[],
                   help="override an app hyper-parameter")
    p.add_argument("--data", action="append", metavar="K=V", default=[],
                   help="override a synthetic-data/graph argument")
    p.add_argument("--graph-file", default=None,
                   help="edge-list file (pregel apps; replaces the synthetic graph)")
    p.add_argument("--max-supersteps", type=int, default=100)
    p.add_argument("--optimizer", default=None,
                   help="per-job elasticity loop: homogeneous | heterogeneous"
                        " | add_one_server | delete_one_server | dotted path"
                        " (the reference's -optimizer binding)")
    p.add_argument("--optimizer-period", type=float, default=5.0,
                   help="seconds between optimization rounds")
    p.add_argument("--model-chkp-period", type=int, default=0,
                   help="snapshot the model table every N epochs (0 = off)")
    p.add_argument("--offline-eval", action="store_true",
                   help="defer model evaluation over the checkpoint chain to"
                        " jobserver shutdown")
    p.add_argument("--auto-resume", action="store_true",
                   help="pod: on follower death, resubmit this job from its"
                        " last chain checkpoint onto surviving executors"
                        " (needs --model-chkp-period > 0)")
    p.add_argument("--pod-isolated", action="store_true",
                   help="pod: exclusive execution — opt out of the cross-job"
                        " unit interleaving (serialized behind FIFO"
                        " admission)")


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="harmony-tpu",
        description="TPU-native multi-tenant elastic training framework",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start-jobserver", help="long-running multi-tenant master")
    p.add_argument("--num-executors", type=int, default=0,
                   help="0 = one per local device")
    p.add_argument("--port", type=int, default=43110)
    p.add_argument("--dashboard-url", default=None,
                   help="POST live job metrics to this dashboard "
                        "(harmony-tpu dashboard prints its URL)")
    p.add_argument("--chkp-root", default=None,
                   help="root for model-checkpoint chains / auto-resume "
                        "(default: $HARMONY_POD_CHKP_ROOT)")
    p.add_argument("--ha-replica-id", default=None,
                   help="HA control plane (set with HARMONY_HA_LOG_DIR; "
                        "docs/DEPLOY.md §HA): this replica's stable "
                        "identity (default: hostname)")
    p.add_argument("--ha-advertise", default=None,
                   help="HA: the host:port OTHER replicas should "
                        "redirect clients to for this replica "
                        "(NOT_LEADER replies; default 127.0.0.1:--port)")
    p.add_argument("--ha-recv-port", type=int, default=None,
                   help="HA: bind the standby log-receiver here "
                        "(peer-replication mode, HARMONY_HA_REPLICAS); "
                        "omit when replicas share HARMONY_HA_LOG_DIR")
    p.add_argument("--ha-bind", default="127.0.0.1",
                   help="HA: interface the submit/standby endpoint "
                        "binds (0.0.0.0 when clients live on other "
                        "hosts, e.g. the GKE control plane)")

    for name in ("submit", "run"):
        p = sub.add_parser(
            name,
            help=("submit a job to a running jobserver" if name == "submit"
                  else "run one job standalone (in-process server)"),
        )
        p.add_argument("app", choices=sorted(PRESETS))
        _common_job_flags(p)
        if name == "submit":
            p.add_argument("--port", type=int, default=None,
                           help="jobserver TCP port (default: the "
                                "HARMONY_JOBSERVER_ADDRS replica list, "
                                "then 43110)")
        else:
            p.add_argument("--num-executors", type=int, default=0)

    p = sub.add_parser(
        "start-pod",
        help="one pod process: leader jobserver on process 0, follower "
             "loop elsewhere (roles from JAX_PROCESS_ID)",
    )
    p.add_argument("--num-executors", type=int, default=0,
                   help="0 = one per GLOBAL device")
    p.add_argument("--port", type=int, default=43110,
                   help="leader's TCP submit port")
    p.add_argument("--pod-port", type=int, default=43111,
                   help="leader's follower-control port")
    p.add_argument("--coordinator", default=None,
                   help="host:port of the jax.distributed coordinator "
                        "(default: $JAX_COORDINATOR_ADDRESS)")
    p.add_argument("--num-processes", type=int, default=0,
                   help="default: $JAX_NUM_PROCESSES")
    p.add_argument("--process-id", type=int, default=-1,
                   help="default: $JAX_PROCESS_ID")
    p.add_argument("--chkp-root", default=None,
                   help="shared/gs:// root for model-checkpoint chains, "
                        "auto-resume, deferred eval "
                        "(default: $HARMONY_POD_CHKP_ROOT; docs/DEPLOY.md)")
    p.add_argument("--pod-leader-addrs", default=None,
                   help="HA: comma-separated host:port control-plane "
                        "endpoints a follower may re-HELLO after leader "
                        "loss (default: the one leader it first joined; "
                        "docs/DEPLOY.md §HA)")

    p = sub.add_parser("status", help="query a running jobserver")
    p.add_argument("--port", type=int, default=None,
                   help="default: $HARMONY_JOBSERVER_ADDRS, then 43110")
    p = sub.add_parser("shutdown", help="graceful jobserver shutdown")
    p.add_argument("--port", type=int, default=None,
                   help="default: $HARMONY_JOBSERVER_ADDRS, then 43110")
    p = sub.add_parser(
        "pod-reshard",
        help="live-migrate table blocks of a RUNNING pod job "
             "(applied at the given epoch on every process in lockstep)",
    )
    p.add_argument("--port", type=int, default=None,
                   help="default: $HARMONY_JOBSERVER_ADDRS, then 43110")
    p.add_argument("--job", required=True)
    p.add_argument("--src", required=True, help="source executor id")
    p.add_argument("--dst", required=True, help="destination executor id")
    p.add_argument("--blocks", type=int, required=True)
    p.add_argument("--epoch", type=int, required=True,
                   help="apply epoch; needs a full window horizon of lead")

    p = sub.add_parser("dashboard", help="metrics dashboard HTTP server")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--db", default=":memory:")

    p = sub.add_parser(
        "lint",
        help="harmonylint: codebase-aware static analysis pinning the "
             "repo's concurrency/SPMD/docs invariants "
             "(docs/STATIC_ANALYSIS.md)",
    )
    p.add_argument("paths", nargs="*",
                   help="files or package dirs to lint "
                        "(default: the installed harmony_tpu/ tree)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report (schema v1)")
    p.add_argument("--passes", default=None,
                   help="comma-separated subset of pass names")
    p.add_argument("--list-passes", action="store_true",
                   help="print the pass catalog and exit")
    p.add_argument("--baseline", default=None,
                   help="baseline JSON: suppress its findings "
                        "(overrides [tool.harmony.lint] baseline)")
    p.add_argument("--write-baseline", default=None, metavar="PATH",
                   help="write the run's active findings as a new "
                        "baseline and exit 0")
    p.add_argument("--verbose", action="store_true",
                   help="also list suppressed findings")

    p = sub.add_parser(
        "inputsvc",
        help="standalone shared input-data service (jax-free worker "
             "process; trainers reach it via HARMONY_INPUT_SERVICE_ADDR "
             "— docs/INPUT_PIPELINE.md §Input service)",
    )
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral, printed as JSON)")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address (multi-host: a DCN-reachable IP)")
    p.add_argument("--workers", type=int, default=None,
                   help="worker slots (default HARMONY_INPUT_WORKERS)")

    p = sub.add_parser(
        "obs",
        help="observability tooling: per-tenant cost top, step-phase "
             "critpath, flight records, /metrics scrape, trace "
             "timelines, the scope table of a device profile "
             "(docs/OBSERVABILITY.md)",
    )
    p.add_argument("what",
                   choices=("top", "flight", "metrics", "trace",
                            "doctor", "critpath", "plan", "incidents",
                            "scopes"))
    p.add_argument("path", nargs="?", default=None,
                   help="scopes: a profile directory (a sampled "
                        "HARMONY_PROFILE_EVERY_N capture included) or an "
                        ".xplane.pb file")
    p.add_argument("--blocks", action="store_true",
                   help="scopes: one row per block (blk3/ffn) instead of "
                        "blocks folded (blk*/ffn)")
    p.add_argument("--top", type=int, default=40,
                   help="scopes: rows a module (default 40)")
    p.add_argument("--port", type=int, default=None,
                   help="jobserver TCP port (top/flight/doctor/critpath/"
                        "plan/incidents: STATUS query; default "
                        "$HARMONY_JOBSERVER_PORT then 43110)")
    p.add_argument("--json", action="store_true",
                   help="top: raw ledger JSON instead of the table; "
                        "doctor: raw diagnoses + history stats; "
                        "critpath: raw phase budgets; plan: the raw "
                        "policy section; incidents: the raw incidents "
                        "section")
    p.add_argument("--url", default=None,
                   help="metrics: exporter base URL (default "
                        "$HARMONY_METRICS_URL); trace: dashboard URL "
                        "(default $HARMONY_DASHBOARD_URL)")
    p.add_argument("--trace-id", default=None,
                   help="trace: the trace to fetch")
    p.add_argument("--job", default=None,
                   help="trace: fetch a job's recent spans instead")

    args = ap.parse_args(argv)

    if args.cmd == "start-jobserver":
        return _cmd_start_jobserver(args)
    if args.cmd == "start-pod":
        return _cmd_start_pod(args)
    if args.cmd == "submit":
        from harmony_tpu.tracing.span import trace_span

        cfg = build_config(args.app, args)
        # root span of the submission: its context rides the SUBMIT
        # message, so the server, pod legs and workers re-parent onto
        # ONE trace_id starting here (even though this short-lived
        # process has no receiver of its own)
        with trace_span("cli.submit", app=args.app, job_id=cfg.job_id):
            resp = _cli_command(
                lambda: _sender(args.port).send_job_submit_command(cfg))
        print(json.dumps(resp))
        return 0 if resp.get("ok") else 1
    if args.cmd == "lint":
        return _cmd_lint(args)
    if args.cmd == "inputsvc":
        # the standalone worker process is deliberately jax-free; its
        # entry shares __main__'s implementation
        from harmony_tpu.inputsvc.__main__ import main as inputsvc_main

        return inputsvc_main([
            "--port", str(args.port), "--host", args.host,
        ] + ([] if args.workers is None
             else ["--workers", str(args.workers)]))
    if args.cmd == "obs":
        return _cmd_obs(args)
    if args.cmd == "run":
        return _cmd_run(args)
    if args.cmd == "pod-reshard":
        resp = _cli_command(
            lambda: _sender(args.port).send_pod_reshard_command(
                args.job, args.src, args.dst, args.blocks, args.epoch))
        print(json.dumps(resp))
        return 0 if resp.get("ok") else 1
    if args.cmd in ("status", "shutdown"):
        sender = _sender(args.port)
        resp = _cli_command(
            lambda: (sender.send_status_command() if args.cmd == "status"
                     else sender.send_shutdown_command()))
        print(json.dumps(resp))
        return 0 if resp.get("ok") else 1
    if args.cmd == "dashboard":
        from harmony_tpu.dashboard.server import DashboardServer
        from harmony_tpu.tracing import flight

        flight.install_signal_dump()
        server = DashboardServer(db_path=args.db, port=args.port).start()
        print(f"dashboard at {server.url}", flush=True)
        try:
            import time

            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            server.stop()
        return 0
    raise SystemExit(f"unknown command {args.cmd}")


def _chkp_root_of(args: argparse.Namespace) -> "str | None":
    """--chkp-root flag, else HARMONY_POD_CHKP_ROOT — the server-side
    root for model-checkpoint chains / auto-resume / deferred eval
    (docs/DEPLOY.md §4). Without it those features refuse per-job with a
    clear error instead of writing nowhere."""
    import os

    return getattr(args, "chkp_root", None) or os.environ.get(
        "HARMONY_POD_CHKP_ROOT")


def _make_server(num_executors: int, dashboard_url=None, chkp_root=None):
    """The in-process JobServer over this process's devices — the one
    process that opens the accelerator (clients stay jax-free)."""
    import jax

    from harmony_tpu.jobserver.server import JobServer
    from harmony_tpu.utils.compcache import enable_compile_cache

    enable_compile_cache()
    n = num_executors or len(jax.devices())
    server = JobServer(num_executors=n, dashboard_url=dashboard_url,
                       chkp_root=chkp_root)
    server.start()
    return server


def _cmd_lint(args: argparse.Namespace) -> int:
    """harmonylint runner — pure stdlib, never imports jax (this must
    stay invocable on a box with no accelerator stack, like the thin
    submit path). Exit codes: 0 clean, 1 findings, 2 usage error."""
    import os

    from harmony_tpu.analysis import (
        all_passes,
        get_pass,
        load_baseline,
        render_json,
        render_text,
        run_lint,
        save_baseline,
    )

    if args.list_passes:
        for p in all_passes():
            print(f"{p.name:22s} {p.description}")
        return 0
    passes = None
    if args.passes:
        try:
            passes = [get_pass(n.strip())
                      for n in args.passes.split(",") if n.strip()]
        except KeyError as e:
            print(e.args[0], file=sys.stderr)
            return 2
    baseline = None
    if args.baseline:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError) as e:
            print(f"baseline: {e}", file=sys.stderr)
            return 2
    kwargs: Dict[str, Any] = {"passes": passes, "baseline": baseline}
    if args.paths:
        missing = [p for p in args.paths
                   if not os.path.isfile(p) and not os.path.isdir(p)]
        if missing:
            # a typo'd path silently dropped would leave the gate green
            # while the file goes unlinted
            print(f"lint: no such path: {', '.join(missing)}",
                  file=sys.stderr)
            return 2
        files = [p for p in args.paths if os.path.isfile(p)]
        dirs = [p for p in args.paths if os.path.isdir(p)]
        if files and dirs:
            print("lint: pass either files or one package dir, not both",
                  file=sys.stderr)
            return 2
        if files:
            kwargs["files"] = files
        elif len(dirs) == 1:
            kwargs["root"] = dirs[0]
        else:
            print("lint: at most one package dir", file=sys.stderr)
            return 2
    try:
        result = run_lint(**kwargs)
    except (ValueError, OSError) as e:
        # broken [tool.harmony.lint] config / unreadable baseline: a
        # USAGE error (exit 2), never confusable with "findings" (1)
        print(f"lint: {e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        try:
            n = save_baseline(result, args.write_baseline)
        except OSError as e:
            # same contract as a bad --baseline read: a failed WRITE is a
            # usage error (2), never confusable with "findings" (1)
            print(f"lint: write-baseline: {e}", file=sys.stderr)
            return 2
        print(f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} to "
              f"{args.write_baseline}")
        return 0
    if args.json:
        print(render_json(result))
    else:
        print(render_text(result, verbose=args.verbose))
    return 0 if result.ok else 1


def _cmd_obs(args: argparse.Namespace) -> int:
    """Observability tooling (docs/OBSERVABILITY.md): dump flight
    records via STATUS, scrape-and-pretty-print a /metrics endpoint, or
    fetch a trace timeline from the dashboard's span store. Output is
    made for piping (`| head`, `| grep`), so a closed pipe ends the
    command quietly instead of stack-tracing."""
    from harmony_tpu.jobserver.client import NotLeaderError

    try:
        return _cmd_obs_inner(args)
    except NotLeaderError as e:
        # an explicitly addressed standby/deposed replica: the refusal
        # is an answer (with the redirect), not a traceback
        print(json.dumps({"ok": False, "not_leader": True,
                          "error": str(e), "leader": e.leader}))
        return 1
    except BrokenPipeError:
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0


#: env knobs behind the shared ``obs`` endpoint resolution (documented
#: in docs/OBSERVABILITY.md §6 / DEPLOY §7) — the flag always wins; the
#: port-based STATUS commands fall back to the HA replica list
#: (HARMONY_JOBSERVER_ADDRS), then the default submit port
ENV_JOBSERVER_PORT = "HARMONY_JOBSERVER_PORT"
ENV_METRICS_URL = "HARMONY_METRICS_URL"
ENV_DASHBOARD_URL = "HARMONY_DASHBOARD_URL"
_OBS_URL_KNOBS = {"metrics": ENV_METRICS_URL, "trace": ENV_DASHBOARD_URL}


def _sender(port):
    """CommandSender for the submit/status/shutdown/reshard commands:
    an explicit --port wins; otherwise the HARMONY_JOBSERVER_ADDRS
    replica list (failover + NOT_LEADER redirects — control-plane HA),
    then the default submit port."""
    from harmony_tpu.jobserver.client import CommandSender

    if port is not None:
        return CommandSender(int(port))
    return CommandSender.from_env()


def _cli_command(fn):
    """Run one client command; a NOT_LEADER refusal from an explicitly
    addressed standby/deposed replica comes back as the documented
    one-line JSON reply (exit 1), never a raw traceback."""
    from harmony_tpu.jobserver.client import NotLeaderError

    try:
        return fn()
    except NotLeaderError as e:
        return {"ok": False, "not_leader": True, "error": str(e),
                "leader": e.leader}


def _resolve_obs_endpoint(args: argparse.Namespace):
    """ONE endpoint resolution for every ``obs`` subcommand (the old
    shape made ``metrics``/``trace`` demand --url while the STATUS
    commands silently used a different flag): explicit flag, then the
    env knobs — HARMONY_JOBSERVER_ADDRS (the HA replica list, so
    ``obs`` keeps answering through a leader takeover) before
    HARMONY_JOBSERVER_PORT — then, for port-based commands only, the
    default submit port. Returns ``("port", int)``, ``("addrs",
    [host:port, ...])`` or ``("url", str)``; raises SystemExit(2) with
    an error NAMING the env knob otherwise."""
    import os

    if args.what in _OBS_URL_KNOBS:
        knob = _OBS_URL_KNOBS[args.what]
        url = args.url or os.environ.get(knob, "").strip()
        if not url:
            raise SystemExit(
                f"obs {args.what} needs --url (or the {knob} env knob)")
        return "url", url.rstrip("/")
    if args.port is not None:
        return "port", int(args.port)
    from harmony_tpu.jobserver.client import jobserver_addrs

    addrs = jobserver_addrs()
    if addrs:
        return "addrs", addrs
    raw = os.environ.get(ENV_JOBSERVER_PORT, "").strip()
    if raw:
        try:
            return "port", int(raw)
        except ValueError:
            raise SystemExit(
                f"obs {args.what}: {ENV_JOBSERVER_PORT}={raw!r} is not "
                "a port number")
    return "port", 43110


def _obs_status_sender(kind: str, endpoint):
    """CommandSender for the STATUS-backed obs subcommands: a plain
    port, or the HA replica list (failover + NOT_LEADER redirects)."""
    from harmony_tpu.jobserver.client import CommandSender

    if kind == "addrs":
        return CommandSender(addrs=endpoint)
    return CommandSender(endpoint)


def _cmd_obs_scopes(args: argparse.Namespace) -> int:
    """``obs scopes``: where a captured profile's device time went, by the
    step program's own scopes (tracing/stepscopes.py) — by device and
    module, ms a step. Reads files only: no server, no jax."""
    from harmony_tpu.tracing import stepscopes

    path = stepscopes.find_xplane(args.path) if args.path else None
    if path is None:
        print("obs scopes needs a profile directory or an .xplane.pb "
              f"(got {args.path!r})", file=sys.stderr)
        return 2
    reduced = stepscopes.reduce_file(path)
    if getattr(args, "json", False):
        print(json.dumps({"file": path, "devices": {
            str(dev): {name: {
                "seconds": entry["seconds"],
                "executions": entry["executions"],
                "rows": [{"scope": r.scope, "pass": r.which,
                          "class": r.klass, "seconds": r.seconds,
                          "calls": r.calls, "flops": r.flops,
                          "inherited_s": r.inherited_s}
                         for r in entry["rows"]]}
                for name, entry in by_module.items()}
            for dev, by_module in reduced.items()}}, indent=1))
        return 0
    if not reduced:
        print(f"{path}: no /host:metadata plane with HLO, or no device ran "
              "anything: nothing to name")
        return 1
    print(path)
    for line in stepscopes.render(reduced, top=args.top,
                                  blocks=args.blocks):
        print(line)
    return 0


def _cmd_obs_inner(args: argparse.Namespace) -> int:
    import urllib.request

    if args.what == "scopes":
        return _cmd_obs_scopes(args)
    try:
        kind, endpoint = _resolve_obs_endpoint(args)
    except SystemExit as e:
        print(e.args[0], file=sys.stderr)
        return 2
    if args.what == "top":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        if not status.get("ok"):
            print(json.dumps(status))
            return 1
        if getattr(args, "json", False):
            print(json.dumps(status.get("tenants", {}), indent=2))
            return 0
        for line in _render_overload(status.get("overload") or {}):
            print(line)
        for line in _render_tenant_top(status.get("tenants", {})):
            print(line)
        return 0
    if args.what == "flight":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        print(json.dumps({
            "flight_records": status.get("flight_records", []),
            "metrics_port": status.get("metrics_port"),
            "stragglers": status.get("stragglers", {}),
            "profile_capture": status.get("profile_capture"),
        }, indent=2))
        return 0 if status.get("ok") else 1
    if args.what == "doctor":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        if not status.get("ok"):
            print(json.dumps(status))
            return 1
        if getattr(args, "json", False):
            print(json.dumps({
                "diagnoses": status.get("diagnoses", []),
                "history": status.get("history", {}),
            }, indent=2))
            return 0
        for line in _render_doctor(status.get("diagnoses", []),
                                   status.get("history", {})):
            print(line)
        return 0
    if args.what == "critpath":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        if not status.get("ok"):
            print(json.dumps(status))
            return 1
        if getattr(args, "json", False):
            print(json.dumps(status.get("phase_budget", {}), indent=2))
            return 0
        for line in _render_critpath(status.get("phase_budget", {})):
            print(line)
        return 0
    if args.what == "plan":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        if not status.get("ok"):
            print(json.dumps(status))
            return 1
        if getattr(args, "json", False):
            print(json.dumps(status.get("policy", {}), indent=2))
            return 0
        for line in _render_policy(status.get("policy", {})):
            print(line)
        return 0
    if args.what == "incidents":
        status = _obs_status_sender(kind, endpoint).send_status_command()
        if not status.get("ok"):
            print(json.dumps(status))
            return 1
        if getattr(args, "json", False):
            print(json.dumps(status.get("incidents", {}), indent=2))
            return 0
        for line in _render_incidents(status.get("incidents", {})):
            print(line)
        return 0
    base = endpoint
    if args.what == "metrics":
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        from harmony_tpu.metrics.registry import parse_exposition

        try:
            families = parse_exposition(text)
        except ValueError as e:
            print(text)
            print(f"(unparseable exposition: {e})", file=sys.stderr)
            return 1
        for name in sorted(families):
            fam = families[name]
            print(f"{name} [{fam['type']}]  {fam['help'] or ''}")
            for sname, labels, value in fam["samples"]:
                lab = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                print(f"  {sname}{{{lab}}} = {value}")
        return 0
    # trace timeline from the dashboard's span store
    if args.trace_id:
        q = f"trace_id={args.trace_id}"
    elif args.job:
        q = f"job_id={args.job}"
    else:
        print("obs trace needs --trace-id or --job", file=sys.stderr)
        return 2
    spans = json.loads(urllib.request.urlopen(
        base + "/api/trace?" + q, timeout=10).read())
    if not spans:
        print("no spans", file=sys.stderr)
        return 1
    from harmony_tpu.tracing.timeline import timeline_rows

    for row in timeline_rows(spans):
        s = row["span"]
        ann = " ".join(
            f"{k}={v}"
            for k, v in sorted((s.get("annotations") or {}).items()))
        print(f"{row['offset_sec']:9.3f}s {'  ' * row['depth']}"
              f"{s['description']} [{row['duration_sec'] * 1000:.1f}ms] "
              f"({s.get('process_id') or '?'}) {ann}")
    return 0


def _render_table(rows: "List[tuple]") -> "List[str]":
    """Fixed-width text table shared by the ``obs`` renderers: rows[0]
    is the header; a dashed separator follows it."""
    widths = [max(len(row[i]) for row in rows)
              for i in range(len(rows[0]))]
    out = []
    for i, row in enumerate(rows):
        out.append("  ".join(c.ljust(w)
                             for c, w in zip(row, widths)).rstrip())
        if i == 0:
            out.append("  ".join("-" * w for w in widths))
    return out


def _render_doctor(diagnoses: list, history: dict) -> "List[str]":
    """One-screen doctor view from a single STATUS scrape: a header
    with the store's shape (series/points/targets — is the sensor
    layer even seeing anything?), then one row per diagnosis, newest
    last. Empty is a real answer: 'no diagnoses' over a populated
    store means the cluster looks healthy; over an EMPTY store it
    means nothing is being scraped — the header disambiguates."""
    out = []
    scraper = history.get("scraper") or {}
    out.append(
        f"history: {history.get('series', 0)} series, "
        f"{history.get('points', 0)} points, "
        f"window {history.get('window_sec', '?')}s @ "
        f"{history.get('resolution_sec', '?')}s, "
        f"{scraper.get('cycles', 0)} scrape cycles, "
        f"targets: {', '.join(history.get('targets', [])) or '-'}")
    if history.get("gap_marks"):
        out.append(f"  ({history['gap_marks']} missed-scrape gap marks, "
                   f"{history.get('restarts', 0)} process restarts seen)")
    if not diagnoses:
        out.append("no diagnoses — all rules silent over the window")
        return out
    rows = [("WHEN", "RULE", "SUBJECT", "CONF", "SUMMARY")]
    import time as _time

    for d in diagnoses:
        rows.append((
            _time.strftime("%H:%M:%S", _time.localtime(d.get("ts", 0))),
            str(d.get("rule", "?")),
            str(d.get("job") or d.get("target") or "-"),
            f"{d.get('confidence', 0.0):.2f}",
            str(d.get("summary", "")),
        ))
    return out + _render_table(rows)


def _render_policy(policy: dict) -> "List[str]":
    """One-screen device-policy view from a single STATUS scrape
    (docs/SCHEDULING.md has the action catalog): a header with the
    engine's mode and gate state, the last computed plan (every
    candidate with why it was or wasn't acted on), and the recent
    actions with their outcomes. 'mode: advise' with planned actions is
    the dry-run answer; 'mode: off' means the loop is disabled."""
    if not policy:
        return ["(no policy section — server predates the policy "
                "engine?)"]
    gate = policy.get("gate") or {}
    out = [
        f"policy: mode={policy.get('mode', '?')} "
        f"period={policy.get('period_sec', '?')}s "
        f"evaluations={policy.get('evaluations', 0)} "
        f"actions={policy.get('actions_total', 0)} "
        f"rejected={policy.get('rejected_total', 0)} "
        f"eval={policy.get('eval_ms', 0.0)}ms",
        f"gate: cooldown={gate.get('cooldown_sec', '?')}s "
        f"confirm={gate.get('confirm', '?')} "
        f"fired={gate.get('fired_total', 0)}"
        + (f" cooling={','.join(gate['cooling'])}"
           if gate.get("cooling") else "")
        + (f" backoffs={gate['backoffs']}"
           if gate.get("backoffs") else ""),
    ]
    plan = policy.get("last_plan") or {}
    if plan:
        out.append(
            f"last plan: idle={len(plan.get('idle_executors') or [])} "
            f"queued={','.join(plan.get('queued') or []) or '-'}")
        for c in plan.get("considered") or []:
            why = c.get("blocked")
            if c.get("check") == "contention":
                out.append(
                    f"  contention: {c.get('claimant')} (priority "
                    f"{c.get('claim_priority')}) vs victims "
                    f"{','.join(c.get('victims') or []) or '-'}")
            else:
                att = c.get("attainment")
                out.append(
                    f"  {c.get('job')}: attainment "
                    + ("-" if att is None else f"{att:.2f}")
                    + f" class={c.get('class') or '-'} "
                    + (f"-> blocked: {why}" if why else "-> grow candidate"))
    actions = policy.get("recent_actions") or []
    if not actions:
        out.append("no actions recorded — the mix looks placeable "
                   "as-is (or the engine is off/advising with nothing "
                   "to advise)")
        return out
    rows = [("WHEN", "ACTION", "TENANT", "OUTCOME", "TARGET", "REASON")]
    import time as _time

    for a in actions:
        rows.append((
            _time.strftime("%H:%M:%S", _time.localtime(a.get("ts", 0))),
            str(a.get("kind", "?")) + ("*" if a.get("shared") else ""),
            str(a.get("job", "?")),
            str(a.get("outcome", "?")),
            ",".join(a.get("executors") or []),
            str(a.get("reason", ""))[:60],
        ))
    out += _render_table(rows)
    out.append("(* = shared/overlapping grant)")
    return out


#: causal nesting rank for the incident timeline: each evidence edge
#: indents under the newest edge of an earlier rank, so the rendered
#: staircase IS the causal story (trigger → diagnosis → action →
#: resolution)
_INCIDENT_RANK = {"trigger": 0, "diagnosis": 1, "action": 2,
                  "resolution": 3}


def _render_incidents(incidents: dict) -> "List[str]":
    """One-screen incident view from a single STATUS scrape
    (docs/OBSERVABILITY.md §10): a header with the lifecycle counts,
    then each incident as its own causal timeline — the evidence chain
    shaped through tracing/timeline.py, offsets relative to the
    trigger. Unknown latencies render '-' (an open incident has no
    MTTR yet; 0 would be a lie)."""
    if not incidents:
        return ["(no incidents section — server predates the incident "
                "engine?)"]

    def _sec(v) -> str:
        return "-" if v is None else f"{v:.3f}s"

    out = [
        f"incidents: open={incidents.get('open', 0)} "
        f"mitigating={incidents.get('mitigating', 0)} "
        f"resolved={incidents.get('resolved', 0)} "
        f"window={incidents.get('window_sec', '?')}s "
        f"mean_mttr={_sec(incidents.get('mttr_mean_sec'))}"
        + (f" adopted={incidents['adopted']}"
           if incidents.get("adopted") else ""),
    ]
    rows = incidents.get("incidents") or []
    if not rows:
        out.append("no incidents — the evidence stream is quiet")
        return out
    from harmony_tpu.tracing.timeline import timeline_rows

    for inc in rows:
        verdict = inc.get("verdict")
        out.append("")
        out.append(
            f"{inc.get('incident_id', '?')} "
            f"[{inc.get('status', '?')}"
            + (f"/{verdict}" if verdict else "") + "] "
            f"subject={inc.get('subject', '?')} "
            f"mttd={_sec(inc.get('mttd_sec'))} "
            f"mitigate={_sec(inc.get('mitigate_sec'))} "
            f"mttr={_sec(inc.get('mttr_sec'))}")
        spans, newest_by_rank = [], {}
        for i, edge in enumerate(inc.get("chain") or []):
            rank = _INCIDENT_RANK.get(edge.get("role"), 0)
            parent = max((sid for r, sid in newest_by_rank.items()
                          if r < rank), default=None)
            spans.append({"span_id": i + 1, "parent_id": parent,
                          "description": str(edge.get("summary")
                                             or edge.get("kind") or "?"),
                          "start_sec": edge.get("ts"),
                          "stop_sec": edge.get("ts"), "edge": edge})
            newest_by_rank[rank] = i + 1
        for row in timeline_rows(spans):
            edge = row["span"]["edge"]
            out.append(
                f"  +{row['offset_sec']:8.3f}s {'  ' * row['depth']}"
                f"{edge.get('role', '?'):<10} "
                f"{row['span']['description']} [{edge.get('src', '?')}]")
    return out


#: waterfall row order + short labels (docs/OBSERVABILITY.md §9 column
#: glossary) — taxonomy order, residual last
_CRITPATH_ROWS = (("input_wait", "input"), ("host_dispatch", "dispatch"),
                  ("grant_wait", "grant"),
                  ("pull_comm", "pull"), ("compute", "compute"),
                  ("push_comm", "push"), ("probe", "probe"),
                  ("bookkeeping", "bookkeep"),
                  ("barrier_wait", "barrier"), ("residual", "residual"))
_CRITPATH_BAR = 30


def _render_critpath(budget: dict) -> "List[str]":
    """One-screen per-tenant step-phase waterfall from a single STATUS
    scrape (docs/OBSERVABILITY.md §9 has the glossary): per tenant a
    classification header, one bar per phase (percent of window wall —
    phases + residual sum to ~100% by the budget invariant), and the
    per-epoch critical path (which worker and phase gated the epoch
    barrier — the straggler report says who, this says why)."""
    if not budget:
        return ["(no phase budget recorded — no worker fed the "
                "budget store in the window)"]
    out: List[str] = []
    for job in sorted(budget,
                      key=lambda j: -(budget[j].get("wall_sec") or 0.0)):
        row = budget[job]
        fr = row.get("fractions") or {}
        ph = row.get("phases") or {}
        strag = row.get("straggler_ratio")
        out.append(
            f"{job} [{row.get('attempt', job)}]  "
            f"{row.get('classification', '?')}  "
            f"wall {row.get('wall_sec', 0.0):.2f}s over "
            f"{row.get('epochs', 0)} epoch(s), "
            f"{len(row.get('per_worker') or {})} worker(s)"
            + (f", straggler x{strag:.2f}" if strag is not None else ""))
        for phase, label in _CRITPATH_ROWS:
            f = float(fr.get(phase, 0.0))
            bar = "#" * max(int(round(f * _CRITPATH_BAR)),
                            1 if f > 0 else 0)
            out.append(f"  {label:9s} {100.0 * f:5.1f}% "
                       f"{ph.get(phase, 0.0):8.3f}s  {bar}")
        cp = row.get("critical_path") or []
        if cp:
            gates = ", ".join(
                f"e{c['epoch']}:{c['worker']}({c['phase']})"
                for c in cp[-6:])
            out.append(f"  critical path: {gates}")
        out.append("")
    if out and not out[-1]:
        out.pop()
    return out


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return "-"  # pragma: no cover - loop always returns


def _render_overload(overload: dict) -> "List[str]":
    """One line of control-plane overload state from STATUS (the
    degradation ladder, jobserver/overload.py). Quiet when healthy:
    nothing at level 0 with no shed history — the common case stays
    one clean tenant table. Anything above normal (or any shed count)
    prints ladder position, the pressure reason, queue fill/lag and
    the per-action shed tallies so an operator sees WHAT fidelity was
    traded before reading the doctor's control_overload card."""
    if not overload:
        return []
    sheds = overload.get("sheds") or {}
    level = int(overload.get("level") or 0)
    if level == 0 and not sheds:
        return []
    q = overload.get("queue_fill")
    lag = overload.get("queue_lag_ms")
    parts = [f"overload: ladder={overload.get('ladder', '?')}"
             f" level={level}"]
    if overload.get("reason"):
        parts.append(f"reason={overload['reason']}")
    if q is not None:
        parts.append(f"queue_fill={float(q):.2f}")
    if lag is not None:
        parts.append(f"lag={float(lag):.0f}ms")
    out = ["  ".join(parts)]
    if sheds:
        out.append("  sheds: " + "  ".join(
            f"{k}={v}" for k, v in sorted(sheds.items())))
    return out


def _render_tenant_top(tenants: dict) -> "List[str]":
    """One-screen per-tenant cost view from a single STATUS scrape
    (docs/OBSERVABILITY.md "Tenant accounting" has the column glossary).
    Unknown-vs-zero is load-bearing: a None (no cost model, no target,
    no peers) renders as '-', never as 0 — 0 is reserved for real
    zeros. Rows sort by windowed device seconds,
    heaviest first (the 'top' semantic)."""
    cols = ("TENANT", "ATTEMPT", "W", "DEV-S", "SPS", "MFU", "HBM",
            "HBM%", "INWAIT%", "SLO", "STRAG")
    rows = [cols]

    def pct(v):
        return f"{100.0 * v:.1f}" if v is not None else "-"

    for r in sorted(tenants.values(),
                    key=lambda r: -(r.get("device_seconds") or 0.0)):
        slo = r.get("slo") or {}
        att = slo.get("attainment")
        slo_cell = "-" if att is None else (
            f"{att:.2f}" + ("!" if slo.get("events") else ""))
        mfu = r.get("mfu")
        strag = r.get("straggler_ratio")
        rows.append((
            str(r.get("job", "?")),
            str(r.get("attempt", "")),
            str(r.get("workers", 0)),
            f"{r.get('device_seconds') or 0.0:.2f}",
            ("-" if r.get("samples_per_sec") is None
             else f"{r['samples_per_sec']:,.0f}"),
            "-" if mfu is None else f"{100.0 * mfu:.2f}%",
            _fmt_bytes(r.get("resident_bytes")),
            pct(r.get("hbm_share")),
            pct(r.get("input_wait_frac")),
            slo_cell,
            "-" if strag is None else f"{strag:.2f}",
        ))
    out = _render_table(rows)
    if len(rows) == 1:
        out.append("(no tenant activity recorded)")
    for job, r in sorted(tenants.items()):
        srv = r.get("serving") or {}
        if not srv.get("enabled"):
            continue
        out.append(
            f"serving {r.get('job', job)}: "
            f"qps {_srv_num(srv.get('qps'), '{:.1f}')}  "
            f"p50 {_srv_num(srv.get('p50_ms'), '{:.1f}ms')}  "
            f"p99 {_srv_num(srv.get('p99_ms'), '{:.1f}ms')}"
            + (f" (slo {srv['slo_p99_ms']:.0f}ms)"
               if srv.get("slo_p99_ms") is not None else "")
            + f"  occupancy {_srv_num(srv.get('batch_occupancy'), '{:.1f}')}"
            f"  cache hit "
            f"{_srv_num(srv.get('cache_hit_rate'), '{:.1%}')}")
    return out


def _srv_num(v, fmt: str) -> str:
    """Serving cells follow the table's unknown-vs-zero contract: an
    unmeasured quantity renders '-', never a fake 0."""
    return "-" if v is None else fmt.format(float(v))


def _cmd_start_jobserver(args: argparse.Namespace) -> int:
    from harmony_tpu.tracing import flight

    flight.install_signal_dump()  # SIGTERM leaves a black box behind
    from harmony_tpu.jobserver import ha as _ha

    if _ha.ha_enabled():
        return _cmd_start_jobserver_ha(args)
    server = _make_server(args.num_executors,
                          dashboard_url=args.dashboard_url,
                          chkp_root=_chkp_root_of(args))
    port = server.serve_tcp(args.port)
    if server.metrics_exporter is not None:
        print(f"metrics at http://0.0.0.0:{server.metrics_exporter.port}"
              "/metrics", flush=True)
    print(f"jobserver ready on port {port}", flush=True)
    try:
        while server.state != "CLOSED":
            import time

            time.sleep(0.5)
    except KeyboardInterrupt:
        server.shutdown()
    return 0


def _cmd_start_jobserver_ha(args: argparse.Namespace) -> int:
    """One HA control-plane replica (docs/DEPLOY.md §HA): stand by on
    the submit port (NOT_LEADER + leader redirect), contend on the
    shared lease, and on winning it replay the durable job log, re-arm
    every in-flight submission, and serve. The server itself is built
    LAZILY at takeover — a standby pays no executors."""
    import os
    import socket as _socket
    import time

    from harmony_tpu.jobserver.ha import HAController
    from harmony_tpu.jobserver.lease import ha_log_dir

    replica = (args.ha_replica_id or os.environ.get("HOSTNAME")
               or _socket.gethostname())

    def factory():
        import jax

        from harmony_tpu.jobserver.server import JobServer
        from harmony_tpu.utils.compcache import enable_compile_cache

        enable_compile_cache()
        return JobServer(num_executors=args.num_executors
                         or len(jax.devices()),
                         dashboard_url=args.dashboard_url,
                         chkp_root=_chkp_root_of(args))

    ctl = HAController(
        factory, log_dir=ha_log_dir(), replica_id=replica,
        submit_port=args.port,
        advertise_addr=args.ha_advertise or f"127.0.0.1:{args.port}",
        recv_port=args.ha_recv_port,
        bind_host=args.ha_bind,
    ).start()
    print(f"HA replica {replica} standing by on port {ctl.port} "
          f"(log dir {ha_log_dir()})", flush=True)
    try:
        while True:
            if ctl.wait_leader(timeout=0.5):
                break
        print(f"HA replica {replica} is LEADER on port {ctl.port} "
              f"(epoch {ctl.lease.epoch}, replay {ctl.replay_ms} ms, "
              f"{len(ctl.rearmed)} submission(s) re-armed)", flush=True)
        while ctl.server is not None and ctl.server.state != "CLOSED":
            time.sleep(0.5)
    except KeyboardInterrupt:
        ctl.stop()
    return 0


def _cmd_start_pod(args: argparse.Namespace) -> int:
    """One pod process (see bin/launch_pod.sh + README 'TPU-pod deploy'):
    joins the jax.distributed runtime, then process 0 becomes the pod
    JobServer (TCP submit + follower control plane) and every other
    process enters the follower loop. The reference's analogue is the
    driver process vs remote evaluator JVM split (JobServerDriver.java:
    149-163)."""
    import os
    import time

    from harmony_tpu.parallel import multihost
    from harmony_tpu.tracing import flight

    flight.install_signal_dump()  # SIGTERM leaves a black box behind
    coordinator = args.coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nprocs = args.num_processes or int(os.environ.get("JAX_NUM_PROCESSES", 0))
    pid = (args.process_id if args.process_id >= 0
           else int(os.environ.get("JAX_PROCESS_ID", -1)))
    if not coordinator or nprocs < 2 or pid < 0:
        print("start-pod needs --coordinator/--num-processes/--process-id "
              "(or the JAX_* env vars); for single-host use start-jobserver",
              file=sys.stderr)
        return 2
    multihost.initialize_distributed(coordinator, nprocs, pid)

    import jax

    from harmony_tpu.utils.compcache import enable_compile_cache

    enable_compile_cache()  # after distributed init: it opens the backend
    n_exec = args.num_executors or len(jax.devices())
    if pid == 0:
        from harmony_tpu.jobserver.pod import PodJobServer

        server = PodJobServer(num_executors=n_exec,
                              num_followers=nprocs - 1,
                              chkp_root=_chkp_root_of(args))
        server.start()
        server.serve_pod(args.pod_port)
        port = server.serve_tcp(args.port)
        print(f"pod jobserver ready: {nprocs} processes, "
              f"{len(jax.devices())} global devices, submit port {port}",
              flush=True)
        try:
            while server.state != "CLOSED":
                time.sleep(0.5)
        except KeyboardInterrupt:
            server.shutdown()
        return 0
    from harmony_tpu.jobserver.pod import PodFollower

    leader_host = coordinator.rsplit(":", 1)[0]
    print(f"pod follower {pid} joining {leader_host}:{args.pod_port}",
          flush=True)
    leader_addrs = None
    if args.pod_leader_addrs:
        leader_addrs = []
        for a in args.pod_leader_addrs.split(","):
            a = a.strip()
            if a:
                host, _, port = a.rpartition(":")
                leader_addrs.append((host or "127.0.0.1", int(port)))
    PodFollower(leader_host, args.pod_port, pid, n_exec,
                leader_addrs=leader_addrs).run()
    return 0


def _cmd_run(args: argparse.Namespace) -> int:
    from harmony_tpu.tracing.span import trace_span

    cfg = build_config(args.app, args)  # validate overrides BEFORE jax spins up
    server = _make_server(args.num_executors)
    try:
        # root span: submit() captures the ambient context, so the whole
        # standalone run shares one trace_id
        with trace_span("cli.run", app=args.app, job_id=cfg.job_id):
            fut = server.submit(cfg)
        result = fut.result()
        print(json.dumps({"job_id": cfg.job_id, "result": _jsonable(result)}))
        return 0
    finally:
        server.shutdown(timeout=60.0)


def _jsonable(obj: Any) -> Any:
    import numpy as np

    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return obj.item()
    return obj


if __name__ == "__main__":
    sys.exit(main())
