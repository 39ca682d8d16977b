"""Metrics dashboard — HTTP + SQLite, dependency-free.

Parity with the reference's dashboard (SURVEY.md §2.6: DashboardConnector
POSTs metrics to a Flask+SQLite web app, jobserver/src/main/resources/
dashboard/dashboard.py, launched by DashboardLauncher.java). Rebuilt on the
stdlib: ``http.server.ThreadingHTTPServer`` + ``sqlite3`` — no Flask in the
image, and the capability is the same: accept metric POSTs, persist them,
serve a per-job view.

Endpoints:
  POST /api/metrics          {"job_id", "kind", "payload": {...}} -> stored
  GET  /api/metrics?job_id=&kind=&limit=   -> JSON rows (newest first)
  GET  /api/jobs             -> JSON job summary (count, last loss, kinds)
  POST /api/spans            {"spans": [span dicts]} -> stored
  GET  /api/trace?trace_id= | ?job_id=     -> spans ordered by start time
  GET  /trace?trace_id=      -> HTML per-trace timeline
  GET  /metrics              -> Prometheus text exposition (this process)
  GET  /                     -> HTML summary table (the web UI)

Hardening (vs the seed): ``limit`` is clamped/rejected instead of riding
raw into SQL, malformed query params get real 400s, and file-backed
databases run in WAL mode with per-request read connections so many
followers POSTing concurrently don't serialize every read behind the
writer's lock.
"""
from __future__ import annotations

import json
import sqlite3
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

_SCHEMA = """
CREATE TABLE IF NOT EXISTS metrics (
    id INTEGER PRIMARY KEY AUTOINCREMENT,
    ts REAL NOT NULL,
    job_id TEXT NOT NULL,
    kind TEXT NOT NULL,
    payload TEXT NOT NULL
);
CREATE INDEX IF NOT EXISTS idx_metrics_job ON metrics (job_id, kind, id);
CREATE TABLE IF NOT EXISTS spans (
    id INTEGER PRIMARY KEY AUTOINCREMENT,
    ts REAL NOT NULL,
    trace_id TEXT NOT NULL,
    span_id TEXT NOT NULL,
    parent_id TEXT,
    job_id TEXT,
    description TEXT NOT NULL,
    start_sec REAL,
    stop_sec REAL,
    process_id TEXT,
    annotations TEXT
);
CREATE INDEX IF NOT EXISTS idx_spans_trace ON spans (trace_id, start_sec);
CREATE INDEX IF NOT EXISTS idx_spans_job ON spans (job_id, id);
"""

#: limit clamp bounds: non-positive and giant values never reach SQL
MAX_QUERY_LIMIT = 1000


class BadRequest(ValueError):
    """Malformed client input — rendered as a 400, never a 500."""


def _clamp_limit(raw: Optional[str], default: int = 100) -> int:
    if raw is None or raw == "":
        return default
    try:
        limit = int(raw)
    except (TypeError, ValueError):
        raise BadRequest(f"limit must be an integer, got {raw!r}")
    return max(1, min(limit, MAX_QUERY_LIMIT))


class DashboardServer:
    """Serve on 127.0.0.1:port (port=0 picks a free one, like the launcher
    probing for a usable port)."""

    def __init__(self, db_path: str = ":memory:", port: int = 0) -> None:
        self._db_path = db_path
        self._file_backed = db_path != ":memory:" and "memory" not in db_path
        self._db = sqlite3.connect(db_path, check_same_thread=False)
        if self._file_backed:
            # WAL: readers proceed during writes, so follower POST storms
            # don't serialize the read API behind the writer's lock (the
            # per-request read connections below are what make this real
            # — one shared connection would still serialize on _db_lock)
            self._db.execute("PRAGMA journal_mode=WAL")
        self._db.executescript(_SCHEMA)
        self._db_lock = threading.Lock()
        handler = self._make_handler()
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
        self._thread: Optional[threading.Thread] = None

    # -- storage ---------------------------------------------------------

    def _read_rows(self, sql: str, args: Tuple = ()) -> List[Tuple]:
        """Run one read query. File-backed: a fresh per-request
        connection (WAL lets it proceed against concurrent writers).
        In-memory: the shared connection under the lock (a second
        :memory: connection would be a different, empty database)."""
        if self._file_backed:
            conn = sqlite3.connect(self._db_path)
            try:
                return conn.execute(sql, args).fetchall()
            finally:
                conn.close()
        with self._db_lock:
            return self._db.execute(sql, args).fetchall()

    def insert(self, job_id: str, kind: str, payload: Dict[str, Any]) -> None:
        with self._db_lock:
            self._db.execute(
                "INSERT INTO metrics (ts, job_id, kind, payload) VALUES (?,?,?,?)",
                (time.time(), job_id, kind, json.dumps(payload)),
            )
            self._db.commit()

    def insert_span(self, span: Dict[str, Any]) -> None:
        """Store one span dict (the Span.to_dict shape). trace_id,
        span_id and description are required; job_id is lifted from the
        annotations so per-job trace queries need no JSON scan."""
        try:
            trace_id = str(span["trace_id"])
            span_id = str(span["span_id"])
            description = str(span["description"])
        except (KeyError, TypeError):
            raise BadRequest(
                "span needs trace_id, span_id and description")
        annotations = span.get("annotations") or {}
        if not isinstance(annotations, dict):
            annotations = {}
        job_id = annotations.get("job_id")
        with self._db_lock:
            self._db.execute(
                "INSERT INTO spans (ts, trace_id, span_id, parent_id, "
                "job_id, description, start_sec, stop_sec, process_id, "
                "annotations) VALUES (?,?,?,?,?,?,?,?,?,?)",
                (
                    time.time(), trace_id, span_id,
                    span.get("parent_id"),
                    str(job_id) if job_id is not None else None,
                    description,
                    span.get("start_sec"), span.get("stop_sec"),
                    span.get("process_id"),
                    json.dumps(annotations, default=repr),
                ),
            )
            self._db.commit()

    def query(
        self, job_id: Optional[str] = None, kind: Optional[str] = None,
        limit: int = 100,
    ) -> List[Dict[str, Any]]:
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        q = "SELECT ts, job_id, kind, payload FROM metrics"
        cond: List[str] = []
        args: List[Any] = []
        if job_id:
            cond.append("job_id = ?")
            args.append(job_id)
        if kind:
            cond.append("kind = ?")
            args.append(kind)
        if cond:
            q += " WHERE " + " AND ".join(cond)
        q += " ORDER BY id DESC LIMIT ?"
        args.append(limit)
        rows = self._read_rows(q, tuple(args))
        return [
            {"ts": ts, "job_id": j, "kind": k, "payload": json.loads(p)}
            for ts, j, k, p in rows
        ]

    def trace(self, trace_id: Optional[str] = None,
              job_id: Optional[str] = None,
              limit: int = MAX_QUERY_LIMIT) -> List[Dict[str, Any]]:
        """Spans of one trace (or one job's traces), ordered by start
        time — the timeline view's source. The job_id variant resolves
        the job's trace ids first and returns those traces WHOLE:
        checkpoint/blockmove spans annotate chkp_id/table rather than
        job_id, and a per-job view that dropped them would show a
        submission with holes in it."""
        if not trace_id and not job_id:
            raise BadRequest("trace query needs trace_id or job_id")
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        if trace_id:
            tids = [trace_id]
        else:
            tids = [r[0] for r in self._read_rows(
                "SELECT DISTINCT trace_id FROM spans WHERE job_id = ? "
                "ORDER BY id DESC LIMIT 8", (job_id,))]
            if not tids:
                return []
        marks = ",".join("?" * len(tids))
        q = ("SELECT trace_id, span_id, parent_id, job_id, description,"
             " start_sec, stop_sec, process_id, annotations FROM spans"
             f" WHERE trace_id IN ({marks}) ORDER BY start_sec LIMIT ?")
        args: Tuple = (*tids, limit)
        out = []
        for row in self._read_rows(q, args):
            (tid, sid, pid_, jid, desc, start, stop, proc, ann) = row
            out.append({
                "trace_id": tid, "span_id": sid, "parent_id": pid_,
                "job_id": jid, "description": desc,
                "start_sec": start, "stop_sec": stop, "process_id": proc,
                "annotations": json.loads(ann) if ann else {},
            })
        return out

    def tenants(self) -> List[Dict[str, Any]]:
        """Newest tenant cost vector per job (the jobserver POSTs ledger
        rows as kind='tenant' at epoch cadence) — the dashboard face of
        ``harmony-tpu obs top``. Rows sort heaviest-first by windowed
        device seconds."""
        q = """
            SELECT m.payload FROM metrics m
            JOIN (SELECT MAX(id) mid FROM metrics WHERE kind = 'tenant'
                  GROUP BY job_id
                 ) c ON m.id = c.mid
        """
        rows = [json.loads(r[0]) for r in self._read_rows(q)]
        rows.sort(key=lambda r: -(r.get("device_seconds") or 0.0))
        return rows

    #: ledger fields /api/history will serve as a series — a strict
    #: allowlist, so a query param never rides into payload lookups
    #: with surprising types (every one is numeric-or-None in the row)
    HISTORY_FIELDS = ("samples_per_sec", "mfu", "input_wait_frac",
                      "device_seconds", "resident_bytes", "hbm_share")

    def history(self, job_id: Optional[str],
                field: str = "samples_per_sec",
                limit: int = 200) -> Dict[str, Any]:
        """Time series for one job from the stored kind='tenant' rows
        (the jobserver posts the ledger at epoch cadence — the rows ARE
        the history), plus the job's kind='diagnosis' rows so the panel
        can overlay verdicts. Without a job_id: the jobs that have any
        history. ``field`` picks the ledger column (HISTORY_FIELDS)."""
        if job_id is None:
            rows = self._read_rows(
                "SELECT DISTINCT job_id FROM metrics "
                "WHERE kind IN ('tenant', 'diagnosis') ORDER BY job_id")
            return {"jobs": [r[0] for r in rows],
                    "fields": list(self.HISTORY_FIELDS)}
        if field not in self.HISTORY_FIELDS:
            raise BadRequest(
                f"field must be one of {'/'.join(self.HISTORY_FIELDS)}")
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        rows = self._read_rows(
            "SELECT ts, payload FROM metrics WHERE kind = 'tenant' "
            "AND job_id = ? ORDER BY id DESC LIMIT ?", (job_id, limit))
        points: List[List[float]] = []
        for ts, payload in reversed(rows):  # oldest first for rendering
            v = json.loads(payload).get(field)
            if isinstance(v, (int, float)):
                points.append([ts, float(v)])
        diags = [json.loads(r[1]) for r in reversed(self._read_rows(
            "SELECT ts, payload FROM metrics WHERE kind = 'diagnosis' "
            "AND job_id = ? ORDER BY id DESC LIMIT 32", (job_id,)))]
        return {"job_id": job_id, "field": field, "points": points,
                "diagnoses": diags}

    def policy_rows(self, job_id: Optional[str] = None,
                    limit: int = 64) -> Dict[str, Any]:
        """Device-policy actions the jobserver posted (kind='policy'
        rows, jobserver/policy.py's dashboard tee) — for one tenant or
        across the cluster, newest last. The operator's 'what did the
        autoscaler do and why' trail beside the diagnosis history."""
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        if job_id is None:
            rows = self._read_rows(
                "SELECT ts, job_id, payload FROM metrics "
                "WHERE kind = 'policy' ORDER BY id DESC LIMIT ?",
                (limit,))
        else:
            rows = self._read_rows(
                "SELECT ts, job_id, payload FROM metrics "
                "WHERE kind = 'policy' AND job_id = ? "
                "ORDER BY id DESC LIMIT ?", (job_id, limit))
        actions = []
        for ts, jid, payload in reversed(rows):  # oldest first
            try:
                p = json.loads(payload)
            except ValueError:
                continue  # one malformed POSTed row must not 400 the rest
            actions.append({"ts": ts, "job_id": jid, **p})
        return {"job_id": job_id, "actions": actions}

    def incident_rows(self, job_id: Optional[str] = None,
                      limit: int = 64) -> Dict[str, Any]:
        """Incident lifecycle transitions the jobserver posted
        (kind='incident' rows, metrics/incidents.py's dashboard tee),
        deduplicated to the NEWEST transition per incident_id, oldest
        first — the operator's causal fault→diagnosis→action→resolution
        trail (docs/OBSERVABILITY.md §10)."""
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        if job_id is None:
            rows = self._read_rows(
                "SELECT ts, job_id, payload FROM metrics "
                "WHERE kind = 'incident' ORDER BY id DESC LIMIT ?",
                (limit * 4,))
        else:
            rows = self._read_rows(
                "SELECT ts, job_id, payload FROM metrics "
                "WHERE kind = 'incident' AND job_id = ? "
                "ORDER BY id DESC LIMIT ?", (job_id, limit * 4))
        newest: Dict[str, Dict[str, Any]] = {}
        for ts, jid, payload in rows:  # newest first: first one wins
            try:
                p = json.loads(payload)
            except ValueError:
                continue  # one malformed POSTed row must not 400 the rest
            iid = p.get("incident_id")
            if not iid or iid in newest:
                continue
            newest[iid] = {"ts": ts, "job_id": jid, **p}
        incidents = sorted(newest.values(),
                           key=lambda p: p.get("opened_ts") or 0)[-limit:]
        return {"job_id": job_id, "incidents": incidents}

    def critpath_rows(self, job_id: str,
                      limit: int = 64) -> List[Dict[str, Any]]:
        """One job's step-phase budget history from the stored
        kind='tenant' rows (the jobserver posts the ledger — now
        carrying each tenant's phase fractions + bound classification —
        at epoch cadence). Oldest first; rows without a budget (the
        tenant predates the phase plane, or no worker fed it) are
        skipped rather than rendered as zeros."""
        limit = max(1, min(int(limit), MAX_QUERY_LIMIT))
        rows = self._read_rows(
            "SELECT ts, payload FROM metrics WHERE kind = 'tenant' "
            "AND job_id = ? ORDER BY id DESC LIMIT ?", (job_id, limit))
        out: List[Dict[str, Any]] = []
        for ts, payload in reversed(rows):
            p = json.loads(payload)
            phases = p.get("phases")
            if not isinstance(phases, dict):
                continue
            out.append({"ts": ts,
                        "phases": {str(k): v for k, v in phases.items()
                                   if isinstance(v, (int, float))},
                        "classification": p.get("phase_class")})
        return out

    def jobs(self) -> List[Dict[str, Any]]:
        # One aggregate query; last_loss = the newest report whose payload
        # has a top-level "loss" key (json_extract, not substring match —
        # '{"stage": "loss"}' must not shadow a real loss report).
        q = """
            SELECT m.job_id, m.payload FROM metrics m
            JOIN (SELECT MAX(id) max_loss_id
                  FROM metrics
                  WHERE json_extract(payload, '$.loss') IS NOT NULL
                  GROUP BY job_id
                 ) c ON m.id = c.max_loss_id
        """
        # Recovery observability (elastic shrink/re-grow, confinement,
        # auto-resume): events POST as kind='recovery'; the summary
        # carries their count and the newest event so a degraded tenant
        # is visible at a glance, not only in leader logs.
        q_rec = """
            SELECT m.job_id, c.n, m.payload FROM metrics m
            JOIN (SELECT MAX(id) max_rec_id, COUNT(*) n
                  FROM metrics WHERE kind = 'recovery'
                  GROUP BY job_id
                 ) c ON m.id = c.max_rec_id
        """
        loss_rows = self._read_rows(q)
        rec_rows = self._read_rows(q_rec)
        all_rows = self._read_rows(
            "SELECT job_id, COUNT(*), MAX(ts) FROM metrics GROUP BY job_id"
        )
        # the NEWEST span row's trace per job (MAX(id), not
        # MAX(trace_id) — trace ids are random hex, and the
        # lexicographic max would link a stale trace after a resubmit)
        trace_rows = self._read_rows(
            """
            SELECT s.job_id, s.trace_id FROM spans s
            JOIN (SELECT MAX(id) mid FROM spans
                  WHERE job_id IS NOT NULL GROUP BY job_id
                 ) m ON s.id = m.mid
            """
        )
        loss_by_job = {r[0]: json.loads(r[1]).get("loss") for r in loss_rows}
        rec_by_job = {
            r[0]: {"recoveries": r[1],
                   "last_recovery": json.loads(r[2]).get("kind")}
            for r in rec_rows
        }
        trace_by_job = {r[0]: r[1] for r in trace_rows}
        return [
            {"job_id": job_id, "num_reports": count, "last_ts": last_ts,
             "last_loss": loss_by_job.get(job_id),
             "recoveries": rec_by_job.get(job_id, {}).get("recoveries", 0),
             "last_recovery": rec_by_job.get(job_id, {}).get("last_recovery"),
             "trace_id": trace_by_job.get(job_id)}
            for job_id, count, last_ts in all_rows
        ]

    # -- http ------------------------------------------------------------

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def start(self) -> "DashboardServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="dashboard-http", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=2)
        with self._db_lock:
            self._db.close()

    @staticmethod
    def _trace_html(spans: List[Dict[str, Any]]) -> str:
        """Minimal per-trace timeline: one row per span, offset/duration
        bars scaled to the trace's wall span, depth from parent links.
        Every span-sourced string is HTML-escaped — span descriptions
        and annotations are client-POSTed data."""
        import html as _html

        from harmony_tpu.tracing.timeline import timeline_rows

        rows_data = timeline_rows(spans)
        if not rows_data:
            return ("<html><body><h1>trace</h1>"
                    "<p>no spans</p></body></html>")
        wall = rows_data[0]["wall_sec"]
        rows = []
        for r in rows_data:
            s, dur = r["span"], r["duration_sec"]
            left = 100.0 * r["offset_sec"] / wall
            width = max(100.0 * dur / wall, 0.3)
            pad = "&nbsp;" * (2 * r["depth"])
            ann = ", ".join(
                f"{_html.escape(str(k))}={_html.escape(str(v))}"
                for k, v in sorted((s.get("annotations") or {}).items()))
            rows.append(
                f"<tr><td>{pad}{_html.escape(str(s['description']))}</td>"
                f"<td>{_html.escape(str(s.get('process_id') or ''))}</td>"
                f"<td>{dur * 1000:.1f}ms</td>"
                f"<td><div style='margin-left:{left:.1f}%;"
                f"width:{width:.1f}%;background:#46f;height:10px'></div>"
                f"</td><td><small>{ann}</small></td></tr>"
            )
        tid = _html.escape(str(spans[0]["trace_id"]))
        return (
            f"<html><head><title>trace {tid}</title></head><body>"
            f"<h1>trace {tid}</h1>"
            f"<p>{len(spans)} span(s), {wall:.3f}s wall</p>"
            "<table border=0 width='100%'>"
            "<tr><th align=left>span</th><th>process</th><th>dur</th>"
            "<th width='50%'>timeline</th><th>annotations</th></tr>"
            + "".join(rows) + "</table></body></html>"
        )

    @staticmethod
    def _history_html(data: Dict[str, Any]) -> str:
        """Sparkline + diagnosis-timeline panel for one job: the series
        as an inline SVG polyline, the diagnoses laid out with the same
        :func:`~harmony_tpu.tracing.timeline.timeline_rows` shaping the
        trace views use (a diagnosis window IS a span: start, stop,
        description). Every rendered string is HTML-escaped — payloads
        are client-POSTed data."""
        import html as _html

        from harmony_tpu.tracing.timeline import timeline_rows

        job = _html.escape(str(data.get("job_id", "?")))
        field = _html.escape(str(data.get("field", "")))
        points = data.get("points") or []
        parts = [f"<html><head><title>history {job}</title></head><body>",
                 f"<h1>history: {job}</h1>"]
        if points:
            ts = [p[0] for p in points]
            vs = [p[1] for p in points]
            t0, t1 = min(ts), max(ts)
            lo, hi = min(vs), max(vs)
            tspan = max(t1 - t0, 1e-9)
            vspan = max(hi - lo, 1e-9)
            w, h = 600, 80
            pts = " ".join(
                f"{(t - t0) / tspan * w:.1f},"
                f"{h - (v - lo) / vspan * h:.1f}"
                for t, v in points)
            parts.append(
                f"<p>{field}: {len(points)} points, "
                f"min {lo:.4g}, max {hi:.4g}</p>"
                f"<svg width='{w}' height='{h + 4}' "
                "style='border:1px solid #ccc'>"
                f"<polyline points='{pts}' fill='none' "
                "stroke='#46f' stroke-width='1.5'/></svg>")
        else:
            parts.append(f"<p>no {field} history recorded</p>")
        def num(v):
            # diagnosis rows are client-POSTed data: a non-numeric
            # window value must degrade to None (timeline_rows handles
            # that) rather than TypeError the whole panel
            return float(v) if isinstance(v, (int, float)) else None

        diags = data.get("diagnoses") or []
        spans = []
        for i, d in enumerate(diags):
            win = d.get("window")
            if not (isinstance(win, (list, tuple)) and len(win) == 2):
                win = [d.get("ts"), d.get("ts")]
            spans.append({
                "trace_id": "doctor", "span_id": str(i),
                "description": f"{d.get('rule', '?')}: "
                               f"{d.get('summary', '')}",
                "start_sec": num(win[0]), "stop_sec": num(win[1]),
            })
        rows_data = timeline_rows(spans)
        if rows_data:
            wall = rows_data[0]["wall_sec"]
            parts.append("<h2>diagnoses</h2>"
                         "<table border=0 width='100%'>"
                         "<tr><th align=left>verdict</th>"
                         "<th width='50%'>window</th></tr>")
            for r in rows_data:
                s, dur = r["span"], r["duration_sec"]
                left = 100.0 * r["offset_sec"] / wall
                width = max(100.0 * dur / wall, 0.3)
                parts.append(
                    f"<tr><td>{_html.escape(str(s['description']))}</td>"
                    f"<td><div style='margin-left:{left:.1f}%;"
                    f"width:{width:.1f}%;background:#e55;height:10px'>"
                    "</div></td></tr>")
            parts.append("</table>")
        else:
            parts.append("<p>no diagnoses recorded</p>")
        parts.append("</body></html>")
        return "".join(parts)

    #: stacked-bar colors per phase (taxonomy order; residual grey —
    #: the explicitly-unattributed share must LOOK unattributed)
    _PHASE_COLORS = (("input_wait", "#fa0"), ("host_dispatch", "#a6f"),
                     ("grant_wait", "#c4a"),
                     ("pull_comm", "#46f"), ("compute", "#4a4"),
                     ("push_comm", "#28c"), ("probe", "#8bd"),
                     ("bookkeeping", "#a85"), ("barrier_wait", "#e55"),
                     ("residual", "#bbb"))

    @staticmethod
    def _incidents_html(data: Dict[str, Any]) -> str:
        """Incident panel (docs/OBSERVABILITY.md §10): one block per
        incident — header with lifecycle status and MTTD/MTTR, then the
        causal evidence chain as an offset timeline shaped through
        tracing/timeline.py. Every payload string is HTML-escaped
        (incident rows are client-POSTed data); unknown latencies
        render '-', never 0."""
        import html as _html

        from harmony_tpu.tracing.timeline import timeline_rows

        incidents = data.get("incidents") or []
        head = ("<html><head><title>incidents</title></head><body>"
                "<h1>incidents</h1>")
        if not incidents:
            return head + "<p>no incidents posted</p></body></html>"

        def sec(v):
            return "-" if not isinstance(v, (int, float)) else f"{v:.3f}s"

        colors = {"trigger": "#d33", "diagnosis": "#d90",
                  "action": "#46f", "resolution": "#2a2"}
        blocks = []
        for inc in incidents:
            chain = [e for e in (inc.get("chain") or [])
                     if isinstance(e, dict)]
            spans = [{"span_id": i + 1, "parent_id": None,
                      "description": str(e.get("summary")
                                         or e.get("kind") or "?"),
                      "start_sec": e.get("ts"), "stop_sec": e.get("ts"),
                      "edge": e}
                     for i, e in enumerate(chain)]
            rows = []
            for r in timeline_rows(spans):
                e = r["span"]["edge"]
                left = min(99.0, 100.0 * r["offset_sec"] / r["wall_sec"])
                color = colors.get(str(e.get("role")), "#888")
                rows.append(
                    f"<tr><td>{_html.escape(str(e.get('role') or '?'))}"
                    f"</td><td>+{r['offset_sec']:.3f}s</td>"
                    f"<td>{_html.escape(r['span']['description'])}</td>"
                    f"<td><div style='margin-left:{left:.1f}%;width:6px;"
                    f"background:{color};height:10px'></div></td></tr>")
            verdict = inc.get("verdict")
            title = (f"{inc.get('incident_id', '?')} "
                     f"[{inc.get('status', '?')}"
                     + (f"/{verdict}" if verdict else "") + "]")
            blocks.append(
                f"<h3>{_html.escape(str(title))}</h3>"
                f"<p>subject {_html.escape(str(inc.get('subject', '?')))}"
                f" &middot; mttd {sec(inc.get('mttd_sec'))}"
                f" &middot; mitigate {sec(inc.get('mitigate_sec'))}"
                f" &middot; mttr {sec(inc.get('mttr_sec'))}</p>"
                "<table border=0 width='100%'>"
                "<tr><th align=left>role</th><th align=left>offset</th>"
                "<th align=left>evidence</th><th width='40%'>timeline"
                "</th></tr>" + "".join(rows) + "</table>")
        return (head + f"<p>{len(incidents)} incident(s)</p>"
                + "".join(blocks) + "</body></html>")

    @classmethod
    def _critpath_html(cls, job_id: str,
                       rows: List[Dict[str, Any]]) -> str:
        """Stacked-phase timeline panel for one job: each stored budget
        sample renders as one 100%-wide stacked bar (phases + residual
        sum to the wall by the budget invariant), shaped through the
        same :func:`~harmony_tpu.tracing.timeline.timeline_rows` helper
        the trace views use — a phase segment IS a span (start =
        cumulative fraction, stop = start + fraction). Every rendered
        string is HTML-escaped — payloads are client-POSTed data."""
        import html as _html

        from harmony_tpu.tracing.timeline import timeline_rows

        job = _html.escape(str(job_id))
        parts = [f"<html><head><title>critpath {job}</title></head>"
                 f"<body><h1>step-phase budget: {job}</h1>"]
        legend = " ".join(
            f"<span style='background:{c};padding:0 6px'>&nbsp;</span>"
            f"{_html.escape(p)}"
            for p, c in cls._PHASE_COLORS)
        parts.append(f"<p>{legend}</p>")
        if not rows:
            parts.append("<p>no phase budget recorded for this job</p>"
                         "</body></html>")
            return "".join(parts)
        parts.append("<table border=0 width='100%'>"
                     "<tr><th align=left>when</th><th align=left>"
                     "class</th><th width='70%'>phases</th></tr>")
        for i, row in enumerate(rows):
            spans = []
            cum = 0.0
            for phase, _c in cls._PHASE_COLORS:
                f = row["phases"].get(phase)
                if not isinstance(f, (int, float)) or f <= 0:
                    continue
                spans.append({"trace_id": "critpath",
                              "span_id": f"{i}:{phase}",
                              "description": phase,
                              "start_sec": cum, "stop_sec": cum + f})
                cum += f
            shaped = timeline_rows(spans)
            wall = shaped[0]["wall_sec"] if shaped else 1.0
            colors = dict(cls._PHASE_COLORS)
            segs = "".join(
                f"<div title='{_html.escape(r['span']['description'])}"
                f" {100.0 * r['duration_sec'] / wall:.1f}%' "
                f"style='display:inline-block;height:12px;"
                f"width:{100.0 * r['duration_sec'] / wall:.2f}%;"
                f"background:"
                f"{colors.get(r['span']['description'], '#bbb')}'>"
                "</div>"
                for r in shaped)
            when = time.strftime("%H:%M:%S",
                                 time.localtime(row.get("ts", 0)))
            cls_name = _html.escape(str(row.get("classification") or "-"))
            parts.append(
                f"<tr><td>{when}</td><td>{cls_name}</td>"
                f"<td><div style='width:100%;background:#eee'>{segs}"
                "</div></td></tr>")
        parts.append("</table></body></html>")
        return "".join(parts)

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _json(self, code: int, obj: Any) -> None:
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _html(self, body: bytes,
                      content_type: str = "text/html") -> None:
                self.send_response(200)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self) -> None:
                path = urlparse(self.path).path
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    msg = json.loads(self.rfile.read(n))
                    if path == "/api/metrics":
                        server.insert(
                            str(msg["job_id"]), str(msg["kind"]),
                            dict(msg["payload"]),
                        )
                        self._json(200, {"ok": True})
                    elif path == "/api/spans":
                        spans = (msg.get("spans")
                                 if isinstance(msg, dict) and "spans" in msg
                                 else [msg])
                        if not isinstance(spans, list):
                            raise BadRequest("spans must be a list")
                        for s in spans:
                            server.insert_span(dict(s))
                        self._json(200, {"ok": True, "stored": len(spans)})
                    else:
                        self._json(404, {"error": "not found"})
                except Exception as e:  # bad payloads must not kill the server
                    self._json(400, {"error": str(e)})

            def do_GET(self) -> None:
                parsed = urlparse(self.path)
                qs = parse_qs(parsed.query)

                def one(key: str) -> Optional[str]:
                    return qs.get(key, [None])[0]

                if parsed.path == "/api/metrics":
                    try:  # malformed queries are a 400, never a dead conn
                        result = server.query(
                            job_id=one("job_id"),
                            kind=one("kind"),
                            limit=_clamp_limit(one("limit")),
                        )
                    except BadRequest as e:
                        self._json(400, {"error": str(e)})
                        return
                    except Exception as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, result)
                elif parsed.path == "/api/trace":
                    try:
                        result = server.trace(
                            trace_id=one("trace_id"),
                            job_id=one("job_id"),
                            limit=_clamp_limit(one("limit"),
                                               default=MAX_QUERY_LIMIT),
                        )
                    except BadRequest as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, result)
                elif parsed.path == "/trace":
                    try:
                        spans = server.trace(trace_id=one("trace_id"),
                                             job_id=one("job_id"))
                    except BadRequest as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._html(server._trace_html(spans).encode())
                elif parsed.path == "/api/history":
                    try:
                        result = server.history(
                            job_id=one("job_id"),
                            field=one("field") or "samples_per_sec",
                            limit=_clamp_limit(one("limit"), default=200),
                        )
                    except BadRequest as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, result)
                elif parsed.path == "/history":
                    jid = one("job_id")
                    if not jid:
                        self._json(400,
                                   {"error": "history needs job_id"})
                        return
                    try:
                        data = server.history(
                            job_id=jid,
                            field=one("field") or "samples_per_sec")
                        body = server._history_html(data).encode()
                    except BadRequest as e:
                        self._json(400, {"error": str(e)})
                        return
                    except Exception as e:
                        # stored rows are client-POSTed data: one
                        # malformed row must render a 400, never drop
                        # the connection for every future panel view
                        self._json(400, {"error": str(e)})
                        return
                    self._html(body)
                elif parsed.path == "/api/critpath":
                    jid = one("job_id")
                    if not jid:
                        self._json(400,
                                   {"error": "critpath needs job_id"})
                        return
                    try:
                        result = server.critpath_rows(
                            jid, limit=_clamp_limit(one("limit"),
                                                    default=64))
                    except Exception as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, {"job_id": jid, "rows": result})
                elif parsed.path == "/critpath":
                    jid = one("job_id")
                    if not jid:
                        self._json(400,
                                   {"error": "critpath needs job_id"})
                        return
                    try:
                        rows = server.critpath_rows(jid)
                        body = server._critpath_html(jid, rows).encode()
                    except Exception as e:
                        # stored rows are client-POSTed data: one
                        # malformed row must render a 400, never drop
                        # the connection for every future panel view
                        self._json(400, {"error": str(e)})
                        return
                    self._html(body)
                elif parsed.path == "/metrics":
                    from harmony_tpu.metrics.registry import get_registry

                    self._html(
                        get_registry().expose().encode(),
                        content_type=(
                            "text/plain; version=0.0.4; charset=utf-8"),
                    )
                elif parsed.path == "/api/policy":
                    try:
                        result = server.policy_rows(
                            job_id=one("job_id"),
                            limit=_clamp_limit(one("limit"), default=64))
                    except Exception as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, result)
                elif parsed.path == "/api/incidents":
                    try:
                        result = server.incident_rows(
                            job_id=one("job_id"),
                            limit=_clamp_limit(one("limit"), default=64))
                    except Exception as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._json(200, result)
                elif parsed.path == "/incidents":
                    try:
                        result = server.incident_rows(
                            job_id=one("job_id"),
                            limit=_clamp_limit(one("limit"), default=64))
                    except Exception as e:
                        self._json(400, {"error": str(e)})
                        return
                    self._html(server._incidents_html(result).encode())
                elif parsed.path == "/api/jobs":
                    self._json(200, server.jobs())
                elif parsed.path == "/api/tenants":
                    self._json(200, server.tenants())
                elif parsed.path == "/":
                    import html as _h
                    from urllib.parse import quote as _q

                    def cell(v, fmt="{}"):
                        # None is "unknown", rendered as a dash — never
                        # as a zero (the ledger's explicit-None contract)
                        return "-" if v is None else fmt.format(v)

                    tenant_rows = "".join(
                        # job cell links to the history panel (sparkline
                        # + diagnosis timeline) for that tenant
                        f"<tr><td><a href='/history?job_id="
                        f"{_q(str(t.get('job', '?')))}'>"
                        f"{_h.escape(str(t.get('job', '?')))}</a></td>"
                        f"<td>{_h.escape(str(t.get('attempt', '')))}</td>"
                        f"<td>{cell(t.get('device_seconds'), '{:.2f}')}</td>"
                        f"<td>{cell(t.get('samples_per_sec'), '{:,.0f}')}</td>"
                        + "<td>"
                        + ("-" if t.get("mfu") is None
                           else f"{100.0 * t['mfu']:.2f}%")
                        + "</td>"
                        f"<td>{cell(t.get('resident_bytes'))}</td>"
                        + "<td>"
                        + ("-" if t.get("hbm_share") is None
                           else f"{100.0 * t['hbm_share']:.1f}%")
                        + "</td>"
                        + "<td>"
                        + ("-" if t.get("input_wait_frac") is None
                           else f"{100.0 * t['input_wait_frac']:.1f}%")
                        + "</td>"
                        + "<td>"
                        + ("-" if (t.get("slo") or {}).get(
                            "attainment") is None
                           else f"{t['slo']['attainment']:.2f}"
                           + ("!" if t["slo"].get("events") else ""))
                        + "</td>"
                        # step-phase bound verdict, linked to the
                        # stacked-phase /critpath panel for the tenant
                        + "<td>"
                        + (f"<a href='/critpath?job_id="
                           f"{_q(str(t.get('job', '?')))}'>"
                           f"{_h.escape(str(t['phase_class']))}</a>"
                           if t.get("phase_class") else "-")
                        + "</td></tr>"
                        for t in server.tenants()
                    )
                    tenants_html = (
                        "<h2>tenants</h2><table border=1>"
                        "<tr><th>job</th><th>attempt</th><th>dev-s</th>"
                        "<th>sps</th><th>MFU</th><th>HBM bytes</th>"
                        "<th>HBM%</th><th>in-wait%</th><th>SLO</th>"
                        "<th>phase</th></tr>"
                        f"{tenant_rows}</table>"
                    ) if tenant_rows else ""

                    rows = "".join(
                        f"<tr><td>{_h.escape(str(j['job_id']))}</td>"
                        f"<td>{j['num_reports']}</td>"
                        f"<td>{_h.escape(str(j['last_loss']))}</td>"
                        f"<td>{j['recoveries'] or ''}"
                        f"{(' (' + _h.escape(str(j['last_recovery'])) + ')') if j['last_recovery'] else ''}"
                        "</td><td>"
                        + (f"<a href='/trace?trace_id="
                           f"{_q(str(j['trace_id']))}'>"
                           f"{_h.escape(str(j['trace_id']))}</a>"
                           if j.get("trace_id") else "")
                        + "</td></tr>"
                        for j in server.jobs()
                    )
                    body = (
                        "<html><head><title>harmony_tpu dashboard</title></head>"
                        "<body><h1>harmony_tpu jobs</h1>"
                        "<table border=1><tr><th>job</th><th>reports</th>"
                        f"<th>last loss</th><th>recoveries</th>"
                        f"<th>trace</th></tr>{rows}"
                        f"</table>{tenants_html}</body></html>"
                    ).encode()
                    self._html(body)
                else:
                    self._json(404, {"error": "not found"})

        return Handler
