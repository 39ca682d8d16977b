"""harmonylint framework: file walker, finding type, pragmas, baseline.

Pure stdlib (``ast`` + ``re`` + ``json``) — the ``harmony-tpu lint``
subcommand rides the thin non-jax CLI path, so nothing in this module
(or any pass) may import jax or any harmony_tpu runtime module at
import time.

Vocabulary:

* A :class:`Pass` inspects a :class:`CodebaseIndex` (parsed sources +
  the doc/deploy artifacts consistency passes compare against) and
  yields :class:`Finding`\\ s anchored at ``file:line`` with a fix hint.
* An inline pragma ``# lint: allow(<pass>) <reason>`` on the finding
  line — or on a comment line directly above it — suppresses that
  pass's findings there. The reason is MANDATORY: a bare allow is
  itself reported (``pragma-hygiene``), because an unjustified
  suppression is exactly the drift this suite exists to stop.
* A baseline file (:func:`load_baseline` / :func:`save_baseline`)
  suppresses a known set of findings by line-independent key, for
  adopting a pass over a tree that has not been cleaned yet. The
  in-repo tree carries NO baseline — tier-1 runs the suite green.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import time
import tokenize
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# repo layout anchors, derived from this file's location
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(_PKG_DIR)

PRAGMA_RE = re.compile(
    r"#\s*lint:\s*allow\(\s*([a-z0-9_, -]+?)\s*\)\s*(.*)$")


@dataclasses.dataclass
class Finding:
    """One rule violation, anchored and actionable."""

    pass_name: str
    file: str          #: repo-relative path, '/'-separated
    line: int
    message: str
    hint: str = ""     #: how to fix it (or where the convention lives)
    col: int = 0
    #: set by the framework when a pragma/baseline suppressed it
    suppressed_by: Optional[str] = None  # "pragma" | "baseline"
    pragma_reason: str = ""

    def key(self) -> str:
        """Line-independent identity used by baselines (lines drift on
        unrelated edits; pass+file+message does not)."""
        return f"{self.pass_name}::{self.file}::{self.message}"

    def format(self) -> str:
        s = f"{self.file}:{self.line}: [{self.pass_name}] {self.message}"
        if self.hint:
            s += f"\n    fix: {self.hint}"
        return s

    def to_json(self) -> Dict[str, Any]:
        return {
            "pass": self.pass_name,
            "file": self.file,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "hint": self.hint,
            "suppressed_by": self.suppressed_by,
            "pragma_reason": self.pragma_reason or None,
        }


class SourceFile:
    """One parsed python file: source text, AST (None on syntax error —
    reported as a framework finding), and the pragma map."""

    def __init__(self, path: str, rel: str) -> None:
        self.path = path
        self.rel = rel
        # errors="replace": one stray non-UTF-8 byte must degrade into a
        # per-file parse finding, not kill the whole run
        with open(path, encoding="utf-8", errors="replace") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[str] = None
        self.parse_error_line: int = 1
        try:
            self.tree = ast.parse(self.text, filename=path)
        except SyntaxError as e:
            self.parse_error = str(e.msg)
            self.parse_error_line = int(e.lineno or 1)
        except ValueError as e:  # e.g. null bytes from the replace above
            self.parse_error = str(e)
        #: line -> [(frozenset(pass names) | {"*"}, reason)]
        self.pragmas: Dict[int, List[Tuple[frozenset, str]]] = {}
        self._scan_pragmas()

    def _scan_pragmas(self) -> None:
        # tokenize, not regex-over-lines: '# lint: allow' inside a string
        # literal must not become a pragma
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.text).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = PRAGMA_RE.search(tok.string)
                if not m:
                    continue
                passes = frozenset(
                    p.strip() for p in m.group(1).split(",") if p.strip())
                self.pragmas.setdefault(tok.start[0], []).append(
                    (passes, m.group(2).strip()))
        except (tokenize.TokenError, SyntaxError):
            # tokenize raises IndentationError (a SyntaxError) on bad
            # dedents too; the parse-error finding covers this file
            pass

    def pragma_for(self, line: int, pass_name: str) -> Optional[Tuple[str, bool]]:
        """Returns (reason, valid) when an allow(<pass>) pragma covers
        ``line``: same line, or a run of comment-only lines directly
        above it. ``valid`` is False when the reason is empty."""
        candidates = list(self.pragmas.get(line, ()))
        lno = line - 1
        while lno >= 1 and lno <= len(self.lines):
            stripped = self.lines[lno - 1].strip()
            if not stripped.startswith("#"):
                break
            candidates.extend(self.pragmas.get(lno, ()))
            lno -= 1
        for passes, reason in candidates:
            if pass_name in passes or "*" in passes:
                return reason, bool(reason)
        return None


def _dotted_name(node: ast.AST) -> str:
    """'os.environ.get' for the func of a Call (best effort, '' when the
    expression is not a plain name/attribute chain)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def is_jit_call(node: ast.AST) -> bool:
    """``jax.jit`` / ``pjit`` (Name or Attribute form) — the ONE
    definition of "a jit wrapper" shared by jit-hygiene and
    use-after-donate, so the two passes can never disagree about which
    wrappers exist."""
    if isinstance(node, ast.Attribute):
        return node.attr in ("jit", "pjit")
    if isinstance(node, ast.Name):
        return node.id in ("jit", "pjit")
    return False


def _find_repo_root(start: str) -> str:
    """Walk up from ``start`` (inclusive — ``lint <repo root>`` must
    resolve to the repo root itself, not its parent) to the nearest dir
    holding pyproject.toml or docs/ — linting ``harmony_tpu/jobserver``
    must still find the real repo's doc/deploy artifacts, not look for
    docs under ``harmony_tpu/``. Falls back to dirname(start)."""
    d = start
    while True:
        if (os.path.isfile(os.path.join(d, "pyproject.toml"))
                or os.path.isdir(os.path.join(d, "docs"))):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return os.path.dirname(start)
        d = parent


class CodebaseIndex:
    """Parsed view of the tree a lint run inspects.

    ``root``: the package directory whose ``**/*.py`` are scanned.
    ``repo_root``: where ``docs/`` and ``deploy/gke/`` live — the
    consistency passes (fault-site-registry, knob-consistency) compare
    code against these artifacts. Fixture trees in tests point both at
    a miniature layout with the same shape.
    """

    def __init__(
        self,
        root: Optional[str] = None,
        repo_root: Optional[str] = None,
        files: Optional[Sequence[str]] = None,
        exclude: Optional[Sequence[str]] = None,
    ) -> None:
        self.root = os.path.abspath(root or _PKG_DIR)
        self.repo_root = os.path.abspath(
            repo_root or _find_repo_root(self.root))
        self.exclude = [e.strip("/") for e in (exclude or ())]
        self.files: List[SourceFile] = []
        #: partial runs see only a slice of the tree — explicit files, a
        #: subpackage dir below the repo's top level, or a non-package
        #: dir (`lint tests/`): "X exists nowhere in code" directions of
        #: the consistency passes are unanswerable there and skip
        #: walking the repo root itself is a SUPERSET of the default
        #: scan — a wider walk must never report fewer findings than
        #: the narrow one, so it keeps the repo-wide directions
        self.partial = files is not None or (
            self.root != self.repo_root
            and (os.path.dirname(self.root) != self.repo_root
                 or not os.path.isfile(
                     os.path.join(self.root, "__init__.py"))))
        if files is not None:
            # explicitly named files are linted even under an exclude
            # prefix — the fixture tests (and a curious operator) point
            # straight at known-bad files on purpose
            paths = [os.path.abspath(p) for p in files]
        else:
            paths = []
            for dirpath, dirnames, names in os.walk(self.root):
                dirnames[:] = [
                    d for d in dirnames
                    if d != "__pycache__"
                    and not self._excluded(os.path.join(dirpath, d))]
                for n in sorted(names):
                    if (n.endswith(".py")
                            and not self._excluded(
                                os.path.join(dirpath, n))):
                        paths.append(os.path.join(dirpath, n))
        for p in sorted(paths):
            self.files.append(SourceFile(p, self._rel(p)))

    def _excluded(self, path: str) -> bool:
        """True when ``path`` sits under a configured exclude prefix
        (repo-root-relative)."""
        if not self.exclude:
            return False
        rel = os.path.relpath(path, self.repo_root).replace(os.sep, "/")
        if rel.startswith(".."):
            return False
        return any(rel == e or rel.startswith(e + "/")
                   for e in self.exclude)

    def _rel(self, path: str) -> str:
        base = (self.repo_root
                if path.startswith(self.repo_root) else self.root)
        return os.path.relpath(path, base).replace(os.sep, "/")

    # -- artifacts the consistency passes compare against ----------------

    def doc_path(self, name: str) -> str:
        return os.path.join(self.repo_root, "docs", name)

    def doc_text(self, name: str) -> str:
        """docs/<name> contents ('' when absent — passes report absence
        themselves when the artifact is load-bearing)."""
        try:
            with open(self.doc_path(name), encoding="utf-8") as f:
                return f.read()
        except OSError:
            return ""

    def doc_texts(self) -> Dict[str, str]:
        """Every docs/*.md, keyed by repo-relative path."""
        out: Dict[str, str] = {}
        docs = os.path.join(self.repo_root, "docs")
        if os.path.isdir(docs):
            for n in sorted(os.listdir(docs)):
                if n.endswith(".md"):
                    out[f"docs/{n}"] = self.doc_text(n)
        return out

    def deploy_manifests(self) -> Dict[str, str]:
        """deploy/gke/*.yaml raw text, keyed by repo-relative path."""
        out: Dict[str, str] = {}
        d = os.path.join(self.repo_root, "deploy", "gke")
        if os.path.isdir(d):
            for n in sorted(os.listdir(d)):
                if n.endswith((".yaml", ".yml")):
                    with open(os.path.join(d, n), encoding="utf-8") as f:
                        out[f"deploy/gke/{n}"] = f.read()
        return out

    def repo_py_texts(self) -> Dict[str, str]:
        """Raw text of every tracked-ish .py under repo_root (scanned
        tree + tests + benchmarks) — for 'is this knob read ANYWHERE'
        style questions that are wider than the lint root."""
        out = {sf.rel: sf.text for sf in self.files}
        for extra in ("tests", "benchmarks"):
            d = os.path.join(self.repo_root, extra)
            if not os.path.isdir(d):
                continue
            for dirpath, dirnames, names in os.walk(d):
                dirnames[:] = [x for x in dirnames if x != "__pycache__"]
                for n in names:
                    if n.endswith(".py"):
                        p = os.path.join(dirpath, n)
                        rel = os.path.relpath(
                            p, self.repo_root).replace(os.sep, "/")
                        try:
                            with open(p, encoding="utf-8") as f:
                                out[rel] = f.read()
                        except OSError:
                            continue
        return out


class Pass:
    """Base class: subclasses set ``name``/``description`` and implement
    :meth:`run`. Findings they emit should use ``self.finding(...)`` so
    the pass name is stamped consistently."""

    name: str = ""
    description: str = ""

    def run(self, index: CodebaseIndex) -> List[Finding]:
        raise NotImplementedError

    def finding(self, file: str, line: int, message: str,
                hint: str = "", col: int = 0) -> Finding:
        return Finding(pass_name=self.name, file=file, line=line,
                       message=message, hint=hint, col=col)


@dataclasses.dataclass
class LintConfig:
    """Resolved run configuration (CLI flags over ``[tool.harmony.lint]``
    in pyproject.toml over defaults)."""

    enable: Optional[List[str]] = None    # None = all registered passes
    disable: List[str] = dataclasses.field(default_factory=list)
    baseline: Optional[str] = None
    #: repo-root-relative path prefixes the directory walk skips —
    #: this repo excludes tests/fixtures/lint (deliberately-bad lint
    #: fodder; linting it red is the fixtures doing their job, not a
    #: finding). Explicitly named files are always linted.
    exclude: List[str] = dataclasses.field(default_factory=list)

    def selected(self, all_names: Sequence[str]) -> List[str]:
        names = list(self.enable) if self.enable else list(all_names)
        unknown = [n for n in names + self.disable if n not in all_names]
        if unknown:
            raise ValueError(f"unknown lint pass(es): {unknown}; "
                             f"known: {sorted(all_names)}")
        return [n for n in names if n not in self.disable]


def _parse_toml_section(text: str, section: str) -> Dict[str, Any]:
    """Minimal TOML reader for one table: strings, string arrays, bools.
    Python 3.10 has no tomllib; pulling in a TOML dependency for three
    keys would violate the no-new-deps rule, so this reads exactly the
    subset ``[tool.harmony.lint]`` uses (tomllib is preferred when the
    interpreter has it)."""
    try:
        import tomllib  # py>=3.11

        data = tomllib.loads(text)
        for part in section.split("."):
            data = data.get(part, {})
        return data if isinstance(data, dict) else {}
    except ImportError:
        pass
    out: Dict[str, Any] = {}
    in_section = False
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip() if not raw.strip().startswith(
            "#") else ""
        if not line:
            continue
        if line.startswith("["):
            in_section = line == f"[{section}]"
            continue
        if not in_section or "=" not in line:
            continue
        key, val = (s.strip() for s in line.split("=", 1))
        if val.startswith("["):
            out[key] = re.findall(r'"([^"]*)"', val)
        elif val.startswith('"'):
            out[key] = val.strip('"')
        elif val in ("true", "false"):
            out[key] = val == "true"
        else:
            try:
                out[key] = int(val)
            except ValueError:
                out[key] = val
    return out


def load_config(repo_root: Optional[str] = None) -> LintConfig:
    """``[tool.harmony.lint]`` from <repo_root>/pyproject.toml (defaults
    when the file or section is absent)."""
    path = os.path.join(repo_root or REPO_ROOT, "pyproject.toml")
    cfg = LintConfig()
    try:
        with open(path, encoding="utf-8") as f:
            raw = _parse_toml_section(f.read(), "tool.harmony.lint")
    except OSError:
        return cfg
    if raw.get("enable"):
        cfg.enable = list(raw["enable"])
    if raw.get("disable"):
        cfg.disable = list(raw["disable"])
    if raw.get("baseline"):
        cfg.baseline = str(raw["baseline"])
    if raw.get("exclude"):
        cfg.exclude = list(raw["exclude"])
    return cfg


# -- baseline ---------------------------------------------------------------

def load_baseline(path: str) -> List[str]:
    """Finding keys a previous run accepted (schema: {"version": 1,
    "entries": [key, ...]})."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("version") != 1:
        raise ValueError(f"{path}: not a harmonylint baseline (version 1)")
    entries = data.get("entries", [])
    if not all(isinstance(e, str) for e in entries):
        raise ValueError(f"{path}: baseline entries must be strings")
    return list(entries)


def save_baseline(result: "LintResult", path: str) -> int:
    """Write the ACTIVE findings of ``result`` as the new baseline;
    returns the entry count. Suppressed findings are not re-baselined."""
    entries = sorted({f.key() for f in result.findings})
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    return len(entries)


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          #: active (fail the run)
    suppressed: List[Finding]        #: pragma- or baseline-suppressed
    passes_run: List[str]
    files_scanned: int
    wall_ms: float
    root: str

    @property
    def ok(self) -> bool:
        return not self.findings


class PragmaHygienePass(Pass):
    """Findings the framework itself owns: unparseable files and
    reason-less pragmas (both would otherwise silently shrink
    coverage). Registered like any pass (so ``--passes`` /
    ``--list-passes`` / ``disable`` all know its name) but ALSO
    prepended to every run unless explicitly disabled — suppressions
    stay justified even under a ``--passes`` subset."""

    name = "pragma-hygiene"
    description = ("files must parse, and every `# lint: allow(...)` "
                   "pragma must carry a justification")

    def run(self, index: CodebaseIndex) -> List[Finding]:
        out: List[Finding] = []
        for sf in index.files:
            if sf.parse_error is not None:
                # line rides the anchor, NOT the message — Finding.key()
                # is the line-independent baseline identity
                out.append(self.finding(
                    sf.rel, sf.parse_error_line,
                    f"file does not parse: {sf.parse_error}",
                    hint="a file the passes cannot read is a hole in "
                         "every invariant this suite pins"))
            for line, entries in sorted(sf.pragmas.items()):
                for passes, reason in entries:
                    if not reason:
                        out.append(self.finding(
                            sf.rel, line,
                            "allow({}) pragma without a reason".format(
                                ",".join(sorted(passes))),
                            hint="say WHY the rule does not apply here — "
                                 "`# lint: allow(<pass>) <justification>`"))
        return out


def run_lint(
    root: Optional[str] = None,
    passes: Optional[Sequence[Pass]] = None,
    config: Optional[LintConfig] = None,
    baseline: Optional[Sequence[str]] = None,
    repo_root: Optional[str] = None,
    files: Optional[Sequence[str]] = None,
) -> LintResult:
    """Run the suite; returns a :class:`LintResult` whose ``findings``
    are the unsuppressed problems (empty = green)."""
    from harmony_tpu.analysis.passes import all_passes

    t0 = time.perf_counter()
    root_abs = os.path.abspath(root or _PKG_DIR)
    repo_abs = os.path.abspath(repo_root or _find_repo_root(root_abs))
    cfg = config or load_config(repo_abs)
    index = CodebaseIndex(root=root_abs, repo_root=repo_abs, files=files,
                          exclude=cfg.exclude)
    if passes is None:
        registry = {p.name: p for p in all_passes()}
        selected = cfg.selected(list(registry))
        run_list = [registry[n] for n in selected]
    else:
        run_list = list(passes)
    if (not any(p.name == PragmaHygienePass.name for p in run_list)
            and PragmaHygienePass.name not in cfg.disable):
        run_list = [PragmaHygienePass()] + run_list
    if baseline is None and cfg.baseline:
        baseline = load_baseline(
            os.path.join(index.repo_root, cfg.baseline))
    baseline_keys = set(baseline or ())

    by_rel = {sf.rel: sf for sf in index.files}
    active: List[Finding] = []
    suppressed: List[Finding] = []
    for p in run_list:
        for f in p.run(index):
            sf = by_rel.get(f.file)
            pragma = (sf.pragma_for(f.line, p.name)
                      if sf is not None else None)
            if pragma is not None and pragma[1]:
                f.suppressed_by = "pragma"
                f.pragma_reason = pragma[0]
                suppressed.append(f)
            elif f.key() in baseline_keys:
                f.suppressed_by = "baseline"
                suppressed.append(f)
            else:
                active.append(f)
    order = {p.name: i for i, p in enumerate(run_list)}
    active.sort(key=lambda f: (f.file, f.line, order.get(f.pass_name, 99)))
    suppressed.sort(key=lambda f: (f.file, f.line))
    return LintResult(
        findings=active,
        suppressed=suppressed,
        passes_run=[p.name for p in run_list],
        files_scanned=len(index.files),
        wall_ms=round((time.perf_counter() - t0) * 1000.0, 2),
        root=index.root,
    )


# -- output -----------------------------------------------------------------

def render_text(result: LintResult, verbose: bool = False) -> str:
    out: List[str] = []
    for f in result.findings:
        out.append(f.format())
    if verbose:
        for f in result.suppressed:
            out.append(f"{f.file}:{f.line}: [{f.pass_name}] suppressed "
                       f"({f.suppressed_by}"
                       + (f": {f.pragma_reason}" if f.pragma_reason else "")
                       + f") {f.message}")
    out.append(
        f"harmonylint: {len(result.findings)} finding(s), "
        f"{len(result.suppressed)} suppressed, "
        f"{result.files_scanned} files, "
        f"{len(result.passes_run)} passes, {result.wall_ms:.0f} ms")
    return "\n".join(out)


def render_json(result: LintResult) -> str:
    """Stable machine-readable schema (pinned by tests/test_analysis.py
    — CI consumers parse this, bump "version" on shape changes)."""
    return json.dumps({
        "version": 1,
        "root": result.root,
        "passes": result.passes_run,
        "files_scanned": result.files_scanned,
        "wall_ms": result.wall_ms,
        "ok": result.ok,
        "findings": [f.to_json() for f in result.findings],
        "suppressed": [f.to_json() for f in result.suppressed],
    }, indent=1, sort_keys=True)
