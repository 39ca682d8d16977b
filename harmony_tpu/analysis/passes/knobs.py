"""knob-consistency: HARMONY_* env knobs ⇄ docs ⇄ deploy manifests.

Generalizes (and supersedes) the one-off env/doc check that lived in
tests/test_gke_manifests.py. Three directions:

1. every ``HARMONY_*`` env READ in code appears in a docs/*.md knob
   table — an undocumented knob is configuration operators cannot
   discover (the DEPLOY/FAULT_TOLERANCE/OBSERVABILITY/DEVICE_HOT_PATH
   tables are the operator surface);
2. every ``HARMONY_*`` variable a deploy/gke manifest wires is actually
   read somewhere in the repo — a manifest env nobody reads is dead
   configuration that LOOKS load-bearing;
3. every manifest-wired knob is documented (the original
   test_gke_manifests rule).

Prefix reads — ``"HARMONY_RETRY_" + field.upper()`` in
config/params.py — are honored: a literal ending in ``_`` counts as
covering every knob it prefixes.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from harmony_tpu.analysis.core import (
    CodebaseIndex,
    Finding,
    Pass,
    _dotted_name as _dotted,
)

_KNOB_RE = re.compile(r"HARMONY_[A-Z0-9_]+")
_MANIFEST_ENV_RE = re.compile(r"-\s*name:\s*(HARMONY_[A-Z0-9_]+)")
_ENVISH_CALL = re.compile(r"(^|\.)(environ|getenv|env_[a-z_]+)($|\.)")

#: The operator surface: knob TABLE ROWS in these docs are what counts
#: as documentation. A knob name-dropped in prose — or in
#: STATIC_ANALYSIS.md's own bug anecdotes — gives operators no
#: name/default/meaning row and must NOT satisfy this pass.
_OPERATOR_DOCS = ("DEPLOY.md", "FAULT_TOLERANCE.md", "OBSERVABILITY.md",
                  "DEVICE_HOT_PATH.md", "INPUT_PIPELINE.md")


def _documented_knobs(index: CodebaseIndex) -> Set[str]:
    out: Set[str] = set()
    for name in _OPERATOR_DOCS:
        for line in index.doc_text(name).splitlines():
            if line.lstrip().startswith("|"):
                out.update(_KNOB_RE.findall(line))
    return out


def _reads_in_tree(tree: ast.AST, rel: str) -> List[Tuple[str, str, int]]:
    """(knob_or_prefix, file, line) for every HARMONY_* literal that is
    part of an environment READ: inside a call whose function name looks
    env-ish (os.environ.get / os.getenv / env_choice / ...), or a
    subscript of ``os.environ``. A knob name in a comment or docstring
    is NOT a read — that distinction is what makes the 'manifest knob
    read nowhere' direction mean something. Module-level constants
    (``ENV_PORT = "HARMONY_METRICS_PORT"`` ... ``environ.get(ENV_PORT)``,
    the exporter/flight idiom) resolve through one level."""
    consts: dict = {}
    body = getattr(tree, "body", [])
    for stmt in body:
        targets = (stmt.targets if isinstance(stmt, ast.Assign)
                   else [stmt.target] if isinstance(stmt, ast.AnnAssign)
                   else [])
        v = getattr(stmt, "value", None)
        if (isinstance(v, ast.Constant) and isinstance(v.value, str)
                and v.value.startswith("HARMONY_")):
            for t in targets:
                if isinstance(t, ast.Name):
                    consts[t.id] = v.value
    out: List[Tuple[str, str, int]] = []
    for node in ast.walk(tree):
        holders: List[ast.AST] = []
        if isinstance(node, ast.Call) and _ENVISH_CALL.search(
                _dotted(node.func)):
            holders = list(node.args)
        elif (isinstance(node, ast.Subscript)
                and _dotted(node.value).endswith("environ")):
            holders = [node.slice]
        for h in holders:
            for sub in ast.walk(h):
                if (isinstance(sub, ast.Constant)
                        and isinstance(sub.value, str)
                        and sub.value.startswith("HARMONY_")):
                    out.append((sub.value, rel, node.lineno))
                elif isinstance(sub, ast.Name) and sub.id in consts:
                    out.append((consts[sub.id], rel, node.lineno))
    return out


def _read_literals(index: CodebaseIndex) -> List[Tuple[str, str, int]]:
    out: List[Tuple[str, str, int]] = []
    for sf in index.files:
        if sf.tree is not None:
            out.extend(_reads_in_tree(sf.tree, sf.rel))
    return out


def _read_fodder(tree: ast.AST) -> Set[str]:
    """Knob-shaped string constants anywhere in the AST EXCEPT
    docstrings — name tables like RetryPolicy._ENV_FIELDS (full names
    read via ``os.environ.get(var)`` in a loop) and ``"HARMONY_X_" +
    field.upper()`` prefix builds. Used ONLY to answer 'is this
    manifest knob read somewhere' (direction 2): looser than
    :func:`_reads_in_tree` but still excludes prose, since comments
    never parse and docstrings are skipped here."""
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                docstrings.add(id(body[0].value))
    out: Set[str] = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and node.value.startswith("HARMONY_")
                and id(node) not in docstrings):
            out.update(_KNOB_RE.findall(node.value))
            if node.value.endswith("_"):
                out.add(node.value)
    return out


def _covered(knob: str, reads: Set[str]) -> bool:
    if knob in reads:
        return True
    return any(r.endswith("_") and knob.startswith(r) for r in reads)


class KnobConsistencyPass(Pass):
    name = "knob-consistency"
    description = ("HARMONY_* knobs read in code are documented, and "
                   "every manifest-wired knob is read and documented")

    def run(self, index: CodebaseIndex) -> List[Finding]:
        out: List[Finding] = []
        documented = _documented_knobs(index)

        reads = _read_literals(index)
        if not documented:
            # no operator knob tables resolvable (docs/ absent — e.g. a
            # site-packages install): one structural finding, not one
            # per read
            if reads:
                out.append(self.finding(
                    "docs/DEPLOY.md", 1,
                    "no operator knob tables found under docs/ "
                    f"({'/'.join(_OPERATOR_DOCS)})",
                    hint="run the lint from the repo root (the knob "
                         "tables are the operator contract this pass "
                         "checks against)"))
            return out
        for knob, file, line in reads:
            if knob.endswith("_"):
                continue  # prefix read; concrete names come from fields
            if knob not in documented:
                out.append(self.finding(
                    file, line,
                    f"env knob {knob} is read here but documented in no "
                    "docs/*.md knob table",
                    hint="add a row (name / default / meaning) to the "
                         "DEPLOY knob table — undocumented knobs are "
                         "how deployments drift from their operators"))

        if index.partial:
            # a file slice cannot prove a manifest knob is read nowhere
            return out

        # direction 2+3 need the WIDER read surface (tests/benchmarks
        # legitimately read bench-only knobs like HARMONY_POD_UNIT_LAT_MS)
        # — still as AST-level READS (tests/test_scripts_compile.py keeps
        # every script of that surface parsing)
        read_names: Set[str] = {k for k, _, _ in reads}
        for sf in index.files:
            if sf.tree is not None:
                read_names.update(_read_fodder(sf.tree))
        scanned = {sf.rel for sf in index.files}
        for rel, text in index.repo_py_texts().items():
            if rel in scanned:
                continue
            tree = ast.parse(text, rel)
            read_names.update(k for k, _, _ in _reads_in_tree(tree, rel))
            read_names.update(_read_fodder(tree))

        for rel, text in sorted(index.deploy_manifests().items()):
            lines = text.splitlines()
            wired: Dict[str, int] = {}
            for lno, line in enumerate(lines, start=1):
                m = _MANIFEST_ENV_RE.search(line)
                if m:
                    wired[m.group(1)] = lno
            for knob, lno in sorted(wired.items()):
                if not _covered(knob, read_names):
                    out.append(self.finding(
                        rel, lno,
                        f"manifest wires {knob} but nothing in the repo "
                        "reads it",
                        hint="dead env looks load-bearing to operators; "
                             "drop it or wire the read"))
                if knob not in documented:
                    out.append(self.finding(
                        rel, lno,
                        f"manifest wires {knob} but no docs/*.md "
                        "documents it",
                        hint="the DEPLOY knob table is the operator "
                             "contract for deploy artifacts"))
        return out
