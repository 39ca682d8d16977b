"""span-hygiene: tracer spans close on every path.

``trace_span`` is a context manager precisely so the exception path
stops the span and emits it (span.py: the ``finally`` stamps
``stop_sec`` and emits). A span opened positionally —
``cm = trace_span(...); cm.__enter__()`` — leaks on any raise between
enter and exit: the span never emits, the flight recorder ring never
sees it, and the trace timeline silently loses the failing subtree,
which is exactly when you need it. Sanctioned shapes:

* ``with trace_span(...):`` (directly, possibly among other items),
* ``stack.enter_context(trace_span(...))`` — ExitStack owns the exit.

Everything else — bare statement, assignment, argument, return — is
flagged.
"""
from __future__ import annotations

import ast
from typing import List, Set

from harmony_tpu.analysis.core import CodebaseIndex, Finding, Pass


_OPENERS = ("trace_span", "job_stage")


def _is_trace_span_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    # job_stage(...) returns a trace_span: its callers are held to the
    # same shapes
    return ((isinstance(f, ast.Name) and f.id in _OPENERS)
            or (isinstance(f, ast.Attribute) and f.attr in _OPENERS))


class SpanHygienePass(Pass):
    name = "span-hygiene"
    description = ("trace_span is opened via `with` (or ExitStack."
                   "enter_context) so exception paths still emit it")

    def run(self, index: CodebaseIndex) -> List[Finding]:
        out: List[Finding] = []
        for sf in index.files:
            if sf.tree is None:
                continue
            sanctioned: Set[int] = set()
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        if _is_trace_span_call(item.context_expr):
                            sanctioned.add(id(item.context_expr))
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "enter_context"):
                    for arg in node.args:
                        if _is_trace_span_call(arg):
                            sanctioned.add(id(arg))
            for node in ast.walk(sf.tree):
                if _is_trace_span_call(node) and id(node) not in sanctioned:
                    out.append(self.finding(
                        sf.rel, node.lineno,
                        "trace_span opened outside a `with` — the span "
                        "leaks (never emits) on the exception path",
                        hint="wrap the traced region in `with trace_span"
                             "(...):` or hand it to an ExitStack",
                        col=node.col_offset))
        return out
