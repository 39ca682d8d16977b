"""jit-hygiene: no construct-and-call jit; step-shaped jits declare
donation intent.

The bug class PR 6 fixed (apps/nmf.py, apps/lda.py,
checkpoint/orbax_io.py, pregel/master.py): building a FRESH ``jax.jit``
wrapper inside a lambda/loop that runs per invocation — each call makes
a new Python closure, so jax's executable cache can never hit and the
program retraces (and recompiles) every time. Two rules:

1. no construct-and-call — ``jax.jit(...)(...)`` / ``pjit(...)(...)``
   in one expression builds a wrapper and throws it away after one
   call. Hoist the wrapper (module scope, a table's ``_jitted`` cache,
   or runtime/progcache).
2. step-shaped jits declare donation intent — any ``jax.jit(fn)`` whose
   traced function is named like a training step (``*step*``,
   ``*epoch*``, ``*superstep*``) must pass ``donate_argnums``
   EXPLICITLY (``()`` is fine: it says "this step deliberately does not
   donate"). Donation is the fused hot path's memory contract; an
   implicit default on a step is how a double-buffered table silently
   doubles HBM.
"""
from __future__ import annotations

import ast
import re
from typing import List

from harmony_tpu.analysis.core import (
    CodebaseIndex,
    Finding,
    Pass,
    is_jit_call,
)

STEP_NAME = re.compile(r"(^|_)(step|epoch|superstep)", re.IGNORECASE)


def _is_jit_call(node: ast.Call) -> bool:
    return is_jit_call(node.func)


class JitHygienePass(Pass):
    name = "jit-hygiene"
    description = ("jit wrappers are cached (no construct-and-call) and "
                   "step-shaped jits declare donate_argnums explicitly")

    def run(self, index: CodebaseIndex) -> List[Finding]:
        out: List[Finding] = []
        for sf in index.files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                if (isinstance(node.func, ast.Call)
                        and _is_jit_call(node.func)):
                    out.append(self.finding(
                        sf.rel, node.lineno,
                        "jit wrapper constructed and invoked in one "
                        "expression (retraces every call)",
                        hint="hoist it into a cached wrapper — "
                             "table._jitted / runtime.progcache / module "
                             "scope", col=node.col_offset))
                if _is_jit_call(node) and node.args:
                    target = node.args[0]
                    if (isinstance(target, ast.Name)
                            and STEP_NAME.search(target.id)
                            and "donate_argnums" not in {
                                k.arg for k in node.keywords}):
                        out.append(self.finding(
                            sf.rel, node.lineno,
                            f"step-shaped jit({target.id}) without an "
                            "explicit donate_argnums",
                            hint="pass donate_argnums=() to declare a "
                                 "deliberate non-donating step",
                            col=node.col_offset))
        return out
