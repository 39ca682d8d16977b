"""Tier-1 lint: jit construction hygiene in hot/warm paths.

Since PR 7 the two AST rules that lived here are the ``jit-hygiene``
pass of harmonylint (harmony_tpu/analysis/passes/jit.py — the full
suite also runs tree-wide in tests/test_analysis.py); these wrappers
keep the original per-rule failure surface. There is no file-level
allowlist: an exception is an inline ``# lint: allow(jit-hygiene)
<reason>`` pragma at the call site, where the justification can't drift
away from the code it vouches for (the package has none since PR 44).
"""
from __future__ import annotations

from lint_helpers import tree_findings


def _findings():
    return tree_findings("jit-hygiene")


def test_no_construct_and_call_jit():
    """jax.jit(...)(...) builds a fresh wrapper per evaluation — the
    retrace-every-call bug class. Every such expression must be hoisted
    into a cached wrapper."""
    offenders = [f.format() for f in _findings()
                 if "constructed and invoked" in f.message]
    assert not offenders, offenders


def test_step_shaped_jits_declare_donation_intent():
    """Any jit over a function named like a training step must say what
    it donates — explicitly, even when the answer is 'nothing'."""
    offenders = [f.format() for f in _findings()
                 if "donate_argnums" in f.message]
    assert not offenders, offenders
