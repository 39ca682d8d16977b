"""use-after-donate: a donated buffer is dead after the jitted call.

The fused hot path's memory contract (docs/DEVICE_HOT_PATH.md): a
buffer passed in a ``donate_argnums`` position of a jitted call is
handed to XLA, which reuses its memory for outputs — touching the old
handle afterwards is undefined (jax surfaces it as a
"donated buffer was deleted" error at best, silent garbage under
async dispatch at worst). PR 6's donation tests pin this dynamically
for the shipped steps; this pass pins the pattern statically wherever a
wrapper's donation positions are visible:

* ``w = jax.jit(fn, donate_argnums=(0,))`` (module- or function-scope;
  ``@functools.partial(jax.jit, donate_argnums=...)`` defs too), then
* ``w(tbl, batch)`` followed by a read of ``tbl`` in the same scope
  with no rebinding in between → finding at the read;
* ``w(tbl, batch)`` inside a loop with no rebinding of ``tbl`` anywhere
  in that loop → finding at the call (the next iteration re-donates a
  dead buffer). ``tbl = w(tbl, batch)`` is the sanctioned shape.

Ping/pong double-buffer rotation (a compute/transfer overlap window)
is understood: a pure-name
tuple assignment like ``ping, pong = pong, ping`` MOVES handles — the
RHS names are handle copies, not device reads, so the rotation itself
never fires a finding, and a donated name whose handle rotates onto a
new name counts as rebound for the loop rule. The deadness follows the
handle instead: after the rotation the ALIAS now holding the donated
buffer is tracked, and a read of it inside the overlap window without a
rebinding fence (``view = drv.wait_view()``-style republish) is flagged
at the read.

Reads inside nested functions are deferred calls the linear scan cannot
order and are out of scope (the dynamic donation tests own those).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from harmony_tpu.analysis.core import (
    CodebaseIndex,
    Finding,
    Pass,
    _dotted_name,
    is_jit_call as _is_jit_func,
)


def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Literal donate_argnums of a jax.jit(...) call (None when absent
    or not statically known)."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for el in v.elts:
                if not (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)):
                    return None
            return tuple(el.value for el in v.elts)
    return None


# event kinds, in execution order within a scope
_DONATE, _STORE, _LOAD = "donate", "store", "load"
#: one pure-name tuple assignment (``a, b = b, a``): handles MOVE
#: atomically (every RHS read precedes every LHS bind), so the whole
#: rotation is ONE event carrying its dst<-src mapping
_MOVE = "move"


class _ScopeScanner:
    """Collects (kind, name, node, loop_stack) events for one scope in
    execution order (values before targets), without descending into
    nested function/class scopes."""

    def __init__(self, wrappers: Dict[str, Tuple[int, ...]]) -> None:
        self.wrappers = dict(wrappers)
        #: (kind, name, node, loop-stack, branch-path, moves); branch-
        #: path is ((if-node-id, arm), ...) so the judge can recognize
        #: mutually exclusive if/else arms and not order them against
        #: each other; moves is the ((dst, src), ...) mapping of a _MOVE
        #: event (empty for every other kind)
        self.events: List[
            Tuple[str, str, ast.AST, Tuple[int, ...],
                  Tuple[Tuple[int, int], ...],
                  Tuple[Tuple[str, str], ...]]] = []
        self._loops: List[int] = []
        self._branches: List[Tuple[int, int]] = []

    def scan(self, body: List[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _emit(self, kind: str, name: str, node: ast.AST,
              moves: Tuple[Tuple[str, str], ...] = ()) -> None:
        self.events.append((kind, name, node, tuple(self._loops),
                            tuple(self._branches), moves))

    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            # nested scope: its deferred execution cannot be ordered
            # against this scope's events — skipped (module docstring)
            for dec in getattr(node, "decorator_list", ()):
                self._expr(dec)
            self._emit(_STORE, node.name, node)
            return
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            value = node.value
            if value is not None:
                # wrapper definition?
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                if (isinstance(value, ast.Call) and _is_jit_func(value.func)
                        and len(targets) == 1
                        and isinstance(targets[0], ast.Name)):
                    pos = _donate_positions(value)
                    if pos:
                        self.wrappers[targets[0].id] = pos
                # ping/pong rotation: a pure-name tuple assignment moves
                # handles without touching device memory — ONE atomic
                # _MOVE event instead of loads+stores (module docstring)
                if (isinstance(value, ast.Tuple)
                        and len(targets) == 1
                        and isinstance(targets[0], ast.Tuple)
                        and len(value.elts) == len(targets[0].elts) > 1
                        and all(isinstance(e, ast.Name)
                                for e in value.elts)
                        and all(isinstance(e, ast.Name)
                                for e in targets[0].elts)):
                    self._emit(_MOVE, "", node, moves=tuple(
                        (dst.id, src.id)
                        for dst, src in zip(targets[0].elts, value.elts)))
                    return
                self._expr(value)
                for t in targets:
                    self._target(t)
            return
        if isinstance(node, ast.AugAssign):
            self._expr(node.value)
            name = _dotted_name(node.target)
            if name:
                self._emit(_LOAD, name, node.target)
                self._emit(_STORE, name, node.target)
            else:
                self._expr(node.target)
            return
        if isinstance(node, ast.Delete):
            for t in node.targets:
                name = _dotted_name(t)
                if name:
                    self._emit(_STORE, name, t)  # the handle is gone
            return
        if isinstance(node, (ast.For, ast.AsyncFor)):
            self._expr(node.iter)
            self._loops.append(id(node))
            self._target(node.target)
            for s in node.body:
                self._stmt(s)
            self._loops.pop()
            for s in node.orelse:
                self._stmt(s)
            return
        if isinstance(node, ast.While):
            self._loops.append(id(node))
            self._expr(node.test)
            for s in node.body:
                self._stmt(s)
            self._loops.pop()
            for s in node.orelse:
                self._stmt(s)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                self._expr(item.context_expr)
                if item.optional_vars is not None:
                    self._target(item.optional_vars)
            for s in node.body:
                self._stmt(s)
            return
        if isinstance(node, ast.If):
            self._expr(node.test)
            self._branches.append((id(node), 0))
            for s in node.body:
                self._stmt(s)
            self._branches[-1] = (id(node), 1)
            for s in node.orelse:
                self._stmt(s)
            self._branches.pop()
            return
        if isinstance(node, ast.Try):
            for s in node.body:
                self._stmt(s)
            for h in node.handlers:
                for s in h.body:
                    self._stmt(s)
            for s in node.orelse:
                self._stmt(s)
            for s in node.finalbody:
                self._stmt(s)
            return
        # Expr / Return / Raise / Assert / everything else: scan values
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)
            elif isinstance(child, ast.stmt):
                self._stmt(child)

    def _target(self, node: ast.AST) -> None:
        if isinstance(node, (ast.Tuple, ast.List)):
            for el in node.elts:
                self._target(el)
            return
        if isinstance(node, ast.Starred):
            self._target(node.value)
            return
        name = _dotted_name(node)
        if name:
            self._emit(_STORE, name, node)
        else:
            # subscript targets etc: the base is LOADED (x[i] = v reads x)
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.expr):
                    self._expr(child)

    def _expr(self, node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else None
            if fname is not None and fname in self.wrappers:
                self._expr_children_of_call(node, self.wrappers[fname])
                return
        if isinstance(node, (ast.Lambda,)):
            return  # deferred
        name = _dotted_name(node)
        if name and isinstance(node, (ast.Name, ast.Attribute)):
            self._emit(_LOAD, name, node)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)
            elif isinstance(child, ast.keyword):
                self._expr(child.value)
            elif isinstance(child, ast.comprehension):
                self._expr(child.iter)
                for c in child.ifs:
                    self._expr(c)

    def _expr_children_of_call(self, node: ast.Call,
                               positions: Tuple[int, ...]) -> None:
        for i, arg in enumerate(node.args):
            name = _dotted_name(arg)
            if i in positions and name:
                self._emit(_DONATE, name, arg)
            else:
                self._expr(arg)
        for kw in node.keywords:
            self._expr(kw.value)


class UseAfterDonatePass(Pass):
    name = "use-after-donate"
    description = ("a name passed in a donate_argnums position is not "
                   "read again before rebinding")

    def run(self, index: CodebaseIndex) -> List[Finding]:
        out: List[Finding] = []
        for sf in index.files:
            if sf.tree is None:
                continue
            # module-level wrappers are visible inside functions
            module_wrappers: Dict[str, Tuple[int, ...]] = {}
            for node in sf.tree.body:
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and _is_jit_func(node.value.func)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    pos = _donate_positions(node.value)
                    if pos:
                        module_wrappers[node.targets[0].id] = pos
            # @functools.partial(jax.jit, donate_argnums=...) defs donate
            # their own params; register them ALL before snapshotting any
            # scope — a caller defined earlier in the file than the
            # decorated step must still see the donation
            for node in ast.walk(sf.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                for dec in node.decorator_list:
                    if (isinstance(dec, ast.Call)
                            and _dotted_name(dec.func).endswith("partial")
                            and dec.args
                            and _is_jit_func(dec.args[0])):
                        pos = _donate_positions(dec)
                        if pos:
                            module_wrappers[node.name] = pos
            scopes: List[Tuple[List[ast.stmt], Dict[str, Tuple[int, ...]]]]
            scopes = [(sf.tree.body, module_wrappers)]
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    scopes.append((node.body, dict(module_wrappers)))
            for body, wrappers in scopes:
                sc = _ScopeScanner(wrappers)
                sc.scan(body)
                out.extend(self._judge(sf.rel, sc.events))
        return out

    @staticmethod
    def _exclusive(a: Tuple[Tuple[int, int], ...],
                   b: Tuple[Tuple[int, int], ...]) -> bool:
        """True when two events sit in different arms of the same
        ``if`` — only one of them executes, so neither orders against
        the other."""
        arms_a = dict(a)
        return any(if_id in arms_a and arms_a[if_id] != arm
                   for if_id, arm in b)

    def _judge(self, rel: str, events) -> List[Finding]:
        out: List[Finding] = []
        for i, (kind, name, node, loops, branches, _mv) in enumerate(events):
            if kind != _DONATE:
                continue
            # `cur` tracks the NAME currently holding the donated (dead)
            # handle — a ping/pong rotation moves the deadness to the
            # alias instead of killing the scan
            cur = name
            for kind2, name2, node2, _loops2, branches2, mv2 in (
                    events[i + 1:]):
                if self._exclusive(branches, branches2):
                    continue  # sibling if/else arm: never both execute
                if kind2 == _MOVE:
                    dst_of = {src: dst for dst, src in mv2}
                    if cur in dst_of:
                        # the dead handle rotated: follow it. (If cur is
                        # also a move TARGET — the swap case — the handle
                        # still leaves; the fresh handle landing on cur
                        # is the rebind the loop rule credits.)
                        cur = dst_of[cur]
                        continue
                    if any(dst == cur for dst, _src in mv2):
                        break  # cur rebound to some other live handle
                    continue
                # tbl.sum() / tbl[k] reads are reads of tbl; only a
                # store of the NAME itself rebinds it
                if name2 != cur and not name2.startswith(cur + "."):
                    continue
                if kind2 == _STORE and name2 == cur:
                    break
                if kind2 == _STORE:
                    continue
                alias = ("" if cur == name else
                         f" (the handle rotated onto {cur!r} without a "
                         "rebinding fence)")
                # message stays line-free (Finding.key() is the baseline
                # identity); the donate site is recoverable from the hint
                out.append(self.finding(
                    rel, node2.lineno,
                    f"{name!r} was donated to a jitted call earlier in "
                    f"this scope and is read here without rebinding"
                    + alias,
                    hint="a donated buffer is dead after the step — "
                         "bind the call's result (`x = step(x, ...)`) "
                         "or stop donating this argument",
                    col=node2.col_offset))
                break
            if loops:
                def rebinds(e) -> bool:
                    if e[0] == _STORE and e[1] == name:
                        return True
                    # a move landing on the donated name gives it a new
                    # handle — the rotation's sanctioned rebind
                    return e[0] == _MOVE and any(
                        dst == name for dst, _src in e[5])

                in_loop = [e for e in events if e[3][:len(loops)] == loops]
                if not any(rebinds(e) for e in in_loop):
                    out.append(self.finding(
                        rel, node.lineno,
                        f"{name!r} is donated inside a loop but never "
                        "rebound in it — the next iteration re-donates "
                        "a dead buffer",
                        hint="bind the result back (`x = step(x, ...)`) "
                             "so each iteration donates a live buffer",
                        col=node.col_offset))
        return out
