"""Names inside the step program: scopes at the step's layer boundaries,
and a reader that finds them again in a captured device profile.

**Writing.** ``step_scope(name)`` is ``jax.named_scope`` restricted to
``VOCABULARY`` — the single list the docs, the lint and the readers share.
A scope is trace-time metadata: it lands in the ``op_name`` of every HLO
instruction traced under it and costs nothing per step. Blocks of a model
are ``step_scope("blk", i)`` -> ``blk<i>``, with the leaf scopes inside.

**Reading.** A profiler capture (``.xplane.pb``) already carries what is
needed: the plane ``/host:metadata`` holds one serialized ``HloProto`` per
executed module (every instruction with its ``metadata.op_name``), and each
``/device:TPU:<n>`` plane's line ``XLA Ops`` holds one event per executed
instruction, named by its HLO line. ``reduce_file`` joins the two into
``{device: {module: rows}}``, a row being ``(scope, pass, class)`` with
seconds, calls and matmul FLOPs. No protobuf package is needed on the
machine: ``_fields`` walks the wire format over the handful of fields read.

The path grammar (jax 0.9): ``jit(_step)/transpose(jvp(blk3))/jvp(blk3)/
checkpoint/rematted_computation/ffn/dot_general``. Transformation wrappers
(``jvp(…)``, ``transpose(…)``) enclose some components, function wrappers
(``jit(name)``) stand for themselves, the last component is the primitive.
``parse_path`` drops the function wrappers, opens the others and keeps the
vocabulary's tokens: the block, then the innermost leaf. The pass is
``remat`` under ``rematted_computation``, else ``bwd`` under
``transpose(``, else ``fwd``.

**Inheritance.** XLA's own instructions (copies, converts, the
``dynamic-update-slice`` chain of a concatenate, async halves) carry no
``op_name``. Such an instruction takes the ``(scope, pass)`` its users
agree on (a value consumed by one layer is that layer's), else its
operands', through at most ``INHERIT_DEPTH`` nameless instructions;
otherwise it is ``unscoped:<opcode>``. It is a heuristic, so every row
says how many of its seconds were inherited.

    python -m harmony_tpu.cli obs scopes <profile dir | file.xplane.pb>
"""
from __future__ import annotations

import functools
import glob
import hashlib
import os
import re
from bisect import bisect_right
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

#: every name a ``step_scope`` may carry (docs/OBSERVABILITY.md has a row
#: for each). ``blk`` takes the block's index; the rest are leaves.
VOCABULARY = (
    "table.pull", "table.grad_rows", "table.push",
    "compute",
    "embed", "noise", "blk", "norm", "merge",
    "mixer.qkv", "mixer.cca", "mixer.rope", "mixer.core", "mixer.streams",
    "mixer.gate", "mixer.out",
    "kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.out",
    "ssd.proj", "ssd.conv", "ssd.gate", "ssd.scan", "ssd.out",
    "ffn",
    "moe.route", "moe.latent", "moe.dispatch", "moe.experts", "moe.shared",
    "moe.combine", "moe.aux",
    "head", "loss", "exit.gate",
    "fm.interact", "fm.loss",
)
BLOCK = "blk"
UNSCOPED = "unscoped"
INHERIT_DEPTH = 256


def step_scope(name: str, index: Optional[int] = None):
    """``jax.named_scope`` for a name of ``VOCABULARY`` (``blk`` with the
    block's index, every other name without one)."""
    if name not in VOCABULARY or (name == BLOCK) != (index is not None):
        raise ValueError(f"step_scope: {name!r} (index {index!r}) is not in "
                         f"the vocabulary")
    import jax

    return jax.named_scope(name if index is None else f"{name}{int(index)}")


#: the files that open scopes, relative to the package (the lint holds the
#: list complete)
SCOPE_SITES = ("apps/widedeep.py", "dolphin/worker.py", "models/moe.py",
               "models/pytree_trainer.py", "models/transformer.py")


@functools.lru_cache(maxsize=None)
def scope_digest() -> str:
    """Six hex digits of the vocabulary and the sources that open scopes."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256(repr(VOCABULARY).encode())
    for rel in ("tracing/stepscopes.py",) + SCOPE_SITES:
        with open(os.path.join(package, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:6]


def in_cache_key(fn):
    """``fn`` renamed ``<name>_<scope_digest()>`` before it is jitted. JAX's
    persistent-cache key leaves debug locations out, and scopes live
    there: an executable cached by a tree with other scopes would be a hit,
    and a capture of it would carry the OLD names (a run shows it:
    PERF.md, PR 33). The module's name is in the key, so the step's name
    carries the scope sites' digest. A program with a Pallas kernel never
    needed it (the kernel's body holds its call site's location); for the
    others the cost is one cold compile of the step after an edit to a
    file of ``SCOPE_SITES``."""
    fn.__name__ = f"{fn.__name__}_{scope_digest()}"
    return fn


# ---------------------------------------------------------------------------
# paths
# ---------------------------------------------------------------------------

_FUNCTION = re.compile(r"\b(?:p?jit|xla_call|custom_jvp_call|"
                       r"custom_vjp_call|shard_map)\([^()]*\)")
_BLOCK = re.compile(rf"^{BLOCK}(\d+)$")
_LEAVES = frozenset(VOCABULARY) - {BLOCK}


def parse_path(op_name: str) -> Optional[Tuple[str, str]]:
    """``(scope, pass)`` of an instruction's ``op_name``, None when no
    vocabulary token stands in it. ``scope`` is ``blk<i>/<leaf>``, ``blk<i>``
    (a block's own glue) or ``<leaf>``."""
    if not op_name:
        return None
    tokens = [t for t in re.split(r"[/()]+", _FUNCTION.sub("", op_name)) if t]
    block = leaf = None
    for t in tokens:
        if _BLOCK.match(t):
            block, leaf = t, None  # a leaf counts inside its block
        elif t in _LEAVES:
            leaf = t
    if block is None and leaf is None:
        return None
    if "rematted_computation" in tokens:
        which = "remat"
    else:
        which = "bwd" if "transpose(" in op_name else "fwd"
    return "/".join(t for t in (block, leaf) if t), which


def strip_block(scope: str) -> str:
    """``blk3/ffn`` -> ``blk*/ffn`` (a reader groups blocks by this)."""
    head, _, rest = scope.partition("/")
    if _BLOCK.match(head):
        return f"{BLOCK}*/{rest}" if rest else f"{BLOCK}*"
    return scope


# ---------------------------------------------------------------------------
# the wire format (varint / length-delimited / fixed), read in place
# ---------------------------------------------------------------------------

def _fields(buf, at: int, end: int) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: a varint as an
    unsigned int, a length-delimited field as ``(start, end)`` in ``buf``,
    fixed 64 / 32 as ``(start, end)`` too."""
    while at < end:
        key = shift = 0
        while True:
            b = buf[at]
            at += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[at]
                at += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 0, value
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[at]
                at += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 2, (at, at + n)
            at += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            yield key >> 3, wire, (at, at + n)
            at += n
        else:
            raise ValueError(f"wire type {wire} at byte {at}")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, wire: int, value) -> List[int]:
    """A repeated integer field's element(s): packed or one by one."""
    if wire == 0:
        return [value]
    out, at, end = [], value[0], value[1]
    while at < end:
        v = shift = 0
        while True:
            b = buf[at]
            at += 1
            v |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        out.append(v)
    return out


# ---------------------------------------------------------------------------
# HloProto -> the instructions of one module
# ---------------------------------------------------------------------------

#: instructions that are no device work, and those of them that are where
#: values come from: they name nothing and inherit nothing (a bitcast or a
#: tuple element between two instructions hands a scope through)
_FREE = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "partition-id", "replica-id", "after-all"})
_SOURCES = _FREE - {"tuple", "get-tuple-element", "bitcast"}
_LAYOUT = frozenset({"copy", "reshape", "transpose", "slice", "concatenate",
                     "dynamic-update-slice", "dynamic-slice", "convert",
                     "pad", "broadcast", "copy-start", "copy-done",
                     "bitcast-convert"})
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")
_MATMUL = frozenset({"dot", "convolution"})
KERNEL_TARGET = "tpu_custom_call"


@dataclass
class Instr:
    name: str = ""
    opcode: str = ""
    op_name: str = ""
    ident: int = 0
    operands: Tuple[int, ...] = ()
    called: Tuple[int, ...] = ()
    dims: Tuple[int, ...] = ()
    target: str = ""
    #: dot: the lhs contracting dimensions. convolution: its dimension
    #: numbers by field number, and a (size, stride, padding_low,
    #: window_dilation, base_dilation) per spatial dimension
    contract: Tuple[int, ...] = ()
    conv: Optional[Dict[int, Any]] = None
    window: Tuple[Tuple[int, int, int, int, int], ...] = ()
    # filled by Module.resolve
    scope: Optional[Tuple[str, str]] = None
    inherited: bool = False
    klass: str = "other"
    flops: float = 0.0


#: ConvolutionDimensionNumbers: the repeated fields (input, kernel and
#: output spatial dimensions); the scalar ones default to 0
_CONV_LISTS = (11, 6, 12)
_IN_SPATIAL, _KERNEL_SPATIAL, _OUT_SPATIAL = _CONV_LISTS
_KERNEL_IN, _OUT_BATCH, _OUT_FEATURE = 3, 9, 10


def _window_dimension(buf, span) -> Tuple[int, int, int, int, int]:
    size, stride, low, dilation, base = 1, 1, 0, 1, 1
    for no, _w, v in _fields(buf, *span):
        if no == 1:
            size = v
        elif no == 2:
            stride = v
        elif no == 3:
            low = _signed(v)
        elif no == 5:
            dilation = v
        elif no == 6:
            base = v
    return size, stride, low, dilation, base


def _window_pairs(n_in: int, n_out: int, size: int, stride: int, low: int,
                  dilation: int, base: int) -> int:
    """(output position, kernel tap) pairs of one spatial dimension that
    read a real input element — padding and the holes of a dilated input
    are no work (a TPU module states a dot with free dimensions as a
    convolution over padded size-1 inputs)."""
    pairs = 0
    reach = (n_in - 1) * base
    for k in range(size):
        for o in range(n_out):
            x = o * stride - low + k * dilation
            if 0 <= x <= reach and x % base == 0:
                pairs += 1
    return pairs


def _instruction(buf, at: int, end: int) -> Instr:
    it = Instr()
    operands: List[int] = []
    called: List[int] = []
    for no, wire, v in _fields(buf, at, end):
        if no == 1:
            it.name = _text(buf, v)
        elif no == 2:
            it.opcode = _text(buf, v)
        elif no == 3:  # shape: dimensions = 3 (tuples keep none)
            dims: List[int] = []
            for n2, w2, v2 in _fields(buf, *v):
                if n2 == 3:
                    dims += _ints(buf, w2, v2)
            it.dims = tuple(dims)
        elif no == 7:  # metadata: op_name = 2
            for n2, _w, v2 in _fields(buf, *v):
                if n2 == 2:
                    it.op_name = _text(buf, v2)
        elif no == 15:  # window: dimensions = 1
            it.window += tuple(
                _window_dimension(buf, v2)
                for n2, _w, v2 in _fields(buf, *v) if n2 == 1)
        elif no == 16:  # convolution_dimension_numbers
            conv: Dict[int, Any] = {n: [] for n in _CONV_LISTS}
            for n2, w2, v2 in _fields(buf, *v):
                if n2 in _CONV_LISTS:
                    conv[n2] += _ints(buf, w2, v2)
                else:
                    conv[n2] = v2
            it.conv = conv
        elif no == 28:
            it.target = _text(buf, v)
        elif no == 30:  # dot_dimension_numbers: lhs contracting = 1
            contract: List[int] = []
            for n2, w2, v2 in _fields(buf, *v):
                if n2 == 1:
                    contract += _ints(buf, w2, v2)
            it.contract = tuple(contract)
        elif no == 35:
            it.ident = v
        elif no == 36:
            operands += _ints(buf, wire, v)
        elif no == 38:
            called += _ints(buf, wire, v)
    it.operands, it.called = tuple(operands), tuple(called)
    return it


def _product(xs) -> float:
    out = 1.0
    for x in xs:
        out *= x
    return out


class Module:
    """One executed module's instructions, every computation's, resolved to
    ``(scope, pass)``, a class and matmul FLOPs."""

    def __init__(self, buf, at: int, end: int) -> None:
        self.name = ""
        self.computations: Dict[int, List[Instr]] = {}
        self.by_name: Dict[str, Instr] = {}
        for no, _w, v in _fields(buf, at, end):
            if no == 1:
                self.name = _text(buf, v)
            elif no == 3:
                ident, instrs = 0, []
                for n2, _w2, v2 in _fields(buf, *v):
                    if n2 == 2:
                        instrs.append(_instruction(buf, *v2))
                    elif n2 == 5:
                        ident = v2
                self.computations[ident] = instrs
                for it in instrs:
                    self.by_name[it.name] = it
        self._resolve()

    # -- what an instruction is ------------------------------------------

    def _body(self, it: Instr) -> List[Instr]:
        """The instructions a fusion / async wrapper / call runs, those of
        a fusion nested inside it included; the root comes last."""
        out: List[Instr] = []
        for c in it.called:
            for inner in self.computations.get(c, []):
                if inner.called and inner.opcode == "fusion":
                    out += self._body(inner)
                out.append(inner)
        return out

    def _flops_of(self, it: Instr, peers: Dict[int, Instr]) -> float:
        """2 x output elements x contracted elements of a ``dot`` or a
        ``convolution`` (a TPU module states its matmuls as convolutions),
        from the module's own shapes."""
        if it.opcode == "dot" and it.operands:
            lhs = peers.get(it.operands[0])
            if lhs is None:
                return 0.0
            return 2.0 * _product(it.dims) * _product(
                lhs.dims[d] for d in it.contract if d < len(lhs.dims))
        if (it.opcode == "convolution" and len(it.operands) > 1
                and it.conv is not None):
            lhs, kernel = (peers.get(o) for o in it.operands[:2])
            if lhs is None or kernel is None:
                return 0.0
            c = it.conv
            try:
                pairs = _product(
                    _window_pairs(lhs.dims[i], it.dims[o], *w)
                    for i, o, w in zip(c[_IN_SPATIAL], c[_OUT_SPATIAL],
                                       it.window))
                return (2.0 * it.dims[c.get(_OUT_BATCH, 0)]
                        * it.dims[c.get(_OUT_FEATURE, 0)]
                        * kernel.dims[c.get(_KERNEL_IN, 0)] * pairs)
            except IndexError:
                return 0.0
        return 0.0

    def _classify(self, it: Instr, body: Sequence[Instr]) -> str:
        if _COLLECTIVE.match(it.opcode):
            return "collective"
        if it.opcode == "custom-call":
            return "kernel" if it.target == KERNEL_TARGET else "other"
        work = [b.opcode for b in body if b.opcode not in _FREE] or [it.opcode]
        if any(op in _MATMUL for op in work):
            return "matmul"
        if all(op in _LAYOUT for op in work):
            return "layout"
        return "other"

    def _own_scope(self, it: Instr, body: Sequence[Instr]
                   ) -> Optional[Tuple[str, str]]:
        """A matmul's name first (a fusion is booked where its FLOPs are),
        then the instruction's own, then its body's root's. A small
        producer fused into a nameless root (a ``dynamic-update-slice`` of
        the gradient's flat vector) does not name the fusion: it inherits."""
        for b in body:
            if b.opcode in _MATMUL:
                found = parse_path(b.op_name)
                if found:
                    return found
        found = parse_path(it.op_name)
        if found or not body:
            return found
        return parse_path(body[-1].op_name)  # the root comes last

    def _resolve(self) -> None:
        fused = {c for instrs in self.computations.values()
                 for it in instrs if it.opcode == "fusion" for c in it.called}
        peers_of = {ident: {it.ident: it for it in instrs}
                    for ident, instrs in self.computations.items()}
        for ident, instrs in self.computations.items():
            for it in instrs:
                it.flops = self._flops_of(it, peers_of[ident])
        for ident, instrs in self.computations.items():
            if ident in fused:
                continue
            peers = peers_of[ident]
            for it in instrs:
                body = self._body(it) if it.opcode in (
                    "fusion", "async-start", "call") else []
                it.klass = self._classify(it, body)
                it.scope = self._own_scope(it, body)
                it.flops += sum(b.flops for b in body)
            _inherit(instrs, peers)


def _inherit(instrs: Sequence[Instr], peers: Dict[int, Instr]) -> None:
    """Name the instructions XLA added (module docstring). A computation
    lists operands before users, so one pass from the end hands a scope
    down a whole chain of nameless users (the ``dynamic-update-slice``s of
    a concatenate end in one named consumer), one pass from the start
    hands it up; ``hops`` counts the nameless instructions in between and
    stops the walk at ``INHERIT_DEPTH``."""
    users: Dict[int, List[Instr]] = {}
    for it in instrs:
        for o in it.operands:
            users.setdefault(o, []).append(it)
    hops: Dict[int, int] = {it.ident: 0 for it in instrs if it.scope}

    def take(it: Instr, others: Sequence[Instr]) -> None:
        near = [o for o in others if o.scope and hops[o.ident] < INHERIT_DEPTH]
        if near and all(o.scope == near[0].scope for o in near):
            it.scope, it.inherited = near[0].scope, True
            hops[it.ident] = 1 + min(hops[o.ident] for o in near)

    for it in reversed(instrs):
        if it.scope is None and it.opcode not in _SOURCES:
            take(it, users.get(it.ident, ()))
    for it in instrs:
        if it.scope is None and it.opcode not in _SOURCES:
            take(it, [peers[o] for o in it.operands if o in peers])


# ---------------------------------------------------------------------------
# XSpace -> modules, and each device's events
# ---------------------------------------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
METADATA_PLANE = "/host:metadata"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_HLO_NAME = re.compile(r"^%(\S+) = ")

Event = Tuple[int, int, str]  # start_ps, end_ps, event metadata's name


def _plane(buf, at: int, end: int):
    """``(name, lines, event_metadata)`` of an XPlane, ``lines`` as the
    spans of its XLine messages and ``event_metadata`` as ``{id: span}``."""
    name, lines, meta = "", [], {}
    for no, _w, v in _fields(buf, at, end):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            lines.append(v)
        elif no == 4:  # map entry: key = 1, value = 2
            key, span = 0, None
            for n2, _w2, v2 in _fields(buf, *v):
                if n2 == 1:
                    key = _signed(v2)
                elif n2 == 2:
                    span = v2
            if span is not None:
                meta[key] = span
    return name, lines, meta


def _metadata_name(buf, span) -> str:
    for no, _w, v in _fields(buf, *span):
        if no == 2:
            return _text(buf, v)
    return ""


def _line_events(buf, span, want: Sequence[str]
                 ) -> Optional[Tuple[str, List[Tuple[int, int, int]]]]:
    """``(line name, [(start_ps, end_ps, metadata id)])`` of an XLine whose
    name is in ``want`` (the line's own timestamp added), else None."""
    name, t0, events = "", 0, []
    for no, _w, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            t0 = _signed(v)
        elif no == 4:
            events.append(v)
    if name not in want:
        return None
    out = []
    base = t0 * 1000
    for ev in events:
        meta = offset = duration = 0
        for no, _w, v in _fields(buf, *ev):
            if no == 1:
                meta = _signed(v)
            elif no == 2:
                offset = _signed(v)
            elif no == 3:
                duration = _signed(v)
        out.append((base + offset, base + offset + duration, meta))
    return name, out


def _hlo_modules(buf, meta: Dict[int, Tuple[int, int]]) -> Dict[str, Module]:
    """``{event name: Module}`` from the ``/host:metadata`` plane: each
    event metadata's first bytes-valued stat is the module's ``HloProto``."""
    out: Dict[str, Module] = {}
    for span in meta.values():
        name, proto = "", None
        for no, _w, v in _fields(buf, *span):
            if no == 2:
                name = _text(buf, v)
            elif no == 5 and proto is None:  # stats: bytes_value = 6
                for n2, w2, v2 in _fields(buf, *v):
                    if n2 == 6 and w2 == 2 and v2[1] > v2[0]:
                        proto = v2
        if proto is None:
            continue
        for no, _w, v in _fields(buf, *proto):
            if no == 1:  # HloProto.hlo_module
                try:
                    out[name] = Module(buf, *v)
                except (ValueError, IndexError):
                    pass  # not an HloProto after all
    return out


def load(path: str):
    """``(modules, devices)`` of an ``.xplane.pb``: ``{module event name:
    Module}`` and ``{ordinal: {"ops": [Event], "modules": [Event]}}``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    modules: Dict[str, Module] = {}
    devices: Dict[int, Dict[str, List[Event]]] = {}
    for no, _w, v in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name, lines, meta = _plane(buf, *v)
        if name == METADATA_PLANE:
            modules.update(_hlo_modules(buf, meta))
            continue
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        names: Dict[int, str] = {}
        dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for span in lines:
            found = _line_events(buf, span, (OPS_LINE, MODULES_LINE))
            if found is None:
                continue
            key = "ops" if found[0] == OPS_LINE else "modules"
            for s, e, ident in found[1]:
                if ident not in names:
                    names[ident] = (_metadata_name(buf, meta[ident])
                                    if ident in meta else "")
                dev[key].append((s, e, names[ident]))
    return modules, devices


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclass
class Row:
    scope: str
    which: str  # fwd | bwd | remat | "" (unscoped)
    klass: str
    seconds: float = 0.0
    calls: int = 0
    flops: float = 0.0
    inherited_s: float = 0.0
    #: the instructions behind the row, by their own seconds (a reader
    #: checks a roofline share per fusion; the CLI lists the heaviest)
    instrs: Dict[str, List[float]] = field(default_factory=dict)


def self_times(ops: Sequence[Event]) -> List[Tuple[int, int, str, int]]:
    """``[(start, end, name, self picoseconds)]`` by start: an event that
    encloses others (a ``while`` around its body's) keeps only the time no
    enclosed event covers, so the self times partition the busy time."""
    out: List[List[Any]] = []
    stack: List[List[Any]] = []
    for s, e, name in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        row = [s, e, name, e - s]
        if stack and e <= stack[-1][1]:
            stack[-1][3] -= e - s
        stack.append(row)
        out.append(row)
    return [(s, e, name, max(0, own)) for s, e, name, own in out]


def reduce_device(modules: Dict[str, Module],
                  events: Dict[str, List[Event]]) -> Dict[str, Dict[str, Any]]:
    """``{module event name: {"rows": [Row], "seconds", "executions",
    "step_s", "runs": [{(scope, pass, class): seconds}]}}`` of one device:
    every ``XLA Ops`` event booked to the module whose ``XLA Modules`` event
    encloses its start, and to its instruction's row there. ``step_s`` is
    the median execution's seconds (a capture cuts its first and last).
    A module the metadata plane does not hold keeps one row,
    ``unscoped:?``."""
    runs = sorted(events["modules"])
    starts = [r[0] for r in runs]
    out: Dict[str, Dict[str, Any]] = {}
    for s, e, text, own in self_times(events["ops"]):
        i = bisect_right(starts, s) - 1
        if i < 0 or runs[i][1] < s:
            where, run = "(no module)", -1
        else:
            where, run = runs[i][2], i
        entry = out.setdefault(where, {"rows": {}, "seconds": 0.0,
                                       "runs": {}})
        module = modules.get(where)
        m = _HLO_NAME.match(text)
        it = module.by_name.get(m.group(1)) if module and m else None
        if it is not None and it.scope:
            key = (it.scope[0], it.scope[1], it.klass)
        else:
            opcode = it.opcode if it is not None else "?"
            key = (f"{UNSCOPED}:{opcode}", "",
                   it.klass if it is not None else "other")
        row = entry["rows"].get(key)
        if row is None:
            row = entry["rows"][key] = Row(*key)
        sec = own * 1e-12
        row.seconds += sec
        row.calls += 1
        entry["seconds"] += sec
        entry["runs"].setdefault(run, {})
        entry["runs"][run][key] = entry["runs"][run].get(key, 0.0) + sec
        if it is not None:
            row.flops += it.flops
            if it.inherited:
                row.inherited_s += sec
            per = row.instrs.setdefault(it.name, [0.0, 0.0])
            per[0] += sec
            per[1] += it.flops
    for entry in out.values():
        entry["rows"] = sorted(entry["rows"].values(),
                               key=lambda r: -r.seconds)
        entry["executions"] = len(entry["runs"])
        entry["runs"] = [entry["runs"][k] for k in sorted(entry["runs"])]
        entry["step_s"] = sorted(sum(run.values())
                                 for run in entry["runs"])[
            len(entry["runs"]) // 2]
    return out


def find_xplane(path: str) -> Optional[str]:
    """``path`` itself, or the newest ``*.xplane.pb`` under a profile
    directory (``jax.profiler`` writes ``plugins/profile/<time>/``)."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def reduce_file(path: str) -> Dict[int, Dict[str, Dict[str, Any]]]:
    """``{device ordinal: reduce_device(...)}`` of a capture; ``{}`` when
    it has no ``/host:metadata`` plane or no device ran anything."""
    modules, devices = load(path)
    if not modules:
        return {}
    return {d: reduce_device(modules, ev) for d, ev in sorted(devices.items())
            if ev["ops"]}


def fold(rows: Sequence[Row], blocks: bool = False) -> List[Row]:
    """``rows`` summed by ``(scope, pass, class)``, the blocks folded into
    ``blk*/`` unless ``blocks``; by seconds."""
    out: Dict[Tuple[str, str, str], Row] = {}
    for r in rows:
        key = (r.scope if blocks else strip_block(r.scope), r.which, r.klass)
        to = out.get(key)
        if to is None:
            to = out[key] = Row(*key)
        to.seconds += r.seconds
        to.calls += r.calls
        to.flops += r.flops
        to.inherited_s += r.inherited_s
        for name, (s, f) in r.instrs.items():
            per = to.instrs.setdefault(name, [0.0, 0.0])
            per[0] += s
            per[1] += f
    return sorted(out.values(), key=lambda r: -r.seconds)


def is_step(entry: Dict[str, Any]) -> bool:
    """A training step pushes: some row lies under ``table.push`` (the comm
    probe's, eval's and the start-up's modules name no such scope)."""
    return any(r.scope == "table.push" for r in entry["rows"])


def step_rows(reduced: Dict[str, Dict[str, Any]], blocks: bool = False
              ) -> Tuple[List[Row], float, float]:
    """The step modules of one device summed: ``(rows, seconds, steps)``,
    ``steps`` the seconds in median executions (a cut one counts as the
    part it is)."""
    steps = [entry for entry in reduced.values() if is_step(entry)]
    return (fold([r for entry in steps for r in entry["rows"]], blocks),
            sum(entry["seconds"] for entry in steps),
            sum(entry["seconds"] / entry["step_s"] for entry in steps
                if entry["step_s"] > 0))


def render(reduced_by_device: Dict[int, Dict[str, Dict[str, Any]]],
           top: int = 40, blocks: bool = False) -> List[str]:
    """The scope table as text, by device and module."""
    out: List[str] = []
    for dev, reduced in reduced_by_device.items():
        for name, entry in sorted(reduced.items(),
                                  key=lambda kv: -kv[1]["seconds"]):
            total = entry["seconds"]
            n = total / entry["step_s"] if entry["step_s"] > 0 else 1.0
            rows = fold(entry["rows"], blocks)
            out.append(
                f"device {dev}  module {name}: {total:.4f} s in "
                f"{entry['executions']} executions, "
                f"{1e3 * entry['step_s']:.3f} ms a step; "
                f"{sum(r.inherited_s for r in rows):.4f} s named by "
                f"inheritance")
            table = [("scope", "pass", "class", "ms/step", "%", "calls",
                      "TFLOP/s", "inherited ms")]
            for r in rows[:top]:
                table.append((
                    r.scope, r.which or "-", r.klass,
                    f"{1e3 * r.seconds / n:.3f}",
                    f"{100 * r.seconds / total:.2f}" if total else "-",
                    str(r.calls),
                    f"{r.flops / r.seconds / 1e12:.1f}"
                    if r.flops and r.seconds else "-",
                    f"{1e3 * r.inherited_s / n:.3f}"
                    if r.inherited_s else "-"))
            if len(rows) > top:
                rest = sum(r.seconds for r in rows[top:])
                table.append((f"({len(rows) - top} more rows)", "", "",
                              f"{1e3 * rest / n:.3f}",
                              f"{100 * rest / total:.2f}", "", "", ""))
            widths = [max(len(r[i]) for r in table) for i in range(8)]
            for i, r in enumerate(table):
                out.append("  " + "  ".join(
                    c.ljust(w) for c, w in zip(r, widths)).rstrip())
                if i == 0:
                    out.append("  " + "  ".join("-" * w for w in widths))
    return out
