"""Names inside the step program (harmony_tpu/tracing/stepscopes.py).

(a) every tenant kind's own step, compiled as the worker builds it, carries
the scopes that kind must have, with forward, backward and rematerialised
instructions told apart; (b) the wire reader on a made-up capture and on
the recorded chip fixture (perf/tests/record_scope_fixture.py); (c) the
vocabulary lint: every ``step_scope`` literal is in the vocabulary, every
vocabulary entry is a row of docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import ast
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from harmony_tpu.tracing import stepscopes as ss  # noqa: E402

FIXTURE = os.path.join(ROOT, "perf", "tests", "fixture_scopes.xplane.pb")

# ---------------------------------------------------------------------------
# (a) the worker's own step, per tenant kind
# ---------------------------------------------------------------------------

LM = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=32,
          max_seq=32, ffn="swiglu", tie_embeddings=False, norm_eps=1e-5,
          attn="blockwise")
ROUTED = dict(moe_experts=8, moe_top_k=2, moe_every=1)
LATENT = dict(attn_kind="mla", kv_lora_rank=24, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, moe_first_dense=1,
              dense_d_ff=96, moe_shared_experts=1, moe_score="sigmoid",
              moe_norm_topk=True, moe_routed_scale=2.446, moe_seq_aux=True,
              moe_aux_weight=0.001)
TABLE = {"table.pull", "table.grad_rows", "table.push"}
BLOCK = {"embed", "blk*/norm", "head", "loss"}
SOFTMAX = {"blk*/mixer.qkv", "blk*/mixer.core", "blk*/mixer.out"}
EXPERTS = {"blk*/moe.route", "blk*/moe.dispatch", "blk*/moe.experts",
           "blk*/moe.combine", "blk*/moe.aux"}
KDA = {"blk*/kda.proj", "blk*/kda.conv", "blk*/kda.gate", "blk*/kda.scan",
       "blk*/kda.out"}

KINDS = {
    "gpt2": (dict(LM, ffn="gelu", tie_embeddings=True, pos="learned"),
             TABLE | BLOCK | SOFTMAX | {"blk*/ffn"}),
    "olmoe": (dict(LM, pos="rope", rope_theta=10000.0, qk_norm=True,
                   moe_aux_weight=0.01, moe_z_weight=0.001, **ROUTED),
              TABLE | BLOCK | SOFTMAX | EXPERTS | {"blk*/mixer.rope"}),
    "moonlight": (dict(LM, pos="rope", rope_theta=50000.0, **ROUTED,
                       **LATENT),
                  TABLE | BLOCK | SOFTMAX | EXPERTS
                  | {"blk*/mixer.rope", "blk*/ffn", "blk*/moe.shared"}),
    "kimi": (dict(LM, n_layers=3, max_seq=80, pos="none", linear_layers=[0, 1],
                  linear_heads=2, linear_head_dim=16, short_conv=4,
                  remat=True, **ROUTED, **LATENT),
             TABLE | BLOCK | SOFTMAX | EXPERTS | KDA
             | {"blk*/ffn", "blk*/moe.shared"}),
}


def _compile_step(trainer, batch):
    """The worker's ``_step_core`` for ``trainer`` on one device, compiled:
    the executable's serialized ``HloModuleProto`` as a ``Module``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harmony_tpu.dolphin.worker import WorkerTasklet
    from harmony_tpu.parallel import build_mesh
    from harmony_tpu.table.table import TableSpec, block_sharding
    from harmony_tpu.utils.platform import traced_on

    mesh = build_mesh(jax.devices()[:1], data=1)
    spec = TableSpec(trainer.model_table_config())
    tasklet = object.__new__(WorkerTasklet)
    tasklet.ctx = types.SimpleNamespace(
        model_table=types.SimpleNamespace(spec=spec), local_table=None)
    tasklet.trainer = trainer
    tsh = block_sharding(mesh, spec.num_blocks)
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(
        traced_on(mesh, tasklet._step_core(mesh)),
        out_shardings=(tsh, None), donate_argnums=0).lower(
        jax.ShapeDtypeStruct(spec.storage_shape, spec.dtype, sharding=tsh),
        tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
              for a in batch),
        {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
         for k in trainer.hyperparams()}).compile()
    proto = compiled.runtime_executable().hlo_modules()[
        0].as_serialized_hlo_module_proto()
    return ss.Module(memoryview(proto), 0, len(proto))


def _rows(module):
    """``{(folded scope, pass)}`` of the module's top-level instructions,
    and the share of named ones that resolve into the vocabulary."""
    fused = {c for instrs in module.computations.values() for it in instrs
             if it.opcode == "fusion" for c in it.called}
    found, named, resolved = set(), 0, 0
    for ident, instrs in module.computations.items():
        for it in instrs:
            if it.op_name and "/" in it.op_name and it.opcode not in ss._FREE:
                named += 1
                resolved += ss.parse_path(it.op_name) is not None
            if ident not in fused and it.scope:
                found.add((ss.strip_block(it.scope[0]), it.scope[1]))
    return found, resolved / max(1, named)


@pytest.fixture(scope="module", params=sorted(KINDS))
def lm_step(request):
    from harmony_tpu.models import TransformerConfig, TransformerTrainer

    app, required = KINDS[request.param]
    trainer = TransformerTrainer(TransformerConfig(**app), row_width=128,
                                 optimizer="adam")
    tokens = np.zeros((2, app["max_seq"] + 1), np.int32)
    return request.param, required, _rows(_compile_step(trainer, (tokens,)))


def test_lm_step_carries_its_scopes(lm_step):
    kind, required, (found, _) = lm_step
    scopes = {s for s, _ in found}
    assert required <= scopes, sorted(required - scopes)
    if kind == "gpt2":  # no rotary, no experts, no KDA in this block
        assert not {s for s in scopes if "moe." in s or "kda." in s
                    or s.endswith("mixer.rope")}


def test_lm_step_tells_the_passes_apart(lm_step):
    kind, _, (found, _) = lm_step
    for scope in ("blk*/mixer.qkv", "head"):
        assert {(scope, "fwd"), (scope, "bwd")} <= found, scope
    # the table path is outside the differentiated function
    assert {w for s, w in found if s.startswith("table.")} == {"fwd"}
    remat = {s for s, w in found if w == "remat"}
    if kind == "kimi":
        assert {"blk*/kda.proj", "blk*/kda.scan", "blk*/moe.experts"} <= remat
    else:
        assert not remat


def test_lm_step_names_resolve(lm_step):
    _, _, (_, share) = lm_step
    assert share >= 0.95, share


def test_keyed_fm_step_scopes():
    from harmony_tpu.apps.widedeep import FMTrainer

    trainer = FMTrainer(vocab_size=4095, num_slots=6, emb_dim=7)
    found, share = _rows(_compile_step(
        trainer, (np.zeros((16, 6), np.int32), np.zeros((16,), np.float32))))
    scopes = {s for s, _ in found}
    assert {"table.pull", "fm.interact", "fm.loss", "table.push"} <= scopes
    assert {("fm.interact", "fwd"), ("fm.interact", "bwd")} <= found
    assert share >= 0.95, share


def test_mlr_step_is_not_nameless():
    from harmony_tpu.apps.mlr import MLRTrainer

    trainer = MLRTrainer(num_classes=4, num_features=32,
                         features_per_partition=8)
    found, share = _rows(_compile_step(
        trainer, (np.zeros((16, 32), np.float32), np.zeros((16,), np.int32))))
    # (this table's pull is a reshape of whole tiles: no instruction left)
    assert {"compute", "table.push"} <= {s for s, _ in found}
    assert share >= 0.95, share


# ---------------------------------------------------------------------------
# the path grammar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,want", [
    ("jit(_step)/jvp(blk0)/ffn/dot_general", ("blk0/ffn", "fwd")),
    ("jit(_step)/transpose(jvp(blk11))/mixer.qkv/dot_general",
     ("blk11/mixer.qkv", "bwd")),
    ("jit(_step)/transpose(jvp(blk1))/jvp(blk1)/checkpoint/"
     "rematted_computation/kda.scan/pallas_call", ("blk1/kda.scan", "remat")),
    ("jit(_step)/transpose(jvp(blk1))/jvp(blk1)/checkpoint/ffn/mul",
     ("blk1/ffn", "bwd")),
    ("jit(_step)/compute/jvp(loss)/jit(log_softmax)/reduce_max",
     ("loss", "fwd")),
    ("jit(_step)/compute/transpose(jvp(head))/transpose", ("head", "bwd")),
    ("jit(_step)/table.push/harmony_fold_row_sections/pallas_call",
     ("table.push", "fwd")),
    ("jit(_step)/compute/jvp(blk2)/add", ("blk2", "fwd")),
    ("jit(_step)/compute/convert_element_type", ("compute", "fwd")),
    # a function's name is no scope, whatever it is called
    ("jit(loss)/jit(head)/mul", None),
    ("jit(_step)/jvp()/dot_general", None),
    ("", None),
])
def test_parse_path(path, want):
    assert ss.parse_path(path) == want


def test_step_scope_refuses_names_outside_the_vocabulary():
    with pytest.raises(ValueError):
        ss.step_scope("mlp")
    with pytest.raises(ValueError):
        ss.step_scope("blk")  # a block needs its index
    with pytest.raises(ValueError):
        ss.step_scope("ffn", 3)  # and only a block takes one
    with ss.step_scope("blk", 3), ss.step_scope("ffn"):
        pass


# ---------------------------------------------------------------------------
# (b) the wire reader on a made-up capture
# ---------------------------------------------------------------------------

def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(no: int, n: int) -> bytes:
    return _varint(no << 3) + _varint(n)


def _sub(no: int, payload: bytes) -> bytes:
    return _varint(no << 3 | 2) + _varint(len(payload)) + payload


def _packed(no: int, values) -> bytes:
    return _sub(no, b"".join(_varint(v) for v in values)) if values else b""


def _instr(ident, name, opcode, operands=(), op_name="", dims=(), called=(),
           contract=None):
    out = _sub(1, name.encode()) + _sub(2, opcode.encode())
    out += _sub(3, _int(2, 11) + _packed(3, dims))
    if op_name:
        out += _sub(7, _sub(2, op_name.encode()))
    if contract is not None:
        out += _sub(30, _packed(1, contract))
    return out + _int(35, ident) + _packed(36, operands) + _packed(38, called)


def _module(name, computations):
    return _sub(1, _sub(1, name.encode()) + b"".join(
        _sub(3, b"".join(_sub(2, i) for i in instrs) + _int(5, ident))
        for ident, instrs in computations))


STEP, PROBE = "jit__step(1)", "jit_pull_fn(2)"


def _made_up(metadata: bool = True) -> bytes:
    """An XSpace of two modules on one device: a step (a pull, a nameless
    copy between two scoped instructions, a forward ``dot``, a backward
    matmul fusion, five nameless converts from a parameter into a push) run
    twice, and a probe that names nothing, run once."""
    pre = "jit(_step)/"
    step = _module("jit__step", [
        (1, [
            _instr(1, "p0", "parameter", dims=(4, 16)),
            _instr(2, "p1", "parameter", dims=(16, 8)),
            _instr(3, "slice.1", "slice", (1,), pre + "table.pull/slice",
                   (4, 16)),
            _instr(4, "copy.1", "copy", (3,), dims=(4, 16)),
            _instr(5, "dot.1", "dot", (4, 2),
                   pre + "jvp(blk0)/ffn/dot_general", (4, 8), contract=(1,)),
            _instr(6, "fusion.1", "fusion", (5, 2), dims=(4, 16), called=(2,)),
            *[_instr(7 + i, f"convert.{i + 1}", "convert",
                     (1 if i == 0 else 6 + i,), dims=(4, 16))
              for i in range(5)],
            _instr(12, "add.1", "add", (11, 6), pre + "table.push/add",
                   (4, 16)),
        ]),
        (2, [
            _instr(1, "q0", "parameter", dims=(4, 8)),
            _instr(2, "q1", "parameter", dims=(16, 8)),
            _instr(3, "dot.2", "dot", (1, 2),
                   pre + "transpose(jvp(blk0))/ffn/dot_general", (4, 16),
                   contract=(1,)),
        ]),
    ])
    probe = _module("jit_pull_fn", [(1, [
        _instr(1, "p0", "parameter", dims=(4, 16)),
        _instr(2, "copy.9", "copy", (1,), dims=(4, 16))])])
    names = {1: STEP, 2: PROBE}
    ops = ["slice.1", "copy.1", "dot.1", "fusion.1",
           *[f"convert.{i + 1}" for i in range(5)], "add.1", "copy.9"]
    for i, op in enumerate(ops):
        names[10 + i] = f"%{op} = f32[4,16]{{1,0}} {op.split('.')[0]}(%x)"
    ident = {v.split(" ")[0][1:]: k for k, v in names.items() if k >= 10}

    def event(meta, start_ns, ns):
        return _sub(4, _int(1, meta) + _int(2, start_ns * 1000)
                    + _int(3, ns * 1000))

    def run(t0):  # every instruction of the step 10 ns, the matmuls 100
        out, t = b"", t0
        for op in ops[:-1]:
            ns = 100 if op in ("dot.1", "fusion.1") else 10
            out += event(ident[op], t, ns)
            t += ns
        return out

    device = _sub(2, b"/device:TPU:0")
    device += _sub(3, _int(1, 1) + _sub(2, b"XLA Modules")
                   + event(1, 0, 1000) + event(2, 2000, 100)
                   + event(1, 5000, 1000))
    device += _sub(3, _int(1, 2) + _sub(2, b"XLA Ops") + run(0)
                   + event(ident["copy.9"], 2000, 50) + run(5000))
    for k, name in names.items():
        device += _sub(4, _int(1, k) + _sub(2, _int(1, k)
                                            + _sub(2, name.encode())))
    meta = _sub(2, ss.METADATA_PLANE.encode())
    for k, (name, proto) in enumerate([(STEP, step), (PROBE, probe)], 1):
        meta += _sub(4, _int(1, -k) + _sub(2, _int(1, -k)
                     + _sub(2, name.encode())
                     + _sub(5, _int(1, 1) + _sub(6, proto))))
    return _sub(1, device) + (_sub(1, meta) if metadata else b"")


@pytest.fixture()
def made_up(tmp_path, monkeypatch):
    monkeypatch.setattr(ss, "INHERIT_DEPTH", 3)
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(_made_up())
    return ss.reduce_file(str(path))[0]


def test_made_up_modules_stay_apart(made_up):
    assert set(made_up) == {STEP, PROBE}
    assert made_up[STEP]["executions"] == 2
    assert made_up[PROBE]["executions"] == 1
    assert ss.is_step(made_up[STEP]) and not ss.is_step(made_up[PROBE])
    assert [(r.scope, r.calls) for r in made_up[PROBE]["rows"]] == [
        ("unscoped:copy", 1)]
    rows, seconds, steps = ss.step_rows(made_up)
    assert steps == pytest.approx(2.0)
    assert made_up[STEP]["step_s"] == pytest.approx(280e-9)
    assert seconds == pytest.approx(made_up[STEP]["seconds"])


def test_made_up_shares_sum_to_the_whole(made_up):
    entry = made_up[STEP]
    assert entry["seconds"] == pytest.approx(2 * 280e-9)
    assert sum(r.seconds for r in entry["rows"]) == pytest.approx(
        entry["seconds"])
    for run in entry["runs"]:
        assert sum(run.values()) == pytest.approx(280e-9)


def test_made_up_inheritance_reaches_a_copy_and_stops_at_the_bound(made_up):
    rows = {(r.scope, r.which, r.klass): r for r in made_up[STEP]["rows"]}
    # the nameless copy between the pull's slice and the forward dot is the
    # dot's (its only user); the dot keeps its own class
    layout = rows[("blk0/ffn", "fwd", "layout")]
    assert layout.calls == 2 and layout.inherited_s == pytest.approx(20e-9)
    assert rows[("blk0/ffn", "fwd", "matmul")].inherited_s == 0
    # the fusion is booked where its dot is
    assert rows[("blk0/ffn", "bwd", "matmul")].calls == 2
    # three converts from the push inherit it, the two beyond the bound
    # (fed by a parameter, which names nothing) stay nameless
    push = rows[("table.push", "fwd", "layout")]
    assert push.calls == 6 and push.inherited_s == pytest.approx(60e-9)
    assert rows[("unscoped:convert", "", "layout")].calls == 4


def test_made_up_matmul_flops(made_up):
    rows = {(r.scope, r.which): r for r in made_up[STEP]["rows"]
            if r.klass == "matmul"}
    # [4, 16] x [16, 8]: 2 x 4 x 8 x 16, and its transpose: 2 x 4 x 16 x 8
    assert rows[("blk0/ffn", "fwd")].flops == 2 * 1024
    assert rows[("blk0/ffn", "bwd")].flops == 2 * 1024


def test_no_metadata_plane_reports_nothing(tmp_path):
    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(_made_up(metadata=False))
    assert ss.reduce_file(str(path)) == {}
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert ss.reduce_file(str(empty)) == {}
    assert ss.find_xplane(str(tmp_path / "nothing")) is None
    assert ss.find_xplane(str(tmp_path)) in (str(path), str(empty))


def test_self_times_partition_nested_events():
    own = {name: t for _, _, name, t in ss.self_times(
        [(0, 100, "while"), (10, 40, "a"), (40, 90, "b"), (100, 120, "c")])}
    assert own == {"while": 20, "a": 30, "b": 50, "c": 20}


# ---------------------------------------------------------------------------
# (b) the recorded chip fixture: a known step of D = 256, F = 1024, B = 512
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return ss.reduce_file(FIXTURE)[0]


def test_recorded_step_and_probe_stay_apart(recorded):
    steps = {k: v for k, v in recorded.items() if ss.is_step(v)}
    assert len(steps) == 1 and next(iter(steps)).startswith("jit__step(")
    assert next(iter(steps.values()))["executions"] == 4
    probe = [v for k, v in recorded.items() if k.startswith("jit_pull_fn(")]
    assert len(probe) == 1 and probe[0]["executions"] == 2
    assert all(r.scope.startswith("unscoped:") for r in probe[0]["rows"])


def test_recorded_shares_and_passes(recorded):
    rows, seconds, steps = ss.step_rows(recorded)
    assert steps == pytest.approx(4.0, rel=0.05)
    assert sum(r.seconds for r in rows) == pytest.approx(seconds)
    found = {(r.scope, r.which) for r in rows}
    assert {("table.pull", "fwd"), ("table.push", "fwd"),
            ("blk*/ffn", "fwd"), ("blk*/ffn", "bwd"), ("head", "fwd"),
            ("head", "bwd"), ("loss", "fwd")} <= found
    unscoped = sum(r.seconds for r in rows if r.scope.startswith("unscoped:"))
    assert unscoped / seconds < 0.05


def test_recorded_matmul_flops(recorded):
    rows, executions = ss.step_rows(recorded)[0], 4
    unit = 2 * 512 * 256 * 1024  # one [B, D] x [D, F] matmul
    flops = {}
    for r in rows:
        if r.klass == "matmul":
            flops[r.scope] = flops.get(r.scope, 0.0) + r.flops / executions
    # ffn: two forward, three backward (the input carries no gradient);
    # head: one forward, two backward
    assert flops["blk*/ffn"] == pytest.approx(5 * unit)
    assert flops["head"] == pytest.approx(3 * unit)


# ---------------------------------------------------------------------------
# (c) the vocabulary lint
# ---------------------------------------------------------------------------

def _step_scope_literals():
    """``[(file, line, first argument)]`` of every ``step_scope(...)`` call
    under harmony_tpu/ (the helper's own module left out)."""
    out = []
    for base, _dirs, names in os.walk(os.path.join(ROOT, "harmony_tpu")):
        for name in names:
            path = os.path.join(base, name)
            if not name.endswith(".py") or path == ss.__file__:
                continue
            with open(path) as f:
                text = f.read()
            if "step_scope(" not in text:
                continue
            for node in ast.walk(ast.parse(text)):
                if (isinstance(node, ast.Call)
                        and getattr(node.func, "id", getattr(
                            node.func, "attr", "")) == "step_scope"):
                    out.append((os.path.relpath(path, ROOT), node.lineno,
                                node.args[0] if node.args else None))
    return out


def test_every_step_scope_literal_is_in_the_vocabulary():
    calls = _step_scope_literals()
    assert len(calls) > 40  # the model, the table path and the trainers
    for path, line, arg in calls:
        assert isinstance(arg, ast.Constant) and arg.value in ss.VOCABULARY, (
            f"{path}:{line}: step_scope takes a literal of VOCABULARY")
    used = {arg.value for _, _, arg in calls}
    assert used == set(ss.VOCABULARY), set(ss.VOCABULARY) ^ used


def test_scope_sites_are_listed_and_ride_in_the_cache_key(monkeypatch):
    sites = {os.path.relpath(path, "harmony_tpu")
             for path, _, _ in _step_scope_literals()}
    assert sites == set(ss.SCOPE_SITES)
    digest = ss.scope_digest()
    assert re.fullmatch(r"[0-9a-f]{6}", digest)

    def _step():
        pass

    assert ss.in_cache_key(_step).__name__ == f"_step_{digest}"
    # another vocabulary is another name, so another cache key
    monkeypatch.setattr(ss, "VOCABULARY", ss.VOCABULARY + ("extra",))
    assert ss.scope_digest.__wrapped__() != digest


def test_every_vocabulary_entry_is_documented():
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        rows = set(re.findall(r"^\| `([a-z_.<>]+)`", f.read(), re.M))
    missing = {("blk<i>" if v == ss.BLOCK else v) for v in ss.VOCABULARY
               } - rows
    assert not missing, sorted(missing)
