"""Checks of the ``ouro-2.6b`` configuration's own files (PR 57). Run with the
rest of ``perf/tests`` (and, all but the rehearsal, collected by
``tests/test_ouro.py`` under tier-1); CPU only, nothing here is a
measurement."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf.run import load_by_path  # noqa: E402

CONFIG = json.load(open(os.path.join(PERF, "configs", "ouro-2.6b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "ouro-2.6b.solo"
APP = CONFIG["job"]["app_params"]
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}
WORK = load_by_path("work", "ouro")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]


def hand_ouro():
    """FLOPs a token of the corpus, by hand from the published shapes: 4
    layers passed 4 times = 16 applications of attention's 2048 x 6144 +
    2048 x 2048 and a SwiGLU of 3 x 2048 x 5632; the triangle at 16 heads of
    128 over 4,096 positions in each; 4 readouts of 49,152 columns."""
    d, f, hd, S, V = 2048, 5632, 128, 4096, 49152
    dense = 6 * 16 * (d * 3 * d + d * d + 3 * d * f)
    pairs = 3 * 16 * 16 * 2 * (hd + hd) * (S * (S + 1) // 2) / S
    return dense + pairs + 6 * 4 * d * V


#: what ``test_step_mfu.py``'s table of hand counts lacks for this cell (a PR
#: may not edit that file): ``conftest.py`` here, and the tier-1 collector
#: ``tests/test_perf_step_scope_readers.py``, add it before its cases run
HAND = {"ouro-2.6b": hand_ouro}


# -- the configuration file ----------------------------------------------------

def test_published_keys_verbatim_and_the_one_cut():
    assert CONFIG["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                                "blob/main/config.json")
    assert CONFIG["reduced"] == ["num_hidden_layers"] == list(
        CONFIG["reduced_from"])
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] == 4 and str(value) in CONFIG["reduced_from"][key]
        else:
            assert CONFIG[key] == value, key
    entry = next(c for c in BENCH["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "perf/configs/ouro-2.6b.json"
    for key in ("norm_placement", "final_norm_in_loop", "gate", "no_biases",
                "loss", "optimizer", "embed_std", "remat", "data"):
        assert CONFIG["assumed"][key], key
    assert "first 4 of 48 layers" in CONFIG["deployment"]
    assert "30%" in CONFIG["deployment"] and "3.5%" in CONFIG["deployment"]


def test_app_params_are_the_sources_sizes():
    app = APP
    assert (app["d_model"], app["d_ff"], app["vocab_size"]) == (
        CONFIG["hidden_size"], CONFIG["intermediate_size"],
        CONFIG["vocab_size"])
    assert app["n_heads"] == CONFIG["num_attention_heads"] == CONFIG[
        "num_key_value_heads"]
    assert app["d_model"] // app["n_heads"] == CONFIG["head_dim"]
    assert app["n_layers"] == CONFIG["num_hidden_layers"] == 4
    assert app["loop_steps"] == CONFIG["total_ut_steps"] == 4
    assert app["rope_theta"] == CONFIG["rope_theta"]
    assert app["norm_eps"] == CONFIG["rms_norm_eps"]
    assert app["tie_embeddings"] is CONFIG["tie_word_embeddings"] is False
    assert (app["pos"], app["ffn"]) == ("rope", "swiglu")
    assert app["sandwich_norm"] and app["exit_gate"] and app["remat"]
    assert app["exit_entropy_weight"] == 0.05
    assert app["max_seq"] == 4096 <= CONFIG["max_position_embeddings"]
    job = CONFIG["job"]
    assert job["data_args"] == {"seq_len": 4097, "vocab_size": 49152}
    assert (job["batch"], job["num_mini_batches"], job["units_per_example"],
            job["comm_probe_period"]) == (1, 1, 4096, 0)
    assert (job["reference"], job["flops_fn"]) == (
        "ouro-2.6b", "ouro:train_flops_per_token")
    for key, text in job["why"].items():
        assert text and "TBD" not in text and "TODO" not in text, key
    assert CONFIG["rehearse"]["app_params"]["loop_steps"] == 4


def test_the_trainer_takes_the_app_params_and_counts_407m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**APP)
    assert tr.num_params == 406_884_353
    assert tr.hyperparams() == {"lr": APP["step_size"]}
    assert tr.config.layer_kinds() == ("mha",) * 4
    assert (tr.config.loop_steps, tr.config.head_dim) == (4, 128)
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    layer = shapes["layers"][0]
    assert set(layer) == {"ln1", "ln1_post", "ln2", "ln2_post", "wqkv", "wo",
                          "w1", "w2", "w3"}
    assert (size(layer["wqkv"]), size(layer["wo"])) == (12_582_912, 4_194_304)
    assert size({k: layer[k] for k in ("w1", "w2", "w3")}) == 34_603_008
    assert size(layer) == 51_388_416
    assert shapes["embed"].shape == (49152, 2048) == shapes["head"].shape[::-1]
    assert (shapes["exit_w"].shape, shapes["exit_b"].shape) == ((2048,), ())
    for part in ("12,582,912", "4,194,304", "34,603,008", "51,388,416",
                 "205,553,664", "100,663,296", "406,884,353"):
        assert part in CONFIG["deployment"], part


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "solo", 1)
    assert len(cell["why"]) <= 200 and "closed loop" in cell["why"]
    assert len(BENCH["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, better, source, layer in (
            ("exit_gate_time_share", "lower", "device_trace", "model"),
            ("exit_entropy_share", "higher", "program_counter", "model"),
            ("exit_readout_roofline_share", "higher", "device_trace",
             "kernels")):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "lm_tokens_per_s" and m["better"] == better
        assert (m["source"], m["layer"]) == (source, layer)
        reader = load_by_path("layer_metrics", name)
        assert (reader.SOURCE, reader.LAYER, reader.UNIT) == (source, layer, "%")
    # every list gpt2-124m.solo is in, and the two flash lists
    for m in BENCH["per_layer"]:
        listed = m.get("workloads")
        if listed and ("gpt2-124m.solo" in listed or m["name"] in (
                "flash_time_share", "hetero_flash_roofline_share")):
            assert CELL in listed, m["name"]
    # no reader of experts, windows or scans
    for name in ("moe_time_share", "flash_masked_share", "kda_time_share",
                 "ssd_time_share", "flash_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 64 * 1024


# -- the work functions ------------------------------------------------------

def test_flops_a_token_equal_the_hand_count_at_two_shapes():
    from perf import work_models

    assert hand_ouro() == 8_153_923_584.0
    assert work_models.count(CONFIG["job"], "flops_fn") == pytest.approx(
        hand_ouro(), rel=1e-12)
    parts = work_models.split(CONFIG["job"])
    assert tuple(parts) == work_models.PARTS
    assert parts["routed"] == parts["scans"] == 0.0
    assert parts["readout"] == 6 * 4 * 2048 * 49152 == 2_415_919_104
    assert sum(parts.values()) == pytest.approx(hand_ouro(), rel=1e-12)
    # the share the cut distorts: the exits over the cell's count, and over
    # the whole model's (48 layers passed 4 times)
    whole = hand_ouro() + 44 / 4 * (parts["dense"] + parts["attention_pairs"])
    assert 0.29 < parts["readout"] / hand_ouro() < 0.31
    assert 0.03 < parts["readout"] / whole < 0.04
    # the rehearse preset, by hand again: d 64, 4 heads of 16, 2 layers x 4
    # passes, a SwiGLU of 96, 48 positions, 512 rows
    tiny = {**APP, **CONFIG["rehearse"]["app_params"]}
    d, hd, S = 64, 16, 48
    dense = 6 * 8 * (d * 3 * d + d * d + 3 * d * 96)
    pairs = 3 * 8 * 4 * 2 * (hd + hd) * (S * (S + 1) // 2) / S
    assert WORK.train_flops_per_token(tiny) == pytest.approx(
        dense + pairs + 6 * 4 * d * 512, rel=1e-12)
    with pytest.raises(ValueError, match="not counted here"):
        WORK.train_flops_per_token({**APP, "window": 4})
    with pytest.raises(ValueError, match="not counted here"):
        WORK.train_flops_per_token({**APP, "loop_steps": 1})
    with pytest.raises(ValueError, match="not counted here"):
        WORK.train_flops_per_token({**APP, "tie_embeddings": True})


def test_each_kernel_is_credited_by_the_shapes_and_bound_by_the_right_peak():
    from harmony_tpu.ops import attention as A
    from harmony_tpu.ops import readout_loss as R

    assert set(WORK.KERNELS) == set(A._KERNEL_NAMES.values())
    assert set(WORK.READOUT_KERNELS) == {R.FWD_NAME, R.DX_NAME, R.DW_NAME}
    triangle = 4096 * 4097 // 2
    fwd = WORK.bound_seconds(APP, 1, "harmony_flash_fwd", PEAKS)
    bwd = WORK.bound_seconds(APP, 1, "harmony_flash_bwd", PEAKS)
    assert fwd["flops"] == 2 * 2 * 128 * 16 * triangle
    assert bwd["flops"] == 2 * 5 * 128 * 16 * triangle
    assert fwd["bytes"] == 4 * 16 * 4096 * 128 * 2 + 16 * 4096 * 4
    assert fwd["binds"] == bwd["binds"] == "bf16 MXU peak"
    assert WORK.heads(APP, "mha") == 16
    for kernel in WORK.READOUT_KERNELS:
        row = WORK.readout_bound_seconds(APP, 1, kernel, PEAKS)
        assert row["flops"] == 2 * 4096 * 49152 * 2048
        assert row["bytes"] >= 4096 * 49152 * 4  # the float32 logits, once
        # 2,048 wide: ~800 FLOPs a byte against the chip's 240
        assert row["binds"] == "bf16 MXU peak"
        assert row["seconds_bound"] == row["flops"] / PEAKS["bf16_flops"]
    dw = WORK.readout_bound_seconds(APP, 1, "harmony_readout_bwd_dw", PEAKS)
    assert dw["bytes"] == (4096 * 49152 * 4 + 4096 * 2048 * 2
                           + 2048 * 49152 * 4)


# -- the readers -------------------------------------------------------------

def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace without the kernels or the scope, a
    program without the counters (every trace and registry of the parent):
    None, and nothing raised."""
    roof = load_by_path("layer_metrics", "exit_readout_roofline_share")
    gate = load_by_path("layer_metrics", "exit_gate_time_share")
    entropy = load_by_path("layer_metrics", "exit_entropy_share")
    assert roof.read({"trace": None}) is None
    assert roof.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None
    assert gate.read({"trace": None}) is None
    assert entropy.read({}) is None
    assert entropy.read({"phases": {"no-such-job-run-t0": None}}) is None
    assert gate.SCOPE == "exit.gate"


def test_readout_roofline_credits_every_call_and_passes_no_100(
        monkeypatch, capsys):
    """A hand-made op list at the cell's sizes, four calls of each kernel,
    each taking twice its bound: every share reads 50; a kernel the work file
    has no row for is named and the metric left out."""
    import jax

    from perf import trace_reduce

    roof = load_by_path("layer_metrics", "exit_readout_roofline_share")
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops, t = [], 0.0
    for kernel in sorted(WORK.READOUT_KERNELS):
        ns = 2e9 * WORK.readout_bound_seconds(APP, 1, kernel, PEAKS)[
            "seconds_bound"]
        for i in range(4):
            ops.append((call(f"{kernel}.{i + 1}"), t, t + ns))
            t += ns
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    monkeypatch.setattr(roof.trace_reduce, "device_ops",
                        lambda profile: {0: ops})
    monkeypatch.setattr(roof, "_load", lambda: object())
    kind = type("D", (), {"device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(jax, "devices", lambda *a: [kind])
    obs = {"trace": {"busy_s": 1.0}, "phases": {CELL + "-run-t0": None}}
    assert roof.read(obs) == pytest.approx(50.0)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["line"] == "exit_readout_roofline" and not line["uncounted"]
    assert {k: (r["calls"], r["binds"]) for k, r in line["kernels"].items()} \
        == {k: (4, "bf16 MXU peak") for k in WORK.READOUT_KERNELS}
    assert all(r["roofline_share"] == pytest.approx(50.0)
               for r in line["kernels"].values())
    ops.append((call("harmony_readout_bwd_other.1"), t, t + 1.0))
    assert roof.read(obs) is None
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["uncounted"] == ["harmony_readout_bwd_other"]


def test_exit_entropy_share_reads_the_jobs_mean_exit_distribution():
    """The counters as the trainer feeds them: a fresh gate of four passes
    reads 87.5, a gate collapsed onto one pass 0."""
    from harmony_tpu.metrics import loop

    entropy = load_by_path("layer_metrics", "exit_entropy_share")
    fresh, gone = CELL + "-run-fresh", CELL + "-run-collapsed"
    for _ in range(3):
        loop.observe(fresh, [[2048.0, 1024.0, 512.0, 512.0]] * 2,
                     [[11.3, 11.2, 11.1, 11.0]] * 2)
    loop.observe(gone, [[0.0, 4096.0, 0.0, 0.0]], [[11.0] * 4])
    assert entropy.read({"phases": {fresh: None}}) == pytest.approx(
        100 * (0.5 * math.log(2) + 0.25 * math.log(4)
               + 0.25 * math.log(8)) / math.log(4)) == pytest.approx(87.5)
    assert entropy.read({"phases": {gone: None}}) == pytest.approx(0.0)
    from harmony_tpu.metrics.registry import get_registry, parse_exposition

    fams = parse_exposition(get_registry().expose())
    positions = {l["job"]: v for _, l, v in
                 fams["harmony_loop_exit_positions_total"]["samples"]}
    assert positions[fresh] == 6 * 4096 and positions[gone] == 4096
    ce = {l["step"]: v for _, l, v in fams["harmony_loop_exit_ce"]["samples"]
          if l["job"] == fresh}
    assert ce == {"1": 11.3, "2": 11.2, "3": 11.1, "4": 11.0}


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (2 layers run 4 times, the
    four norms, the gate and the entropy term) through the jobserver, the
    check of every exit and the replay, to a last line that says
    ``correct``."""
    for _ in range(3):
        # the measured job is sized from the warm-up's rate; on a loaded CPU
        # host it can end inside the window, which is not what is tested
        out = subprocess.run(
            [sys.executable, os.path.join(PERF, "run.py"), "--workload", CELL,
             "--rehearse", "--seconds", "6", "--seed", "2147483659"],
            cwd=ROOT, capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [json.loads(l) for l in out.stdout.splitlines()
                 if l.startswith("{")]
        window = next(l for l in lines if l.get("line") == "window")
        if not window["ended_before_window_end"]:
            break
    check = next(l for l in lines if l.get("line") == "logits_check")
    assert check["ok"] and check["dtype"] == "float32"
    assert len(check["program"]["exits"]) == 4
    assert set(check["detected"]) == set(
        load_by_path("reference", "ouro-2.6b").RUN_ABLATIONS)
    assert all(check["held"].values())
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
