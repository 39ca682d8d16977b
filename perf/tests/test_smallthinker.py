"""Checks of the ``smallthinker-21b-a3b`` configuration's own files (PR 36).
Run with the rest of ``perf/tests``; CPU only, nothing here is a
measurement."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf.run import load_by_path  # noqa: E402

CONFIG = json.load(open(os.path.join(PERF, "configs",
                                     "smallthinker-21b-a3b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "smallthinker-21b-a3b.solo"
LAYOUT = [0, 1, 1, 1] * 13
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
WORK = load_by_path("work", "smallthinker")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]


def test_published_keys_verbatim_and_the_three_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows_held"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows_held"]) == (4, 8, 18992)
    assert 8 * CONFIG["experts_held"] == CONFIG["moe_num_primary_experts"]
    assert 8 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "smallthinker-21b-a3b")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(entry["why"]) <= 200
    for key in ("deployment", "assumed"):
        assert CONFIG[key]
    for key in ("router_input", "window_edge", "moe_aux_weight", "optimizer",
                "init", "wqkv", "expert_activation", "data"):
        assert CONFIG["assumed"][key]


def test_app_params_are_the_sources_sizes():
    app, c = CONFIG["job"]["app_params"], CONFIG
    assert (app["d_model"], app["n_heads"], app["n_kv_heads"],
            app["mha_head_dim"], app["d_ff"], app["moe_experts"],
            app["moe_top_k"], app["moe_norm_topk"], app["norm_eps"],
            app["rope_theta"], app["tie_embeddings"], app["window"],
            app["max_seq"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["moe_ffn_hidden_size"], c["moe_num_primary_experts"],
        c["moe_num_active_primary_experts"], c["norm_topk_prob"],
        c["rms_norm_eps"], c["rope_theta"], c["tie_word_embeddings"],
        c["sliding_window_size"], c["max_position_embeddings"])
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        c["num_hidden_layers"], c["experts_held"], c["vocab_rows_held"])
    n = c["num_hidden_layers"]
    assert app["window_layers"] == [
        i for i in range(n) if c["sliding_window_layout"][i]]
    assert c["rope_layout"] == c["sliding_window_layout"]
    assert (app["pos"], app["ffn"], app["moe_act"], app["moe_score"]
            if "moe_score" in app else "softmax") == (
        "rope", "swiglu", "relu", "softmax")
    assert app["moe_route_block_input"] is True and app["moe_every"] == 1
    assert CONFIG["job"]["data_args"] == {
        "seq_len": app["max_seq"] + 1, "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == app["max_seq"] == 16384
    assert CONFIG["job"]["batch"] * app["max_seq"] * app["moe_top_k"] == 98304
    for key, text in CONFIG["job"]["why"].items():
        assert text and "TODO" not in text, key


def test_the_trainer_takes_the_app_params_and_counts_371m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer
    from harmony_tpu.models.moe import chunk_plan

    tr = TransformerTrainer(**CONFIG["job"]["app_params"])
    assert tr.num_params == 370_547_200
    assert tr.hyperparams() == {"lr": 2e-6, "beta2": 0.95}
    # unit embedding rows and a lr that leaves the routers where they start:
    # what keeps every layer at one chunk in every step (PERF.md section 6),
    # and a feed every two steps for the harness's rate fit
    assert tr.config.embed_std == 1.0
    assert CONFIG["job"]["env"] == {"HARMONY_EPOCH_WINDOW": "2"}
    assert tr.config.layer_kinds() == ("full", "swa", "swa", "swa")
    assert tr.config.moe_layers() == (0, 1, 2, 3)
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    layer = shapes["layers"][0]
    assert layer["wqkv"].shape == (2560, 3584 + 512 + 512)
    assert layer["wo"].shape == (3584, 2560)
    assert size({k: layer[k] for k in ("wqkv", "wo")}) == 20_971_520
    moe = layer["moe"]
    assert moe["router"].shape == (2560, 64) and set(moe) == {
        "router", "wg", "wu", "wd"}
    assert size({k: moe[k] for k in ("wg", "wu", "wd")}) == 47_185_920
    assert size(layer) == 68_326_400
    assert shapes["embed"].shape == (18992, 2560) == shapes["head"].shape[::-1]
    assert 4 * size(layer) + 2 * 48_619_520 + 2560 == tr.num_params
    # Moonlight's plan: a chunk of 24,576 of the 98,304 slots
    assert chunk_plan(98304, 8, 64) == (24576, 4)


def test_work_functions_count_the_band_by_layer_kind():
    app, batch = CONFIG["job"]["app_params"], CONFIG["job"]["batch"]
    S, W = 16384, 4096
    assert WORK.band_pairs(S, W) == W * (W + 1) // 2 + (S - W) * W == 58_722_304
    assert WORK.causal_pairs(S) == 134_225_920
    assert WORK.band_pairs(S, S + 5) == WORK.causal_pairs(S)
    assert WORK.band_pairs(80, 16) == sum(min(i + 1, 16) for i in range(80))
    assert WORK.layer_kinds(app) == ("full", "swa", "swa", "swa")
    fwd = WORK.flash_flops_per_call(app, batch, "harmony_flash_win_fwd")
    assert fwd == 2 * 2 * 128 * 28 * 58_722_304
    assert WORK.flash_flops_per_call(
        app, batch, "harmony_flash_win_bwd_dkv") == 2 * fwd
    assert WORK.flash_flops_per_call(
        app, batch, "harmony_flash_win_bwd_dq") == 1.5 * fwd
    assert WORK.flash_flops_per_call(app, batch, "harmony_flash_fwd") == (
        2 * 2 * 128 * 28 * 134_225_920)
    # a step's attention: 2,304 FLOPs a pair over 1 full + 3 windowed layers
    step = sum(WORK.flash_flops_per_call(app, batch, k) * (1 if "_win_" not
               in k else 3) for k in WORK.KERNELS)
    assert step == pytest.approx(20.0e12, rel=0.01)
    # band bytes: every operand once, K and V once a K/V head
    q_rows, kv_rows = 28 * S * 128 * 2, 4 * S * 128 * 2
    assert WORK.flash_bytes_per_call(app, batch, "harmony_flash_win_fwd") == (
        2 * q_rows + 2 * kv_rows + 28 * S * 4)
    assert WORK.flash_bytes_per_call(app, batch, "harmony_flash_win_fwd") == (
        WORK.flash_bytes_per_call(app, batch, "harmony_flash_fwd"))
    for kernel in WORK.KERNELS:
        row = WORK.bound_seconds(app, batch, kernel, PEAKS)
        assert row["binds"] == "bf16 MXU peak"
        assert row["seconds_bound"] == pytest.approx(row["flops"] / 197e12)
    # the program's kernel names are the work file's
    from harmony_tpu.ops import attention

    assert set(WORK.KERNELS) == set(attention._KERNEL_NAMES.values()) | set(
        attention._WIN_KERNEL_NAMES.values())


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"swa_flash_roofline_share", "flash_masked_share",
            "flash_time_share", "moe_time_share", "moe_routing_time_share",
            "moe_chunks_per_call", "expert_load_max_over_mean",
            "mixer_time_share", "device_idle_share", "peak_hbm_share",
            "unscoped_time_share"} <= mine
    # the readers whose work functions do not count this configuration
    assert not {"flash_roofline_share", "gmm_roofline_share",
                "routed_gmm_roofline_share", "kda_time_share",
                "kda_roofline_share"} & mine
    for name, better, source in (
            ("swa_flash_roofline_share", "higher", "device_trace"),
            ("flash_masked_share", "lower", "program_counter")):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["layer"], entry["unit"], entry["better"],
                entry["source"]) == ("lm_tokens_per_s", "kernels", "%", better,
                                     source)
        reader = load_by_path("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            "kernels", "%", source)
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    # appended after Kimi Linear's; a later PR appends after it, so no "last"
    assert rate["workloads"].index(CELL) \
        > rate["workloads"].index("kimi-linear-48b-a3b.solo")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 8 and four >= 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace without the kernels (the recorded
    one-chip fixture: every trace of the parent), a program without the
    gauges: None, and nothing raised."""
    from perf import trace_reduce

    fk = load_by_path("layer_metrics", "_flash_kernels")
    for text, want in (("harmony_flash_win_fwd", "harmony_flash_win_fwd"),
                       ("harmony_flash_win_bwd_dkv.3",
                        "harmony_flash_win_bwd_dkv"),
                       ("harmony_flash_bwd_dq.12", "harmony_flash_bwd_dq"),
                       ("harmony_kda_fwd.1", None), ("fusion.7", None)):
        m = fk.KERNEL.match(text)
        assert (m.group(1) if m else None) == want
    roof = load_by_path("layer_metrics", "swa_flash_roofline_share")
    assert roof.read({"trace": None}) is None
    assert roof.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None
    masked = load_by_path("layer_metrics", "flash_masked_share")
    assert masked.read({"phases": {}}) is None
    assert masked.read({"phases": {"no-such-cell-run-t0": None}}) is None
    profile = trace_reduce.load(os.path.join(HERE, "fixture_1chip.xplane.pb"))
    assert fk.kernel_seconds(profile) is None or not any(
        "_win_" in k for k in fk.kernel_seconds(profile)["kernels"])


def test_roofline_is_a_share_of_the_band_and_no_kernel_passes_100(
        monkeypatch, capsys):
    """A hand-made op list at the cell's sizes, each call taking twice its
    bound: the windowed calls are credited the band's pairs, not the
    triangle's, and every share reads 50."""
    from perf import trace_reduce

    fk = load_by_path("layer_metrics", "_flash_kernels")
    app = CONFIG["job"]["app_params"]
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops, t = [], 0.0
    for kernel in sorted(WORK.KERNELS):
        ns = 2e9 * WORK.bound_seconds(app, 1, kernel, PEAKS)["seconds_bound"]
        for i in range(3 if "_win_" in kernel else 1):
            ops.append((call(f"{kernel}.{i + 1}"), t, t + ns))
            t += ns
    ops.append(("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
                t, 2 * t))
    monkeypatch.setattr(fk.trace_reduce, "device_ops", lambda profile: {0: ops})
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    found = fk.kernel_seconds(None)
    assert found["kernels"]["harmony_flash_win_fwd"]["calls"] == 3
    assert found["kernels"]["harmony_flash_fwd"]["calls"] == 1
    roof = load_by_path("layer_metrics", "swa_flash_roofline_share")
    share = load_by_path("layer_metrics", "flash_time_share")
    monkeypatch.setattr(roof, "of_this_run", lambda: found)
    monkeypatch.setattr(share, "of_this_run", lambda: found)
    obs = {"trace": {"busy_s": 1.0}, "phases": {CELL + "-run-t0": None}}
    assert share.read(obs) == pytest.approx(50.0)   # both names are summed
    import jax

    class _Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert roof.read(obs) == pytest.approx(50.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "swa_flash_roofline"
    assert set(line["kernels"]) == set(WORK.KERNELS)
    for row in line["kernels"].values():
        assert row["roofline_share"] == pytest.approx(50.0) and (
            row["roofline_share"] <= 100.0)
        assert row["binds"] == "bf16 MXU peak"
    ratio = (line["kernels"]["harmony_flash_win_fwd"]["gflop_per_call"]
             / line["kernels"]["harmony_flash_fwd"]["gflop_per_call"])
    assert ratio == pytest.approx(58_722_304 / 134_225_920)
    # a cell without a window has nothing for this reader
    monkeypatch.setitem(obs, "phases", {"moonlight-16b-a3b.solo-run-t0": None})
    assert roof.read(obs) is None


def test_masked_share_weighs_the_gauges_by_elements_and_calls(monkeypatch):
    from harmony_tpu.metrics.registry import get_registry
    from harmony_tpu.runtime import progcache

    job = CELL + "-run-t0"
    band = lambda share, computed: {
        "window": 0, "kv_heads": 4, "band_grid_steps": 1, "sub_blocks": 1,
        "masked_sub_blocks": 1, "computed": computed, "masked_share": share}
    monkeypatch.setattr("harmony_tpu.tracing.span.current_job", lambda: job)
    progcache.note_kernel_plan("harmony_flash_fwd", 512, 16384, 1024, 1, True,
                               d=128, dv=128, band=band(0.1, 100.0))
    progcache.note_kernel_plan("harmony_flash_win_fwd", 512, 16384, 1024, 1,
                               True, d=128, dv=128, band=band(0.3, 50.0))
    progcache.note_kernel_plan("harmony_flash_win_bwd_dq", 512, 16384, 512, 1,
                               True, d=128, dv=128, band=band(0.2, 40.0))
    from harmony_tpu.metrics import kda

    kda.note_layer_kinds(job, ("full", "swa", "swa", "swa"))
    reader = load_by_path("layer_metrics", "flash_masked_share")
    # remat: forwards twice. full fwd 2 x 100 @ 0.1; win fwd 3 x 2 x 50 @ 0.3;
    # win dq 3 x 40 @ 0.2
    want = 100.0 * (200 * 0.1 + 300 * 0.3 + 120 * 0.2) / (200 + 300 + 120)
    assert reader.read({"phases": {job: None}}) == pytest.approx(want)
    assert get_registry() is not None


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (a window of 16 over 80
    positions, 4 query heads over 2 K/V heads, 8 experts top-2 with 4 held)
    through the jobserver, the logits check and the replay, to a last line
    that says ``correct``."""
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
    assert check["ok"] and check["dtype"] == "float32" and check["window"] == 16
    assert set(check["detected"]) == set(
        load_by_path("reference", "smallthinker-21b-a3b").LOGIT_ABLATIONS)
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
