"""Checks of the ``kimi-linear-48b-a3b`` configuration's own files (PR 31). Run
with the rest of ``perf/tests``; CPU only, nothing here is a measurement."""
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
                                     "kimi-linear-48b-a3b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "kimi-linear-48b-a3b.solo"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_published_keys_verbatim_and_the_five_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "num_attention_heads",
                       "linear_attn_config"}
    group, theirs = CONFIG["linear_attn_config"], PUBLISHED["linear_attn_config"]
    assert {k for k in theirs if group[k] != theirs[k]} == {"num_heads"}
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "experts_held", "vocab_rows_held",
        "num_attention_heads", "linear_attn_config",
        "linear_attn_config.num_heads"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows_held"], CONFIG["num_attention_heads"],
            group["num_heads"]) == (5, 8, 20480, 8, 8)
    assert 32 * CONFIG["experts_held"] == CONFIG["num_experts"]
    assert 8 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]
    assert 4 * group["num_heads"] == theirs["num_heads"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(entry["why"]) <= 200
    for key in ("deployment", "assumed"):
        assert CONFIG[key]


def test_app_params_are_the_sources_sizes():
    app, c = CONFIG["job"]["app_params"], CONFIG
    group = c["linear_attn_config"]
    assert (app["d_model"], app["n_heads"], app["d_ff"], app["dense_d_ff"],
            app["kv_lora_rank"], app["qk_nope_head_dim"],
            app["qk_rope_head_dim"], app["v_head_dim"], app["moe_experts"],
            app["moe_top_k"], app["moe_shared_experts"], app["moe_first_dense"],
            app["moe_norm_topk"], app["moe_routed_scale"], app["moe_score"],
            app["norm_eps"], app["rope_theta"], app["tie_embeddings"],
            app["moe_every"], app["linear_heads"], app["linear_head_dim"],
            app["short_conv"]) == (
        c["hidden_size"], c["num_attention_heads"], c["moe_intermediate_size"],
        c["intermediate_size"], c["kv_lora_rank"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"], c["num_experts"],
        c["num_experts_per_token"], c["num_shared_experts"],
        c["first_k_dense_replace"], c["moe_renormalize"],
        c["routed_scaling_factor"], c["moe_router_activation_func"],
        c["rms_norm_eps"], c["rope_theta"], c["tie_word_embeddings"],
        c["moe_layer_freq"], group["num_heads"], group["head_dim"],
        group["short_conv_kernel_size"])
    assert c["q_lora_rank"] is None and "q_lora_rank" not in app
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        c["num_hidden_layers"], c["experts_held"], c["vocab_rows_held"])
    # the source counts layers from 1
    n = c["num_hidden_layers"]
    assert [i + 1 for i in app["linear_layers"]] == [
        i for i in group["kda_layers"] if i <= n]
    assert [i for i in group["full_attn_layers"] if i <= n] == [4]
    assert (app["pos"], app["ffn"], app["attn_kind"]) == ("none", "swiglu", "mla")
    assert c["mla_use_nope"] is True and app["remat"] is True
    assert CONFIG["job"]["data_args"] == {
        "seq_len": app["max_seq"] + 1, "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == app["max_seq"] == 8192
    assert CONFIG["job"]["batch"] * app["max_seq"] * app["moe_top_k"] == 65536


def test_the_trainer_takes_the_app_params_and_counts_465m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**CONFIG["job"]["app_params"])
    assert tr.num_params == 464_821_024
    assert tr.hyperparams() == {"lr": 0.00022, "beta2": 0.95}
    assert tr.config.layer_kinds() == ("kda", "kda", "kda", "mla", "kda")
    assert tr.config.moe_layers() == (1, 2, 3, 4)
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    first, kda_layer, _, latent, _ = shapes["layers"]
    assert size(first["kda"]) == 10_321_032             # 10.32 M at 8 heads
    assert size({k: first[k] for k in ("w1", "w2", "w3")}) == 63_700_992
    assert size({k: latent[k] for k in (
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo")}) == 8_274_432   # 8.27 M
    moe = kda_layer["moe"]
    assert size({k: moe[k] for k in ("wg", "wu", "wd")}) == 56_623_104
    assert size({k: v for k, v in moe.items()
                 if k.startswith("shared_")}) == 7_077_888
    assert moe["router"].shape == (2304, 256) and moe["bias"].shape == (256,)
    assert shapes["embed"].shape == (20480, 2304) == shapes["head"].shape[::-1]
    assert size(first) == 74_026_632 and size(kda_layer) == 74_616_712
    assert size(latent) == 72_570_112
    assert (size(first) + 3 * size(kda_layer) + size(latent)
            + 2 * 47_185_920 + 2304) == tr.num_params


def test_work_functions_count_the_chunked_algorithm():
    work = load_by_path("work", "kimi_linear")
    app, batch = CONFIG["job"]["app_params"], CONFIG["job"]["batch"]
    chunk = 64 * 64 * 5 * 128 + 6 * 64 * 128 * 128
    fwd = work.kda_flops_per_call(app, batch, "harmony_kda_fwd")
    assert fwd == 8 * 128 * chunk
    assert work.kda_flops_per_call(app, batch, "harmony_kda_bwd") == 3 * fwd
    per_head = (3 * 8192 * 128 * 2 + 8192 * 128 * 4 + 8192 * 4
                + 8192 * 128 * 2 + 128 * 128 * 128 * 4)
    assert work.kda_bytes_per_call(app, batch, "harmony_kda_fwd") == 8 * per_head
    peaks = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
    row = work.bound_seconds(app, batch, "harmony_kda_fwd", peaks)
    assert row["binds"] == "HBM peak"
    assert row["seconds_bound"] == pytest.approx(8 * per_head / 819e9)
    # the kernel's chunk is the work file's
    from harmony_tpu.ops import kda

    assert kda.CHUNK == work.CHUNK
    assert set(kda.KERNEL_NAMES.values()) == set(work.KERNELS)
    # flash and the grouped matmuls are Moonlight's work file's, by name
    moon = load_by_path("work", "moonlight")
    assert moon.moe_layers(app) == 4
    assert moon.slots_per_step(app, batch) == 65536
    assert moon.flash_flops_per_call(app, batch, "harmony_flash_fwd") == (
        2 * (8 * 8192 ** 2 / 2) * (192 + 128))


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"kda_time_share", "kda_roofline_share", "flash_time_share",
            "flash_roofline_share", "moe_time_share",
            "expert_load_max_over_mean", "device_idle_share",
            "peak_hbm_share"} <= mine
    for name in ("kda_time_share", "kda_roofline_share"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert (entry["moves"], entry["layer"], entry["unit"]) == (
            "lm_tokens_per_s", "kernels", "%")
        assert os.path.exists(os.path.join(PERF, "layer_metrics", name + ".py"))
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    # appended after Moonlight's; a later PR appends after it, so no "last"
    assert rate["workloads"].index(CELL) \
        > rate["workloads"].index("moonlight-16b-a3b.solo")
    # of seven cells or more, those on four chips stay under a quarter
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 7 and four >= 1
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_kda_readers_fold_events_by_kernel_name():
    kk = load_by_path("layer_metrics", "_kda_kernels")
    for text, want in (("harmony_kda_fwd", "harmony_kda_fwd"),
                       ("harmony_kda_bwd.12", "harmony_kda_bwd"),
                       ("harmony_flash_fwd.1", None), ("fusion.7", None)):
        m = kk.KERNEL.match(text)
        assert (m.group(1) if m else None) == want
    for name in ("kda_time_share", "kda_roofline_share"):
        reader = load_by_path("layer_metrics", name)
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None


def test_kda_readers_on_the_recorded_fixture():
    """The one-chip fixture trace holds no KDA kernel, as every trace of the
    parent does: the reduction finds none and reports nothing, it does not
    raise."""
    from perf import trace_reduce

    kk = load_by_path("layer_metrics", "_kda_kernels")
    profile = trace_reduce.load(os.path.join(HERE, "fixture_1chip.xplane.pb"))
    assert kk.kernel_seconds(profile) is None


def test_kda_seconds_sum_by_kernel_and_the_roofline_is_a_share(monkeypatch,
                                                               capsys):
    """``kernel_seconds`` over a hand-made op list at the cell's sizes; the
    roofline reader divides the calls' bound by their seconds."""
    from perf import trace_reduce

    kk = load_by_path("layer_metrics", "_kda_kernels")
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops = [(call("harmony_kda_fwd.1"), 0.0, 2e6),
           (call("harmony_kda_fwd.2"), 3e6, 5e6),
           (call("harmony_kda_bwd.1"), 5e6, 13e6),
           (call("harmony_flash_fwd.1"), 13e6, 14e6),
           ("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop", 14e6, 20e6)]
    monkeypatch.setattr(kk.trace_reduce, "device_ops", lambda profile: {0: ops})
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    found = kk.kernel_seconds(None)
    assert found["busy_s"] == pytest.approx(0.019)
    assert found["kernels"]["harmony_kda_fwd"] == {
        "seconds": pytest.approx(0.004), "calls": 2}
    assert found["kernels"]["harmony_kda_bwd"]["calls"] == 1
    assert "harmony_flash_fwd" not in found["kernels"]
    share = load_by_path("layer_metrics", "kda_time_share")
    roof = load_by_path("layer_metrics", "kda_roofline_share")
    monkeypatch.setattr(share, "of_this_run", lambda: found)
    monkeypatch.setattr(roof, "of_this_run", lambda: found)
    obs = {"trace": {"busy_s": 1.0}, "phases": {CELL + "-run-t0": None}}
    assert share.read(obs) == pytest.approx(100 * 0.012 / 0.019)
    import jax

    class _Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    value = roof.read(obs)
    work = load_by_path("work", "kimi_linear")
    peaks = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
    app = CONFIG["job"]["app_params"]
    need = (2 * work.bound_seconds(app, 1, "harmony_kda_fwd", peaks)["seconds_bound"]
            + work.bound_seconds(app, 1, "harmony_kda_bwd", peaks)["seconds_bound"])
    assert value == pytest.approx(100 * need / 0.012) and 0 < value < 100
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "kda_roofline"
    assert line["kernels"]["harmony_kda_fwd"]["binds"] == "HBM peak"


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset through the jobserver, the
    logits check and the replay, to a last line that says ``correct``."""
    out = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", CELL,
         "--rehearse", "--seconds", "6", "--seed", "2147483659"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    check = next(l for l in lines if l.get("line") == "logits_check")
    assert check["ok"] and check["dtype"] == "float32"
    assert set(check["ablations_q90"]) == set(
        load_by_path("reference", "kimi-linear-48b-a3b").LOGIT_ABLATIONS)
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
