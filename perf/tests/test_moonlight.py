"""Checks of the ``moonlight-16b-a3b`` configuration's own files (PR 29). Run
with the rest of ``perf/tests``; CPU only, nothing here is a measurement."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf.run import load_by_path  # noqa: E402

CONFIG = json.load(open(os.path.join(PERF, "configs", "moonlight-16b-a3b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "moonlight-16b-a3b.solo"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


def test_published_keys_verbatim_and_the_three_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows_held"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows_held"]) == (2, 8, 20480)
    assert 8 * CONFIG["experts_held"] == CONFIG["n_routed_experts"]
    assert 8 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "moonlight-16b-a3b")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_app_params_are_the_sources_sizes():
    app, c = CONFIG["job"]["app_params"], CONFIG
    assert (app["d_model"], app["n_heads"], app["d_ff"], app["dense_d_ff"],
            app["max_seq"], app["kv_lora_rank"], app["qk_nope_head_dim"],
            app["qk_rope_head_dim"], app["v_head_dim"], app["moe_experts"],
            app["moe_top_k"], app["moe_shared_experts"], app["moe_first_dense"],
            app["moe_norm_topk"], app["moe_routed_scale"], app["moe_score"],
            app["moe_seq_aux"], app["norm_eps"], app["rope_theta"],
            app["tie_embeddings"], app["moe_every"]) == (
        c["hidden_size"], c["num_attention_heads"], c["moe_intermediate_size"],
        c["intermediate_size"], c["max_position_embeddings"],
        c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
        c["v_head_dim"], c["n_routed_experts"], c["num_experts_per_tok"],
        c["n_shared_experts"], c["first_k_dense_replace"], c["norm_topk_prob"],
        c["routed_scaling_factor"], c["scoring_func"], c["seq_aux"],
        c["rms_norm_eps"], c["rope_theta"], c["tie_word_embeddings"],
        c["moe_layer_freq"])
    assert c["q_lora_rank"] is None and "q_lora_rank" not in app
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        c["num_hidden_layers"], c["experts_held"], c["vocab_rows_held"])
    assert (app["pos"], app["ffn"], app["attn_kind"]) == ("rope", "swiglu", "mla")
    assert "moe_z_weight" not in app and "remat" not in app
    assert CONFIG["job"]["data_args"] == {
        "seq_len": c["max_position_embeddings"] + 1,
        "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == c["max_position_embeddings"]
    assert CONFIG["job"]["batch"] * app["max_seq"] * app["moe_top_k"] == 98304


def test_the_trainer_takes_the_app_params_and_counts_267m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**CONFIG["job"]["app_params"])
    assert tr.num_params == 267_267_136
    assert tr.hyperparams() == {"lr": 0.00022, "beta2": 0.95}
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    dense, expert = shapes["layers"]
    attention = size({k: dense[k] for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")})
    assert attention == 13_763_072                       # 13.77 M a layer
    assert size({k: dense[k] for k in ("w1", "w2", "w3")}) == 69_206_016
    moe = expert["moe"]
    assert size({k: moe[k] for k in ("wg", "wu", "wd")}) == 69_206_016  # 8 held
    assert size({k: v for k, v in moe.items() if k.startswith("shared_")}) == 17_301_504
    assert moe["router"].shape == (2048, 64) and moe["bias"].shape == (64,)
    assert shapes["embed"].shape == (20480, 2048) == shapes["head"].shape[::-1]
    assert size(dense) == 82_973_184 and size(expert) == 100_405_824
    assert size(dense) + size(expert) + 2 * 41_943_040 + 2048 == tr.num_params
    assert tr.config.moe_layers() == (1,)


def test_work_functions_count_one_expert_layer_and_the_causal_half():
    work = load_by_path("work", "moonlight")
    app, batch = CONFIG["job"]["app_params"], CONFIG["job"]["batch"]
    assert work.moe_layers(app) == 1        # olmoe's moe_layers would say 2
    assert load_by_path("work", "olmoe").moe_layers(app) == 2
    assert work.slots_per_step(app, batch) == 98304
    per_call = work.gmm_flops_per_call(app, 12288)
    assert per_call == 2.0 * 12288 * 2048 * 1408
    assert work.gmm_flops_per_step(app, 12288) == 9 * per_call
    pairs = 2 * 16 * 8192 ** 2 / 2
    assert work.flash_flops_per_call(app, batch, "harmony_flash_fwd") == (
        2 * pairs * (192 + 128))
    assert work.flash_flops_per_call(app, batch, "harmony_flash_bwd_dkv") == (
        2 * pairs * (2 * 192 + 2 * 128))
    assert work.flash_flops_per_call(app, batch, "harmony_flash_bwd_dq") == (
        2 * pairs * (2 * 192 + 128))


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "moonlight-16b-a3b", "solo", 1)
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"flash_time_share", "flash_roofline_share",
            "routed_gmm_roofline_share", "moe_time_share",
            "expert_load_max_over_mean", "device_idle_share"} <= mine
    assert "gmm_roofline_share" not in mine  # its work file counts 2 layers
    for name in ("flash_time_share", "flash_roofline_share",
                 "routed_gmm_roofline_share"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        # first of its cells; later configurations append theirs
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == "lm_tokens_per_s"
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 6
    assert 1 <= four <= max(1, len(BENCH["workloads"]) // 4)


def test_flash_readers_fold_events_by_kernel_name():
    fk = load_by_path("layer_metrics", "_flash_kernels")
    for text, want in (("harmony_flash_fwd", "harmony_flash_fwd"),
                       ("harmony_flash_bwd_dkv.12", "harmony_flash_bwd_dkv"),
                       ("harmony_flash_bwd_dq.3", "harmony_flash_bwd_dq"),
                       ("harmony_gmm_fwd.1", None), ("fusion.7", None)):
        m = fk.KERNEL.match(text)
        assert (m.group(1) if m else None) == want
    for name in ("flash_time_share", "flash_roofline_share",
                 "routed_gmm_roofline_share"):
        reader = load_by_path("layer_metrics", name)
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None


def test_flash_readers_on_the_recorded_fixture():
    """The one-chip fixture trace holds no flash kernel: the reduction finds
    none and reports nothing, it does not raise."""
    from perf import trace_reduce

    fk = load_by_path("layer_metrics", "_flash_kernels")
    profile = trace_reduce.load(os.path.join(HERE, "fixture_1chip.xplane.pb"))
    assert fk.kernel_seconds(profile) is None


def test_flash_seconds_sum_by_kernel(monkeypatch):
    """``kernel_seconds`` over a hand-made op list: every event counts, by
    name, whatever its ``.<n>`` suffix; busy is the union of all ops."""
    from perf import trace_reduce

    fk = load_by_path("layer_metrics", "_flash_kernels")
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops = [(call("harmony_flash_fwd.1"), 0.0, 2e6),
           (call("harmony_flash_fwd.2"), 3e6, 5e6),
           (call("harmony_flash_bwd_dkv.1"), 5e6, 9e6),
           (call("harmony_gmm_fwd.1"), 9e6, 10e6),
           ("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop", 10e6, 20e6)]
    monkeypatch.setattr(fk.trace_reduce, "device_ops", lambda profile: {0: ops})
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    found = fk.kernel_seconds(None)
    assert found["busy_s"] == pytest.approx(0.019)
    assert found["kernels"]["harmony_flash_fwd"] == {
        "seconds": pytest.approx(0.004), "calls": 2}
    assert found["kernels"]["harmony_flash_bwd_dkv"]["calls"] == 1
    assert "harmony_gmm_fwd" not in found["kernels"]


def test_routed_reader_takes_its_layers_from_the_program():
    reader = load_by_path("layer_metrics", "routed_gmm_roofline_share")
    assert reader.traced_steps({"phases": {}}) is None
    mk = reader.mk
    cell = mk.cell_of([CELL + "-run-t0"])
    assert cell.name == CELL and cell.batch == 2
    assert cell.job["app_params"]["d_ff"] == CONFIG["moe_intermediate_size"]
