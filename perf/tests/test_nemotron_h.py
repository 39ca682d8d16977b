"""Checks of the ``nemotron-3-super-120b-a12b`` configuration's own files
(PR 38). Run with the rest of ``perf/tests``; CPU only, nothing here is a
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

NAME = "nemotron-3-super-120b-a12b"
CONFIG = json.load(open(os.path.join(PERF, "configs", NAME + ".json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = NAME + ".solo"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
HELD = {"num_hidden_layers": 11, "experts_held": 8, "vocab_rows_held": 16384,
        "mamba_heads_held": 16, "mamba_groups_held": 1,
        "attention_heads_held": 4, "kv_heads_held": 1,
        "shared_expert_columns_held": 672}
WORK = load_by_path("work", "nemotron_h")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
APP = CONFIG["job"]["app_params"]


def test_published_keys_verbatim_and_the_eight_cuts():
    assert len(PATTERN) == 88 and (PATTERN.count("M"), PATTERN.count("E"),
                                   PATTERN.count("*")) == (40, 40, 8)
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == list(HELD)
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert {k: CONFIG[k] for k in HELD} == HELD
    # 64 chips a layer: experts 64-way, everything dense 8-way
    assert 64 * HELD["experts_held"] == CONFIG["n_routed_experts"]
    for held, published in (("vocab_rows_held", "vocab_size"),
                            ("mamba_heads_held", "mamba_num_heads"),
                            ("mamba_groups_held", "n_groups"),
                            ("attention_heads_held", "num_attention_heads"),
                            ("shared_expert_columns_held",
                             "moe_shared_expert_intermediate_size")):
        assert 8 * HELD[held] == CONFIG[published], held
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CONFIG["reduced"] and len(entry["why"]) <= 200
    assert "64 chips" in CONFIG["deployment"]
    for key in ("sublayer", "positions", "mamba", "mamba_init", "experts",
                "moe_aux_weight", "optimizer", "init", "wqkv", "data"):
        assert CONFIG["assumed"][key]
    assert "LEFT OUT" in CONFIG["departures"]["multi_token_prediction"]


def test_app_params_are_the_sources_sizes():
    want = {
        "d_model": CONFIG["hidden_size"], "mha_head_dim": CONFIG["head_dim"],
        "n_heads": HELD["attention_heads_held"],
        "n_kv_heads": HELD["kv_heads_held"], "n_layers": 11,
        "layer_pattern": PATTERN[:11], "vocab_size": 16384,
        "d_ff": CONFIG["moe_intermediate_size"],
        "norm_eps": CONFIG["layer_norm_epsilon"],
        "ssd_heads": HELD["mamba_heads_held"],
        "ssd_groups": HELD["mamba_groups_held"],
        "ssd_head_dim": CONFIG["mamba_head_dim"],
        "ssd_state": CONFIG["ssm_state_size"],
        "ssd_chunk": CONFIG["chunk_size"], "short_conv": CONFIG["conv_kernel"],
        "moe_experts": CONFIG["n_routed_experts"],
        "moe_top_k": CONFIG["num_experts_per_tok"], "moe_experts_held": 8,
        "moe_latent": CONFIG["moe_latent_size"],
        "moe_routed_scale": float(CONFIG["routed_scaling_factor"]),
        "moe_norm_topk": CONFIG["norm_topk_prob"],
        "moe_shared_experts": CONFIG["n_shared_experts"],
        "moe_shared_d_ff": HELD["shared_expert_columns_held"],
        "moe_gated": False, "moe_act": CONFIG["mlp_hidden_act"],
        "moe_score": "sigmoid", "pos": "none",
        "tie_embeddings": CONFIG["tie_word_embeddings"],
        "max_seq": 8192, "remat": True, "dtype": "bfloat16"}
    assert {k: APP[k] for k in want} == want
    # a whole period of the published 5 : 5 : 1
    assert (APP["layer_pattern"].count("M"), APP["layer_pattern"].count("E"),
            APP["layer_pattern"].count("*")) == (5, 5, 1)
    assert CONFIG["expand"] * CONFIG["hidden_size"] == (
        CONFIG["mamba_num_heads"] * CONFIG["mamba_head_dim"])
    job = CONFIG["job"]
    assert (job["batch"], job["num_mini_batches"], job["comm_probe_period"],
            job["units_per_example"], job["data_args"]["seq_len"]) == (
                1, 1, 0, 8192, 8193)
    assert job["env"] == {"HARMONY_EPOCH_WINDOW": "2"}  # a drain every 2 steps
    for key in ("batch", "comm_probe_period", "loss_rtol", "check_epochs",
                "env"):
        assert len(job["why"][key]) > 40, key


def test_the_trainer_takes_the_app_params_and_counts_508m():
    from harmony_tpu.models import TransformerTrainer
    from harmony_tpu.models.moe import chunk_plan
    from harmony_tpu.ops.grouped_matmul import tile_plan

    tr = TransformerTrainer(**APP)
    assert tr.num_params == 508_189_680
    assert tr.hyperparams() == {"lr": 5e-7, "beta2": 0.95}
    assert tr.config.moe_layers() == (1, 3, 5, 8, 10)
    assert tr.config.ssd_widths == (1024, 1280, 2320)
    assert tr.config.qkv_widths == (512, 128, 128)
    # 180,224 slots, 1/64 held: 32 chunks of 5,632, one while the router
    # stays inside twice the balanced share; 2688 = 21 x 128 stays whole
    assert chunk_plan(8192 * 22, 8, 512) == (5632, 32)
    assert tile_plan(5632, 1024, 2688, "bfloat16")[2] == 2688


def test_work_functions_count_the_scan_a_group_once():
    fwd = WORK.ssd_flops_per_call(APP, 1, "harmony_ssd_fwd")
    chunk = 128 * 128 * (64 + 128 / 16) + 4 * 128 * 128 * 64
    assert fwd == 16 * 64 * chunk
    assert WORK.ssd_flops_per_call(APP, 1, "harmony_ssd_bwd") == 3 * fwd
    assert WORK.ssd_flops_per_call(APP, 2, "harmony_ssd_fwd") == 2 * fwd
    x, bc, decay = 16 * 8192 * 64 * 2, 2 * 8192 * 128 * 2, 16 * 8192 * 4
    states = 16 * 64 * 64 * 128 * 4
    assert WORK.ssd_bytes_per_call(APP, 1, "harmony_ssd_fwd") == (
        2 * x + bc + decay + states)
    assert WORK.ssd_bytes_per_call(APP, 1, "harmony_ssd_bwd") == (
        3 * x + 2 * (bc + decay) + states)
    # two groups: b and c twice, c b^T twice
    two = {**APP, "ssd_groups": 2}
    assert WORK.ssd_bytes_per_call(two, 1, "harmony_ssd_fwd") == (
        2 * x + 2 * bc + decay + states)
    assert WORK.ssd_flops_per_call(two, 1, "harmony_ssd_fwd") > fwd
    for kernel in WORK.KERNELS:
        bound = WORK.bound_seconds(APP, 1, kernel, PEAKS)
        assert bound["binds"] == "HBM peak"
        assert 5e-5 < bound["seconds_bound"] < 5e-4
    with pytest.raises(KeyError):
        WORK.ssd_flops_per_call(APP, 1, "harmony_kda_fwd")


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "solo", 1)
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"ssd_time_share", "ssd_roofline_share", "moe_latent_time_share",
            "flash_time_share", "moe_time_share", "moe_routing_time_share",
            "moe_chunks_per_call", "expert_load_max_over_mean",
            "mixer_time_share", "device_idle_share", "peak_hbm_share",
            "host_dispatch_share", "unscoped_time_share"} <= mine
    # the readers whose work functions do not count this configuration
    assert not {"flash_roofline_share", "swa_flash_roofline_share",
                "flash_masked_share", "gmm_roofline_share",
                "routed_gmm_roofline_share", "kda_time_share",
                "kda_roofline_share"} & mine
    for name, better, layer in (("ssd_time_share", "lower", "kernels"),
                                ("ssd_roofline_share", "higher", "kernels"),
                                ("moe_latent_time_share", "lower", "model")):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"][0] == CELL  # later cells may follow
        assert (entry["moves"], entry["layer"], entry["unit"], entry["better"],
                entry["source"]) == ("lm_tokens_per_s", layer, "%", better,
                                     "device_trace")
        reader = load_by_path("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            layer, "%", "device_trace")
    # appended in this order; a later PR appends after them, so no "last"
    names = [m["name"] for m in BENCH["per_layer"]]
    at = [names.index(n) for n in ("ssd_time_share", "ssd_roofline_share",
                                   "moe_latent_time_share")]
    assert at == sorted(at) and at[0] > names.index("moe_chunks_per_call")
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert CELL in rate["workloads"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 9 and four >= 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace without the kernels or the scope (the
    recorded fixtures: every trace of the parent): None, and nothing
    raised."""
    from perf import trace_reduce

    sk = load_by_path("layer_metrics", "_ssd_kernels")
    for text, want in (("harmony_ssd_fwd", "harmony_ssd_fwd"),
                       ("harmony_ssd_bwd.12", "harmony_ssd_bwd"),
                       ("harmony_kda_fwd.1", None), ("fusion.7", None)):
        m = sk.KERNEL.match(text)
        assert (m.group(1) if m else None) == want
    for name in ("ssd_time_share", "ssd_roofline_share",
                 "moe_latent_time_share"):
        reader = load_by_path("layer_metrics", name)
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None
    profile = trace_reduce.load(os.path.join(HERE, "fixture_1chip.xplane.pb"))
    assert sk.kernel_seconds(profile) is None
    # a scope table without the latent's scope: the recorded gpt2 capture
    scopes = load_by_path("layer_metrics", "_step_scopes")
    found = scopes.table(os.path.join(HERE, "fixture_scopes.xplane.pb"))
    assert found is not None and not any(
        r.scope == "blk*/moe.latent" for r in found["rows"])


def test_roofline_is_a_share_of_the_need_and_reads_under_100(monkeypatch,
                                                             capsys):
    """The reader's arithmetic on a made-up trace: each kernel's calls x its
    bound over its seconds, and a kernel AT its bound reads 100."""
    roof = load_by_path("layer_metrics", "ssd_roofline_share")
    bounds = {k: WORK.bound_seconds(APP, 1, k, PEAKS)["seconds_bound"]
              for k in WORK.KERNELS}
    made = {"busy_s": 1.0, "kernels": {
        "harmony_ssd_fwd": {"calls": 10, "seconds": 10 * 4 * bounds[
            "harmony_ssd_fwd"]},
        "harmony_ssd_bwd": {"calls": 5, "seconds": 5 * bounds[
            "harmony_ssd_bwd"]}}}
    monkeypatch.setattr(roof, "of_this_run", lambda: made)
    import jax

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    value = roof.read({"trace": {"busy_s": 1.0},
                       "phases": {CELL + "-run-t0": None}})
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    shares = {k: v["roofline_share"] for k, v in line["kernels"].items()}
    assert shares["harmony_ssd_fwd"] == pytest.approx(25.0)
    assert shares["harmony_ssd_bwd"] == pytest.approx(100.0)
    total = 10 * bounds["harmony_ssd_fwd"] + 5 * bounds["harmony_ssd_bwd"]
    assert value == pytest.approx(100.0 * total / sum(
        k["seconds"] for k in made["kernels"].values()))
    assert 25.0 < value < 100.0


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (all three letters, two
    groups, two chunks and a part, a top-k over held and unheld experts)
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
    assert check["ok"] and check["dtype"] == "float32"
    assert set(check["detected"]) == set(
        load_by_path("reference", NAME).LOGIT_ABLATIONS)
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
