"""Checks of the ``sdar-30b-a3b`` configuration's own files (PR 49). Run with
the rest of ``perf/tests``; CPU only, nothing here is a measurement."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf import work_models  # noqa: E402
from perf.run import load_by_path  # noqa: E402

CONFIG = json.load(open(os.path.join(PERF, "configs", "sdar-30b-a3b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "sdar-30b-a3b.solo"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
WORK = load_by_path("work", "sdar")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
NEW_METRICS = (("bd_flash_roofline_share", "higher", "device_trace",
                "kernels"),
               ("bd_streams_time_share", "lower", "device_trace", "model"))
APP = CONFIG["job"]["app_params"]


def hand_sdar():
    """Forward + backward FLOPs a token of the CORPUS from the published
    shapes: both streams through the layers' matmul parameters x 6 (attention
    whole, the router at its full width, top-8 of 128 with 16 held), the
    pairs ``L^2 + L B`` a head and sequence at ``2 (hd + hd)`` forward and
    twice that backward, ONE readout."""
    d, f, L, B, V, layers = 2048, 768, 8192, 4, 18992, 4
    attention = 2 * d * 32 * 128 + 2 * d * 4 * 128
    dense = 6 * 2 * layers * (attention + d * 128)
    routed = 6 * 2 * layers * (8 * 16 / 128 * 3 * d * f)
    pairs = 3 * layers * 32 * 512 * (L + B)
    return dense + routed + pairs + 6 * d * V


#: what ``test_step_mfu.py``'s table of hand counts lacks for this cell (a PR
#: may not edit that file): ``conftest.py`` here, and the tier-1 collector
#: ``tests/test_perf_step_scope_readers.py``, add it before its cases run
HAND = {"sdar-30b-a3b": hand_sdar}


def program_config():
    from harmony_tpu.models.transformer import TransformerConfig

    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    return TransformerConfig(**{k: v for k, v in APP.items() if k in names})


def test_published_keys_verbatim_and_the_three_cuts():
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows_held"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert CONFIG[key] == 4
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["experts_held"], CONFIG["vocab_rows_held"]) == (16, 18992)
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    for key, was in (("num_hidden_layers", "48"), ("experts_held", "128"),
                     ("vocab_rows_held", "151936")):
        assert was in CONFIG["reduced_from"][key]
    assert "8-way expert parallel" in CONFIG["deployment"]
    assert CONFIG["vocab_rows_held"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["experts_held"] * 8 == PUBLISHED["num_experts"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    # every assumed reading names the test that pins it
    import re

    tier1 = open(os.path.join(ROOT, "tests", "test_sdar.py")).read()
    for key, text in CONFIG["assumed"].items():
        if key[1:2] == "_":
            name = re.search(r"Pinned by (?:tests/test_sdar\.py )?(test_\w+)",
                             text)
            assert name and f"def {name.group(1)}(" in tier1, key


def test_app_params_are_the_sources_sizes():
    assert (APP["d_model"], APP["n_heads"], APP["n_kv_heads"],
            APP["mha_head_dim"]) == (2048, 32, 4, 128)
    assert (APP["d_ff"], APP["moe_experts"], APP["moe_top_k"]) == (768, 128, 8)
    assert APP["moe_norm_topk"] is CONFIG["norm_topk_prob"] is True
    assert APP["rope_theta"] == CONFIG["rope_theta"] == 1e6
    assert APP["norm_eps"] == CONFIG["rms_norm_eps"]
    assert APP["tie_embeddings"] is CONFIG["tie_word_embeddings"] is False
    assert APP["moe_every"] == CONFIG["decoder_sparse_step"] == 1
    assert (APP["n_layers"], APP["moe_experts_held"], APP["vocab_size"]) == (
        CONFIG["num_hidden_layers"], CONFIG["experts_held"],
        CONFIG["vocab_rows_held"])
    assert (APP["objective"], APP["diffusion_block"], APP["head_norm"]) == (
        "block_diffusion", 4, True)
    assert APP["mask_token"] == APP["vocab_size"] - 1
    assert APP["max_seq"] <= CONFIG["max_position_embeddings"]
    job = CONFIG["job"]
    assert job["data_args"] == {"seq_len": APP["max_seq"],
                                "vocab_size": APP["vocab_size"],
                                "block": APP["diffusion_block"]}
    assert job["units_per_example"] == job["batch"] * APP["max_seq"] == 8192
    assert job["env"] == {"HARMONY_EPOCH_WINDOW": "2"}
    # each held expert's token-slots a layer under uniform routing
    assert 2 * APP["max_seq"] * APP["moe_top_k"] // APP["moe_experts"] == 1024


def test_the_trainer_takes_the_app_params_and_counts_456m_by_part():
    import jax

    from harmony_tpu.models.transformer import TransformerLM

    cfg = program_config()
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(tree))
    layer = shapes["layers"][0]
    assert size(layer["wqkv"]) + size(layer["wo"]) == 18_874_368
    assert size(layer["moe"]["router"]) == 262_144
    assert size(layer["q_head_norm"]) + size(layer["k_head_norm"]) == 256
    experts = {k: layer["moe"][k] for k in ("wg", "wu", "wd")}
    assert size(experts) == 75_497_472
    assert size(layer) == 94_638_336
    assert size(shapes["embed"]) == size(shapes["head"]) == 38_895_616
    assert size(shapes) == 456_346_624
    assert cfg.layer_kinds() == ("full",) * 4
    assert cfg.moe_layers() == (0, 1, 2, 3)


def test_the_yardstick_counts_the_configuration_by_hand():
    job = CONFIG["job"]
    assert job["flops_fn"] == "sdar:block_diffusion_flops_per_token"
    assert work_models.resolve(job["flops_fn"]) \
        is work_models.sibling("sdar").block_diffusion_flops_per_token
    counted = work_models.count(job, "flops_fn")
    assert counted == hand_sdar() == 2989817856.0
    parts = work_models.split(job)
    assert tuple(parts) == work_models.PARTS
    assert float(sum(parts.values())) == counted
    assert parts == {"dense": 918552576.0, "routed": 226492416.0,
                     "attention_pairs": 1611399168.0, "scans": 0.0,
                     "readout": 233373696.0}
    # the new mechanism does most of the work in its cell
    assert parts["attention_pairs"] / counted == pytest.approx(0.539, abs=1e-3)


def test_the_layers_are_the_programs():
    """The count's layers against the program's own ``TransformerConfig``:
    every one a softmax block that routes, both streams through each."""
    cfg = program_config()
    assert len(cfg.layer_kinds()) == APP["n_layers"] == len(cfg.moe_layers())
    one = WORK.block_diffusion_flops_split(APP)
    two = WORK.block_diffusion_flops_split({**APP, "n_layers": 8})
    for part in ("dense", "routed", "attention_pairs"):
        assert two[part] == 2 * one[part]
    assert two["readout"] == one["readout"]


def test_remat_counts_nothing_and_the_other_moves():
    base = WORK.block_diffusion_flops_split(APP)
    assert WORK.block_diffusion_flops_split({**APP, "remat": False}) == base
    two = WORK.block_diffusion_flops_split({**APP, "moe_experts_held": 32})
    assert two["routed"] == 2 * base["routed"] > 0
    assert {k: v for k, v in two.items() if k != "routed"} \
        == {k: v for k, v in base.items() if k != "routed"}
    half = WORK.block_diffusion_flops_split({**APP, "diffusion_block": 2})
    assert base["attention_pairs"] - half["attention_pairs"] \
        == 3 * 512 * 32 * 4 * APP["diffusion_block"] / 2
    assert {k: v for k, v in half.items() if k != "attention_pairs"} \
        == {k: v for k, v in base.items() if k != "attention_pairs"}


@pytest.mark.parametrize("change", [
    {"window": 4096}, {"objective": "next_token"}, {"moe_every": 2},
    {"ffn": "gelu"}, {"diffusion_block": 3}, {"linear_layers": [1]},
    {"moe_top_k": 0}])
def test_a_step_it_cannot_count_raises(change):
    with pytest.raises(ValueError):
        WORK.block_diffusion_flops_per_token({**APP, **change})


def test_work_functions_count_the_stacked_call():
    """One call a layer: both streams' queries (2 L rows a query head)
    against the clean keys (L rows a K/V head); ``L^2`` pairs a head."""
    L, h, hd = 8192, 32, 128
    assert WORK.pairs_per_head(APP) == L * L
    causal = load_by_path("work", "smallthinker").causal_pairs(L)
    assert 2 * causal - L == L * L  # two triangles, the diagonal once
    for name, products in (("harmony_flash_bd_fwd", 2),
                           ("harmony_flash_bd_bwd_dkv", 4),
                           ("harmony_flash_bd_bwd_dq", 3)):
        assert WORK.flash_flops_per_call(APP, 1, name) \
            == 2 * products * hd * h * L * L
        bound = WORK.bound_seconds(APP, 1, name, PEAKS)
        assert bound["binds"] == "bf16 MXU peak"
        assert bound["seconds_bound"] == pytest.approx(
            bound["flops"] / PEAKS["bf16_flops"])
    q, kv, stat = h * 2 * L * hd * 2, 4 * L * hd * 2, h * 2 * L * 4
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_bd_fwd") \
        == 2 * q + 2 * kv + stat
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_bd_bwd_dkv") \
        == 2 * q + 4 * kv + 2 * stat
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_bd_bwd_dq") \
        == 3 * q + 2 * kv + 2 * stat
    with pytest.raises(KeyError):
        WORK.flash_flops_per_call(APP, 1, "harmony_flash_fwd")
    # the program's names for them
    from harmony_tpu.ops.attention import kernel_name

    assert {kernel_name(k, None, 4) for k in ("fwd", "dkv", "dq")} \
        == set(WORK.KERNELS)


def test_the_accepted_work_file_counts_the_grouped_matmuls():
    """``perf/work/olmoe.py`` reads ``d_model``, ``d_ff``, ``moe_every`` and
    the reported rows: K = 2048, N = 768 here, every layer an expert layer."""
    olmoe = load_by_path("work", "olmoe")
    rows = 16384
    assert olmoe.gmm_flops_per_step(APP, rows) > 0


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("zaya1-8b.solo")
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"bd_flash_roofline_share", "bd_streams_time_share",
            "flash_time_share", "flash_masked_share", "step_mfu_share",
            "moe_time_share", "moe_routing_time_share", "moe_chunks_per_call",
            "expert_load_max_over_mean", "mixer_time_share", "ffn_time_share",
            "device_idle_share", "peak_hbm_share", "unscoped_time_share",
            "table_path_time_share", "head_loss_time_share",
            "dense_matmul_roofline_share", "host_dispatch_share"} <= mine
    # the readers whose work functions do not count this configuration
    assert not {"flash_roofline_share", "cca_flash_roofline_share",
                "kda_time_share", "swa_flash_roofline_share",
                "ssd_time_share", "moe_skip_share"} & mine
    for name, better, source, layer in NEW_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["layer"], entry["unit"], entry["better"],
                entry["source"]) == ("lm_tokens_per_s", layer, "%", better,
                                     source)
        reader = load_by_path("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (layer, "%",
                                                              source)
    order = [m["name"] for m in BENCH["per_layer"]]
    assert order.index("bd_flash_roofline_share") \
        > order.index("cca_flash_roofline_share")
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "zaya1-8b.solo")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 11 and four == 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_scopes_are_in_the_vocabulary_and_in_their_groups():
    from harmony_tpu.tracing import stepscopes

    scopes = load_by_path("layer_metrics", "_step_scopes")
    assert {"mixer.streams", "noise"} <= set(stepscopes.VOCABULARY)
    row = lambda scope: type("R", (), {"scope": scope, "klass": "fusion"})()
    assert scopes.group_of(row("blk*/mixer.streams")) == "mixer"
    assert scopes.group_of(row("noise")) == "other_model"
    assert load_by_path("layer_metrics", "bd_streams_time_share").SCOPE == (
        "blk*/mixer.streams")
    assert stepscopes.parse_path(
        "jit(_step)/jvp(blk2)/mixer.core/mixer.streams/dot_general") == (
            "blk2/mixer.streams", "fwd")


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace of another configuration (the parent's
    program has neither the scope nor the kernels), no measured job: None,
    and nothing raised."""
    for name, *_ in NEW_METRICS:
        reader = load_by_path("layer_metrics", name)
        assert reader.read({}) is None
        assert reader.read({"trace": None, "phases": {}}) is None
        assert reader.read({"trace": None,
                            "phases": {"no-such-cell-run-t0": None}}) is None
    roof = load_by_path("layer_metrics", "bd_flash_roofline_share")
    assert roof.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None


def test_the_generator_same_seed_same_bytes():
    gen = load_by_path("generators", "block_diffusion_tokens")
    a = gen.make(3, 64, 512, 4, seed=2147483659)
    b = gen.make(3, 64, 512, 4, seed=2147483659)
    c = gen.make(3, 64, 512, 4, seed=2147483660)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].tobytes() != c[0].tobytes()
    tokens, masked, rate = a
    assert (tokens.dtype, masked.dtype, rate.dtype) == (
        np.int32, np.int8, np.float32)
    assert (tokens.shape, masked.shape, rate.shape) == (
        (3, 64), (3, 64), (3, 16))
    assert tokens.min() >= 0 and tokens.max() < 511  # never the mask token
    assert set(np.unique(masked)) <= {0, 1}
    assert gen.EPS <= rate.min() and rate.max() <= 1.0
    with pytest.raises(ValueError):
        gen.make(1, 66, 512, 4, seed=0)
    # a block's tokens are masked at the block's own rate
    _, masked, rate = gen.make(64, 4096, 512, 4, seed=5)
    by_block = masked.reshape(64, -1, 4).mean(axis=-1)
    low, high = rate < 0.25, rate > 0.75
    assert by_block[low].mean() < 0.2 and by_block[high].mean() > 0.8
    assert abs(masked.mean() - 0.5) < 0.01


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (2 layers, 4 query heads
    over 1 K/V head, 8 experts with 4 held, 32 positions in blocks of 4)
    through the jobserver on a tuple batch, the logits and gradient check
    under seeded norm weights and the replay, to a last line that says
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
    ref = load_by_path("reference", "sdar-30b-a3b")
    assert check["ok"] and check["dtype"] == "float32"
    assert set(check["detected"]) == set(ref.ABLATIONS)
    assert set(check["loss_ablations"]) == set(ref.LOSS_ABLATIONS)
    assert 0 < check["masked_share"] < 1
    rc = next(l for l in lines if l.get("line") == "reference_check")
    assert rc["ok"] and max(rc["tenants"][0]["rel_err"]) <= 1e-5
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
