"""Checks of the ``zaya1-8b`` configuration's own files (PR 45). Run with the
rest of ``perf/tests``; CPU only, nothing here is a measurement."""
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

from perf import work_models  # noqa: E402
from perf.run import load_by_path  # noqa: E402

CONFIG = json.load(open(os.path.join(PERF, "configs", "zaya1-8b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "zaya1-8b.solo"
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
WORK = load_by_path("work", "zaya1")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
NEW_METRICS = (("cca_prep_time_share", "lower", "device_trace", "model"),
               ("moe_skip_share", "higher", "program_counter", "model"),
               ("cca_flash_roofline_share", "higher", "device_trace",
                "kernels"))


def hand_zaya1():
    """Forward + backward FLOPs a token from the published shapes: matmul
    parameters a token passes through x 6, + the pairs. CCA's q / k / the two
    value projections / o; the router counted as ``d x 16`` (what
    perf/work_models.py knows of a router); top-1 of 16 with 8 held."""
    d, f, s, V = 2048, 2048, 8192, 32784
    mixer = d * (8 * 128 + 2 * 128 + 2 * 128) + 8 * 128 * d
    layer = mixer + d * 16 + 1 * 8 / 16 * 3 * d * f
    return 6 * (4 * layer + d * V) + 4 * 3 * (128 + 128) * 8 * s


#: what ``test_step_mfu.py``'s table of hand counts lacks for this cell (a PR
#: may not edit that file): ``conftest.py`` here, and the tier-1 collector
#: ``tests/test_perf_step_scope_readers.py``, add it before its cases run
HAND = {"zaya1-8b": hand_zaya1}


def test_published_keys_verbatim_and_the_three_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows_held"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows_held"]) == (4, 8, 32784)
    assert 2 * CONFIG["experts_held"] == CONFIG["num_experts"]
    assert 8 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "zaya1-8b")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CONFIG["reduced"]
    assert len(entry["why"]) <= 200
    for key in ("deployment", "assumed"):
        assert CONFIG[key]
    # the six points the issue calls out, each with its other reading and
    # the test that pins it
    six = [k for k in CONFIG["assumed"] if k[1] == "_" and k[0] in "abcdef"]
    assert sorted(k[0] for k in six) == list("abcdef")
    for key in six:
        text = CONFIG["assumed"][key]
        assert "other reading" in text and "Pinned by" in text, key
    tests = open(os.path.join(ROOT, "tests", "test_zaya1.py")).read()
    for key in six:
        name = CONFIG["assumed"][key].split("Pinned by ")[1].split()[-1]
        assert f"def {name}(" in tests, name
    for key in ("gelu", "cca_layout", "init", "moe_aux_weight", "optimizer",
                "data"):
        assert CONFIG["assumed"][key]


def test_app_params_are_the_sources_sizes():
    app, c = CONFIG["job"]["app_params"], CONFIG
    rope = c["rope_parameters"]["hybrid"]
    assert (app["d_model"], app["n_heads"], app["n_kv_heads"],
            app["mha_head_dim"], app["d_ff"], app["moe_experts"],
            app["moe_top_k"], app["moe_router_hidden"], app["norm_eps"],
            app["rope_theta"], app["rope_fraction"], app["tie_embeddings"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["moe_intermediate_size"], c["num_experts"],
        c["num_experts_per_tok"], c["router_hidden_size"], c["rms_norm_eps"],
        rope["rope_theta"], rope["partial_rotary_factor"],
        c["tie_word_embeddings"])
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        c["num_hidden_layers"], c["experts_held"], c["vocab_rows_held"])
    assert app["cca"] and app["merge_scaled"] and app["moe_null_expert"]
    assert (app["pos"], app["ffn"], app["moe_every"], app["moe_aux_weight"],
            app.get("attn_kind", "mha")) == ("rope", "swiglu", 1, 0.0, "mha")
    assert not [k for k in app if k.endswith("_layers") and k != "n_layers"]
    assert app["max_seq"] <= c["max_position_embeddings"]
    assert CONFIG["job"]["data_args"] == {
        "seq_len": app["max_seq"] + 1, "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == app["max_seq"] == 8192
    for key, text in CONFIG["job"]["why"].items():
        assert text and "TO BE FILLED" not in text, key


def test_the_trainer_takes_the_app_params_and_counts_495m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer
    from harmony_tpu.models.moe import chunk_plan

    tr = TransformerTrainer(**CONFIG["job"]["app_params"])
    assert tr.num_params == 494_825_548
    assert tr.hyperparams() == {"lr": 2e-6, "beta2": 0.95}
    assert tr.config.layer_kinds() == ("mha",) * 4
    assert tr.config.moe_layers() == (0, 1, 2, 3)
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    layer = shapes["layers"][0]
    assert layer["wqkv"].shape == (2048, 1024 + 256 + 256)
    assert layer["wo"].shape == (1024, 2048)
    assert size({k: layer[k] for k in ("wqkv", "wo")}) == 5_242_880
    assert size(layer["cca"]) == 332_802
    assert layer["cca"]["conv1"].shape == (2, 10, 128, 128)
    moe = layer["moe"]
    experts = {k: moe[k] for k in ("wg", "wu", "wd")}
    assert size(experts) == 100_663_296
    assert size(moe) - size(experts) == 661_009
    assert moe["r_w3"].shape == (256, 17) and moe["bias"].shape == (17,)
    assert size({k: layer[k] for k in ("ln1", "ln2", "merge1", "merge2")}
                ) == 20_480
    assert size(layer) == 106_920_467
    assert shapes["embed"].shape == (32784, 2048) and "head" not in shapes
    assert 4 * size(layer) + 67_141_632 + 2048 == tr.num_params
    # half the experts held: nothing to cut, the full-length pass
    assert chunk_plan(8192, 8, 16) == (8192, 0)
    # every leaf is whole table rows (PR 42): a 2-element temperature too
    assert all(f.rows % 8 == 0 and f.first % 8 == 0
               for f in tr.leaf_rows.leaves)


def test_the_yardstick_counts_the_configuration_by_hand():
    """``lm_train_flops_per_token`` of the cell's ``app_params`` without an
    edit to perf/work_models.py: CCA's matmuls are its ``full`` mixer at 8
    heads over 2 of 128, the experts its ``moe`` part at half held; the
    convolutions and the router's hidden layers count nothing (0.33 M and
    0.63 M multiply-adds a token and layer beside 11.6 M: the share of the
    peak is understated by ~3%, never overstated)."""
    app = CONFIG["job"]["app_params"]
    d, f, s = 2048, 2048, 8192
    mixer = d * (8 * 128 + 2 * 2 * 128) + 8 * 128 * d
    assert mixer == 5_242_880
    layer = 6 * (mixer + d * 16 + 0.5 * 3 * d * f)
    pairs = 3 * (128 + 128) * 8 * (2 * (s * (s + 1) // 2) - s) / s
    want = 4 * (layer + pairs) + 6 * d * 32784
    assert want == hand_zaya1() == 881_786_880
    assert work_models.lm_train_flops_per_token(app) == pytest.approx(
        want, rel=1e-12)
    split = work_models.lm_train_flops_split(app)
    assert split["scans"] == 0 and split["routed"] == 4 * 6 * 0.5 * 3 * d * f
    assert split["readout"] / want == pytest.approx(0.457, abs=0.005)
    kinds = work_models.layer_kinds(app)
    assert kinds == [{"mixer": "full", "ffn": "moe"}] * 4


def test_work_functions_count_the_latents_attention():
    app = CONFIG["job"]["app_params"]
    s, pairs = 8192, 8192 * 8193 // 2
    assert WORK.causal_pairs(s) == pairs
    for kernel, products in (("harmony_flash_fwd", 2),
                             ("harmony_flash_bwd_dkv", 4),
                             ("harmony_flash_bwd_dq", 3)):
        assert WORK.flash_flops_per_call(app, 1, kernel) == (
            2.0 * products * 128 * 8 * pairs)
        assert WORK.flash_flops_per_call(app, 2, kernel) == 2 * (
            WORK.flash_flops_per_call(app, 1, kernel))
    q, kv, stat = 8 * s * 128 * 2, 2 * s * 128 * 2, 8 * s * 4
    assert WORK.flash_bytes_per_call(app, 1, "harmony_flash_fwd") == (
        2 * q + 2 * kv + stat)
    assert WORK.flash_bytes_per_call(app, 1, "harmony_flash_bwd_dq") == (
        3 * q + 2 * kv + 2 * stat)
    assert WORK.flash_bytes_per_call(app, 1, "harmony_flash_bwd_dkv") == (
        2 * q + 4 * kv + 2 * stat)
    bound = WORK.bound_seconds(app, 1, "harmony_flash_fwd", PEAKS)
    assert bound["binds"] == "bf16 MXU peak"
    assert bound["seconds_bound"] == pytest.approx(
        bound["flops"] / PEAKS["bf16_flops"])
    # the accepted cells' arithmetic, as it stands, for the kernels without a
    # window; a windowed kernel's name is not this configuration's
    assert set(WORK.KERNELS) == {"harmony_flash_fwd", "harmony_flash_bwd_dkv",
                                 "harmony_flash_bwd_dq"}


def test_the_accepted_work_file_counts_the_grouped_matmuls():
    """``routed_gmm_roofline_share``'s reader takes everything from the
    cell's ``app_params`` through ``perf/work/moonlight.py``: K = N = 2048,
    four expert layers, one slot a token."""
    app, work = CONFIG["job"]["app_params"], load_by_path("work", "moonlight")
    assert work.moe_layers(app) == 4
    assert work.slots_per_step(app, 1) == 8192
    assert work.gmm_flops_per_call(app, 4096) == 2.0 * 4096 * 2048 * 2048
    assert work.gmm_flops_per_step(app, 4096) == (
        9 * 4 * work.gmm_flops_per_call(app, 4096))
    reader = load_by_path("layer_metrics", "routed_gmm_roofline_share")
    assert reader.read({"trace": None}) is None


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1-8b", "solo", 1)
    assert len(cell["why"]) <= 200
    assert BENCH["workloads"][-1] is cell and BENCH["configs"][-1]["name"] == (
        "zaya1-8b")
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"cca_prep_time_share", "moe_skip_share",
            "cca_flash_roofline_share", "flash_time_share", "step_mfu_share",
            "moe_time_share", "moe_routing_time_share", "moe_chunks_per_call",
            "expert_load_max_over_mean", "mixer_time_share",
            "device_idle_share", "peak_hbm_share", "unscoped_time_share",
            "table_path_time_share", "head_loss_time_share",
            # moonlight.py's work functions read d_model, d_ff and
            # moe_layers() from the cell's app_params: K = N = 2048 here
            "routed_gmm_roofline_share"} <= mine
    # the readers whose work functions do not count this configuration
    assert not {"flash_roofline_share", "gmm_roofline_share",
                "kda_time_share",
                "swa_flash_roofline_share", "ssd_time_share"} & mine
    for name, better, source, layer in NEW_METRICS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["layer"], entry["unit"], entry["better"],
                entry["source"]) == ("lm_tokens_per_s", layer, "%", better,
                                     source)
        reader = load_by_path("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (layer, "%",
                                                              source)
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == [
        n for n, *_ in NEW_METRICS]
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "nemotron-3-super-120b-a12b.solo")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) == 10 and four == 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_scopes_are_in_the_vocabulary_and_in_their_groups():
    from harmony_tpu.tracing import stepscopes

    scopes = load_by_path("layer_metrics", "_step_scopes")
    assert {"mixer.cca", "merge"} <= set(stepscopes.VOCABULARY)
    row = lambda scope: type("R", (), {"scope": scope, "klass": "fusion"})()
    assert scopes.group_of(row("blk*/mixer.cca")) == "mixer"
    assert scopes.group_of(row("blk*/merge")) == "other_model"
    assert scopes.group_of(row("blk*/moe.route")) == "moe_routing"
    assert load_by_path("layer_metrics", "cca_prep_time_share").SCOPE == (
        "blk*/mixer.cca")
    assert stepscopes.parse_path(
        "jit(_step)/jvp(blk2)/mixer.cca/dot_general") == ("blk2/mixer.cca",
                                                          "fwd")


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace of another configuration (the parent's
    program has neither the scope nor the counter), no measured job: None,
    and nothing raised."""
    for name, *_ in NEW_METRICS:
        reader = load_by_path("layer_metrics", name)
        assert reader.read({"trace": None, "phases": {}}) is None
        assert reader.read({"trace": None,
                            "phases": {"no-such-cell-run-t0": None}}) is None
    roof = load_by_path("layer_metrics", "cca_flash_roofline_share")
    assert roof.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None


def test_skip_share_is_null_slots_over_all_slots():
    from harmony_tpu.metrics import moe
    import numpy as np

    reader = load_by_path("layer_metrics", "moe_skip_share")
    job = "zaya1-8b.solo-run-skiptest"
    tokens = np.zeros((2, 1, 4))
    tokens[:, 0] = [10, 20, 30, 20]                      # 80 slots a step
    moe.observe(job, tokens, 2, [0], null_slots=np.full((2, 1), 20.0))
    assert reader.read({"phases": {job: None}}) == pytest.approx(20.0)
    plain = "zaya1-8b.solo-run-noskip"
    moe.observe(plain, tokens, 2, [0])  # a router without the output
    assert reader.read({"phases": {plain: None}}) is None


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (2 layers, 4 query heads
    over 2 K/V heads, 4 experts + "no expert" with 2 held) through the
    jobserver, the logits check under seeded identities and the replay, to
    a last line that says ``correct``."""
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
        load_by_path("reference", "zaya1-8b").LOGIT_ABLATIONS)
    assert check["null_slot_share"] > 0
    ref = next(l for l in lines if l.get("line") == "reference_check")
    assert ref["ok"] and max(ref["tenants"][0]["rel_err"]) <= 1e-5
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
