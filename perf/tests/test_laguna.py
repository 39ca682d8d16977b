"""Checks of the ``laguna-s-2.1`` configuration's own files (PR 54). Run with
the rest of ``perf/tests`` (and, all but the rehearsal, collected by
``tests/test_laguna.py`` under tier-1); CPU only, nothing here is a
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

CONFIG = json.load(open(os.path.join(PERF, "configs", "laguna-s-2.1.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "laguna-s-2.1.solo"
APP = CONFIG["job"]["app_params"]
TYPES = ["full_attention"] + ["sliding_attention"] * 3
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": TYPES * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
REDUCED = ["num_hidden_layers", "experts_held", "vocab_rows_held",
           "attention_heads_held", "kv_heads_held", "dense_columns_held",
           "shared_expert_columns_held"]
WORK = load_by_path("work", "laguna")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]


def hand_laguna():
    """FLOPs a token of the corpus, by hand from the configuration's held
    shapes: 2 full blocks of 6 query heads and 3 windowed ones of 9 over 1
    K/V head of 128, each with its gate; a dense MLP of 1,536 columns in
    block 0; in blocks 1-4 a 256-wide router, 128 shared columns and 10 x 8
    / 256 expert passes of 1,024 columns; the triangle at 6 heads and the
    512-key band at 9; a readout of 12,544 rows."""
    d, hd, S, W, V = 3072, 128, 16384, 512, 12544
    attn = lambda h: d * (h * hd + 2 * hd) + h * hd * d + d * h
    dense = 6 * (2 * attn(6) + 3 * attn(9) + 3 * d * 1536
                 + 4 * (d * 256 + 3 * d * 128))
    routed = 6 * 4 * (10 * 8 / 256) * 3 * d * 1024
    triangle = S * (S + 1) // 2
    band = W * (W + 1) // 2 + (S - W) * W
    pairs = 3 * 2 * (hd + hd) * (2 * 6 * triangle + 3 * 9 * band) / S
    return dense + routed + pairs + 6 * d * V


#: what ``test_step_mfu.py``'s table of hand counts lacks for this cell (a PR
#: may not edit that file): ``conftest.py`` here, and the tier-1 collector
#: ``tests/test_perf_step_scope_readers.py``, add it before its cases run
HAND = {"laguna-s-2.1": hand_laguna}


def test_published_keys_verbatim_and_the_seven_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == REDUCED
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    assert [CONFIG[k] for k in REDUCED] == [5, 8, 12544, 6, 1, 1536, 128]
    # everything dense 8-way, the routed experts 32-way; no width is cut
    assert 32 * CONFIG["experts_held"] == CONFIG["num_experts"]
    for held, whole in (("vocab_rows_held", "vocab_size"),
                        ("attention_heads_held", "num_attention_heads"),
                        ("kv_heads_held", "num_key_value_heads"),
                        ("dense_columns_held", "intermediate_size"),
                        ("shared_expert_columns_held",
                         "shared_expert_intermediate_size")):
        assert 8 * CONFIG[held] == CONFIG[whole], held
    entry = next(c for c in BENCH["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == REDUCED and len(entry["why"]) <= 200
    assert entry["file"] == "perf/configs/laguna-s-2.1.json"
    for key in ("deployment", "assumed", "departures"):
        assert CONFIG[key]
    for key in ("rule", "gate_form", "router_score", "router_extras",
                "no_qk_norm", "no_shared_gate", "no_selection_bias",
                "moe_aux_weight", "window_edge", "rotary", "optimizer",
                "embed_std", "wqkv", "data", "dataset"):
        assert CONFIG["assumed"][key], key
    assert "sigmoid" in CONFIG["assumed"]["router_score"]  # both readings


def test_app_params_are_the_sources_sizes():
    app, c = APP, CONFIG
    n = c["num_hidden_layers"]
    per_layer = c["num_attention_heads_per_layer"][:n]
    kinds = c["layer_types"][:n]
    assert kinds == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert (app["d_model"], app["mha_head_dim"], app["d_ff"],
            app["moe_experts"], app["moe_top_k"], app["moe_norm_topk"],
            app["moe_routed_scale"], app["norm_eps"], app["tie_embeddings"],
            app["window"], app["moe_first_dense"]) == (
        c["hidden_size"], c["head_dim"], c["moe_intermediate_size"],
        c["num_experts"], c["num_experts_per_tok"], c["norm_topk_prob"],
        c["moe_routed_scaling_factor"], c["rms_norm_eps"],
        c["tie_word_embeddings"], c["sliding_window"],
        len(c["mlp_only_layers"]))
    assert app["window_layers"] == [
        i for i, k in enumerate(kinds) if k == "sliding_attention"]
    # the held eighth of each kind's published head count, by kind
    full = {h for h, k in zip(per_layer, kinds) if k == "full_attention"}
    swa = {h for h, k in zip(per_layer, kinds) if k == "sliding_attention"}
    assert (full, swa) == ({48}, {72})
    assert app["kind_heads"] == {"full": 48 // 8, "swa": 72 // 8}
    assert (app["n_heads"], app["n_kv_heads"]) == (
        c["attention_heads_held"], c["kv_heads_held"])
    # both rotaries are the published groups, whole
    assert app["kind_rope"] == {
        "full": c["rope_parameters"]["full_attention"],
        "swa": c["rope_parameters"]["sliding_attention"]}
    assert app["attn_gate"] == "head" and set(c["gating_types"]) == {"per_head"}
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"],
            app["dense_d_ff"], app["moe_shared_d_ff"]) == (
        n, c["experts_held"], c["vocab_rows_held"], c["dense_columns_held"],
        c["shared_expert_columns_held"])
    assert (app["pos"], app["ffn"], app.get("moe_score", "softmax"),
            app["moe_shared_experts"], app["moe_every"]) == (
        "rope", "swiglu", "softmax", 1, c["decoder_sparse_step"])
    assert CONFIG["job"]["data_args"] == {
        "seq_len": app["max_seq"] + 1, "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == app["max_seq"]
    assert app["max_seq"] in (16384, 8192)  # the AOT figure chose (job.why)
    for key, text in CONFIG["job"]["why"].items():
        assert text and "TBD" not in text and "TODO" not in text, key


def test_the_trainer_takes_the_app_params_and_counts_436m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**APP)
    assert tr.num_params == 435_836_928
    assert tr.hyperparams() == {"lr": 2e-6, "beta2": 0.95}
    assert tr.config.embed_std == 1.0
    assert tr.config.layer_kinds() == ("full", "swa", "swa", "swa", "full")
    assert tr.config.moe_layers() == (1, 2, 3, 4)
    assert [tr.config.heads(k) for k in ("full", "swa")] == [6, 9]
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    attention = lambda l: size({k: l[k] for k in ("wqkv", "wo", "wgate")})
    dense, swa, full = (shapes["layers"][i] for i in (0, 1, 4))
    assert dense["wqkv"].shape == (3072, 768 + 128 + 128)
    assert swa["wqkv"].shape == (3072, 1152 + 128 + 128)
    assert (dense["wgate"].shape, swa["wgate"].shape) == ((6, 3072), (9, 3072))
    assert (attention(dense), attention(swa), attention(full)) == (
        5_523_456, 7_891_968, 5_523_456)
    assert size({k: dense[k] for k in ("w1", "w2", "w3")}) == 14_155_776
    moe = swa["moe"]
    assert set(moe) == {"router", "wg", "wu", "wd", "shared_wg", "shared_wu",
                        "shared_wd"}
    assert moe["router"].shape == (3072, 256)
    assert size({k: moe[k] for k in ("wg", "wu", "wd")}) == 75_497_472
    assert size({k: v for k, v in moe.items() if "shared" in k}) == 1_179_648
    assert (size(dense), size(swa), size(full)) == (
        19_685_376, 85_361_664, 82_993_152)
    assert shapes["embed"].shape == (12544, 3072) == shapes["head"].shape[::-1]
    for part in ("5,523,456", "7,891,968", "14,155,776", "75,497,472",
                 "1,179,648", "786,432", "435,836,928"):
        assert part in CONFIG["deployment"], part


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "solo", 1)
    assert len(cell["why"]) <= 200 and "closed loop" in cell["why"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, better in (("hetero_flash_roofline_share", "higher"),
                         ("attn_gate_time_share", "lower")):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "lm_tokens_per_s" and m["better"] == better
        assert m["source"] == "device_trace"
        assert os.path.exists(os.path.join(PERF, "layer_metrics", name + ".py"))
    for name in ("step_mfu_share", "flash_masked_share", "flash_time_share",
                 "mixer_time_share", "moe_time_share", "ffn_time_share",
                 "head_loss_time_share", "dense_matmul_roofline_share",
                 "expert_load_max_over_mean", "moe_chunks_per_call",
                 "peak_hbm_share", "device_idle_share", "window_stall_s",
                 "table_path_time_share"):
        assert CELL in by_name[name]["workloads"], name
    # the grouped matmuls' reader counts this model's shapes rightly through
    # Moonlight's work file (four expert layers after the leading dense one,
    # experts 1,024 wide) — but it pairs 9 calls a layer with a step's rows,
    # and under ``remat`` the gate and up products run again in the backward:
    # 44 calls a step where it wants 36, so it refuses, as it does in Kimi
    # Linear's cell (PERF.md section 7), and the cell is not on its list
    moon = load_by_path("work", "moonlight")
    assert moon.moe_layers(APP) == 4 and APP["remat"]
    assert moon.gmm_flops_per_call(APP, 640 * 8) == 2 * 5120 * 3072 * 1024
    assert CELL not in by_name["routed_gmm_roofline_share"]["workloads"]
    assert CONFIG["job"]["comm_probe_period"] == 0
    # none of the four readers that key the two-kernel backward's names
    for name in ("flash_roofline_share", "swa_flash_roofline_share",
                 "cca_flash_roofline_share", "bd_flash_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert CELL in rate["workloads"]
    assert CONFIG["job"]["flops_fn"] == "laguna:train_flops_per_token"


# -- the work functions ------------------------------------------------------

def test_flops_a_token_equal_the_hand_count_at_two_shapes():
    from perf import work_models

    assert work_models.count(CONFIG["job"], "flops_fn") == pytest.approx(
        hand_laguna(), rel=1e-12)
    parts = work_models.split(CONFIG["job"])
    assert tuple(parts) == work_models.PARTS and parts["scans"] == 0.0
    assert sum(parts.values()) == pytest.approx(hand_laguna(), rel=1e-12)
    # the rehearse preset, by hand again: d 64, heads 4 / 6 over 2 of 16, a
    # window of 8 over 48 positions, dense 96, 16 experts top-4 with 8 held
    # of 32 columns, 16 shared columns, 512 rows
    tiny = {**APP, **CONFIG["rehearse"]["app_params"]}
    d, hd, S, W = 64, 16, 48, 8
    attn = lambda h: d * (h * hd + 2 * 2 * hd) + h * hd * d + d * h
    dense = 6 * (2 * attn(4) + 3 * attn(6) + 3 * d * 96
                 + 4 * (d * 16 + 3 * d * 16))
    routed = 6 * 4 * (4 * 8 / 16) * 3 * d * 32
    pairs = 3 * 2 * (hd + hd) * (
        2 * 4 * (S * (S + 1) // 2)
        + 3 * 6 * (W * (W + 1) // 2 + (S - W) * W)) / S
    assert WORK.train_flops_per_token(tiny) == pytest.approx(
        dense + routed + pairs + 6 * d * 512, rel=1e-12)
    with pytest.raises(ValueError, match="not counted here"):
        WORK.train_flops_per_token({**APP, "moe_latent": 4})
    with pytest.raises(ValueError, match="not counted here"):
        WORK.train_flops_per_token({**APP, "attn_gate": "none"})


def test_each_kernel_is_credited_by_its_own_kinds_heads_and_mask():
    S, W, hd = 16384, 512, 128
    triangle, band = S * (S + 1) // 2, W * (W + 1) // 2 + (S - W) * W
    want = {"harmony_flash_fwd": 2 * 2 * hd * 6 * triangle,
            "harmony_flash_bwd": 2 * 5 * hd * 6 * triangle,
            "harmony_flash_win_fwd": 2 * 2 * hd * 9 * band,
            "harmony_flash_win_bwd": 2 * 5 * hd * 9 * band}
    assert set(WORK.KERNELS) == set(want)
    for kernel, flops in want.items():
        assert WORK.flash_flops_per_call(APP, 1, kernel) == flops
        assert WORK.flash_flops_per_call(APP, 3, kernel) == 3 * flops
        row = WORK.bound_seconds(APP, 1, kernel, PEAKS)
        assert row["binds"] == "bf16 MXU peak"
        assert row["seconds_bound"] == flops / PEAKS["bf16_flops"]
    # bytes: q, k, v, o and a float32 statistic a row; the backward q, dO, k,
    # v, two statistics, dq, dk, dv — the kind's heads, ONE K/V head
    row = lambda h: h * S * hd * 2
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_win_fwd") == (
        2 * row(9) + 2 * row(1) + 9 * S * 4)
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_bwd") == (
        3 * row(6) + 4 * row(1) + 2 * 6 * S * 4)
    # the kernels' names are the ones this tree's ops/attention.py gives
    from harmony_tpu.ops import attention as A

    assert set(WORK.KERNELS) == set(A._KERNEL_NAMES.values()) | set(
        A._WIN_KERNEL_NAMES.values())


# -- the readers -------------------------------------------------------------

def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace without the kernels or the scope (the
    recorded fixtures: every trace of the parent), another cell: None, and
    nothing raised."""
    roof = load_by_path("layer_metrics", "hetero_flash_roofline_share")
    gate = load_by_path("layer_metrics", "attn_gate_time_share")
    assert roof.read({"trace": None}) is None
    assert roof.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None
    assert gate.read({"trace": None}) is None
    assert (roof.LAYER, roof.UNIT, gate.LAYER, gate.SCOPE) == (
        "kernels", "%", "model", "blk*/mixer.gate")


def test_roofline_credits_each_kind_and_no_kernel_passes_100(
        monkeypatch, capsys):
    """A hand-made op list at the cell's sizes, each call taking twice its
    bound: every share reads 50, and the windowed call is credited 9 heads x
    the band where the full one is credited 6 x the triangle."""
    from perf import trace_reduce

    fk = load_by_path("layer_metrics", "_flash_kernels")
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops, t = [], 0.0
    for kernel in sorted(WORK.KERNELS):
        ns = 2e9 * WORK.bound_seconds(APP, 1, kernel, PEAKS)["seconds_bound"]
        for i in range(3 if "_win_" in kernel else 2):
            ops.append((call(f"{kernel}.{i + 1}"), t, t + ns))
            t += ns
    monkeypatch.setattr(fk.trace_reduce, "device_ops", lambda profile: {0: ops})
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    found = fk.kernel_seconds(None)
    assert found["kernels"]["harmony_flash_win_bwd"]["calls"] == 3
    roof = load_by_path("layer_metrics", "hetero_flash_roofline_share")
    monkeypatch.setattr(roof, "of_this_run", lambda: found)
    obs = {"trace": {"busy_s": 1.0}, "phases": {CELL + "-run-t0": None}}
    import jax

    class _Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert roof.read(obs) == pytest.approx(50.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "hetero_flash_roofline"
    assert set(line["kernels"]) == set(WORK.KERNELS)
    for name, row in line["kernels"].items():
        assert row["roofline_share"] == pytest.approx(50.0)
        assert row["roofline_share"] <= 100.0
        assert row["heads"] == (9 if "_win_" in name else 6)
    ratio = (line["kernels"]["harmony_flash_win_fwd"]["gflop_per_call"]
             / line["kernels"]["harmony_flash_fwd"]["gflop_per_call"])
    assert ratio == pytest.approx(9 * 8_257_792 / (6 * 134_225_920))
    # a kernel the cell's work file has no row for: named in the line, and
    # no share; a cell whose work file counts no flash kernels: nothing
    odd = {"busy_s": 1.0, "kernels": {"harmony_flash_bwd_dq": {
        "seconds": 1.0, "calls": 1}}}
    monkeypatch.setattr(roof, "of_this_run", lambda: odd)
    assert roof.read(obs) is None
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["uncounted"] == ["harmony_flash_bwd_dq"] and not line["kernels"]
    assert line["work"] == "work/laguna.py"
    monkeypatch.setattr(roof, "of_this_run", lambda: found)
    monkeypatch.setitem(obs, "phases", {"smallthinker-21b-a3b.solo-run-t0": None})
    assert roof.read(obs) is None


def test_masked_share_reads_each_kinds_calls(monkeypatch):
    """``flash_masked_share`` (the accepted reader) on this model's gauges:
    the full kernels twice a step, the windowed ones three times."""
    from harmony_tpu.metrics import kda
    from harmony_tpu.runtime import progcache

    job = CELL + "-run-t0"
    band = lambda share, computed, group: {
        "window": 0, "kv_heads": 1, "group": group, "band_grid_steps": 1,
        "sub_blocks": 1, "masked_sub_blocks": 1, "computed": computed,
        "masked_share": share}
    monkeypatch.setattr("harmony_tpu.tracing.span.current_job", lambda: job)
    progcache.note_kernel_plan("harmony_flash_bwd", 16384, 512, 512, 1, True,
                               d=128, dv=128, band=band(0.1, 100.0, 6))
    progcache.note_kernel_plan("harmony_flash_win_bwd", 512, 512, 512, 1,
                               True, d=128, dv=128, band=band(0.5, 40.0, 9))
    kda.note_layer_kinds(job, ("full", "swa", "swa", "swa", "full"),
                         heads={"full": 6, "swa": 9})
    reader = load_by_path("layer_metrics", "flash_masked_share")
    want = 100.0 * (200 * 0.1 + 120 * 0.5) / (200 + 120)
    assert reader.read({"phases": {job: None}}) == pytest.approx(want)
    rows = {r["kernel"]: r for r in progcache.kernel_plans()[job]}
    assert rows["harmony_flash_bwd"]["group"] == 6
    assert rows["harmony_flash_win_bwd"]["group"] == 9


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (both kinds of block, 4 / 6
    query heads over 2 K/V heads, a window of 8 over 48 positions, YaRN past
    its original 16, 16 experts top-4 with 8 held) through the jobserver, the
    logits check in its three ranges and the replay, to a last line that
    says ``correct``."""
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
    assert check["ranges"] == {"before_window": [0, 8],
                               "window_to_original": [8, 16],
                               "past_original": [16, None]}
    assert set(check["detected"]) == set(
        load_by_path("reference", "laguna-s-2.1").RUN_ABLATIONS)
    assert check["gradients"]["worst"] < check["gradients"]["limit"]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
