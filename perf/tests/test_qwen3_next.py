"""Checks of the ``qwen3-next-80b-a3b`` configuration's own files (PR 61). Run
with the rest of ``perf/tests`` (and, all but the rehearsal, collected by
``tests/test_qwen3_next.py`` under tier-1); CPU only, nothing here is a
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
                                     "qwen3-next-80b-a3b.json")))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "qwen3-next-80b-a3b.solo"
APP = CONFIG["job"]["app_params"]
REDUCED = ["num_hidden_layers", "experts_held", "vocab_rows_held"]
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
WORK = load_by_path("work", "qwen3_next")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
CHUNK = 64


def chunk_flops(dk, dv):
    """The chunked delta rule's products a chunk (perf/work/kimi_linear.py's
    docstring), by hand."""
    return CHUNK * CHUNK * (3 * dk + 2 * dv) + 6 * CHUNK * dk * dv


def hand_qwen3_next():
    """FLOPs a token of the corpus, by hand from the published shapes: three
    Gated DeltaNet mixers (2048 x 12288 + 2048 x 64 + 4096 x 2048) and one
    gated attention mixer (2048 x 9216 + 4096 x 2048), four expert layers
    (router 2048 x 512, shared 3 x 2048 x 512, ten of 512 experts of which 16
    are held), the 256-wide triangle at 16 heads, three scans of 32 value
    heads, the 18,992-column readout."""
    d, S, V = 2048, APP["max_seq"], 18992
    gdn = d * 12288 + d * 64 + 4096 * d
    attn = d * (8192 + 512 + 512) + 4096 * d
    moe = d * 512 + 3 * d * 512
    dense = 6 * (3 * gdn + attn + 4 * moe)
    routed = 6 * 4 * (10 * 16 / 512) * 3 * d * 512
    pairs = 3 * 16 * 2 * (256 + 256) * (S * (S + 1) // 2) / S
    scans = 3 * 3 * 32 * chunk_flops(128, 128) / CHUNK
    return dense + routed + pairs + scans + 6 * d * V


#: what ``test_step_mfu.py``'s table of hand counts lacks for this cell (a PR
#: may not edit that file): ``conftest.py`` here, and the tier-1 collector
#: ``tests/test_perf_step_scope_readers.py``, add it before its cases run
HAND = {"qwen3-next-80b-a3b": hand_qwen3_next}


# -- the configuration file ----------------------------------------------------

def test_published_keys_verbatim_and_the_three_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == REDUCED == list(CONFIG["reduced_from"])
    assert [CONFIG[k] for k in REDUCED] == [4, 16, 18992]
    assert 32 * CONFIG["experts_held"] == CONFIG["num_experts"]
    assert 8 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert entry["reduced"] == REDUCED and len(entry["why"]) <= 200
    assert entry["file"] == "perf/configs/qwen3-next-80b-a3b.json"
    assert BENCH["configs"][-1] is entry  # appended, nothing moved
    for key in ("deployment", "assumed", "departures"):
        assert CONFIG[key]
    for key in ("rule", "no_mtp", "w_qkvz_order", "w_ba_order", "wq_order",
                "decay_init", "conv", "l2_norm", "gdn_out", "attention",
                "router", "moe_aux_weight", "optimizer", "embed_std", "remat",
                "data", "dataset"):
        assert CONFIG["assumed"][key], key
    assert "32-way expert parallel" in CONFIG["deployment"]
    assert "first 4 of 48 layers" in CONFIG["deployment"]


def test_app_params_are_the_sources_sizes():
    app, c = APP, CONFIG
    n = c["num_hidden_layers"]
    assert (app["d_model"], app["n_heads"], app["n_kv_heads"],
            app["mha_head_dim"], app["d_ff"], app["moe_experts"],
            app["moe_top_k"], app["moe_norm_topk"], app["norm_eps"],
            app["tie_embeddings"], app["rope_theta"], app["rope_fraction"],
            app["linear_heads"], app["linear_value_heads"],
            app["linear_head_dim"], app["short_conv"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["moe_intermediate_size"], c["num_experts"],
        c["num_experts_per_tok"], c["norm_topk_prob"], c["rms_norm_eps"],
        c["tie_word_embeddings"], c["rope_theta"], c["partial_rotary_factor"],
        c["linear_num_key_heads"], c["linear_num_value_heads"],
        c["linear_key_head_dim"], c["linear_conv_kernel_dim"])
    assert c["linear_key_head_dim"] == c["linear_value_head_dim"]
    assert c["shared_expert_intermediate_size"] == app["d_ff"]  # 1 expert wide
    # layer i is softmax attention where (i + 1) % interval == 0
    assert app["linear_layers"] == [
        i for i in range(n) if (i + 1) % c["full_attention_interval"]]
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        n, c["experts_held"], c["vocab_rows_held"])
    assert (app["pos"], app["ffn"], app["linear_kind"], app["attn_gate"],
            app["head_norm"], app["norm_offset"], app["moe_shared_gate"],
            app["moe_shared_experts"], app["moe_every"]) == (
        "rope", "swiglu", "gdn", "element", True, True, True, 1,
        c["decoder_sparse_step"])
    assert CONFIG["job"]["data_args"] == {
        "seq_len": app["max_seq"] + 1, "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == app["max_seq"]
    assert app["max_seq"] in (16384, 8192)  # the AOT figure chose (job.why)
    for key, text in CONFIG["job"]["why"].items():
        assert text and "TBD" not in text and "TODO" not in text, key


def test_the_trainer_takes_the_app_params_and_counts_424m_by_part():
    import jax
    import numpy as np

    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**APP)
    assert tr.num_params == 424_340_544
    assert tr.hyperparams() == {"lr": 2e-6, "beta2": 0.95}
    assert tr.config.layer_kinds() == ("gdn", "gdn", "gdn", "mha")
    assert tr.config.moe_layers() == (0, 1, 2, 3)
    assert [tr.config.heads(k) for k in ("gdn", "mha")] == [32, 16]
    shapes = jax.eval_shape(lambda: tr.model.init(jax.random.PRNGKey(0)))
    size = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    gdn, attn = shapes["layers"][0], shapes["layers"][3]
    assert {k: v.shape for k, v in gdn["gdn"].items()} == {
        "w_qkvz": (2048, 12288), "conv": (4, 8192), "w_ba": (64, 2048),
        "a_log": (32,), "dt_bias": (32,), "o_norm": (128,),
        "wo": (4096, 2048)}
    assert size(gdn["gdn"]) == 33_718_464
    assert attn["wqkv"].shape == (2048, 8192 + 512 + 512)
    assert size({k: attn[k] for k in ("wqkv", "wo", "q_head_norm",
                                      "k_head_norm")}) == 27_263_488
    moe = gdn["moe"]
    assert set(moe) == {"router", "wg", "wu", "wd", "shared_wg", "shared_wu",
                        "shared_wd", "shared_gate"}
    assert moe["router"].shape == (2048, 512) and size(moe) == 54_528_000
    assert (size(gdn), size(attn)) == (88_250_560, 81_795_584)
    assert shapes["embed"].shape == (18992, 2048) == shapes["head"].shape[::-1]
    for part in ("33,718,464", "27,263,488", "54,528,000", "88,250,560",
                 "81,795,584", "38,895,616", "424,340,544"):
        assert part in CONFIG["deployment"], part


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert BENCH["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "solo", 1)
    assert len(cell["why"]) <= 200 and "closed loop" in cell["why"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == [
        "gdn_time_share", "gdn_roofline_share"]
    for name, better in (("gdn_time_share", "lower"),
                         ("gdn_roofline_share", "higher")):
        m = by_name[name]
        assert m == {"name": name, "unit": "%", "better": better,
                     "source": "device_trace", "layer": "kernels",
                     "moves": "lm_tokens_per_s", "workloads": [CELL]}
        reader = load_by_path("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            "kernels", "%", "device_trace")
    kimi = "kimi-linear-48b-a3b.solo"
    apart = {"kda_time_share", "kda_roofline_share", "flash_roofline_share"}
    for name, m in by_name.items():
        if kimi in (m.get("workloads") or []):
            assert (CELL in m["workloads"]) == (name not in apart), name
            if name not in apart:
                assert m["workloads"][-1] == CELL
    for name in ("hetero_flash_roofline_share", "attn_gate_time_share",
                 "step_mfu_share", "flash_time_share", "moe_time_share",
                 "peak_hbm_share"):
        assert by_name[name]["workloads"][-1] == CELL, name
    # the four readers that key the two-kernel backward's names, and the
    # grouped matmuls' (ledger notes, PRs 56-60): a benchmark PR's
    for name in ("flash_roofline_share", "swa_flash_roofline_share",
                 "cca_flash_roofline_share", "bd_flash_roofline_share",
                 "gmm_roofline_share", "routed_gmm_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == "lm_tokens_per_s")
    assert rate["workloads"][-1] == CELL
    assert CONFIG["job"]["flops_fn"] == "qwen3_next:train_flops_per_token"
    assert CONFIG["job"]["comm_probe_period"] == 0
    assert CONFIG["job"]["env"] == {"HARMONY_EPOCH_WINDOW": "2"}


# -- the work functions ------------------------------------------------------

def test_flops_a_token_equal_the_hand_count_at_two_shapes():
    from perf import work_models

    assert work_models.count(CONFIG["job"], "flops_fn") == pytest.approx(
        hand_qwen3_next(), rel=1e-12)
    parts = work_models.split(CONFIG["job"])
    assert tuple(parts) == work_models.PARTS and min(parts.values()) > 0
    assert sum(parts.values()) == pytest.approx(hand_qwen3_next(), rel=1e-12)
    # the rehearse preset, by hand again: d 64, 2 key / 4 value heads of 16,
    # 4-over-2 heads of 32, 80 positions (two chunks: one and a part),
    # 16 experts top-4 with 8 held of 32 columns, 512 rows
    tiny = {**APP, **CONFIG["rehearse"]["app_params"]}
    d, S = 64, 80
    gdn = d * (2 * 32 + 2 * 64) + d * 8 + 64 * d
    attn = d * (2 * 128 + 64 + 64) + 128 * d
    dense = 6 * (3 * gdn + attn + 4 * (d * 16 + 3 * d * 32))
    routed = 6 * 4 * (4 * 8 / 16) * 3 * d * 32
    pairs = 3 * 4 * 2 * (32 + 32) * (S * (S + 1) // 2) / S
    scans = 3 * 3 * 4 * 2 * chunk_flops(16, 16) / S
    assert WORK.train_flops_per_token(tiny) == pytest.approx(
        dense + routed + pairs + scans + 6 * d * 512, rel=1e-12)
    for change in ({"moe_latent": 4}, {"attn_gate": "head"},
                   {"linear_kind": "kda"}):
        with pytest.raises(ValueError, match="not counted here"):
            WORK.train_flops_per_token({**APP, **change})


def test_each_kernel_is_credited_with_the_scalar_need():
    S, dh, hd = APP["max_seq"], 128, 256
    chunks = 32 * (S // CHUNK)
    for name, which, times in (("harmony_gdn_fwd", "fwd", 1),
                               ("harmony_gdn_bwd", "bwd", 3),
                               ("harmony_kda_fwd", "fwd", 1),
                               ("harmony_kda_bwd", "bwd", 3)):
        assert WORK.GDN_KERNELS[name] == which
        assert WORK.gdn_flops_per_call(APP, 1, name) == (
            times * chunks * chunk_flops(dh, dh))
        assert WORK.gdn_bound_seconds(APP, 1, name, PEAKS)["binds"] == "HBM peak"
    # bytes: q and k ONCE a key head (16), v and o a value head (32), g and
    # beta float32 [S] a value head, S / 64 boundary states a value head
    qk, v = 2 * 16 * S * dh * 2, 32 * S * dh * 2
    scalars, states = 2 * 32 * S * 4, 32 * (S // CHUNK) * dh * dh * 4
    assert WORK.gdn_bytes_per_call(APP, 1, "harmony_gdn_fwd") == (
        qk + v + scalars + v + states)
    assert WORK.gdn_bytes_per_call(APP, 1, "harmony_kda_bwd") == (
        2 * (qk + v + scalars) + v + states)
    assert WORK.gdn_bytes_per_call(APP, 2, "harmony_gdn_bwd") == 2 * (
        WORK.gdn_bytes_per_call(APP, 1, "harmony_gdn_bwd"))
    # the kernels' names are the ones this tree's ops/kda.py gives
    from harmony_tpu.ops import kda

    assert set(WORK.GDN_KERNELS) == set(kda.KERNEL_NAMES.values())
    # flash: 16 heads x the triangle, 256 wide, over 2 K/V heads
    triangle = S * (S + 1) // 2
    assert WORK.flash_flops_per_call(APP, 1, "harmony_flash_fwd") == (
        2 * 2 * hd * 16 * triangle)
    assert WORK.flash_flops_per_call(APP, 1, "harmony_flash_bwd") == (
        2 * 5 * hd * 16 * triangle)
    row = lambda h: h * S * hd * 2
    assert WORK.flash_bytes_per_call(APP, 1, "harmony_flash_fwd") == (
        2 * row(16) + 2 * row(2) + 16 * S * 4)
    assert (WORK.heads(APP, "full"), WORK.heads(APP, "gdn")) == (16, 32)
    assert WORK.bound_seconds(APP, 1, "harmony_flash_bwd", PEAKS)[
        "binds"] == "bf16 MXU peak"


# -- the readers -------------------------------------------------------------

def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A run without a trace, a trace without the kernels (every trace of the
    parent), another cell: None, and nothing raised."""
    roof = load_by_path("layer_metrics", "gdn_roofline_share")
    share = load_by_path("layer_metrics", "gdn_time_share")
    for reader in (roof, share):
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"busy_s": 1.0}, "phases": {}}) is None
    kernels = load_by_path("layer_metrics", "_gdn_kernels")
    assert kernels.KERNEL.match("harmony_gdn_fwd.3").group(1) == "harmony_gdn_fwd"
    assert kernels.KERNEL.match("harmony_kda_bwd").group(1) == "harmony_kda_bwd"
    assert kernels.KERNEL.match("harmony_flash_fwd") is None


def test_roofline_credits_the_scalar_need_and_no_kernel_passes_100(
        monkeypatch, capsys):
    """A hand-made op list at the cell's sizes, each call taking four times
    its bound: every share reads 25; the same under either kernel's name."""
    from perf import trace_reduce

    gk = load_by_path("layer_metrics", "_gdn_kernels")
    call = lambda name: (f"%{name} = bf16[2]{{0}} custom-call(bf16[2]{{0}} %p), "
                         f"custom_call_target=\"tpu_custom_call\"")
    ops, t = [], 0.0
    for kernel in ("harmony_gdn_fwd", "harmony_gdn_bwd"):
        ns = 4e9 * WORK.gdn_bound_seconds(APP, 1, kernel, PEAKS)["seconds_bound"]
        for i in range(3):
            ops.append((call(f"{kernel}.{i + 1}"), t, t + ns))
            t += ns
    monkeypatch.setattr(gk.trace_reduce, "device_ops", lambda profile: {0: ops})
    if trace_reduce.classify(ops[0][0]) != "kernel":
        pytest.skip("trace_reduce names kernels otherwise than this fixture")
    found = gk.kernel_seconds(None)
    assert found["kernels"]["harmony_gdn_bwd"]["calls"] == 3
    roof = load_by_path("layer_metrics", "gdn_roofline_share")
    share = load_by_path("layer_metrics", "gdn_time_share")
    monkeypatch.setattr(roof, "of_this_run", lambda: found)
    monkeypatch.setattr(share, "of_this_run", lambda: {**found, "busy_s":
                                                       2 * found["busy_s"]})
    obs = {"trace": {"busy_s": 1.0}, "phases": {CELL + "-run-t0": None}}
    import jax

    class _Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert roof.read(obs) == pytest.approx(25.0)
    assert share.read(obs) == pytest.approx(50.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["line"] == "gdn_roofline"
    assert set(line["kernels"]) == {"harmony_gdn_fwd", "harmony_gdn_bwd"}
    for row in line["kernels"].values():
        assert row["roofline_share"] == pytest.approx(25.0)
        assert row["roofline_share"] <= 100.0
        assert row["binds"] == "HBM peak"
    # a cell whose work file counts no such kernels: nothing
    monkeypatch.setitem(obs, "phases", {"kimi-linear-48b-a3b.solo-run-t0": None})
    assert roof.read(obs) is None


def test_rehearsal_runs_to_a_correct_line():
    """``--rehearse`` on the CPU: the tiny preset (three Gated DeltaNet blocks
    of 2 key / 4 value heads and a gated softmax block, 80 positions, 16
    experts top-4 with 8 held and a gated shared expert) through the
    jobserver, the logits check with its perturbed pass and the replay, to a
    last line that says ``correct``."""
    for _ in range(3):
        # the measured job is sized from the warm-up's rate; on a loaded CPU
        # host it can end inside the window, which is not what is tested
        out = subprocess.run(
            [sys.executable, os.path.join(PERF, "run.py"), "--workload", CELL,
             "--rehearse", "--seconds", "6", "--seed", "2147483659"],
            cwd=ROOT, capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [json.loads(x) for x in out.stdout.splitlines()
                 if x.startswith("{")]
        window = next(x for x in lines if x.get("line") == "window")
        if not window["ended_before_window_end"]:
            break
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    check = next(x for x in lines if x.get("line") == "logits_check")
    assert check["ok"] and check["dtype"] == "float32"
    assert set(check["ablations"]) == {"fp8_operands", "value_head_mod"}
    assert all(check["detected"].values())
    assert check["perturbed"]["q90"] <= check["limits"]["q90"]
