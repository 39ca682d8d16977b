"""Checks of the ``olmoe-1b-7b`` configuration's own files (PR 25). Run with
the rest of ``perf/tests``; CPU only, nothing here is a measurement."""
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

CONFIG = json.load(open(os.path.join(PERF, "configs", "olmoe-1b-7b.json")))
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_published_keys_verbatim_and_the_three_cuts():
    changed = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab_rows_held"]
    assert (CONFIG["num_hidden_layers"], CONFIG["experts_held"],
            CONFIG["vocab_rows_held"]) == (2, 16, 12576)
    assert 4 * CONFIG["experts_held"] == CONFIG["num_experts"]
    assert 4 * CONFIG["vocab_rows_held"] == CONFIG["vocab_size"]


def test_app_params_are_the_sources_sizes():
    app, c = CONFIG["job"]["app_params"], CONFIG
    assert (app["d_model"], app["n_heads"], app["d_ff"], app["max_seq"],
            app["moe_experts"], app["moe_top_k"], app["norm_eps"],
            app["rope_theta"], app["tie_embeddings"]) == (
        c["hidden_size"], c["num_attention_heads"], c["intermediate_size"],
        c["max_position_embeddings"], c["num_experts"],
        c["num_experts_per_tok"], c["rms_norm_eps"], c["rope_theta"],
        c["tie_word_embeddings"])
    assert c["norm_topk_prob"] is False  # the program never renormalises
    assert (app["n_layers"], app["moe_experts_held"], app["vocab_size"]) == (
        c["num_hidden_layers"], c["experts_held"], c["vocab_rows_held"])
    assert (app["pos"], app["qk_norm"], app["ffn"], app["moe_every"]) == (
        "rope", True, "swiglu", 1)
    assert CONFIG["job"]["data_args"] == {
        "seq_len": c["max_position_embeddings"] + 1,
        "vocab_size": c["vocab_rows_held"]}
    assert CONFIG["job"]["units_per_example"] == c["max_position_embeddings"]


def test_the_trainer_takes_the_app_params_and_counts_287m():
    from harmony_tpu.models import TransformerTrainer

    tr = TransformerTrainer(**CONFIG["job"]["app_params"])
    assert tr.num_params == 286_672_896  # 2 x 117.58 M + 51.51 M
    assert tr.hyperparams() == {"lr": 0.0004, "beta2": 0.95}  # the issue's


def test_work_function_counts_the_held_rows_only():
    work = load_by_path("work", "olmoe")
    app, batch = CONFIG["job"]["app_params"], CONFIG["job"]["batch"]
    assert work.moe_layers(app) == 2
    assert work.slots_per_step(app, batch) == 65536
    per_call = work.gmm_flops_per_call(app, 16384)
    assert per_call == 2.0 * 16384 * 2048 * 1024
    assert work.gmm_flops_per_step(app, 16384) == 18 * per_call


def test_kernel_readers_fold_events_by_kernel_name():
    mk = load_by_path("layer_metrics", "_moe_kernels")
    for text, want in (("harmony_gmm_fwd", "harmony_gmm_fwd"),
                       ("harmony_gmm_dw.12", "harmony_gmm_dw"),
                       ("harmony_moe_route.3", "harmony_moe_route"),
                       ("harmony_flash_fwd.1", None), ("fusion.7", None)):
        m = mk.KERNEL.match(text)
        assert (m.group(1) if m else None) == want
    for name in ("moe_time_share", "gmm_roofline_share"):
        reader = load_by_path("layer_metrics", name)
        assert reader.read({"trace": None}) is None


def _steps(starts):
    """Executions of the step program at ``starts`` (8 long) and their two
    calls (1 long, at start + 1 and start + 4)."""
    runs = [(float(t), float(t) + 8.0) for t in starts]
    calls = [("harmony_gmm_fwd", t + at, t + at + 1.0)
             for t, _ in runs for at in (1.0, 4.0)]
    return runs, calls


def test_roofline_pairs_each_traced_call_with_its_own_steps_rows():
    """Two calls a step, the device's module events cut them. Window 1 (held
    slots 10, 20, 30 a step) began before the trace: its first step is a lone
    call with no module event around it, then the steps at 10 and 20. Window
    2 (40, 50): the steps at 30 and 40. Window 3's (50, 60) rows are reported
    after the trace ended. Each window's span opens inside the NEXT window's
    first step, after that step's first call (at 33 and 52: what the calls'
    own times could not cut since PR 42)."""
    mk = load_by_path("layer_metrics", "_moe_kernels")
    runs, calls = _steps((10, 20, 30, 40, 50, 60))
    calls = [("harmony_gmm_fwd", 5.0, 6.0)] + calls
    idle = [(28.5, 29.5), (48.5, 49.5)]  # the drain's own programs: no call
    spans = [(33.0, [10.0, 20.0, 30.0]), (52.0, [40.0, 50.0])]
    got = mk.pair(calls, runs + idle, spans, 2)
    # the steps at 10, 20, 30 and 40, two calls each, in order
    assert [slots for _, _, slots in got] == [20.0, 20.0, 30.0, 30.0,
                                              40.0, 40.0, 50.0, 50.0]
    assert all(name == "harmony_gmm_fwd" and abs(sec - 1e-9) < 1e-15
               for name, sec, _ in got)
    # a window replayed epoch by epoch reports its steps in a burst of spans
    assert got == mk.pair(calls, runs, [(33.0, [10.0]), (33.5, [20.0, 30.0]),
                                        (52.0, [40.0]), (53.0, [50.0])], 2)
    # the trace's end cut the last execution: left out, not refused
    assert got == mk.pair(calls[:-1], runs, spans, 2)
    # an execution with another count of calls than a step's; other than the
    # span's number of steps between two spans: no pairing, no number
    extra = [("harmony_gmm_dw", 36.0, 37.0)]
    assert mk.pair(calls + extra, runs, spans, 2) is None
    assert mk.pair(calls, runs, [spans[0], (52.0, [40.0, 45.0, 50.0])], 2) is None
    assert mk.pair(calls, runs, spans, 3) is None
    # no span, or no module event to cut by: nothing to pair
    assert mk.pair(calls, runs, [], 2) == []
    assert mk.pair(calls, [], spans, 2) == []


@pytest.mark.parametrize("fixture", ["fixture_1chip", "fixture_4chip",
                                     "fixture_scopes", "fixture_spans"])
def test_the_devices_module_events_hold_every_operation(fixture):
    """What ``pair`` cuts by, on traces recorded on the chip: the first
    device's executed programs, in order and apart, and every ``XLA Ops``
    event of that device starts inside one of them."""
    mk = load_by_path("layer_metrics", "_moe_kernels")
    profile = mk.trace_reduce.load(os.path.join(PERF, "tests",
                                                fixture + ".xplane.pb"))
    runs = mk.module_runs(profile)
    assert len(runs) >= 5 and runs == sorted(runs)
    assert all(a[1] <= b[0] for a, b in zip(runs, runs[1:]))
    per_dev = mk.trace_reduce.device_ops(profile)
    ops = per_dev[min(d for d, found in per_dev.items() if found)]
    assert ops and all(any(s <= at < e for s, e in runs) for _, at, _ in ops)


def test_the_configuration_comes_from_the_measured_jobs_id():
    mk = load_by_path("layer_metrics", "_moe_kernels")
    cell = mk.cell_of(["olmoe-1b-7b.solo-run-t0"])
    assert cell.name == "olmoe-1b-7b.solo"
    assert cell.job["app_params"]["d_ff"] == CONFIG["intermediate_size"]
    assert mk.cell_of(["gpt2-124m.pair-run-t1"]).name == "gpt2-124m.pair"
    assert mk.cell_of(["no-such-cell-run-t0"]) is None
    assert mk.traced_steps({"phases": {}}) is None


def test_the_drains_span_carries_each_steps_held_slots(tmp_path):
    """Program and reader joined: under a profiler session
    ``metrics/moe.py`` ``observe`` opens ``moe.observe`` with the drained
    steps' held token-slots, and ``_moe_kernels.drains`` reads them back."""
    import glob

    import jax
    import numpy as np

    from harmony_tpu.metrics import moe

    mk = load_by_path("layer_metrics", "_moe_kernels")
    tokens = np.zeros((3, 2, 8))          # [steps, layers, experts]
    tokens[:, :, 0], tokens[:, :, 5] = [[1], [2], [3]], 100   # expert 5: absent
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        moe.observe("olmoe-1b-7b.solo-run-t0", tokens, experts_held=4)
        moe.observe("someone-else", tokens, experts_held=4)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = mk.drains(mk.trace_reduce.load(path), ["olmoe-1b-7b.solo-run-t0"])
    assert [held for _, held in found] == [[2.0, 4.0, 6.0]]
