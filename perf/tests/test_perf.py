"""Checks of the benchmark's own files. Run by hand:

    python -m pytest perf/tests -q

Not under ``tests/``: the repo's tier-1 count does not change with these.
Everything here runs on the CPU; nothing here is a measurement.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf import rates, trace_reduce  # noqa: E402
from perf.run import load_by_path as _load  # noqa: E402
from perf.generators import criteo, random_tokens  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


# -- BENCHMARK.json resolves to files, names obey the driver's rule ---------

def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert NAME.match(key)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    if "bound" in entry:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert PATH.match(conf["file"]) and conf["file"].startswith("perf/")
    config = json.load(open(os.path.join(ROOT, conf["file"])))
    traffic = json.load(open(os.path.join(PERF, "traffic", cell["traffic"] + ".json")))
    job = config["job"]
    assert sorted(conf["reduced"]) == sorted(config["reduced"])
    assert os.path.exists(os.path.join(PERF, "reference", job["reference"] + ".py"))
    from harmony_tpu.config.base import resolve_symbol

    assert callable(resolve_symbol(job["data_fn"]))
    assert callable(resolve_symbol(job["trainer"]))
    from perf import work_models

    # the name resolves (perf/work_models.py, or "<sibling>:<function>" in
    # perf/work/<sibling>.py) and counts something; a FLOPs count has its twin
    for key in ("flops_fn", "bytes_fn"):
        if job.get(key):
            assert work_models.count(job, key) > 0
    if job.get("flops_fn"):
        assert sum(work_models.split(job).values()) \
            == work_models.count(job, "flops_fn")
    assert traffic["tenants"] and 0 < traffic["batch_share"] <= 1
    # a mix overrides only fields the configuration's job has, each with a why
    assert set(traffic.get("job", {})) <= set(job)
    assert set(traffic.get("job", {})) == set(traffic.get("why_job", {}))
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    assert "setup_s" in e2e and job["rate_metric"] in e2e
    layer = [m for m in BENCH["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_that_agrees(metric):
    reader = _load("layer_metrics", metric["name"].split(".")[0])
    assert reader.LAYER == metric["layer"]
    assert reader.UNIT == metric["unit"]
    assert reader.SOURCE == metric["source"]
    assert reader.read({}) is None  # nothing to read -> nothing reported


def test_gpt2_app_params_are_the_sources_sizes():
    c = json.load(open(os.path.join(PERF, "configs", "gpt2-124m.json")))
    app = c["job"]["app_params"]
    assert (app["vocab_size"], app["d_model"], app["n_heads"], app["n_layers"],
            app["d_ff"], app["max_seq"]) == (
        c["vocab_size"], c["n_embd"], c["n_head"], c["n_layer"],
        c["n_inner"], c["n_positions"])
    assert c["reduced"] == []


# -- generators ---------------------------------------------------------------

FIELDS = json.load(open(os.path.join(PERF, "configs", "criteo-fm.json")))[
    "num_embeddings_per_feature"]


def test_generators_same_seed_same_bytes():
    a = criteo.make(4096, 2 ** 20 - 1, FIELDS, seed=7)
    b = criteo.make(4096, 2 ** 20 - 1, FIELDS, seed=7)
    c = criteo.make(4096, 2 ** 20 - 1, FIELDS, seed=8)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].tobytes() != c[0].tobytes()
    t = random_tokens.make(8, 65, 512, seed=3)
    assert t.tobytes() == random_tokens.make(8, 65, 512, seed=3).tobytes()
    assert t.dtype == np.int32 and t.min() >= 0 and t.max() < 512


@pytest.mark.parametrize("config", ["criteo-fm", "criteo-fm-x4"])
def test_criteo_fields_keep_the_published_ranges(config):
    """The fields' ranges are the source's, cut only by the cap the
    configuration states under ``reduced_to``; the table is filled."""
    c = json.load(open(os.path.join(PERF, "configs", config + ".json")))
    args = c["job"]["data_args"]
    sizes, vocab = args["field_sizes"], args["vocab_size"]
    assert sizes == c["num_embeddings_per_feature"] and len(sizes) == 26
    assert vocab == c["job"]["app_params"]["vocab_size"]
    cap = criteo.max_ind_range(sizes, vocab)
    assert c["reduced_to"]["max_ind_range"].startswith(f"{cap}: ")
    first, per = criteo.field_ranges(sizes, vocab)
    assert list(per) == [min(n, cap) for n in sizes]
    assert first[0] == 0 and list(first[1:]) == list(np.cumsum(per)[:-1])
    assert vocab - 26 < per.sum() <= vocab
    assert sum(min(n, cap + 1) for n in sizes) > vocab  # the largest cap


def test_criteo_ids_have_a_zipf_tail():
    vocab = 2 ** 24 - 1
    ids, y = criteo.make(65536, vocab, FIELDS, zipf_a=1.05, seed=1)
    first, per = criteo.field_ranges(FIELDS, vocab)
    assert ids.dtype == np.int32 and ids.shape == (65536, 26)
    assert (ids >= first).all() and (ids < first + per).all()
    # a field of 36 ids is all duplicates; every one of its ids is drawn
    small = FIELDS.index(36)
    assert len(np.unique(ids[:, small])) == 36
    counts = np.sort(np.unique(ids[:, 0], return_counts=True)[1])[::-1]
    # a large field: heavy head, long tail - the hottest id takes percents
    # of the field, yet thousands of ids appear
    assert counts[0] / ids.shape[0] > 0.03 and len(counts) > 5000
    # log-log slope of count against rank over the head is about -a
    rank = np.arange(1, 65)
    slope = np.polyfit(np.log(rank), np.log(counts[:64]), 1)[0]
    assert -1.35 < slope < -0.8
    # a batch's share of distinct keys, as the configuration file says
    batch = ids[:8192]
    assert 0.24 < len(np.unique(batch)) / batch.size < 0.26
    assert 0.15 < y.mean() < 0.4


# -- rates --------------------------------------------------------------------

def test_rate_from_a_polled_staircase():
    rng = np.random.default_rng(0)
    true_rate, feed = 1000.0, 1.3  # a feed every 1.3 s
    polls = []
    for k in range(int(25 / 0.2)):
        t = 0.2 * k + rng.uniform(0, 0.01)
        polls.append((t, float(int(t / feed)) * feed * true_rate))
    pts = rates.change_points(polls)
    assert len(pts) == int(24.8 / feed)
    fit = rates.slope(pts)
    assert abs(fit["rate"] / true_rate - 1) < 0.005
    assert fit["residual_s"] < 0.2
    assert feed - 0.25 < fit["max_gap_s"] < feed + 0.25
    assert rates.slope(pts[:1]) is None
    # regular feeds: the steady fit IS the one-line fit, to the last bit
    same = rates.steady(pts, 0.2)
    assert (same["rate"], same["residual_s"]) == (fit["rate"], fit["residual_s"])
    assert same["stalls"] == 0 and same["stall_s"] == 0


@pytest.mark.parametrize("late_feed", [False, True])
def test_rate_over_the_regular_stretches(late_feed):
    """One stall of 4.4 s among feeds 2.33 s apart (gpt2-124m.pair's): the
    one-line fit reads a fifth low, the steady fit within a percent or two,
    and the stall's seconds are reported. ``late_feed``: the feed after the
    stall is seen a second late as well (a long gap, then a short one)."""
    true_rate, feed, stall = 13.7, 2.33, 4.4
    errs = []
    for seed in range(40):
        rng = np.random.default_rng(seed)
        t, pts = rng.uniform(0, 1), []
        for i in range(12):
            t += feed + (stall if i == 4 else 0.0)
            late = 1.0 if late_feed and i == 4 else 0.0
            seen = 0.2 * (int((t + late) / 0.2) + 1)  # the next poll
            pts.append((seen, true_rate * feed * i))
        pts = [p for p in pts if p[0] <= pts[0][0] + 20.0]
        fit = rates.steady(pts, 0.2)
        assert fit["whole_rate"] < 0.85 * true_rate
        assert fit["stalls"] == (2 if late_feed else 1)
        assert abs(fit["stall_s"] - stall) < 0.45
        errs.append(fit["rate"] / true_rate - 1)
    assert max(abs(e) for e in errs) < 0.03
    assert abs(float(np.median(errs))) < 0.004
    # feeds nearly as fast as polls: gaps of one or two polls are no breaks
    fast = [(0.2 * k, float(i)) for i, k in enumerate([0, 1, 3, 4, 6, 7, 8, 10])]
    assert rates.steady(fast, 0.2)["stalls"] == 0
    assert rates.steady(fast)["stalls"] > 0


def test_feeds_carry_their_widths_and_a_coarse_end_point_weighs_little():
    """A feed's stamp is the middle of the two polls it fell between; with
    the widths given, one feed caught late on the coarse grid (a job's
    first) no longer tilts the line."""
    polls = [(0.0, 0.0), (0.2, 0.0), (0.4, 5.0), (0.425, 5.0), (0.45, 9.0)]
    got = rates.feeds(polls)
    assert [(round(t, 4), v, round(w, 4)) for t, v, w in got] == [
        (0.3, 5.0, 0.2), (0.4375, 9.0, 0.025)]
    feed, rate = 0.809, 39.5
    # every feed stamped to 25 ms, the first seen 0.2 s late
    pts = [(feed * i + (0.19 if i == 0 else 0.0), rate * feed * i)
           for i in range(25)]
    widths = [0.2] + [0.025] * 24
    plain = rates.steady(pts, 0.2)
    weighed = rates.steady(pts, 0.2, widths)
    assert abs(plain["rate"] / rate - 1) > 1e-3
    assert abs(weighed["rate"] / rate - 1) < 5e-5
    assert weighed["n"] == 25 and weighed["stalls"] == 0
    # equal widths are no weights at all
    even = rates.steady(pts, 0.2, [0.05] * 25)
    assert abs(even["rate"] / plain["rate"] - 1) < 1e-12


@pytest.mark.parametrize("feed", [0.809, 1.0])
def test_poller_asks_often_where_a_feed_is_due(feed):
    """gpt2-124m.pair's 0.809 s and OLMoE's 1.0 s between feeds, the two on
    which the 0.2 s grid read two levels 1% apart (PR 41): after a job's
    first two feeds every one is caught between polls 25 ms apart, with a
    few polls a feed more, and the rate over 5 s is within 0.3% where a
    span of whole polls is off by up to 4%."""
    import time

    from perf import run

    class Fake:
        def __init__(self):
            self.t0, self.asked = time.monotonic() + 0.13, 0

        def client(self):
            return None

        def status(self, _client):
            self.asked += 1
            k = max(0, int((time.monotonic() - self.t0) / feed))
            return {"tenants": {"j": {"examples_total": 32.0 * k}},
                    "phase_budget": {}}

    server = Fake()
    poller = run.Poller(server)
    poller.watched = ["j"]
    start = time.monotonic()
    while time.monotonic() < start + 0.13 + 7.6 * feed:
        poller.poll()
    feeds = poller.feeds["j"]
    assert len(feeds) == 7
    assert [w > 0.1 for _, _, w in feeds[:2]] == [True, True]
    assert all(w < 2 * run.FINE_PERIOD_S for _, _, w in feeds[2:])
    grid = (time.monotonic() - start) / run.POLL_PERIOD_S
    assert grid - 2 < server.asked < grid + 10 * len(feeds)
    fit = rates.steady([f[:2] for f in feeds], run.POLL_PERIOD_S,
                       [f[2] for f in feeds])
    assert abs(fit["rate"] * feed / 32.0 - 1) < 0.003


# -- trace reduction ------------------------------------------------------------

def test_union_and_classification():
    total, merged = trace_reduce.union_seconds([(0, 4), (2, 6), (10, 11), (11, 11)])
    assert total == 7 and merged == [(0, 6), (10, 11)]
    hlo = {
        "collective": '%psum.7 = f32[64,128]{1,0:T(8,128)} all-reduce(f32[64,128]'
                      '{1,0:T(8,128)} %x), replica_groups={{0,1,2,3}}',
        "kernel": '%harmony_gather_rows.1 = f32[1024,128]{1,0:T(8,128)} custom-call('
                  's32[1024]{0:T(1024)S(1)} %i, f32[4096,128]{1,0:T(8,128)} %t), '
                  'custom_call_target="tpu_custom_call"',
        # a fusion that CONSUMES a collective is not one
        "xla": '%add_fusion = (f32[8]{0:T(256)}, u32[]{:S(2)}) fusion(f32[8]{0:T(256)} '
               '%all-reduce.1), kind=kLoop, calls=%fused_computation',
    }
    for want, text in hlo.items():
        assert trace_reduce.classify(text) == want
    assert trace_reduce.parse_op(hlo["kernel"]) == ("harmony_gather_rows.1",
                                                     "custom-call")
    assert trace_reduce.parse_op(hlo["xla"]) == ("add_fusion", "fusion")


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_recorded_trace(chips):
    path = os.path.join(HERE, f"fixture_{chips}chip.xplane.pb")
    if not os.path.exists(path):
        pytest.skip(f"{os.path.basename(path)} not recorded")
    red = trace_reduce.reduce(trace_reduce.load(path))
    # what record_fixture.py ran: 4 rounds, each followed by a 20 ms sleep
    assert red["devices"] == chips
    assert 0.06 < red["window_s"] < 1.0
    assert 0 < red["busy_s"] < 0.5 * red["window_s"]
    assert red["kernel_s"] > 0
    assert (red["collective_s"] > 0) == (chips > 1)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    # the first device's gaps, the longest by where they start
    gaps = sum(s for _, s in red["idle_gaps"])
    if chips == 1:
        assert abs(gaps + red["busy_s"] - red["window_s"]) < 1e-6 * red["window_s"] + 1e-9
    assert 3 * 0.018 < gaps < red["window_s"]  # three sleeps lie inside
    assert re.match(r"^dev0\+\d+\.\d{4}s$", red["idle_gaps"][0][0])
    assert red["idle_gaps"][0][1] == max(s for _, s in red["idle_gaps"][:9])


# -- the references tell broken arithmetic apart ----------------------------------

def test_keyed_reference_sees_the_interaction_term():
    ref = _load("reference", "criteo-fm")
    app = {"vocab_size": 4095, "num_slots": 26, "emb_dim": 127, "step_size": 0.05}
    data = criteo.make(256, 4095, FIELDS, seed=0)
    full = ref.replay(app, data, 64, 4, seed=0)
    broken = ref.replay(app, data, 64, 4, seed=0, ablate="no_interaction")
    rtol = json.load(open(os.path.join(PERF, "configs", "criteo-fm.json")))["job"]["loss_rtol"]
    assert all(abs(a - b) / a > 10 * rtol for a, b in zip(full, broken))


def test_lm_reference_sees_the_adam_moments():
    ref = _load("reference", "gpt2-124m")
    c = json.load(open(os.path.join(PERF, "configs", "gpt2-124m.json")))
    app = {**c["job"]["app_params"], **c["rehearse"]["app_params"]}
    data = (random_tokens.make(8, 65, 512, seed=0),)
    full = ref.replay(app, data, 4, 4, seed=0)
    rtol = c["job"]["loss_rtol"]
    for what in ("no_m", "no_v"):
        broken = ref.replay(app, data, 4, 4, seed=0, ablate=what)
        assert abs(full[3] - broken[3]) / full[3] > rtol, what


# -- a rehearsal of every cell reaches its last line ------------------------------

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearse_reaches_the_last_line(cell, trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "2", "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert last["failed"] == 0
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    assert last["device"]["count"] == chips


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", CELLS[0],
         "--seconds", "1"], capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
