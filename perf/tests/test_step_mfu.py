"""Checks of ``step_mfu_share`` (PR 41): the model FLOPs a token of every LM
configuration — the count its file names under ``job.flops_fn``, found by
``perf/work_models.py`` ``resolve``: that file's ``lm_train_flops_per_token``
or a sibling's own (PR 48) — against a hand count from the published shapes,
the one function's rules, the harness's quotient (``perf/run.py``
``work_model_shares``) and the reader on a synthetic ``obs`` with no trace,
the entry of ``BENCHMARK.json``, and the door a configuration's own count
comes in by, shown open on a sibling written for the test. CPU only, nothing
here is a measurement. Tier-1 collects these cases through ``perf/tests/
test_step_scopes.py`` (its last lines say why)."""
from __future__ import annotations

import functools
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(HERE)
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf import run, work_models as WORK  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
READER = run.load_by_path("layer_metrics", "step_mfu_share")
PEAKS = json.load(open(os.path.join(PERF, "peaks.json")))["TPU v5 lite"]
PEAK = PEAKS["bf16_flops"]
LM_CELLS = next(m for m in BENCH["end_to_end"]
                if m["name"] == "lm_tokens_per_s")["workloads"]
KEYED_CELLS = next(m for m in BENCH["end_to_end"]
                   if m["name"] == "keyed_samples_per_s")["workloads"]


def job_of(config: str):
    with open(os.path.join(PERF, "configs", config + ".json")) as f:
        return json.load(f)["job"]


def app_of(config: str):
    return job_of(config)["app_params"]


def tri(s, heads, width):
    """Forward + backward FLOPs a token of ``heads`` causal heads whose q.k
    and v widths sum to ``width``: 3 x 2 x width x (s^2 / 2 pairs) / s."""
    return 3 * width * heads * s


# -- the hand counts: matmul parameters a token passes through, from the
# -- published shapes, x 6; + the pairs; + the scans -----------------------

def hand_gpt2():
    d, L, s, V = 768, 12, 1024, 50257
    block = 3 * d * d + d * d + 2 * d * 3072       # qkv, out, the MLP
    return 6 * (L * block + d * V) + L * tri(s, 12, 64 + 64)


def hand_olmoe():
    d, s, V = 2048, 4096, 12576
    mixer = d * 3 * d + d * d                      # 16 heads of 128, equal
    layer = mixer + d * 64 + 8 * 16 / 64 * 3 * d * 1024   # router, top-8, 16 of 64 held
    return 6 * (2 * layer + d * V) + 2 * tri(s, 16, 128 + 128)


def hand_moonlight():
    d, s, V, h = 2048, 8192, 20480, 16
    mla = d * h * 192 + d * (512 + 64) + 512 * h * (128 + 128) + h * 128 * d
    dense = 3 * d * 11264                          # the leading dense layer
    moe = d * 64 + 3 * d * 2 * 1408 + 6 * 8 / 64 * 3 * d * 1408   # router, 2 shared, top-6, 8 of 64
    return 6 * (2 * mla + dense + moe + d * V) + 2 * tri(s, h, 192 + 128)


def hand_kimi_linear():
    d, s, V = 2304, 8192, 20480
    kda = 3 * d * 1024 + 2 * (d * 128 + 128 * 1024) + d * 8 + 1024 * d  # 8 heads of 128
    mla = d * 8 * 192 + d * (512 + 64) + 512 * 8 * (128 + 128) + 8 * 128 * d
    dense = 3 * d * 9216
    moe = d * 256 + 3 * d * 1024 + 8 * 8 / 256 * 3 * d * 1024   # router, 1 shared, top-8, 8 of 256
    chunk = 64 * 64 * 5 * 128 + 6 * 64 * 128 * 128           # a head and chunk of 64
    scans = 4 * 3 * 8 * chunk / 64                            # four KDA blocks
    return (6 * (4 * kda + mla + dense + 4 * moe + d * V)
            + tri(s, 8, 192 + 128) + scans)


def hand_smallthinker():
    d, s, V, w = 2560, 16384, 18992, 4096
    mixer = d * (28 + 4 + 4) * 128 + 28 * 128 * d  # 28 query over 4 K/V heads
    moe = d * 64 + 6 * 8 / 64 * 3 * d * 768        # router, top-6, 8 of 64 held
    band2 = 2 * (w * (w + 1) // 2 + (s - w) * w) - s  # twice the band, diagonal at half
    swa = 3 * (128 + 128) * 28 * band2 / s
    return (6 * (4 * (mixer + moe) + d * V) + tri(s, 28, 256) + 3 * swa)


def hand_nemotron():
    d, s, V = 4096, 8192, 16384
    ssd = d * (2 * 1024 + 2 * 128 + 16) + 1024 * d   # 16 heads of 64, 1 group of 128
    attn = d * (4 + 1 + 1) * 128 + 4 * 128 * d
    moe = (d * 512 + 2 * d * 1024 + 2 * d * 672      # router, latent pair, shared columns
           + 22 * 8 / 512 * 2 * 1024 * 2688)         # top-22, 8 of 512 held, ungated
    chunk = 128 * 128 * (64 + 128 / 16) + 4 * 128 * 128 * 64
    scans = 5 * 3 * 16 * chunk / 128
    return (6 * (5 * ssd + attn + 5 * moe + d * V) + tri(s, 4, 256) + scans)


HAND = {"gpt2-124m": hand_gpt2, "olmoe-1b-7b": hand_olmoe,
        "moonlight-16b-a3b": hand_moonlight,
        "kimi-linear-48b-a3b": hand_kimi_linear,
        "smallthinker-21b-a3b": hand_smallthinker,
        "nemotron-3-super-120b-a12b": hand_nemotron}


@pytest.mark.parametrize("config", sorted(HAND))
def test_flops_a_token_equal_the_hand_count(config):
    got = WORK.count(job_of(config), "flops_fn")
    assert got == pytest.approx(HAND[config](), rel=1e-12)


def test_gpt2_is_the_count_it_always_had_to_the_unit():
    assert WORK.lm_train_flops_per_token(app_of("gpt2-124m")) == 797815296.0


def lm_configs():
    """Every configuration of the benchmark whose rate is ``lm_tokens_per_s``
    (a later PR's too), with its ``job`` block."""
    out = {}
    for conf in BENCH["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            job = json.load(f)["job"]
        if job["rate_metric"] == "lm_tokens_per_s":
            out[conf["name"]] = job
    return out


def test_every_lm_configuration_names_the_count_and_is_counted():
    """Held to a COUNT, not to one function's name: whatever ``flops_fn``
    names resolves, its twin splits it over ``PARTS``, and a hand count from
    the published shapes (``HAND``; a later configuration's joins from its own
    ``perf/tests/test_<config>.py``, conftest.py) equals it."""
    jobs = lm_configs()
    assert set(HAND) >= set(jobs), "an LM configuration without a hand count"
    assert {w["config"] for w in BENCH["workloads"]
            if w["name"] in LM_CELLS} == set(jobs)
    for name, job in jobs.items():
        counted = WORK.count(job, "flops_fn")  # the name resolves
        parts = WORK.split(job)
        assert tuple(parts) == WORK.PARTS and min(parts.values()) >= 0
        assert parts["dense"] > 0 and parts["readout"] > 0, name
        assert counted == float(sum(parts.values())), name
        assert counted == pytest.approx(HAND[name](), rel=1e-12), name


def counted_here(configs):
    """Those of ``configs`` whose count is ``lm_train_flops_per_token``: the
    three checks below ask ``work_models.layer_kinds`` and that function's
    keys. A configuration with its own count (``"<sibling>:<function>"``)
    brings their like in its own ``perf/tests/test_<config>.py``."""
    return [c for c in configs
            if job_of(c)["flops_fn"] == "lm_train_flops_per_token"]


@pytest.mark.parametrize("config", counted_here(sorted(HAND)))
def test_the_layers_are_the_programs(config):
    """The kinds this file derives from the keys against the program's own
    ``TransformerConfig`` (a test may ask it; the metric does not)."""
    import dataclasses

    from harmony_tpu.models.transformer import TransformerConfig

    app = app_of(config)
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    cfg = TransformerConfig(**{k: v for k, v in app.items() if k in names})
    mine = WORK.layer_kinds(app)
    assert [i for i, k in enumerate(mine) if k["ffn"] == "moe"] \
        == list(cfg.moe_layers())
    same = {"mha": "full", "attn": "full", "moe": ""}
    assert [k["mixer"] for k in mine] \
        == [same.get(k, k) for k in cfg.layer_kinds()]


@pytest.mark.parametrize("change", [
    {"attn_kind": "gqa2"}, {"ffn": "geglu"}, {"sparse_layers": [1]},
    {"layer_pattern": "MXM", "n_layers": 3}, {"layer_pattern": "ME"}])
def test_a_model_it_cannot_count_raises(change):
    """A later configuration with a mixer, a feed-forward part or a list of
    layers this file does not know gets no share: never a plausible one."""
    with pytest.raises(ValueError):
        WORK.lm_train_flops_per_token({**app_of("gpt2-124m"), **change})
    for kind in ("retention", "kda2"):
        with pytest.raises(ValueError):
            WORK.mixer_params(app_of("gpt2-124m"), kind)
    with pytest.raises(ValueError):
        WORK.ffn_params(app_of("gpt2-124m"), "glu", 0)
    with pytest.raises(ValueError):  # the Switch path: no cell runs it
        WORK.lm_train_flops_per_token({**app_of("olmoe-1b-7b"), "moe_top_k": 0})


@pytest.mark.parametrize("config", counted_here([
    "kimi-linear-48b-a3b", "smallthinker-21b-a3b", "gpt2-124m"]))
def test_remat_counts_nothing(config):
    app = app_of(config)
    assert WORK.lm_train_flops_per_token({**app, "remat": True}) \
        == WORK.lm_train_flops_per_token({**app, "remat": False})


@pytest.mark.parametrize("config", counted_here([
    "olmoe-1b-7b", "moonlight-16b-a3b", "nemotron-3-super-120b-a12b"]))
def test_doubling_the_held_experts_adds_the_routed_term(config):
    app = app_of(config)
    one, two = WORK.lm_train_flops_split(app), WORK.lm_train_flops_split(
        {**app, "moe_experts_held": 2 * app["moe_experts_held"]})
    assert two["routed"] == 2 * one["routed"] > 0
    assert {k: v for k, v in two.items() if k != "routed"} \
        == {k: v for k, v in one.items() if k != "routed"}


def test_a_window_counts_fewer_pairs_and_the_same_once_it_covers_the_sequence():
    app = app_of("smallthinker-21b-a3b")
    s = app["max_seq"]
    full = WORK.attention_flops(app, "full")
    assert WORK.attention_flops(app, "swa") < full
    for w in (s, s + 1, 4 * s):
        assert WORK.attention_flops({**app, "window": w}, "swa") == full
    # the diagonal at half in both: a one-key window is half a pair a row
    assert WORK.attention_flops({**app, "window": 1}, "swa") \
        == pytest.approx(full / s)


@pytest.mark.parametrize("cell", LM_CELLS)
def test_the_share_needs_no_trace_no_span_and_no_counter(cell, monkeypatch,
                                                         capsys):
    """The harness's quotient from the cell, the table of peaks and a rate
    alone, with an empty registry: tokens/s x the hand count / peak, printed
    as ``model_flops_utilisation``; the reader, on an ``obs`` with NO
    ``trace`` key, is 100 x the printed share to the last digit."""
    from harmony_tpu.metrics import registry

    monkeypatch.setattr(registry, "_registry", None)  # a fresh, empty one
    c = run.Cell(cell, False)
    rates = [3.0, 4.5] if len(c.tenants) == 2 else [7.25]
    assert len(c.tenants) == len(rates)
    tokens = sum(r * float(c.job["units_per_example"]) for r in rates)
    shares = run.work_model_shares(c, PEAKS, tokens)
    config = next(w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert set(shares) == {"model_flops_utilisation"}
    assert shares["model_flops_utilisation"] == pytest.approx(
        tokens * HAND[config]() / (PEAK * 1), rel=1e-12)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line == {"line": "model_flops_utilisation",
                    "flops_per_unit": WORK.count(c.job, "flops_fn"),
                    "share_of_peak": shares["model_flops_utilisation"]}
    obs = {"fits": [{"rate": r} for r in rates],
           "model_flops_share": shares["model_flops_utilisation"]}
    assert READER.read(obs) == 100.0 * line["share_of_peak"] > 0


@pytest.mark.parametrize("cell", KEYED_CELLS)
def test_a_keyed_cell_has_no_share_of_the_flops_peak(cell, capsys):
    c = run.Cell(cell, False)
    shares = run.work_model_shares(c, PEAKS, 1e6)
    assert set(shares) == {"table_bandwidth"} and shares["table_bandwidth"] > 0
    assert '"model_flops_utilisation"' not in capsys.readouterr().out
    assert READER.read({"fits": [{"rate": 1.0}], "trace": None,
                        "model_flops_share": shares.get(
                            "model_flops_utilisation")}) is None


def test_the_reader_reports_nothing_only_where_the_harness_has_no_share():
    """No fitted rate or no row of peaks (a CPU rehearsal): ``perf/run.py``
    computes no share and hands None; never a number then, always one
    otherwise, trace or not."""
    assert READER.read({}) is None
    assert READER.read({"fits": [], "model_flops_share": None}) is None
    assert READER.read({"model_flops_share": 0.25}) == 25.0
    assert READER.read({"model_flops_share": 0.25, "trace": None,
                        "phases": {}}) == 25.0


# -- the door: a configuration's own count, in a sibling of its own (PR 48) --

DOOR_SIBLING = '''"""Written by perf/tests/test_step_mfu.py: the count of a step that runs TWO
streams of every sequence through the layers (s + block attention pairs a
head and token) and reads out ONE."""


def door_flops_split(app):
    if app.get("attn_kind", "mha") != "mha" or "block" not in app:
        raise ValueError("not counted here")
    d, h, s = app["d_model"], app["n_heads"], app["max_seq"]
    layer = 4 * d * d + 2 * d * app["d_ff"]
    pairs = 2 * (d // h + d // h) * h * (s + app["block"])
    return {"dense": 2 * 6.0 * app["n_layers"] * layer, "routed": 0.0,
            "attention_pairs": 3.0 * app["n_layers"] * pairs, "scans": 0.0,
            "readout": 6.0 * d * app["vocab_size"]}


def door_flops_per_token(app):
    return float(sum(door_flops_split(app).values()))


def nothing_per_token(app):
    return None


def nan_per_token(app):
    return float("nan")


def lonely_per_token(app):
    return door_flops_per_token(app)


nothing_split = nan_split = door_flops_split
'''
DOOR_HAND = (2 * 6 * 12 * (4 * 768 * 768 + 2 * 768 * 3072)
             + 3 * 12 * 2 * 128 * 12 * (1024 + 32) + 6 * 768 * 50257)


def door(tmp_path, monkeypatch, flops_fn):
    """A benchmark of one cell in ``tmp_path`` whose configuration is
    gpt2-124m's with a ``block`` key and ``flops_fn`` for its count, beside a
    sibling ``perf/work/door_count.py``; the harness and ``test_perf.py``
    look there until the test ends. Returns ``(the cell's entry,
    test_perf)``. (A plain function: tier-1 gathers this file's ``test_*``
    names only, so a fixture of its own would not be found there.)"""
    checks = run.load_by_path("tests", "test_perf")  # before PERF moves
    config = json.load(open(os.path.join(PERF, "configs", "gpt2-124m.json")))
    config["job"]["app_params"]["block"] = 32
    config["job"]["flops_fn"] = flops_fn
    bench = {**BENCH, "configs": [
        {"name": "door", "source": "https://example.org/door",
         "file": "perf/configs/door.json", "reduced": config["reduced"],
         "why": "a configuration that names its own count"}],
        "workloads": [{"name": "door.solo", "config": "door",
                       "traffic": "solo", "chips": 1, "why": "the door"}],
        "end_to_end": [{**m, "workloads": ["door.solo"]}
                       for m in BENCH["end_to_end"]
                       if m["name"] in ("setup_s", "lm_tokens_per_s")],
        "per_layer": [{**m, "workloads": ["door.solo"]}
                      for m in BENCH["per_layer"]
                      if m["name"] == "step_mfu_share"]}
    perf = tmp_path / "perf"
    for sub in ("configs", "traffic", "work", "reference"):
        (perf / sub).mkdir(parents=True)
    (perf / "configs" / "door.json").write_text(json.dumps(config))
    (perf / "work" / "door_count.py").write_text(DOOR_SIBLING)
    (perf / "reference" / (config["job"]["reference"] + ".py")).write_text("")
    (perf / "traffic" / "solo.json").write_text(
        open(os.path.join(PERF, "traffic", "solo.json")).read())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for module in (run, checks):
        monkeypatch.setattr(module, "ROOT", str(tmp_path))
        monkeypatch.setattr(module, "PERF", str(perf))
    monkeypatch.setattr(checks, "BENCH", bench)
    # siblings are loaded once a process: this test's get a cache of their own
    monkeypatch.setattr(WORK, "sibling", functools.lru_cache(maxsize=None)(
        WORK.sibling.__wrapped__))
    return bench["workloads"][0], checks


def test_a_configuration_may_name_its_own_count(tmp_path, monkeypatch, capsys):
    """``"<sibling>:<function>"`` is what ``model_flops_utilisation`` and
    ``step_mfu_share`` report — that function's number, not this
    repository's one function's — and the cell's file check passes."""
    entry, checks = door(tmp_path, monkeypatch,
                         "door_count:door_flops_per_token")
    checks.test_cell_resolves_to_files(entry)
    c = run.Cell("door.solo", False)
    # gpt2-124m's shapes: the one function counts them 797,815,296.0
    assert WORK.count(c.job, "flops_fn") == float(DOOR_HAND) != 797815296.0
    assert sum(WORK.split(c.job).values()) == DOOR_HAND
    tokens = 7.25 * float(c.job["units_per_example"])
    shares = run.work_model_shares(c, PEAKS, tokens)
    assert shares == {"model_flops_utilisation": pytest.approx(
        tokens * DOOR_HAND / PEAK, rel=1e-12)}
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line == {"line": "model_flops_utilisation",
                    "flops_per_unit": float(DOOR_HAND),
                    "share_of_peak": shares["model_flops_utilisation"]}
    assert READER.read({"fits": [{"rate": 7.25}], "model_flops_share":
                        shares["model_flops_utilisation"]}) \
        == 100.0 * line["share_of_peak"] > 0


@pytest.mark.parametrize("flops_fn", [
    "no_such_function", "no_such_sibling:door_flops_per_token",
    "door_count:no_such_function", "door_count:", ":lm_train_flops_per_token",
    "door_count:nothing_per_token", "door_count:nan_per_token",
    "door_count:lonely_per_token"])
def test_a_count_that_is_not_there_reports_no_share(flops_fn, tmp_path,
                                                    monkeypatch, capsys):
    """A name that does not resolve, a function that returns no number, a
    count without its twin: the cell fails its file check loudly, and (the
    twin aside, which a run does not ask) the run prints why and reports NO
    share, never ``lm_train_flops_per_token``'s."""
    entry, checks = door(tmp_path, monkeypatch, flops_fn)
    with pytest.raises(Exception):
        checks.test_cell_resolves_to_files(entry)
    if flops_fn.endswith("lonely_per_token"):
        return
    c = run.Cell("door.solo", False)
    shares = run.work_model_shares(c, PEAKS, 1e5)
    assert shares == {}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["line"] for x in lines] == ["work_model_failed"]
    assert lines[0]["key"] == "flops_fn" and lines[0]["name"] == flops_fn
    assert READER.read({"fits": [{"rate": 1.0}], "model_flops_share":
                        shares.get("model_flops_utilisation")}) is None


def test_the_entry_in_the_benchmark():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "step_mfu_share")
    assert entry == {"name": "step_mfu_share", "unit": "%", "better": "higher",
                     "source": "host_clock", "layer": "model",
                     "moves": "lm_tokens_per_s", "workloads": LM_CELLS}
    assert (READER.LAYER, READER.UNIT, READER.SOURCE) == (
        "model", "%", "host_clock")
    assert os.path.exists(os.path.join(PERF, "layer_metrics",
                                       "step_mfu_share.py"))
    # appended after PR 38's entries; a later PR appends after it, so no "last"
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("step_mfu_share") > names.index("moe_latent_time_share")
    assert names.count("step_mfu_share") == 1
    # the one whole-step share beside the kernels' rooflines that move the rate
    rooflines = [m for m in BENCH["per_layer"] if "roofline" in m["name"]
                 and m["moves"] == "lm_tokens_per_s"]
    assert len(rooflines) >= 7
    for m in rooflines:
        assert set(m["workloads"]) <= set(entry["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
