"""chip_smoke.py's off-chip contract, and the pieces it relies on that can
be pinned without a chip: with no TPU it must fail and print no result; the
native library must follow the SOURCE's content, not file times."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(cwd, script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the child must not reach for a device
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, text=True,
        capture_output=True, timeout=300,
    )


def _result_lines(stdout):
    """Lines of stdout that parse as a result object ({"ok": ...})."""
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj and "device" in obj:
            out.append(obj)
    return out


def test_chip_smoke_fails_without_a_tpu():
    """JAX_PLATFORMS=cpu: non-zero exit and NO result line — a CPU run is
    never reported under the device's name."""
    proc = _run_smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """Alone in a directory — nothing else of the repo beside it — it
    fails too (it is a driver of the system, not a stand-alone demo)."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []


def test_chip_smoke_tenants_are_the_documented_widths():
    """The smoke's tenants keep the full WIDTH its docstring states (the
    trio's per-sample work is large matmuls; the LM's is ``LM_WIDTHS``); only
    datasets and step counts are cut."""
    sys.path.insert(0, REPO)
    import chip_smoke

    by_id = {"mlr": {"num_classes": 256, "num_features": 8192,
                     "features_per_partition": 512},
             "nmf": {"num_cols": 4096, "rank": 256},
             "lda": {"vocab_size": 8192, "num_topics": 64, "max_doc_len": 128}}
    mlr = chip_smoke.mlr_job().params.app_params
    nmf = chip_smoke.nmf_job().params.app_params
    lda = chip_smoke.lda_job().params.app_params
    for key in ("num_classes", "num_features", "features_per_partition"):
        assert mlr[key] == by_id["mlr"][key]
    for key in ("num_cols", "rank"):
        assert nmf[key] == by_id["nmf"][key]
    for key in ("vocab_size", "num_topics", "max_doc_len"):
        assert lda[key] == by_id["lda"][key]
    lm = chip_smoke.lm_job().params.app_params
    assert (lm["vocab_size"], lm["d_model"], lm["n_heads"], lm["n_layers"],
            lm["d_ff"], lm["max_seq"]) == (8192, 512, 8, 8, 2048, 1024)
    assert lm["attn"] == "auto"
    fm = chip_smoke.fm_job().params.app_params
    assert 1 + fm["emb_dim"] == 128  # the width the Pallas gather takes
    # every tenant crosses the TCP wire as JSON
    for job in (chip_smoke.mlr_job(), chip_smoke.lm_job(),
                chip_smoke.fm_job()):
        json.dumps(job.to_dict())


class TestNativeLibraryFollowsSourceContent:
    """native/__init__.py keys the built library on the content of
    native/harmony_native.cc: a copy of the tree preserves neither mtime
    ordering nor provenance, so neither is consulted."""

    def test_path_is_a_function_of_the_source_bytes(self, tmp_path,
                                                    monkeypatch):
        from harmony_tpu import native

        src = tmp_path / "harmony_native.cc"
        src.write_text("int a;\n")
        monkeypatch.setattr(native, "_SRC", str(src))
        monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
        first = native._lib_path()
        os.utime(src, (1, 1))  # an OLD mtime changes nothing
        assert native._lib_path() == first
        src.write_text("int b;\n")
        os.utime(src, (1, 1))  # same mtime, new content: a new library
        assert native._lib_path() != first

    def test_stale_library_is_not_loaded_and_is_replaced(self, tmp_path,
                                                         monkeypatch):
        """A library left over from OTHER source content — however new
        its mtime — is never loaded: the loader builds the one the
        current content names, and drops the leftover."""
        import shutil

        from harmony_tpu import native

        if shutil.which("g++") is None:
            pytest.skip("no g++ here")
        shutil.copy(os.path.join(REPO, "native", "harmony_native.cc"),
                    tmp_path)
        leftover = tmp_path / "libharmony_native-0000000000000000.so"
        leftover.write_bytes(b"not a library")  # newest file in the dir
        monkeypatch.setattr(native, "_SRC",
                            str(tmp_path / "harmony_native.cc"))
        monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.delenv("HARMONY_TPU_NO_NATIVE", raising=False)
        assert native.available()
        assert native.crc32(b"abc") == 0x352441C2
        built = [n for n in os.listdir(tmp_path) if n.endswith(".so")]
        assert built == [os.path.basename(native._lib_path())]
