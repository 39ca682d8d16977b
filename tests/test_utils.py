"""DAG + StateMachine unit tests (ref test analogues: DAGImplTest, state
machine usage in WorkerStateManagerTest)."""
import pytest

from harmony_tpu.utils import DAG, CyclicDependencyError, IllegalTransitionError, StateMachine


class TestDAG:
    def test_ready_and_release(self):
        d = DAG()
        for v in "abcd":
            d.add_vertex(v)
        d.add_edge("a", "b")
        d.add_edge("a", "c")
        d.add_edge("b", "d")
        d.add_edge("c", "d")
        assert d.roots() == ["a"]
        released = d.remove("a")
        assert sorted(released) == ["b", "c"]
        assert sorted(d.roots()) == ["b", "c"]
        assert d.remove("b") == []  # d still blocked by c
        assert d.remove("c") == ["d"]

    def test_cycle_rejected(self):
        d = DAG()
        d.add_vertex(1)
        d.add_vertex(2)
        d.add_edge(1, 2)
        with pytest.raises(CyclicDependencyError):
            d.add_edge(2, 1)

    def test_topological_order(self):
        d = DAG()
        for v in range(5):
            d.add_vertex(v)
        d.add_edge(0, 2)
        d.add_edge(1, 2)
        d.add_edge(2, 3)
        d.add_edge(2, 4)
        order = d.topological_order()
        assert order.index(2) > order.index(0)
        assert order.index(2) > order.index(1)
        assert order.index(3) > order.index(2)
        assert len(order) == 5


class TestStateMachine:
    def make(self):
        return StateMachine(
            states=["INIT", "RUN", "CLEANUP"],
            transitions=[("INIT", "RUN"), ("RUN", "CLEANUP")],
            initial="INIT",
        )

    def test_transitions(self):
        sm = self.make()
        assert sm.state == "INIT"
        sm.transition("RUN")
        assert sm.is_state("RUN")
        with pytest.raises(IllegalTransitionError):
            sm.transition("INIT")

    def test_compare_and_transition(self):
        sm = self.make()
        assert not sm.compare_and_transition("RUN", "CLEANUP")
        assert sm.compare_and_transition("INIT", "RUN")

    def test_wait_for(self):
        import threading

        sm = self.make()
        t = threading.Timer(0.05, lambda: sm.transition("RUN"))
        t.start()
        assert sm.wait_for("RUN", timeout=2.0)


class TestMultihost:
    """Single-host degradation paths of the multi-host wiring (a real
    multi-process run needs a pod; these pin the no-op semantics)."""

    def test_initialize_noop_single_host(self, monkeypatch):
        from harmony_tpu.parallel import multihost

        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        assert multihost.initialize_distributed() is False
        assert multihost.is_multihost() is False
        assert multihost.process_index() == 0
        assert multihost.process_count() == 1

    def test_global_mesh_spans_devices(self, devices):
        from harmony_tpu.parallel import multihost

        mesh = multihost.global_mesh(data=2, model=4)
        assert mesh.shape == {"data": 2, "model": 4}

    def test_sync_barrier_single_host(self):
        from harmony_tpu.parallel import multihost

        multihost.sync_global_devices("test")  # must not hang or raise

    def test_half_configured_launch_raises(self, monkeypatch):
        from harmony_tpu.parallel import multihost

        monkeypatch.setattr(multihost, "_initialized", False)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
        monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
        with pytest.raises(ValueError, match="incomplete multi-host config"):
            multihost.initialize_distributed()
        monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
        monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
        with pytest.raises(ValueError, match="JAX_PROCESS_ID"):
            multihost.initialize_distributed()


class _Dev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


class _FakeMesh:
    """A (data, model) mesh of fake devices — route selection reads
    ``mesh.devices`` and the axis sizes, nothing else."""

    axis_names = ("data", "model")

    def __init__(self, devs):
        import numpy as np

        self.devices = np.asarray(devs, dtype=object)
        self.shape = dict(zip(self.axis_names, self.devices.shape))


_TPU = _Dev("tpu", "TPU v5 lite")
_CPU = _Dev("cpu", "cpu")


class TestRouteSelection:
    """Kernel routes are chosen from the devices of the mesh a program is
    traced for (utils.platform.on_mesh), never from the process's default
    backend — which in this suite is always the CPU."""

    def test_device_and_mesh_predicates(self):
        from harmony_tpu.utils.platform import (
            device_is_tpu,
            devices_are_tpu,
            mesh_is_tpu,
        )

        assert device_is_tpu(_TPU) and not device_is_tpu(_CPU)
        # the platform decides, not a name that merely mentions a TPU
        assert not device_is_tpu(_Dev("cpu", "TPU v5 lite"))
        assert devices_are_tpu([_TPU, _TPU])
        assert not devices_are_tpu([_TPU, _CPU])  # mixed: no TPU route
        assert not devices_are_tpu([])
        assert mesh_is_tpu(_FakeMesh([[_TPU, _TPU]]))
        assert not mesh_is_tpu(_FakeMesh([[_TPU, _CPU]]))

    def test_scope_names_the_traced_mesh(self, mesh8):
        from harmony_tpu.utils import platform as plat

        assert plat.trace_mesh() is None
        assert plat.trace_is_tpu() is False  # unplaced: default device, cpu
        tpu_mesh = _FakeMesh([[_TPU] * 4])
        with plat.on_mesh(tpu_mesh):
            assert plat.trace_mesh() is tpu_mesh
            assert plat.trace_is_tpu() is True  # in a CPU-default process
            with plat.on_mesh(mesh8):  # innermost wins; restored on exit
                assert plat.trace_is_tpu() is False
            assert plat.trace_mesh() is tpu_mesh
        assert plat.trace_mesh() is None

    def test_traced_on_scopes_every_trace(self):
        import jax
        import jax.numpy as jnp

        from harmony_tpu.utils import platform as plat

        seen = []

        def f(x):
            seen.append(plat.trace_is_tpu())
            return x + 1

        jf = jax.jit(plat.traced_on(_FakeMesh([[_TPU]]), f))
        jf(jnp.ones((2,)))
        jf(jnp.ones((3,)))  # a retrace, made outside any caller scope
        assert seen == [True, True]
        assert plat.trace_mesh() is None

    def test_scope_is_thread_local(self):
        import threading

        from harmony_tpu.utils import platform as plat

        seen = {}
        with plat.on_mesh(_FakeMesh([[_TPU]])):
            t = threading.Thread(
                target=lambda: seen.update(other=plat.trace_mesh()))
            t.start()
            t.join(timeout=10)
        assert seen == {"other": None}

    def test_auto_attention_follows_the_traced_mesh(self, mesh8):
        from harmony_tpu.models.common import resolve_attn
        from harmony_tpu.utils.platform import on_mesh

        assert resolve_attn("auto", 128) == "blockwise"
        with on_mesh(_FakeMesh([[_TPU]])):
            assert resolve_attn("auto", 128) == "flash"
            assert resolve_attn("auto", 300) == "blockwise"  # cannot tile
            assert resolve_attn("blockwise", 128) == "blockwise"
        with on_mesh(mesh8):
            assert resolve_attn("auto", 128) == "blockwise"

    def test_table_ops_take_kernels_only_on_a_tpu_mesh(self, mesh8):
        """TableSpec.pull traced for a TPU mesh lowers the Pallas gather
        (cross-lowered here for the TPU); traced for a CPU mesh, or for
        no mesh at all, it is the XLA gather."""
        import jax
        import jax.numpy as jnp

        from harmony_tpu.config.params import TableConfig
        from harmony_tpu.table import TableSpec
        from harmony_tpu.utils.platform import traced_on

        spec = TableSpec(TableConfig(table_id="rs", capacity=64,
                                     value_shape=(128,), num_blocks=8))
        arr = jnp.zeros(spec.storage_shape, jnp.float32)
        keys = jnp.zeros((16,), jnp.int32)

        def text(fn):
            return jax.jit(fn).trace(arr, keys).lower(
                lowering_platforms=("tpu",)).as_text()

        one_chip = _FakeMesh([[_TPU]])
        assert "tpu_custom_call" in text(traced_on(one_chip, spec.pull))
        assert "tpu_custom_call" not in text(traced_on(mesh8, spec.pull))
        assert "tpu_custom_call" not in text(spec.pull)


class TestChipPeaks:
    def test_known_kind(self):
        from harmony_tpu.utils.platform import chip_peaks, peak_bf16_flops

        assert peak_bf16_flops(_TPU) == 197e12
        assert chip_peaks(_TPU).hbm_bytes_per_s == 819e9

    def test_off_tpu_is_none(self):
        from harmony_tpu.utils.platform import chip_peaks, peak_bf16_flops

        assert chip_peaks(_CPU) is None
        assert peak_bf16_flops() is None  # conftest pins the cpu backend

    def test_unknown_tpu_kind_raises(self):
        """A TPU that is not in the table is an error, not a default —
        and not a quiet None from the ledger's MFU denominator either."""
        from harmony_tpu.metrics import accounting
        from harmony_tpu.utils import platform as plat

        with pytest.raises(KeyError, match="TPU v9"):
            plat.chip_peaks(_Dev("tpu", "TPU v9 hypothetical"))
        import jax

        orig = jax.devices
        try:
            jax.devices = lambda *a: [_Dev("tpu", "TPU v9 hypothetical")]
            with pytest.raises(KeyError):
                accounting._peak_flops()
        finally:
            jax.devices = orig
        assert accounting._peak_flops() is None  # off-TPU stays None


class TestCompileCache:
    """utils/compcache.py: the persistent compile cache can be placed from
    outside, otherwise sits at one fixed in-checkout path, and stays off
    where it is unsafe."""

    @pytest.fixture()
    def one_device(self, monkeypatch):
        """The suite runs on 8 virtual devices; these cases are about a
        one-device process."""
        import jax

        monkeypatch.setattr(jax, "devices", lambda *a: [_CPU])

    @pytest.fixture()
    def updates(self, monkeypatch):
        import jax

        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_dir_is_honoured_and_not_overridden(
            self, monkeypatch, tmp_path, one_device, updates):
        from harmony_tpu.utils import compcache

        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert compcache.compile_cache_dir() == placed
        assert compcache.enable_compile_cache() == placed
        # the operator placed it: no directory is named in code
        assert "jax_compilation_cache_dir" not in updates
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_unset_is_the_fixed_checkout_path(self, monkeypatch):
        import os

        from harmony_tpu.utils import compcache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compcache.compile_cache_dir() == os.path.join(
            repo, ".jax_cache")
        assert compcache.compile_cache_dir() == compcache.CHECKOUT_CACHE_DIR
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_cpu_process_stays_off_the_checkout_cache(
            self, monkeypatch, one_device, updates):
        """CPU executables are specialised to the build host; the
        checkout is copied between machines — tier-1 and CPU runs never
        write the in-checkout cache."""
        from harmony_tpu.utils import compcache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compcache.enable_compile_cache() is None
        assert updates == {}

    def test_accelerator_process_uses_the_checkout_cache(
            self, monkeypatch, one_device, updates):
        import jax

        from harmony_tpu.utils import compcache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert (compcache.enable_compile_cache()
                == compcache.CHECKOUT_CACHE_DIR)
        assert (updates["jax_compilation_cache_dir"]
                == compcache.CHECKOUT_CACHE_DIR)

    def test_multi_device_process_switches_the_cache_off(
            self, monkeypatch, tmp_path, updates):
        """More than one device: executables loaded from the cache for a
        sub-mesh halt the chip (PERF.md, PR 21) — the cache goes off, an
        operator-placed one included."""
        import jax

        from harmony_tpu.utils import compcache

        assert len(jax.devices()) > 1  # the suite's 8 virtual devices
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compcache.enable_compile_cache() is None
        assert updates == {"jax_enable_compilation_cache": False}


class TestEnvChoice:
    """Operator rollback knobs must warn (once) on unrecognized values
    instead of silently staying on the default."""

    def test_valid_and_missing(self, monkeypatch):
        from harmony_tpu.utils.platform import env_choice

        monkeypatch.delenv("X_KNOB", raising=False)
        assert env_choice("X_KNOB", ("a", "b")) is None
        monkeypatch.setenv("X_KNOB", "b")
        assert env_choice("X_KNOB", ("a", "b")) == "b"

    def test_invalid_warns_once_and_ignores(self, monkeypatch, caplog):
        import logging

        from harmony_tpu.utils import platform as plat

        monkeypatch.setattr(plat, "_WARNED_ENV", set())
        monkeypatch.setenv("Y_KNOB", "Bogus")
        with caplog.at_level(logging.WARNING):
            assert plat.env_choice("Y_KNOB", ("a", "b")) is None
            assert plat.env_choice("Y_KNOB", ("a", "b")) is None
        warns = [r for r in caplog.records if "Y_KNOB" in r.getMessage()]
        assert len(warns) == 1  # once, not per call
