"""Which devices a program runs on, and what those chips can do.

The fast paths (Pallas kernels, MXU duplicate-fold push, matmul
histograms) exist for TPUs. Whether to take one is decided while a program
is TRACED, from the mesh the program is being traced for — the process's
default backend is the wrong question (a CPU-mesh table in a TPU-default
process is normal in tests and benchmarks). Whoever knows the mesh names it
with :func:`on_mesh` / :func:`traced_on`; code deep inside a trainer or a
table op reads it back with :func:`trace_mesh` / :func:`trace_is_tpu`.
On a TPU a kernel that cannot be built is an error: nothing here, and no
caller, turns a failed build into a reference route.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Iterable, NamedTuple, Optional

import jax

_WARNED_ENV: set = set()


def env_choice(var: str, allowed: tuple) -> Optional[str]:
    """Value of env ``var`` when it is one of ``allowed``, else None —
    warning ONCE about unrecognized non-empty values. These vars are
    operator rollback knobs; a typo silently falling through to the
    default would leave the operator believing a rollback is in effect."""
    val = os.environ.get(var)
    if not val:
        return None
    if val in allowed:
        return val
    if var not in _WARNED_ENV:
        _WARNED_ENV.add(var)
        import logging

        logging.getLogger(__name__).warning(
            "%s=%r is not one of %s — IGNORED, default route stays active",
            var, val, list(allowed),
        )
    return None


def device_is_tpu(d: jax.Device) -> bool:
    return d.platform == "tpu"


def devices_are_tpu(devices: Iterable[jax.Device]) -> bool:
    """True when EVERY device is a TPU (a mixed set takes no TPU route)."""
    devices = list(devices)
    return bool(devices) and all(device_is_tpu(d) for d in devices)


def mesh_is_tpu(mesh) -> bool:
    return devices_are_tpu(mesh.devices.flat)


# -- the mesh a program is being traced for ---------------------------------

_TRACE = threading.local()


@contextlib.contextmanager
def on_mesh(mesh):
    """Name the mesh the program traced inside this scope will run on.
    Thread-local and re-entrant; the innermost scope wins."""
    prev = getattr(_TRACE, "mesh", None)
    _TRACE.mesh = mesh
    try:
        yield mesh
    finally:
        _TRACE.mesh = prev


def traced_on(mesh, fn):
    """``fn`` with every trace of it made inside ``on_mesh(mesh)`` — wrap
    the function handed to ``jax.jit`` so the scope is active whenever jit
    (re)traces it, not only at the first call site."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with on_mesh(mesh):
            return fn(*args, **kwargs)

    return scoped


def trace_mesh():
    """The innermost :func:`on_mesh` mesh, or None outside any scope."""
    return getattr(_TRACE, "mesh", None)


def trace_is_tpu() -> bool:
    """Does the program being traced run on TPUs? Inside an
    :func:`on_mesh` scope: that mesh's devices. Outside one the program
    is an unplaced jit, which runs on the process's default devices."""
    mesh = trace_mesh()
    if mesh is not None:
        return mesh_is_tpu(mesh)
    return devices_are_tpu(jax.devices()[:1])


# -- chip peaks ---------------------------------------------------------------


class ChipPeaks(NamedTuple):
    bf16_flops: float      # dense bf16 matmul, FLOP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    hbm_bytes: float       # HBM capacity per chip


#: Published per-chip peaks, keyed by the ``device_kind`` string the chip
#: reports. Source: Google Cloud TPU documentation, "TPU v5e" system
#: architecture page (197 TFLOP/s bf16, 819 GB/s, 16 GB HBM per chip).
#: Add a row — with its source — when the system runs on another chip;
#: a TPU that is not in this table is an error, not a default.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 16e9),
}


def chip_peaks(device: Optional[jax.Device] = None) -> Optional[ChipPeaks]:
    """Peaks of ``device`` (default: the first default device). None off
    TPU; an unknown TPU ``device_kind`` raises."""
    d = device if device is not None else jax.devices()[0]
    if not device_is_tpu(d):
        return None
    kind = str(d.device_kind)
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peak figures for TPU device_kind {kind!r}; add it to "
            "harmony_tpu.utils.platform.CHIP_PEAKS with its source"
        ) from None


def peak_bf16_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s for one chip (MFU denominators), None off TPU."""
    peaks = chip_peaks(device)
    return None if peaks is None else peaks.bf16_flops
