"""The pod-launch harness the host-cost scripts (``blockmove_bench.py``,
``podunits.py``) and the multi-process tests share: children pinned to a
virtual-device CPU backend, whatever accelerator the parent holds."""
from __future__ import annotations


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def sanitized_cpu_env(devices_per_proc: int) -> dict:
    """Child env for spawned pod/distributed workers: an n-virtual-device
    CPU backend, whatever accelerator the parent holds."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}"
    )
    return env


def wait_for_ready(proc, deadline_s: float, marker: str = "READY") -> bool:
    """Read ``proc.stdout`` lines until ``marker`` (skipping benign startup
    prints), EOF, or the deadline. Each readline runs on a helper thread so
    a silently-wedged process hits the deadline instead of blocking
    forever."""
    import threading
    import time

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        box = {}
        t = threading.Thread(
            target=lambda: box.update(line=proc.stdout.readline()),
            daemon=True,
        )
        t.start()
        t.join(max(0.1, deadline - time.monotonic()))
        line = box.get("line", "")
        if line.strip() == marker:
            return True
        if not line:  # EOF: process exited without the marker
            return False
    return False
