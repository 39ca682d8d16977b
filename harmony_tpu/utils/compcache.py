"""Where XLA's persistent compilation cache lives — and when it is safe.

One rule for every entry point that compiles (``harmony-tpu run`` /
``start-jobserver`` / ``start-pod``, ``chip_smoke.py``, the benchmark's
server):

  * ``JAX_COMPILATION_CACHE_DIR`` set — the operator placed the cache; JAX
    reads that variable itself and this module names no directory;
  * unset — one fixed, git-ignored directory inside the checkout. The
    directory is part of the cache key, so it must not move between runs:
    never ``~``, a temporary name, a pid or a time.

Two kinds of process stay off the cache altogether:

  * a process whose default backend is the CPU keeps off the in-checkout
    cache: CPU executables are specialised to the build host's CPU
    features, the checkout gets copied between machines, and loading
    another host's entries risks an illegal instruction;
  * a process with MORE THAN ONE device has the cache switched off, even
    an operator-placed one. On a four-chip v5e host (jax/jaxlib 0.9.0,
    libtpu 0.0.34) an executable LOADED from the persistent cache for a
    sub-mesh that does not start at device 0 halts the TensorCore the
    first time it runs ("Core halted unexpectedly" / "The program
    continuator has halted unexpectedly"), while the same program
    compiled fresh runs. Plain JAX reproduces it without this package — a
    2-device jit over devices (2, 3), run in a second process so that it
    is a cache hit — and carving sub-meshes out of the host for tenants is
    what this system does, so no cache entry may ever reach such a
    program (PERF.md, PR 21 has the runs). Executables for devices (0, 1)
    and for all four loaded fine; single devices other than 0 were not
    tried.

(The in-memory program cache, runtime/progcache.py, is a different thing
and is always on.)
"""
from __future__ import annotations

import logging
import os
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the in-checkout cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent cache belongs in: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
    path."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> Optional[str]:
    """Decide the persistent cache for this process; returns the directory
    in use, or None when the process stays off it (see the module
    docstring). Opens the backend; call before the first compile."""
    import jax

    if len(jax.devices()) > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        logging.getLogger(__name__).warning(
            "persistent compile cache OFF: %d devices in this process, and "
            "executables loaded from the cache for a sub-mesh halt the chip "
            "(harmony_tpu/utils/compcache.py)", len(jax.devices()))
        return None
    placed = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not placed:
        if jax.default_backend() == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    # cache every program, however small or quick to compile: a job's
    # start-up is dozens of small programs besides the step
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compile_cache_dir()
