"""Lightweight phase timing.

Parity with the reference's Dolphin ``Tracer`` (dolphin/metric/Tracer.java,
93 LoC: start/record/avg) used by ETModelAccessor for pull/push timers and by
trainers for compute timing. On TPU, device work is async-dispatched, so
``record`` optionally blocks on a jax array to charge the wall-clock to the
right phase.
"""
from __future__ import annotations

import time
from typing import Any, Optional


class Tracer:
    def __init__(self, instrument: Optional[str] = None) -> None:
        #: optional phase name: when set, every record() feeds the
        #: process registry's step-time histogram labeled phase=<name>
        #: (metrics/registry.py) so phase timings are scrapeable, not
        #: only averaged in-process
        self.instrument = instrument
        self._t0: Optional[float] = None
        self.total_sec = 0.0
        self.count = 0
        self.elem_count = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def record(self, num_elems: int = 1, block_on: Any = None) -> float:
        """Stop the stopwatch; returns the elapsed seconds of this span.

        ``block_on``: a jax array (or pytree leaf) to block on so async
        device work is attributed to this phase rather than the next one.
        """
        if self._t0 is None:
            raise RuntimeError("record() without start()")
        if block_on is not None:
            import jax

            jax.block_until_ready(block_on)
        dt = time.perf_counter() - self._t0
        self.total_sec += dt
        self.count += 1
        self.elem_count += num_elems
        self._t0 = None
        if self.instrument:
            try:
                from harmony_tpu.metrics.registry import (
                    STEP_TIME_BUCKETS,
                    get_registry,
                )

                get_registry().histogram(
                    "harmony_phase_seconds",
                    "Tracer-timed phase seconds (pull/push/compute ...)",
                    ("phase",),
                    buckets=STEP_TIME_BUCKETS,
                ).labels(phase=self.instrument).observe(dt)
            except Exception:
                pass  # the stopwatch must never fail on its histogram
        return dt

    def avg_sec(self) -> float:
        return self.total_sec / self.count if self.count else 0.0

    def throughput(self) -> float:
        """Elements per second over all recorded spans."""
        return self.elem_count / self.total_sec if self.total_sec > 0 else 0.0

    def reset(self) -> None:
        self.__init__(self.instrument)
