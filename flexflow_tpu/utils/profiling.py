"""Profiling / tracing.

Reference analogs (SURVEY.md §5):
  - ``--profiling`` per-op kernel timing  → per-step wall timing with true
    device synchronization (timed work ends in ``block_until_ready`` or
    a device-to-host fetch — dispatch alone returns before the device
    finishes);
  - ``-lg:prof`` Legion/Realm profiles    → ``jax.profiler`` traces
    (XPlane, viewable in TensorBoard/Perfetto) via
    ``Profiler(trace_dir=...)``; the program's ``obs.events.span`` s land
    in the same trace as ``ff:<name>``;
  - Legion iteration tracing              → jit caching (automatic); the
    profiler records compile (first-call) time separately from steady-state.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import numpy as np


def sync(value: Any) -> None:
    """Force completion of device work feeding `value` (a D2H fetch of
    one leaf)."""
    import jax
    leaves = jax.tree.leaves(value)
    if leaves:
        np.asarray(leaves[-1])


class Profiler:
    """Per-step timing accumulator used by fit() under --profiling.

    ``name`` labels this profiler's gauges in the metrics registry so
    two profilers in one process (train + eval loops) don't overwrite
    each other's ``ff_profiler_*`` rows."""

    def __init__(self, trace_dir: Optional[str] = None,
                 name: str = "default"):
        self.trace_dir = trace_dir
        self.name = name
        self.step_times: List[float] = []
        self.compile_time: float = 0.0
        self._trace_active = False

    def start_trace(self):
        if self.trace_dir and not self._trace_active:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self._trace_active = True

    def stop_trace(self):
        if self._trace_active:
            import jax
            jax.profiler.stop_trace()
            self._trace_active = False

    @contextlib.contextmanager
    def step(self, sync_value=None):
        t0 = time.perf_counter()
        yield
        if sync_value is not None:
            sync(sync_value)
        dt = time.perf_counter() - t0
        if not self.step_times:
            self.compile_time = dt   # first step includes jit compile
        self.step_times.append(dt)

    def summary(self) -> Dict[str, float]:
        # steady-state excludes the first (jit-compiling) step; with a
        # SINGLE recorded step there is no steady-state sample at all —
        # reporting the compile step as mean/p50 overstated step time by
        # the whole compile, so the steady stats are 0.0 there and
        # compile_s carries the one measurement
        steady = self.step_times[1:]
        out = {
            "steps": len(self.step_times),
            "compile_s": self.compile_time,
            "mean_step_s": float(np.mean(steady)) if steady else 0.0,
            "p50_step_s": float(np.median(steady)) if steady else 0.0,
            "p90_step_s": float(np.percentile(steady, 90))
            if steady else 0.0,
            "max_step_s": float(np.max(steady)) if steady else 0.0,
            "total_s": float(np.sum(self.step_times)),
        }
        # route the summary into the metrics registry so a serving /
        # training process exposes its step timings at GET /metrics
        from ..obs.metrics_registry import REGISTRY
        for k in ("compile_s", "mean_step_s", "p50_step_s",
                  "p90_step_s", "max_step_s"):
            REGISTRY.gauge(f"ff_profiler_{k}",
                           f"Profiler.summary() {k}").set(
                out[k], profiler=self.name)
        return out
