"""Device-resident metric accumulation for the async-dispatch train loop.

The reference FlexFlow gets step-level overlap for free from Legion's
asynchronous task launches; the JAX port loses it the moment the host
calls ``np.asarray`` on a per-step metric — that is a device sync, so
the host can never run more than one step ahead and the XLA
async-dispatch pipeline stays one deep. :class:`MetricsBuffer` restores
the overlap:

  - each step's metric dict (tiny device scalars, including the fused
    ``all_finite`` flag the jitted step computes — see
    ``Executor.make_train_step``) is *pushed* without any host fetch;
    the values stay device-resident;
  - a bounded in-flight window (``FFConfig.async_dispatch_steps``,
    default 8) keeps the host from racing unboundedly ahead: pushing
    step N only blocks on the step leaving the window (N - window),
    which on an in-order device stream bounds in-flight work to
    ``window`` steps;
  - :meth:`flush` fetches every pending step in **one**
    ``jax.device_get`` and folds them, in push order, into the attached
    :class:`~flexflow_tpu.runtime.metrics.PerfMetrics` — numerically
    identical (bit-exact) to the old per-step-fetch loop, just batched;
  - the NaN screen becomes a host check of the fetched ``all_finite``
    flags at flush points: the first non-finite step index is kept
    (:attr:`first_bad_step`) and :meth:`raise_if_poisoned` raises
    :class:`NonFiniteMetrics` — callers (the resilience supervisor,
    ``FFModel.save_checkpoint``) flush + screen **before any checkpoint
    save**, preserving the invariant that a poisoned state never
    reaches a checkpoint.

Sync-every-step fallback (``FF_SYNC_EVERY_STEP=1`` or
``async_dispatch_steps <= 0``): every push flushes immediately — the
old loop's semantics (errors and NaNs surface at the step that caused
them), but still converting each metric exactly once.

Observability: host-blocked milliseconds (window blocks + flush
fetches) accumulate into the ``ff_host_blocked_ms_total`` gauge; when
tracing is on each window block is a ``metrics_buffer.window_wait``
span and each flush's fetch a ``metrics_buffer.flush`` span.
"""
from __future__ import annotations

import math
import os
import time
from collections import deque
from typing import Any, Dict, Optional

import jax

from ..obs import events as obs_events
from ..obs.events import _env_on
from ..obs.metrics_registry import REGISTRY
from .metrics import COUNTER_PREFIX

ENV_SYNC = "FF_SYNC_EVERY_STEP"

#: metric key carrying the fused in-jit loss-finiteness flag; stripped
#: from the dicts folded into PerfMetrics
ALL_FINITE_KEY = "all_finite"


def sync_every_step_forced() -> bool:
    """Is the sync-every-step fallback forced by the environment?"""
    return _env_on(os.environ.get(ENV_SYNC))


class NonFiniteMetrics(RuntimeError):
    """A flushed step reported a non-finite loss/metric. ``step`` is the
    global train-step index of the FIRST bad step in the flushed run —
    the rollback attribution the supervisor needs."""

    def __init__(self, step: int, value: float):
        super().__init__(f"non-finite loss {value} at step {step}")
        self.step = step
        self.value = value


class MetricsBuffer:
    """Deferred, device-resident per-step metric accumulator.

    ``window <= 0`` means sync-every-step (each push flushes
    immediately). ``pm`` is the :class:`PerfMetrics` flushes fold into;
    drivers may swap it per epoch (``buf.pm = pm``). ``max_pending``
    bounds MEMORY the way ``window`` bounds in-flight compute: a driver
    that reaches no flush point for a long stretch (``verbose=False``
    fits, a huge ``checkpoint_every``) still folds every
    ``max_pending`` steps instead of retaining an epoch's worth of
    per-step device scalars."""

    def __init__(self, window: int = 8, pm=None, max_pending: int = 512):
        self.window = int(window)
        self.max_pending = max(1, int(max_pending))
        self.pm = pm
        # (global step index, device metric dict, batch size)
        self._pending: deque = deque()
        self.steps_flushed = 0
        self.flushes = 0
        self.blocked_ms = 0.0
        self._gauge_reported_ms = 0.0
        self.first_bad_step: Optional[int] = None
        self.first_bad_value: float = float("nan")

    @classmethod
    def for_config(cls, config, pm=None) -> "MetricsBuffer":
        """Resolve the window from config + environment: the env
        override is read here (not at import) so tests and debug
        sessions can toggle it between fits."""
        window = int(getattr(config, "async_dispatch_steps", 8))
        if sync_every_step_forced():
            window = 0
        return cls(window=window, pm=pm)

    # ------------------------------------------------------------------
    @property
    def sync(self) -> bool:
        return self.window <= 0

    @property
    def pending(self) -> int:
        return len(self._pending)

    @property
    def poisoned(self) -> bool:
        return self.first_bad_step is not None

    def raise_if_poisoned(self) -> None:
        if self.first_bad_step is not None:
            raise NonFiniteMetrics(self.first_bad_step,
                                   self.first_bad_value)

    # ------------------------------------------------------------------
    def push(self, step_idx: int, bm: Dict[str, Any],
             batch_size: int) -> None:
        """Record one step's device metric dict. No host fetch in async
        mode; in sync mode this flushes (old-loop semantics)."""
        self._pending.append((int(step_idx), bm, int(batch_size)))
        if self.sync or len(self._pending) >= self.max_pending:
            self.flush()
            return
        if len(self._pending) > self.window:
            # bound in-flight work: block on the step LEAVING the
            # window; earlier steps completed before it (in-order
            # stream), later ones are the window we keep open
            leaving = self._pending[len(self._pending) - self.window - 1]
            v = leaving[1].get("loss")
            if v is None and leaving[1]:
                v = next(iter(leaving[1].values()))
            if hasattr(v, "block_until_ready"):
                # hot path: accumulate blocked time locally; the
                # registry gauge is only touched at flush time
                t0 = time.perf_counter()
                with obs_events.span("metrics_buffer.window_wait"):
                    v.block_until_ready()
                self.blocked_ms += (time.perf_counter() - t0) * 1000.0

    def flush(self) -> int:
        """Fetch every pending step in one ``jax.device_get``, fold
        into ``pm`` in push order, update the NaN screen. Returns the
        number of steps folded."""
        if not self._pending:
            return 0
        entries = list(self._pending)
        self._pending.clear()
        t0 = time.perf_counter()
        with obs_events.span("metrics_buffer.flush", steps=len(entries),
                             window=self.window) as flush_span:
            fetched = jax.device_get([bm for _, bm, _ in entries])
            blocked = time.perf_counter() - t0
            flush_span.set(blocked_ms=round(blocked * 1000.0, 3))
        for (step_idx, _, bsz), vals in zip(entries, fetched):
            vals = dict(vals)
            ok = vals.pop(ALL_FINITE_KEY, None)
            loss = vals.get("loss")
            if ok is None:
                # step fn without the fused flag (e.g. a custom step):
                # fall back to screening the fetched loss
                ok = loss is None or math.isfinite(float(loss))
            if self.pm is not None:
                self.pm.update(vals, bsz)
            if obs_events.enabled():
                for key, v in vals.items():
                    if key.startswith(COUNTER_PREFIX):
                        obs_events.counter(key[len(COUNTER_PREFIX):],
                                           float(v))
            if not bool(ok) and self.first_bad_step is None:
                self.first_bad_step = step_idx
                self.first_bad_value = float(loss) if loss is not None \
                    else float("nan")
        self.blocked_ms += blocked * 1000.0
        self.flushes += 1
        self.steps_flushed += len(entries)
        # gauge updated once per flush (not per step): the hot loop's
        # only host costs are a deque append and the window block
        REGISTRY.gauge(
            "ff_host_blocked_ms_total",
            "Cumulative host milliseconds blocked on device sync "
            "(metric flushes + in-flight window bounds)"
        ).inc(self.blocked_ms - self._gauge_reported_ms)
        self._gauge_reported_ms = self.blocked_ms
        return len(entries)
