"""XLA's own compile events on the program's recorder.

JAX tells ``jax.monitoring`` listeners whenever it traces a function to
a jaxpr, lowers the jaxpr to an MLIR module, hands the module to the
backend (``compile_or_get_cached``: XLA's compile, or the persistent
cache's read-and-deserialise) and what the cache said. This module puts
those on :mod:`.events`, so that "where did the set-up go" and "which
step recompiled" are read from the same ring as the program's spans:

  span ``xla.trace``            ``/jax/core/compile/jaxpr_trace_duration``
  span ``xla.lower``            ``.../jaxpr_to_mlir_module_duration``
  span ``xla.backend_compile``  ``.../backend_compile_duration``
  span ``xla.cache_load``       ``/jax/compilation_cache/``
                                ``cache_retrieval_time_sec``
  counter ``xla.cache_hits``      ``/jax/compilation_cache/cache_hits``
  counter ``xla.cache_misses``    ``/jax/compilation_cache/cache_misses``
  counter ``xla.cache_requests``  ``.../compile_requests_use_cache``
  counter ``xla.compiles/<fun_name>``  one a backend-compile event

The three ``/jax/core/compile`` spans carry ``fun_name``: the traced
function's ``__name__``. JAX gives the lowering and the backend compile
the module's name, ``jit(<fun_name>)``; the wrapper is taken off, so
one function has one name in all three (and in ``executor.jit``, which
says which names are the program's steps).

**Nesting.** A jitted function called while another is being traced
is traced itself (every ``jnp`` function is one), so ``xla.trace``
spans nest: sum them by ``fun_name``, or take their union, never all
of them. ``backend_compile_duration`` wraps ``compile_or_get_cached``,
so on a cache hit the ``xla.cache_load`` span lies INSIDE that call's
``xla.backend_compile``: the load is a part of it, and nothing may add
the two. JAX reports a miss (``xla.cache_misses``) when it WRITES the
new entry, so a compile too small or too quick for the cache's
thresholds (``utils/compilation_cache.py`` sets both to 0 on an
accelerator) is a request that is neither a hit nor a miss.

**The clock.** JAX stamps a compile event's start and end with
``time.time()``; the recorder's clock is ``time.perf_counter()``. The
offset between the two is sampled in the callback itself, which JAX
calls within microseconds of the event's end, and not once at
installation: the wall clock is slewed against the monotonic one
(NTP, up to 500 ppm) and a recorder in a server stays on for days, so
one early sample would drift by up to tens of milliseconds an hour.
What is left is the slew over the event's own duration (under 50 ms on
a 100 s compile at the worst slew NTP allows, microseconds as a rule),
all of it on the span's START: the end is exact to the two clocks'
resolution. A step of the wall clock inside an event moves its start
by the step. ``xla.cache_load`` comes as a duration alone and ends at
its callback's instant, which is when ``_cache_read`` had returned.

These spans are recorded after the fact (``record_span``), so they do
not reach the profiler's trace; the enclosing ``ff:model.compile`` /
``ff:executor.<name>_step`` annotations do, live.

**Cost.** JAX calls the listeners on compile events only, never on a
cached dispatch: a steady step pays nothing, recorder on or off.
:func:`install` is called by ``events.enable()`` and :func:`uninstall`
by ``events.disable()``: in a process whose recorder was never on none
of these listeners exists.
"""
from __future__ import annotations

import re
import threading
import time

from . import events as _ev

_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.backend_compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "xla.cache_hits",
    "/jax/compilation_cache/cache_misses": "xla.cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache":
        "xla.cache_requests",
}
_MODULE_NAME = re.compile(r"^\w+\((.*)\)$")

_install_lock = threading.Lock()
_installed = False


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kwargs) -> None:
    # benign race: disabled fast path (see events.enabled())
    if not _ev._enabled:  # ffcheck: ok(guarded-field)
        return
    name = _SPANS.get(event)
    if name is None:
        return
    offset = time.perf_counter() - time.time()
    fun_name = str(kwargs.get("fun_name", ""))
    wrapped = _MODULE_NAME.match(fun_name)
    if wrapped:
        fun_name = wrapped.group(1)
    _ev.record_span(name, start_time + offset, end_time - start_time,
                    fun_name=fun_name)
    if name == "xla.backend_compile":
        _ev.counter("xla.compiles/" + fun_name)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    # benign race: disabled fast path (see events.enabled())
    if not _ev._enabled:  # ffcheck: ok(guarded-field)
        return
    if event == _CACHE_LOAD:
        _ev.record_span("xla.cache_load",
                        time.perf_counter() - duration_secs, duration_secs)


def _on_event(event: str, **kwargs) -> None:
    # benign race: disabled fast path (see events.enabled())
    if not _ev._enabled:  # ffcheck: ok(guarded-field)
        return
    name = _COUNTERS.get(event)
    if name is not None:
        _ev.counter(name)


def install() -> None:
    """Register the three listeners with ``jax.monitoring``, once."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def uninstall() -> None:
    """Take the listeners off again (``events.disable()``)."""
    global _installed
    with _install_lock:
        if not _installed:
            return
        from jax import monitoring
        for unregister, callback in (
                (monitoring.unregister_event_time_span_listener,
                 _on_time_span),
                (monitoring.unregister_event_duration_listener,
                 _on_duration),
                (monitoring.unregister_event_listener, _on_event)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):
                # someone's ``clear_event_listeners()`` took it already
                pass
        _installed = False
