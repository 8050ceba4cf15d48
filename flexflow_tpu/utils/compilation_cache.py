"""Persistent XLA compilation cache: where it lives.

Every fresh process re-traces and re-compiles its jitted steps; a
BERT-class train step takes from tens of seconds to minutes. JAX's
persistent cache turns a repeat compile — across processes — into a disk
read, but only if every process looks in the same directory: the
directory is part of how a deployment is laid out, so it is placed from
outside the program, never by it.

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself. Nothing in
    this package sets a cache directory, on any platform.
  - not set, on an accelerator: the one fixed ``<checkout>/.jax_cache``.
    Never a temporary, per-process or dated path — those never hit.
  - not set, on the CPU platform: no cache. CPU compiles here are tests
    at toy sizes, and an XLA:CPU executable is tied to the instruction
    set of the host that built it, so a checkout carried to another
    machine could load code its CPU cannot run.

On an accelerator it also makes cache keys reproducible across starts
(see the note on Mosaic kernels in ``enable_compilation_cache``).

Called by every accelerator entry point: ``FFModel.compile``, the
serving repository's loads and ``chip_smoke.py``.
"""
from __future__ import annotations

import os
import re

CHECKOUT_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str | None:
    """Make sure this process compiles through the persistent cache and
    return its directory (None: the CPU platform with nothing set)."""
    import jax

    on_cpu = jax.default_backend() == "cpu"
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if on_cpu:
            return None
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if not on_cpu:
        # keep every program: a small one still costs a round trip
        # through the compiler on each of a fleet's cold starts
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # A Mosaic kernel travels inside the HLO as serialized MLIR,
        # debug locations included, and by default a location is the
        # Python call chain of whoever traced the kernel FIRST in this
        # process (a cost-model microbenchmark on a cold start, the
        # train step on a warm one). JAX strips HLO metadata from the
        # cache key but cannot see inside that blob, so every program
        # holding a kernel missed the cache on the second start. One
        # frame per location is the same whoever calls.
        one_frame_locations()
        # With the metadata stripped from the key, a program that differs
        # from a cached one only in its names (scopes, layer names) loads
        # the OTHER program's executable, names and all: its
        # ``as_text()`` and every profiler trace of it then tell of
        # scopes this program never had, or of none (seen on the chip:
        # PR 25's parent loaded the step PR 25 had compiled and "had"
        # its phase scopes). The names are what a trace is read by, so
        # they are part of the key; with one frame a location they are
        # the same on every start.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        # ... but not where the checkout lies on disk: a location's file
        # is named from the checkout's root (unless the deployment has
        # set a canonicalization of its own)
        if not jax.config.jax_hlo_source_file_canonicalization_regex:
            jax.config.update(
                "jax_hlo_source_file_canonicalization_regex",
                "^" + re.escape(os.path.dirname(CHECKOUT_CACHE_DIR) + os.sep))
    return path


def one_frame_locations() -> None:
    """Cut every MLIR location to the one frame that made the op. NOT
    ``jax_include_full_tracebacks_in_locations = False``, which gives
    the same one frame but loses the name stack on the way to the
    compiled step: every ``op_name`` came out as the bare primitive
    (``dot_general``) and every Pallas call as ``tpu_custom_call.N``,
    so a profiler trace could not tell a layer, a phase or a kernel
    from another (PERF.md, PR 25)."""
    import jax
    jax.config.update("jax_traceback_in_locations_limit", 1)


def cache_entries(path: str | None) -> set[str]:
    """Names of the executables in the cache directory (each starts with
    the jitted function's name); empty when it does not exist. A count
    of these sees a compile that MISSED; what hit, what it cost to
    load and which function asked are the recorder's ``xla.cache_hits``
    / ``xla.cache_load`` / ``xla.compiles/<fun_name>``
    (``obs/xla_events.py``)."""
    if not path or not os.path.isdir(path):
        return set()
    return {name for name in os.listdir(path) if name.endswith("-cache")}
