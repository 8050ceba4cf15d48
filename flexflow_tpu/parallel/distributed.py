"""Multi-host execution: the jax.distributed control plane.

Reference parity: the reference trains across nodes via Legion control
replication + GASNet launch and per-operator NCCL communicators
(``/root/reference/MULTI-NODE.md``, ``src/runtime/model.cc:3129-3168``
``ncclInitCommunicator``, ``include/flexflow/config.h:157`` numNodes).
TPU-native redesign: one controller process per host joins a single
global device world via ``jax.distributed.initialize``; after that,
``jax.devices()`` is the global view and GSPMD + XLA collectives carry
cross-host traffic over ICI (within a slice) or DCN (across slices) —
there are no per-op communicators to create, so the whole NCCL plumbing
layer collapses into this one rendezvous.

Launch convention (the analog of the reference's ``mpirun`` wrapper):
set ``FF_COORDINATOR_ADDRESS`` / ``FF_NUM_PROCESSES`` / ``FF_PROCESS_ID``
(or pass ``--coordinator-address`` / ``--process-id`` / ``--nodes``) on
each host, or rely on jax's own cloud-TPU auto-detection by setting only
``FF_DISTRIBUTED=auto``.
"""
from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger("flexflow_tpu")

_initialized_here = False


def is_initialized() -> bool:
    """True when a jax.distributed client exists (ours or ambient).

    Detection order (tests/test_distributed.py pins the degradation):
    the public ``jax.distributed.is_initialized`` when this jax has it,
    then the private ``jax._src.distributed`` global state, then our own
    ``_initialized_here`` flag — so a jax upgrade that drops either API
    degrades to the flag (correct for every world WE joined) instead of
    silently reporting single-process."""
    import jax
    try:  # public API (newer jax)
        fn = getattr(jax.distributed, "is_initialized", None)
        if fn is not None and fn():
            return True
    except Exception:  # pragma: no cover - public-API drift
        pass
    try:  # private fallback: sees worlds initialized by the host program
        from jax._src import distributed as _jd
        if getattr(_jd.global_state, "client", None) is not None:
            return True
    except Exception:  # pragma: no cover - private-API drift
        pass
    return _initialized_here


def client():
    """The live distributed-runtime client (KV store + barriers), or
    None outside a multi-process world. The coordination layer
    (``resilience/coord.py``) builds heartbeats and bounded barriers on
    this."""
    try:
        from jax._src import distributed as _jd
        return getattr(_jd.global_state, "client", None)
    except Exception:  # pragma: no cover - private-API drift
        return None


def _enable_cpu_collectives() -> None:
    """Multi-process CPU worlds need a cross-process collectives backend
    (the XLA CPU client ships gloo for exactly this); without it every
    multi-controller computation dies with "Multiprocess computations
    aren't implemented on the CPU backend". Must run before the CPU
    client is created — maybe_initialize calls it right before
    ``jax.distributed.initialize`` (which has the same constraint).
    TPU/GPU backends ignore the option."""
    import jax
    impl = os.environ.get("FF_CPU_COLLECTIVES", "gloo")
    if not impl or impl == "none":
        return
    jax.config.update("jax_cpu_collectives_implementation", impl)


def maybe_initialize(config=None) -> bool:
    """Join the multi-host world if configured; returns True when running
    multi-process after the call. Idempotent — safe to call from every
    ``FFModel.compile``.

    Resolution order: explicit config flags, then ``FF_*`` env vars, then
    (``FF_DISTRIBUTED=auto``) jax's own cluster auto-detection.
    """
    global _initialized_here
    import jax

    if is_initialized():
        return jax.process_count() > 1

    addr = os.environ.get("FF_COORDINATOR_ADDRESS", "")
    nproc = int(os.environ.get("FF_NUM_PROCESSES", "0"))
    pid = int(os.environ.get("FF_PROCESS_ID", "-1"))
    auto = os.environ.get("FF_DISTRIBUTED", "") == "auto"
    if config is not None:
        addr = getattr(config, "coordinator_address", "") or addr
        if getattr(config, "process_id", -1) >= 0:
            pid = config.process_id
        if getattr(config, "num_nodes", 1) > 1 and nproc == 0:
            nproc = config.num_nodes

    if not addr and not auto:
        return False

    kwargs = {}
    if addr:
        kwargs = dict(coordinator_address=addr, num_processes=nproc,
                      process_id=pid)
    _enable_cpu_collectives()
    try:
        jax.distributed.initialize(**kwargs)
        _initialized_here = True
    except RuntimeError as e:  # already initialized by the host program
        if "already" not in str(e).lower():
            raise
    log.info("distributed: process %d/%d, %d local / %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())
    return jax.process_count() > 1


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def local_row_range(sharding, global_shape) -> tuple:
    """[lo, hi) rows of the leading dim owned by THIS process under
    ``sharding`` — which rows of a host-resident global batch this
    process must materialize (replicated layouts return the full range).
    """
    idx_map = sharding.addressable_devices_indices_map(tuple(global_shape))
    lo, hi = global_shape[0], 0
    for idx in idx_map.values():
        r = idx[0] if idx else slice(None)
        lo = min(lo, r.start if r.start is not None else 0)
        hi = max(hi, r.stop if r.stop is not None else global_shape[0])
    return (0, global_shape[0]) if lo >= hi else (lo, hi)


def put_global(value, sharding):
    """device_put that works in both single- and multi-process worlds.

    Multi-process: each process contributes its addressable shard of the
    host-resident global array (``jax.make_array_from_process_local_data``
    — the TPU-native analog of the reference dataloader's per-node
    zero-copy partition, ``src/dataloader/dataloader.cc``).
    """
    import jax
    if sharding is None:
        return jax.device_put(value)
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    # row-contribution fast path only applies when the cross-process
    # partitioning is on the leading (batch) dim — true for all loader
    # shardings; anything else goes through device_put (each process
    # holds the full host value)
    idx_map = sharding.addressable_devices_indices_map(tuple(value.shape))
    only_rows = all(
        all(r.start in (None, 0) and r.stop in (None, s)
            for r, s in zip(idx[1:], value.shape[1:]))
        for idx in idx_map.values())
    if not only_rows:
        return jax.device_put(value, sharding)
    lo, hi = local_row_range(sharding, value.shape)
    return jax.make_array_from_process_local_data(
        sharding, value[lo:hi], value.shape)
