"""Measurement-grounded cost-model calibration (v2).

The analytic cost model in ``search/costmodel.py`` prices compute from
datasheet FLOP/s and collectives from machine-model link constants. Both
are host-blind: on the CPU simulation substrate (and on any
oversubscribed host) they miss three effects the r05 fidelity study
showed to dominate the prediction error (VERDICT r5 "What's weak" #1):

  - **host dispatch overhead** — every jitted call pays a fixed host
    cost that dwarfs tiny per-shard kernels (the bert 2.06x-vs-5.85x
    under-prediction at per-device batch 1);
  - **memory bandwidth** — the dlrm/xdl ~3x over-prediction traces to a
    shared host-memory ceiling the per-device HBM constant cannot see;
  - **parallel efficiency** — N "devices" of a virtual CPU mesh share a
    few physical cores, so N concurrent shard tasks do NOT run N-way
    parallel; the simulator's makespan must know the real speedup.

This module microbenchmarks all three on the live backend, plus the real
XLA collectives (all-reduce / all-gather / reduce-scatter / all-to-all
over mesh axes) at import-time shapes, and persists every measurement in
an on-disk table keyed by ``(backend, kind, dtype, shape-class,
axis-size)`` — the same cross-process amortization pattern as
``utils/compilation_cache.py``: a fresh process reuses the table with
zero re-measurements. Hierarchical per-link + per-collective calibration
follows the cost-model decomposition of arXiv:2110.10548 /
arXiv:2112.01075 (separate collective and redistribution terms per
fabric level).

Opt-in: ``FFConfig.calibration_v2 = "true"`` or ``FF_CALIBRATION_V2=1``
in the environment ("auto" honors the env var only, so default search
behavior — and every recorded benchmark — is unchanged unless asked).
Force re-calibration by deleting ``<repo>/.ffcache/calibration_v2.json``
(see docs/calibration.md).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..obs import events as obs_events

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".ffcache")

#: collective payload sizes measured per (kind, axis-size): the small
#: class pins the fixed dispatch/rendezvous floor that dominates small
#: transfers (the r05 mlp searched-cost was under-priced ~85x for lack
#: of it), the larger classes the per-byte regime
COLLECTIVE_SIZES = (1 << 16, 1 << 20, 1 << 23)

COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")

#: ring-attention hop payloads measured over the dedicated seq axis
#: (coll_ppermute rows): one neighbor-exchange of the local K/V block
PPERMUTE_SIZES = (1 << 16, 1 << 20, 1 << 23)


def shape_class(nbytes: int) -> int:
    """Power-of-two size bucket: measurements and lookups for payloads
    within the same factor-of-2 band share one table entry."""
    if nbytes <= 1:
        return 1
    return 1 << int(round(math.log2(nbytes)))


#: process-wide staleness generation: bumped whenever any table
#: instance rewrites a stale sidecar (mark_stale / put superseding a
#: mark). Every ``CalibrationTable`` revalidates its in-memory sidecar
#: set against this counter (and the sidecar file's mtime, for marks
#: written by ANOTHER process), and every ``MeshCalibration`` drops its
#: lookup memos — so an in-process stale mark written by the drift
#: detector through a fresh table object is a miss IMMEDIATELY, not
#: after the next process restart.
_stale_gen = 0


def stale_generation() -> int:
    return _stale_gen


class CalibrationTable:
    """Persistent microbenchmark results, one JSON file per cache dir.

    Every entry is keyed ``backend|kind|dtype|shape_class|axis_size`` so
    a value measured on one backend (or for one dtype) can never be
    served for another. ``measured`` counts live microbenchmarks run by
    THIS process — a second process loading a warm table must report 0.
    """

    def __init__(self, cache_dir: Optional[str] = None):
        self._cache_dir = cache_dir or _DEFAULT_DIR
        self._data: Optional[Dict[str, float]] = None
        self._stale: Optional[set] = None
        self._stale_seen_gen = -1      # _stale_gen at last sidecar read
        self._stale_mtime = None       # sidecar mtime_ns at last read
        self.measured = 0          # live measurements this process

    @property
    def path(self) -> str:
        return os.path.join(self._cache_dir, "calibration_v2.json")

    @property
    def stale_path(self) -> str:
        """Sidecar naming rows the drift detector voted out: a stale
        key answers like a miss (so exactly IT is re-measured on the
        next calibration load) while every healthy row keeps serving
        warm — the surgical alternative to deleting the whole table."""
        return os.path.join(self._cache_dir, "calibration_v2_stale.json")

    @staticmethod
    def key(backend: str, kind: str, dtype: str = "-",
            sclass: int = 0, axis_size: int = 0) -> str:
        return f"{backend}|{kind}|{dtype}|{sclass}|{axis_size}"

    def _load(self) -> Dict[str, float]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    self._data = {k: float(v)
                                  for k, v in json.load(f).items()}
            except Exception:
                self._data = {}
        return self._data

    def _stale_sidecar_mtime(self):
        try:
            return os.stat(self.stale_path).st_mtime_ns
        except OSError:
            return None

    def _load_stale(self) -> set:
        # revalidate against the process-wide staleness generation (a
        # mark written through ANY table object this process created)
        # and the sidecar mtime (a mark written by another process) —
        # a live table must treat fresh stale marks as misses without
        # waiting for a restart
        mt = self._stale_mtime
        if self._stale is not None and self._stale_seen_gen != _stale_gen:
            mt = self._stale_sidecar_mtime()
        if self._stale is None or mt != self._stale_mtime:
            try:
                with open(self.stale_path) as f:
                    self._stale = {str(k) for k in json.load(f)}
            except Exception:  # noqa: BLE001 — no sidecar = none stale
                self._stale = set()
            self._stale_mtime = self._stale_sidecar_mtime()
        self._stale_seen_gen = _stale_gen
        return self._stale

    def _write_stale(self) -> None:
        global _stale_gen
        _stale_gen += 1
        self._stale_seen_gen = _stale_gen
        try:
            os.makedirs(self._cache_dir, exist_ok=True)
            tmp = self.stale_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(sorted(self._stale or set()), f)
            os.replace(tmp, self.stale_path)
            self._stale_mtime = self._stale_sidecar_mtime()
        except Exception:  # noqa: BLE001 — persistence is best-effort
            pass

    def mark_stale(self, keys) -> int:
        """Mark full table keys (``backend|kind|dtype|sclass|axis``) as
        stale: they stop answering (get/entries skip them) until a fresh
        measurement re-files them via :meth:`put`. Returns how many of
        the keys actually exist in the table (unknown keys are ignored —
        a drift report from another machine's table must not poison
        this one)."""
        data = self._load()
        stale = self._load_stale()
        hit = 0
        for k in keys:
            if k in data:
                stale.add(k)
                hit += 1
        if hit:
            self._write_stale()
        return hit

    def stale_keys(self) -> List[str]:
        return sorted(self._load_stale())

    def get(self, backend: str, kind: str, dtype: str = "-",
            sclass: int = 0, axis_size: int = 0) -> Optional[float]:
        key = self.key(backend, kind, dtype, sclass, axis_size)
        if key in self._load_stale():
            return None
        return self._load().get(key)

    def put(self, backend: str, kind: str, dtype: str, sclass: int,
            axis_size: int, value: float) -> None:
        data = self._load()
        key = self.key(backend, kind, dtype, sclass, axis_size)
        data[key] = value
        stale = self._load_stale()
        if key in stale:
            # a fresh measurement supersedes the drift verdict
            stale.discard(key)
            self._write_stale()
        try:
            os.makedirs(self._cache_dir, exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(data, f)
            os.replace(tmp, self.path)
        except Exception:  # noqa: BLE001 — persistence is best-effort
            pass

    def get_or_measure(self, backend: str, kind: str, dtype: str,
                       sclass: int, axis_size: int,
                       fn: Callable[[], float]) -> Optional[float]:
        """Serve from the table; run ``fn`` (a microbenchmark) only on a
        genuine miss, recording the result for future processes."""
        hit = self.get(backend, kind, dtype, sclass, axis_size)
        if hit is not None:
            obs_events.counter("calibration.cache_hits")
            return hit
        obs_events.counter("calibration.cache_misses")
        try:
            with obs_events.span("calibration.measure", kind=kind,
                                 axis_size=axis_size, sclass=sclass):
                v = float(fn())
        except Exception:  # noqa: BLE001 — calibration is best-effort
            return None
        self.measured += 1
        self.put(backend, kind, dtype, sclass, axis_size, v)
        return v

    def entries(self, backend: str, kind: str, dtype: str = "-",
                axis_size: int = 0) -> List[Tuple[int, float]]:
        """(shape_class, value) pairs for one (backend, kind, dtype,
        axis-size), sorted by shape class — interpolation input."""
        prefix = f"{backend}|{kind}|{dtype}|"
        suffix = f"|{axis_size}"
        stale = self._load_stale()
        out = []
        for k, v in self._load().items():
            if k.startswith(prefix) and k.endswith(suffix) \
                    and k not in stale:
                out.append((int(k[len(prefix):-len(suffix)]), v))
        return sorted(out)

    # ------------------------------------------------------------------
    # targeted in-process re-measurement (the drift detector's heal)
    # ------------------------------------------------------------------
    def remeasure_stale(self, dmesh=None, keys=None) -> Dict[str, float]:
        """Re-measure exactly the stale-marked rows on the live backend,
        in-process — no table delete, no restart. Each re-measured value
        is re-filed via :meth:`put` (which clears its stale mark), so
        attached ``MeshCalibration`` objects answer from the fresh row
        on their next lookup. Rows this process cannot realize — another
        backend's keys, collective degrees with no matching mesh-axis
        prefix, ring rows without a seq axis — are left stale for a
        process that can. Returns ``{key: seconds}`` for the rows
        actually re-measured; ``keys`` narrows the work to a subset
        (default: every stale key)."""
        import jax
        backend = jax.default_backend()
        todo = [str(k) for k in (keys if keys is not None
                                 else self.stale_keys())]
        stale = self._load_stale()
        mesh = dmesh.mesh if dmesh is not None else None
        axis_names = list(mesh.shape.keys()) if mesh is not None else []
        try:
            axis_tiers = dict(dmesh.axis_tiers) \
                if dmesh is not None else {}
        except Exception:  # noqa: BLE001 — tiers are best-effort
            axis_tiers = {}
        out: Dict[str, float] = {}
        with obs_events.span("calibration.remeasure_stale",
                             n_stale=len(todo)):
            for key in todo:
                if key not in stale:
                    continue
                parts = key.split("|")
                if len(parts) != 5 or parts[0] != backend:
                    continue
                _, kind, dtype, sc_s, ax_s = parts
                try:
                    sclass, axis_size = int(sc_s), int(ax_s)
                except ValueError:
                    continue
                try:
                    with obs_events.span("calibration.measure",
                                         kind=kind, axis_size=axis_size,
                                         sclass=sclass):
                        v = self._remeasure_one(
                            kind, dtype, sclass, axis_size, dmesh,
                            mesh, axis_names, axis_tiers)
                except Exception:  # noqa: BLE001 — best-effort per row
                    v = None
                if v is None:
                    continue
                self.measured += 1
                # filed under the PARSED key (not the re-derived shape
                # class): the stale row itself must be superseded
                self.put(backend, kind, dtype, sclass, axis_size,
                         float(v))
                out[key] = float(v)
        if out:
            try:
                from ..obs.metrics_registry import REGISTRY
                REGISTRY.counter(
                    "ff_calibration_rows_remeasured_total",
                    "Stale calibration rows re-measured in-process by "
                    "remeasure_stale").inc(len(out))
            except Exception:  # noqa: BLE001 — metering is best-effort
                pass
        return out

    def _remeasure_one(self, kind: str, dtype: str, sclass: int,
                       axis_size: int, dmesh, mesh, axis_names,
                       axis_tiers) -> Optional[float]:
        """One stale row's fresh measurement (seconds / bytes-per-s /
        efficiency), or None when this process cannot realize it."""
        if kind == "host_dispatch":
            return _bench_dispatch()
        if kind == "host_membw":
            return _bench_membw()
        if kind == "parallel_eff":
            if mesh is None or dmesh.num_devices != axis_size:
                return None
            return _bench_parallel_eff(mesh, axis_size)
        if kind.startswith("coll_"):
            if mesh is None:
                return None
            coll, _, tier = kind[len("coll_"):].partition("@")
            tier = tier or None
            if coll == "ppermute":
                # single-axis ring: the dedicated seq axis when its
                # size matches, else the innermost axis of that size
                ring_ax = getattr(dmesh, "seq_axis", None)
                if ring_ax is None \
                        or int(mesh.shape[ring_ax]) != axis_size:
                    ring_ax = next(
                        (a for a in reversed(axis_names)
                         if int(mesh.shape[a]) == axis_size), None)
                if ring_ax is None:
                    return None
                tiers = {axis_tiers.get(ring_ax, "ici")}
                if tier is not None and tiers != {tier}:
                    return None
                v = _bench_collective(mesh, "ppermute", sclass,
                                      axes=(ring_ax,), dtype=dtype)
            else:
                if coll not in COLLECTIVES:
                    return None
                # realize the degree as a mesh-axis prefix product —
                # the same grid _calibrate_mesh measured
                p, n_axes = 1, None
                for k, a in enumerate(axis_names, start=1):
                    p *= int(mesh.shape[a])
                    if p == axis_size:
                        n_axes = k
                        break
                    if p > axis_size:
                        break
                if n_axes is None:
                    return None
                tiers = {axis_tiers.get(a, "ici")
                         for a in axis_names[:n_axes]}
                if tier is not None and tiers != {tier}:
                    return None
                v = _bench_collective(mesh, coll, sclass,
                                      n_axes=n_axes, dtype=dtype)
            return v * _link_degradation_factor(tiers)
        return None


def _link_degradation_factor(tiers) -> float:
    """Max registered chaos-drill bandwidth degradation across
    ``tiers`` (resilience/faults.py ``degrade_link@N:tier:factor``).
    The CPU-sim substrate cannot physically slow a modeled link, so the
    timing path scales measured collective seconds by this factor
    instead — a measurement taken while a drill is active reflects the
    degraded fabric exactly as a real slow link would."""
    try:
        from ..resilience.faults import link_degradation
        return max([float(link_degradation(t)) for t in tiers]
                   or [1.0])
    except Exception:  # noqa: BLE001 — no drill machinery = healthy
        return 1.0


# ----------------------------------------------------------------------
# microbenchmarks (each returns seconds; every timed call ends in a
# device->host fetch of its result)
# ----------------------------------------------------------------------

def _timed(f, args, warmup: int = 2, repeats: int = 5) -> float:
    """MIN over repeats: host-load noise is one-sided (contention only
    adds time), and a polluted measurement persisted to the table is
    served forever — the minimum is the stable estimator here."""
    for _ in range(warmup):
        float(np.asarray(f(*args)).ravel()[0])
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(np.asarray(f(*args)).ravel()[0])
        ts.append(time.perf_counter() - t0)
    return float(min(ts))


def _bench_dispatch() -> float:
    """Fixed per-call host cost of one trivial jitted op (trace/dispatch/
    fetch) — the floor under every per-shard kernel."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    return _timed(f, (jnp.zeros((8,), jnp.float32),), repeats=9)


def _bench_membw(nbytes: int = 64 << 20) -> float:
    """Effective memory bandwidth (bytes/s) of a streaming read at
    ``nbytes`` working set — the shared ceiling concurrent shards hit.
    The jitted body REDUCES to a scalar so the sync fetch moves 4
    bytes: fetching the full output would time the device-to-host link
    (PCIe), not memory, on accelerator backends."""
    import jax
    import jax.numpy as jnp
    n = nbytes // 4
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda x: jnp.sum(x * 1.0001 + 1.0))
    dt = _timed(f, (x,), repeats=5)
    if dt < 1e-3:
        # a 64 MiB stream cannot finish in under a millisecond on any
        # current part — the work was eliminated or the clock lied;
        # failing here makes the caller fall back to the spec constant
        # instead of persisting a physically impossible bandwidth
        raise RuntimeError(f"membw bench eliminated (dt={dt:.2e}s)")
    return nbytes / dt


def _bench_parallel_eff(mesh, n_dev: int) -> float:
    """Measured efficiency of ``n_dev`` concurrent shard tasks: time one
    matmul on a single device, then the SAME per-shard matmul replicated
    across every mesh device via shard_map. On real hardware the wall
    time is flat (eff ~ 1); on an oversubscribed virtual CPU mesh the
    shards serialize onto the physical cores (eff ~ cores / n_dev)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    m = 384
    a = jnp.ones((m, m), jnp.float32)

    def chain(x):
        for _ in range(4):
            x = x @ x * 1e-3
        return jnp.sum(x)[None]      # (1,): concatenable per-shard value

    t1 = _timed(jax.jit(chain), (a,), repeats=3)
    axes = tuple(mesh.axis_names)
    big = jnp.ones((m * n_dev, m), jnp.float32)
    big = jax.device_put(big, NamedSharding(mesh, P(axes)))

    def sharded(x):
        return shard_map(chain, mesh=mesh,
                         in_specs=P(axes), out_specs=P(axes))(x)

    tn = _timed(jax.jit(sharded), (big,), repeats=3)
    return float(min(1.0, max(1.0 / n_dev, t1 / max(tn, 1e-9))))


def _bench_collective(mesh, coll: str, nbytes: int,
                      n_axes: Optional[int] = None,
                      dtype: str = "float32",
                      axes: Optional[Tuple[str, ...]] = None) -> float:
    """One logical collective over the first ``n_axes`` mesh axes (all
    by default) at ``nbytes`` payload per group, on the live backend.
    With a subset, the remaining axes run the same collective
    concurrently in independent groups — exactly how a sub-degree
    collective executes inside a larger mesh, contention included.
    ``dtype`` sets the wire payload type — the quantized-collective
    rows (int8/fp8) time the same logical collectives at narrow
    payloads; a backend that cannot lower them raises and the caller
    records nothing (itemsize-scaled float32 rows stand in)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    jdt = {"float32": jnp.float32, "int8": jnp.int8,
           "float8_e4m3": jnp.float8_e4m3fn,
           "float8_e5m2": jnp.float8_e5m2}[dtype]
    isz = np.dtype(jdt).itemsize
    all_axes = tuple(mesh.axis_names)
    coll_axes = axes if axes is not None \
        else (all_axes[:n_axes] if n_axes else all_axes)
    axes = all_axes
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    deg = int(np.prod([mesh.shape[a] for a in coll_axes]))
    if coll == "ppermute":
        # ring-hop exchange: every device sends its WHOLE local block
        # to its +1 neighbor on the ring axis — ``nbytes`` is the
        # per-device (= per-hop per-link) payload
        m = max(nbytes // isz * n_dev, n_dev * n_dev)
    else:
        # ``nbytes`` is the PER-GROUP payload (what xfer_cost queries);
        # a subset collective has n_dev/deg concurrent groups, so the
        # global array scales up to keep each group's volume at nbytes
        m = max(nbytes // isz * (n_dev // deg), n_dev * n_dev)
    m -= m % (n_dev * n_dev)       # shardable + all_to_all reshapable
    x = jnp.ones((m,), jdt)

    def acc(y):
        # per-shard (1,) value; integer/fp8 payloads fold in fp32 so
        # the sync-fetch scalar is well-defined on every backend
        return jnp.sum(y.astype(jnp.float32))[None]

    # every body returns a (1,) per-shard value gathered with
    # out_specs=P(axes): no replication claim, works for all kinds
    if coll == "all_reduce":
        def body(xl):
            return acc(jax.lax.psum(xl, coll_axes))
    elif coll == "all_gather":
        def body(xl):
            return acc(jax.lax.all_gather(xl, coll_axes, tiled=True))
    elif coll == "reduce_scatter":
        def body(xl):
            return acc(jax.lax.psum_scatter(
                xl, coll_axes, scatter_dimension=0, tiled=True))
    elif coll == "all_to_all":
        def body(xl):
            return acc(jax.lax.all_to_all(
                xl.reshape(deg, -1), coll_axes, 0, 0))
    elif coll == "ppermute":
        # one ring hop (the unit step of ring attention's K/V
        # rotation): a single named axis only — a ring over a
        # flattened multi-axis prefix is not a neighbor exchange
        if len(coll_axes) != 1:
            raise ValueError("ppermute benches a single mesh axis")
        ax = coll_axes[0]
        perm = [(i, (i + 1) % deg) for i in range(deg)]

        def body(xl):
            return acc(jax.lax.ppermute(xl, ax, perm))
    else:
        raise ValueError(coll)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(axes),
                          out_specs=P(axes)))
    return _timed(f, (x,), repeats=3)


# ----------------------------------------------------------------------
# the attachable calibration object
# ----------------------------------------------------------------------

@dataclasses.dataclass
class MeshCalibration:
    """Measured host + collective terms the cost model consults.

    ``collective_time`` answers from the persisted table by log-log
    interpolation between the measured shape classes of the matching
    (backend, collective, dtype, axis-size) row; a query for a degree
    that was never measured returns None and the cost model falls back
    to its fitted/analytic path.
    """
    backend: str
    dispatch_s: Optional[float] = None
    mem_bw: Optional[float] = None
    parallel_eff: Dict[int, float] = dataclasses.field(default_factory=dict)
    table: Optional[CalibrationTable] = None
    dtype: str = "float32"
    # lookup memos — collective_time sits inside xfer_cost, the
    # search's hottest evaluator loop (1e4-1e6 calls per search), and
    # the table only changes when a drift verdict lands, so the
    # full-table key scans are done once per (coll, degree) per
    # staleness generation (stale marks / re-measurements drop them)
    _pts: Dict = dataclasses.field(default_factory=dict, repr=False)
    _degs: Dict = dataclasses.field(default_factory=dict, repr=False)
    _seen_gen: int = dataclasses.field(default=-1, repr=False)

    def _sync_gen(self) -> None:
        if self._seen_gen != _stale_gen:
            self._pts.clear()
            self._degs.clear()
            self._seen_gen = _stale_gen

    def _points(self, coll: str, degree: int,
                tier: Optional[str] = None,
                dtype: Optional[str] = None) -> List[Tuple[int, float]]:
        """Measured (shape_class, seconds) points for one collective at
        one degree. ``tier`` selects the tier-keyed rows
        (``coll_<kind>@<tier>``, written by :func:`calibrate_mesh` on
        multi-tier meshes); flat rows remain the fallback so warm
        pre-tier tables keep answering without re-measurement.
        ``dtype`` selects wire-dtype rows (``int8``/``float8_*``,
        measured by :func:`calibrate_mesh` when quantized collectives
        are enabled) instead of the default element dtype."""
        self._sync_gen()
        kind = f"{coll}@{tier}" if tier else coll
        dt = dtype or self.dtype
        key = (kind, degree, dt)
        hit = self._pts.get(key)
        if hit is None:
            hit = self.table.entries(self.backend, f"coll_{kind}",
                                     dt, axis_size=degree)
            self._pts[key] = hit
        return hit

    def efficiency(self, n_shards: int) -> float:
        """Measured parallel efficiency for ``n_shards`` concurrent shard
        tasks (1.0 = ideal). Unmeasured widths interpolate between the
        measured ones (ideal at 1)."""
        if n_shards <= 1 or not self.parallel_eff:
            return 1.0
        if n_shards in self.parallel_eff:
            return self.parallel_eff[n_shards]
        pts = sorted(self.parallel_eff.items())
        lo_n, lo_e = 1, 1.0
        for n, e in pts:
            if n >= n_shards:
                # linear in log(n): eff falls off as oversubscription grows
                t = ((math.log(n_shards) - math.log(lo_n))
                     / max(math.log(n) - math.log(lo_n), 1e-9))
                return lo_e + t * (e - lo_e)
            lo_n, lo_e = n, e
        return pts[-1][1]          # wider than measured: worst measured

    def _degrees_measured(self, coll: str) -> List[int]:
        if self.table is None:
            return []
        self._sync_gen()
        hit = self._degs.get(coll)
        if hit is None:
            prefix = f"{self.backend}|coll_{coll}|{self.dtype}|"
            stale = self.table._load_stale()
            out = set()
            for k in self.table._load():
                if k.startswith(prefix) and k not in stale:
                    out.add(int(k.rsplit("|", 1)[1]))
            hit = sorted(out)
            self._degs[coll] = hit
        return hit

    def collective_time(self, coll: str, degree: int, nbytes: float,
                        tier: Optional[str] = None,
                        dtype: Optional[str] = None) -> Optional[float]:
        if self.table is None or degree <= 1 or nbytes <= 0:
            return None
        if dtype is not None:
            # wire-dtype rows are measured opportunistically (quantized
            # collectives enabled): STRICT like tier rows — a miss
            # returns None and the caller falls back to the
            # itemsize-scaled float32 query, never a wrong row
            pts = self._points(coll, degree, tier, dtype=dtype)
            if not pts:
                return None
            return self._interp(pts, nbytes)
        if tier is not None:
            # STRICT: a tier-scoped query answers only from rows
            # measured for that tier. Falling back to the flat rows
            # here would price a DCN leg at the innermost fabric's
            # measured speed (~20x under on the virtual 2-slice config)
            # — the caller's fallback is the tier's machine-model
            # constants, not a wrong measurement. Flat (tier=None)
            # queries keep the whole warm table, so pre-tier caches
            # still answer with zero re-measurement.
            pts = self._points(coll, degree, tier)
            if not pts:
                return None
        else:
            pts = self._points(coll, degree)
        if not pts:
            # nearest measured degree (log distance): a degree-3 query
            # on a mesh measured at {2, 4, 8} answers from the closest
            # curve rather than falling to the host-blind analytic model
            degs = self._degrees_measured(coll)
            if not degs:
                return None
            near = min(degs, key=lambda d: abs(math.log(d)
                                               - math.log(degree)))
            if not (0.5 <= near / degree <= 2.0):
                return None          # too far to stand in
            pts = self._points(coll, near)
        return self._interp(pts, nbytes)

    @staticmethod
    def _interp(pts: List[Tuple[int, float]], nbytes: float) -> float:
        # at/below the smallest measured class the fixed dispatch/
        # rendezvous floor dominates: CLAMP, never extrapolate downward
        # (a 16 KiB collective does not cost 16/64 of the 64 KiB one)
        if nbytes <= pts[0][0]:
            return pts[0][1]
        if len(pts) == 1:
            sc, t = pts[0]
            return t * nbytes / sc   # single point: linear in volume
        # log-log interpolation (upward extrapolation on the top pair)
        xs = [math.log(sc) for sc, _ in pts]
        ys = [math.log(max(t, 1e-12)) for _, t in pts]
        x = math.log(max(nbytes, 1.0))
        i = 1
        while i < len(xs) - 1 and xs[i] < x:
            i += 1
        slope = (ys[i] - ys[i - 1]) / max(xs[i] - xs[i - 1], 1e-9)
        y = ys[i - 1] + slope * (x - xs[i - 1])
        return math.exp(y)

    def row_key(self, coll: str, degree: int, nbytes: float,
                tier: Optional[str] = None) -> Optional[str]:
        """Full table key (``backend|kind|dtype|shape_class|axis_size``)
        of the measured row anchoring a :meth:`collective_time` answer —
        the nearest measured shape class at the answering degree. The
        drift detector (obs/drift.py) attributes an out-of-band
        predicted-vs-measured ratio to exactly this row and marks it
        stale. None = the query would not answer from the table (the
        prediction came from the analytic model instead)."""
        if self.table is None or degree <= 1 or nbytes <= 0:
            return None
        kind = f"{coll}@{tier}" if tier else coll
        pts = self._points(coll, degree, tier)
        deg = degree
        if not pts and tier is None:
            degs = self._degrees_measured(coll)
            if degs:
                near = min(degs, key=lambda d: abs(math.log(d)
                                                   - math.log(degree)))
                if 0.5 <= near / degree <= 2.0:
                    deg = near
                    pts = self._points(coll, near)
        if not pts:
            return None
        sc = min(pts, key=lambda p: abs(
            math.log(max(p[0], 1)) - math.log(max(nbytes, 1.0))))[0]
        return CalibrationTable.key(self.backend, f"coll_{kind}",
                                    self.dtype, sc, deg)

    def collective_marginal(self, coll: str, degree: int,
                            nbytes: float,
                            dtype: Optional[str] = None
                            ) -> Optional[float]:
        """Per-byte MARGINAL cost of a collective — the measured curve's
        top-range slope times the volume, with the fixed dispatch/
        rendezvous floor amortized away. This prices per-op gradient
        all-reduces: XLA's all-reduce combiner coalesces the per-layer
        reductions of a training step into a few large collectives, so
        the executed program pays the floor once, not once per layer —
        charging it per op made every many-layer DP baseline look
        ~per-layer-floor too expensive and inverted the searched-vs-DP
        ranking on dense tower models (candle/mlp)."""
        if self.table is None or degree <= 1 or nbytes <= 0:
            return None
        full = self.collective_time(coll, degree, nbytes, dtype=dtype)
        if full is None:
            return None
        pts = self._points(coll, degree, dtype=dtype)
        if dtype is not None and len(pts) < 2:
            # wire-dtype rows: no nearest-degree stand-in (strict, like
            # tier rows) — fall back to the top point's average
            return full
        if not pts:
            degs = self._degrees_measured(coll)
            if not degs:
                return full
            near = min(degs, key=lambda d: abs(math.log(d)
                                               - math.log(degree)))
            pts = self._points(coll, near)
        if len(pts) < 2:
            return full
        (s1, t1), (s2, t2) = pts[-2], pts[-1]
        slope = (t2 - t1) / max(s2 - s1, 1.0)
        if slope <= 0.0:
            # non-monotone measured pair (transient load during the
            # smaller bench, persisted forever): fall back to the top
            # point's average per-byte cost rather than pricing every
            # gradient all-reduce at zero
            slope = t2 / max(s2, 1.0)
        return min(full, slope * nbytes)


def calibrate_mesh(dmesh=None, cache_dir: Optional[str] = None,
                   collectives: Tuple[str, ...] = COLLECTIVES,
                   sizes: Tuple[int, ...] = COLLECTIVE_SIZES,
                   table: Optional[CalibrationTable] = None,
                   wire_dtypes: Tuple[str, ...] = ()
                   ) -> MeshCalibration:
    """Measure (or load) every calibration term for the live backend and
    the given mesh. Persisted measurements are reused across processes;
    a warm table makes this call measurement-free. ``wire_dtypes``
    additionally measures the quantized-collective payload rows
    (int8/fp8) for the same (collective, degree, size) grid — passed by
    the search when ``FFConfig.quantized_collectives`` is on; a backend
    that cannot lower a narrow collective records nothing and lookups
    fall back to itemsize-scaled float32 rows (docs/calibration.md)."""
    import jax
    with obs_events.span("search.calibrate_mesh"):
        return _calibrate_mesh(jax.default_backend(), dmesh, cache_dir,
                               collectives, sizes, table, wire_dtypes)


def _calibrate_mesh(backend, dmesh, cache_dir, collectives, sizes,
                    table, wire_dtypes=()) -> MeshCalibration:
    tab = table if table is not None else CalibrationTable(cache_dir)
    calib = MeshCalibration(backend=backend, table=tab)
    calib.dispatch_s = tab.get_or_measure(
        backend, "host_dispatch", "-", 0, 0, _bench_dispatch)
    calib.mem_bw = tab.get_or_measure(
        backend, "host_membw", "-", 0, 0, _bench_membw)
    if dmesh is not None and dmesh.num_devices > 1:
        n = dmesh.num_devices
        mesh = dmesh.mesh
        eff = tab.get_or_measure(backend, "parallel_eff", "-", 0, n,
                                 lambda: _bench_parallel_eff(mesh, n))
        if eff is not None:
            calib.parallel_eff[n] = eff
        # collective degrees: every prefix product of the mesh axes
        # (e.g. 2, 4, 8 on a 2x2x2 virtual mesh) — a sub-degree
        # collective runs concurrently in groups across the remaining
        # axes, exactly as the search would place it; capped at 4
        # degree points to bound the one-time measurement cost
        sizes_list = list(mesh.shape.values())
        degrees = []
        p = 1
        for k, s in enumerate(sizes_list, start=1):
            p *= s
            degrees.append((p, k))
        if len(degrees) > 4:
            keep = {0, len(degrees) - 1,
                    len(degrees) // 3, 2 * len(degrees) // 3}
            degrees = [d for i, d in enumerate(degrees) if i in keep]
        # tier annotation of each measured degree prefix: the outermost
        # tier the prefix axes touch (dmesh.axis_tiers; None when the
        # machine is single-tier — flat keys only, as before)
        axis_names = list(mesh.shape.keys())
        try:
            axis_tiers = dict(dmesh.axis_tiers)
            multi_tier = len(set(axis_tiers.values())) > 1
        except Exception:  # noqa: BLE001 — tiers are best-effort
            axis_tiers, multi_tier = {}, False
        for coll in collectives:
            for deg, n_axes in degrees:
                if deg <= 1:
                    continue
                prefix_tiers = {axis_tiers.get(a, "ici")
                                for a in axis_names[:n_axes]}
                # mirror ONLY pure single-tier prefixes: a mixed-tier
                # prefix's measurement filed under the outermost tier
                # would later answer a pure-tier query of a differently
                # shaped mesh sharing this table (the entries carry no
                # mesh identity) — the exact mispricing the strict tier
                # lookup exists to prevent
                tier = next(iter(prefix_tiers)) \
                    if multi_tier and len(prefix_tiers) == 1 else None
                for nbytes in sizes:
                    v = tab.get_or_measure(
                        backend, f"coll_{coll}", "float32",
                        shape_class(nbytes), deg,
                        lambda c=coll, s=nbytes, k=n_axes,
                        pt=frozenset(prefix_tiers):
                            _bench_collective(mesh, c, s, n_axes=k)
                            * _link_degradation_factor(pt))
                    # mirror the measurement under the tier key (no
                    # re-measurement): tier-aware lookups answer from
                    # coll_<kind>@<tier> first, flat stays the fallback
                    if v is not None and tier is not None and tab.get(
                            backend, f"coll_{coll}@{tier}", "float32",
                            shape_class(nbytes), deg) is None:
                        tab.put(backend, f"coll_{coll}@{tier}",
                                "float32", shape_class(nbytes), deg, v)
                    # quantized wire rows (same grid, narrow payload):
                    # keyed by the wire dtype so a float32 query can
                    # never answer from them; failures record nothing
                    # (get_or_measure swallows the raise) and the
                    # itemsize-scaled float32 rows stand in
                    for wdt in wire_dtypes:
                        vw = tab.get_or_measure(
                            backend, f"coll_{coll}", wdt,
                            shape_class(nbytes), deg,
                            lambda c=coll, s=nbytes, k=n_axes, w=wdt,
                            pt=frozenset(prefix_tiers):
                                _bench_collective(mesh, c, s, n_axes=k,
                                                  dtype=w)
                                * _link_degradation_factor(pt))
                        if vw is not None and tier is not None \
                                and tab.get(backend,
                                            f"coll_{coll}@{tier}", wdt,
                                            shape_class(nbytes),
                                            deg) is None:
                            tab.put(backend, f"coll_{coll}@{tier}",
                                    wdt, shape_class(nbytes), deg, vw)
        # ring-hop rows (coll_ppermute): ONE neighbor exchange over a
        # single mesh axis — the unit step ring attention's K/V
        # rotation pays (degree-1) times. Measured over the dedicated
        # seq axis when the mesh has one (that IS the ring), else the
        # innermost axis; tier-mirrored like the grouped collectives so
        # placement-path pricing stays strict per tier.
        ring_ax = getattr(dmesh, "seq_axis", None) or axis_names[-1]
        ring_deg = int(mesh.shape[ring_ax])
        if ring_deg > 1:
            ring_tier = axis_tiers.get(ring_ax, "ici") \
                if multi_tier else None
            for nbytes in PPERMUTE_SIZES:
                v = tab.get_or_measure(
                    backend, "coll_ppermute", "float32",
                    shape_class(nbytes), ring_deg,
                    lambda s=nbytes, a=ring_ax:
                        _bench_collective(mesh, "ppermute", s,
                                          axes=(a,))
                        * _link_degradation_factor(
                            {axis_tiers.get(a, "ici")}))
                if v is not None and ring_tier is not None and tab.get(
                        backend, f"coll_ppermute@{ring_tier}",
                        "float32", shape_class(nbytes),
                        ring_deg) is None:
                    tab.put(backend, f"coll_ppermute@{ring_tier}",
                            "float32", shape_class(nbytes), ring_deg, v)
    return calib


def calibration_enabled(cfg=None) -> bool:
    """Resolve the opt-in: config "true"/"false" wins; "auto" (and no
    config at all) honors the FF_CALIBRATION_V2 env var."""
    mode = str(getattr(cfg, "calibration_v2", "auto") or "auto").lower()
    if mode in ("true", "on", "1", "yes"):
        return True
    if mode in ("false", "off", "0", "no"):
        return False
    return os.environ.get("FF_CALIBRATION_V2", "").lower() \
        in ("1", "true", "yes", "on")
