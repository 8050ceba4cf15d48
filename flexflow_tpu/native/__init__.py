"""ctypes bindings for the native (C++) runtime library.

The reference implements its runtime core in C++ (simulator, dataloader,
graph machinery — SURVEY.md §2.1/§2.3); this package is the TPU rebuild's
native layer: ``flexflow_tpu/native/src/ffruntime.cc`` compiled to ``libffruntime.so``.

``ensure_built()`` compiles the library on first use, and again whenever
the source is newer than the binary (g++, no external deps). The binary
is never committed, so a checkout always runs what its own source says.
Every entry point has a pure-Python fallback for hosts WITHOUT a
toolchain, and the tests assert C++ == Python semantics; with a compiler
present a failed build is an error, not a reason to fall back.
"""
from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libffruntime.so")
# the C++ source ships INSIDE the package (package-data), so a
# pip-installed copy can rebuild the library on any host with g++
_SRC = os.path.join(_HERE, "src", "ffruntime.cc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _stale() -> bool:
    return not os.path.exists(_SO) or (
        os.path.exists(_SRC)
        and os.path.getmtime(_SRC) > os.path.getmtime(_SO))


def ensure_built(force: bool = False) -> bool:
    """Compile libffruntime.so if it is missing or older than its
    source. Returns False when this host cannot build it (no g++, or no
    source and no binary); raises when g++ is there and the build
    fails."""
    if not (force or _stale()):
        return True
    if not os.path.exists(_SRC):
        return os.path.exists(_SO)
    cxx = shutil.which("g++")
    if cxx is None:
        return False
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [cxx, "-O3", "-std=c++17", "-fPIC", "-Wall", "-pthread",
             "-shared", "-o", tmp, _SRC],
            check=True, capture_output=True, text=True, timeout=120)
        os.replace(tmp, _SO)   # atomic: a concurrent loader sees old or new
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {_SO} failed (g++ exit {e.returncode}):\n"
            f"{e.stderr[-2000:]}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return True


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    # benign: double-checked locking — the unlocked read is an atomic
    # reference load; _lock orders the one-time build+publish below
    if _lib is not None:  # ffcheck: ok(guarded-field)
        return _lib  # ffcheck: ok(guarded-field)
    with _lock:
        if _lib is not None:
            return _lib
        if not ensure_built():
            return None
        lib = ctypes.CDLL(_SO)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f64p = ctypes.POINTER(ctypes.c_double)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.ffsim_simulate.restype = ctypes.c_double
        lib.ffsim_simulate.argtypes = [
            ctypes.c_int32, i32p, f64p, ctypes.c_int64, i32p, i32p,
            ctypes.c_int32, f64p]
        lib.ffsim_critical_path.restype = ctypes.c_double
        lib.ffsim_critical_path.argtypes = [
            ctypes.c_int32, f64p, ctypes.c_int64, i32p, i32p]
        lib.ffdl_gather.restype = None
        lib.ffdl_gather.argtypes = [u8p, u8p, i64p, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_int32]
        lib.ffgraph_closure.restype = ctypes.c_int32
        lib.ffgraph_closure.argtypes = [ctypes.c_int32, ctypes.c_int64,
                                        i32p, i32p, u64p]
        lib.ffb_new.restype = ctypes.c_void_p
        lib.ffb_free.argtypes = [ctypes.c_void_p]
        lib.ffb_n_tasks.restype = ctypes.c_int64
        lib.ffb_n_tasks.argtypes = [ctypes.c_void_p]
        lib.ffb_n_edges.restype = ctypes.c_int64
        lib.ffb_n_edges.argtypes = [ctypes.c_void_p]
        lib.ffb_add_tasks.restype = ctypes.c_int32
        lib.ffb_add_tasks.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                      i32p, f64p]
        lib.ffb_cross_deps.restype = None
        lib.ffb_cross_deps.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                       i32p, ctypes.c_int32, i32p]
        lib.ffb_collective.restype = ctypes.c_int32
        lib.ffb_collective.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, i32p, i32p, f64p,
            ctypes.c_int32, ctypes.c_double, ctypes.c_int32,
            ctypes.c_int32, i32p, i32p]
        lib.ffb_simulate.restype = ctypes.c_double
        lib.ffb_simulate.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.ffb_get.restype = None
        lib.ffb_get.argtypes = [ctypes.c_void_p, i32p, f64p, i32p, i32p]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def _as(arr, dtype):
    return np.ascontiguousarray(np.asarray(arr, dtype=dtype))


# ---------------------------------------------------------------------------
# task-graph simulation
# ---------------------------------------------------------------------------
def simulate(proc: Sequence[int], duration: Sequence[float],
             edges: Sequence[Tuple[int, int]], n_procs: int,
             want_starts: bool = False):
    """Event-driven task-graph simulation (reference
    ``Simulator::simulate_runtime``). Returns makespan, or (makespan,
    starts). Uses the C++ engine when available, else the Python fallback."""
    lib = get_lib()
    if lib is None:
        return simulate_py(proc, duration, edges, n_procs, want_starts)
    proc_a = _as(proc, np.int32)
    dur_a = _as(duration, np.float64)
    n = len(proc_a)
    e = np.asarray(edges, dtype=np.int32).reshape(-1, 2)
    esrc = _as(e[:, 0], np.int32)
    edst = _as(e[:, 1], np.int32)
    starts = np.zeros(n, np.float64) if want_starts else None
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    ms = lib.ffsim_simulate(
        n, proc_a.ctypes.data_as(i32p), dur_a.ctypes.data_as(f64p),
        len(e), esrc.ctypes.data_as(i32p), edst.ctypes.data_as(i32p),
        int(n_procs),
        starts.ctypes.data_as(f64p) if starts is not None else None)
    if ms < 0:
        raise ValueError("task graph contains a cycle or bad ids")
    return (ms, starts) if want_starts else ms


def simulate_py(proc, duration, edges, n_procs, want_starts: bool = False):
    """Pure-Python reference implementation (same scheduling semantics)."""
    import heapq
    n = len(proc)
    succ = [[] for _ in range(n)]
    indeg = [0] * n
    for s, d in edges:
        succ[s].append(d)
        indeg[d] += 1
    ready = [0.0] * n
    start = [0.0] * n
    avail = [0.0] * int(n_procs)
    pq = [(0.0, i) for i in range(n) if indeg[i] == 0]
    heapq.heapify(pq)
    done = 0
    makespan = 0.0
    while pq:
        rt, t = heapq.heappop(pq)
        st = max(rt, avail[proc[t]])
        ft = st + duration[t]
        start[t] = st
        avail[proc[t]] = ft
        makespan = max(makespan, ft)
        done += 1
        for s in succ[t]:
            ready[s] = max(ready[s], ft)
            indeg[s] -= 1
            if indeg[s] == 0:
                heapq.heappush(pq, (ready[s], s))
    if done != n:
        raise ValueError("task graph contains a cycle")
    if want_starts:
        return makespan, np.asarray(start)
    return makespan


def critical_path(duration, edges) -> float:
    """Longest path ignoring processor contention (overlap lower bound)."""
    lib = get_lib()
    dur_a = _as(duration, np.float64)
    n = len(dur_a)
    e = np.asarray(edges, dtype=np.int32).reshape(-1, 2)
    if lib is not None:
        i32p = ctypes.POINTER(ctypes.c_int32)
        f64p = ctypes.POINTER(ctypes.c_double)
        esrc = _as(e[:, 0], np.int32)
        edst = _as(e[:, 1], np.int32)
        cp = lib.ffsim_critical_path(
            n, dur_a.ctypes.data_as(f64p), len(e),
            esrc.ctypes.data_as(i32p), edst.ctypes.data_as(i32p))
        if cp < 0:
            raise ValueError("cycle")
        return cp
    # python fallback
    succ = [[] for _ in range(n)]
    indeg = [0] * n
    for s, d in e:
        succ[s].append(int(d))
        indeg[d] += 1
    order = [i for i in range(n) if indeg[i] == 0]
    fin = [0.0] * n
    best = 0.0
    for t in order:
        ft = fin[t] + float(dur_a[t])
        best = max(best, ft)
        for s in succ[t]:
            fin[s] = max(fin[s], ft)
            indeg[s] -= 1
            if indeg[s] == 0:
                order.append(s)
    if len(order) != n:
        raise ValueError("cycle")
    return best


# ---------------------------------------------------------------------------
# task-graph builder (search hot loop)
# ---------------------------------------------------------------------------
_I32P = ctypes.POINTER(ctypes.c_int32)
_F64P = ctypes.POINTER(ctypes.c_double)


class TaskBuffer:
    """Task-graph accumulation buffer for the strategy search.

    Native-backed when libffruntime.so is available (the ring-collective
    expansion of one search is ~20M dependency edges — the round-4
    profile's hottest Python loop); the pure-Python branch implements
    IDENTICAL semantics (tests assert parity). One logical collective is
    one call either way."""

    def __init__(self):
        self._lib = get_lib()
        if self._lib is not None:
            self._h = self._lib.ffb_new()
        else:
            self.proc: list = []
            self.dur: list = []
            self.edges: list = []

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and getattr(self, "_h", None):
            lib.ffb_free(self._h)
            self._h = None

    @property
    def n_tasks(self) -> int:
        if self._lib is not None:
            return int(self._lib.ffb_n_tasks(self._h))
        return len(self.proc)

    def add_tasks(self, procs, durs) -> int:
        """Append len(procs) tasks; returns the first id (consecutive)."""
        if self._lib is not None:
            p = _as(procs, np.int32)
            d = _as(durs, np.float64)
            return int(self._lib.ffb_add_tasks(
                self._h, len(p), p.ctypes.data_as(_I32P),
                d.ctypes.data_as(_F64P)))
        first = len(self.proc)
        self.proc.extend(int(x) for x in procs)
        self.dur.extend(float(x) for x in durs)
        return first

    def cross_deps(self, a, b) -> None:
        """All-pairs dependencies: every a[i] -> every b[j]."""
        if not len(a) or not len(b):
            return
        if self._lib is not None:
            aa = _as(a, np.int32)
            bb = _as(b, np.int32)
            self._lib.ffb_cross_deps(
                self._h, len(aa), aa.ctypes.data_as(_I32P),
                len(bb), bb.ctypes.data_as(_I32P))
            return
        for x in a:
            for y in b:
                self.edges.append((int(x), int(y)))

    def collective(self, route_off, route_procs, route_fac, rounds: int,
                   per_round_secs: float, n_seg: int, deps) -> list:
        """Ring-collective expansion (see ffb_collective in
        src/ffruntime.cc for the dependency structure). Returns
        the final task id of each participant that produced tasks."""
        n_routes = len(route_off) - 1
        if n_routes <= 0 or rounds <= 0:
            return []
        if self._lib is not None:
            off = _as(route_off, np.int32)
            procs = _as(route_procs, np.int32)
            fac = None if route_fac is None else _as(route_fac, np.float64)
            dep = _as(deps, np.int32)
            out = np.zeros(n_routes, np.int32)
            n = self._lib.ffb_collective(
                self._h, n_routes, off.ctypes.data_as(_I32P),
                procs.ctypes.data_as(_I32P),
                fac.ctypes.data_as(_F64P) if fac is not None else None,
                int(rounds), float(per_round_secs), max(1, int(n_seg)),
                len(dep), dep.ctypes.data_as(_I32P),
                out.ctypes.data_as(_I32P))
            return [int(x) for x in out[:n]]
        # python mirror of ffb_collective
        n_seg = max(1, int(n_seg))
        prev_last = [-1] * n_routes
        for r in range(rounds):
            cur = [-1] * n_routes
            for i in range(n_routes):
                h0, h1 = route_off[i], route_off[i + 1]
                if h0 >= h1:
                    cur[i] = prev_last[i]
                    continue
                last = -1
                for _s in range(n_seg):
                    prev = -1
                    for h in range(h0, h1):
                        d = (per_round_secs / n_seg) * (
                            route_fac[h] if route_fac is not None else 1.0)
                        t = len(self.proc)
                        self.proc.append(int(route_procs[h]))
                        self.dur.append(d)
                        if prev < 0:
                            if r == 0:
                                for k in deps:
                                    self.edges.append((int(k), t))
                            else:
                                pp = prev_last[(i - 1) % n_routes]
                                if pp >= 0:
                                    self.edges.append((pp, t))
                                if prev_last[i] >= 0:
                                    self.edges.append((prev_last[i], t))
                        else:
                            self.edges.append((prev, t))
                        prev = t
                    if prev >= 0:
                        last = prev
                cur[i] = last if last >= 0 else prev_last[i]
            prev_last = cur
        return [t for t in prev_last if t >= 0]

    def arrays(self):
        """(proc, dur, edges Nx2) copies — tests/introspection only."""
        if self._lib is None:
            return (list(self.proc), list(self.dur),
                    [tuple(e) for e in self.edges])
        n = int(self._lib.ffb_n_tasks(self._h))
        m = int(self._lib.ffb_n_edges(self._h))
        proc = np.zeros(n, np.int32)
        dur = np.zeros(n, np.float64)
        esrc = np.zeros(m, np.int32)
        edst = np.zeros(m, np.int32)
        self._lib.ffb_get(self._h, proc.ctypes.data_as(_I32P),
                          dur.ctypes.data_as(_F64P),
                          esrc.ctypes.data_as(_I32P),
                          edst.ctypes.data_as(_I32P))
        return proc, dur, np.stack([esrc, edst], axis=1)

    def simulate(self, n_procs: int) -> float:
        """Play the accumulated DAG through the event simulator."""
        if self._lib is not None:
            ms = self._lib.ffb_simulate(self._h, int(n_procs))
            if ms < 0:
                raise ValueError("task graph contains a cycle or bad ids")
            return float(ms)
        return simulate_py(self.proc, self.dur, self.edges, n_procs)


# ---------------------------------------------------------------------------
# dataloader gather
# ---------------------------------------------------------------------------
def gather_batch(src: np.ndarray, indices: np.ndarray,
                 out: Optional[np.ndarray] = None,
                 n_threads: int = 4) -> np.ndarray:
    """out[b] = src[indices[b]] — threaded C++ row gather when available
    (reference dataloader batch-copy tasks)."""
    src = np.ascontiguousarray(src)
    idx = _as(indices, np.int64)
    # normalize negative indices + bounds-check: the C++ path must match
    # np.take semantics exactly (no silent OOB reads)
    n_rows = src.shape[0]
    idx = np.where(idx < 0, idx + n_rows, idx)
    if len(idx) and (idx.min() < 0 or idx.max() >= n_rows):
        raise IndexError("gather_batch index out of range")
    batch = len(idx)
    row_shape = src.shape[1:]
    if out is None:
        out = np.empty((batch,) + row_shape, dtype=src.dtype)
    elif (out.shape != (batch,) + row_shape or out.dtype != src.dtype
          or not out.flags.c_contiguous):
        raise ValueError(
            f"out must be C-contiguous {(batch,) + row_shape} {src.dtype}")
    lib = get_lib()
    if lib is None:
        np.take(src, idx, axis=0, out=out)
        return out
    sample_bytes = int(np.prod(row_shape, dtype=np.int64)) * src.itemsize
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ffdl_gather(
        src.ctypes.data_as(u8p), out.ctypes.data_as(u8p),
        idx.ctypes.data_as(i64p), batch, sample_bytes, int(n_threads))
    return out


# ---------------------------------------------------------------------------
# reachability closure
# ---------------------------------------------------------------------------
def transitive_closure(n: int, edges) -> np.ndarray:
    """Packed-bitset transitive closure: bool matrix reach[i, j]."""
    words = (n + 63) // 64
    e = np.asarray(edges, dtype=np.int32).reshape(-1, 2)
    lib = get_lib()
    if lib is not None:
        out = np.zeros(n * words, np.uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        esrc = _as(e[:, 0], np.int32)
        edst = _as(e[:, 1], np.int32)
        rc = lib.ffgraph_closure(n, len(e), esrc.ctypes.data_as(i32p),
                                 edst.ctypes.data_as(i32p),
                                 out.ctypes.data_as(u64p))
        if rc != 0:
            raise ValueError("cycle")
        bits = np.unpackbits(out.reshape(n, words).view(np.uint8),
                             axis=1, bitorder="little")
        return bits[:, :n].astype(bool)
    # python fallback
    reach = np.zeros((n, n), bool)
    indeg = [0] * n
    succ = [[] for _ in range(n)]
    pred = [[] for _ in range(n)]
    for s, d in e:
        succ[s].append(int(d))
        pred[d].append(int(s))
        indeg[d] += 1
    order = [i for i in range(n) if indeg[i] == 0]
    for t in order:
        for p in pred[t]:
            reach[t] |= reach[p]
            reach[t, p] = True
        for s in succ[t]:
            indeg[s] -= 1
            if indeg[s] == 0:
                order.append(s)
    if len(order) != n:
        raise ValueError("cycle")
    return reach
