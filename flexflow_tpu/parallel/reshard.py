"""Searched, memory-optimal resharding collectives for layout transitions.

Every layout transition in the stack — bank-boundary rejoins
(``parallel/banks.py``), pipeline-region entry/exit
(``parallel/pipeline_lowering.py`` + executor), and the elastic
re-plan's reshard-restored-state path (``resilience/elastic.py`` riding
``runtime/checkpoint.py``) — used to lower through GSPMD's generic
resharding: the partitioner was free to pick gather/scatter rewrites
("involuntary full rematerialization"), which is slow, memory-peaky,
and — on the reshape/concat rewrites this repo's two standing alignment
failures exercised — outright miscompiled on the CPU backend.

Following PAPERS.md "Memory-efficient array redistribution through
portable collective communication" (arXiv 2112.01075), a transition
``src layout → dst layout`` is instead lowered to a short sequence of
portable collective steps with explicit semantics:

  - ``gather``   — all-gather a suffix of a dim's mesh axes (the dim's
                   minor-most shard factors), inflating the local shard;
  - ``alltoall`` — move one mesh axis from one dim's sharding to
                   another's at CONSTANT per-device memory (the paper's
                   key primitive: an all-to-all replaces an
                   allgather+slice pair, cutting both time and peak);
  - ``slice``    — locally slice a dim by new mesh axes (no traffic).

The planner enumerates candidate step orderings (all-to-all-first /
gather-first / the naive gather-everything-then-slice baseline), scores
each for TIME and PEAK TRANSIENT MEMORY with the calibrated collective
tables (``search/calibration.py`` via
``search/costmodel.OpCostModel.reshard_step_cost``), and executes the
winner as ONE ``shard_map`` whose in/out specs pin the src/dst layouts —
GSPMD has no freedom left to fumble the transition. Plans are cached
per (src, dst, mesh, dtype, shape-class) in ``.ffcache`` alongside the
calibration tables, so warm processes never re-plan.

``FF_NAIVE_RESHARD=1`` keeps the pre-planner path (bare
``with_sharding_constraint`` / ``device_put``) as the bench/fallback
baseline. Every planned transition emits an obs span plus the
``ff_reshard_bytes_total`` / ``ff_reshard_plans_total{kind=...}``
counters, and the chosen step sequence is appended to the strategy
audit record when a search wrote one (``obs/audit.py``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ffconst import OperatorType
from ..obs import events as obs_events
from ..obs.metrics_registry import REGISTRY

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".ffcache")

#: ops whose GSPMD partitioning rewrites are the risky ones (reshape /
#: concat re-tiling is where the backward-propagated constraint
#: miscompiled); transitions on their outputs go through the planner
LAYOUT_OPS = frozenset({
    OperatorType.OP_RESHAPE, OperatorType.OP_TRANSPOSE,
    OperatorType.OP_CONCAT, OperatorType.OP_SPLIT, OperatorType.OP_FLAT,
    OperatorType.OP_SLICE, OperatorType.OP_PAD, OperatorType.OP_REVERSE,
    OperatorType.OP_SQUEEZE, OperatorType.OP_UNSQUEEZE,
})


def naive_reshard() -> bool:
    """``FF_NAIVE_RESHARD=1``: keep the pre-planner transition path
    (bare sharding constraints / whole-array device_put) — the bench
    baseline and the escape hatch. Read per call: the flag is consulted
    at trace/restore time, so separate compiles (e.g. the bench's
    paired legs) can flip it per process."""
    return os.environ.get("FF_NAIVE_RESHARD", "").lower() \
        in ("1", "true", "yes", "on")


# ----------------------------------------------------------------------
# layout normalization
# ----------------------------------------------------------------------

def norm_spec(spec, rank: int) -> Tuple[Tuple[str, ...], ...]:
    """PartitionSpec → per-dim tuples of mesh axes, padded to ``rank``.
    ``None`` (no constraint) normalizes to fully replicated — the only
    layout a transition can assume for an unconstrained value."""
    dims: List[Tuple[str, ...]] = []
    if spec is not None:
        for e in tuple(spec):
            if e is None:
                dims.append(())
            elif isinstance(e, (tuple, list)):
                dims.append(tuple(e))
            else:
                dims.append((e,))
    while len(dims) < rank:
        dims.append(())
    return tuple(dims[:rank])


def _to_partition_spec(norm: Sequence[Tuple[str, ...]]):
    from jax.sharding import PartitionSpec as P
    entries: List[Any] = []
    for d in norm:
        if not d:
            entries.append(None)
        elif len(d) == 1:
            entries.append(d[0])
        else:
            entries.append(tuple(d))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def layout_key(norm: Sequence[Tuple[str, ...]]) -> str:
    return "|".join("+".join(d) if d else "-" for d in norm)


# ----------------------------------------------------------------------
# step vocabulary + plans
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Step:
    """One portable collective in a lowering plan. ``axes`` are mesh
    axes in major→minor order; for ``alltoall`` the axis moves from
    ``src_dim``'s sharding (where it is minor-most) onto ``dim``'s
    (appended minor-most)."""
    kind: str                       # "gather" | "alltoall" | "slice"
    dim: int
    axes: Tuple[str, ...]
    src_dim: int = -1               # alltoall only

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.kind, "dim": self.dim,
                "axes": list(self.axes), "src_dim": self.src_dim}

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "Step":
        return cls(d["kind"], int(d["dim"]), tuple(d["axes"]),
                   int(d.get("src_dim", -1)))


@dataclasses.dataclass
class ReshardPlan:
    """A scored lowering of one src→dst transition."""
    src: Tuple[Tuple[str, ...], ...]
    dst: Tuple[Tuple[str, ...], ...]
    steps: List[Step]
    est_time_s: float = 0.0
    peak_bytes: float = 0.0         # per-device transient working set
    naive_peak_bytes: float = 0.0   # the gather-everything baseline's
    kind: str = "searched"          # "searched" | "naive" | "constraint"

    def describe(self) -> List[str]:
        out = []
        for s in self.steps:
            if s.kind == "alltoall":
                out.append(f"alltoall[{'+'.join(s.axes)}] "
                           f"dim{s.src_dim}->dim{s.dim}")
            else:
                out.append(f"{s.kind}[{'+'.join(s.axes)}] dim{s.dim}")
        return out


def _candidate_steps(src, dst, priority: Sequence[str]
                     ) -> Optional[List[Step]]:
    """Greedy lowering of src→dst under a step-kind priority order.
    Invariants maintained: a dim is only ever gathered over the suffix
    of its axes beyond its common prefix with the target (minor-most
    shard factors — the only relayout ``all_gather(tiled)`` realizes
    exactly), slices append minor-most axes in target order, and an
    all-to-all moves exactly one minor-most axis onto the next axis its
    target dim needs. Returns None when the greedy walk cannot reach
    ``dst`` (caller falls back to the naive candidate)."""
    cur = [list(d) for d in src]
    tgt = [list(d) for d in dst]
    ndim = len(cur)
    steps: List[Step] = []

    def prefix_len(d):
        k = 0
        while k < len(cur[d]) and k < len(tgt[d]) \
                and cur[d][k] == tgt[d][k]:
            k += 1
        return k

    def find_move() -> Optional[Step]:
        for i in range(ndim):
            if len(cur[i]) <= prefix_len(i):
                continue
            a = cur[i][-1]
            for j in range(ndim):
                if j == i or cur[j] != tgt[j][:len(cur[j])]:
                    continue
                if len(cur[j]) < len(tgt[j]) \
                        and tgt[j][len(cur[j])] == a:
                    return Step("alltoall", dim=j, axes=(a,), src_dim=i)
        return None

    def find_gather() -> Optional[Step]:
        for i in range(ndim):
            k = prefix_len(i)
            if len(cur[i]) > k:
                return Step("gather", dim=i, axes=tuple(cur[i][k:]))
        return None

    def find_slice() -> Optional[Step]:
        used = {a for c in cur for a in c}
        for j in range(ndim):
            if cur[j] != tgt[j][:len(cur[j])]:
                continue
            pend = tgt[j][len(cur[j]):]
            take: List[str] = []
            for a in pend:
                if a in used:
                    break
                take.append(a)
            if take:
                return Step("slice", dim=j, axes=tuple(take))
        return None

    finders = {"alltoall": find_move, "gather": find_gather,
               "slice": find_slice}
    while cur != tgt:
        step = None
        for kind in priority:
            step = finders[kind]()
            if step is not None:
                break
        if step is None:
            return None
        steps.append(step)
        if step.kind == "gather":
            del cur[step.dim][len(cur[step.dim]) - len(step.axes):]
        elif step.kind == "slice":
            cur[step.dim].extend(step.axes)
        else:
            cur[step.src_dim].pop()
            cur[step.dim].append(step.axes[0])
        if len(steps) > 8 * ndim + 8:       # safety against livelock
            return None
    return steps


def _tier_staged(steps: Sequence[Step],
                 axis_tiers: Dict[str, str]) -> Optional[List[Step]]:
    """Hierarchical lowering of a candidate: split every gather whose
    axes span more than one hardware tier into per-tier staged gathers
    (minor-most run first — the only order ``all_gather(tiled)``
    realizes), so each leg is ONE portable collective confined to one
    fabric and the cost model prices it at that tier's bandwidth
    (arXiv 2110.10548's per-tier reduction phases). Returns None when
    nothing splits (single-tier plans stay byte-identical)."""
    out: List[Step] = []
    changed = False
    for st in steps:
        if st.kind != "gather" or len(st.axes) < 2:
            out.append(st)
            continue
        # group the axis tuple (major→minor) into consecutive same-tier
        # runs; emit minor-most run first
        runs: List[List[str]] = [[st.axes[0]]]
        for a in st.axes[1:]:
            if axis_tiers.get(a) == axis_tiers.get(runs[-1][-1]):
                runs[-1].append(a)
            else:
                runs.append([a])
        if len(runs) == 1:
            out.append(st)
            continue
        changed = True
        for run in runs[::-1]:
            out.append(Step("gather", dim=st.dim, axes=tuple(run)))
    return out if changed else None


def _naive_steps(src, dst) -> List[Step]:
    """The generic gather/scatter lowering: fully replicate, then slice
    to the destination — what GSPMD's 'full rematerialization' does."""
    steps: List[Step] = []
    for i, axes in enumerate(src):
        if axes:
            steps.append(Step("gather", dim=i, axes=tuple(axes)))
    for j, axes in enumerate(dst):
        if axes:
            steps.append(Step("slice", dim=j, axes=tuple(axes)))
    return steps


# ----------------------------------------------------------------------
# stats (tests + audit introspection)
# ----------------------------------------------------------------------

class ReshardStats:
    """Process-wide reshard accounting, mirrored into the Prometheus
    registry (``ff_reshard_*``). Kept as plain attributes so tests and
    the elastic e2e can assert 'this state went through the planner'."""

    def __init__(self):
        self.lock = threading.Lock()
        self.reset()

    def reset(self):
        # __init__ assigns self.lock before calling reset(), so the
        # lock always exists here — hold it so a concurrent record()
        # never interleaves with a test's reset
        with self.lock:
            self.planned = 0
            self.plan_cache_hits = 0
            self.executed_searched = 0
            self.executed_naive = 0
            self.host_placements = 0
            self.bytes_total = 0.0
            self.last_plans: List[Dict[str, Any]] = []

    def record(self, kind: str, nbytes: float,
               record: Optional[Dict[str, Any]] = None):
        with self.lock:
            if kind == "searched":
                self.executed_searched += 1
            else:
                self.executed_naive += 1
            self.bytes_total += nbytes
            if record is not None:
                self.last_plans.append(record)
                del self.last_plans[:-64]
        REGISTRY.counter(
            "ff_reshard_plans_total",
            "Executed layout-transition lowerings by kind").inc(kind=kind)
        REGISTRY.counter(
            "ff_reshard_bytes_total",
            "Bytes moved through planned layout transitions").inc(
                max(nbytes, 0.0))
        obs_events.counter(f"reshard.{kind}")


STATS = ReshardStats()


# ----------------------------------------------------------------------
# the planner
# ----------------------------------------------------------------------

class ReshardPlanner:
    """Plan + execute layout transitions on one mesh.

    ``cost_model`` is a ``search.costmodel.OpCostModel`` (analytic by
    default; when calibration v2 is enabled the persisted collective
    tables answer first — the planner READS those tables, it never
    writes them, so the ranker-fidelity baseline is untouched).
    """

    def __init__(self, dmesh, cost_model=None,
                 cache_dir: Optional[str] = None, persist: bool = True):
        self.dmesh = dmesh
        self._cm = cost_model
        self._cache_dir = cache_dir or _DEFAULT_DIR
        self._memo: Dict[Tuple, ReshardPlan] = {}
        # persist=False: read the warm disk cache but never write it —
        # the static plan verifier probes seam legality without seeding
        # plans the executor would then count as ITS disk hits, while
        # still reusing already-planned lowerings instead of re-running
        # the candidate search on every verified compile
        self._disk: Optional[Dict[str, Any]] = None
        self._persist = persist
        self.audit_path: Optional[str] = None
        self._audit_records: List[Dict[str, Any]] = []
        # communication–computation overlap (runtime/overlap.py): when
        # resolved on, multi-leg TIER-STAGED plans execute PIPELINED —
        # the tensor splits into chunks on an untouched dim so leg k+1
        # of chunk j runs while leg k of chunk j+1 still occupies the
        # other fabric, instead of the legs running back-to-back.
        # None = resolve from FF_OVERLAP lazily (FFModel.compile sets
        # it from FFConfig.overlap); bit-exact either way — chunking a
        # collective on an untouched dim is pure data movement.
        self.overlap_on: Optional[bool] = None
        self.mesh_key = "x".join(
            f"{a}{s}" for a, s in dmesh.axis_sizes.items())
        # multi-tier meshes key their plans per tier layout: a plan
        # chosen for a flat mesh (or before the hierarchy existed) must
        # not be replayed where tier-staged lowering applies;
        # single-tier meshes keep their warm cache entries verbatim
        tiers = self.axis_tiers
        if tiers:
            self.mesh_key += "|" + ",".join(
                f"{a}={tiers[a]}" for a in sorted(tiers))

    # -- cost model (lazy: most transitions are planned at first trace)
    @property
    def cost_model(self):
        if self._cm is None:
            from ..search.costmodel import OpCostModel
            cm = OpCostModel(self.dmesh.spec, cache_dir=self._cache_dir)
            try:
                from ..search.calibration import (CalibrationTable,
                                                  MeshCalibration)
                import jax
                # attach the persisted tables READ-ONLY: lookups answer
                # from warm entries; misses fall to the analytic model
                # (no microbenchmarks are run from the execution path)
                cm.calib = MeshCalibration(
                    backend=jax.default_backend(),
                    table=CalibrationTable(self._cache_dir))
            except Exception:  # noqa: BLE001 — calibration optional
                pass
            try:
                from .placement import AxisPlacement
                pl = AxisPlacement.from_dmesh(self.dmesh)
                if pl is not None and pl.multi_tier:
                    cm.attach_placement(pl, "hier")
            except Exception:  # noqa: BLE001 — placement optional
                pass
            self._cm = cm
        return self._cm

    @property
    def axis_tiers(self) -> Dict[str, str]:
        """Mesh-axis → tier map for hierarchical step staging; empty on
        single-tier machines and duck-typed meshes without one."""
        try:
            tiers = dict(self.dmesh.axis_tiers)
            return tiers if len(set(tiers.values())) > 1 else {}
        except Exception:  # noqa: BLE001
            return {}

    # -- disk plan cache ------------------------------------------------
    @property
    def _disk_path(self) -> str:
        return os.path.join(self._cache_dir, "reshard_plans.json")

    def _disk_cache(self) -> Dict[str, Any]:
        if self._disk is None:
            try:
                with open(self._disk_path) as f:
                    self._disk = json.load(f)
            except Exception:  # noqa: BLE001
                self._disk = {}
        return self._disk

    def _disk_put(self, key: str, doc: Dict[str, Any]) -> None:
        cache = self._disk_cache()
        cache[key] = doc
        if not self._persist:
            return
        try:
            os.makedirs(self._cache_dir, exist_ok=True)
            tmp = self._disk_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(cache, f)
            os.replace(tmp, self._disk_path)
        except Exception:  # noqa: BLE001 — persistence is best-effort
            pass

    # -------------------------------------------------------------------
    def _divisible(self, norm, shape) -> bool:
        sizes = self.dmesh.axis_sizes
        for d, axes in enumerate(norm):
            deg = 1
            for a in axes:
                if a not in sizes:
                    return False
                deg *= sizes[a]
            if deg > 1 and (d >= len(shape) or shape[d] % deg != 0):
                return False
        return True

    def _score(self, steps: Sequence[Step], shape, itemsize: int,
               src) -> Tuple[float, float]:
        """(est time s, peak per-device transient bytes) of a plan.
        Peak counts both live buffers of the in-flight step — the
        quantity the paper minimizes and the bench leg gates on."""
        sizes = self.dmesh.axis_sizes
        cm = self.cost_model
        global_bytes = float(int(np.prod(shape)) * itemsize) \
            if shape else float(itemsize)
        deg = 1
        for axes in src:
            for a in axes:
                deg *= sizes[a]
        local = global_bytes / max(deg, 1)
        peak, t = local, 0.0

        def step_cost(kind: str, g: int, vol: float, axes) -> float:
            # a step whose axes CROSS tiers executes as one XLA
            # collective whose decomposition we do not control — price
            # it conservatively as a flat ring at the bottleneck tier
            # (the tier-staged candidate, one fabric per step, gets the
            # per-tier pricing and wins whenever hierarchy pays)
            pl = getattr(cm, "placement", None)
            if pl is not None and axes:
                path = pl.path_for_axes(axes)
                if len(path) > 1:
                    from .placement import _ring_tree
                    return _ring_tree(kind, vol, path)[0]
            return cm.reshard_step_cost(kind, g, vol, axes=axes)

        for st in steps:
            g = 1
            for a in st.axes:
                g *= sizes[a]
            if st.kind == "gather":
                out_local = local * g
                t += step_cost("all_gather", g, out_local, st.axes)
            elif st.kind == "alltoall":
                out_local = local
                t += step_cost("all_to_all", g, local * g, st.axes)
            else:
                out_local = local / g
                t += cm.reshard_step_cost("slice", g, local)
            peak = max(peak, local + out_local)
            local = out_local
        return t, peak

    def plan(self, src_spec, dst_spec, shape, itemsize: int = 4
             ) -> ReshardPlan:
        """Choose the lowering for ``src_spec → dst_spec`` on arrays of
        ``shape``: enumerate candidate step orderings, score each for
        time and peak transient memory, pick the fastest whose peak
        does not exceed the naive baseline's. Cached in memory and on
        disk per (mesh, src, dst, itemsize, shape-class)."""
        rank = len(shape)
        src = norm_spec(getattr(src_spec, "spec", src_spec), rank)
        dst = norm_spec(getattr(dst_spec, "spec", dst_spec), rank)
        if src == dst:
            # no transition needed: the planner VERIFIED no data moves
            return ReshardPlan(src, dst, [], kind="noop")
        if not (self._divisible(src, shape) and
                self._divisible(dst, shape)):
            # a layout the mesh cannot tile evenly: leave the value to
            # GSPMD's constraint semantics rather than mis-slicing it.
            # Checked BEFORE the cache: plans are keyed by shape-CLASS
            # (factor-of-2 band), and a cached divisible-shape plan must
            # never be replayed onto a same-band indivisible shape
            return ReshardPlan(src, dst, [], kind="constraint")
        from ..search.calibration import shape_class
        nbytes = int(np.prod(shape)) * itemsize if shape else itemsize
        key = (self.mesh_key, layout_key(src), layout_key(dst),
               itemsize, shape_class(nbytes))
        hit = self._memo.get(key)
        if hit is not None:
            return hit
        plan = self._plan_uncached(src, dst, shape, itemsize, key)
        self._memo[key] = plan
        return plan

    def _plan_uncached(self, src, dst, shape, itemsize, key
                       ) -> ReshardPlan:
        dkey = "|".join(str(k) for k in key)
        doc = self._disk_cache().get(dkey)
        naive = _naive_steps(src, dst)
        naive_t, naive_peak = self._score(naive, shape, itemsize, src)
        if doc is not None:
            obs_events.counter("reshard.plan_cache_hits")
            with STATS.lock:
                STATS.plan_cache_hits += 1
            steps = [Step.from_json(s) for s in doc["steps"]]
            # re-score the cached steps at THIS shape: the cache key is
            # a factor-of-2 shape-class band, so the persisted numbers
            # may belong to a different same-band shape — peak and
            # naive-peak must be a consistent pair at the actual shape
            # or the peak<=naive gate misfires both ways
            t, peak = self._score(steps, shape, itemsize, src)
            return ReshardPlan(src, dst, steps, est_time_s=t,
                               peak_bytes=peak,
                               naive_peak_bytes=naive_peak,
                               kind=doc.get("kind", "searched"))
        with obs_events.span("reshard.plan", src=layout_key(src),
                             dst=layout_key(dst)):
            candidates: List[Tuple[float, float, List[Step], str]] = []
            tiers = self.axis_tiers
            for prio in (("alltoall", "slice", "gather"),
                         ("alltoall", "gather", "slice"),
                         ("gather", "slice", "alltoall")):
                steps = _candidate_steps(src, dst, prio)
                if steps is not None:
                    t, peak = self._score(steps, shape, itemsize, src)
                    candidates.append((t, peak, steps, "searched"))
                    if tiers:
                        # hierarchical variant: tier-crossing gathers
                        # staged per fabric (one portable collective
                        # per tier leg — the executor-side lowering of
                        # the searched reduction trees)
                        staged = _tier_staged(steps, tiers)
                        if staged is not None:
                            t2, p2 = self._score(staged, shape,
                                                 itemsize, src)
                            candidates.append((t2, p2, staged,
                                               "searched"))
            candidates.append((naive_t, naive_peak, naive, "naive"))
            # fastest plan whose peak transient memory never exceeds
            # the naive baseline's (every candidate qualifies by
            # construction, but keep the guard explicit); at equal
            # predicted cost, prefer the plan with the FEWEST
            # tier-crossing steps — an unstaged tier-crossing gather
            # leaves the hierarchical decomposition to XLA, the staged
            # variant pins it (one portable collective per fabric leg)
            def crossing(steps: Sequence[Step]) -> int:
                if not tiers:
                    return 0
                return sum(1 for st in steps
                           if len({tiers.get(a) for a in st.axes}) > 1)

            ok = [c for c in candidates if c[1] <= naive_peak + 1e-9] \
                or candidates
            ok.sort(key=lambda c: (round(c[0], 9), c[1],
                                   crossing(c[2]), len(c[2])))
            t, peak, steps, kind = ok[0]
        plan = ReshardPlan(src, dst, steps, est_time_s=t,
                           peak_bytes=peak, naive_peak_bytes=naive_peak,
                           kind=kind)
        with STATS.lock:
            STATS.planned += 1
        obs_events.counter("reshard.plans_created")
        self._disk_put(dkey, {"steps": [s.to_json() for s in steps],
                              "time_s": t, "peak_bytes": peak,
                              "kind": kind})
        self._audit(plan, shape)
        return plan

    def _audit(self, plan: ReshardPlan, shape) -> None:
        rec = {"src": layout_key(plan.src), "dst": layout_key(plan.dst),
               "shape": list(shape), "steps": plan.describe(),
               "est_time_s": plan.est_time_s,
               "peak_bytes": plan.peak_bytes,
               "naive_peak_bytes": plan.naive_peak_bytes,
               "kind": plan.kind}
        self._audit_records.append(rec)
        del self._audit_records[:-64]
        obs_events.instant("reshard.plan_chosen", **{
            k: v for k, v in rec.items() if k != "shape"})
        if self.audit_path:
            from ..obs.audit import annotate_strategy_audit
            annotate_strategy_audit(
                self.audit_path, {"reshard_plans":
                                  list(self._audit_records)})

    # -------------------------------------------------------------------
    def execute(self, x, plan: ReshardPlan):
        """Run a plan inside the current trace: one ``shard_map`` whose
        in/out specs pin the src/dst layouts and whose body applies the
        explicit collective steps. Differentiable (all steps have exact
        transposes under shard_map)."""
        import jax
        from jax.sharding import NamedSharding
        from jax import shard_map
        mesh = self.dmesh.mesh
        dst_P = _to_partition_spec(plan.dst)
        nbytes = float(getattr(x, "size", 0) or 0) * \
            float(np.dtype(x.dtype).itemsize if hasattr(x, "dtype") else 4)
        if plan.kind in ("constraint", "noop") or not plan.steps:
            # "noop" (planner verified src == dst, nothing moves) counts
            # as searched; "constraint" (mesh can't tile the shape, GSPMD
            # picks the lowering) IS the naive path — account it as such
            STATS.record("naive" if naive_reshard()
                         or plan.kind == "constraint" else "searched",
                         nbytes)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, dst_P))
        src_P = _to_partition_spec(plan.src)
        sizes = self.dmesh.axis_sizes
        steps = list(plan.steps)

        def run_steps(xl):
            for st in steps:
                ax = st.axes if len(st.axes) > 1 else st.axes[0]
                if st.kind == "gather":
                    xl = jax.lax.all_gather(xl, ax, axis=st.dim,
                                            tiled=True)
                elif st.kind == "alltoall":
                    xl = jax.lax.all_to_all(xl, ax, split_axis=st.dim,
                                            concat_axis=st.src_dim,
                                            tiled=True)
                else:
                    idx = 0
                    deg = 1
                    for a in st.axes:
                        idx = idx * sizes[a] + jax.lax.axis_index(a)
                        deg *= sizes[a]
                    blk = xl.shape[st.dim] // deg
                    xl = jax.lax.dynamic_slice_in_dim(
                        xl, idx * blk, blk, st.dim)
            return xl

        pipe = self._pipeline_chunks(plan, tuple(getattr(x, "shape", ())),
                                     nbytes)
        if pipe is None:
            body = run_steps
        else:
            chunk_dim, n_chunks = pipe

            def body(xl):  # noqa: F811 — pipelined variant
                # tier-staged legs pipelined across fabric legs
                # (runtime/overlap.py): chunks are data-independent,
                # so leg k+1 of chunk j overlaps leg k of chunk j+1 on
                # the other fabric. Splitting on an untouched dim
                # commutes with every step — bit-exact with run_steps.
                import jax.numpy as jnp
                parts = jnp.split(xl, n_chunks, axis=chunk_dim)
                return jnp.concatenate([run_steps(p) for p in parts],
                                       axis=chunk_dim)

            from ..obs.metrics_registry import REGISTRY
            REGISTRY.counter(
                "ff_reshard_pipelined_total",
                "Tier-staged reshard plans executed with pipelined "
                "fabric legs").inc()
            obs_events.counter("reshard.pipelined_legs")

        out = shard_map(body, mesh=mesh, in_specs=src_P, out_specs=dst_P,
                        check_vma=False)(x)
        STATS.record("searched", nbytes, record={
            "src": layout_key(plan.src), "dst": layout_key(plan.dst),
            "steps": plan.describe()})
        return out

    def _pipeline_chunks(self, plan: ReshardPlan, shape,
                         nbytes: float) -> Optional[Tuple[int, int]]:
        """(chunk_dim, n_chunks) for pipelined tier-staged execution,
        or None for the serial (default) leg order. Pipelining applies
        only when overlap is on, the plan has >= 2 collective legs on
        >= 2 distinct hardware tiers (the PR 9 tier-staged lowering),
        the payload clears 1 MiB (below that the extra per-leg launch
        latency outweighs the overlap), and some tensor dim is touched
        by NO step and divides into chunks at the shard-local entry
        shape."""
        on = self.overlap_on
        if on is None:
            from ..runtime.overlap import overlap_enabled
            on = overlap_enabled(None)
        if not on or len(plan.steps) < 2 or nbytes < (1 << 20):
            return None
        tiers = self.axis_tiers
        if not tiers:
            return None
        leg_tiers = {tiers.get(a) for st in plan.steps
                     if st.kind != "slice" for a in st.axes}
        if len(leg_tiers) < 2:
            return None
        touched = set()
        for st in plan.steps:
            touched.add(st.dim)
            if st.kind == "alltoall":
                touched.add(st.src_dim)
        for d in range(len(shape)):
            if d in touched:
                continue
            deg = 1
            if d < len(plan.src):
                for a in plan.src[d]:
                    deg *= self.dmesh.axis_sizes.get(a, 1)
            local = shape[d] // max(deg, 1)
            for n in (4, 2):
                if local % n == 0 and local >= n:
                    return d, n
        return None

    def apply(self, x, src_spec, dst_spec):
        """Plan (or load) and execute one transition; the module's
        single entry point for in-graph layout changes. With
        ``FF_NAIVE_RESHARD=1`` this degrades to the bare sharding
        constraint (the pre-planner behavior)."""
        import jax
        from jax.sharding import NamedSharding
        dst_P = _to_partition_spec(
            norm_spec(getattr(dst_spec, "spec", dst_spec),
                      len(x.shape)))
        if naive_reshard():
            nbytes = float(getattr(x, "size", 0) or 0) * \
                float(np.dtype(x.dtype).itemsize
                      if hasattr(x, "dtype") else 4)
            STATS.record("naive", nbytes)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.dmesh.mesh, dst_P))
        itemsize = int(np.dtype(x.dtype).itemsize) \
            if hasattr(x, "dtype") else 4
        plan = self.plan(src_spec, dst_spec, tuple(x.shape), itemsize)
        return self.execute(x, plan)


# ----------------------------------------------------------------------
# executor hook: transition-aware output constraint
# ----------------------------------------------------------------------

def planner_for(strategy) -> ReshardPlanner:
    """The per-strategy planner (created by the executor; built lazily
    here for strategies executed without one, e.g. hand-built tests)."""
    pl = getattr(strategy, "resharder", None)
    if pl is None:
        pl = ReshardPlanner(strategy.dmesh)
        strategy.resharder = pl
    return pl


def tensor_spec(strategy, t):
    """The strategy-assigned PartitionSpec of tensor ``t``: the owning
    layer's output spec, or the graph-input spec (None = unknown /
    unconstrained). The single spec-resolution helper shared by the
    bank-boundary and pipeline-boundary wiring."""
    if t.owner_layer is not None:
        os_ = strategy.ops.get(t.owner_layer.name)
        if os_ is not None and t.owner_idx < len(os_.outputs):
            return os_.outputs[t.owner_idx]
        return None
    return strategy.inputs.get(t.name)


def _input_specs_replicated(strategy, layer) -> bool:
    """True when every input of ``layer`` is unconstrained/replicated
    under ``strategy`` — i.e. the op's output provably carries no
    sharding yet and a sharded output constraint is a genuine
    replicated→sharded transition."""
    for t in layer.inputs:
        spec = tensor_spec(strategy, t)
        if spec is not None and any(norm_spec(spec, len(t.shape))):
            return False
    return True


def constrain_output(o, sharding, strategy, layer):
    """The executor's per-op output constraint. For pure layout ops
    (reshape/transpose/concat/...) whose inputs are replicated and
    whose assigned output spec is sharded, the transition is executed
    EXPLICITLY through the planner (a local slice — no communication)
    instead of a bare ``with_sharding_constraint``: GSPMD's backward
    propagation of a tiled constraint through reshape/concat is the
    documented miscompile the standing alignment failure exercised.
    Everything else keeps the plain constraint (a matching constraint
    on an already-sharded chain is a no-op hint, not a transition)."""
    import jax
    spec = sharding.spec
    rank = len(getattr(o, "shape", ()))
    if naive_reshard() \
            or not any(norm_spec(spec, rank)) \
            or layer.op_type not in LAYOUT_OPS \
            or not _input_specs_replicated(strategy, layer):
        return jax.lax.with_sharding_constraint(o, sharding)
    from jax.sharding import PartitionSpec as P
    return planner_for(strategy).apply(o, P(), spec)


# ----------------------------------------------------------------------
# host→device placement (checkpoint restore / elastic reshard)
# ----------------------------------------------------------------------

def place_host(arr: np.ndarray, sharding) -> Any:
    """Place one host array against a target sharding, shard-by-shard:
    ``jax.make_array_from_callback`` hands each device ONLY its own
    slice, so restoring a sharded leaf never materializes a full
    per-device replica (the memory-peaky part of the old whole-array
    ``device_put`` path). This is the planner's host→device step — the
    route the elastic re-plan's reshard-restored-state takes
    (``resilience/elastic.py`` → ``runtime/checkpoint.py`` → here).
    ``FF_NAIVE_RESHARD=1`` restores the plain ``device_put``."""
    import jax
    nbytes = float(arr.size * arr.itemsize)
    if sharding is None:
        return jax.device_put(arr)
    if getattr(sharding, "is_fully_replicated", False):
        # no per-shard slicing to win: every device needs the whole
        # array either way, and device_put broadcasts one host copy
        if not naive_reshard():
            with STATS.lock:
                STATS.host_placements += 1
        return jax.device_put(arr, sharding)
    if naive_reshard():
        STATS.record("naive", nbytes)
        return jax.device_put(arr, sharding)
    try:
        out = jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    except Exception:  # noqa: BLE001 — odd shardings: fall back
        STATS.record("naive", nbytes)
        return jax.device_put(arr, sharding)
    with STATS.lock:
        STATS.host_placements += 1
    STATS.record("searched", nbytes)
    return out
