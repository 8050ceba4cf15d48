"""Execution cost model: per-op compute costs + inter-op transfer costs
over the TPU machine model.

Analog of the reference's Simulator (``src/runtime/simulator.cc``):
  - ``measure_operator_cost`` (``simulator.cc:537``) ≙
    ``OpCostModel.measure``: jit-compile the op's own ``emit`` at the
    shard-local shape on the real device, warmup + repeat + median — the
    direct analog of ``inner_measure_operator_cost`` (``model.cu:38``) —
    cached in-memory AND on disk by (generation, op params, degrees) like
    the reference's ``hash_to_operator_cost``. ``op_cost`` consults the
    measurement when ``measure_on_device`` is set (search on a real chip)
    and falls back to the analytic roofline (FLOPs on the MXU vs bytes
    over HBM) otherwise — e.g. on the CPU simulation platform.
  - ``estimate_xfer_cost`` ≙ resharding cost between PartitionSpecs:
    collective volume over ICI bandwidth + per-hop latency.
  - weight sync ≙ gradient all-reduce ring cost over the dp axes.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.layer import Layer
from ..dtypes import itemsize
from ..ffconst import OperatorType, PARALLEL_OPS
from ..obs import events as obs_events
from ..ops import get_op_def
from ..parallel.machine import DeviceMesh, MachineSpec
from ..parallel.topology import link_degradation_factor


#: where the measured rows persist unless a caller names a directory
#: (tests/conftest.py points it outside the checkout)
_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".ffcache")


@dataclasses.dataclass
class CostMetrics:
    """Reference ``CostMetrics`` (``simulator.h:54``) parity."""
    forward_time: float = 0.0     # seconds
    backward_time: float = 0.0
    sync_time: float = 0.0
    inputs_memory: int = 0
    outputs_memory: int = 0
    weights_memory: int = 0

    @property
    def total_memory(self) -> int:
        return self.inputs_memory + self.outputs_memory + self.weights_memory


class OpCostModel:
    """Analytic + measured operator costs on one chip."""

    # MXU efficiency defaults by op class (fraction of peak achieved);
    # refined by calibrate() microbenchmarks when a chip is available.
    _DEFAULT_EFF = 0.5

    # ops worth a per-op microbenchmark (compile time ~seconds each);
    # everything cheaper uses the analytic roofline, as fusion makes
    # standalone elementwise timings meaningless under XLA anyway
    _MEASURE_MIN_FLOPS = 1e7

    def __init__(self, spec: MachineSpec, cache_dir: Optional[str] = None):
        self.spec = spec
        self.cache: Dict[Tuple, CostMetrics] = {}
        self.mxu_eff = self._DEFAULT_EFF
        self.overhead_s = 2e-6  # per-op dispatch/fusion overhead inside XLA
        # measured collective constants (calibrate_collectives); None =
        # use the machine-model ICI numbers. On the CPU simulation
        # platform the model's v5e ICI bandwidths overstate one host's
        # memcpy fabric by orders of magnitude — the round-2 root cause
        # of searched strategies losing to DP on DLRM/XDL.
        self.coll_bw: Optional[float] = None
        self.coll_lat: Optional[float] = None
        # segmented-transfer settings for the task simulator (reference
        # EnhancedMachineModel, machine_model.cc: --simulator-segment-size
        # / --simulator-max-num-segments). max_segments 1 = whole-message
        # store-and-forward; >1 lets multi-hop transfers pipeline
        # segment-wise across their route in tasksim.py.
        self.segment_size: int = 16777216
        self.max_segments: int = 1
        # measurement-grounded calibration v2 (search/calibration.py):
        # host dispatch overhead, memory bandwidth, parallel efficiency
        # and per-collective tables measured on the live backend. None =
        # analytic terms only (unchanged legacy behavior).
        self.calib = None
        # hierarchical placement (parallel/placement.py, arXiv
        # 2110.10548): when attached, collectives are priced against
        # the (tier, degree) path their mesh axes span and the cheapest
        # reduction-tree shape is chosen per site. None = flat-mesh
        # pricing (bit-identical legacy behavior); policy "flat" keeps
        # the placement but scores every collective as a flat ring at
        # its bottleneck tier (the searched-vs-flat baseline).
        self.placement = None
        self.placement_policy: Optional[str] = None
        # per-site chosen trees, for the strategy audit record and the
        # adopted strategy's serialized tree shapes (bounded)
        self.algo_choices: Dict[Tuple, Dict[str, Any]] = {}
        self._tree_memo: Dict[Tuple, Any] = {}
        # quantized gradient collectives (ops/quantized_collectives.py,
        # arXiv 2506.17615): when a policy dict {"mode", "wire"} is
        # attached, grad-sync sites are additionally scored with their
        # slow legs narrowed to the wire dtype (int8/fp8, per-chunk
        # scales + error feedback) — per-tensor on flat syncs,
        # per-phase on the reduction trees — and the cheaper side wins
        # per the mode (auto) or the mode's mandate (dcn_only/all).
        # None (default) keeps every prediction bit-identical.
        self.quantization: Optional[Dict[str, str]] = None
        # wire dtype of the most recent weight_sync_cost answer (the
        # audit breakdown records it per grad-sync site — the drift
        # detector attributes quantized rows by it)
        self.last_sync_wire: str = "float32"
        # calibration-row provenance tap (obs/drift.py): when a list is
        # installed here, every pricing call appends WHICH calibration
        # row (or analytic term) produced its answer. Installed only by
        # the audit breakdown path (GraphCostEvaluator.
        # graph_cost_breakdown) — None keeps the search's hot loops at
        # one attribute read per call.
        self.provenance: Optional[List[Dict[str, Any]]] = None
        # overlap-aware scoring (runtime/overlap.py's model half): when
        # set, GraphCostEvaluator prices each gradient-sync site at its
        # EXPOSED cost — max(0, comm − hideable backward compute) under
        # a single-comm-channel queue model — instead of the serial
        # full cost, and records the hidden/exposed split per site.
        # Off (the default) keeps every prediction bit-identical to the
        # serial model. Set by search/optimizer.py from FFConfig.overlap
        # / FF_OVERLAP; the event-driven simulator (tasksim.py
        # overlap_estimate) is the authority this additive split is
        # checked against (bench comm_overlap leg, within 2x).
        self.overlap_mode = False
        # on-device measurement (reference measure_operator_cost analog)
        self.measure_on_device = False
        self.measure_budget_s = 120.0   # total wall budget for microbenches
        self._measure_spent_s = 0.0
        # layer name -> "ExcType: message" of a microbenchmark that
        # raised (that op is priced analytically instead)
        self.measure_failures: Dict[str, str] = {}
        self._unmeasurable: set = set()  # per-process, deliberately not on disk
        self._disk: Optional[Dict[str, Any]] = None
        self._cache_dir = cache_dir or _DEFAULT_DIR

    # ------------------------------------------------------------------
    # disk cache (reference hash_to_operator_cost persisted)
    # ------------------------------------------------------------------
    @property
    def _disk_path(self) -> str:
        return os.path.join(self._cache_dir,
                            f"opcost_{self.spec.generation}.json")

    def _disk_cache(self) -> Dict[str, Any]:
        if self._disk is None:
            try:
                with open(self._disk_path) as f:
                    self._disk = json.load(f)
            except Exception:
                self._disk = {}
        return self._disk

    def _disk_put(self, key: str, value) -> None:
        cache = self._disk_cache()
        cache[key] = value
        try:
            os.makedirs(self._cache_dir, exist_ok=True)
            tmp = self._disk_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(cache, f)
            os.replace(tmp, self._disk_path)
        except Exception:
            pass

    # ------------------------------------------------------------------
    def attach_placement(self, placement, policy: str = "hier") -> None:
        """Attach an :class:`~flexflow_tpu.parallel.placement.
        AxisPlacement`: collective costs become (tier-path, algorithm)-
        aware. ``policy`` is the axis-consumption model — ``"hier"``
        (per-op collectives innermost-first, gradient sync on the
        complement, best tree per site) or ``"flat"`` (flat-ring
        scoring at the bottleneck tier — the baseline the search is
        compared against). Clears every cached cost priced under the
        previous placement."""
        if policy not in ("hier", "flat"):
            raise ValueError(f"unknown placement policy {policy!r}")
        self.placement = placement
        self.placement_policy = policy if placement is not None else None
        self.cache.clear()
        self._tree_memo.clear()
        self.algo_choices.clear()

    def attach_quantization(self, mode: Optional[str],
                            wire: str = "int8") -> None:
        """Attach (or detach, ``mode=None``/"off") the quantized-
        collectives scoring policy. Clears every cached cost priced
        under the previous policy."""
        if mode in (None, "off"):
            self.quantization = None
        else:
            from ..ops.quantized_collectives import QSYNC_MODES
            if mode not in QSYNC_MODES:
                raise ValueError(f"unknown quantization mode {mode!r}")
            self.quantization = {"mode": mode, "wire": wire}
        self.cache.clear()
        self._tree_memo.clear()

    def _quant_overhead_s(self, volume_bytes: float) -> float:
        """In-jit quantize+dequantize cost of one synced tensor: two
        streaming passes over the payload at measured (or datasheet)
        memory bandwidth."""
        mem_bw = self.spec.hbm_bandwidth
        if self.calib is not None and self.calib.mem_bw:
            mem_bw = self.calib.mem_bw
        return 2.0 * volume_bytes / max(mem_bw, 1.0)

    def _flat_wire_sync(self, volume_bytes: float, degree: int,
                        wire: str) -> float:
        """Flat quantized grad-sync candidate: the calibrated wire-
        dtype rows answer first (measured int8/fp8 collectives), else
        the float32 tables itemsize-scaled (the same curve queried at
        the narrow payload's byte volume), else the analytic ring at
        wire bytes — plus the quantize/dequantize overhead."""
        from ..parallel.placement import (bandwidth_multiplier,
                                          wire_byte_scale)
        wb = volume_bytes * wire_byte_scale(wire)
        t = None
        if self.calib is not None:
            t = self.calib.collective_marginal("all_reduce", degree, wb,
                                               dtype=wire)
            if t is None:
                t = self.calib.collective_marginal("all_reduce", degree,
                                                   wb)
        if t is None:
            ici_bw = (self.coll_bw or self.spec.ici_bandwidth) \
                / link_degradation_factor("ici")
            ici_lat = self.coll_lat if self.coll_lat is not None \
                else self.spec.ici_latency_us * 1e-6
            # two wire collectives (reduce leg + gather leg) pay twice
            # the latency rounds of the single fused ring — the
            # conservative side of the comparison
            t = (bandwidth_multiplier("all_reduce", degree)
                 * (degree - 1) / degree * wb / ici_bw
                 + 2 * (degree - 1) * ici_lat)
        return float(t) + self._quant_overhead_s(volume_bytes)

    def quantized_sync_quote(self, volume_bytes: float, degree: int,
                             skeleton: Sequence[Tuple[Tuple[str, ...],
                                                      str]],
                             mode: Optional[str] = None,
                             wire: Optional[str] = None
                             ) -> Optional[Tuple[float, float,
                                                 List[Optional[str]]]]:
        """Score one gradient tensor's sync at full precision vs with
        its legs quantized, over the tier-phase ``skeleton``
        (``[(axes, tier), ...]`` innermost first — what the runtime
        executes). Returns ``(baseline_s, quantized_s, phase_wires)``
        with ``phase_wires[i]`` the wire dtype of phase i (None =
        full-precision); all-None when the mode rejects quantization
        for this tensor. None when no policy applies."""
        q = self.quantization or {}
        mode = mode or q.get("mode")
        wire = wire or q.get("wire") or "int8"
        if not mode or mode == "off" or degree <= 1 or volume_bytes <= 0:
            return None
        saved = self.quantization
        try:
            self.quantization = None
            base = self.weight_sync_cost(volume_bytes, degree)
        finally:
            self.quantization = saved
        tiers = [t for _, t in skeleton] or ["ici"]
        if len(skeleton) <= 1 or self.placement is None:
            # flat sync: both sides answer from the same calibrated
            # curve (the wire side at the narrow payload's byte volume
            # — the itemsize-scaled fallback — or from measured
            # wire-dtype rows when they exist), so the auto comparison
            # is apples-to-apples
            if mode == "dcn_only":
                return None
            qc = self._flat_wire_sync(volume_bytes, degree, wire)
            if mode == "auto" and qc >= base:
                return base, base, [None] * len(tiers)
            return base, qc, [wire] * len(tiers)
        from ..parallel.placement import wire_byte_scale

        def phase_cost(volume, d, tier, w) -> float:
            pl = self.placement
            bw = None
            if pl is not None:
                try:
                    bw = pl.tier_graph.tier(tier).bandwidth
                except Exception:  # noqa: BLE001 — unknown tier
                    bw = None
            if bw is None:
                bw = self.spec.dcn_bandwidth if tier == "dcn" \
                    else (self.coll_bw or self.spec.ici_bandwidth)
            bw /= link_degradation_factor(tier)
            return 2.0 * (d - 1) / d * volume * wire_byte_scale(w) / bw

        def total_cost(phase_wires) -> float:
            # staged tree: inner legs reduce-scatter, so each outer leg
            # carries the tier-reduced volume (the runtime's shape).
            # Per-phase degrees resolve from the skeleton's real axes
            # through the placed axis sizes; a tierless (single-phase)
            # skeleton is the whole degree.
            sizes = dict(getattr(self.placement, "axis_sizes", None)
                         or {})
            resolved = []
            for (axes, _tier) in skeleton:
                d = 1
                for a in axes:
                    d *= int(sizes.get(a, 1)) or 1
                resolved.append(d)
            known = 1
            for d in resolved:
                known *= d
            if known != degree:
                if len(resolved) <= 1:
                    resolved = [degree]
                else:       # fold the unexplained remainder outermost
                    resolved[-1] = max(
                        1, degree * resolved[-1] // max(known, 1))
            cost, v = 0.0, volume_bytes
            for (_axes, tier), d, w in zip(skeleton, resolved,
                                           phase_wires):
                if d <= 1:
                    continue
                cost += phase_cost(v, d, tier, w)
                v = v / d          # staged: outer legs see reduced bytes
            if any(phase_wires):
                cost += self._quant_overhead_s(volume_bytes)
            return cost

        def wires(pred) -> List[Optional[str]]:
            return [wire if pred(t) else None for t in tiers]

        if mode == "dcn_only":
            cands = [wires(lambda t: t == "dcn")]
        elif mode == "all":
            cands = [wires(lambda t: True)]
        else:
            cands = [wires(lambda t: True)]
            if "dcn" in tiers and len(set(tiers)) > 1:
                cands.insert(0, wires(lambda t: t == "dcn"))
        best: Optional[Tuple[float, List[Optional[str]]]] = None
        for pw in cands:
            if not any(pw):
                continue
            c = total_cost(pw)
            if best is None or c < best[0]:
                best = (c, pw)
        if best is None:
            return None
        if mode == "auto" and best[0] >= base:
            return base, base, [None] * len(tiers)
        return base, best[0], best[1]

    def _placed_collective(self, volume_bytes: float, collective: str,
                           degree: int, axes: Optional[Tuple[str, ...]],
                           prefer: str, site: str) -> Optional[float]:
        """Tier-path pricing of one collective under the attached
        placement. Returns None when the path stays within one tier —
        the caller keeps its flat-mesh pricing, so single-tier machines
        are bit-identical to the historical model."""
        pl = self.placement
        if pl is None or degree <= 1 or volume_bytes <= 0:
            return None
        if self.placement_policy == "flat" and axes is None:
            # the legacy greedy allocator consumed axes in declaration
            # order — DCN first — so the flat baseline's per-op groups
            # land outermost and its sync group on what remains
            prefer = "outer" if prefer == "inner" else "inner"
        path = pl.path_for_axes(axes) if axes \
            else pl.path_for_degree(degree, prefer=prefer)
        if not path:
            return None
        if len(path) == 1 and \
                path[0][0].name == pl.tier_graph.innermost().name:
            # confined to the innermost fabric: the legacy (flat-mesh)
            # pricing IS that tier's pricing — keep it bit-identical,
            # calibrated fast paths included
            return None
        from ..parallel.placement import (_ring_tree, TreeChoice,
                                          choose_reduction_tree,
                                          tree_bandwidth_cost)
        # memo key carries the EXACT volume: a shape-class bucket here
        # made cost non-monotonic in volume (same-band payloads up to
        # ~2x apart returned the first-seen absolute cost)
        q = self.quantization
        memo_key = (site, collective, degree,
                    tuple((t.name, d) for t, d in path),
                    int(volume_bytes), self.placement_policy,
                    (q["mode"], q["wire"]) if q else None)
        choice = self._tree_memo.get(memo_key)
        if choice is None:
            if self.placement_policy == "flat":
                cost, phases = _ring_tree(collective, volume_bytes, path)
                choice = TreeChoice(algo="ring", phases=phases,
                                    cost_s=cost, flat_cost_s=cost)
            else:
                choice = choose_reduction_tree(self, collective,
                                               volume_bytes, path)
            if choice is None:
                return None
            if site == "grad_sync":
                # MARGINAL (bandwidth-only) pricing, the placed analog
                # of collective_marginal: XLA's all-reduce combiner
                # coalesces per-layer gradient reductions, so the
                # per-leg latency rounds are paid once per step, not
                # once per layer — charging them per op inverted the
                # searched-vs-DP ranking on dense tower models (see
                # weight_sync_cost). Applied to BOTH policies so the
                # searched-vs-flat audit ratio stays apples-to-apples.
                choice = TreeChoice(
                    algo=choice.algo, phases=choice.phases,
                    cost_s=tree_bandwidth_cost(choice.phases,
                                               pl.tier_graph),
                    flat_cost_s=choice.flat_cost_s)
                qchoice = self._quantize_tree(choice, pl.tier_graph,
                                              volume_bytes)
                if qchoice is not None:
                    choice = qchoice
            if len(self._tree_memo) > 4096:
                self._tree_memo.clear()
            self._tree_memo[memo_key] = choice
            self._record_choice(site, collective, degree, path, choice,
                                volume_bytes)
        if site == "grad_sync":
            self.last_sync_wire = next(
                (p.wire for p in choice.phases if p.wire), "float32")
        if self.provenance is not None:
            # tier-path pricing provenance (best effort): the
            # bottleneck (outermost) tier's row is the one a drift on
            # this entry should re-measure
            tier = path[-1][0].name
            key = self.calib.row_key(collective, degree, volume_bytes,
                                     tier=tier) \
                if self.calib is not None else None
            self._prov("sync" if site == "grad_sync" else "xfer",
                       f"coll_{collective}@{tier}", key, tier)
        return float(choice.cost_s)

    def _quantize_tree(self, choice, tier_graph, volume_bytes):
        """Per-PHASE precision choice on a grad-sync reduction tree
        (ops/quantized_collectives.py): re-price the chosen tree with
        some legs' wire dtype narrowed — the DCN legs only (dcn_only,
        and the auto candidate that keeps ICI full-precision) or every
        leg (all) — through the same bandwidth-marginal algebra
        (``tree_bandwidth_cost`` scales each leg by its wire's byte
        ratio), plus the quantize/dequantize overhead. Returns the
        quantized TreeChoice when the policy adopts it, else None."""
        q = self.quantization
        if q is None or not choice.phases:
            return None
        from ..parallel.placement import Phase, TreeChoice, \
            tree_bandwidth_cost
        wire, mode = q["wire"], q["mode"]

        def variant(pred):
            return [Phase(p.collective, p.tier, p.degree,
                          p.volume_bytes,
                          wire=wire if pred(p.tier) else None)
                    for p in choice.phases]

        cands = []
        if mode in ("dcn_only", "auto"):
            ph = variant(lambda t: t == "dcn")
            if any(p.wire for p in ph):
                cands.append(ph)
        if mode in ("all", "auto"):
            cands.append(variant(lambda t: True))
        best = None
        for ph in cands:
            if not any(p.wire for p in ph):
                continue
            cost = tree_bandwidth_cost(ph, tier_graph) \
                + self._quant_overhead_s(volume_bytes)
            if best is None or cost < best[0]:
                best = (cost, ph)
        if best is None:
            return None
        if mode == "auto" and best[0] >= choice.cost_s:
            return None
        return TreeChoice(algo=choice.algo, phases=best[1],
                          cost_s=best[0],
                          flat_cost_s=choice.flat_cost_s)

    def _record_choice(self, site, collective, degree, path, choice,
                       volume_bytes) -> None:
        if self.placement_policy == "hier":
            # only genuine selections count: the flat-policy baseline
            # re-pricing (searched-vs-flat audit) must not inflate the
            # algorithm counters with phantom ring "choices"
            from ..obs.metrics_registry import REGISTRY
            REGISTRY.counter(
                "ff_collective_algo_total",
                "Reduction-tree algorithms chosen by the "
                "placement-aware cost model").inc(algo=choice.algo)
            obs_events.counter(f"placement.algo_{choice.algo}")
        key = (site, collective, degree,
               tuple((t.name, d) for t, d in path))
        if len(self.algo_choices) > 512:
            self.algo_choices.clear()
        self.algo_choices[key] = {
            "site": site, "collective": collective, "degree": degree,
            "tier_path": [[t.name, d] for t, d in path],
            "volume_bytes": float(volume_bytes),
            **choice.to_json()}

    def _prov(self, term: str, table: Optional[str],
              key: Optional[str] = None, tier: Optional[str] = None
              ) -> None:
        """Record one provenance row when the tap is installed (audit
        breakdowns only): ``term`` is the audit-entry component the
        answer lands in ("compute" | "xfer" | "sync"), ``table`` the
        calibration table family, ``key`` the exact row."""
        p = self.provenance
        if p is not None:
            p.append({"term": term, "table": table, "key": key,
                      "tier": tier})

    # ------------------------------------------------------------------
    def attach_calibration(self, calib) -> None:
        """Attach a ``calibration.MeshCalibration``: measured host
        dispatch overhead + memory bandwidth + parallel efficiency enter
        ``op_cost`` and the persisted collective tables take precedence
        in ``xfer_cost``. Invalidates the in-memory op cache — costs
        priced under the old terms must not survive."""
        self.calib = calib
        self.cache.clear()

    # ------------------------------------------------------------------
    def calibrate(self):
        """Measure real matmul throughput on the local device to set the
        efficiency factor (one-time, <1s). The timed call ends in a
        device-to-host fetch of its result. A device that cannot run a
        2048^2 matmul cannot train either, so a failure propagates."""
        import jax
        import jax.numpy as jnp
        n = 2048
        reps = 8
        a = jnp.ones((n, n), jnp.bfloat16)

        def chain(x):
            for _ in range(reps):
                x = x @ x
                x = x * jnp.bfloat16(1e-3)
            return jnp.sum(x.astype(jnp.float32))

        f = jax.jit(chain)
        float(np.asarray(f(a)))  # compile + sync
        t0 = time.perf_counter()
        float(np.asarray(f(a)))
        dt = (time.perf_counter() - t0) / reps
        achieved = 2.0 * n ** 3 / dt
        self.mxu_eff = min(1.0, max(0.05, achieved / self.spec.peak_flops))

    # ------------------------------------------------------------------
    def calibrate_collectives(self, dmesh: "DeviceMesh") -> None:
        """Fit effective all-reduce bandwidth + latency by timing a real
        ring all-reduce at two sizes on the live mesh (same pattern as
        ``calibrate()`` for matmuls; the reference trusts per-link
        constants from its machine model, ``machine_model.cc``). The fit
        t(s) = 2(n-1)/n * s/bw + (n-1)*lat replaces the machine-model
        ICI constants in ``xfer_cost`` — essential on the CPU simulation
        platform, where the v5e constants mispredict collectives badly.
        Disk-cached per (backend, mesh shape, slice structure): a fit
        from one mesh topology must not be reused for a differently
        shaped or multi-slice mesh of the same device count, where
        effective all-reduce bandwidth differs. A mesh that cannot run
        an all-reduce cannot train data-parallel either, so a failure
        propagates."""
        import jax
        n = dmesh.num_devices
        if n <= 1:
            return
        shape = "x".join(f"{a}{s}"
                         for a, s in dmesh.axis_sizes.items())
        slices = getattr(getattr(dmesh, "spec", None), "num_slices", 1)
        key = f"coll_{jax.default_backend()}_{n}_{shape}_s{slices}"
        cached = self._disk_cache().get(key)
        if cached:
            self.coll_bw, self.coll_lat = cached
            return
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = dmesh.mesh
        axes = tuple(mesh.axis_names)

        def bench(nbytes: int) -> float:
            m = max(nbytes // 4, 1024)
            x = jnp.ones((m,), jnp.float32)

            @jax.jit
            def f(x):
                return shard_map(
                    lambda xl: jax.lax.psum(xl, axes), mesh=mesh,
                    in_specs=P(None), out_specs=P(None))(x)

            float(np.asarray(f(x)[0]))  # compile + sync
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                float(np.asarray(f(x)[0]))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        s1, s2 = 1 << 20, 16 << 20
        t1, t2 = bench(s1), bench(s2)
        a = 2.0 * (n - 1) / n
        if t2 > t1 > 0:
            bw = a * (s2 - s1) / (t2 - t1)
            lat = max((t1 - a * s1 / bw) / (n - 1), 1e-9)
        else:  # noisy fit: bandwidth-only estimate from the big size
            bw = a * s2 / max(t2, 1e-9)
            lat = 1e-9
        self.coll_bw = float(min(max(bw, 1e7), 1e13))
        self.coll_lat = float(min(lat, 1e-2))
        self._disk_put(key, [self.coll_bw, self.coll_lat])

    # ------------------------------------------------------------------
    # on-device per-op measurement (simulator.cc:537 / model.cu:38 analog)
    # ------------------------------------------------------------------
    @staticmethod
    def _local_shape(shape: Sequence[int],
                     degrees: Dict[int, int]) -> Tuple[int, ...]:
        out = list(shape)
        for d, deg in degrees.items():
            if 0 <= d < len(out) and deg > 1 and out[d] % deg == 0:
                out[d] = out[d] // deg
        return tuple(out)

    def _make_arg(self, shape, dtype, rng: np.random.Generator,
                  int_high: int):
        import jax.numpy as jnp
        from ..dtypes import to_jnp
        jdt = to_jnp(dtype)
        if np.issubdtype(np.dtype(jdt if jdt != jnp.bfloat16 else np.float32),
                         np.integer):
            return jnp.asarray(
                rng.integers(0, max(int_high, 2), size=shape), jdt)
        return jnp.asarray(rng.standard_normal(shape) * 0.02, jdt)

    def measure(self, layer: Layer, shard_degrees: Dict[int, int],
                weight_shard_degree: int = 1, warmup: int = 2,
                repeats: int = 5) -> Optional[CostMetrics]:
        """Microbenchmark one op's fwd and fwd+bwd at shard-local shape on
        the local device (jit the op's own ``emit``; warmup + repeat +
        median; device-to-host fetch as the sync barrier). Returns None
        when the op cannot be measured standalone — the caller falls
        back to the analytic roofline, and ``measure_failures`` keeps
        the reason per op so the fallback is never silent."""
        import jax
        import jax.numpy as jnp
        from ..dtypes import to_jnp
        from ..ops import EmitCtx

        op = get_op_def(layer.op_type)
        out_shape = layer.outputs[0].shape if layer.outputs else ()
        out_rank = len(out_shape)
        # A degree on the LAST output dim is feature/head sharding: it is
        # realized by sharding the weight's output dim, NOT by shrinking
        # the op input (column-parallel linear/attention). Degrees on
        # earlier dims (batch/spatial) shrink the activations.
        act_degrees = {d: g for d, g in shard_degrees.items()
                       if d < out_rank - 1}
        eff_wdeg = weight_shard_degree * shard_degrees.get(out_rank - 1, 1)
        rng = np.random.default_rng(0)
        int_high = int(layer.params.get(
            "num_entries", layer.params.get("vocab_size", 100)))
        ins = []
        for t in layer.inputs:
            ls = self._local_shape(t.shape, act_degrees) \
                if len(t.shape) == len(out_shape) else t.shape
            ins.append(self._make_arg(ls, t.dtype, rng, int_high))
        w: Dict[str, Any] = {}
        # the dim a weight shards on: its output features (last dim) —
        # except attention, which shards its HEADS (wq/wk/wv (e, h, d)
        # on h, their biases and wo (h, d, e) on h; bo stays whole).
        # Halving d instead gave wq 32 and wo 64 of it, and every
        # sharded-attention microbenchmark died in its einsum.
        from ..executor import _TP_WEIGHT_DIMS
        head_dim = _TP_WEIGHT_DIMS["attn"] \
            if layer.op_type == OperatorType.OP_MULTIHEAD_ATTENTION else {}
        for spec in (layer.weights or op.weights(
                layer.params, [t.shape for t in layer.inputs],
                [t.dtype for t in layer.inputs])):
            ws = list(spec.shape)
            d = head_dim.get(spec.name, len(ws) - 1)
            if eff_wdeg > 1 and ws and d is not None \
                    and ws[d] % eff_wdeg == 0:
                ws[d] //= eff_wdeg
            w[spec.name] = self._make_arg(tuple(ws), spec.dtype, rng, 2)
        state = {}
        state_spec = getattr(op, "state_spec", None)
        if state_spec is not None:
            ss = state_spec(layer.params, [t.shape for t in layer.inputs],
                            [t.dtype for t in layer.inputs]) or {}
            for sname, (sshape, sdt) in ss.items():
                init = jnp.ones if sname == "var" else jnp.zeros
                state[sname] = init(sshape, to_jnp(sdt))

        def make_ctx():
            return EmitCtx(training=True,
                           rngs={layer.name: jax.random.key(0)},
                           state={layer.name: state})

        def fwd(ins_, w_):
            outs = op.emit(layer.params, list(ins_), w_, make_ctx(),
                           layer.name)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

        float_ins = [i for i, a in enumerate(ins)
                     if jnp.issubdtype(a.dtype, jnp.floating)]

        def fwdbwd(ins_, w_):
            def loss(w__, fins):
                full = list(ins_)
                for i, a in zip(float_ins, fins):
                    full[i] = a
                return fwd(full, w__)
            args = (w_, [ins_[i] for i in float_ins])
            g = jax.grad(loss, argnums=(0, 1))(*args)
            return jax.tree_util.tree_reduce(
                lambda acc, x: acc + jnp.sum(x.astype(jnp.float32)), g, 0.0)

        def timed(fn):
            f = jax.jit(fn)
            for _ in range(warmup):
                float(np.asarray(f(ins, w)))  # fetch = sync barrier
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                float(np.asarray(f(ins, w)))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        t_all = time.perf_counter()
        try:
            fwd_t = timed(fwd)
            tot_t = timed(fwdbwd) if (float_ins or w) else fwd_t
            return CostMetrics(forward_time=fwd_t,
                               backward_time=max(tot_t - fwd_t, 0.0))
        except Exception as e:  # noqa: BLE001 — priced analytically
            self.measure_failures[layer.name] = \
                f"{type(e).__name__}: {e}"[:300]
            obs_events.counter("costmodel.measure_failures")
            return None
        finally:
            # real elapsed time, success or failure: a 60s failed
            # compile must burn 60s of budget, not a token 1s
            self._measure_spent_s += time.perf_counter() - t_all

    def _measured_cost(self, layer: Layer, shard_degrees: Dict[int, int],
                       weight_shard_degree: int,
                       key: Tuple) -> Optional[CostMetrics]:
        """Disk-cached measurement; None = not measurable / over budget."""
        dkey = repr(key)
        cached = self._disk_cache().get(dkey)
        if cached is not None:
            obs_events.counter("costmodel.measure_cache_hits")
            return CostMetrics(forward_time=cached[0],
                               backward_time=cached[1])
        if key in self._unmeasurable:
            return None
        if self._measure_spent_s >= self.measure_budget_s:
            obs_events.counter("costmodel.measure_over_budget")
            return None
        obs_events.counter("costmodel.measure_cache_misses")
        with obs_events.span("costmodel.measure", op=layer.name):
            cm = self.measure(layer, shard_degrees, weight_shard_degree)
        if cm is None:
            # in-memory only: a failure may be transient (device busy,
            # flaky compile) and must not poison future processes
            self._unmeasurable.add(key)
            return None
        self._disk_put(dkey, [cm.forward_time, cm.backward_time])
        return cm

    # ------------------------------------------------------------------
    def op_cost(self, layer: Layer, shard_degrees: Dict[int, int],
                weight_shard_degree: int = 1) -> CostMetrics:
        """Cost of one op with its output dims partitioned by
        ``shard_degrees`` (dim -> degree). Compute scales ~1/prod(degrees);
        memory likewise."""
        key = (layer.param_key(), tuple(sorted(shard_degrees.items())),
               weight_shard_degree)
        obs_events.counter("costmodel.queries")
        hit = self.cache.get(key)
        if hit is not None:
            obs_events.counter("costmodel.cache_hits")
            if self.provenance is not None:
                self._op_prov(key)
            return hit
        op = get_op_def(layer.op_type)
        in_shapes = [t.shape for t in layer.inputs]
        out_shapes = [t.shape for t in layer.outputs]
        total_deg = 1
        for d in shard_degrees.values():
            total_deg *= max(d, 1)
        flops = op.flops(layer.params, in_shapes, out_shapes) / total_deg
        in_bytes = sum(int(np.prod(t.shape)) * itemsize(t.dtype)
                       for t in layer.inputs) // total_deg
        out_bytes = sum(int(np.prod(t.shape)) * itemsize(t.dtype)
                        for t in layer.outputs) // total_deg
        w_bytes = sum(int(np.prod(w.shape)) * itemsize(w.dtype)
                      for w in layer.weights) // max(weight_shard_degree, 1)
        bytes_moved = in_bytes + out_bytes + w_bytes
        own = op.bytes_moved(layer.params, in_shapes, out_shapes)
        if own is not None:
            bytes_moved = own / total_deg + w_bytes
        t_compute = flops / (self.spec.peak_flops * self.mxu_eff)
        # calibration v2: measured memory bandwidth replaces the
        # datasheet HBM constant; measured host dispatch overhead
        # replaces the fixed 2us guess; measured parallel efficiency
        # stretches per-shard time when concurrent shards oversubscribe
        # the host (N virtual devices on C < N cores) — the host terms
        # the r05 fidelity study showed the blind model lacks
        mem_bw = self.spec.hbm_bandwidth
        dispatch = self.overhead_s
        par_eff = 1.0
        if self.calib is not None:
            if self.calib.mem_bw:
                mem_bw = self.calib.mem_bw
            if self.calib.dispatch_s:
                dispatch = self.calib.dispatch_s
            # SPMD executes EVERY op on every device simultaneously —
            # replicated ops run N full copies, sharded ops N shards —
            # so the whole mesh's concurrency applies regardless of the
            # op's own shard degrees (a replicated op escaping the
            # stretch would under-price replication vs sharding)
            par_eff = self.calib.efficiency(max(self.spec.num_devices, 1))
        t_mem = bytes_moved / mem_bw
        fwd = max(t_compute, t_mem) / max(par_eff, 1e-6) + dispatch
        bwd = fwd * op.backward_flops_factor() \
            if layer.op_type != OperatorType.OP_INPUT else 0.0
        if (self.measure_on_device and flops >= self._MEASURE_MIN_FLOPS
                and layer.op_type not in PARALLEL_OPS
                and layer.op_type != OperatorType.OP_INPUT):
            mm = self._measured_cost(layer, shard_degrees,
                                     weight_shard_degree,
                                     (self.spec.generation,) + key)
            if mm is not None:
                fwd, bwd = mm.forward_time, mm.backward_time
        cm = CostMetrics(forward_time=fwd, backward_time=bwd,
                         inputs_memory=in_bytes, outputs_memory=out_bytes,
                         weights_memory=w_bytes)
        self.cache[key] = cm
        if self.provenance is not None:
            self._op_prov(key)
        return cm

    def _op_prov(self, key: Tuple) -> None:
        """Compute-term provenance for one ``op_cost`` answer: the
        on-device measured row when one exists, else the calibrated
        host terms (membw/dispatch/parallel-eff — re-measuring those
        three is what fixes a drifting compute prediction), else the
        bare analytic roofline."""
        from .calibration import CalibrationTable
        if self.measure_on_device:
            dkey = repr((self.spec.generation,) + key)
            if self._disk_cache().get(dkey) is not None:
                self._prov("compute", "opcost", dkey)
                return
        if self.calib is not None:
            b = self.calib.backend
            self._prov("compute", "host_membw",
                       CalibrationTable.key(b, "host_membw"))
            self._prov("compute", "host_dispatch",
                       CalibrationTable.key(b, "host_dispatch"))
            if self.calib.parallel_eff:
                n = max(self.spec.num_devices, 1)
                self._prov("compute", "parallel_eff",
                           CalibrationTable.key(b, "parallel_eff", "-",
                                                0, n))
        else:
            self._prov("compute", None)

    # ------------------------------------------------------------------
    def xfer_cost(self, volume_bytes: float, collective: str,
                  degree: int,
                  axes: Optional[Tuple[str, ...]] = None) -> float:
        """Collective time (ring algorithms): all-gather/reduce-scatter
        move (d-1)/d of the volume; all-reduce 2(d-1)/d; all-to-all
        (d-1)/d with per-hop latency.

        Hierarchical placement (``attach_placement``): when the
        collective's mesh axes (``axes``, or the placement policy's
        axis consumption for a bare degree) span more than one hardware
        tier, the cost is the cheapest reduction-tree shape over that
        (tier, degree) path — ring vs recursive halving vs two/three-
        phase hierarchical trees (``parallel/placement.py``,
        arXiv 2110.10548) — and the choice is recorded for the audit
        record. Single-tier paths (and no placement) keep the exact
        historical pricing below.

        Multi-slice machines without a placement: a collective whose
        degree exceeds ``devices_per_slice`` necessarily crosses DCN;
        its cost is the standard hierarchical decomposition —
        intra-slice leg over ICI plus an inter-slice leg on the
        slice-reduced volume over DCN (reference analog: per-link-type
        simulation in ``src/runtime/network.cc`` /
        ``simulator.h:381-499``).

        Calibration v2: a persisted measured table for this
        (backend, collective, degree) answers first — real XLA
        collective timings at import-time shapes interpolated across
        shape classes; degrees never measured fall through to the
        fitted/analytic ring model."""
        obs_events.counter("costmodel.xfer_queries")
        placed = self._placed_collective(volume_bytes, collective,
                                         degree, axes, "inner",
                                         "op_collective")
        if placed is not None:
            floor = (self.calib.dispatch_s or 0.0) \
                if self.calib is not None else 0.0
            return max(floor, placed)
        floor = 0.0
        if self.calib is not None:
            kind = "all_to_all" if collective == "permute" else collective
            t = self.calib.collective_time(kind, degree, volume_bytes)
            if t is not None:
                if self.provenance is not None:
                    self._prov("xfer", f"coll_{kind}",
                               self.calib.row_key(kind, degree,
                                                  volume_bytes))
                return float(t)
            # even off-table, no collective is cheaper than one measured
            # host dispatch — the floor the host-blind model lacked
            floor = self.calib.dispatch_s or 0.0
        if self.provenance is not None and degree > 1 \
                and volume_bytes > 0:
            self._prov("xfer", None)     # analytic ring model
        ici_bw = (self.coll_bw or self.spec.ici_bandwidth) \
            / link_degradation_factor("ici")
        ici_lat = self.coll_lat if self.coll_lat is not None \
            else self.spec.ici_latency_us * 1e-6
        per_slice = self.spec.devices_per_slice
        if self.spec.num_slices > 1 and degree > per_slice:
            d_in = math.gcd(degree, per_slice) or 1
            d_out = degree // d_in
            t = (self._ring_cost(volume_bytes, collective, d_in,
                                 ici_bw, ici_lat)
                 + self._ring_cost(volume_bytes / max(d_in, 1),
                                   collective, d_out,
                                   self.spec.dcn_bandwidth
                                   / link_degradation_factor("dcn"),
                                   self.spec.dcn_latency_us * 1e-6))
        else:
            t = self._ring_cost(volume_bytes, collective, degree,
                                ici_bw, ici_lat)
        # zero-cost (elided) collectives stay free; everything real is
        # floored at one measured host dispatch
        return max(floor, t) if t > 0 else t

    @staticmethod
    def _ring_cost(volume_bytes: float, collective: str, degree: int,
                   bw: float, lat: float) -> float:
        if degree <= 1 or volume_bytes <= 0:
            return 0.0
        from ..parallel.placement import bandwidth_multiplier
        frac = (degree - 1) / degree
        mult = bandwidth_multiplier(collective, degree)
        return mult * frac * volume_bytes / bw + (degree - 1) * lat

    def reshard_step_cost(self, kind: str, degree: int,
                          volume_bytes: float,
                          axes: Optional[Tuple[str, ...]] = None
                          ) -> float:
        """Cost of ONE step of a reshard lowering plan
        (``parallel/reshard.py``): ``all_gather`` / ``all_to_all`` price
        through ``xfer_cost`` — the calibrated collective tables answer
        first, and with a placement attached the step's actual mesh
        ``axes`` select its tier path — while ``slice`` is a local block
        copy (no traffic), priced at measured memory bandwidth plus one
        dispatch."""
        if degree <= 1 or volume_bytes <= 0:
            return 0.0
        if kind == "slice":
            mem_bw = self.spec.hbm_bandwidth
            dispatch = self.overhead_s
            if self.calib is not None:
                if self.calib.mem_bw:
                    mem_bw = self.calib.mem_bw
                if self.calib.dispatch_s:
                    dispatch = self.calib.dispatch_s
            return volume_bytes / max(mem_bw, 1.0) + dispatch
        return self.xfer_cost(volume_bytes, kind, degree, axes=axes)

    def resharding_cost(self, tensor_bytes: float,
                        src_degrees: Dict[int, int],
                        dst_degrees: Dict[int, int]) -> float:
        """Cost of moving a tensor between two dim->degree layouts
        (reference ``estimate_xfer_cost`` / Repartition special case)."""
        if src_degrees == dst_degrees:
            return 0.0
        src_total = int(np.prod(list(src_degrees.values()))) \
            if src_degrees else 1
        dst_total = int(np.prod(list(dst_degrees.values()))) \
            if dst_degrees else 1
        if src_total == 1 and dst_total > 1:
            return 0.0  # slicing a replicated tensor is local
        if dst_total == 1:
            return self.xfer_cost(tensor_bytes, "all_gather", src_total)
        same_dims = set(src_degrees) == set(dst_degrees)
        if same_dims:
            return self.xfer_cost(tensor_bytes, "permute",
                                  max(src_total, dst_total))
        return self.xfer_cost(tensor_bytes, "all_to_all",
                              max(src_total, dst_total))

    def weight_sync_cost(self, weight_bytes: float, dp_degree: int,
                         axes: Optional[Tuple[str, ...]] = None) -> float:
        """Per-step gradient all-reduce (reference NCCL optimizer path).

        Hierarchical placement: the data-parallel group lives on the
        axes the per-op groups did NOT consume — outermost tiers
        included — so a tier-crossing sync is priced as the best
        reduction tree over its path (e.g. intra-slice reduce-scatter →
        inter-slice all-reduce over hosts → intra-slice all-gather)
        instead of one flat DCN-bottlenecked ring.

        Calibrated (single-tier): priced at the measured curve's
        MARGINAL (per-byte) cost — XLA's all-reduce combiner coalesces
        per-layer gradient reductions into a few large collectives, so
        the fixed dispatch floor is paid once per step, not once per op
        (calibration.MeshCalibration.collective_marginal)."""
        self.last_sync_wire = "float32"
        placed = self._placed_collective(weight_bytes, "all_reduce",
                                         dp_degree, axes, "outer",
                                         "grad_sync")
        if placed is not None:
            return placed
        t = None
        if self.calib is not None and dp_degree > 1 and weight_bytes > 0:
            t = self.calib.collective_marginal("all_reduce", dp_degree,
                                               weight_bytes)
            if t is not None:
                t = float(t)
        if t is None:
            n0 = len(self.provenance) if self.provenance is not None \
                else 0
            t = self.xfer_cost(weight_bytes, "all_reduce", dp_degree)
            if self.provenance is not None:
                # the fallthrough priced through xfer_cost, but this IS
                # the gradient sync — drift diffs it under "sync"
                for row in self.provenance[n0:]:
                    row["term"] = "sync"
        elif self.provenance is not None:
            self._prov("sync", "coll_all_reduce",
                       self.calib.row_key("all_reduce", dp_degree,
                                          weight_bytes))
        # quantized flat candidate (ops/quantized_collectives.py): the
        # per-TENSOR precision choice — int8/fp8 wire payload at 1/4 of
        # the bytes, error feedback carried as runtime state. "auto"
        # takes it only when the scaled curve predicts a win; "all"
        # mandates it. (dcn_only is a tree-leg policy — the flat path
        # has no DCN leg to narrow.)
        q = self.quantization
        if q is not None and q["mode"] in ("auto", "all") \
                and dp_degree > 1 and weight_bytes > 0:
            qc = self._flat_wire_sync(weight_bytes, dp_degree,
                                      q["wire"])
            if q["mode"] == "all" or qc < t:
                self.last_sync_wire = q["wire"]
                if self.provenance is not None:
                    from ..parallel.placement import wire_byte_scale
                    self._prov("sync", "coll_all_reduce",
                               self.calib.row_key(
                                   "all_reduce", dp_degree,
                                   weight_bytes
                                   * wire_byte_scale(q["wire"]))
                               if self.calib is not None else None,
                               None)
                    self.provenance[-1]["wire"] = q["wire"]
                return qc
        return t

    # ------------------------------------------------------------------
    # serving objective (search/serving_plan.py)
    # ------------------------------------------------------------------
    def decode_collective_cost(self, volume_bytes: float,
                               collective: str, degree: int,
                               axes: Optional[Tuple[str, ...]] = None
                               ) -> float:
        """Latency-side price of ONE decode-step collective.

        Decode-step payloads are tiny ((bucket × hidden) activations at
        seq-len 1) and fire once per generated token — XLA cannot
        coalesce them across tokens the way the gradient-sync combiner
        batches per-layer reductions, so the per-dispatch floor and
        per-hop latency terms dominate. Routes through ``xfer_cost``
        (calibrated small-message table rows, placement/tree selection,
        dispatch floor) — deliberately NOT the bandwidth-marginal
        ``weight_sync_cost``/``collective_marginal`` path, which prices
        exactly the coalescing decode does not get."""
        return self.xfer_cost(volume_bytes, collective, degree,
                              axes=axes)

    def kv_read_time(self, kv_bytes: float) -> float:
        """HBM time to stream a resident KV cache once — the per-step
        memory floor of autoregressive decode (every step reads the
        full local cache). Uses the calibrated memory bandwidth when a
        calibration is attached."""
        if kv_bytes <= 0:
            return 0.0
        mem_bw = self.spec.hbm_bandwidth
        if self.calib is not None and self.calib.mem_bw:
            mem_bw = self.calib.mem_bw
        return kv_bytes / max(mem_bw, 1.0)
