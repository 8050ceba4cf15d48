"""Static plan verifier: prove a searched strategy executable, pre-device.

FlexFlow's simulator *scores* strategies but never proves them runnable —
this repo learned that twice (PR 6's GSPMD 4x-values and NaN-transition
miscompiles, both shipped by a search that was happy with the plan).
Following the legality conditions of portable-collective redistribution
(PAPERS.md, arXiv 2112.01075), this module checks a (strategy, layers,
machine) triple statically, at compile time, before a device ever runs
a step:

  1. **op-shard** — every op output / weight / graph-input
     PartitionSpec is mesh-axis sound (axes exist, no axis reused
     within a spec, spec rank fits the tensor rank) and every sharded
     dim is divisible by its axes' product (an indivisible shard is
     exactly the layout GSPMD falls back to generic padding/resharding
     on — the miscompile class the planner exists to bypass);
  2. **seam** — every layout seam lowers to a legal
     :class:`~flexflow_tpu.parallel.reshard.ReshardPlanner` plan:
     layout-op output constraints, bank stack/rejoin boundaries,
     pipeline-region entry/exit, and checkpoint-restore placement
     (``reshard.place_host``). A seam whose plan comes back
     ``kind="constraint"`` would fall back to GSPMD's generic
     resharding at runtime — flagged as an error with the op/seam
     attributed;
  3. **memory** — a conservative static per-device peak-memory envelope
     (params + grads + optimizer slots + peak activation pair + the
     largest planned reshard transient) against the machine model's HBM
     (or ``--device-mem-mb``);
  4. **collective-order** — SPMD deadlock freedom: all ranks must issue
     the same collective sequence. Full-mesh constraints and planned
     shard_map seams are order-consistent by construction; the
     structures that can diverge — bank members, place-group branches
     (MPMD-inside-SPMD ``lax.switch``), ragged-pipeline prologue/
     epilogue (``lax.cond`` on the stage index) — must not contain
     collective ops, and subset axes must not collide with the pipeline
     axes (the banks×pipeline double transition, PR 6's NaN bug).
     Extends to OVERLAPPED schedules (``strategy.overlap``,
     ``runtime/overlap.py``): the bucketed grad-sync launch order must
     be a dense total order per device, buckets disjoint with no
     subset-group (bank/place-group/pipeline) members, and the launch
     order must agree with backward completion order — a bucket
     scheduled ahead of a gradient backward has not produced yet is
     the overlapped-schedule deadlock class, rejected statically
     (fixture-pinned).
  5. **placement** — hierarchical-placement soundness (arXiv
     2110.10548, ``parallel/placement.py``): ``axis_tiers`` must map
     real mesh axes to known hardware tiers, every serialized
     reduction-tree phase must stay within a tier its site's tier path
     covers (a phase whose subset crosses an uncovered tier would
     deadlock or silently traverse the wrong fabric), and a
     latency-bound per-op collective placed across DCN — one whose
     payload is below the DCN bandwidth-latency product, so every step
     pays pure inter-slice latency — is a compile-time error with the
     offending tier attributed.
  6. **zero** — per-parameter optimizer-state sharding soundness
     (``strategy.zero``, arXiv 2004.13336): every sharded moment's
     spec must name real mesh axes, divide its weight's shape, and
     never reuse an axis the weight's own placement consumes (the
     collision that turns the reduce-scatter update into GSPMD
     generic resharding). The memory envelope (check 3) prices the
     optimizer slots per-parameter against the same assignment, so a
     plan that only fits *because* of ZeRO verifies.
  7. **kernel** — per-op kernel-implementation soundness
     (``strategy.kernel_impls``, kernels/registry.py): every adopted
     impl must be registered and its availability predicate must hold
     on the adopted mesh/shapes — ``ring`` without a mesh sequence
     axis is the fixture-pinned rejection. The memory envelope
     (check 3) counts ring-assigned attention ops at 1/seq-degree
     activation residency, so a context that only fits *because* of
     ring attention verifies.

``FFModel.compile`` runs this post-search (``FFConfig.plan_verify``,
``FF_PLAN_VERIFY=0`` to disable); failures raise
:class:`PlanVerificationError` naming the offending op/seam, findings
are appended to the strategy audit record, and every run bumps the
``ff_plan_verify_*`` counters under a ``plan_verify.run`` span.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import events as obs_events
from ..obs.metrics_registry import REGISTRY

__all__ = ["Finding", "PlanReport", "PlanVerificationError",
           "StructMesh", "memory_envelope", "verify_plan",
           "verify_model", "verify_serving_plan", "verify_strategy_file"]


# ---------------------------------------------------------------------------
# findings + report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Finding:
    """One verification finding, attributed to an op and (optionally) a
    seam. ``check`` is the engine that produced it (op-shard / seam /
    memory / collective-order), ``severity`` "error" or "warn"."""
    check: str
    severity: str
    op: str
    message: str
    seam: Optional[str] = None

    def format(self) -> str:
        where = f"{self.op}" + (f" @ {self.seam}" if self.seam else "")
        return f"[{self.check}] {where}: {self.message}"

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class PlanVerificationError(ValueError):
    """A strategy failed static verification. ``findings`` carries the
    error-severity findings, each attributed to an op/seam."""

    def __init__(self, findings: Sequence[Finding], context: str = ""):
        self.findings = [f for f in findings if f.severity == "error"]
        lines = [f.format() for f in self.findings]
        head = f"plan verification failed ({len(lines)} error(s))"
        if context:
            head += f" for {context}"
        super().__init__(head + ":\n  " + "\n  ".join(lines))


@dataclasses.dataclass
class PlanReport:
    """The result of one verification pass: findings plus the derived
    artifacts (memory breakdown, static collective schedule)."""
    findings: List[Finding] = dataclasses.field(default_factory=list)
    memory: Dict[str, float] = dataclasses.field(default_factory=dict)
    collectives: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)
    duration_s: float = 0.0

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "warn"]

    def ok(self) -> bool:
        return not self.errors

    def add(self, check: str, severity: str, op: str, message: str,
            seam: Optional[str] = None) -> None:
        self.findings.append(Finding(check, severity, op, message, seam))

    def to_json(self) -> Dict[str, Any]:
        return {"findings": [f.to_json() for f in self.findings],
                "memory": dict(self.memory),
                "collectives": list(self.collectives),
                "duration_s": self.duration_s,
                "ok": self.ok()}

    def raise_if_failed(self, context: str = "") -> None:
        if not self.ok():
            raise PlanVerificationError(self.findings, context)


# ---------------------------------------------------------------------------
# spec helpers (layout normalization itself lives in parallel.reshard)
# ---------------------------------------------------------------------------

def _spec_entries(spec) -> List[Tuple[str, ...]]:
    """Per-entry mesh-axis tuples of a PartitionSpec (or its JSON form),
    WITHOUT rank padding — used for rank/soundness checks."""
    out: List[Tuple[str, ...]] = []
    if spec is None:
        return out
    for e in tuple(spec):
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(e))
        else:
            out.append((e,))
    return out


def _check_spec(report: PlanReport, axis_sizes: Dict[str, int], op: str,
                what: str, spec, shape: Optional[Sequence[int]],
                seam: Optional[str] = None) -> None:
    """Mesh-axis soundness + divisibility of one PartitionSpec against
    one (possibly unknown) shape."""
    entries = _spec_entries(spec)
    if not entries:
        return
    seen: set = set()
    for axes in entries:
        for a in axes:
            if a not in axis_sizes:
                report.add("op-shard", "error", op,
                           f"{what} spec {spec} names unknown mesh axis "
                           f"{a!r} (mesh axes: {sorted(axis_sizes)})",
                           seam)
            elif a in seen:
                report.add("op-shard", "error", op,
                           f"{what} spec {spec} reuses mesh axis {a!r} "
                           f"(an axis may shard at most one dim)", seam)
            seen.add(a)
    if shape is None:
        return
    if len(entries) > len(shape):
        report.add("op-shard", "error", op,
                   f"{what} spec {spec} has {len(entries)} entries for a "
                   f"rank-{len(shape)} tensor of shape {tuple(shape)}",
                   seam)
        return
    for d, axes in enumerate(entries):
        deg = 1
        for a in axes:
            deg *= axis_sizes.get(a, 1)
        if deg > 1 and shape[d] % deg != 0:
            report.add("op-shard", "error", op,
                       f"{what} dim {d} of shape {tuple(shape)} is not "
                       f"divisible by its shard degree {deg} "
                       f"(axes {axes}) — this layout only executes via "
                       f"GSPMD's generic padded resharding", seam)


def _spec_degree(spec, axis_sizes: Dict[str, int]) -> int:
    """Total shard degree of a spec (shared definition:
    ``runtime/zero.spec_degree``)."""
    from ..runtime.zero import spec_degree
    return spec_degree(spec, axis_sizes)


def _opt_slots(optimizer) -> int:
    """Optimizer-state leaves per parameter for the memory envelope
    (shared definition: ``runtime/zero.opt_slots``)."""
    from ..runtime.zero import opt_slots
    return opt_slots(optimizer)


def _zero_of(strategy, zero=None):
    """Normalize a per-parameter ZeRO assignment: the explicit ``zero``
    argument wins, else the strategy's own ``.zero`` attribute; JSON
    dicts are lifted to :class:`~flexflow_tpu.runtime.zero.
    ZeroAssignment`. None = fully replicated optimizer state."""
    from ..runtime.zero import ZeroAssignment
    z = zero if zero is not None else getattr(strategy, "zero", None)
    if z is None or isinstance(z, ZeroAssignment):
        return z
    return ZeroAssignment.from_json(z)


# ---------------------------------------------------------------------------
# the verifier
# ---------------------------------------------------------------------------

def verify_plan(strategy, layers: Sequence, *,
                machine_spec=None,
                graph_inputs: Sequence = (),
                optimizer=None,
                hbm_bytes: Optional[float] = None,
                context: str = "") -> PlanReport:
    """Statically verify one (strategy, layers, machine) triple.

    ``strategy`` is a :class:`~flexflow_tpu.parallel.strategy.
    ShardingStrategy` (or any object with ``.ops``/``.inputs``/
    ``.banks``/``.place_groups``/``.pipeline`` and a ``.dmesh`` carrying
    ``axis_sizes``); ``layers`` the executable layer list the specs are
    keyed by (the rewritten program when the search rewrote the graph).
    Returns a :class:`PlanReport`; call :meth:`PlanReport.
    raise_if_failed` (what ``FFModel.compile`` does) to turn errors into
    a typed :class:`PlanVerificationError`.
    """
    t0 = time.perf_counter()
    report = PlanReport()
    dmesh = getattr(strategy, "dmesh", None)
    axis_sizes: Dict[str, int] = dict(getattr(dmesh, "axis_sizes", {}))
    spec = machine_spec or getattr(dmesh, "spec", None)
    by_name = {l.name: l for l in layers}

    _check_op_shards(report, strategy, by_name, axis_sizes, graph_inputs)
    reshard_peak = _check_seams(report, strategy, layers, by_name,
                                axis_sizes, spec, graph_inputs)
    _check_collective_order(report, strategy, layers, by_name, axis_sizes)
    _check_overlap(report, getattr(strategy, "overlap", None),
                   grouped=_overlap_grouped(strategy, layers),
                   pos={l.name: i for i, l in enumerate(layers)},
                   op_types={name: l.op_type
                             for name, l in by_name.items()},
                   have_layers=bool(by_name))
    _check_memory(report, strategy, layers, axis_sizes, spec, optimizer,
                  hbm_bytes, reshard_peak)
    _check_placement(report,
                     getattr(strategy, "axis_tiers", None) or {},
                     getattr(strategy, "collective_trees", None) or (),
                     axis_sizes, spec)
    unaddressable = _zero_unaddressable(strategy, layers)
    _check_zero(report, _zero_of(strategy),
                {name: getattr(os_, "weights", {}) or {}
                 for name, os_ in getattr(strategy, "ops", {}).items()},
                {name: {w.name: tuple(w.shape)
                        for w in (l.weights or ())}
                 for name, l in by_name.items()},
                axis_sizes, have_layers=bool(by_name),
                unaddressable=unaddressable)
    qsync = getattr(strategy, "qsync", None)
    qsync_tiers = dict(getattr(strategy, "axis_tiers", None) or {})
    if not qsync_tiers:
        # a non-searched (preset) strategy carries no placement record:
        # the mesh's own axis→tier derivation is the ground truth the
        # plan was built against
        try:
            qsync_tiers = dict(dmesh.axis_tiers)
        except Exception:  # noqa: BLE001 — tierless mesh
            qsync_tiers = {}
    _check_qsync(report,
                 qsync.to_json() if qsync is not None
                 and hasattr(qsync, "to_json") else qsync,
                 qsync_tiers,
                 {name: getattr(os_, "weights", {}) or {}
                  for name, os_ in getattr(strategy, "ops",
                                           {}).items()},
                 axis_sizes, have_layers=bool(by_name),
                 known_layers=set(by_name),
                 unaddressable=unaddressable)
    kimpls = getattr(strategy, "kernel_impls", None) or {}
    if kimpls:
        from ..kernels import registry as kreg
        seq_deg = int(axis_sizes.get("seq", 0) or 0)
        attn_ctxs = {name: ctx for name, l in by_name.items()
                     if (ctx := kreg.layer_ctx(l, seq_deg)) is not None}
        _check_kernel(report, kimpls, axis_sizes, attn_ctxs,
                      have_layers=bool(by_name),
                      known_layers=set(by_name))
    serving_doc = getattr(strategy, "serving", None)
    if serving_doc:
        _check_serving(report, serving_doc, by_name, axis_sizes, spec,
                       hbm_bytes)

    report.duration_s = time.perf_counter() - t0
    REGISTRY.counter("ff_plan_verify_runs_total",
                     "Static plan verification passes").inc()
    for f in report.findings:
        REGISTRY.counter("ff_plan_verify_findings_total",
                         "Plan verification findings by check"
                         ).inc(check=f.check)
    if report.errors:
        REGISTRY.counter("ff_plan_verify_errors_total",
                         "Plan verifications that found errors").inc()
    obs_events.record_span("plan_verify.run", t0, report.duration_s,
                           findings=len(report.findings),
                           errors=len(report.errors),
                           context=context or "")
    return report


# -- check 1: per-op shard specs --------------------------------------------

def _check_op_shards(report, strategy, by_name, axis_sizes,
                     graph_inputs) -> None:
    weight_shapes = {
        name: {w.name: tuple(w.shape) for w in (l.weights or ())}
        for name, l in by_name.items()}
    for name, os_ in getattr(strategy, "ops", {}).items():
        layer = by_name.get(name)
        for i, sp in enumerate(getattr(os_, "outputs", ()) or ()):
            if sp is None:
                continue
            shape = None
            if layer is not None and i < len(layer.outputs):
                shape = layer.outputs[i].shape
            _check_spec(report, axis_sizes, name, f"output[{i}]", sp,
                        shape)
            _check_conv_sequence(report, axis_sizes, layer, sp)
            _check_block_diffusion_sequence(report, axis_sizes, layer, sp)
            _check_stream_axes(report, axis_sizes, layer, sp)
        for wname, sp in (getattr(os_, "weights", {}) or {}).items():
            if sp is None:
                continue
            _check_paired_heads(report, axis_sizes, layer, wname, sp)
            shape = weight_shapes.get(name, {}).get(wname)
            _check_spec(report, axis_sizes, name, f"weight {wname!r}",
                        sp, shape, seam="checkpoint-restore")
    in_shapes = {t.name: tuple(t.shape) for t in graph_inputs}
    for tname, sp in getattr(strategy, "inputs", {}).items():
        _check_spec(report, axis_sizes, tname, "input", sp,
                    in_shapes.get(tname))


def _check_paired_heads(report, axis_sizes, layer, wname, spec) -> None:
    """Differential attention pairs adjacent heads, and a layer may read
    the keys and values another projected, in heads: a shard of a
    weight's heads would have to keep every pair, and the group of
    query pairs on a key pair, whole on both layers, which nothing
    emits (``search/opshard.py`` offers batch only)."""
    params = getattr(layer, "params", None) or {}
    if not params.get("differential") \
            or wname not in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
        return
    if any(axis_sizes.get(a, 1) > 1
           for axes in _spec_entries(spec) for a in axes):
        report.add("op-shard", "error", layer.name,
                   f"weight {wname!r} spec {spec} shards a differential "
                   f"attention layer's heads: adjacent heads are a pair "
                   f"and a shard that keeps the pairs whole is not built")


def _check_stream_axes(report, axis_sizes, layer, spec) -> None:
    """A hyper-connection node is per token: batch and sequence shard
    freely. Every axis after them does not. The maps mix all of a
    token's streams, and the norm and the product with ``phi`` sum over
    all of its channels: a shard of either would need its partial sums
    reduced, which no layer here emits and no cost row prices
    (``search/opshard.py`` offers neither)."""
    from ..ffconst import OperatorType
    if getattr(layer, "op_type", None) != OperatorType.OP_HYPER_CONNECTION:
        return
    for dim, axes in enumerate(_spec_entries(spec)):
        if dim >= 2 and any(axis_sizes.get(a, 1) > 1 for a in axes):
            report.add("op-shard", "error", layer.name,
                       f"output spec {spec} shards dim {dim} of a "
                       f"hyper-connection node: beyond batch and sequence "
                       f"the streams, the channels and the maps are one "
                       f"token's, and the partial sums a shard of them "
                       f"needs reduced are not built")


def _check_conv_sequence(report, axis_sizes, layer, spec) -> None:
    """Three kinds of layer read what lies before a position: a gated
    short convolution its ``taps - 1`` predecessors, a gated delta rule
    (with a decay a channel or a head) and a state-space mixer those AND
    the state every earlier position left. Batch- and
    channel- (head-) sharded layouts are local; a sequence-sharded one
    needs a halo exchange, and for the delta rule each shard's final
    state handed to the next, which no layer here emits and no cost row
    prices (``search/opshard.py`` offers none)."""
    from ..ffconst import OperatorType
    needs = {OperatorType.OP_GATED_SHORT_CONV:
             "a short convolution of {taps} taps: each shard needs",
             OperatorType.OP_GATED_DELTA_RULE:
             "a gated delta rule: each shard needs the state its "
             "neighbour leaves and",
             OperatorType.OP_STATE_SPACE_MIXER:
             "a state-space mixer: each shard needs the state its "
             "neighbour leaves and",
             OperatorType.OP_SELECTIVE_SCAN_MIXER:
             "a selective-scan mixer: each shard needs the state its "
             "neighbour leaves and"}.get(getattr(layer, "op_type", None))
    if needs is None:
        return
    entries = _spec_entries(spec)
    if len(entries) > 1 and any(axis_sizes.get(a, 1) > 1
                                for a in entries[1]):
        taps = layer.params["taps"]
        report.add("op-shard", "error", layer.name,
                   f"output spec {spec} shards the sequence of "
                   f"{needs.format(taps=taps)} a halo of {taps - 1} "
                   f"positions from its neighbour, which is not built")


def _check_block_diffusion_sequence(report, axis_sizes, layer, spec) -> None:
    """The 2 L positions of a block-diffusion step are the halves of ONE
    sequence: the noising op joins them, and an attention layer under the
    mask reads the clean half from the noised one's rows. Batch- and
    head-sharded layouts are local; a sequence-sharded one would need
    the other half's keys from another shard, which nothing emits (ring
    attention has no such mask, ``kernels/registry.py``;
    ``search/opshard.py`` offers none)."""
    from ..ffconst import OperatorType
    kind = getattr(layer, "op_type", None)
    params = getattr(layer, "params", None) or {}
    if kind != OperatorType.OP_BLOCK_DIFFUSION_NOISE and not (
            kind == OperatorType.OP_MULTIHEAD_ATTENTION
            and params.get("block_diffusion_block")):
        return
    entries = _spec_entries(spec)
    if len(entries) > 1 and any(axis_sizes.get(a, 1) > 1
                                for a in entries[1]):
        report.add("op-shard", "error", layer.name,
                   f"output spec {spec} shards the sequence of a "
                   f"block-diffusion step: the noised and the clean half "
                   f"are one sequence under the mask, and a shard of it "
                   f"is not built")


# -- check 2: layout seams --------------------------------------------------

class StructMesh:
    """Structural mesh stand-in: ``axis_sizes`` plus a machine spec —
    everything the verifier, ``load_strategy``, and
    ``ReshardPlanner.plan`` need, with no jax devices behind it. Used
    by the CLI's strategy verification and the fixture tests."""

    def __init__(self, axis_sizes: Dict[str, int], spec=None):
        from ..parallel.machine import MachineSpec
        self.axis_sizes = {str(k): int(v) for k, v in axis_sizes.items()}
        self.spec = spec or MachineSpec(
            num_devices=int(np.prod(list(self.axis_sizes.values())
                                    or [1])),
            generation="cpu-sim")


def _seam_planner(strategy, spec, axis_sizes):
    """A non-persisting planner over the strategy's mesh: seam probes
    must not warm the executor's shared disk cache."""
    from ..parallel.reshard import ReshardPlanner
    return ReshardPlanner(StructMesh(axis_sizes, spec), persist=False)


def _probe_seam(report, planner, op: str, seam: str, src, dst,
                shape: Sequence[int], itemsize: int = 4) -> float:
    """Plan one seam transition; error when the planner cannot lower it
    (kind="constraint" = the GSPMD generic-resharding fallback — the
    PR 6 miscompile class). Returns the plan's transient peak bytes."""
    try:
        plan = planner.plan(src, dst, tuple(shape), itemsize)
    except Exception as e:  # noqa: BLE001 — surface, don't crash
        report.add("seam", "error", op,
                   f"planner failed to lower {src} -> {dst} on shape "
                   f"{tuple(shape)}: {e}", seam)
        return 0.0
    if plan.kind == "constraint":
        report.add(
            "seam", "error", op,
            f"transition {src} -> {dst} on shape {tuple(shape)} has no "
            f"legal portable-collective lowering (indivisible shard) "
            f"and would fall back to GSPMD generic resharding — the "
            f"known miscompile class the reshard planner exists to "
            f"bypass", seam)
        return 0.0
    report.collectives.append(
        {"seam": seam, "op": op, "kind": plan.kind,
         "steps": plan.describe()})
    return float(plan.peak_bytes)


def _check_seams(report, strategy, layers, by_name, axis_sizes, spec,
                 graph_inputs) -> float:
    from jax.sharding import PartitionSpec as P

    from ..parallel.reshard import (LAYOUT_OPS, _input_specs_replicated,
                                    norm_spec)
    planner = _seam_planner(strategy, spec, axis_sizes)
    peak = 0.0
    from ..dtypes import itemsize as _isz

    # (a) layout-op output constraints (executor emit_layers →
    #     reshard.constrain_output): replicated inputs + sharded output
    #     spec on a reshape/concat/... is an explicit transition
    for layer in layers:
        if layer.op_type not in LAYOUT_OPS:
            continue
        os_ = getattr(strategy, "ops", {}).get(layer.name)
        if os_ is None:
            continue
        for i, sp in enumerate(os_.outputs or ()):
            if sp is None or i >= len(layer.outputs):
                continue
            shape = layer.outputs[i].shape
            if not any(norm_spec(sp, len(shape))):
                continue
            if not _input_specs_replicated(strategy, layer):
                continue
            peak = max(peak, _probe_seam(
                report, planner, layer.name, "layout-op-output",
                P(), sp, shape, _isz(layer.outputs[i].dtype)))

    # (b) bank boundaries (executor._emit_bank → banks.shard_stack /
    #     rejoin_stack): the stacked member input moves onto the bank
    #     layout (an axis move) and the output stack rejoins by an
    #     explicit bank-dim gather
    for bk in getattr(strategy, "banks", None) or ():
        peak = max(peak, _check_bank(report, planner, strategy, bk,
                                     by_name, axis_sizes, _isz))

    # (c) pipeline-region entry/exit (pipeline_lowering.
    #     region_entry_transition / region_exit_transition)
    region = getattr(strategy, "pipeline", None)
    if region is not None:
        peak = max(peak, _check_pipeline_region(
            report, planner, strategy, region, layers, axis_sizes,
            graph_inputs))

    # (d) checkpoint-restore placement (reshard.place_host): a sharded
    #     weight restores shard-by-shard, which needs the same
    #     divisibility the op-shard check proved — attribute any
    #     sharded-but-indivisible weight to this seam (done in
    #     _check_op_shards via seam="checkpoint-restore").
    return peak


def _check_bank(report, planner, strategy, bk, by_name, axis_sizes,
                _isz) -> float:
    from jax.sharding import PartitionSpec as P

    from ..parallel.reshard import norm_spec, tensor_spec
    name = f"bank[{'+'.join(bk.members[:2])}{'...' if len(bk.members) > 2 else ''}]"
    missing = [m for m in bk.members if m not in by_name]
    if missing:
        report.add("seam", "error", name,
                   f"bank members {missing} are not in the program",
                   "bank-boundary")
        return 0.0
    bad_axes = [a for a in bk.axes if a not in axis_sizes]
    if bad_axes:
        report.add("seam", "error", name,
                   f"bank axes {bad_axes} are not mesh axes "
                   f"(mesh: {sorted(axis_sizes)})", "bank-boundary")
        return 0.0
    B = 1
    for a in bk.axes:
        B *= axis_sizes[a]
    K = len(bk.members)
    if K % max(B, 1) != 0:
        report.add("seam", "error", name,
                   f"bank degree {B} (axes {tuple(bk.axes)}) does not "
                   f"divide the member count {K}", "bank-boundary")
        return 0.0
    members = [by_name[m] for m in bk.members]
    m0 = members[0]
    if not m0.inputs or not m0.outputs:
        return 0.0
    bank_spec = bk.axes[0] if len(bk.axes) == 1 else tuple(bk.axes)
    batch_spec = None
    ish = m0.inputs[0].shape
    if bk.batch_axes and ish:
        bdeg = 1
        for a in bk.batch_axes:
            bdeg *= axis_sizes.get(a, 1)
        if ish[0] % bdeg == 0:
            batch_spec = (bk.batch_axes[0] if len(bk.batch_axes) == 1
                          else tuple(bk.batch_axes))
    stacked = (K,) + tuple(ish)
    # entry: member-input layout lifted one dim right → bank layout
    mem = norm_spec(tensor_spec(strategy, m0.inputs[0]), len(ish))
    src = P(None, *[tuple(d) if d else None for d in mem])
    dst = P(bank_spec, batch_spec, *([None] * (len(stacked) - 2)))
    peak = _probe_seam(report, planner, name, "bank-stack", src, dst,
                       stacked, _isz(m0.inputs[0].dtype))
    # exit: gather ONLY the bank dim (banks.rejoin_stack)
    osh = (K,) + tuple(m0.outputs[0].shape)
    pad = [None] * (len(osh) - 2)
    peak = max(peak, _probe_seam(
        report, planner, name, "bank-rejoin",
        P(bank_spec, batch_spec, *pad), P(None, batch_spec, *pad),
        osh, _isz(m0.outputs[0].dtype)))
    return peak


def _find_tensor(layers, graph_inputs, guid):
    for t in graph_inputs:
        if t.guid == guid:
            return t
    for l in layers:
        for t in l.outputs:
            if t.guid == guid:
                return t
    return None


def _check_pipeline_region(report, planner, strategy, region, layers,
                           axis_sizes, graph_inputs) -> float:
    from jax.sharding import PartitionSpec as P

    from ..parallel.reshard import norm_spec, tensor_spec
    peak = 0.0
    rname = f"pipeline[{region.n_stages} stages]"
    pp = getattr(region, "pp_axis", None)
    if pp is None or pp not in axis_sizes:
        report.add("seam", "error", rname,
                   f"pipeline pp_axis {pp!r} is not a mesh axis "
                   f"(mesh: {sorted(axis_sizes)})", "pipeline-entry")
        return peak
    if axis_sizes[pp] != region.n_stages:
        report.add("seam", "error", rname,
                   f"pp axis {pp!r} has size {axis_sizes[pp]} but the "
                   f"region has {region.n_stages} stages (one stage per "
                   f"pipeline rank)", "pipeline-entry")
    tp = getattr(region, "tp_axis", None)
    if tp is not None and tp not in axis_sizes:
        report.add("seam", "error", rname,
                   f"pipeline tp_axis {tp!r} is not a mesh axis",
                   "pipeline-entry")
    if getattr(region, "n_chunks", 1) > 1 \
            and region.n_microbatches % region.n_stages != 0:
        report.add("seam", "error", rname,
                   f"interleaved schedule needs M % S == 0, got "
                   f"M={region.n_microbatches} S={region.n_stages}",
                   "pipeline-entry")
    # entry: sharded activation gathered to replicated before the
    # microbatch reshape (region_entry_transition)
    entry_t = _find_tensor(layers, graph_inputs, region.entry_guid)
    if entry_t is not None and entry_t.shape:
        B = entry_t.shape[0]
        M = max(region.n_microbatches, 1)
        if B % M != 0:
            report.add("seam", "error", rname,
                       f"batch {B} is not divisible into {M} "
                       f"microbatches", "pipeline-entry")
        src = tensor_spec(strategy, entry_t)
        if src is not None and any(norm_spec(src, len(entry_t.shape))):
            from ..dtypes import itemsize as _isz
            peak = max(peak, _probe_seam(
                report, planner, rname, "pipeline-entry", src, P(),
                entry_t.shape, _isz(entry_t.dtype)))
    # exit: the engine's (M, mb, ...) output gathered back to
    # replicated (region_exit_transition) — dp-sharded on dim 1
    exit_t = _find_tensor(layers, graph_inputs, region.exit_guid)
    dp_axes = tuple(getattr(region, "dp_axes", ()) or ())
    if exit_t is not None and exit_t.shape and dp_axes:
        dp = dp_axes[0] if len(dp_axes) == 1 else tuple(dp_axes)
        M = max(region.n_microbatches, 1)
        B = exit_t.shape[0]
        if B % M == 0:
            ys_shape = (M, B // M) + tuple(exit_t.shape[1:])
            xs_spec = P(None, dp, *([None] * (len(ys_shape) - 2)))
            from ..dtypes import itemsize as _isz
            peak = max(peak, _probe_seam(
                report, planner, rname, "pipeline-exit", xs_spec, P(),
                ys_shape, _isz(exit_t.dtype)))
    return peak


# -- check 3: memory envelope -----------------------------------------------

def memory_envelope(strategy, layers, axis_sizes, optimizer, *,
                    reshard_peak: float = 0.0,
                    zero=None) -> Dict[str, float]:
    """Conservative static per-device memory envelope of one plan:
    params + grads + optimizer slots + live fwd/bwd activation pair +
    the largest planned reshard transient.

    The optimizer-slot term is **per-parameter**: a leaf the ZeRO
    assignment shards (``strategy.zero`` / the ``zero`` argument)
    counts at ``slots x bytes / (weight degree x zero degree)`` instead
    of the flat ``params x slots`` — so a plan that only fits *because*
    of optimizer-state sharding verifies (and the ZeRO planner adopts
    against the same arithmetic the verifier will enforce). With no
    assignment the numbers are bit-identical to the historical flat
    formula. Shared by ``_check_memory`` and
    ``search/zero_plan.plan_zero_assignment``."""
    from ..dtypes import itemsize as _isz
    from ..parallel.reshard import tensor_spec
    ops = getattr(strategy, "ops", {})
    zero_a = _zero_of(strategy, zero)
    unaddressable = _zero_unaddressable(strategy, layers) \
        if zero_a is not None else {}
    bank_deg = {}
    for bk in getattr(strategy, "banks", None) or ():
        d = 1
        for a in bk.axes:
            d *= axis_sizes.get(a, 1)
        for m in bk.members:
            bank_deg[m] = max(d, 1)
    slots = _opt_slots(optimizer)
    kernel_impls = getattr(strategy, "kernel_impls", None) or {}
    seq_degree = int(axis_sizes.get("seq", 1) or 1)
    params_local = 0.0
    opt_local = 0.0
    n_zero_sharded = 0
    act_peak, act_op = 0.0, ""
    for layer in layers:
        os_ = ops.get(layer.name)
        wspecs = getattr(os_, "weights", {}) if os_ is not None else {}
        for w in layer.weights or ():
            total = float(int(np.prod(w.shape)) or 1) * _isz(w.dtype)
            deg = _spec_degree(wspecs.get(w.name), axis_sizes)
            deg *= bank_deg.get(layer.name, 1)
            local = total / max(deg, 1)
            params_local += local
            # unaddressable layers (bank/place-group/pipeline state
            # lives under group keys) can never realize zero savings
            # at runtime — counting them would make the envelope
            # optimistic (the zero check errors on them separately)
            zdeg = 1
            if zero_a is not None and layer.name not in unaddressable:
                zdeg = zero_a.degree_for(layer.name, w.name)
            if zdeg > 1:
                n_zero_sharded += 1
            opt_local += slots * local / max(zdeg, 1)
        local = 0.0
        for t in list(layer.inputs) + list(layer.outputs):
            total = float(int(np.prod(t.shape)) or 1) * _isz(t.dtype)
            # inputs resolve through their PRODUCER's assigned spec
            # (tensor_spec) — counting them unsharded would inflate the
            # envelope by the sharding degree and false-fail the gate
            sp = tensor_spec(strategy, t)
            local += total / max(_spec_degree(sp, axis_sizes), 1)
        if kernel_impls.get(layer.name) == "ring" and seq_degree > 1:
            # ring attention (kernels/ring_attention.py) executes
            # inside a shard_map over the sequence axis: each device
            # holds only the 1/seq-degree chunk of q/k/v/output, and
            # the K/V block rotates in place — the op's live residency
            # divides by the seq degree. This is what lets a context
            # that only fits BECAUSE of ring attention verify.
            local /= seq_degree
        if local > act_peak:
            act_peak, act_op = local, layer.name
    total = params_local * 2 + opt_local + 2 * act_peak + reshard_peak
    return {
        "params_bytes": params_local,
        "grads_bytes": params_local,
        "opt_state_bytes": opt_local,
        "opt_slots": float(slots),
        "zero_sharded_params": float(n_zero_sharded),
        "peak_activation_bytes": act_peak,
        "peak_activation_op": act_op,
        "reshard_transient_bytes": reshard_peak,
        "envelope_bytes": total,
    }


def _check_memory(report, strategy, layers, axis_sizes, spec, optimizer,
                  hbm_bytes, reshard_peak) -> None:
    if hbm_bytes is None:
        hbm_bytes = getattr(spec, "hbm_bytes", None)
    if not hbm_bytes:
        return
    env = memory_envelope(strategy, layers, axis_sizes, optimizer,
                          reshard_peak=reshard_peak)
    # (XLA's scheduler can only do better than this ENVELOPE;
    # rematerialization and fusion shrink the activation term, never
    # grow it)
    report.memory = {**env, "hbm_bytes": float(hbm_bytes)}
    total = env["envelope_bytes"]
    act_op = env["peak_activation_op"]
    if total > hbm_bytes:
        zero_note = ""
        if env["zero_sharded_params"]:
            zero_note = (f", with {env['zero_sharded_params']:.0f} "
                         f"ZeRO-sharded opt leaves already counted")
        report.add(
            "memory", "error", act_op or "<model>",
            f"static per-device envelope {total / 2**20:.1f} MiB exceeds "
            f"the machine model's {hbm_bytes / 2**20:.1f} MiB HBM "
            f"(params {env['params_bytes'] / 2**20:.1f} MiB x 2 + opt "
            f"state {env['opt_state_bytes'] / 2**20:.1f} MiB"
            f"{zero_note} + 2 x peak activation "
            f"{env['peak_activation_bytes'] / 2**20:.1f} MiB [{act_op}] "
            f"+ reshard transient {reshard_peak / 2**20:.1f} MiB)",
            "memory-envelope")


# -- check 3.5: per-parameter ZeRO assignment ---------------------------------

def _zero_unaddressable(strategy, layers) -> Dict[str, str]:
    """Layers whose optimizer state the per-layer assignment CANNOT
    address at runtime: bank / place-group members (state stacked
    under the group key on device subsets) and layers inside a
    pipeline region (state stacked under template keys). The planner
    excludes them; an imported assignment that shards one would claim
    envelope savings the runtime can't realize — flagged as an error
    instead of letting an optimistic plan verify and OOM at step 1."""
    out: Dict[str, str] = {}
    for bk in getattr(strategy, "banks", None) or ():
        for m in bk.members:
            out[m] = "bank"
    for pg in getattr(strategy, "place_groups", None) or ():
        for m in pg.members:
            out[m] = "place-group"
    region = getattr(strategy, "pipeline", None)
    if region is not None:
        for l in list(layers)[region.start:region.end]:
            out[l.name] = "pipeline-region"
    return out


def _check_zero(report, zero_a, weight_specs, weight_shapes, axis_sizes,
                have_layers: bool = True,
                unaddressable: Optional[Dict[str, str]] = None) -> None:
    """Soundness of a per-parameter optimizer-state sharding assignment
    (``strategy.zero``): every sharded moment's spec must name real
    mesh axes, divide its weight's shape, and — the invariant that
    makes the GSPMD lowering a reduce-scatter instead of a resharding
    storm — must NOT reuse a mesh axis the weight's own placement
    already consumes. A colliding assignment is a typed compile-time
    error (:class:`PlanVerificationError`), not a runtime surprise."""
    if zero_a is None:
        return
    from ..runtime.zero import spec_axes
    unaddressable = unaddressable or {}
    for lname, ws in zero_a.decisions.items():
        lw_specs = weight_specs.get(lname, {})
        lw_shapes = weight_shapes.get(lname, {})
        if lname in unaddressable \
                and any(rec.get("spec") is not None
                        for rec in ws.values()):
            report.add(
                "zero", "error", lname,
                f"zero assignment shards optimizer state of "
                f"{unaddressable[lname]} member {lname!r}, whose state "
                f"is stacked under a group key the per-layer "
                f"assignment cannot address — the runtime would leave "
                f"it replicated while the memory envelope counted it "
                f"sharded (an optimistic plan that OOMs at step 1)",
                "zero-assignment")
            continue
        if have_layers and lname not in weight_shapes:
            if any(rec.get("spec") is not None for rec in ws.values()):
                report.add("zero", "error", lname,
                           f"zero assignment shards state of op "
                           f"{lname!r}, which is not in the program",
                           "zero-assignment")
            continue
        for wname, rec in ws.items():
            sp = rec.get("spec")
            if sp is None:
                continue
            sp = _json_spec(sp) if isinstance(sp, list) else sp
            shape = lw_shapes.get(wname)
            if have_layers and lw_shapes and wname not in lw_shapes:
                report.add("zero", "error", lname,
                           f"zero assignment shards unknown weight "
                           f"{wname!r} (weights: {sorted(lw_shapes)})",
                           "zero-assignment")
                continue
            _check_spec(report, axis_sizes, lname,
                        f"opt-state for weight {wname!r}", sp, shape,
                        seam="zero-assignment")
            wspec = lw_specs.get(wname)
            # the moment FOLLOWS the weight's own placement on the
            # weight's sharded dims (m/v are zeros_like the param);
            # the ZeRO axes proper are the EXTRA ones. A weight axis
            # re-used on a DIFFERENT dim is the collision that turns
            # the reduce-scatter update into generic resharding.
            z_entries = _spec_entries(sp)
            w_entries = _spec_entries(wspec)
            w_entries += [()] * (len(z_entries) - len(w_entries))
            w_axes = set(spec_axes(wspec))
            overlap = sorted(
                a for d, axes in enumerate(z_entries)
                for a in axes
                if a in w_axes and a not in w_entries[d])
            if overlap:
                report.add(
                    "zero", "error", lname,
                    f"zero assignment shards the {wname!r} optimizer "
                    f"state over mesh axis(es) {overlap} that the "
                    f"weight's own placement {wspec} already consumes "
                    f"on a different dim — the moment must shard over "
                    f"the axes the weight is REPLICATED on "
                    f"(reduce-scatter group), or the update "
                    f"degenerates to GSPMD generic resharding",
                    "zero-assignment")


# -- check 3.75: quantized grad-sync plan -------------------------------------

def _check_qsync(report, qsync_doc, axis_tiers, weight_specs,
                 axis_sizes, have_layers: bool = True,
                 known_layers=(), unaddressable=None) -> None:
    """Soundness of a quantized-collectives plan (``strategy.qsync``,
    ops/quantized_collectives.py):

      - a quantized phase is legal only on its DECLARED tier path —
        every axis a phase names must exist and sit on the phase's
        declared tier per ``axis_tiers`` (a plan that labels an ICI
        axis as a "dcn" leg would narrow the FAST fabric while the
        accuracy-risk gate believed only the slow one was touched);
      - replicated-math seams stay full-precision: only the gradient
        all-reduce of a REPLICATED weight may quantize — a decision on
        a sharded weight (whose gradient flows through per-op
        collectives) or a bank / place-group / pipeline member is an
        error;
      - wire dtypes must be known, and an axis may appear in at most
        one phase of a decision.
    """
    if not qsync_doc:
        return
    from ..parallel.placement import WIRE_ITEMSIZE
    from ..parallel.topology import TIER_ORDER
    from ..runtime.zero import spec_degree
    unaddressable = unaddressable or {}
    known_layers = set(known_layers or ())
    decisions = (qsync_doc or {}).get("decisions", {})
    for lname, ws in decisions.items():
        lw_specs = weight_specs.get(lname, {})
        quantized = any(
            p.get("wire") for rec in ws.values()
            for p in rec.get("phases", ()))
        if not quantized:
            continue
        if lname in unaddressable:
            report.add(
                "qsync", "error", lname,
                f"qsync plan quantizes gradient sync of "
                f"{unaddressable[lname]} member {lname!r}, whose "
                f"gradients live under a group key on a device subset "
                f"— the explicit sync cannot address them and the "
                f"implicit one would stay full-precision while the "
                f"plan claimed otherwise", "qsync-plan")
            continue
        if have_layers and known_layers and lname not in known_layers:
            report.add("qsync", "error", lname,
                       f"qsync plan names op {lname!r}, which is not "
                       f"in the program", "qsync-plan")
            continue
        for wname, rec in ws.items():
            phases = rec.get("phases", ())
            if not any(p.get("wire") for p in phases):
                continue
            wspec = lw_specs.get(wname)
            if wspec is not None \
                    and spec_degree(wspec, axis_sizes) > 1:
                report.add(
                    "qsync", "error", lname,
                    f"qsync plan quantizes the gradient of weight "
                    f"{wname!r}, whose placement {wspec} is SHARDED — "
                    f"its gradient flows through per-op (replicated-"
                    f"math) collectives, which must stay full-"
                    f"precision; only the data-parallel all-reduce of "
                    f"a replicated weight may quantize", "qsync-plan")
            seen_axes: set = set()
            for p in phases:
                wire = p.get("wire")
                tier = str(p.get("tier", "ici"))
                if wire is not None and wire not in WIRE_ITEMSIZE:
                    report.add("qsync", "error", lname,
                               f"phase on tier {tier!r} names unknown "
                               f"wire dtype {wire!r} (known: "
                               f"{sorted(WIRE_ITEMSIZE)})",
                               "qsync-plan")
                if tier not in TIER_ORDER:
                    report.add("qsync", "error", lname,
                               f"phase declares unknown tier {tier!r} "
                               f"(tiers: {list(TIER_ORDER)})",
                               "qsync-plan")
                for a in p.get("axes", ()):
                    if axis_sizes and a not in axis_sizes:
                        report.add(
                            "qsync", "error", lname,
                            f"phase on tier {tier!r} names unknown "
                            f"mesh axis {a!r} (axes: "
                            f"{sorted(axis_sizes)})", "qsync-plan")
                        continue
                    if a in seen_axes:
                        report.add(
                            "qsync", "error", lname,
                            f"axis {a!r} appears in more than one "
                            f"phase of {wname!r}'s sync — the staged "
                            f"reduction would traverse it twice",
                            "qsync-plan")
                    seen_axes.add(a)
                    actual = (axis_tiers or {}).get(a, "ici")
                    if wire is not None and actual != tier:
                        report.add(
                            "qsync", "error", lname,
                            f"quantized phase declares tier {tier!r} "
                            f"but its axis {a!r} is placed on tier "
                            f"{actual!r} — a quantized leg is legal "
                            f"only on its declared tier path (the "
                            f"accuracy-risk gate scoped the narrowing "
                            f"to {tier!r} fabric)", "qsync-plan")


# -- check 3.7: per-op kernel implementations --------------------------------

def _check_kernel(report, kimpls, axis_sizes: Dict[str, int],
                  attn_ctxs: Dict[str, Dict[str, Any]], *,
                  have_layers: bool, known_layers=()) -> None:
    """Adopted kernel-impl assignment (``strategy.kernel_impls``,
    kernels/registry.py): every impl name must be registered and its
    availability predicate must hold on the adopted mesh/shapes —
    ``ring`` on a mesh without a sequence axis is THE fixture-pinned
    rejection (an imported plan would otherwise reach emit and fail
    deep inside tracing). ``attn_ctxs`` maps attention layer names to
    their predicate contexts; a name missing from it with layers known
    is a kernel impl assigned to a non-attention op."""
    from ..kernels import registry as kreg
    seq_deg = int(axis_sizes.get("seq", 0) or 0)
    for key, impl in (kimpls or {}).items():
        if key == "opt_update":
            # not a layer name: the kind key of strategy files written
            # while there was a fused optimizer update to choose
            report.add(
                "kernel", "error", key,
                f"unknown kernel op kind 'opt_update' (impl {impl!r}; "
                f"known kinds: {sorted(kreg.REGISTRY)})", "kernel-impl")
            continue
        if impl not in kreg.impl_names(kreg.ATTENTION):
            report.add(
                "kernel", "error", key,
                f"unknown attention impl {impl!r} (known: "
                f"{sorted(kreg.impl_names(kreg.ATTENTION))})",
                "kernel-impl")
            continue
        ctx = attn_ctxs.get(key)
        if ctx is None:
            # the "attention" kind key names no layer: a forced choice
            # for every attention op, of whatever kind
            named = have_layers and key != kreg.ATTENTION
            if named and key not in known_layers:
                report.add(
                    "kernel", "error", key,
                    f"kernel impl {impl!r} is assigned to an op the "
                    f"program does not contain", "kernel-impl")
                continue
            if named:
                report.add(
                    "kernel", "error", key,
                    f"kernel impl {impl!r} is assigned to a "
                    f"non-attention op", "kernel-impl")
                continue
            # no shapes to hold it to (the kind key, or a spec-only
            # strategy file with no program block), but the one
            # mesh-level requirement still binds
            if impl == "ring" and seq_deg < 2:
                report.add(
                    "kernel", "error", key,
                    "kernel impl 'ring' requires a mesh sequence axis "
                    "('seq', degree >= 2); the strategy's mesh_axes "
                    f"have {dict(axis_sizes)}", "kernel-impl")
            continue
        reason = kreg.get_impl(kreg.ATTENTION, impl).available(ctx)
        if reason is not None:
            report.add(
                "kernel", "error", key,
                f"kernel impl {impl!r} is not available on the "
                f"adopted mesh/shapes: {reason}", "kernel-impl")


# -- check 4: collective-ordering consistency --------------------------------

def _check_collective_order(report, strategy, layers, by_name,
                            axis_sizes) -> None:
    from ..ffconst import PARALLEL_OPS
    region = getattr(strategy, "pipeline", None)
    region_names: set = set()
    if region is not None:
        region_names = {l.name for l in layers[region.start:region.end]}
        pp_axes = {a for a in (getattr(region, "pp_axis", None),
                               getattr(region, "tp_axis", None))
                   if a is not None}
    else:
        pp_axes = set()

    def subset_check(kind: str, members, axes, seam: str) -> None:
        name = f"{kind}[{'+'.join(list(members)[:2])}" \
               f"{'...' if len(members) > 2 else ''}]"
        overlap = set(axes) & pp_axes
        if overlap:
            report.add(
                "collective-order", "error", name,
                f"{kind} axes {sorted(overlap)} collide with the "
                f"pipeline region's stage/tp axes — the double "
                f"transition this composes is the banks x pipeline "
                f"NaN-miscompile class (PR 6); place the {kind} on "
                f"disjoint axes", seam)
        inside = sorted(set(members) & region_names)
        if inside:
            report.add(
                "collective-order", "error", name,
                f"members {inside} lie inside the pipeline region: "
                f"their subset lowering cannot nest in the GPipe "
                f"shard_map (stage-divergent collective sequence = "
                f"deadlock)", seam)
        for m in members:
            l = by_name.get(m)
            if l is not None and l.op_type in PARALLEL_OPS:
                report.add(
                    "collective-order", "error", m,
                    f"collective op {l.op_type.name} cannot be a {kind} "
                    f"member: only its subset would issue the "
                    f"collective (rank-divergent sequence = deadlock)",
                    seam)

    for bk in getattr(strategy, "banks", None) or ():
        subset_check("bank", bk.members, bk.axes, "bank-boundary")
    for pg in getattr(strategy, "place_groups", None) or ():
        # (a member's OUTPUT spec may legitimately shard over the
        # placement axis — the lowering rejoins branches with a masked
        # full-axis psum, so the constraint applies to the rejoined
        # value, not inside a branch)
        subset_check("place-group", pg.members, (pg.axis,),
                     "place-group")
    if region is not None:
        from ..ffconst import PARALLEL_OPS as _POPS
        for l in list(getattr(region, "prologue", ()) or ()) \
                + list(getattr(region, "epilogue", ()) or ()):
            if l.op_type in _POPS:
                report.add(
                    "collective-order", "error", l.name,
                    "collective op inside a ragged-pipeline prologue/"
                    "epilogue runs under lax.cond on the stage index — "
                    "only one stage would issue it (deadlock)",
                    "pipeline-prologue")


# -- check 4.5: overlapped grad-sync schedule --------------------------------

def _overlap_grouped(strategy, layers) -> Dict[str, str]:
    """Layer name -> subset-group kind for the overlap check: bank /
    place-group members and pipeline-region layers — the layers whose
    gradients are NOT per-layer addressable on every rank."""
    grouped: Dict[str, str] = {}
    for bk in getattr(strategy, "banks", None) or ():
        for m in bk.members:
            grouped[m] = "bank"
    for pg in getattr(strategy, "place_groups", None) or ():
        for m in pg.members:
            grouped[m] = "place-group"
    region = getattr(strategy, "pipeline", None)
    if region is not None:
        for l in list(layers)[region.start:region.end]:
            grouped[l.name] = "pipeline-region"
    return grouped


def _check_overlap(report, overlap_rec, *, grouped: Dict[str, str],
                   pos: Dict[str, int], op_types: Dict[str, Any],
                   have_layers: bool) -> None:
    """Collective-ordering soundness of an overlapped grad-sync schedule
    (``strategy.overlap``, built by ``runtime/overlap.py`` or imported):

      - the bucket launch order must be TOTAL per device — a dense,
        duplicate-free ``order`` sequence. Every rank derives the same
        chain from the same record, so a total order here is a total
        order everywhere (the no-new-deadlock-class invariant: two
        ranks can never launch bucket collectives in different orders);
      - bucket members must be disjoint, exist in the program, and not
        be collective (parallel) ops;
      - members must not sit inside a pipeline region, bank, or place
        group: their gradients live under group keys on device subsets,
        so a bucket naming one would launch its sync collective from a
        SUBSET of ranks while the chain token holds the rest — the
        rank-divergent launch sequence the total order exists to
        prevent;
      - the launch order must agree with backward completion order:
        every member of bucket k must come LATER in program order than
        every member of bucket k+1 (backward produces deep layers'
        grads first). A bucket scheduled before a grad that backward
        has not produced yet would stall the whole chain on it — on an
        async multi-runtime the overlapped-schedule deadlock class
        (rejection pinned by ``tests/fixtures/badplan_overlap_order.
        json``).
    """
    if not overlap_rec:
        return
    from ..ffconst import PARALLEL_OPS
    buckets = list(overlap_rec.get("buckets") or ())
    if not buckets:
        return
    orders = [int(b.get("order", -1)) for b in buckets]
    if sorted(orders) != list(range(len(buckets))):
        report.add(
            "collective-order", "error", "overlap-schedule",
            f"bucket launch order {orders} is not a dense total order "
            f"over {len(buckets)} buckets — ranks could disagree on "
            f"the grad-sync launch sequence (deadlock)",
            "overlap-schedule")
        return
    seen: Dict[str, int] = {}
    by_order = sorted(buckets, key=lambda b: int(b.get("order", 0)))
    for b in by_order:
        o = int(b.get("order", 0))
        name = f"overlap-bucket[{o}]"
        for m in b.get("members") or ():
            if m in seen:
                report.add(
                    "collective-order", "error", name,
                    f"member {m!r} appears in buckets {seen[m]} and "
                    f"{o} — its grad sync would launch twice, in a "
                    f"chain position other ranks may resolve "
                    f"differently", "overlap-schedule")
            seen[m] = o
            op_type = op_types.get(m)
            if have_layers and m not in op_types:
                report.add("collective-order", "error", name,
                           f"member {m!r} is not in the program",
                           "overlap-schedule")
                continue
            if op_type is not None and op_type in PARALLEL_OPS:
                report.add(
                    "collective-order", "error", name,
                    f"collective op {getattr(op_type, 'name', op_type)}"
                    f" cannot be an overlap-bucket member (it has no "
                    f"weight gradient to sync; chaining it reorders "
                    f"the per-op collective sequence across ranks)",
                    "overlap-schedule")
            if m in grouped:
                report.add(
                    "collective-order", "error", name,
                    f"member {m!r} is a {grouped[m]} member: its "
                    f"gradients live under a group key on a device "
                    f"subset, so only that subset would launch the "
                    f"bucket's sync while the chain token holds the "
                    f"other ranks (rank-divergent launch = deadlock)",
                    "overlap-schedule")
    if not pos:
        return
    for prev, nxt in zip(by_order, by_order[1:]):
        prev_members = [m for m in (prev.get("members") or ()) if m in pos]
        nxt_members = [m for m in (nxt.get("members") or ()) if m in pos]
        if not prev_members or not nxt_members:
            continue
        lo = min(pos[m] for m in prev_members)
        hi = max(pos[m] for m in nxt_members)
        if lo <= hi:
            bad_prev = min(prev_members, key=lambda m: pos[m])
            bad_nxt = max(nxt_members, key=lambda m: pos[m])
            report.add(
                "collective-order", "error",
                f"overlap-bucket[{int(prev.get('order', 0))}]",
                f"launch order contradicts backward completion order: "
                f"bucket {int(prev.get('order', 0))} member "
                f"{bad_prev!r} (program position {pos[bad_prev]}) "
                f"launches before bucket {int(nxt.get('order', 0))} "
                f"member {bad_nxt!r} (position {pos[bad_nxt]}), but "
                f"backward produces {bad_nxt!r}'s gradient FIRST — "
                f"the chain would stall every later bucket on a grad "
                f"not yet produced (the overlapped-schedule deadlock "
                f"class)", "overlap-schedule")


# -- check 5: hierarchical placement -----------------------------------------

def _dcn_tier_constants(spec) -> Tuple[float, float]:
    """(bandwidth bytes/s, latency s) of the DCN tier: the machine
    model's tier graph when available, else the MachineSpec defaults —
    strategy-file verification has no machine behind it but the
    latency-bound check must still bind."""
    try:
        tg = spec.tier_graph
        for t in tg.tiers:
            if t.name == "dcn":
                return t.bandwidth, t.latency_s
    except Exception:  # noqa: BLE001
        pass
    bw = getattr(spec, "dcn_bandwidth", None) or 25e9
    lat = (getattr(spec, "dcn_latency_us", None) or 10.0) * 1e-6
    return float(bw), float(lat)


def _check_placement(report, axis_tiers, collective_trees, axis_sizes,
                     spec) -> None:
    from ..parallel.topology import TIER_ORDER
    for axis, tier in dict(axis_tiers).items():
        if axis_sizes and axis not in axis_sizes:
            report.add("placement", "error", axis,
                       f"axis_tiers names axis {axis!r} absent from the "
                       f"mesh (axes: {sorted(axis_sizes)})",
                       "axis-placement")
        if tier not in TIER_ORDER:
            report.add("placement", "error", axis,
                       f"axis {axis!r} is placed on unknown tier "
                       f"{tier!r} (tiers: {list(TIER_ORDER)})",
                       "axis-placement")
    dcn_bw, dcn_lat = _dcn_tier_constants(spec)
    # devices reachable WITHOUT crossing DCN: a collective whose degree
    # fits inside this span had an inner placement available — crossing
    # DCN anyway is a placement error; a wider collective has no choice
    # (flagging it would reject every full-mesh reduction)
    inner_span = 1
    for axis, tier in dict(axis_tiers).items():
        if tier != "dcn":
            inner_span *= int(axis_sizes.get(axis, 1))
    for rec in collective_trees:
        site = str(rec.get("site", "?"))
        coll = str(rec.get("collective", "?"))
        name = f"{site}/{coll}"
        path = [(str(t), int(d)) for t, d in rec.get("tier_path", ())]
        covered = {t for t, _ in path}
        bad_tiers = sorted(t for t in covered if t not in TIER_ORDER)
        if bad_tiers:
            report.add("placement", "error", name,
                       f"tier path {path} names unknown tier(s) "
                       f"{bad_tiers}", "reduction-tree")
            continue
        deg_of = dict(path)
        total_deg = 1
        for _t, d in path:
            total_deg *= d
        outermost = path[-1][0] if path else None
        for ph in rec.get("phases", ()):
            pt = str(ph.get("tier"))
            ph_deg = int(ph.get("degree", 1))
            # a single-phase ring / halving-doubling tree SPANS the
            # whole path through its bottleneck (outermost) tier: its
            # degree is the path's total product, which is legal there
            spans_path = pt == outermost and ph_deg == total_deg
            if pt not in covered:
                report.add(
                    "placement", "error", name,
                    f"tree phase {ph.get('collective')}[x"
                    f"{ph.get('degree')}] runs on tier {pt!r}, which "
                    f"the site's tier path {path} does not cover — the "
                    f"phase's participant subset would traverse a "
                    f"fabric the placement never reserved",
                    "reduction-tree")
            elif ph_deg > deg_of.get(pt, 1) and not spans_path:
                report.add(
                    "placement", "error", name,
                    f"tree phase {ph.get('collective')} degree "
                    f"{ph.get('degree')} exceeds the {pt} tier's "
                    f"degree {deg_of.get(pt, 1)} in path {path}",
                    "reduction-tree")
        # latency-bound per-op collective across DCN when an inner
        # placement existed: the payload is below the DCN bandwidth-
        # latency product, so the inter-slice leg is pure latency EVERY
        # step — a placement the search must never ship. Collectives
        # wider than the intra-slice span have no inner option and are
        # a strategy (not placement) matter; grad sync, once per step
        # on the whole gradient, only warns.
        avoidable = axis_tiers and \
            int(rec.get("degree", 0) or 0) <= inner_span
        if "dcn" in covered and site != "grad_sync" and avoidable:
            vol = float(rec.get("volume_bytes", 0.0) or 0.0)
            d_dcn = deg_of.get("dcn", 1)
            bound = dcn_bw * dcn_lat * max(d_dcn, 1)
            if 0 < vol < bound:
                report.add(
                    "placement", "error", name,
                    f"latency-bound per-step collective placed across "
                    f"tier 'dcn': payload {vol / 1024:.1f} KiB is below "
                    f"the DCN bandwidth-latency product "
                    f"({bound / 1024:.0f} KiB at "
                    f"{dcn_bw / 1e9:.0f} GB/s x {dcn_lat * 1e6:.0f} us "
                    f"x{d_dcn}) — every step pays pure inter-slice "
                    f"latency; place this collective on an inner tier",
                    "latency-bound-dcn")
        elif "dcn" in covered and site == "grad_sync":
            vol = float(rec.get("volume_bytes", 0.0) or 0.0)
            d_dcn = deg_of.get("dcn", 1)
            if 0 < vol < dcn_bw * dcn_lat * max(d_dcn, 1):
                report.add(
                    "placement", "warn", name,
                    f"gradient sync across DCN is latency-bound at "
                    f"{vol / 1024:.1f} KiB — consider a larger "
                    f"per-step gradient volume or intra-slice "
                    f"replication", "latency-bound-dcn")


# ---------------------------------------------------------------------------
# wiring helpers
# ---------------------------------------------------------------------------

# -- check 8: per-(model, batch-class) serving plans --------------------------

def _check_serving(report, serving_doc, by_name, axis_sizes, spec,
                   hbm_bytes) -> None:
    """Serving-block soundness: every bucket's KV-cache shard degree
    must divide the layer's KV-head count (a decode step cannot split
    a KV head across devices), the recorded per-layer KV bytes must
    match the declared geometry, each bucket's op specs must be
    mesh-sound, and the decode-resident envelope (weights + KV cache +
    live activations) at the LARGEST bucket must fit the machine's
    HBM. The memory gate is what makes a replicated-KV plan that only
    fits sharded fail typed at compile instead of OOMing on the first
    large-bucket request. ``serving_doc`` is always the JSON block
    (``ServingPlan.to_block`` form) — both the in-memory attach and
    ``load_strategy`` carry it that way."""
    from ..dtypes import itemsize as _isz
    try:
        buckets = {int(k): (v or {}) for k, v in
                   (serving_doc.get("buckets") or {}).items()}
    except (TypeError, ValueError):
        report.add("serving", "error", "<serving>",
                   "serving block bucket keys must be integers",
                   "serving-plan")
        return
    if not buckets:
        report.add("serving", "error", "<serving>",
                   "serving block carries no buckets", "serving-plan")
        return
    max_seq = int(serving_doc.get("max_seq") or 0)
    if max_seq <= 0:
        report.add("serving", "error", "<serving>",
                   "serving block has no max_seq (KV geometry is "
                   "unsized)", "serving-plan")
        return
    for bucket, sub in sorted(buckets.items()):
        ctx = f"bucket={bucket}"
        for name, os_ in (sub.get("ops") or {}).items():
            layer = by_name.get(name)
            for i, sp in enumerate(os_.get("outputs") or ()):
                if sp is None:
                    continue
                shape = None
                if layer is not None and i < len(layer.outputs):
                    shape = layer.outputs[i].shape
                _check_spec(report, axis_sizes, name,
                            f"serving[{ctx}] output[{i}]",
                            _json_spec(sp), shape)
            wsh = {w.name: tuple(w.shape)
                   for w in (getattr(layer, "weights", None) or ())}
            for wname, sp in (os_.get("weights") or {}).items():
                if sp is None:
                    continue
                _check_spec(report, axis_sizes, name,
                            f"serving[{ctx}] weight {wname!r}",
                            _json_spec(sp), wsh.get(wname),
                            seam="checkpoint-restore")
        for name, kv in (sub.get("kv") or {}).items():
            kv = kv or {}
            deg = int(kv.get("shard_degree") or 1)
            kvh = int(kv.get("num_kv_heads") or 0)
            hd = int(kv.get("head_dim") or 0)
            if by_name and name not in by_name:
                report.add("serving", "error", name,
                           f"serving[{ctx}]: KV entry names a layer "
                           f"absent from the program", "serving-kv")
                continue
            if deg < 1 or kvh <= 0 or kvh % deg != 0:
                report.add(
                    "serving", "error", name,
                    f"serving[{ctx}]: KV shard degree {deg} does not "
                    f"divide num_kv_heads {kvh} — a decode step cannot "
                    f"split a KV head across devices", "serving-kv")
                continue
            sdeg = int(kv.get("seq_shard_degree") or 1)
            if sdeg > 1:
                # seq-sharded KV only executes on a mesh whose sequence
                # axis carries the degree: the decode-step combine is a
                # ppermute rotation OVER that axis
                mesh_seq = int(axis_sizes.get("seq", 1) or 1)
                if mesh_seq % sdeg != 0 or mesh_seq < sdeg:
                    report.add(
                        "serving", "error", name,
                        f"serving[{ctx}]: KV seq shard degree {sdeg} "
                        f"needs a mesh sequence axis of that degree "
                        f"(mesh has seq={mesh_seq})", "serving-kv")
                    continue
                if max_seq and max_seq % sdeg != 0:
                    report.add(
                        "serving", "error", name,
                        f"serving[{ctx}]: KV seq shard degree {sdeg} "
                        f"does not divide max_seq {max_seq}",
                        "serving-kv")
                    continue
            want = (2 * bucket * max_seq * kvh * hd * 4) \
                // (deg * max(sdeg, 1))
            got = int(kv.get("bytes") or 0)
            if got and hd and got != want:
                report.add(
                    "serving", "error", name,
                    f"serving[{ctx}]: recorded KV bytes {got} disagree "
                    f"with the geometry 2*{bucket}*{max_seq}*{kvh}*"
                    f"{hd}*4/({deg}*{sdeg}) = {want}", "serving-kv")
    # decode-resident envelope at the LARGEST bucket. Needs the layer
    # list for weight/output shapes; spec-only strategy files verify
    # structurally above and skip the gate.
    if not hbm_bytes:
        hbm_bytes = getattr(spec, "hbm_bytes", None)
    if not by_name or not hbm_bytes:
        return
    bucket = max(buckets)
    env = serving_envelope(buckets[bucket], bucket, by_name, axis_sizes)
    total = env["envelope_bytes"]
    act_op = env["peak_activation_op"]
    if total > hbm_bytes:
        report.add(
            "serving", "error", act_op or "<model>",
            f"serving envelope at bucket {bucket} "
            f"{total / 2**20:.1f} MiB exceeds the machine model's "
            f"{hbm_bytes / 2**20:.1f} MiB HBM (weights "
            f"{env['weights_bytes'] / 2**20:.1f} MiB + KV cache "
            f"{env['kv_bytes'] / 2**20:.1f} MiB + 2 x peak activation "
            f"{env['peak_activation_bytes'] / 2**20:.1f} MiB [{act_op}])"
            f" — shard the KV cache (head-parallel attention, or "
            f"seq-sharded KV on a sequence-axis mesh) or drop the "
            f"bucket", "serving-memory")


def serving_envelope(sub: Dict, bucket: int, by_name: Dict,
                     axis_sizes: Dict[str, int]) -> Dict[str, float]:
    """Decode-resident per-device envelope of ONE bucket's serving
    sub-strategy (``ServingPlan.to_block()`` bucket form): sharded
    weights + resident KV cache + a live fwd activation pair, with
    activations rescaled from the compile batch to the bucket. No
    grads/optimizer terms — serving is forward-only. Shared by
    ``_check_serving``'s HBM gate and the serving search/smoke, so a
    plan adopted by the search verifies against the same arithmetic."""
    from ..dtypes import itemsize as _isz
    ops_doc = sub.get("ops") or {}
    params_local = 0.0
    kv_local = float(sum(int((kv or {}).get("bytes") or 0)
                         for kv in (sub.get("kv") or {}).values()))
    act_peak, act_op = 0.0, ""
    for name, layer in by_name.items():
        os_ = ops_doc.get(name) or {}
        wspecs = os_.get("weights") or {}
        for w in layer.weights or ():
            total = float(int(np.prod(w.shape)) or 1) * _isz(w.dtype)
            sp = wspecs.get(w.name)
            deg = _spec_degree(_json_spec(sp), axis_sizes) if sp else 1
            params_local += total / max(deg, 1)
        outs = os_.get("outputs") or ()
        local = 0.0
        for i, t in enumerate(layer.outputs):
            total = float(int(np.prod(t.shape)) or 1) * _isz(t.dtype)
            if t.shape and t.shape[0]:
                # activations were shaped at the compile batch; the
                # serving bucket is what is live at runtime
                total *= bucket / float(t.shape[0])
            sp = outs[i] if i < len(outs) else None
            deg = _spec_degree(_json_spec(sp), axis_sizes) if sp else 1
            local += total / max(deg, 1)
        if local > act_peak:
            act_peak, act_op = local, name
    return {
        "weights_bytes": params_local,
        "kv_bytes": kv_local,
        "peak_activation_bytes": act_peak,
        "peak_activation_op": act_op,
        "envelope_bytes": params_local + kv_local + 2 * act_peak,
    }


def verify_serving_plan(plan, layers: Sequence, dmesh, *,
                        hbm_bytes: Optional[float] = None,
                        context: str = "") -> PlanReport:
    """Verify a searched :class:`~flexflow_tpu.search.serving_plan.
    ServingPlan` (or its serialized ``serving`` block) against the
    program and mesh it was searched for. Raises a typed
    :class:`PlanVerificationError` on error findings — called by
    ``optimize_serving_strategy`` before a plan is exported and by the
    serving smoke gate."""
    t0 = time.perf_counter()
    report = PlanReport()
    axis_sizes: Dict[str, int] = dict(getattr(dmesh, "axis_sizes", {}))
    spec = getattr(dmesh, "spec", None)
    by_name = {l.name: l for l in layers}
    block = plan.to_block() if hasattr(plan, "to_block") else dict(plan)
    _check_serving(report, block, by_name, axis_sizes, spec, hbm_bytes)
    report.duration_s = time.perf_counter() - t0
    REGISTRY.counter("ff_plan_verify_runs_total",
                     "Static plan verification passes").inc()
    for f in report.findings:
        REGISTRY.counter("ff_plan_verify_findings_total",
                         "Plan verification findings by check"
                         ).inc(check=f.check)
    obs_events.record_span("plan_verify.serving", t0, report.duration_s,
                           findings=len(report.findings),
                           errors=len(report.errors),
                           context=context or "")
    report.raise_if_failed(context or "the serving plan")
    return report


def verify_model(model) -> PlanReport:
    """Verify a compiled-to-the-strategy :class:`FFModel` (called from
    ``FFModel.compile`` post-search). Raises
    :class:`PlanVerificationError` on error findings; appends the report
    to the strategy audit record when the search wrote one."""
    program = model.executor.program
    cfg = model.config
    hbm = None
    if getattr(cfg, "device_mem_mb", 0):
        hbm = float(cfg.device_mem_mb) * (1 << 20)
    report = verify_plan(
        model.strategy, program.layers,
        machine_spec=model.dmesh.spec,
        graph_inputs=model.graph_inputs,
        optimizer=model.optimizer,
        hbm_bytes=hbm,
        context="FFModel.compile")
    audit_path = getattr(model, "_strategy_audit_path", None)
    if audit_path:
        from ..obs.audit import annotate_strategy_audit
        annotate_strategy_audit(audit_path,
                                {"plan_verify": report.to_json()})
    report.raise_if_failed("the compiled strategy")
    return report


def verify_strategy_file(path: str, doc: Optional[Dict] = None
                         ) -> PlanReport:
    """Structural verification of a saved strategy JSON (``ffcheck
    --verify-strategies``): mesh-axis soundness of every recorded spec,
    bank/place-group divisibility, and — when the file carries the
    searched program — full shape-level divisibility via the recorded
    layer list. No devices are touched. ``doc`` skips re-parsing when
    the caller already holds the loaded JSON."""
    import json

    t0 = time.perf_counter()
    if doc is None:
        with open(path) as f:
            doc = json.load(f)
    report = PlanReport()
    axis_sizes = {str(k): int(v)
                  for k, v in (doc.get("mesh_axes") or {}).items()}
    if not axis_sizes:
        report.add("op-shard", "error", path,
                   "strategy file has no mesh_axes section")
        report.duration_s = time.perf_counter() - t0
        return report
    # shapes from the serialized program, when present (output shapes
    # re-inferred through the op registry; inputs are name-only in the
    # wire format, so input tensors are synthesized unconstrained)
    out_shapes: Dict[str, List[Tuple[int, ...]]] = {}
    weight_shapes: Dict[str, Dict[str, Tuple[int, ...]]] = {}
    prog = doc.get("program")
    if prog:
        try:
            out_shapes, weight_shapes = _program_shapes(prog)
        except Exception as e:  # noqa: BLE001 — degrade to spec-only
            report.add("op-shard", "warn", path,
                       f"could not reconstruct program shapes ({e}); "
                       f"verifying specs without divisibility")
    for name, os_ in (doc.get("ops") or {}).items():
        for i, sp in enumerate(os_.get("outputs") or ()):
            if sp is None:
                continue
            shape = None
            shapes = out_shapes.get(name)
            if shapes and i < len(shapes):
                shape = shapes[i]
            _check_spec(report, axis_sizes, name, f"output[{i}]",
                        _json_spec(sp), shape)
        for wname, sp in (os_.get("weights") or {}).items():
            if sp is None:
                continue
            _check_spec(report, axis_sizes, name, f"weight {wname!r}",
                        _json_spec(sp),
                        weight_shapes.get(name, {}).get(wname),
                        seam="checkpoint-restore")
    for tname, sp in (doc.get("inputs") or {}).items():
        if sp is not None:
            _check_spec(report, axis_sizes, tname, "input",
                        _json_spec(sp), None)
    for b in doc.get("banks") or ():
        K = len(b.get("members") or ())
        B = 1
        bad = []
        for a in b.get("axes") or ():
            if a not in axis_sizes:
                bad.append(a)
            B *= axis_sizes.get(a, 1)
        name = f"bank[{'+'.join((b.get('members') or ['?'])[:2])}]"
        if bad:
            report.add("seam", "error", name,
                       f"bank axes {bad} are not mesh axes",
                       "bank-boundary")
        if K and K % max(B, 1) != 0:
            report.add("seam", "error", name,
                       f"bank degree {B} does not divide member count "
                       f"{K}", "bank-boundary")
    # placement annotations (axis_tiers / collective_trees): tier
    # soundness, tree-phase coverage, and the latency-bound-across-DCN
    # rejection — the machine constants come from the file's meta block
    # when present, else the MachineSpec defaults
    spec = None
    meta = doc.get("meta") or {}
    if meta.get("machine_file"):
        try:
            from ..parallel.machine import MachineSpec
            spec = MachineSpec.from_file(meta["machine_file"])
        except Exception:  # noqa: BLE001 — fall to defaults
            spec = None
    _check_placement(report, doc.get("axis_tiers") or {},
                     doc.get("collective_trees") or (), axis_sizes,
                     spec)
    # subset-group membership, shared by the zero check (unaddressable
    # state) and the overlap check (divergent bucket launch) — ONE walk
    # so a future group kind cannot go missing from one of them
    grouped: Dict[str, str] = {}
    for b in doc.get("banks") or ():
        for m in b.get("members") or ():
            grouped[m] = "bank"
    for g in doc.get("place_groups") or ():
        for m in g.get("members") or ():
            grouped[m] = "place-group"
    # per-parameter ZeRO assignment (doc["zero"]): axis soundness,
    # divisibility (when the program's weight shapes are known), and
    # the weight-axis-overlap rejection
    zdoc = doc.get("zero")
    if zdoc:
        from ..runtime.zero import ZeroAssignment
        w_specs = {
            name: {w: _json_spec(s)
                   for w, s in (os_.get("weights") or {}).items()
                   if s is not None}
            for name, os_ in (doc.get("ops") or {}).items()}
        _check_zero(report, ZeroAssignment.from_json(zdoc), w_specs,
                    weight_shapes, axis_sizes,
                    have_layers=bool(weight_shapes),
                    unaddressable=grouped)
    # quantized grad-sync plan (doc["qsync"]): wire/tier soundness,
    # the quantized-phase-on-declared-tier rule, and the replicated-
    # math-seam rejection (sharded weights stay full-precision)
    qdoc = doc.get("qsync")
    if qdoc:
        w_specs = {
            name: {w: _json_spec(s)
                   for w, s in (os_.get("weights") or {}).items()
                   if s is not None}
            for name, os_ in (doc.get("ops") or {}).items()}
        _check_qsync(report, qdoc, doc.get("axis_tiers") or {},
                     w_specs, axis_sizes,
                     have_layers=bool(weight_shapes),
                     known_layers=set(weight_shapes),
                     unaddressable=grouped)
    # overlapped grad-sync schedule (doc["overlap"]): launch-order
    # totality, member disjointness/subset-group exclusion, and — when
    # the file carries the serialized program — backward-completion
    # order consistency via the recorded layer order
    ovdoc = doc.get("overlap")
    if ovdoc:
        prog_layers = (prog or {}).get("layers") or ()
        pos = {ls["name"]: i for i, ls in enumerate(prog_layers)}
        op_types = {}
        from ..ffconst import OperatorType
        for ls in prog_layers:
            try:
                op_types[ls["name"]] = OperatorType[ls["op_type"]]
            except KeyError:
                op_types[ls["name"]] = None
        _check_overlap(report, ovdoc, grouped=grouped, pos=pos,
                       op_types=op_types, have_layers=bool(op_types))
    # per-op kernel implementations (doc["kernel_impls"]): registered
    # impl names + availability predicates on the recorded mesh/shapes;
    # 'ring' without a seq axis in mesh_axes is the pinned rejection
    kdoc = doc.get("kernel_impls")
    if kdoc:
        from ..kernels import registry as kreg
        attn_ctxs: Dict[str, Dict[str, Any]] = {}
        known: set = set()
        prog_layers = (prog or {}).get("layers") or ()
        if prog_layers:
            from ..search.serialization import _param_from_json
            for ls in prog_layers:
                known.add(ls["name"])
                if ls.get("op_type") not in ("OP_MULTIHEAD_ATTENTION",
                                             "OP_LATENT_ATTENTION"):
                    continue
                latent = ls["op_type"] == "OP_LATENT_ATTENTION"
                try:
                    params = {k: _param_from_json(v)
                              for k, v in ls.get("params", {}).items()}
                    shapes = out_shapes.get(ls["name"])
                    q_len = int(shapes[0][1]) \
                        if shapes and len(shapes[0]) > 1 else 0
                    attn_ctxs[ls["name"]] = kreg.attention_ctx(
                        params, q_len, q_len,
                        seq_degree=axis_sizes.get("seq", 0),
                        latent=latent)
                except Exception:  # noqa: BLE001 — shape unknown ≠ unsound
                    # minimal ctx: mesh-level predicates (the ring seq
                    # axis) still bind; shape-level ones pass open
                    attn_ctxs[ls["name"]] = kreg.attention_ctx(
                        {}, 0, 0, seq_degree=axis_sizes.get("seq", 0),
                        latent=latent)
        _check_kernel(report, kdoc, axis_sizes, attn_ctxs,
                      have_layers=bool(prog_layers),
                      known_layers=known)
    # per-(model, batch-class) serving block (doc["serving"]): bucket
    # structure, per-bucket spec soundness, and KV-shard/GQA
    # divisibility — the envelope gate needs live layer shapes and is
    # enforced at compile/search time instead
    sdoc = doc.get("serving")
    if sdoc:
        _check_serving(report, sdoc, {}, axis_sizes, spec, None)
    report.duration_s = time.perf_counter() - t0
    return report


def _json_spec(j):
    """JSON spec form → PartitionSpec-like tuple (no jax import)."""
    return tuple(tuple(e) if isinstance(e, list) else e for e in j)


def _program_shapes(prog):
    """Re-infer every recorded layer's output + weight shapes from a
    serialized program (search/serialization.program_to_json form).
    Graph inputs carry no shapes in the wire format, so layers whose
    inputs reach back to them are skipped (shape unknown ≠ unsound)."""
    from ..ffconst import OperatorType
    from ..ops import get_op_def
    out_shapes: Dict[str, List[Tuple[int, ...]]] = {}
    out_dtypes: Dict[str, List[Any]] = {}
    weight_shapes: Dict[str, Dict[str, Tuple[int, ...]]] = {}
    from ..search.serialization import _param_from_json
    for ls in prog.get("layers", ()):
        shapes, dtypes = [], []
        known = True
        for ref in ls["inputs"]:
            if "op" in ref and ref["op"] in out_shapes:
                src_shapes = out_shapes[ref["op"]]
                src_dtypes = out_dtypes[ref["op"]]
                if ref["idx"] < len(src_shapes):
                    shapes.append(src_shapes[ref["idx"]])
                    dtypes.append(src_dtypes[ref["idx"]])
                    continue
            known = False
            break
        if not known:
            continue
        try:
            params = {k: _param_from_json(v)
                      for k, v in ls["params"].items()}
            op = get_op_def(OperatorType[ls["op_type"]])
            outs = op.infer(params, shapes, dtypes)
            out_shapes[ls["name"]] = [tuple(s) for s, _ in outs]
            out_dtypes[ls["name"]] = [d for _, d in outs]
            weight_shapes[ls["name"]] = {
                w.name: tuple(w.shape)
                for w in op.weights(params, shapes, dtypes) or ()}
        except Exception:  # noqa: BLE001 — unknown op: skip its shapes
            continue
    return out_shapes, weight_shapes
