"""Quantized gradient collectives: int8/fp8 sync as a searched choice.

EQuARX (PAPERS.md, arXiv 2506.17615) shows that an all-reduce whose
wire payload is int8/fp8 with per-chunk scaling and error-feedback
recovers most of the slow-fabric bandwidth at negligible accuracy cost.
This module makes that a first-class, *searched* decision:

  - **kernels** — in-jit quantize → collective → dequantize, built from
    portable collectives (``all_to_all`` reduce-scatter leg +
    ``all_gather``) so the wire bytes really shrink: per-chunk absolute-
    max scaling (:data:`QSYNC_CHUNK` elements per scale), round-to-
    nearest int8 or a direct fp8 cast, and **error feedback** — each
    device carries the quantization error it withheld as a residual and
    re-injects it next step, so the bias never accumulates;
  - **plan** — :class:`QsyncPlan` records, per gradient tensor, the
    wire dtype of each *phase* of its sync (PR 9's reduction trees make
    the DCN leg an explicit phase: quantize it, keep the ICI legs
    full-precision). Planned by :func:`plan_qsync` from the same
    calibrated cost model that prices the rest of the search, gated by
    ``FFConfig.quantized_collectives`` (off/auto/dcn_only/all),
    serialized with the strategy (``--import`` honors it verbatim) and
    statically checked by ``analysis/plan_verifier``;
  - **runtime state** — the error-feedback residual is sharding-aware
    runtime state: one leaf of shape ``(degree,) + grad.shape`` per
    quantized tensor, dim 0 sharded over the sync axes so each device
    holds exactly its own residual. It rides in the optimizer-state
    tree under :data:`RESIDUAL_SLOT` (stripped before the optimizer
    update), checkpoints with it, and survives elastic world changes by
    **sum-folding** (:func:`refit_residual`) — merging devices sums
    their withheld gradient mass, so no error is lost or double-counted.

The runtime path executes only on plans it can honor exactly
(:func:`runtime_schedule`): pure data-parallel programs whose weights
are replicated. Everything else keeps the implicit GSPMD sync — and
with the flag off (the default) nothing here runs at all, pinned
bit-exact by ``tools/quantized_sync_smoke.py``.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import events as obs_events
from ..parallel.placement import (QSYNC_CHUNK, WIRE_ITEMSIZE,
                                  wire_byte_scale)

__all__ = ["RESIDUAL_SLOT", "QsyncPlan", "resolve_qsync_mode",
           "wire_available", "quantize_chunked", "dequantize_chunked",
           "quantized_all_reduce", "phased_sync", "plan_qsync",
           "runtime_schedule", "init_residuals", "refit_residual",
           "sharded_grads"]

#: reserved optimizer-state slot carrying the error-feedback residuals —
#: stripped before ``optimizer.update`` (executor), checkpointed with
#: the rest of the state, special-cased by restore for world changes
RESIDUAL_SLOT = "qsync_residual"

QSYNC_MODES = ("off", "auto", "dcn_only", "all")

_QMAX = {"int8": 127.0}


def _wire_jnp(wire: str):
    import jax.numpy as jnp
    return {"int8": jnp.int8,
            "float8_e4m3": jnp.float8_e4m3fn,
            "float8_e5m2": jnp.float8_e5m2}[wire]


def wire_available(wire: str) -> bool:
    """Whether this wire dtype exists in the installed jax/ml_dtypes."""
    try:
        _wire_jnp(wire)
        return True
    except Exception:  # noqa: BLE001 — absent dtype = unavailable
        return False


def resolve_qsync_mode(cfg=None) -> str:
    """Resolve the quantized-collectives opt-in: the
    ``FF_QUANTIZED_COLLECTIVES`` env var wins when set (how the smokes
    and bench drive subprocesses), else ``FFConfig.
    quantized_collectives``; default ``"off"`` — the bit-exact path.
    ``"disable"`` (the ``--no-quantized-collectives`` spelling) also
    resolves off — see :func:`qsync_disabled` for its stronger
    meaning."""
    env = os.environ.get("FF_QUANTIZED_COLLECTIVES", "").strip().lower()
    mode = env or str(getattr(cfg, "quantized_collectives", "off")
                      or "off").lower()
    if mode in ("", "false", "no", "0", "disable", "disabled"):
        mode = "off"
    if mode in ("true", "yes", "1", "on"):
        mode = "auto"
    if mode not in QSYNC_MODES:
        raise ValueError(f"unknown quantized_collectives mode {mode!r} "
                         f"(expected one of {QSYNC_MODES})")
    return mode


def qsync_disabled(cfg=None) -> bool:
    """True when quantization is EXPLICITLY disabled — the env var set
    to an off value, or ``quantized_collectives="disable"`` (what
    ``--no-quantized-collectives`` parses to). Distinct from the plain
    default ``"off"``: an imported strategy's qsync plan is honored
    verbatim under the default, but an explicit disable STRIPS it
    (``FFModel._plan_qsync``) so a user can A/B an exported quantized
    strategy against full precision."""
    env = os.environ.get("FF_QUANTIZED_COLLECTIVES", "").strip().lower()
    if env in ("off", "false", "no", "0", "disable", "disabled"):
        return True
    return str(getattr(cfg, "quantized_collectives", "") or "").lower() \
        in ("disable", "disabled")


def resolve_qsync_wire(cfg=None) -> str:
    """Wire dtype for quantized legs: ``FF_QSYNC_WIRE`` / ``FFConfig.
    qsync_wire``, default int8 (fp8 variants fall back to int8 when the
    installed jax lacks the dtype)."""
    wire = os.environ.get("FF_QSYNC_WIRE", "").strip().lower() \
        or str(getattr(cfg, "qsync_wire", "int8") or "int8").lower()
    if wire not in WIRE_ITEMSIZE:
        raise ValueError(f"unknown qsync wire dtype {wire!r} "
                         f"(expected one of {sorted(WIRE_ITEMSIZE)})")
    if not wire_available(wire):
        return "int8"
    return wire


# ---------------------------------------------------------------------------
# kernels (in-jit; shard_map-body helpers)
# ---------------------------------------------------------------------------

def quantize_chunked(x, wire: str):
    """Per-chunk absolute-max quantization of a float array whose last
    dim is the chunk dim: returns ``(q, scale)`` with ``q`` in the wire
    dtype and ``scale`` float32 broadcastable over the chunk. int8
    rounds to nearest (±127 range); fp8 is a direct cast after
    scaling to the format's finite max."""
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if wire == "int8":
        qmax = _QMAX["int8"]
    else:
        qmax = float(jnp.finfo(_wire_jnp(wire)).max)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    y = x / scale
    if wire == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(_wire_jnp(wire))
    return q, scale


def dequantize_chunked(q, scale):
    import jax.numpy as jnp
    return q.astype(jnp.float32) * scale


def _group_index(axes: Sequence[str], sizes: Dict[str, int]):
    """Flat index of this device within the ``axes`` product group, in
    the same (first-axis-major) order jax's tuple-axis collectives
    enumerate the group."""
    import jax
    idx = None
    for a in axes:
        k = jax.lax.axis_index(a)
        idx = k if idx is None else idx * sizes[a] + k
    return idx


def quantized_all_reduce(x, axes: Tuple[str, ...], wire: str,
                         degree: int, sizes: Dict[str, int],
                         residual=None):
    """Error-feedback quantized all-reduce (SUM) over ``axes`` — call
    inside a shard_map body.

    Structure (EQuARX): quantize the full local vector per chunk →
    ``all_to_all`` the wire payload (the reduce-scatter leg: device i
    receives every device's chunks of segment i) → dequantize +
    accumulate in fp32 → requantize the reduced segment →
    ``all_gather`` the wire payload → dequantize. Only quantized bytes
    (plus one fp32 scale per :data:`QSYNC_CHUNK` elements) ever cross
    the fabric.

    Error feedback: ``residual`` (this device's withheld error from the
    previous step, same shape as ``x``) is added before quantization;
    the returned residual is the new local quantization error, with the
    owner's requantization error of the gather leg folded into its own
    segment. Returns ``(sum_over_group, new_residual)``.
    """
    import jax
    import jax.numpy as jnp
    shape = x.shape
    flat = x.astype(jnp.float32).ravel()
    if residual is not None:
        flat = flat + residual.astype(jnp.float32).ravel()
    n = flat.size
    unit = degree * QSYNC_CHUNK
    pad = (-n) % unit
    if pad:
        flat_p = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    else:
        flat_p = flat
    seg = flat_p.reshape(degree, -1, QSYNC_CHUNK)      # (d, k, C)
    k = seg.shape[1]
    q, s = quantize_chunked(seg, wire)
    r_new = flat_p - dequantize_chunked(q, s).ravel()
    # reduce-scatter leg: after all_to_all, this device holds every
    # group member's chunks of ITS segment
    q2 = jax.lax.all_to_all(q, axes, 0, 0)
    s2 = jax.lax.all_to_all(s, axes, 0, 0)
    red = jnp.sum(dequantize_chunked(q2, s2), axis=0)  # (k, C)
    qr, sr = quantize_chunked(red, wire)
    # the gather leg's requantization error belongs to the segment
    # OWNER (this device) — fold it into the residual at its own range
    gerr = (red - dequantize_chunked(qr, sr)).ravel()
    start = _group_index(axes, sizes) * (k * QSYNC_CHUNK)
    cur = jax.lax.dynamic_slice(r_new, (start,), (k * QSYNC_CHUNK,))
    r_new = jax.lax.dynamic_update_slice(r_new, cur + gerr, (start,))
    ag_q = jax.lax.all_gather(qr, axes, tiled=True)    # (d*k, C)
    ag_s = jax.lax.all_gather(sr, axes, tiled=True)
    out = dequantize_chunked(ag_q, ag_s).ravel()[:n].reshape(shape)
    return out, r_new[:n].reshape(shape)


def _add_at(buf, delta, start):
    """buf[start:start+len(delta)] += delta with a traced offset."""
    import jax
    cur = jax.lax.dynamic_slice(buf, (start,), (delta.shape[0],))
    return jax.lax.dynamic_update_slice(buf, cur + delta, (start,))


def phased_sync(x, phases: Sequence[Tuple[Tuple[str, ...],
                                          Optional[str]]],
                sizes: Dict[str, int], residual=None):
    """Gradient MEAN over the ordered inner→outer ``phases`` — call
    inside a shard_map body. Each phase is ``(axes, wire)``:
    ``wire=None`` is full-precision, a wire name a quantized leg.

    Multi-phase syncs execute as the real hierarchical tree — inner
    legs reduce-scatter (so the outer fabric only ever carries the
    tier-reduced volume, PR 9's two-phase shape), the outermost leg
    all-reduces, then the inner legs all-gather back — with each leg's
    payload in its phase's wire dtype. Error feedback: ``residual``
    (this device's withheld error, pre-sync gradient space) is added up
    front; every quantized leg's local error is accumulated back at the
    offset of the window this device owned at that depth, so next
    step's staged reduction re-injects each error exactly where (and
    exactly once) it was withheld. Returns ``(mean, new_residual)`` —
    ``new_residual`` is None when no phase quantizes."""
    import jax
    import jax.numpy as jnp
    shape = x.shape
    active: List[Tuple[Tuple[str, ...], Optional[str], int]] = []
    total = 1
    for axes, wire in phases:
        d = 1
        for a in axes:
            d *= int(sizes.get(a, 1))
        if d <= 1:
            continue
        active.append((tuple(axes), wire, d))
        total *= d
    if not active:
        return x, residual
    any_q = any(w for _, w, _ in active)
    if not any_q:
        out = x.astype(jnp.float32)
        for axes, _w, _d in active:
            out = jax.lax.psum(out, axes)
        return (out / total).astype(x.dtype), residual
    if len(active) == 1:
        axes, wire, d = active[0]
        if wire is None:
            return jax.lax.psum(x.astype(jnp.float32), axes) / total, \
                residual
        out, r_new = quantized_all_reduce(
            x, axes, wire, d, sizes, residual=residual)
        return out / total, r_new
    # staged hierarchical sync
    flat = x.astype(jnp.float32).ravel()
    n = flat.size
    if residual is not None:
        flat = flat + residual.astype(jnp.float32).ravel()
    unit = total * QSYNC_CHUNK
    pad = (-n) % unit
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    err = jnp.zeros_like(flat)
    cur = flat
    start = jnp.int32(0)      # offset of this device's window in flat
    starts: List[Any] = []    # window offset stack, one per down-leg
    down, (last_axes, last_wire, last_d) = active[:-1], active[-1]
    for axes, wire, d in down:
        seglen = cur.shape[0] // d
        gi = _group_index(axes, sizes)
        if wire is None:
            cur = jax.lax.psum_scatter(cur, axes, scatter_dimension=0,
                                       tiled=True)
        else:
            q, s = quantize_chunked(
                cur.reshape(d, -1, QSYNC_CHUNK), wire)
            e = cur - dequantize_chunked(q, s).ravel()
            err = _add_at(err, e, start)
            q2 = jax.lax.all_to_all(q, axes, 0, 0)
            s2 = jax.lax.all_to_all(s, axes, 0, 0)
            cur = jnp.sum(dequantize_chunked(q2, s2), axis=0).ravel()
        start = start + gi * seglen
        starts.append(start)
    if last_wire is None:
        cur = jax.lax.psum(cur, last_axes)
    else:
        cur, e = quantized_all_reduce(cur, last_axes, last_wire,
                                      last_d, sizes, residual=None)
        err = _add_at(err, e.ravel(), start)
    deeper = []               # degree product of the phases after leg k
    p = last_d
    for _axes, _wire, d in reversed(down):
        deeper.insert(0, p)
        p *= d
    for (axes, wire, d), st, dp in zip(reversed(down), reversed(starts),
                                       reversed(deeper)):
        if wire is None:
            cur = jax.lax.all_gather(cur, axes, tiled=True)
        else:
            # the requantization error of the gather payload belongs at
            # the window held going INTO this leg — and at this point
            # the ``dp`` devices sharing that window hold IDENTICAL
            # reduced values, so the identical error is scaled by 1/dp:
            # next step's staged reduction sums the copies back to
            # exactly one error mass
            q, s = quantize_chunked(
                cur.reshape(-1, QSYNC_CHUNK), wire)
            e = cur - dequantize_chunked(q, s).ravel()
            err = _add_at(err, e / dp, st)
            qg = jax.lax.all_gather(q, axes, tiled=True)
            sg = jax.lax.all_gather(s, axes, tiled=True)
            cur = dequantize_chunked(qg, sg).ravel()
    out = (cur[:n] / total).reshape(shape)
    return out, err[:n].reshape(shape)


# ---------------------------------------------------------------------------
# the per-tensor / per-phase plan
# ---------------------------------------------------------------------------

class QsyncPlan:
    """Per-tensor, per-phase wire-dtype plan for gradient sync.

    ``decisions`` maps layer name -> weight name -> a record dict::

        {"wire": "int8" | "float8_e4m3" | "float8_e5m2" | None,
         "phases": [{"axes": [..], "tier": str, "wire": str | None}],
         "baseline_s": float,     # predicted full-precision sync cost
         "quantized_s": float}    # predicted cost of this plan

    ``wire=None`` (or no quantized phase) keeps that tensor full-
    precision. Serializes with the strategy (``search/serialization``)
    so ``--import`` honors the decision verbatim, and is statically
    checked by ``analysis/plan_verifier``'s qsync pass.
    """

    def __init__(self, decisions: Optional[Dict[str, Dict[str, Dict]]]
                 = None, mode: str = "auto", wire: str = "int8"):
        self.decisions: Dict[str, Dict[str, Dict]] = decisions or {}
        self.mode = mode
        self.wire = wire

    def record_for(self, layer: str, wname: str) -> Optional[Dict]:
        return self.decisions.get(layer, {}).get(wname)

    def phases_for(self, layer: str, wname: str
                   ) -> Optional[List[Tuple[Tuple[str, ...],
                                            Optional[str]]]]:
        rec = self.record_for(layer, wname)
        if rec is None:
            return None
        return [(tuple(p.get("axes") or ()), p.get("wire"))
                for p in rec.get("phases", ())]

    def quantized_params(self) -> List[Tuple[str, str]]:
        out = []
        for lname, ws in self.decisions.items():
            for wname, rec in ws.items():
                if any(p.get("wire") for p in rec.get("phases", ())):
                    out.append((lname, wname))
        return out

    def __len__(self) -> int:
        return sum(len(ws) for ws in self.decisions.values())

    def __bool__(self) -> bool:
        return len(self.quantized_params()) > 0

    def summary(self) -> Dict[str, Any]:
        q = self.quantized_params()
        return {
            "mode": self.mode, "wire": self.wire,
            "n_params": len(self), "n_quantized": len(q),
            "baseline_s_total": sum(
                rec.get("baseline_s", 0.0)
                for ws in self.decisions.values()
                for rec in ws.values()),
            "quantized_s_total": sum(
                rec.get("quantized_s", 0.0)
                for ws in self.decisions.values()
                for rec in ws.values()),
        }

    def to_json(self) -> Dict[str, Any]:
        return {"mode": self.mode, "wire": self.wire,
                "decisions": self.decisions}

    @classmethod
    def from_json(cls, doc: Optional[Dict[str, Any]]
                  ) -> Optional["QsyncPlan"]:
        if not doc:
            return None
        return cls(dict(doc.get("decisions", {})),
                   mode=str(doc.get("mode", "auto")),
                   wire=str(doc.get("wire", "int8")))


def _tier_phases(dmesh, strategy) -> List[Tuple[Tuple[str, ...], str]]:
    """Mesh axes grouped by hardware tier, innermost tier first — the
    phase skeleton both the planner and the runtime share. The adopted
    strategy's ``axis_tiers`` is the ground truth when present (it is
    what the verifier checks against); a tierless machine is one "ici"
    phase spanning every axis."""
    from ..parallel.topology import TIER_RANK
    sizes = dict(dmesh.axis_sizes)
    tiers = dict(getattr(strategy, "axis_tiers", None) or {})
    if not tiers:
        try:
            tiers = dict(dmesh.axis_tiers)
        except Exception:  # noqa: BLE001 — tierless machine
            tiers = {}
    by_tier: Dict[str, List[str]] = {}
    for a in sizes:
        by_tier.setdefault(tiers.get(a, "ici"), []).append(a)
    return [(tuple(by_tier[t]), t)
            for t in sorted(by_tier, key=lambda t: TIER_RANK.get(t, 99))]


def plan_qsync(strategy, layers: Sequence, dmesh, cost_model, *,
               mode: str = "auto", wire: str = "int8"
               ) -> Optional["QsyncPlan"]:
    """Plan per-tensor, per-phase gradient-sync precision for an
    adopted strategy.

    Scores every trainable replicated-weight parameter's sync at full
    precision vs with its slow legs quantized, through
    ``OpCostModel.quantized_sync_quote`` (the calibrated wire-dtype
    rows / itemsize-scaled fallback — the same pricing the search used
    with the policy attached). The accuracy-risk gate is structural:
    only the *gradient all-reduce of replicated weights* may quantize —
    sharded weights' per-op collectives (replicated-math seams) and
    bank / place-group / pipeline state always stay full-precision.
    Returns None when nothing quantizes."""
    import time
    t0 = time.perf_counter()
    if mode == "off":
        return None
    if getattr(strategy, "pipeline", None) is not None:
        return None
    axis_sizes = dict(dmesh.axis_sizes)
    n_dev = 1
    for s in axis_sizes.values():
        n_dev *= s
    if n_dev <= 1:
        return None
    from ..dtypes import itemsize
    from ..ops import ensure_weight_specs
    from ..runtime.zero import spec_degree
    grouped: set = set()
    for bk in getattr(strategy, "banks", None) or ():
        grouped.update(bk.members)
    for pg in getattr(strategy, "place_groups", None) or ():
        grouped.update(pg.members)
    skeleton = _tier_phases(dmesh, strategy)
    has_dcn = any(t == "dcn" for _, t in skeleton)
    if mode == "dcn_only" and not has_dcn:
        return None
    ops = getattr(strategy, "ops", {})
    plan = QsyncPlan({}, mode=mode, wire=wire)
    for layer in layers:
        if layer.name in grouped or not getattr(layer, "trainable", True):
            continue
        if not ensure_weight_specs(layer):
            continue
        os_ = ops.get(layer.name)
        for w in layer.weights or ():
            wspec = os_.weights.get(w.name) if os_ is not None else None
            if spec_degree(wspec, axis_sizes) > 1:
                continue   # replicated-math seam: stays full-precision
            wbytes = float(int(np.prod(w.shape)) or 1) * itemsize(w.dtype)
            quote = cost_model.quantized_sync_quote(
                wbytes, n_dev, skeleton, mode=mode, wire=wire)
            if quote is None:
                continue
            base_s, quant_s, phase_wires = quote
            if not any(phase_wires):
                continue
            plan.decisions.setdefault(layer.name, {})[w.name] = {
                "wire": wire,
                "phases": [{"axes": list(axes), "tier": tier, "wire": pw}
                           for (axes, tier), pw in zip(skeleton,
                                                       phase_wires)],
                "baseline_s": float(base_s),
                "quantized_s": float(quant_s),
            }
    if not plan:
        return None
    from ..obs.metrics_registry import REGISTRY
    s = plan.summary()
    REGISTRY.counter(
        "ff_qsync_plans_total",
        "Quantized-collective plans adopted by mode").inc(mode=mode)
    REGISTRY.gauge(
        "ff_qsync_quantized_params",
        "Gradient tensors whose sync the last adopted plan "
        "quantized").set(s["n_quantized"])
    obs_events.record_span("qsync.plan", t0, time.perf_counter() - t0,
                           mode=mode, n_quantized=s["n_quantized"])
    return plan


def audit_record(plan: QsyncPlan) -> Dict[str, Any]:
    """The strategy-audit ``"quantized_sync"`` section: summary plus
    every tensor's per-phase wire choice with both predicted costs."""
    per_param = []
    for lname, ws in plan.decisions.items():
        for wname, rec in ws.items():
            per_param.append({
                "param": f"{lname}/{wname}",
                "wire": rec.get("wire"),
                "phases": [
                    {"tier": p.get("tier"),
                     "wire": p.get("wire") or "float32"}
                    for p in rec.get("phases", ())],
                "baseline_s": rec.get("baseline_s", 0.0),
                "quantized_s": rec.get("quantized_s", 0.0),
            })
    return {**plan.summary(), "per_param": per_param}


# ---------------------------------------------------------------------------
# runtime: the explicit-sync training path
# ---------------------------------------------------------------------------

class QsyncSchedule:
    """Resolved executable schedule: the plan plus the mesh facts the
    shard_map body needs (axis sizes, total degree)."""

    def __init__(self, plan: QsyncPlan, dmesh):
        self.plan = plan
        self.axes: Tuple[str, ...] = tuple(dmesh.axis_sizes.keys())
        self.sizes: Dict[str, int] = dict(dmesh.axis_sizes)
        self.degree = 1
        for s in self.sizes.values():
            self.degree *= s

    def phases_for(self, layer: str, wname: str
                   ) -> List[Tuple[Tuple[str, ...], Optional[str]]]:
        phases = self.plan.phases_for(layer, wname)
        if phases is None:
            return [(self.axes, None)]
        return phases


def runtime_schedule(program, strategy, config, dmesh
                     ) -> Optional[QsyncSchedule]:
    """Build the executable quantized-sync schedule, or None when the
    configuration cannot honor the plan exactly — the caller keeps the
    implicit (GSPMD) sync. The explicit path requires a pure data-
    parallel program: gradient sync is the ONLY cross-device collective
    it owns, so weights must be replicated, no pipeline / bank /
    place-group subsets, no stateful ops (their per-device state would
    silently diverge), and no gradient accumulation."""
    plan = getattr(strategy, "qsync", None)
    if plan is None or not plan.quantized_params():
        return None

    def fallback(why: str) -> None:
        import logging
        obs_events.counter("qsync.runtime_fallbacks")
        logging.getLogger("flexflow_tpu").warning(
            "quantized-collectives plan present but the runtime path "
            "is ineligible (%s); keeping the implicit full-precision "
            "sync", why)

    if getattr(strategy, "pipeline", None) is not None:
        fallback("pipelined region")
        return None
    if (getattr(strategy, "banks", None)
            or getattr(strategy, "place_groups", None)):
        fallback("bank/place-group subsets")
        return None
    if max(getattr(config, "gradient_accumulation_steps", 1), 1) > 1:
        fallback("gradient accumulation")
        return None
    n = 1
    for s in dmesh.axis_sizes.values():
        n *= s
    if n <= 1:
        return None
    from ..ops import get_op_def
    from ..runtime.zero import spec_degree
    axis_sizes = dict(dmesh.axis_sizes)
    ops = getattr(strategy, "ops", {})
    for layer in program.layers:
        os_ = ops.get(layer.name)
        for w in layer.weights or ():
            sp = os_.weights.get(w.name) if os_ is not None else None
            if spec_degree(sp, axis_sizes) > 1:
                fallback(f"sharded weight {layer.name}/{w.name}")
                return None
        state_spec = getattr(get_op_def(layer.op_type), "state_spec",
                             None)
        if state_spec is not None and state_spec(
                layer.params, [t.shape for t in layer.inputs],
                [t.dtype for t in layer.inputs]):
            fallback(f"stateful op {layer.name}")
            return None
    for t in program.input_tensors:
        if t.get_tensor() is not None:
            continue       # baked constant, not a per-batch input
        if not t.shape or t.shape[0] % n != 0:
            fallback(f"input {t.name} batch dim not divisible by {n}")
            return None
    return QsyncSchedule(plan, dmesh)


def init_residuals(schedule: QsyncSchedule, program, dmesh
                   ) -> Dict[str, Dict[str, Any]]:
    """Zero error-feedback residuals for every quantized tensor: shape
    ``(degree,) + weight.shape`` float32, dim 0 sharded over the sync
    axes via ``reshard.place_host`` so each device materializes only
    its own row. Keyed like the params tree, stored under
    :data:`RESIDUAL_SLOT` in the optimizer state."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel import reshard as reshard_mod
    by_name = {l.name: l for l in program.layers}
    quantized = set(schedule.plan.quantized_params())
    out: Dict[str, Dict[str, Any]] = {}
    spec0 = schedule.axes[0] if len(schedule.axes) == 1 \
        else tuple(schedule.axes)
    for (lname, wname) in sorted(quantized):
        layer = by_name.get(lname)
        if layer is None:
            continue
        wshape = None
        for w in layer.weights or ():
            if w.name == wname:
                wshape = tuple(w.shape)
        if wshape is None:
            continue
        arr = np.zeros((schedule.degree,) + wshape, np.float32)
        sh = NamedSharding(dmesh.mesh,
                           P(spec0, *([None] * len(wshape))))
        out.setdefault(lname, {})[wname] = \
            reshard_mod.place_host(arr, sh)
    return out


def refit_residual(arr: np.ndarray, new_degree: int) -> np.ndarray:
    """Re-fit a saved residual ``(d_old,) + shape`` to a world of
    ``new_degree`` sync participants. Residuals are per-device withheld
    gradient mass whose SUM is what error feedback re-injects, so:
    merging devices sum-folds their rows, growing worlds keep the old
    rows and zero-fill the new ones, and a non-divisible change folds
    everything into row 0 — in every case total withheld mass is
    preserved exactly."""
    arr = np.asarray(arr, np.float32)
    d_old = arr.shape[0]
    if d_old == new_degree:
        return arr
    rest = arr.shape[1:]
    if d_old % new_degree == 0:
        return arr.reshape((new_degree, d_old // new_degree) + rest
                           ).sum(axis=1)
    out = np.zeros((new_degree,) + rest, np.float32)
    if new_degree % d_old == 0:
        out[:d_old] = arr
    else:
        out[0] = arr.sum(axis=0)
    return out


def strip_residual(opt_state):
    """(residual_tree_or_None, opt_state_without_slot) — the executor
    separates the residuals before the optimizer update (optimizers
    rebuild their slot dict and would silently drop a foreign slot)."""
    if not isinstance(opt_state, dict) or RESIDUAL_SLOT not in opt_state:
        return None, opt_state
    return (opt_state[RESIDUAL_SLOT],
            {k: v for k, v in opt_state.items() if k != RESIDUAL_SLOT})


def sharded_grads(executor, params, state, batch, step, residual):
    """The explicit-sync replacement for ``jax.grad`` + implicit GSPMD
    gradient sync: one shard_map over the whole mesh computes each
    device's LOCAL gradients (full fwd+bwd on its batch shard, weights
    replicated), then syncs every gradient tensor explicitly — plain
    ``psum`` legs at full precision, quantized all-reduce legs on the
    wire dtype the plan chose, error-feedback residuals carried in and
    out. Metrics sync with their proper reductions (means average,
    counts sum, RMS combines in the square domain). Returns
    ``(grads, metrics, new_residuals)`` — grads/metrics replicated,
    residuals sharded over the sync axes.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..runtime import metrics as metrics_mod
    from jax import shard_map
    sched: QsyncSchedule = executor._qsync
    axes = sched.axes
    sizes = sched.sizes
    n = sched.degree
    spec0 = axes[0] if len(axes) == 1 else tuple(axes)
    residual = residual or {}

    def body(params_l, state_l, batch_l, res_l):
        shard_index = _group_index(axes, sizes)

        def loss_fn(p):
            # shard_index marks shard-local emission: absolute-batch-
            # shape ops rescale and per-device dropout streams
            # decorrelate (matching the global path's independent
            # per-row masks in distribution)
            outs, _, aux, capture = executor._forward(
                p, state_l, batch_l, True, step, strategy=None,
                shard_index=shard_index)
            loss, bm = executor._loss_and_metrics(
                outs, capture, batch_l["label"], aux)
            return loss, bm
        g, bm = jax.grad(loss_fn, has_aux=True)(params_l)
        new_res: Dict[str, Dict[str, Any]] = {}
        synced: Dict[str, Dict[str, Any]] = {}
        for lname, ws in g.items():
            sl: Dict[str, Any] = {}
            for wname, leaf in ws.items():
                phases = sched.phases_for(lname, wname)
                r = res_l.get(lname, {}).get(wname)
                out, r_new = phased_sync(
                    leaf, phases, sizes,
                    residual=None if r is None else r[0])
                sl[wname] = out.astype(leaf.dtype)
                if r is not None:
                    # keep the slot even when the plan left this leaf
                    # full-precision (structure must round-trip)
                    new_res.setdefault(lname, {})[wname] = \
                        (r[0] if r_new is None else r_new)[None]
            synced[lname] = sl

        def sync_metric(k, v):
            if metrics_mod.is_count(k):
                return jax.lax.psum(v, axes)
            if k in metrics_mod.RMS_KEYS:
                return jnp.sqrt(jax.lax.psum(v * v, axes) / n)
            return jax.lax.psum(v, axes) / n

        bm = {k: sync_metric(k, v) for k, v in bm.items()}
        return synced, bm, new_res

    rep = P()
    batch_specs = jax.tree.map(
        lambda a: P(spec0, *([None] * (a.ndim - 1))), batch)
    res_specs = jax.tree.map(
        lambda a: P(spec0, *([None] * (a.ndim - 1))), residual)
    # prefix pytrees: replicated params/state in, replicated synced
    # grads + metrics out, residuals sharded over the sync axes both
    # ways (each device sees exactly its own (1, ...) row)
    fn = shard_map(
        body, mesh=executor.dmesh.mesh,
        in_specs=(rep, rep, batch_specs, res_specs),
        out_specs=(rep, rep, res_specs),
        check_vma=False)
    return fn(params, state, batch, residual)
