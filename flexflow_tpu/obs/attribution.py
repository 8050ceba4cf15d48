"""Step-time attribution: measured per-op costs for the audit record.

The strategy audit record (:mod:`.audit`) carries two PREDICTED sides —
``adopted`` and ``dp_baseline`` — priced by the additive evaluator whose
entries sum exactly to its graph total. Nothing in the runtime ever
closed the loop: calibration rows go stale silently, and every fidelity
question ("is the cost model still right on THIS machine?") needs a
hand-run A/B. This module is the closing half (the simulator-calibration
loop of arXiv 2110.10548, which A/Bs predicted reduction trees against
measured collectives): profile a few steady-state steps of the compiled
plan and write a ``measured`` side into the same record, keyed 1:1 to
the predicted entries, so :mod:`.drift` can diff them row by row.

Two measurement modes:

  - **spans** (the default): the executor's program is re-run as
    instrumented sub-steps — one jitted ``fwd+bwd`` per op (with the
    strategy's sharding constraints applied, so collectives execute),
    one timed gradient-sync collective per weighted op, one timed
    optimizer update — each bracketed by a host timer with a device
    sync. The per-entry times cover the instrumented step end to end,
    so their sum tracks the instrumented step's wall time by
    construction (pinned by test). A separate timing of the REAL
    compiled step is recorded as ``jit_step_wall_s`` — the fused
    executable is faster than the sub-step decomposition (XLA fuses
    across ops; each sub-step pays its own dispatch), and both numbers
    matter: per-op ratios for drift, the fused wall for throughput.
  - **coarse** (pipelined regions): the per-op decomposition cannot
    thread a GPipe region's stacked params, so only the compiled-step
    wall is measured and the per-op entries are marked unmeasured.

Enabling: ``FF_ATTRIB=1`` or ``FFConfig.attribution = "true"``
(``--attribution``); either implies tracing (the audit record only
exists when tracing is on). The harness runs ONCE, after ``fit``
completes — it adds zero work to the training step itself. Profiling
runs on deep copies of params/optimizer state with a synthetic batch,
so the trained model is never mutated.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

from . import audit as obs_audit
from . import events as obs_events

#: entries below this predicted+measured floor are dispatch noise on the
#: CPU sim; drift skips them (see obs/drift.py)
DEFAULT_STEPS = 3


def attribution_enabled(cfg=None) -> bool:
    """Resolve the opt-in: config "true"/"false" wins; "auto" (and no
    config at all) honors the FF_ATTRIB env var."""
    mode = str(getattr(cfg, "attribution", "auto") or "auto").lower()
    if mode in ("true", "on", "1", "yes"):
        return True
    if mode in ("false", "off", "0", "no"):
        return False
    return os.environ.get("FF_ATTRIB", "").lower() \
        in ("1", "true", "yes", "on")


def attribution_steps(cfg=None) -> int:
    try:
        return max(1, int(os.environ["FF_ATTRIB_STEPS"]))
    except (KeyError, ValueError):
        pass
    return max(1, int(getattr(cfg, "attribution_steps", DEFAULT_STEPS)
                      or DEFAULT_STEPS))


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------

def _sync(x) -> float:
    """Timed work ends in a device→host fetch of its result (same
    convention as calibration.py)."""
    import numpy as np
    return float(np.asarray(x).ravel()[0])


def _bytes_of_spec(w) -> int:
    import numpy as np
    from ..dtypes import itemsize
    return int(np.prod(w.shape)) * itemsize(w.dtype)


def _weight_degree(strategy, lname: str, wname: str,
                   axis_sizes: Dict[str, int]) -> int:
    """Shard degree of one weight under the strategy (product of mesh
    axis sizes its PartitionSpec consumes)."""
    try:
        sh = strategy.weight_sharding(lname, wname)
    except Exception:  # noqa: BLE001 — missing specs mean replicated
        return 1
    spec = getattr(sh, "spec", None)
    if spec is None:
        return 1
    deg = 1
    for part in spec:
        if part is None:
            continue
        names = part if isinstance(part, tuple) else (part,)
        for a in names:
            deg *= axis_sizes.get(a, 1)
    return max(1, deg)


def _degradation(tiers) -> float:
    """Max active chaos-drill link slowdown over a sync group's tiers
    (resilience/faults.py ``degrade_link``): the virtual mesh cannot
    physically slow the modeled link, so the measured sync wall time is
    scaled instead — the drift detector then sees exactly what a real
    degraded fabric would show it."""
    try:
        from ..parallel.topology import link_degradation_factor
        return max([link_degradation_factor(t) for t in tiers] or [1.0])
    except Exception:  # noqa: BLE001 — no drill machinery = healthy
        return 1.0


def _axes_for_degree(axis_sizes: Dict[str, int], deg: int
                     ) -> Optional[Tuple[str, ...]]:
    """A contiguous mesh-axis run whose sizes multiply to ``deg`` —
    the group the measured grad-sync proxy collective runs over.
    Suffix runs are tried first (grad sync lives on the leftover inner
    axes under the tier-aware allocator)."""
    names = list(axis_sizes)
    starts = list(range(len(names) - 1, -1, -1))
    for i in starts:
        p = 1
        for j in range(i, len(names)):
            p *= axis_sizes[names[j]]
            if p == deg:
                return tuple(names[i:j + 1])
            if p > deg:
                break
    return None


# ----------------------------------------------------------------------
# instrumented sub-step measurement (the spans mode)
# ----------------------------------------------------------------------

class _SubStepHarness:
    """Per-op jitted callables over the executor's program, threaded
    through a shared env exactly like ``GraphProgram.emit`` — but one
    XLA executable per op, so each op's forward+backward (collectives
    included, via the strategy's sharding constraints) is individually
    timeable with a host clock."""

    def __init__(self, ff):
        import jax
        self.ff = ff
        self.ex = ff.executor
        self.program = self.ex.program
        self.strategy = ff.strategy
        self.dmesh = ff.dmesh
        self.rngs = self.ex._rngs_for_step(0)
        self._fns: Dict[str, Any] = {}
        self._fwd_fns: Dict[str, Any] = {}
        self._sync_fns: Dict[Tuple, Any] = {}
        self._jax = jax

    def _ctx(self):
        from ..ops import EmitCtx
        return EmitCtx(training=True, rngs=self.rngs,
                       state=self.ff.state or {}, config=self.ff.config)

    def _constrain(self, layer, i, o):
        from ..parallel import reshard as reshard_mod
        if self.strategy is None or not hasattr(o, "ndim"):
            return o
        sh = self.strategy.output_sharding(layer.name, i)
        if sh is None:
            return o
        return reshard_mod.constrain_output(o, sh, self.strategy, layer)

    def _emit(self, layer, ins, w):
        from ..ops import get_op_def
        op = get_op_def(layer.op_type)
        outs = op.emit(layer.params, list(ins), w, self._ctx(), layer.name)
        return [self._constrain(layer, i, o) for i, o in enumerate(outs)]

    def fwd_fn(self, layer):
        """jitted ``(ins, w) -> (outs, probe_scalar)``."""
        fn = self._fwd_fns.get(layer.name)
        if fn is None:
            import jax.numpy as jnp

            def fwd(ins, w):
                outs = self._emit(layer, ins, w)
                probe = sum((jnp.sum(o.astype(jnp.float32))
                             for o in outs if hasattr(o, "astype")),
                            jnp.float32(0.0))
                return outs, probe

            fn = self._fwd_fns[layer.name] = self._jax.jit(fwd)
        return fn

    def fwdbwd_fn(self, layer, float_idx: List[int], has_w: bool):
        """jitted ``(ins, w) -> (outs, gradsum)``: forward plus the
        gradients w.r.t. float inputs and weights — the per-op analog of
        ``OpCostModel.measure``'s fwd+bwd body, at GLOBAL shapes with
        the strategy's shardings (so tp/dp collectives execute)."""
        if not float_idx and not has_w:
            return self.fwd_fn(layer)
        fn = self._fns.get(layer.name)
        if fn is None:
            jax = self._jax
            import jax.numpy as jnp

            def fwdbwd(ins, w):
                def loss(w_, fins):
                    full = list(ins)
                    for i, a in zip(float_idx, fins):
                        full[i] = a
                    outs = self._emit(layer, full, w_)
                    s = sum((jnp.sum(o.astype(jnp.float32))
                             for o in outs if hasattr(o, "astype")),
                            jnp.float32(0.0))
                    return s, outs
                (_, outs), g = jax.value_and_grad(
                    loss, argnums=(0, 1), has_aux=True)(
                        w, [ins[i] for i in float_idx])
                gsum = jax.tree_util.tree_reduce(
                    lambda acc, x: acc + jnp.sum(x.astype(jnp.float32)),
                    g, jnp.float32(0.0))
                return outs, gsum

            fn = self._fns[layer.name] = self._jax.jit(fwdbwd)
        return fn

    def sync_fn(self, dp_deg: int, n_elems: int):
        """jitted grad-sync proxy: one all-reduce of ``n_elems`` f32
        over a mesh-axis group of degree ``dp_deg`` — what XLA lowers
        the weight-gradient sync of one op to (the combiner-coalesced
        step pays it fewer times; per-op timing is the attribution
        grain, matching the predicted entries)."""
        key = (dp_deg, n_elems)
        fn = self._sync_fns.get(key)
        if fn is not None:
            return fn
        axes = _axes_for_degree(dict(self.dmesh.axis_sizes), dp_deg)
        if axes is None:
            self._sync_fns[key] = None
            return None
        jax = self._jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = self.dmesh.mesh
        all_axes = tuple(mesh.axis_names)

        def body(x):
            return jnp.sum(jax.lax.psum(x, axes))[None]

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                              out_specs=P(all_axes)))
        x = jnp.ones((max(8, n_elems),), jnp.float32)
        # the tier names this sync group spans: measured wall times are
        # scaled by any active degrade_link drill on them at ACCRUAL
        # time (the drill may fire mid-run, after this fn is built) —
        # the CPU-sim mesh has no physical link to slow
        try:
            tiers = frozenset(dict(self.dmesh.axis_tiers).get(a, "ici")
                              for a in axes)
        except Exception:  # noqa: BLE001 — untrier'd mesh
            tiers = frozenset()
        fn = self._sync_fns[key] = (f, x, tiers)
        return fn


def _measure_spans(ff, steps: int, predicted: List[Dict[str, Any]]
                   ) -> Dict[str, Any]:
    """The instrumented sub-step measurement. Returns the measured side
    (``mode="spans"``)."""
    import jax.numpy as jnp
    import numpy as np
    from ..ffconst import PARALLEL_OPS
    from ..search.optimizer import _synth_batch
    from ..search.calibration import shape_class

    h = _SubStepHarness(ff)
    program = h.program
    batch = _synth_batch(ff)
    pred_set = {e["name"] for e in predicted}
    n_dev = ff.dmesh.num_devices
    axis_sizes = dict(ff.dmesh.axis_sizes)

    # ---- per-layer plan: callables, weights, sync payloads ----
    # EVERY program layer runs (downstream ops read their outputs from
    # the shared env — input/no-op passthroughs included); only the
    # layers present in the predicted breakdown get entries, the rest
    # fold into unattributed_s
    plan = []
    for layer in program.layers:
        w = ff.params.get(layer.name, {}) if ff.params else {}
        sync_spec = None
        if layer.weights:
            wbytes = sum(_bytes_of_spec(s) for s in layer.weights)
            wdeg = max((_weight_degree(ff.strategy, layer.name, s.name,
                                       axis_sizes)
                        for s in layer.weights), default=1)
            dp_deg = max(1, n_dev // max(wdeg, 1))
            if dp_deg > 1 and wbytes > 0:
                # bucket payloads by shape class so the jit count stays
                # bounded on deep towers of same-sized layers
                n_elems = max(8, shape_class(wbytes // max(wdeg, 1)) // 4)
                sync_spec = (dp_deg, n_elems)
        plan.append({"layer": layer, "w": w, "sync": sync_spec})

    # ---- warmup + fwd/bwd split probe (compiles excluded from steps) --
    env = program.init_env(batch)
    frac = {}
    for item in plan:
        layer = item["layer"]
        ins = [env[t.guid] for t in layer.inputs]
        float_idx = [i for i, a in enumerate(ins)
                     if hasattr(a, "dtype")
                     and jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)]
        item["float_idx"] = float_idx
        fb = h.fwdbwd_fn(layer, float_idx, bool(item["w"]))
        item["fn"] = fb
        outs, g = fb(ins, item["w"])      # compile
        _sync(g)
        fwd = h.fwd_fn(layer)
        o2, p = fwd(ins, item["w"])       # compile
        _sync(p)
        t_f, t_fb = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            _, p = fwd(ins, item["w"])
            _sync(p)
            t_f.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            outs, g = fb(ins, item["w"])
            _sync(g)
            t_fb.append(time.perf_counter() - t0)
        tf, tfb = min(t_f), max(min(t_fb), 1e-9)
        frac[layer.name] = min(1.0, max(0.05, tf / tfb))
        for o, t in zip(outs, layer.outputs):
            env[t.guid] = o
        if item["sync"] is not None:
            fx = h.sync_fn(*item["sync"])
            if fx is not None:
                _sync(fx[0](fx[1]))       # compile
            item["sync_fn"] = fx
            # wanted but no mesh-axis group realizes the dp degree:
            # the entry must say so, or a predicted-nonzero vs
            # measured-zero sync would read as (phantom) drift
            item["sync_unmeasured"] = fx is None

    # optimizer update (timed once per step, zero grads — placement and
    # math are what cost, not the values)
    g0 = h._jax.tree.map(jnp.zeros_like, ff.params)
    upd = h._jax.jit(
        lambda p, g, o: ff.optimizer.update(p, g, o, 1))
    p2, o2 = upd(ff.params, g0, ff.opt_state)   # compile; discard
    h._jax.block_until_ready(o2)

    # ---- K measured steps ----
    acc: Dict[str, Dict[str, float]] = {
        item["layer"].name: {"t": 0.0, "sync": 0.0} for item in plan}
    unattributed = 0.0
    update_s = 0.0
    walls = []
    for _ in range(steps):
        env = program.init_env(batch)
        t_step0 = time.perf_counter()
        for item in plan:
            layer = item["layer"]
            ins = [env[t.guid] for t in layer.inputs]
            t0 = time.perf_counter()
            outs, g = item["fn"](ins, item["w"])
            _sync(g)
            dt = time.perf_counter() - t0
            acc[layer.name]["t"] += dt
            for o, t in zip(outs, layer.outputs):
                env[t.guid] = o
            fx = item.get("sync_fn")
            if fx is not None:
                t0 = time.perf_counter()
                _sync(fx[0](fx[1]))
                acc[layer.name]["sync"] += \
                    (time.perf_counter() - t0) * _degradation(fx[2])
        t0 = time.perf_counter()
        p2, o2 = upd(ff.params, g0, ff.opt_state)
        h._jax.block_until_ready(o2)
        update_s += time.perf_counter() - t0
        walls.append(time.perf_counter() - t_step0)

    # ---- aggregate, keyed 1:1 to the predicted entries ----
    by_name = {}
    for item in plan:
        layer = item["layer"]
        t = acc[layer.name]["t"] / steps
        sync = acc[layer.name]["sync"] / steps
        if layer.name not in pred_set:
            unattributed += t + sync
            continue
        is_par = layer.op_type in PARALLEL_OPS
        f = frac.get(layer.name, 0.5)
        by_name[layer.name] = {
            "name": layer.name,
            "op_type": getattr(layer.op_type, "name", str(layer.op_type)),
            "fwd_s": 0.0 if is_par else t * f,
            "bwd_s": 0.0 if is_par else t * (1.0 - f),
            "xfer_s": t if is_par else 0.0,
            "sync_s": sync,
            "total_s": t + sync,
            "measured": True,
            "sync_measured": not item.get("sync_unmeasured", False),
        }
    entries = []
    for e in predicted:
        m = by_name.get(e["name"])
        if m is None:
            m = {"name": e["name"], "op_type": e.get("op_type", ""),
                 "fwd_s": 0.0, "bwd_s": 0.0, "xfer_s": 0.0,
                 "sync_s": 0.0, "total_s": 0.0, "measured": False}
        entries.append(m)
    total = sum(e["total_s"] for e in entries)
    return {
        "mode": "spans",
        "n_steps": steps,
        "step_wall_s": float(np.mean(walls)),
        "update_s": update_s / steps,
        "unattributed_s": unattributed,
        "total_s": total,
        "compute_s": sum(e["fwd_s"] + e["bwd_s"] for e in entries),
        "xfer_s": sum(e["xfer_s"] for e in entries),
        "sync_s": sum(e["sync_s"] for e in entries),
        "per_op": entries,
    }


# ----------------------------------------------------------------------
# compiled-step wall (all modes) + coarse fallback
# ----------------------------------------------------------------------

def _time_compiled_step(ff, steps: int) -> Optional[float]:
    """Mean steady wall of the REAL compiled train step, on deep copies
    (the step donates its inputs; the trained model must not move)."""
    import jax
    import jax.numpy as jnp
    from ..search.optimizer import _synth_batch
    try:
        step = ff.executor.make_train_step()
        cp = jax.tree.map(jnp.array, (ff.params, ff.opt_state, ff.state))
        p, o, s = cp
        batch = _synth_batch(ff)
        p, o, s, bm = step(p, o, s, jnp.int32(0), batch)  # compile+warm
        _sync(bm["loss"])
        ts = []
        for i in range(steps):
            t0 = time.perf_counter()
            p, o, s, bm = step(p, o, s, jnp.int32(i + 1), batch)
            _sync(bm["loss"])
            ts.append(time.perf_counter() - t0)
        return sum(ts) / len(ts)
    except Exception:  # noqa: BLE001 — attribution is best-effort
        return None


def _measure_coarse(ff, steps: int, predicted: List[Dict[str, Any]]
                    ) -> Dict[str, Any]:
    wall = _time_compiled_step(ff, steps)
    entries = [{"name": e["name"], "op_type": e.get("op_type", ""),
                "fwd_s": 0.0, "bwd_s": 0.0, "xfer_s": 0.0, "sync_s": 0.0,
                "total_s": 0.0, "measured": False} for e in predicted]
    return {"mode": "coarse", "n_steps": steps,
            "step_wall_s": wall, "total_s": 0.0,
            "compute_s": 0.0, "xfer_s": 0.0, "sync_s": 0.0,
            "per_op": entries}


# ----------------------------------------------------------------------
# measured exposed-comm entry (overlap prediction coverage)
# ----------------------------------------------------------------------

def _attach_measured_overlap(side: Dict[str, Any]) -> None:
    """Attach the measured ``overlap`` block to the measured side so
    :mod:`.drift` can diff the overlap-aware evaluator's predicted
    exposed comm against reality (ISSUE 13: drift detection covers the
    overlap prediction, not just per-op costs).

    Estimator: ``exposed_comm_s = max(0, fused step wall − measured
    compute − optimizer update)`` — the step time the compute terms
    cannot account for, i.e. communication left on the critical path.
    The spans mode's per-op compute carries its own dispatch overhead,
    so this is a LOWER bound on exposed comm (it can clamp to 0 on the
    CPU sim); the drift band absorbs the bias, and the per-op
    ``sync_s`` entries record the SERIALIZED comm cost next to it.
    Also bumps ``ff_comm_exposed_s_total{side="measured"}``."""
    try:
        wall = side.get("jit_step_wall_s")
        if wall is None:
            return
        compute = float(side.get("compute_s", 0.0) or 0.0)
        update = float(side.get("update_s", 0.0) or 0.0)
        exposed = max(0.0, float(wall) - compute - update)
        side["overlap"] = {
            "exposed_comm_s": exposed,
            "comm_serial_s": float(side.get("sync_s", 0.0) or 0.0)
            + float(side.get("xfer_s", 0.0) or 0.0),
            "estimator": "step_wall_minus_compute",
        }
        from .metrics_registry import REGISTRY
        REGISTRY.counter(
            "ff_comm_exposed_s_total",
            "Communication seconds exposed on the step critical path"
        ).inc(exposed, side="measured")
        # hidden = serialized comm the step wall did not pay — like the
        # predicted side, an ALL-communication quantity (the counter
        # help says so); xfer and sync are not separable in the wall
        hidden = max(0.0, side["overlap"]["comm_serial_s"] - exposed)
        side["overlap"]["hidden_comm_s"] = hidden
        REGISTRY.counter(
            "ff_comm_overlap_hidden_s_total",
            "Communication seconds hidden behind backward compute "
            "(overlap-aware scoring)").inc(hidden, side="measured")
    except Exception:  # noqa: BLE001 — the entry is best-effort
        pass


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

def run_attribution(ff, steps: Optional[int] = None
                    ) -> Optional[Dict[str, Any]]:
    """Profile the compiled plan and write the ``measured`` side into
    the model's strategy audit record, then run the drift detector over
    the predicted/measured pair. Best-effort: returns the measured side,
    or None when there is no audit record to attribute against (e.g.
    ``--only-data-parallel`` skips the search audit entirely)."""
    import logging
    log = logging.getLogger("flexflow_tpu")
    path = getattr(ff, "_strategy_audit_path", None)
    if not path or not os.path.exists(path):
        log.info("attribution: no strategy audit record for this "
                 "compile (searchless path?) — skipping")
        return None
    if ff.executor is None or ff.params is None:
        return None
    try:
        doc = obs_audit.load_strategy_audit(path)
    except Exception:  # noqa: BLE001
        return None
    predicted = (doc.get("adopted") or {}).get("per_op") or []
    if not predicted:
        return None
    steps = steps if steps is not None else attribution_steps(ff.config)
    t0 = time.perf_counter()
    try:
        # pipelined regions and device-subset groups stack member
        # weights under group keys the per-layer decomposition cannot
        # address — coarse (compiled-step-wall-only) mode
        grouped = (ff.executor.pipe is not None
                   or bool(getattr(ff.strategy, "banks", None))
                   or bool(getattr(ff.strategy, "place_groups", None)))
        if grouped:
            side = _measure_coarse(ff, steps, predicted)
        else:
            side = _measure_spans(ff, steps, predicted)
        side["jit_step_wall_s"] = _time_compiled_step(ff, steps)
    except Exception as e:  # noqa: BLE001 — must never kill training
        log.warning("attribution harness failed: %r", e)
        obs_events.counter("attribution.failures")
        return None
    _attach_measured_overlap(side)
    side["duration_s"] = round(time.perf_counter() - t0, 6)
    side["written_unix_s"] = time.time()
    obs_audit.annotate_strategy_audit(path, {"measured": side})
    obs_events.record_span("obs.attribution", t0,
                           time.perf_counter() - t0, mode=side["mode"],
                           steps=steps)
    obs_events.counter("attribution.runs")
    from .metrics_registry import REGISTRY
    REGISTRY.counter("ff_attribution_runs_total",
                     "Step-time attribution harness runs").inc(
                         mode=side["mode"])
    # drift detection over the freshly measured pair
    try:
        from . import drift as obs_drift
        doc = dict(doc, measured=side)
        report_path = obs_drift.detect_and_write(doc)
        if report_path:
            obs_audit.annotate_strategy_audit(
                path, {"drift_report": report_path})
    except Exception as e:  # noqa: BLE001
        log.warning("drift detection failed: %r", e)
    return side
