"""Closed-loop plan adaptation: drift-triggered re-calibration,
background re-search, and bounded hot-swap.

The strategy search adopts a plan against the machine it measured at
compile time; the fleet the plan actually runs on then drifts — a DCN
uplink browns out, the workload's batch distribution shifts, a serving
replica's breaker opens. The pieces that *detect* each of these already
exist (``obs/drift.py`` marks mispriced calibration rows stale,
``resilience/faults.py`` registers degraded links, the scheduler's
circuit breaker and admission EWMA track serving health); this module
closes the loop:

  evidence -> debounce -> targeted re-calibration of exactly the
  stale-marked rows (``CalibrationTable.remeasure_stale``) -> re-search
  on the refreshed tables -> gated adoption (plan verifier + predicted
  win >= ``win_ratio``) -> hot-swap with bit-exact state carryover ->
  measured post-swap A/B guard that rolls back a regression.

Flap control is structural, not best-effort: every completed decision —
adopted, rejected, no-win or rolled back — arms a cooldown before the
next one, and non-adoptions grow it exponentially (``backoff`` up to
``max_cooldown_s``), so a fleet the controller cannot actually help
gets probed at exponentially sparser intervals instead of thrashing.
An adoption resets the backoff: the fleet changed, fresh evidence
deserves a fresh budget.

Training swaps ride the same machinery as checkpoint restore: the live
params/opt-state/state are snapshotted to host, the candidate strategy
is compiled through the ordinary ``FFModel.compile`` path (so the ZeRO
planner, qsync planner, forced kernels and plan verifier all re-bind on
it), and the snapshot is re-placed onto the new shardings via
``reshard.place_host`` — values bit-identical, only placement changes.
Serving swaps go through ``ModelRepository.hot_swap`` under graceful
drain and are re-scored from ``ServingPlanSession.measured_profile``.

Reference analog: FlexFlow's ``recompile_on_condition``
(``model.cc:2422``) evaluates a trigger each iteration and rebuilds the
task graph when it fires; this controller is that hook driven by the
calibration-drift evidence instead of a user lambda, which is also how
it attaches to a live training loop (``attach_training`` installs a
``runtime.recompile.RecompileState``).
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs import events as obs_events
from ..obs.metrics_registry import REGISTRY
from . import status

__all__ = ["ReplanPolicy", "ReplanController"]


def _count(trigger: str, outcome: str) -> None:
    REGISTRY.counter(
        "ff_replans_total",
        "Closed-loop plan adaptations by trigger and outcome"
    ).inc(trigger=trigger, outcome=outcome)


@dataclass
class ReplanPolicy:
    """Knobs of the adaptation loop. Defaults are deliberately
    conservative: two consecutive evidence polls before acting, a 10%
    predicted win before a swap is even attempted, and a measured guard
    band wider than CPU-sim timing noise."""
    win_ratio: float = 1.1        # predicted incumbent/candidate floor
    debounce_polls: int = 2       # consecutive evidence polls to act
    cooldown_s: float = 60.0      # base gap between decisions
    backoff: float = 2.0          # cooldown growth on non-adoption
    max_cooldown_s: float = 3600.0
    guard_band: float = 1.05      # measured A/B regression tolerance
    search_budget: int = 200      # MCMC proposals per re-search
    search_seed: int = 0
    poll_every: int = 1           # training steps between polls
    ewma_ratio: float = 2.0       # scheduler batch-EWMA drift trigger
    measured_guard: bool = True   # run the post-swap A/B (off = adopt
                                  # on the predicted gate alone,
                                  # recorded as gate="deferred")
    background: bool = False      # search on a worker thread; the swap
                                  # itself always runs on the caller's
                                  # (training) thread at a step boundary


class ReplanController:
    """One controller per process; drive it either synchronously
    (``step_once`` — tests, smokes, serving) or hooked into a live
    training loop (``attach_training`` — the supervisor's per-step
    recompile hook evaluates it between steps)."""

    def __init__(self, ff=None, policy: Optional[ReplanPolicy] = None,
                 cache_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.ff = ff
        self.policy = policy or ReplanPolicy()
        self.cache_dir = cache_dir
        self._clock = clock
        self._lock = threading.Lock()
        self._streak = 0
        self._cooldown_until = 0.0
        self._cooldown_s = self.policy.cooldown_s
        self.replans = 0              # adopted swaps
        self.rollbacks = 0            # A/B-guard reverts
        self.last_trigger: Optional[str] = None
        self.last_outcome: Optional[str] = None
        self.history: List[Dict[str, Any]] = []
        self._schedulers: List[Any] = []
        self._ewma_baseline: Dict[int, float] = {}
        # a fired workload_shift clause is consumed-on-read from the
        # fault registry; the controller holds it as live evidence until
        # the next completed decision so the debounce does not eat it
        self._shift: Optional[int] = None
        # background mode: (trigger, evidence, candidate) produced by
        # the worker thread, adopted by the next step_once on the
        # training thread
        self._pending: Optional[Tuple[str, list, Dict[str, Any]]] = None
        self._worker: Optional[threading.Thread] = None

    # ------------------------------------------------------ evidence --
    def attach_scheduler(self, sched) -> None:
        """Watch a serving ``BatchScheduler``: an open circuit breaker
        or a batch-latency EWMA ``ewma_ratio``x above its first-seen
        baseline becomes replan evidence."""
        self._schedulers.append(sched)

    def poll_evidence(self) -> List[Dict[str, Any]]:
        """Everything currently arguing for a re-plan, most actionable
        first. Pure read (except the one-shot workload-shift consume,
        which the controller keeps holding until it acts on it)."""
        ev: List[Dict[str, Any]] = []
        from . import faults
        # stale calibration rows: the drift detector (obs/drift.py)
        # marked predicted-vs-measured out-of-band rows for re-measure
        try:
            table = self._table()
            table._load_stale()
            stale = sorted(table._stale or ())
            if stale:
                ev.append({"trigger": "drift", "n_stale": len(stale),
                           "stale_keys": stale[:8]})
        except Exception:  # noqa: BLE001 — evidence intake is best-effort
            pass
        deg = faults.degraded_links()
        if deg:
            ev.append({"trigger": "degraded", "links": deg})
        shift = faults.pending_workload_shift()
        if shift is not None:
            self._shift = shift
        if self._shift is not None:
            ev.append({"trigger": "workload_shift", "batch": self._shift})
        for sched in self._schedulers:
            try:
                st = sched.stats()
                if st.get("circuit") == "open":
                    ev.append({"trigger": "breaker",
                               "model": st.get("model")})
                ewma = getattr(sched, "_ewma_batch_s", None)
                base = self._ewma_baseline.get(id(sched))
                if ewma:
                    if base is None:
                        self._ewma_baseline[id(sched)] = float(ewma)
                    elif ewma > base * self.policy.ewma_ratio:
                        ev.append({"trigger": "slo",
                                   "ewma_s": round(float(ewma), 6),
                                   "baseline_s": round(base, 6)})
            except Exception:  # noqa: BLE001
                pass
        return ev

    def _table(self):
        from ..search.calibration import CalibrationTable
        return CalibrationTable(self.cache_dir) if self.cache_dir \
            else CalibrationTable()

    # -------------------------------------------------- control loop --
    def step_once(self, ff=None) -> str:
        """One control-loop iteration; returns the outcome tag:
        ``quiet`` | ``debounce`` | ``cooldown`` | ``searching`` (a
        background search is in flight) | ``rejected`` | ``no_win`` |
        ``adopted`` | ``rolled_back`` | ``error``."""
        ff = ff if ff is not None else self.ff
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is not None:
            trigger, ev, cand = pending
            return self._adopt(ff, trigger, ev, cand)
        if self._worker is not None and self._worker.is_alive():
            return "searching"
        ev = self.poll_evidence()
        if not ev:
            self._streak = 0
            return "quiet"
        self._streak += 1
        if self._streak < self.policy.debounce_polls:
            return "debounce"
        if self._clock() < self._cooldown_until:
            return "cooldown"
        trigger = ev[0]["trigger"]
        if self.policy.background:
            self._launch(ff, trigger, ev)
            return "searching"
        status.set_value("replan_candidate", "searching")
        t0 = time.perf_counter()
        try:
            cand = self._prepare(ff, trigger)
        except Exception as e:  # noqa: BLE001 — the loop must survive
            self._finish(ff, trigger, "error", {"error": repr(e)}, ev, t0)
            return "error"
        why = cand.pop("reject", None)
        if why is not None:
            self._finish(ff, trigger, why, cand, ev, t0)
            return why
        return self._adopt(ff, trigger, ev, cand, t0=t0)

    def _launch(self, ff, trigger: str, ev: list) -> None:
        """Background mode: re-calibration + search + gates run off the
        training thread; only the swap itself (next ``step_once``)
        touches the live model."""
        status.set_value("replan_candidate", "searching")

        def run():
            t0 = time.perf_counter()
            try:
                cand = self._prepare(ff, trigger)
            except Exception as e:  # noqa: BLE001
                self._finish(ff, trigger, "error", {"error": repr(e)},
                             ev, t0)
                return
            why = cand.pop("reject", None)
            if why is not None:
                self._finish(ff, trigger, why, cand, ev, t0)
                return
            with self._lock:
                self._pending = (trigger, ev, cand)
            status.set_value("replan_candidate", "pending")

        self._worker = threading.Thread(target=run, name="ff-replan",
                                        daemon=True)
        self._worker.start()

    # ------------------------------------- recalibrate + search + gate --
    def _prepare(self, ff, trigger: str) -> Dict[str, Any]:
        """Heal the tables, search a candidate, gate it. Returns the
        candidate bundle, or ``{"reject": "rejected"|"no_win", ...}``."""
        with obs_events.span("replan.recalibrate", trigger=trigger):
            table = self._table()
            remeasured = table.remeasure_stale(ff.dmesh)
        with obs_events.span("replan.search", trigger=trigger,
                             budget=self.policy.search_budget):
            cand = self._search(ff)
        cand["remeasured"] = sorted(remeasured)
        with obs_events.span("replan.gate", trigger=trigger):
            ok, why, gate = self._gate(ff, cand)
        cand.update(gate)
        if not ok:
            cand["reject"] = why
        return cand

    def _search(self, ff) -> Dict[str, Any]:
        """Re-search on freshly calibrated tables and price the
        incumbent under the SAME tables, so the predicted-win gate is a
        like-for-like comparison on current machine evidence."""
        from ..search.mcmc import (assignment_to_strategy,
                                   data_parallel_assignment, mcmc_search)
        cm = self._fresh_cost_model(ff)
        best, best_cost, sim = mcmc_search(
            ff.layers, ff.dmesh, cm, budget=self.policy.search_budget,
            seed=self.policy.search_seed)
        inc_assign, basis = self._incumbent_assignment(ff, sim)
        if inc_assign is None:
            inc_assign = data_parallel_assignment(ff.layers, ff.dmesh,
                                                  sim.options)
            basis = "dp"
        inc_cost = sim.evaluate(inc_assign).total
        strategy = assignment_to_strategy(ff.layers, ff.graph_inputs,
                                          best, ff.dmesh, sim)
        if cm.placement is not None:
            # re-price only the adopted assignment with cleared memos so
            # the recorded tree choices are its sites (optimizer.py does
            # the same after mcmc_search)
            cm.attach_placement(cm.placement, "hier")
            sim.evaluate(best)
            strategy.collective_trees = list(cm.algo_choices.values())
            strategy.axis_tiers = cm.placement.to_json()
        return {"strategy": strategy, "assign": best,
                "predicted_s": best_cost, "incumbent_s": inc_cost,
                "incumbent_basis": basis,
                "predicted_ratio": inc_cost / max(best_cost, 1e-12)}

    def _fresh_cost_model(self, ff):
        """A cost model calibrated the way ``optimize_strategy`` does it
        — measured collectives, persisted tables — so the
        re-search ranks plans on the machine as it is NOW (the refreshed
        rows from ``remeasure_stale``, the degradation factors from the
        fault registry)."""
        from ..search.costmodel import OpCostModel
        from ..search.optimizer import _attach_placement
        cfg, dmesh = ff.config, ff.dmesh
        cm = OpCostModel(dmesh.spec)
        cm.segment_size = max(1, cfg.simulator_segment_size)
        cm.max_segments = max(1, cfg.simulator_max_num_segments)
        _attach_placement(cfg, cm, dmesh)
        if not cfg.machine_model_file:
            cm.calibrate_collectives(dmesh)
            from ..search.calibration import (calibration_enabled,
                                              calibrate_mesh)
            if calibration_enabled(cfg):
                try:
                    cm.attach_calibration(calibrate_mesh(dmesh))
                except Exception:  # noqa: BLE001 — best-effort
                    pass
        return cm

    def _incumbent_assignment(self, ff, sim):
        """Reconstruct the live strategy as a simulator assignment: per
        layer, walk the (small) degree lattice and keep the tuple whose
        materialized sharding equals the incumbent's specs. Returns
        (assign, basis) — basis ``"specs"`` when every sharded layer
        matched, ``"mixed"`` when some fell back to the DP degree, or
        (None, None) when fewer than half matched (caller prices the DP
        baseline instead and records it)."""
        from ..search.mcmc import (assignment_to_sharding,
                                   data_parallel_assignment)
        inc = getattr(ff, "strategy", None)
        ops = getattr(inc, "ops", {}) or {}
        if not ops:
            return None, None
        valid = sorted(set(ff.dmesh.valid_degrees()))
        dp = data_parallel_assignment(ff.layers, ff.dmesh, sim.options)
        assign: Dict[str, Tuple[int, ...]] = {}
        sharded = matched = 0
        for layer in ff.layers:
            opts = sim.options[layer.name]
            want = ops.get(layer.name)
            if want is None or not opts:
                assign[layer.name] = (1,) * len(opts)
                continue
            sharded += 1
            target = (tuple(want.outputs),
                      tuple(sorted(want.weights.items())))
            hit = None
            if len(valid) ** len(opts) <= 4096:
                for degs in itertools.product(valid, repeat=len(opts)):
                    res = assignment_to_sharding(layer, opts, degs,
                                                 ff.dmesh)
                    if res is None:
                        continue
                    got = (tuple(res[0]),
                           tuple(sorted(res[1].items())))
                    if got == target:
                        hit = degs
                        break
            if hit is not None:
                matched += 1
                assign[layer.name] = hit
            else:
                assign[layer.name] = dp.get(layer.name,
                                            (1,) * len(opts))
        if sharded and matched * 2 < sharded:
            return None, None
        return assign, ("specs" if matched == sharded else "mixed")

    def _gate(self, ff, cand) -> Tuple[bool, str, Dict[str, Any]]:
        """Candidate admission: statically sound AND predicted at least
        ``win_ratio`` faster than the incumbent under the same refreshed
        tables. A failed gate leaves the incumbent completely untouched."""
        gate: Dict[str, Any] = {}
        from ..analysis.plan_verifier import (PlanVerificationError,
                                              verify_plan)
        try:
            verify_plan(cand["strategy"], ff.layers,
                        machine_spec=ff.dmesh.spec,
                        graph_inputs=ff.graph_inputs,
                        optimizer=ff.optimizer,
                        context="replan").raise_if_failed()
        except PlanVerificationError as e:
            gate["verifier"] = str(e)[:400]
            return False, "rejected", gate
        ratio = cand["predicted_ratio"]
        gate["win_ratio_floor"] = self.policy.win_ratio
        if ratio < self.policy.win_ratio:
            return False, "no_win", gate
        return True, "", gate

    # ---------------------------------------------------- hot-swap --
    def _adopt(self, ff, trigger: str, ev: list, cand: Dict[str, Any],
               t0: Optional[float] = None) -> str:
        """Swap the candidate in with bit-exact state carryover, run the
        measured A/B guard, roll back on regression."""
        t0 = time.perf_counter() if t0 is None else t0
        status.set_value("replan_candidate", "pending")
        incumbent = ff.strategy
        snap, step = self._snapshot(ff)
        detail: Dict[str, Any] = {
            k: cand[k] for k in ("predicted_s", "incumbent_s",
                                 "incumbent_basis", "predicted_ratio",
                                 "remeasured") if k in cand}
        try:
            with obs_events.span("replan.swap", trigger=trigger):
                self._install(ff, cand["strategy"])
                self._replace_state(ff, snap, step)
        except Exception as e:  # noqa: BLE001 — a candidate that fails
            # to compile must heal back to the incumbent, not crash
            with obs_events.span("replan.swap", trigger=trigger,
                                 rollback=True):
                self._install(ff, incumbent)
                self._replace_state(ff, snap, step)
            detail["error"] = repr(e)
            self._finish(ff, trigger, "rejected", detail, ev, t0)
            return "rejected"
        guard = self._ab_guard(ff, incumbent, cand["strategy"]) \
            if self.policy.measured_guard else {"gate": "deferred"}
        detail.update(guard)
        if guard.get("gate") == "regression":
            with obs_events.span("replan.swap", trigger=trigger,
                                 rollback=True):
                self._install(ff, incumbent)
                self._replace_state(ff, snap, step)
            self.rollbacks += 1
            self._finish(ff, trigger, "rolled_back", detail, ev, t0)
            return "rolled_back"
        self.replans += 1
        self._finish(ff, trigger, "adopted", detail, ev, t0)
        return "adopted"

    @staticmethod
    def _snapshot(ff):
        """Host copies of the live training state — the same capture a
        checkpoint save makes, minus the disk round-trip."""
        import jax
        import numpy as np
        snap = {"params": jax.tree.map(np.asarray, ff.params),
                "opt_state": jax.tree.map(np.asarray, ff.opt_state),
                "state": jax.tree.map(np.asarray, ff.state)}
        return snap, ff._step

    @staticmethod
    def _install(ff, strategy) -> None:
        """Compile ``strategy`` through the ordinary path (warm
        recompile, same shape as ``elastic.replan_on_device_loss``) so
        the ZeRO/qsync/kernel planners and the plan verifier re-bind on
        exactly the plan the run will execute."""
        out_t = ff._output_tensor
        if out_t is not None and \
                getattr(out_t, "owner_layer", None) not in ff.layers:
            # the incumbent's search rewrote the graph (inserted
            # parallel ops): its output tensor is not producible from
            # ff.layers, which is what the candidate was searched over —
            # let compile() re-derive the user graph's output
            out_t = None
        ff.strategy = None
        ff.executor = None
        ff._prebuilt_executor = None
        ff.compile(optimizer=ff.optimizer, loss_type=ff.loss_type,
                   metrics=list(ff.metrics),
                   machine_spec=ff.dmesh.spec, strategy=strategy,
                   output_tensor=out_t)

    @staticmethod
    def _replace_state(ff, snap, step: int) -> None:
        """Re-place the snapshot onto the freshly compiled shardings —
        the checkpoint-restore pattern (``runtime/checkpoint.py``):
        values bit-identical, only placement changes, so the loss
        history continues exactly where the incumbent left it."""
        import jax
        import numpy as np
        from ..parallel.reshard import place_host
        from ..runtime.checkpoint import _restore_opt_state

        def replace(tmpl, new):
            return jax.tree.map(
                lambda t, n: place_host(
                    np.asarray(n).astype(t.dtype).reshape(t.shape),
                    t.sharding if hasattr(t, "sharding") else None),
                tmpl, new)

        ff.params = replace(ff.params, snap["params"])
        ff.opt_state = _restore_opt_state(ff, snap["opt_state"], replace)
        ff.state = replace(ff.state, snap["state"])
        ff._step = step

    def _ab_guard(self, ff, incumbent, candidate) -> Dict[str, Any]:
        """Post-swap measured A/B: time a few synthetic train steps of
        both plans back to back (the floor guard's ``_time_strategy`` —
        fresh executors and synthetic state, the live model untouched).
        ``regression`` = candidate measurably slower; ``measured_win`` =
        measurably faster; ``deferred`` = inside the noise band, adopt
        on the predicted gate (recorded so the audit shows which gate
        admitted the swap)."""
        from ..search.optimizer import _time_strategy
        with obs_events.span("replan.guard"):
            try:
                cand_s, _, _, _ = _time_strategy(ff, candidate, None)
                inc_s, _, _, _ = _time_strategy(ff, incumbent, None)
            except Exception as e:  # noqa: BLE001 — an unmeasurable
                # guard defers to the predicted gate rather than block
                return {"gate": "deferred", "guard_error": repr(e)}
            finally:
                # _time_strategy parks its executor for compile() to
                # adopt; nothing here will, so drop the hand-off
                ff._prebuilt_executor = None
        out = {"measured_candidate_s": cand_s, "measured_incumbent_s": inc_s,
               "measured_ratio": inc_s / max(cand_s, 1e-12)}
        if cand_s > inc_s * self.policy.guard_band:
            out["gate"] = "regression"
        elif cand_s * self.policy.guard_band < inc_s:
            out["gate"] = "measured_win"
        else:
            out["gate"] = "deferred"
        return out

    # -------------------------------------------------- bookkeeping --
    def _finish(self, ff, trigger: str, outcome: str, detail: Dict,
                ev: list, t0: float) -> None:
        now = self._clock()
        if outcome == "adopted":
            self._cooldown_s = self.policy.cooldown_s
        else:
            self._cooldown_s = min(self._cooldown_s * self.policy.backoff,
                                   self.policy.max_cooldown_s)
        self._cooldown_until = now + self._cooldown_s
        self._streak = 0
        self._shift = None
        self.last_trigger, self.last_outcome = trigger, outcome
        rec = {"trigger": trigger, "outcome": outcome,
               "cooldown_s": self._cooldown_s,
               "elapsed_s": round(time.perf_counter() - t0, 3),
               "evidence": ev, **detail}
        # strategies don't serialize; the audit record carries numbers
        rec.pop("strategy", None)
        rec.pop("assign", None)
        self.history.append(rec)
        _count(trigger, outcome)
        status.set_value("replan_last_trigger", trigger)
        status.set_value("replan_last_outcome", outcome)
        status.set_value("replan_candidate", "idle")
        status.set_value("replan_cooldown_until_unix_s",
                         time.time() + max(0.0, self._cooldown_until - now))
        if outcome == "adopted":
            status.record("replans")
        elif outcome == "rolled_back":
            status.record("replan_rollbacks")
        obs_events.instant("replan.decision", trigger=trigger,
                           outcome=outcome)
        path = getattr(ff, "_strategy_audit_path", None) if ff else None
        if path:
            from ..obs.audit import annotate_strategy_audit
            annotate_strategy_audit(path, {"replan": {
                "events": list(self.history)}})
        if outcome in ("adopted", "rolled_back"):
            # every swap decision leaves a black box: which evidence,
            # which gates, what the A/B measured
            try:
                from ..obs.flight import dump_flight_record
                dump_flight_record(f"replan_{outcome}",
                                   extra={"replan": rec})
            except Exception:  # noqa: BLE001
                pass

    # -------------------------------------------- training attach --
    def attach_training(self, ff):
        """Install the controller as the model's dynamic-recompilation
        hook: ``fit()`` and the Supervisor evaluate ``trigger`` once per
        step and rebuild the jitted step when a swap happened — the
        reference ``recompile_on_condition`` contract."""
        every = max(1, self.policy.poll_every)

        def trigger(rs) -> bool:
            if rs.iteration % every:
                return False
            return self.step_once(ff) in ("adopted", "rolled_back")

        return ff.recompile_on_condition(trigger, lambda rs: None)

    # ------------------------------------------------- serving side --
    def serve_replan(self, repo, name: str, *, scheduler=None,
                     builder: Optional[Callable[[], Any]] = None,
                     dmesh=None, session=None) -> str:
        """One serving-side adaptation pass for model ``name`` in
        ``repo``: serving drift (measured decode vs the plan's
        predictions) / an open breaker / degraded links trigger targeted
        re-calibration, then ``builder()`` produces the re-searched
        session (``optimize_serving_strategy`` +
        ``build_serving_plan_session`` in a real deployment; tests pass
        a lightweight factory) and the swap rides ``repo.hot_swap``
        under graceful drain. Returns the outcome tag; call
        :meth:`rescore_serving` after post-swap traffic to arm the
        measured rollback."""
        session = session if session is not None else repo.get(name)
        t0 = time.perf_counter()
        ev: List[Dict[str, Any]] = []
        try:
            from ..obs.drift import serving_drift_report
            rep = serving_drift_report(session, cache_dir=self.cache_dir)
            if rep and rep.get("n_out_of_band"):
                ev.append({"trigger": "serving_drift",
                           "n_out_of_band": rep["n_out_of_band"]})
        except Exception:  # noqa: BLE001
            pass
        if scheduler is not None:
            try:
                if scheduler.stats().get("circuit") == "open":
                    ev.append({"trigger": "breaker", "model": name})
            except Exception:  # noqa: BLE001
                pass
        from . import faults
        if faults.degraded_links():
            ev.append({"trigger": "degraded",
                       "links": faults.degraded_links()})
        if not ev:
            return "quiet"
        if self._clock() < self._cooldown_until:
            return "cooldown"
        trigger = ev[0]["trigger"]
        status.set_value("replan_candidate", "searching")
        with obs_events.span("replan.recalibrate", trigger=trigger,
                             mode="serving"):
            table = self._table()
            remeasured = table.remeasure_stale(dmesh)
        if builder is None:
            # evidence handled as far as this process can: tables are
            # healed; re-search/rebuild belongs to the deployment layer
            self._finish(None, trigger, "recalibrated",
                         {"remeasured": sorted(remeasured)}, ev, t0)
            return "recalibrated"
        with obs_events.span("replan.search", trigger=trigger,
                             mode="serving"):
            new_session = builder()
        old = list(repo.get_instances(name))
        baseline = {}
        try:
            baseline = dict(session.measured_profile())
        except Exception:  # noqa: BLE001
            pass
        with obs_events.span("replan.swap", trigger=trigger,
                             mode="serving"):
            repo.hot_swap(name, new_session, scheduler=scheduler)
        self.replans += 1
        self._swap_ctx = {"repo": repo, "name": name, "old": old,
                          "scheduler": scheduler, "baseline": baseline}
        self._finish(None, trigger, "adopted",
                     {"remeasured": sorted(remeasured),
                      "mode": "serving"}, ev, t0)
        return "adopted"

    def rescore_serving(self, session=None) -> str:
        """The serving analog of the training A/B guard: compare the
        swapped-in session's measured decode profile (needs post-swap
        traffic) against the pre-swap baseline on shared buckets; a
        ``guard_band`` regression swaps the old instances back under the
        same drain path. Returns ``adopted`` | ``rolled_back`` |
        ``pending`` (no comparable traffic yet)."""
        ctx = getattr(self, "_swap_ctx", None)
        if ctx is None:
            return "pending"
        repo, name = ctx["repo"], ctx["name"]
        session = session if session is not None else repo.get(name)
        try:
            prof = dict(session.measured_profile())
        except Exception:  # noqa: BLE001
            prof = {}
        worse = []
        for bucket, base in (ctx["baseline"] or {}).items():
            cur = prof.get(bucket)
            if not cur or not base:
                continue
            b, c = base.get("decode_step_s"), cur.get("decode_step_s")
            if b and c and c > b * self.policy.guard_band:
                worse.append((bucket, b, c))
        if not worse:
            if prof:
                self._swap_ctx = None
            return "adopted" if prof else "pending"
        with obs_events.span("replan.swap", mode="serving",
                             rollback=True):
            repo.hot_swap(name, ctx["old"],
                          scheduler=ctx["scheduler"])
        self.rollbacks += 1
        self._swap_ctx = None
        status.record("replan_rollbacks")
        status.set_value("replan_last_outcome", "rolled_back")
        _count("serving_guard", "rolled_back")
        obs_events.instant("replan.decision", trigger="serving_guard",
                           outcome="rolled_back")
        return "rolled_back"
