"""Closed-loop plan adaptation (resilience/replan.py): evidence
debounce, cooldown + exponential backoff, gate rejections that leave
the incumbent untouched, measured-regression rollback, bit-exact
training hot-swap, the fit()-integrated recompile hook, serving swap
under in-flight load, and the one-shot adaptation drills."""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.obs.metrics_registry import REGISTRY
from flexflow_tpu.resilience import (ReplanController, ReplanPolicy,
                                     faults)
from flexflow_tpu.resilience import status as rstatus
from flexflow_tpu.resilience.replan import ReplanController as _Ctl


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    rstatus.reset()
    yield
    faults.clear()
    rstatus.reset()


def _mlp(seed=0):
    """Tiny DP-compiled model — no search, fast compile, and the
    incumbent strategy is exactly reproducible for swap parity."""
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.seed = seed
    ff = FFModel(cfg)
    t = ff.create_tensor((16, 16), name="x")
    d = ff.dense(t, 32, activation="relu", name="d1")
    d = ff.dense(d, 8, name="d2")
    ff.compile(SGDOptimizer(0.05), "mse", ["mean_squared_error"])
    return ff


def _batch(rng=None):
    rng = rng or np.random.RandomState(0)
    return {"x": rng.randn(16, 16).astype(np.float32),
            "label": rng.randn(16, 8).astype(np.float32)}


def _losses(ff, batch, n):
    step = ff.executor.make_train_step()
    return [float(np.asarray(ff._run_train_step(step, batch)["loss"]))
            for _ in range(n)]


def _dp_candidate(ff):
    """A fresh materialization of the DP assignment: a different
    strategy OBJECT with identical math, so a swap onto it must leave
    the loss history bit-identical."""
    from flexflow_tpu.search.costmodel import OpCostModel
    from flexflow_tpu.search.mcmc import (StrategySimulator,
                                          assignment_to_strategy,
                                          data_parallel_assignment)
    sim = StrategySimulator(ff.layers, ff.dmesh,
                            OpCostModel(ff.dmesh.spec))
    dp = data_parallel_assignment(ff.layers, ff.dmesh, sim.options)
    return assignment_to_strategy(ff.layers, ff.graph_inputs, dp,
                                  ff.dmesh, sim)


def _force_search(monkeypatch, ctl, strategy, ratio=2.0):
    monkeypatch.setattr(ctl, "_search", lambda ff: {
        "strategy": strategy, "assign": {}, "predicted_s": 1.0,
        "incumbent_s": ratio, "incumbent_basis": "specs",
        "predicted_ratio": ratio})


# ------------------------------------------------------------------
# drills: one-shot firing into the degradation / workload registries
# ------------------------------------------------------------------
def test_adaptation_drills_fire_exactly_once():
    faults.install("degrade_link@3:dcn:4.0;workload_shift@5:8")
    for s in (1, 2):
        faults.raise_pending(s)
        assert faults.degraded_links() == {}
    faults.raise_pending(3)
    assert faults.degraded_links() == {"dcn": 4.0}
    # one-shot: replaying the same step must not compound the factor
    faults.raise_pending(3)
    assert faults.degraded_links() == {"dcn": 4.0}
    assert faults.pending_workload_shift() is None
    faults.raise_pending(5)
    assert faults.pending_workload_shift() == 8   # consumed on read
    assert faults.pending_workload_shift() is None
    faults.raise_pending(5)
    assert faults.pending_workload_shift() is None
    faults.clear()
    assert faults.degraded_links() == {}


# ------------------------------------------------------------------
# debounce, cooldown, exponential backoff — no model needed
# ------------------------------------------------------------------
def test_debounce_then_cooldown_with_backoff(monkeypatch):
    now = [0.0]
    ctl = ReplanController(policy=ReplanPolicy(
        debounce_polls=2, cooldown_s=10.0, backoff=2.0),
        clock=lambda: now[0])
    monkeypatch.setattr(ctl, "_prepare",
                        lambda ff, trig: {"reject": "no_win",
                                          "predicted_ratio": 1.0})
    assert ctl.step_once() == "quiet"
    faults.set_link_degradation("dcn", 2.0)
    assert ctl.step_once() == "debounce"          # 1st evidence poll
    assert ctl.step_once() == "no_win"            # 2nd poll: acts
    # a completed decision arms the cooldown: nothing happens inside it
    assert ctl.step_once() == "debounce"
    assert ctl.step_once() == "cooldown"
    assert ctl._cooldown_s == 20.0                # backoff grew it
    now[0] = 25.0
    # evidence persisted through the whole window — already debounced,
    # so expiry acts immediately
    assert ctl.step_once() == "no_win"
    assert ctl._cooldown_s == 40.0                # and again
    assert len(ctl.history) == 2                  # <=1 per window
    c = REGISTRY.counter("ff_replans_total")
    assert c.value(trigger="degraded", outcome="no_win") == 2.0


def test_background_search_adopts_at_next_poll(monkeypatch):
    ctl = ReplanController(policy=ReplanPolicy(
        debounce_polls=1, background=True))
    monkeypatch.setattr(ctl, "_prepare",
                        lambda ff, trig: {"strategy": "S"})
    adopted = []
    monkeypatch.setattr(ctl, "_adopt",
                        lambda ff, trig, ev, cand, t0=None:
                        adopted.append(cand) or "adopted")
    faults.set_link_degradation("dcn", 2.0)
    assert ctl.step_once() == "searching"
    ctl._worker.join(timeout=10)
    assert ctl.step_once() == "adopted"
    assert adopted and adopted[0]["strategy"] == "S"


# ------------------------------------------------------------------
# gates: rejected / no-win candidates leave the incumbent untouched
# ------------------------------------------------------------------
def test_verifier_rejection_leaves_incumbent(monkeypatch):
    ff = _mlp()
    inc_strategy, inc_exec = ff.strategy, ff.executor
    ctl = ReplanController(ff, ReplanPolicy(debounce_polls=1))
    cand = _dp_candidate(ff)
    _force_search(monkeypatch, ctl, cand, ratio=3.0)
    from flexflow_tpu.analysis import plan_verifier

    def deny(*a, **k):
        raise plan_verifier.PlanVerificationError([], context="test")

    monkeypatch.setattr(plan_verifier, "verify_plan", deny)
    faults.set_link_degradation("dcn", 4.0)
    assert ctl.step_once() == "rejected"
    assert ff.strategy is inc_strategy            # object-identical
    assert ff.executor is inc_exec
    assert ctl.replans == 0
    assert rstatus.snapshot()["replans"] == 0
    assert rstatus.snapshot()["replan_last_outcome"] == "rejected"


def test_predicted_no_win_leaves_incumbent(monkeypatch):
    ff = _mlp()
    inc_exec = ff.executor
    ctl = ReplanController(ff, ReplanPolicy(debounce_polls=1,
                                            win_ratio=1.1))
    _force_search(monkeypatch, ctl, _dp_candidate(ff), ratio=1.05)
    faults.set_link_degradation("dcn", 4.0)
    assert ctl.step_once() == "no_win"
    assert ff.executor is inc_exec
    assert ctl.history[-1]["win_ratio_floor"] == 1.1


# ------------------------------------------------------------------
# the swap itself: bit-exact carryover, measured rollback
# ------------------------------------------------------------------
def test_training_swap_is_bit_exact(monkeypatch):
    batch = _batch()
    base = _losses(_mlp(), batch, 6)

    ff = _mlp()
    pre = _losses(ff, batch, 3)
    params_before = {k: {w: np.asarray(v) for w, v in d.items()}
                     for k, d in ff.params.items()}
    ctl = ReplanController(ff, ReplanPolicy(debounce_polls=1,
                                            measured_guard=False))
    _force_search(monkeypatch, ctl, _dp_candidate(ff), ratio=2.0)
    faults.set_link_degradation("dcn", 4.0)
    assert ctl.step_once() == "adopted"
    assert ff._step == 3                          # step counter carried
    # state carryover is bit-exact: every leaf survives the re-place
    for lname, ws in params_before.items():
        for wname, want in ws.items():
            got = np.asarray(ff.params[lname][wname])
            assert np.array_equal(got, want), f"{lname}/{wname}"
    # and the loss history continues exactly where it left off
    post = _losses(ff, batch, 3)
    assert pre + post == base
    assert ctl.replans == 1
    assert rstatus.snapshot()["replans"] == 1
    assert ctl.history[-1]["gate"] == "deferred"


def test_measured_regression_rolls_back(monkeypatch):
    batch = _batch()
    base = _losses(_mlp(), batch, 6)

    ff = _mlp()
    pre = _losses(ff, batch, 3)
    ctl = ReplanController(ff, ReplanPolicy(debounce_polls=1,
                                            measured_guard=True))
    _force_search(monkeypatch, ctl, _dp_candidate(ff), ratio=2.0)
    monkeypatch.setattr(ctl, "_ab_guard",
                        lambda ff_, inc, cand: {"gate": "regression",
                                                "measured_ratio": 0.5})
    faults.set_link_degradation("dcn", 4.0)
    assert ctl.step_once() == "rolled_back"
    assert ctl.rollbacks == 1 and ctl.replans == 0
    assert rstatus.snapshot()["replan_rollbacks"] == 1
    # the rollback re-placed the pre-swap state: training continues
    # bit-exactly on the incumbent
    post = _losses(ff, batch, 3)
    assert pre + post == base
    c = REGISTRY.counter("ff_replans_total")
    assert c.value(trigger="degraded", outcome="rolled_back") >= 1.0


def test_attach_training_swaps_mid_fit(monkeypatch):
    rng = np.random.RandomState(1)
    ff = _mlp()
    ctl = ReplanController(ff, ReplanPolicy(debounce_polls=1,
                                            measured_guard=False,
                                            cooldown_s=3600.0))
    _force_search(monkeypatch, ctl, _dp_candidate(ff), ratio=2.0)
    rs = ctl.attach_training(ff)
    faults.set_link_degradation("dcn", 4.0)
    X = rng.randn(64, 16).astype(np.float32)
    Y = rng.randn(64, 8).astype(np.float32)
    hist = ff.fit(x=X, y=Y, epochs=2, verbose=False)
    assert hist and np.isfinite(hist[-1]["loss"])
    # the hook fired once (cooldown holds every later poll) and the
    # rebuilt jitted step kept training
    assert ctl.replans == 1
    assert rs.recompilations == 1
    assert ctl.last_outcome == "adopted"


# ------------------------------------------------------------------
# serving: hot-swap under in-flight load + measured re-score rollback
# ------------------------------------------------------------------
class _Sess:
    input_names = ["x"]

    def __init__(self, tag, profile=None, gate=None):
        self.tag, self.served = tag, 0
        self._profile = profile or {}
        self._gate = gate           # an Event a batch waits for

    def clone(self):
        return self

    def infer(self, inputs):
        self.served += 1
        if self._gate is not None:
            assert self._gate.wait(15.0)
        return np.zeros((inputs["x"].shape[0], 1), np.float32)

    def measured_profile(self):
        return dict(self._profile)


def test_serving_swap_under_load_and_rescore_rollback():
    import threading
    import time

    from flexflow_tpu.serving import BatchScheduler, ModelRepository
    drained = threading.Event()
    old = _Sess("old", {"1": {"decode_step_s": 0.001, "n": 4}},
                gate=drained)
    new = _Sess("new", {"1": {"decode_step_s": 0.01, "n": 4}})
    repo = ModelRepository()
    repo.register("m", old)
    sched = BatchScheduler(old, max_batch=2, max_delay_ms=1.0,
                           name="replan_swap")
    try:
        x = np.zeros((1, 1), np.float32)
        results, errs = [], []

        def fire():
            try:
                results.append(sched.infer({"x": x}, timeout=15))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def until(what, holds):
            end = time.monotonic() + 15.0
            while not holds():
                assert time.monotonic() < end, what
                time.sleep(0.002)

        inflight = [threading.Thread(target=fire) for _ in range(4)]
        for t in inflight:
            t.start()
        # the swap starts with all four ADMITTED (a request that meets
        # the drain at the door is shed, by design, and was this test's
        # race) and the old session holds its batch until the drain has
        # begun: the backlog flushes on the old instances under load
        until("four admitted", lambda: sched._pending == 4)
        opener = threading.Thread(target=lambda: (
            until("draining", lambda: sched.stats()["draining"]),
            drained.set()))
        opener.start()
        ctl = ReplanController(policy=ReplanPolicy(debounce_polls=1))
        faults.set_link_degradation("dcn", 4.0)
        out = ctl.serve_replan(repo, "m", scheduler=sched,
                               builder=lambda: new, session=old)
        for t in inflight + [opener]:
            t.join()
        assert out == "adopted"
        assert not errs and len(results) == 4     # nothing dropped
        assert repo.get("m").tag == "new"
        assert sched.infer({"x": x}, timeout=15) is not None
        assert new.served > 0
        # the re-score guard sees the 10x decode regression and swaps
        # the old instances back under the same drain path
        assert ctl.rescore_serving(session=new) == "rolled_back"
        assert repo.get("m").tag == "old"
        assert sched.infer({"x": x}, timeout=15) is not None
        assert ctl.rollbacks == 1
    finally:
        sched.close()


def test_serve_replan_without_builder_recalibrates_only():
    from flexflow_tpu.serving import ModelRepository
    repo = ModelRepository()
    repo.register("m", _Sess("only"))
    ctl = ReplanController(policy=ReplanPolicy(debounce_polls=1))
    assert ctl.serve_replan(repo, "m") == "quiet"
    faults.set_link_degradation("dcn", 4.0)
    assert ctl.serve_replan(repo, "m") == "recalibrated"
    assert repo.get("m").tag == "only"            # untouched


# ------------------------------------------------------------------
# /healthz surface
# ------------------------------------------------------------------
def test_health_fields_carry_adaptation_state():
    rstatus.set_value("replan_cooldown_until_unix_s", None)
    out = rstatus.health_fields()
    assert out["replan_cooldown_remaining_s"] == 0.0
    assert "replan_cooldown_until_unix_s" not in out
    import time as _t
    rstatus.set_value("replan_cooldown_until_unix_s", _t.time() + 30.0)
    rem = rstatus.health_fields()["replan_cooldown_remaining_s"]
    assert 25.0 < rem <= 30.0
    for k in ("replans", "replan_rollbacks", "replan_last_trigger",
              "replan_last_outcome", "replan_candidate"):
        assert k in out
