"""Measured DP-floor guard on search adoption (search/optimizer.py).

The round-2 A/B showed searched strategies losing to DP on 4 of 9
workloads because the CPU-sim cost model mispredicts collectives. The
guard times a few real steps of both programs and keeps DP when the
searched one measures slower — prediction proposes, measurement decides.
"""
import numpy as np
import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.search import optimizer as opt_mod


def _searched_model(floor_guard="true", budget=4):
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.only_data_parallel = False
    cfg.search_budget = budget
    cfg.search_floor_guard = floor_guard
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 64), name="x")
    t = ff.dense(x, 256, ActiMode.AC_MODE_RELU, name="fc0")
    t = ff.dense(t, 256, ActiMode.AC_MODE_RELU, name="fc1")
    out = ff.dense(t, 10, name="out")
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    return ff


def test_guard_keeps_dp_when_searched_measures_slower(monkeypatch):
    """Force the measured times: searched 'loses' -> DP must be adopted
    and the executable program must be the unrewritten graph."""
    times = {"calls": 0}

    def fake_time(ff, strategy, info):
        times["calls"] += 1
        # first call times the searched strategy, second times DP
        t = 1.0 if times["calls"] == 1 else 0.5
        return t, None, [t, t], None

    monkeypatch.setattr(opt_mod, "_time_strategy", fake_time)
    ff = _searched_model(floor_guard="true")
    assert times["calls"] == 2
    rec = ff._floor_guard_record
    assert rec["adopted"] == "dp"
    assert rec["searched_s_per_step"] == 1.0
    assert rec["dp_s_per_step"] == 0.5
    # adopted strategy is plain DP: every op sharded only over batch axis
    errs = ff.strategy.validate()
    assert not errs
    # the step still trains
    rng = np.random.default_rng(0)
    b = {"x": rng.normal(size=(8, 64)).astype(np.float32),
         "label": rng.integers(0, 10, size=(8, 1)).astype(np.int32)}
    step = ff.executor.make_train_step()
    bm = ff._run_train_step(step, b)
    assert np.isfinite(float(np.asarray(bm["loss"])))


def test_guard_adopts_searched_when_it_wins(monkeypatch):
    times = {"calls": 0}

    def fake_time(ff, strategy, info):
        times["calls"] += 1
        t = 0.5 if times["calls"] == 1 else 1.0
        return t, None, [t, t], None

    monkeypatch.setattr(opt_mod, "_time_strategy", fake_time)
    ff = _searched_model(floor_guard="true")
    assert ff._floor_guard_record["adopted"] == "searched"


def test_margin_inside_the_noise_keeps_the_floor(monkeypatch):
    """Searched 'faster' by less than the timing noise is no measured
    win: data parallel stays, reproducibly."""
    sides = iter([[0.4989, 0.4999, 0.5009] * 4,    # searched: 0.1 ms less
                  [0.4990, 0.5000, 0.5010] * 4])   # data parallel

    def fake_time(ff, strategy, info):
        times = next(sides)         # 12 steps each: nothing left to extend
        return sum(times) / len(times), None, times, None

    monkeypatch.setattr(opt_mod, "_time_strategy", fake_time)
    ff = _searched_model(floor_guard="true")
    rec = ff._floor_guard_record
    assert rec["adopted"] == "dp" and rec["unresolved"]


def test_guard_off_by_default_on_cpu():
    """auto mode: CPU simulator runs skip the double-compile, and the
    record says that the guard did not run and why."""
    ff = _searched_model(floor_guard="auto")
    assert "cpu" in ff._floor_guard_record["skipped"]


def test_guard_failure_is_recorded_not_swallowed(monkeypatch):
    """A guard that cannot even time data parallel never kills the
    compile, but the model says that the searched plan was adopted
    unguarded, with the typed cause."""
    def boom(ff, strategy, info):
        raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(opt_mod, "_time_strategy", boom)
    ff = _searched_model(floor_guard="true")
    assert ff._floor_guard_record["skipped"].startswith("MemoryError:")
    assert "floor_guard" in ff._compile_skips


def test_searched_plan_that_does_not_run_loses_to_the_floor(monkeypatch):
    """A searched program that raises on its first steps (does not fit,
    does not compile) is not adopted unguarded: data parallel is."""
    real = opt_mod._time_strategy

    def searched_ooms(ff, strategy, info):
        if info is not None or strategy.ops != \
                opt_mod.ShardingStrategy.data_parallel(
                    ff.layers, ff.graph_inputs, ff.dmesh).ops:
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")
        return real(ff, strategy, info)

    monkeypatch.setattr(opt_mod, "_time_strategy", searched_ooms)
    ff = _searched_model(floor_guard="true")
    rec = ff._floor_guard_record
    assert rec["adopted"] == "dp" and rec["dp_s_per_step"] > 0
    assert rec["searched_error"].startswith("MemoryError:")
    assert "floor_guard.searched" in ff._compile_skips
    assert not ff.strategy.validate()


def test_guard_holds_one_training_state_at_a_time(monkeypatch):
    """The guard times searched then DP; neither side's params/moments
    may outlive its timing run (two resident copies of a model that
    half fills a chip do not fit)."""
    import gc
    import weakref

    import jax
    refs = []
    real = opt_mod._GuardRun.time_steps

    def spy(self, n):
        gc.collect()
        assert not any(r() is not None for r in refs), \
            "a previous guard run's state is still resident"
        init = self.executor.init_params_and_state

        def tracked():
            p, s = init()
            refs.extend(weakref.ref(a) for a in jax.tree.leaves(p))
            return p, s
        self.executor.init_params_and_state = tracked
        try:
            real(self, n)
        finally:
            self.executor.init_params_and_state = init

    monkeypatch.setattr(opt_mod._GuardRun, "time_steps", spy)
    ff = _searched_model(floor_guard="true", budget=2)
    assert ff._floor_guard_record["adopted"] in ("searched", "dp")
    assert len(refs) > 0


def test_guard_real_timing_path():
    """No monkeypatch: the guard actually compiles and times both
    programs on the 8-virtual-device CPU mesh."""
    ff = _searched_model(floor_guard="true", budget=2)
    rec = ff._floor_guard_record
    assert rec["searched_s_per_step"] > 0
    assert rec["dp_s_per_step"] > 0
    assert rec["adopted"] in ("searched", "dp")


def test_fit_runs_the_step_the_guard_compiled(monkeypatch):
    """The guard feeds its batch the way fit() does, so the winning
    side's executable is the one training runs — not a near-copy that
    differs in input placement and compiles all over again (on the chip
    that second compile of BERT-large cost 106 s). Which side wins is
    not this test's question and, between two programs this small, is
    the machine's load: both sides are compiled and timed for real, and
    the searched side's times are then read as 10 s a step, so the floor
    is what is adopted in every run."""
    real, calls = opt_mod._time_strategy, []

    def searched_loses(ff, strategy, info):
        t, executor, times, run = real(ff, strategy, info)
        calls.append(t)
        if len(calls) == 1:             # the searched side is timed first
            return 10.0, executor, [10.0, 10.0], run
        return t, executor, times, run

    monkeypatch.setattr(opt_mod, "_time_strategy", searched_loses)
    ff = _searched_model(floor_guard="true", budget=2)
    assert len(calls) == 2 and ff._floor_guard_record["adopted"] == "dp"
    step = ff.executor.make_train_step().__wrapped__
    assert step._cache_size() == 1
    rng = np.random.default_rng(0)
    ff.fit(x=rng.normal(size=(8, 64)).astype(np.float32),
           y=rng.integers(0, 10, size=(8, 1)).astype(np.int32),
           epochs=2, verbose=False)
    assert step._cache_size() == 1


def test_guard_export_annotation(tmp_path, monkeypatch):
    def fake_time(ff, strategy, info):
        return 0.5, None, [0.5, 0.5], None

    monkeypatch.setattr(opt_mod, "_time_strategy", fake_time)
    path = str(tmp_path / "strategy.json")
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.only_data_parallel = False
    cfg.search_budget = 2
    cfg.search_floor_guard = "true"
    cfg.export_strategy_file = path
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 64), name="x")
    out = ff.dense(x, 10, name="out")
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    import json
    with open(path) as f:
        doc = json.load(f)
    assert doc["floor_guard"]["adopted"] == "searched"


def test_guard_export_rewritten_on_rejection(tmp_path, monkeypatch):
    """A rejected searched strategy must NOT survive in the export file:
    --import bypasses search and guard, so the file must describe the
    ADOPTED (DP) strategy."""
    calls = {"n": 0}

    def fake_time(ff, strategy, info):
        calls["n"] += 1
        t = 1.0 if calls["n"] == 1 else 0.5
        return t, None, [t, t], None

    monkeypatch.setattr(opt_mod, "_time_strategy", fake_time)
    path = str(tmp_path / "strategy.json")
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.only_data_parallel = False
    cfg.search_budget = 2
    cfg.search_floor_guard = "true"
    cfg.export_strategy_file = path
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 64), name="x")
    out = ff.dense(x, 10, name="out")
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    import json
    with open(path) as f:
        doc = json.load(f)
    assert doc["meta"]["floor_guard"]["adopted"] == "dp"
    assert not doc.get("program")  # DP carries no rewritten program
    # round-trip: importing the exported file yields a valid strategy
    cfg2 = FFConfig()
    cfg2.batch_size = 8
    cfg2.import_strategy_file = path
    ff2 = FFModel(cfg2)
    x2 = ff2.create_tensor((8, 64), name="x")
    out2 = ff2.dense(x2, 10, name="out")
    ff2.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
                output_tensor=out2)
    assert not ff2.strategy.validate()
