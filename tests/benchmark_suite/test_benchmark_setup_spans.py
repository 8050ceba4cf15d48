"""The reduction from the program's recorder to the seven per-layer
metrics of the set-up (``benchmarks/harness/setup_reduce.py``, seven
files of ``benchmarks/layer_metrics/``), and their entries in the
manifest.

The witness is ``RING`` below: a recorder's ring small enough to count
by hand, in seconds on the recorder's clock.

  0.0-10.0   model.compile: compile.mesh 0-0.5, .search 0.5-0.6, .plan
             0.6-1.0, .verify 1.0-1.2, .init 1.2-8.2 (holding
             executor.init_params 1.3-8.0), .opt_state 8.2-9.0
  10.0-16.0  executor.init_params, the runner's draw from its seed
  16.5       executor.jit train -> step_fn
  17.0-25.0  the inspection, under no span: xla.trace step_fn 17-20
             (holding a nested xla.trace of ``add``), xla.lower 20-21,
             xla.backend_compile 21-25 holding xla.cache_load 21.5-24.5
  25.5       executor.jit eval -> step_fn, the SAME name
  26.0-29.0  executor.eval_step holding its own trace 26.1-27.0, lower
             27.0-27.3, backend compile 27.3-28.8 (cache load 27.4-28.4)
  29.0-31.7  the reference: trace / lower / backend compile of ``err``,
             which no executor.jit names; 31.7-32.0 under nothing
  32.0-36.0  fit.epoch 0: loader_next 32.0-32.1, executor.train_step
             32.1-35.0 (phase compile; trace 32.2-33.2, lower 33.2-33.5,
             backend compile 33.5-34.7, cache load 33.6-34.6), flush
             35.0-35.8, callbacks 35.8-36.0
  36.0-37.0  fit.epoch 1, the second warm-up group: THE SET-UP ENDS
  37.0-38.0  fit.epoch 2, the window: a retrace of step_fn planted at
             37.2 (it hit the cache: no file, no backend compile), a
             nested trace of ``add`` and a trace of ``other_fn``
  38.0-39.0  fit.epoch 3, the last
  39.5       a trace of step_fn after ``fit`` (the witness's): outside
"""
import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_benchmark_harness as harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells, setup_reduce as su  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
stub_profiler = harness.stub_profiler
CELLS_1_2 = ["bert_large.train.1chip", "gpt2_124m.train.1chip"]
CELLS_3_5 = ["joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
             "kimi_linear_48b_a3b.train.1chip"]
NEW = {     # name: (unit, better, source, layer, moves), in the entries' order
    "host_init_s": ("s", "lower", "program_span", "executor", "setup_s"),
    "step_trace_s": ("s", "lower", "program_span", "executor", "setup_s"),
    "step_backend_compile_s": ("s", "lower", "program_span", "executor",
                               "setup_s"),
    "xla_cache_load_s": ("s", "lower", "program_span", "entry", "setup_s"),
    "xla_cache_misses": ("count", "lower", "program_counter", "entry",
                         "setup_s"),
    "setup_attributed_share": ("%", "higher", "program_span", "entry",
                               "setup_s"),
    "retraces_after_warmup": ("count", "lower", "program_span", "executor",
                              "train_tokens_per_s"),
}
# the seven entries without a list that the manifest had before these
SHARED = ["compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"]


def _span(name, start, end, **attrs):
    return {"name": name, "kind": "span", "ts": start, "dur": end - start,
            "tid": 1, "attrs": attrs or None}


def _mark(ts, name, fun_name):
    return {"name": "executor.jit", "kind": "instant", "ts": ts, "dur": 0.0,
            "tid": 1, "attrs": {"name": name, "fun_name": fun_name}}


def _xla(kind, fun_name, start, end):
    return _span("xla." + kind, start, end, fun_name=fun_name)


# in the order a recorder would hold them: a span lands when it closes
RING = [
    _span("compile.mesh", 0.0, 0.5), _span("compile.search", 0.5, 0.6),
    _span("compile.plan", 0.6, 1.0), _span("compile.verify", 1.0, 1.2),
    _span("executor.init_params", 1.3, 8.0, parameters=7, bytes=28),
    _span("compile.init", 1.2, 8.2), _span("compile.opt_state", 8.2, 9.0),
    _span("model.compile", 0.0, 10.0, n_devices=1, n_layers=3),
    _span("executor.init_params", 10.0, 16.0, parameters=7, bytes=28),
    _mark(16.5, "train", "step_fn"),
    _xla("trace", "add", 17.5, 17.6), _xla("trace", "step_fn", 17.0, 20.0),
    _xla("lower", "step_fn", 20.0, 21.0),
    _span("xla.cache_load", 21.5, 24.5),
    _xla("backend_compile", "step_fn", 21.0, 25.0),
    _mark(25.5, "eval", "step_fn"),
    _xla("trace", "step_fn", 26.1, 27.0), _xla("lower", "step_fn", 27.0, 27.3),
    _span("xla.cache_load", 27.4, 28.4),
    _xla("backend_compile", "step_fn", 27.3, 28.8),
    _span("executor.eval_step", 26.0, 29.0, phase="compile", step=0),
    _xla("trace", "err", 29.0, 29.5), _xla("lower", "err", 29.5, 29.7),
    _xla("backend_compile", "err", 29.7, 31.7),
    _span("fit.loader_next", 32.0, 32.1),
    _xla("trace", "step_fn", 32.2, 33.2), _xla("lower", "step_fn", 33.2, 33.5),
    _span("xla.cache_load", 33.6, 34.6),
    _xla("backend_compile", "step_fn", 33.5, 34.7),
    _span("executor.train_step", 32.1, 35.0, phase="compile", step=0),
    _span("metrics_buffer.flush", 35.0, 35.8),
    _span("fit.callbacks", 35.8, 36.0), _span("fit.epoch", 32.0, 36.0),
    _span("fit.loader_next", 36.0, 36.1),
    _span("executor.train_step", 36.1, 36.2, phase="steady", step=1),
    _span("metrics_buffer.flush", 36.2, 36.9), _span("fit.epoch", 36.0, 37.0),
    _span("executor.train_step", 37.0, 37.1, phase="steady", step=2),
    _xla("trace", "add", 37.3, 37.31), _xla("trace", "step_fn", 37.2, 37.5),
    _xla("trace", "other_fn", 37.6, 37.7),
    _span("fit.epoch", 37.0, 38.0), _span("fit.epoch", 38.0, 39.0),
    _xla("trace", "step_fn", 39.5, 39.6),
]
BY_HAND = {
    "host_init_s": 6.7 + 6.0,
    "step_trace_s": (3.0 + 1.0) + (1.0 + 0.3),       # not the eval step's
    "step_backend_compile_s": 4.0 + 1.2,
    "xla_cache_load_s": 3.0 + 1.0 + 1.0,             # every function's
    "xla_cache_misses": 2,
    # compile.* 0-9, the second draw 10-16, the inspection 17-25, the
    # eval step's own compile 26.1-28.8, the reference 29-31.7, epoch 0
    # to the flush's end 32-35.8, epoch 1 likewise 36-36.9; of 37
    "setup_attributed_share": 100 * (9 + 6 + 8 + 2.7 + 2.7 + 3.8 + 0.9) / 37,
    "retraces_after_warmup": 1,
}
COUNTERS = {"xla.cache_misses": 2, "xla.cache_hits": 3,
            "xla.cache_requests": 5}


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


def _ctx(monkeypatch, ring, counters=None, dropped=0, warmup_groups=2):
    """What a traced run hands the readers, the process's ring swapped
    for ``ring``."""
    from flexflow_tpu.obs import events as obs
    monkeypatch.setattr(obs, "events", lambda: list(ring))
    monkeypatch.setattr(obs, "dropped", lambda: dropped)
    return types.SimpleNamespace(
        counters=dict(COUNTERS if counters is None else counters),
        cell=types.SimpleNamespace(
            traffic={"warmup_groups": warmup_groups}, bench_dir=BENCH))


# ----------------------------------------------------------------------
# the reduction, by hand
# ----------------------------------------------------------------------
def test_the_seven_numbers_against_a_hand_count():
    r = su.reduce_ring(RING, COUNTERS, 0, 2)
    assert {k: r[k] for k in su.METRICS} == pytest.approx(BY_HAND)
    assert tuple(BY_HAND) == su.METRICS == tuple(NEW)
    assert r["setup_s"] == 37.0
    # the inspection's, which is the first: what the runner's line
    # "train step compiled or loaded in" clocked from outside
    assert r["first_compile_s"] == {
        "xla.trace": 3.0, "xla.lower": 1.0, "xla.backend_compile": 4.0}
    assert r["by_function"]["err"] == pytest.approx({
        "xla.trace": 0.5, "xla.lower": 0.2, "xla.backend_compile": 2.0})
    assert r["by_span"]["executor.eval_step"] == 3.0


@pytest.mark.parametrize("metric", list(NEW))
def test_each_reader_gives_its_number_of_the_ring(monkeypatch, metric,
                                                  capsys):
    ctx = _ctx(monkeypatch, RING)
    assert _read(metric, ctx) == pytest.approx(BY_HAND[metric])
    # reduced once, kept on the ctx, and told in the run's earlier lines
    monkeypatch.setattr(su, "reduce_ring", None)
    assert _read(metric, ctx) == pytest.approx(BY_HAND[metric])
    said = capsys.readouterr().out
    assert said.count("[bench] set-up by the recorder: 37.000s") == 1
    assert "trace 3.000 + lower 1.000 + backend compile 4.000 = 8.000s" \
        in said
    assert "cache_requests 5, cache_hits 3, cache_misses 2" in said


def test_a_warm_start_reads_no_miss_and_a_clean_window_no_retrace():
    window = [e for e in RING
              if not (e["name"] == "xla.trace" and 37.0 <= e["ts"] < 39.0)]
    r = su.reduce_ring(window, {"xla.cache_hits": 5}, 0, 2)
    assert r["xla_cache_misses"] == 0 and r["retraces_after_warmup"] == 0
    # a retrace of the eval step's counts too: any executor.jit function
    late = window + [_xla("trace", "fwd", 38.5, 38.6),
                     _mark(16.6, "forward", "fwd")]
    assert su.reduce_ring(late, {}, 0, 2)["retraces_after_warmup"] == 1


def test_the_cuts_follow_the_warmup_groups():
    r = su.reduce_ring(RING, COUNTERS, 0, 1)
    assert r["setup_s"] == 36.0
    # epoch 1's steady step is in the window now; the planted retrace
    # still is
    assert r["retraces_after_warmup"] == 1
    assert r["setup_attributed_share"] == pytest.approx(
        100 * (9 + 6 + 8 + 2.7 + 2.7 + 3.8) / 36)
    r = su.reduce_ring(RING, COUNTERS, 0, 3)
    assert r["setup_s"] == 38.0
    assert r["retraces_after_warmup"] == 0          # it is set-up's now
    assert r["step_trace_s"] == pytest.approx(5.3 + 0.3)


def test_a_train_step_with_a_name_of_its_own_needs_no_containment():
    ring = [dict(e, attrs=dict(e["attrs"], fun_name="eval_fn"))
            if e["name"].startswith("xla.") and 26.0 <= e["ts"] < 29.0
            and e["attrs"] else e for e in RING]
    ring = [_mark(25.5, "eval", "eval_fn")
            if e["name"] == "executor.jit" and e["attrs"]["name"] == "eval"
            else e for e in ring]
    r = su.reduce_ring(ring, COUNTERS, 0, 2)
    assert {k: r[k] for k in su.METRICS} == pytest.approx(BY_HAND)


@pytest.mark.parametrize("why,ring,dropped,warmup_groups", [
    ("an event was dropped", RING, 1, 2),
    ("the loop closed fewer epochs than the warm-up has", RING, 0, 5),
    ("no executor.jit: the parent's recorder",
     [e for e in RING if e["name"] != "executor.jit"
      and not e["name"].startswith("xla.")], 0, 2),
    ("no event at all", [], 0, 2),
])
def test_nothing_is_read_where_the_ring_cannot_give_it(
        monkeypatch, capsys, why, ring, dropped, warmup_groups):
    assert su.reduce_ring(ring, COUNTERS, dropped, warmup_groups) \
        == dict.fromkeys(su.METRICS)
    ctx = _ctx(monkeypatch, ring, dropped=dropped,
               warmup_groups=warmup_groups)
    assert [_read(metric, ctx) for metric in NEW] == [None] * 7
    assert "[bench]" not in capsys.readouterr().out


def test_union_of_intervals():
    assert su.union_s([]) == 0.0
    assert su.union_s([(3, 4), (0, 2), (1, 1.5), (1.5, 2.5)]) == 3.5


# ----------------------------------------------------------------------
# the manifest, by name
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(entry, workload) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


@pytest.mark.parametrize("name", list(NEW))
def test_every_new_metric_has_its_entry_and_its_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    unit, better, source, layer, moves = NEW[name]
    entry = dict(by_name[name])
    entry.pop("workloads", None)
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves}
    assert all(_reports(by_name[name], w) for w in CELLS_1_2)
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_seven_and_the_older_shared_entries_stand_by_name(manifest):
    """By name, and open to later entries and cells: every name once,
    the seven shared entries with no list, the seven of the set-up
    after them, ``compile_s`` as it was, the five cells in their
    order."""
    order = [m["name"] for m in manifest["per_layer"]]
    assert len(set(order)) == len(order)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in SHARED:
        assert "workloads" not in by_name[name], name
        assert order.index(name) < order.index("host_init_s")
    assert [n for n in order if n in SHARED] == SHARED
    assert [n for n in order if n in NEW] == list(NEW)
    cells_ = [w["name"] for w in manifest["workloads"]]
    assert [c for c in cells_ if c in CELLS_1_2 + CELLS_3_5] \
        == CELLS_1_2 + CELLS_3_5
    assert by_name["compile_s"] == {
        "name": "compile_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "entry", "moves": "setup_s"}
    assert by_name["in_window_compiles"]["source"] == "program_counter"


@pytest.mark.parametrize("workload", CELLS_1_2 + CELLS_3_5)
def test_every_training_cell_reports_the_seven(workload):
    """The readers find the same spans in every training cell's ring,
    so the entries list no cells."""
    mine = {m["name"] for m in cells.resolve_cell(ROOT, workload).per_layer}
    assert set(NEW) <= mine


# ----------------------------------------------------------------------
# through the runner, at a tiny size: the program's own ring
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """``test_benchmark_harness.py``'s tiny cells, and the seven metrics
    listing them beside cells 1 and 2 (as a ``benchmark`` PR would
    append a cell to their lists)."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, (base, over) in harness.TINY.items():
        harness.add_cell(root, name, base, over, "train_tiny",
                         harness.TINY_TRAFFIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    for m in man["per_layer"]:
        if m["name"] in NEW and "workloads" in m:
            m["workloads"] = m["workloads"] + [
                f"{name}.train" for name in harness.TINY]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


@pytest.mark.parametrize("config", sorted(harness.TINY))
def test_a_traced_run_reads_the_seven_from_the_programs_ring(
        tiny_root, stub_profiler, config):
    from flexflow_tpu.obs import events as obs
    was_enabled = obs.enabled()
    said = []
    try:
        res = bench_run.run_cell(tiny_root, f"{config}.train", 2 ** 31 + 37,
                                 0.3, True, say=said.append)
        ring = obs.events()
    finally:
        if not was_enabled:
            obs.disable()
        obs.clear()
    got = {name: res["metrics"][name] for name in NEW}
    assert {name: m["unit"] for name, m in got.items()} \
        == {name: spec[0] for name, spec in NEW.items()}
    value = {name: m["value"] for name, m in got.items()}
    assert value["retraces_after_warmup"] == 0 \
        == res["metrics"]["in_window_compiles"]["value"]
    assert value["xla_cache_misses"] == 0        # (no cache on the CPU)
    assert value["xla_cache_load_s"] == 0
    # two draws: compile()'s and the runner's from its seed
    draws = [e for e in ring if e["name"] == "executor.init_params"]
    assert len(draws) == 2
    assert value["host_init_s"] == pytest.approx(
        sum(e["dur"] for e in draws))
    assert value["step_trace_s"] > 0 and value["step_backend_compile_s"] > 0
    assert 50.0 < value["setup_attributed_share"] <= 100.0
    # the steps are known from the reference by name alone
    marks = {e["attrs"]["name"]: e["attrs"]["fun_name"]
             for e in ring if e["name"] == "executor.jit"}
    assert marks == {"train": "step_fn", "eval": "step_fn"}
    compiled = {(e["attrs"] or {}).get("fun_name") for e in ring
                if e["name"] == "xla.backend_compile"}
    assert {"step_fn", "err"} <= compiled
    # the runner's own clock around the inspection, and XLA's events of
    # it on the recorder's, tell the same seconds
    line, = [s for s in said if s.startswith("train step compiled or")]
    clocked = float(line.split(" in ")[1].split("s:")[0])
    first = [next(e["dur"] for e in ring if e["name"] == name
                  and e["attrs"]["fun_name"] == "step_fn")
             for name in ("xla.trace", "xla.lower", "xla.backend_compile")]
    assert sum(first) <= clocked + 0.05
    assert sum(first) > 0.5 * clocked


def test_an_untraced_run_reads_none_of_them(tiny_root, stub_profiler):
    res = bench_run.run_cell(tiny_root, "gpt2_tiny.train", 2 ** 31 + 37,
                             0.3, False, say=lambda s: None)
    assert not set(NEW) & set(res["metrics"])
