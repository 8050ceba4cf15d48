"""Observability subsystem (flexflow_tpu/obs): span ring buffer,
Prometheus exposition, Chrome trace export, strategy audit records,
executor step spans, /metrics + /healthz end-to-end, and the
disabled-mode no-op guarantees (ISSUE 2)."""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.obs import events
from flexflow_tpu.obs.metrics_registry import MetricsRegistry
from flexflow_tpu.obs.trace_export import (export_chrome_trace,
                                           to_chrome_trace)


@pytest.fixture
def traced():
    """Tracing on with a fresh buffer; restores the PRIOR enabled state
    after (the ci.sh FF_TRACE=1 smoke pass runs other test files in the
    same process — teardown must not switch their tracing off)."""
    was_enabled = events.enabled()
    events.enable(capacity=events.DEFAULT_CAPACITY)
    events.clear()
    try:
        yield events
    finally:
        if not was_enabled:
            events.disable()
        events.clear()


# ----------------------------------------------------------------------
# events: spans, counters, ring buffer
# ----------------------------------------------------------------------

def test_span_nesting(traced):
    with events.span("outer", depth=0):
        time.sleep(0.002)
        with events.span("inner"):
            time.sleep(0.002)
    evs = {e["name"]: e for e in events.events()}
    assert set(evs) == {"outer", "inner"}
    o, i = evs["outer"], evs["inner"]
    # the inner span completes first but nests inside the outer's window
    assert i["ts"] >= o["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-9
    assert o["attrs"] == {"depth": 0}
    assert o["tid"] == threading.get_ident()


def test_ring_buffer_wraparound():
    was_enabled = events.enabled()
    events.enable(capacity=8)
    events.clear()
    try:
        for k in range(12):
            with events.span(f"s{k}"):
                pass
        evs = events.events()
        assert len(evs) == 8
        # newest 8 survive, oldest first
        assert [e["name"] for e in evs] == [f"s{k}" for k in range(4, 12)]
        assert events.dropped() == 4
    finally:
        events.enable(capacity=events.DEFAULT_CAPACITY)  # restore ring
        if not was_enabled:
            events.disable()
        events.clear()


def test_counters_and_instants(traced):
    events.counter("x")
    events.counter("x", 2)
    events.instant("tick", why="test")
    assert events.counters() == {"x": 3}
    inst = [e for e in events.events() if e["kind"] == "instant"]
    assert len(inst) == 1 and inst[0]["name"] == "tick"
    assert inst[0]["attrs"] == {"why": "test"}


def test_disabled_mode_is_noop():
    was_enabled = events.enabled()
    events.disable()
    events.clear()
    try:
        events.counter("never")
        events.instant("never")
        with events.span("never"):
            pass
        events.record_span("never", 0.0, 1.0)
        assert events.events() == []
        assert events.counters() == {}
        # a span OPENED while disabled records nothing even if tracing
        # turns on mid-flight (its t0 was never taken)
        s = events.span("straddle")
        s.__enter__()
        events.enable()
        s.__exit__(None, None, None)
        assert all(e["name"] != "straddle" for e in events.events())
    finally:
        if was_enabled:
            events.enable()
        else:
            events.disable()
        events.clear()


def test_threaded_recording(traced):
    def worker(k):
        for j in range(50):
            with events.span(f"w{k}"):
                events.counter("work")

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert events.counters()["work"] == 200
    assert len(events.events()) == 200


# ----------------------------------------------------------------------
# metrics registry: Prometheus exposition golden text
# ----------------------------------------------------------------------

def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("ff_requests_total", "Requests").inc(model="m")
    reg.counter("ff_requests_total").inc(2, model="n")
    reg.gauge("ff_queue_depth", "Queue depth").set(3, model="m")
    h = reg.histogram("ff_lat", "Latency", buckets=(0.01, 0.1))
    h.observe(0.005, model="m")
    h.observe(0.05, model="m")
    h.observe(7.0, model="m")
    golden = """\
# HELP ff_requests_total Requests
# TYPE ff_requests_total counter
ff_requests_total{model="m"} 1
ff_requests_total{model="n"} 2
# HELP ff_queue_depth Queue depth
# TYPE ff_queue_depth gauge
ff_queue_depth{model="m"} 3
# HELP ff_lat Latency
# TYPE ff_lat histogram
ff_lat_bucket{model="m",le="0.01"} 1
ff_lat_bucket{model="m",le="0.1"} 2
ff_lat_bucket{model="m",le="+Inf"} 3
ff_lat_sum{model="m"} 7.055
ff_lat_count{model="m"} 3
"""
    assert reg.render() == golden


def test_prometheus_help_escaping_golden():
    # HELP text with a newline and a backslash must render as ONE line
    # (escaped per the exposition format) or the scrape parser breaks
    reg = MetricsRegistry()
    reg.counter("ff_esc", 'path C:\\x "quoted"\nline two').inc()
    golden = """\
# HELP ff_esc path C:\\\\x "quoted"\\nline two
# TYPE ff_esc counter
ff_esc 1
"""
    assert reg.render() == golden
    assert len([l for l in reg.render().splitlines()
                if l.startswith("# HELP")]) == 1


def test_registry_kind_conflict():
    reg = MetricsRegistry()
    reg.counter("dup", "c")
    with pytest.raises(TypeError):
        reg.gauge("dup")


# ----------------------------------------------------------------------
# Chrome trace export golden
# ----------------------------------------------------------------------

def test_chrome_trace_export_golden(tmp_path, traced):
    events.record_span("phase_a", 10.0, 0.5, k=1)
    events.record_span("phase_b", 10.5, 0.25)
    events.instant("marker")
    events.counter("c", 4)
    path = export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    pid = os.getpid()
    # span/instant payload events, metadata ('M') stripped: rebased to
    # the earliest event (phase_a at 10.0s -> ts 0)
    te = [e for e in doc["traceEvents"] if e["ph"] in ("X", "i")]
    assert te[0]["name"] == "phase_a" and te[0]["ph"] == "X"
    assert te[0]["ts"] == 0.0 and te[0]["dur"] == 500000.0
    assert te[0]["pid"] == pid and te[0]["args"] == {"k": 1}
    assert te[1]["name"] == "phase_b" and te[1]["ts"] == 500000.0 \
        and te[1]["dur"] == 250000.0
    assert te[2]["ph"] == "i" and te[2]["s"] == "t"
    # process/thread metadata + counters as Chrome 'C' counter events
    metas = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= metas
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert [(e["name"], e["args"]["value"]) for e in cs] == [("c", 4)]
    assert doc["otherData"]["counters"] == {"c": 4}
    assert doc["displayTimeUnit"] == "ms"
    # the same doc from the API matches the exported file
    assert to_chrome_trace() == doc


# ----------------------------------------------------------------------
# executor wiring: per-step spans, compile-vs-steady split
# ----------------------------------------------------------------------

def _tiny_mlp(search_budget=None):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import build_mlp
    cfg = FFConfig()
    cfg.batch_size = 16
    if search_budget is None:
        cfg.only_data_parallel = True
    else:
        cfg.search_budget = search_budget
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=32, hidden=(64,), num_classes=8)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    rng = np.random.default_rng(0)
    batch = {"input": rng.normal(size=(16, 32)).astype(np.float32),
             "label": rng.integers(0, 8, size=(16, 1)).astype(np.int32)}
    return ff, batch


def test_executor_step_spans_compile_vs_steady(traced):
    ff, batch = _tiny_mlp()
    step = ff.executor.make_train_step()
    for _ in range(3):
        ff._run_train_step(step, batch)
    spans = [e for e in events.events()
             if e["name"] == "executor.train_step"]
    assert len(spans) == 3
    assert [s["attrs"]["phase"] for s in spans] == \
        ["compile", "steady", "steady"]
    # the compiling first call dwarfs a steady replay
    assert spans[0]["dur"] > spans[1]["dur"]
    assert events.counters()["executor.train_steps"] == 3
    assert any(e["name"] == "model.compile" for e in events.events())
    # the raw jitted callable stays reachable
    assert callable(step.__wrapped__)


def test_recompile_event(traced):
    from flexflow_tpu.obs.metrics_registry import REGISTRY
    ff, batch = _tiny_mlp()
    before = REGISTRY.counter("ff_recompiles_total").value()
    ff.recompile_on_condition(
        trigger=lambda rs: rs.iteration == 2,
        alter=lambda rs: None)
    ff.fit(x=batch["input"], y=batch["label"], epochs=3, verbose=False)
    assert any(e["name"] == "runtime.recompile"
               for e in events.events())
    assert events.counters().get("executor.recompiles") == 1
    assert REGISTRY.counter("ff_recompiles_total").value() == before + 1
    # fit routed the throughput gauge
    assert REGISTRY.gauge("ff_train_samples_per_sec").value() > 0


# ----------------------------------------------------------------------
# strategy audit record (acceptance criterion)
# ----------------------------------------------------------------------

def test_unity_search_writes_strategy_audit(traced):
    ff, _ = _tiny_mlp(search_budget=4)
    path = getattr(ff, "_strategy_audit_path", None)
    assert path and os.path.exists(path), \
        "unity search with tracing on must write a strategy audit record"
    doc = json.load(open(path))
    assert doc["search_algo"] == "unity"
    for side in ("adopted", "dp_baseline"):
        rec = doc[side]
        assert rec["per_op"], side
        total = sum(e["total_s"] for e in rec["per_op"])
        # per-op predicted totals sum to the side's reported cost
        np.testing.assert_allclose(total, rec["total_s"], rtol=1e-9)
        comp = sum(e["fwd_s"] + e["bwd_s"] for e in rec["per_op"])
        np.testing.assert_allclose(comp, rec["compute_s"], rtol=1e-9)
    assert doc["predicted_dp_over_searched"] > 0
    assert events.counters().get("search.audit_records") == 1


def test_audit_not_written_when_disabled(tmp_path):
    was_enabled = events.enabled()
    events.disable()
    try:
        ff, _ = _tiny_mlp(search_budget=4)
        assert getattr(ff, "_strategy_audit_path", None) is None
    finally:
        if was_enabled:
            events.enable()


def test_mcmc_search_writes_strategy_audit(traced):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import build_mlp
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.search_algo = "mcmc"
    cfg.search_budget = 20
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=32, hidden=(64,), num_classes=8)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    path = getattr(ff, "_strategy_audit_path", None)
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["search_algo"] == "mcmc"
    for side in ("adopted", "dp_baseline"):
        total = sum(e["total_s"] for e in doc[side]["per_op"])
        np.testing.assert_allclose(total, doc[side]["total_s"],
                                   rtol=1e-9)


# ----------------------------------------------------------------------
# serving: /metrics + /healthz end-to-end against a live serve_async
# ----------------------------------------------------------------------

def _onnx_mlp(batch=4, in_dim=8, hidden=16, out_dim=4):
    from flexflow_tpu.frontends import onnx_wire as w
    rng = np.random.default_rng(7)
    w1 = rng.normal(size=(hidden, in_dim)).astype(np.float32) * 0.3
    w2 = rng.normal(size=(out_dim, hidden)).astype(np.float32) * 0.3
    return w.make_model(
        nodes=[w.make_node("Gemm", ["x", "w1"], ["h"], name="fc1",
                           transB=1),
               w.make_node("Relu", ["h"], ["hr"], name="relu1"),
               w.make_node("Gemm", ["hr", "w2"], ["y"], name="fc2",
                           transB=1)],
        inputs=[w.make_value_info("x", 1, [batch, in_dim])],
        outputs=[w.make_value_info("y", 1, [batch, out_dim])],
        initializers=[w.make_tensor("w1", w1), w.make_tensor("w2", w2)])


def test_metrics_and_healthz_endpoints():
    import socket
    from flexflow_tpu.serving import ModelRepository, serve_async

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = ModelRepository()
    repo.load_onnx("m", _onnx_mlp())
    srv = serve_async(repo, port=port, block=False)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        r = urllib.request.urlopen(base + "/healthz", timeout=30)
        assert r.status == 200
        assert json.loads(r.read())["status"] == "ok"
        x = np.zeros((2, 8), np.float32)
        body = json.dumps({"inputs": [{
            "name": "x", "shape": [2, 8],
            "data": x.ravel().tolist()}]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            base + "/v2/models/m/infer", data=body), timeout=60)
        assert r.status == 200
        r = urllib.request.urlopen(base + "/metrics", timeout=30)
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
        # request-latency histogram buckets for the model just served
        assert "# TYPE ff_request_latency_seconds histogram" in text
        assert 'ff_request_latency_seconds_bucket{le="' in text \
            or 'ff_request_latency_seconds_bucket{model="m",le="' in text
        assert 'ff_request_latency_seconds_count{model="m"}' in text
        assert 'ff_requests_total{model="m"}' in text
        assert 'ff_queue_depth{model="m"}' in text
        assert 'ff_scheduler_instances{model="m"}' in text
        # the JSON metrics surface is unchanged
        m = json.loads(urllib.request.urlopen(
            base + "/v2/metrics", timeout=30).read())
        assert m["models"]["m"]["completed"] >= 1
    finally:
        srv.stop()


def test_threading_front_serves_metrics_too():
    from flexflow_tpu.serving import ModelRepository, serve_http
    repo = ModelRepository()
    repo.load_onnx("m", _onnx_mlp())
    srv, t, scheds = serve_http(repo, port=0, block=False)
    try:
        port = srv.server_address[1]
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30)
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        assert "ff_queue_depth" in r.read().decode()
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30)
        assert json.loads(r.read())["ready"] is True
    finally:
        srv.shutdown()
        for sc in scheds.values():
            sc.close()


# ----------------------------------------------------------------------
# satellites: profiler summary, FF_LOG parsing
# ----------------------------------------------------------------------

def test_profiler_summary_p90_max_and_single_step():
    from flexflow_tpu.utils.profiling import Profiler
    p = Profiler()
    for _ in range(4):
        with p.step():
            time.sleep(0.003)
    s = p.summary()
    assert {"p90_step_s", "max_step_s"} <= set(s)
    assert s["max_step_s"] >= s["p90_step_s"] >= s["p50_step_s"] > 0
    # single recorded step = compile only; steady-state stats must NOT
    # report the compiling step as a steady step time
    p1 = Profiler()
    with p1.step():
        time.sleep(0.003)
    s1 = p1.summary()
    assert s1["compile_s"] >= 0.003
    assert s1["mean_step_s"] == 0.0 and s1["max_step_s"] == 0.0
    from flexflow_tpu.obs.metrics_registry import REGISTRY
    assert REGISTRY.gauge("ff_profiler_compile_s").value(
        profiler="default") >= 0.003


def test_ff_log_env_parsing():
    from flexflow_tpu.utils.logger import parse_ff_log
    assert parse_ff_log("dp=2,sim=1,xfers=0") == \
        {"dp": 2, "sim": 1, "xfers": 0}
    assert parse_ff_log(" dp = 2 , bogus, =3, x=y ") == {"dp": 2}
    assert parse_ff_log("") == {}


def test_recursive_logger_thread_safety(capsys):
    from flexflow_tpu.utils.logger import RecursiveLogger, set_log_level
    set_log_level("obs_t", 2)
    log = RecursiveLogger("obs_t")

    def worker():
        for _ in range(20):
            with log.enter("o"):
                log.log("i")

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    lines = capsys.readouterr().err.strip().splitlines()
    assert len(lines) == 4 * 20 * 2
    # per-thread depth: every inner line is exactly one level deep —
    # never stacked by a sibling thread's concurrent enter()
    assert set(lines) == {"[obs_t] o", "[obs_t]   i"}


# ----------------------------------------------------------------------
# one clock: spans in the profiler's trace, scopes in the train step
# ----------------------------------------------------------------------

class _FakeAnnotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))


def test_enabled_span_is_a_trace_annotation_named_ff(traced, monkeypatch):
    import jax
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    with events.span("outer", k=1):
        with events.span("inner"):
            pass
    assert _FakeAnnotation.log == [
        ("enter", "ff:outer"), ("enter", "ff:inner"),
        ("exit", "ff:inner"), ("exit", "ff:outer")]
    assert [e["name"] for e in events.events()] == ["inner", "outer"]
    # after the fact there is nothing to enter: no annotation
    events.record_span("late", 0.0, 1.0)
    assert len(_FakeAnnotation.log) == 4


def test_disabled_span_neither_imports_nor_calls_the_profiler(monkeypatch):
    import sys
    was_enabled = events.enabled()
    events.disable()
    try:
        # a None entry makes any ``import jax.profiler`` raise
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        with events.span("never") as s:
            s.set(k=1)
        with pytest.raises(ImportError):
            from jax.profiler import TraceAnnotation  # noqa: F401
    finally:
        if was_enabled:
            events.enable()


def test_fit_loop_spans_nest_in_fit_epoch_on_one_thread(traced):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import build_mlp
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.async_dispatch_steps = 1      # so that the window has to wait
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=32, hidden=(64,), num_classes=8)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(48, 32)).astype(np.float32)
    y = rng.integers(0, 8, size=(48, 1)).astype(np.int32)

    class Seen:
        epochs = []

        def on_epoch_end(self, epoch, report, model):
            self.epochs.append(epoch)

    events.clear()
    ff.fit(x=x, y=y, epochs=1, callbacks=[Seen()], verbose=False)
    assert Seen.epochs == [0]
    evs = events.events()
    epoch, = [e for e in evs if e["name"] == "fit.epoch"]
    assert epoch["attrs"] == {"epoch": 0, "batches": 3}
    inner = [e for e in evs if e is not epoch and e["name"] != "obs.attribution"]
    count = {}
    for e in inner:
        count[e["name"]] = count.get(e["name"], 0) + 1
        assert e["tid"] == epoch["tid"], e
        assert e["ts"] >= epoch["ts"] and \
            e["ts"] + e["dur"] <= epoch["ts"] + epoch["dur"] + 1e-9, e
    # the epoch that builds the step also holds its ``executor.jit``
    # mark and XLA's events of its one compile (and of what it traced
    # on the way), on the loop's thread like the rest
    assert count.pop("executor.jit") == 1
    xla = {k: count.pop(k) for k in sorted(count) if k.startswith("xla.")}
    assert xla["xla.lower"] == xla["xla.backend_compile"] == 1
    assert xla["xla.trace"] >= 1 and len(xla) == 3
    inner = [e for e in inner if e["name"] in count]
    # three batches: four fetches (the last one ends the epoch), three
    # dispatches, two waits on the step leaving a window of one
    assert count == {"fit.loader_next": 4, "executor.train_step": 3,
                     "metrics_buffer.window_wait": 2,
                     "metrics_buffer.flush": 1, "fit.callbacks": 1}
    flush, = [e for e in inner if e["name"] == "metrics_buffer.flush"]
    assert flush["attrs"]["steps"] == 3 and flush["attrs"]["window"] == 1
    assert flush["attrs"]["blocked_ms"] >= 0
    order = [e["name"] for e in sorted(inner, key=lambda e: e["ts"])]
    assert order[0] == "fit.loader_next" and order[-1] == "fit.callbacks"
    assert order[-2] == "metrics_buffer.flush"


def tiny_gpt2_lowered_step(seq: int = 128):
    """The lowered train step of a two-layer GPT-2 with the flash kernel
    forced (interpret mode here)."""
    import dataclasses
    import jax.numpy as jnp
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models.nlp import GPTConfig, build_gpt2
    cfg = FFConfig()
    cfg.batch_size = 2
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:flash"
    ff = FFModel(cfg)
    out = build_gpt2(ff, 2, seq, dataclasses.replace(
        GPTConfig.tiny(), max_position=seq))
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    ids = np.zeros((2, seq), np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (2, 1))
    batch = next(iter(ff._combined_loader(
        [ids, pos], np.zeros((2, seq, 1), np.int32), shuffle=False)))
    return ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state, jnp.int32(0), batch)


def tiny_gpt2_step_text() -> str:
    """Debug locations and all: what carries the scopes and kernel
    names."""
    return tiny_gpt2_lowered_step().as_text(debug_info=True)


@pytest.fixture(scope="module")
def step_text():
    return tiny_gpt2_step_text()


@pytest.mark.parametrize("pattern", [
    r"jit\(step_fn\)/jvp\(ff\.forward\)/",
    r"transpose\(jvp\(ff\.forward\)\)/",
    r"jit\(step_fn\)/jvp\(ff\.loss\)/",
    r"jit\(step_fn\)/ff\.optimizer/",
    r"jvp\(ff\.forward\)/lm_head/dot_general",
    r"transpose\(jvp\(ff\.forward\)\)/lm_head/dot_general",
    # (a layer without a name of its own is numbered by the process)
    r"/jvp\(ff\.forward\)/op_multihead_attention_\d+/",
    r"/transpose\(jvp\(ff\.forward\)\)/op_multihead_attention_\d+/",
    # (on this 8-device mesh the kernels sit in a shard_map, whose body
    # starts a name stack of its own: the kernel's name is all it has)
    r"flash_attention_fwd/pallas_call",
    r"flash_attention_bwd_dq/pallas_call",
    r"flash_attention_bwd_dkv/pallas_call"])
def test_train_step_carries_phase_layer_and_kernel_names(pattern,
                                                         step_text):
    import re
    assert re.search(pattern, step_text)


def test_step_text_is_the_same_in_two_fresh_processes():
    """The compile cache's guard: a scope or kernel name that differed
    from one process to the next (a counter, an ``id()``) would make
    every warm start compile the step again."""
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    code = ("import hashlib, sys; sys.path[:0] = [%r, %r]; import conftest;"
            " import test_obs; print(hashlib.sha256(test_obs."
            "tiny_gpt2_step_text().encode()).hexdigest())"
            % (os.path.dirname(here), here))
    procs = [subprocess.Popen([sys.executable, "-c", code],
                              stdout=subprocess.PIPE, text=True)
             for _ in range(2)]
    digests = [p.communicate(timeout=300)[0].strip().splitlines()[-1]
               for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert len(digests[0]) == 64 and digests[0] == digests[1]


# ----------------------------------------------------------------------
# flash.grid: what the flash kernels' grids cost, at trace time
# ----------------------------------------------------------------------

def _flash_grid_events():
    return [e for e in events.events() if e["name"] == "flash.grid"]


def test_traced_train_step_records_one_flash_grid_instant_per_kernel_call(
        traced):
    """Two causal attention layers at seq 2048: the derived backward
    tiles on a grid of several blocks a side, part of them above the
    diagonal, and a forward whose every step holds all 2048 keys and
    walks them in four pieces, part of those above the diagonal."""
    tiny_gpt2_lowered_step(seq=2048)
    by_kernel = {}
    for e in _flash_grid_events():
        by_kernel.setdefault(e["attrs"]["kernel"], []).append(e["attrs"])
    assert sorted(by_kernel) == ["flash_attention_bwd_dkv",
                                 "flash_attention_bwd_dq",
                                 "flash_attention_fwd"]
    for seen in by_kernel.values():    # two layers a trace of the step
        assert len(seen) == len(by_kernel["flash_attention_fwd"])
        assert len(seen) % 2 == 0
        for g in seen:
            assert g["fetched_steps"] == g["live_steps"] <= g["steps"], g
            assert (g["live_steps"] < g["steps"]) == ("bwd" in g["kernel"])
            assert 2048 % g["block_q"] == 0 and 2048 % g["block_k"] == 0
    fwd = by_kernel["flash_attention_fwd"][0]
    assert (fwd["block_q"], fwd["block_k"], fwd["piece_k"]) == (
        1024, 2048, 512)
    assert fwd["live_pieces"] * 8 == fwd["steps"] * 4 * 6


def test_no_flash_grid_event_and_no_profiler_with_events_off(monkeypatch):
    import sys
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.kernels import flash_attention
    was_enabled = events.enabled()
    events.disable()
    events.clear()
    try:
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        q = jnp.zeros((1, 2, 256, 64), jnp.float32)
        jax.eval_shape(jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, q, causal=True, interpret=True))), q)
        assert _flash_grid_events() == []
        with pytest.raises(ImportError):
            from jax.profiler import TraceAnnotation  # noqa: F401
    finally:
        if was_enabled:
            events.enable()


# ----------------------------------------------------------------------
# set-up and compiles: XLA's own events on the recorder
# (obs/xla_events.py), the phases of compile(), executor.jit
# ----------------------------------------------------------------------

XLA_SPANS = ("xla.trace", "xla.lower", "xla.backend_compile")


def _of(name, fun_name=None):
    return [e for e in events.events() if e["name"] == name
            and (fun_name is None
                 or (e["attrs"] or {}).get("fun_name") == fun_name)]


def _ours(listeners):
    return [f for f in listeners
            if getattr(f, "__module__", "") == "flexflow_tpu.obs.xla_events"]


def _listeners():
    from jax._src import monitoring
    return (_ours(monitoring.get_event_time_span_listeners()),
            _ours(monitoring.get_event_duration_listeners()),
            _ours(monitoring.get_event_listeners()))


def test_a_fresh_jit_leaves_its_trace_lower_and_compile_inside_the_caller(
        traced):
    import jax
    import jax.numpy as jnp

    def probe_fn(x):
        return jnp.sin(x) * 2.0

    f = jax.jit(probe_fn)
    x3, x4 = jnp.ones((3,)), jnp.ones((4,))     # (eager ops compile here)
    events.clear()
    with events.span("caller"):
        f(x3).block_until_ready()
    caller, = _of("caller")
    for name in XLA_SPANS:
        got, = _of(name, "probe_fn")
        # on the recorder's clock, though JAX stamped it on another
        assert caller["ts"] - 5e-3 <= got["ts"]
        assert got["ts"] + got["dur"] <= caller["ts"] + caller["dur"] + 5e-3
        assert got["dur"] > 0 and got["kind"] == "span"
    assert events.counters()["xla.compiles/probe_fn"] == 1
    # a cached dispatch tells nothing
    n = len(events.events())
    f(x3).block_until_ready()
    assert len(events.events()) == n
    # a new shape is a recompile, and it is named
    f(x4).block_until_ready()
    assert [len(_of(name, "probe_fn")) for name in XLA_SPANS] == [2, 2, 2]
    assert events.counters()["xla.compiles/probe_fn"] == 2


def test_cache_events_move_the_counters_and_a_load_is_a_span(traced):
    from jax import monitoring
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert events.counters() == {
        "xla.cache_hits": 2, "xla.cache_misses": 1, "xla.cache_requests": 1}
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 3.0)
    now = time.perf_counter()
    load, = [e for e in events.events() if e["name"].startswith("xla.")]
    assert load["name"] == "xla.cache_load" and load["dur"] == 0.25
    assert abs(load["ts"] + load["dur"] - now) < 5e-3   # ends at the call
    # the name JAX gives a lowering or a compile is the module's
    t = time.time()
    monitoring.record_event_time_span(
        "/jax/core/compile/backend_compile_duration", t - 1.5, t,
        fun_name="jit(step_fn)")
    monitoring.record_event_time_span("/jax/other", t - 1.5, t)
    span, = _of("xla.backend_compile")
    assert span["attrs"] == {"fun_name": "step_fn"}
    assert span["dur"] == pytest.approx(1.5)
    assert abs(span["ts"] + 1.5 - time.perf_counter()) < 5e-3
    assert events.counters()["xla.compiles/step_fn"] == 1


def test_the_listeners_exist_only_while_the_recorder_is_on():
    import jax
    import jax.numpy as jnp
    was_enabled = events.enabled()
    try:
        events.enable()
        events.enable()                          # idempotent
        assert [len(fs) for fs in _listeners()] == [1, 1, 1]
        events.disable()
        assert [len(fs) for fs in _listeners()] == [0, 0, 0]
        events.clear()
        jax.jit(lambda x: x + 1)(jnp.ones((5,))).block_until_ready()
        assert events.events() == [] and events.counters() == {}
    finally:
        if was_enabled:
            events.enable()
        events.clear()


def test_a_process_that_never_traced_has_no_listener_of_ours():
    """With the recorder never on, a compile and a fit register nothing
    with ``jax.monitoring`` and never import the listeners' module."""
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    code = (
        "import os, sys; os.environ.pop('FF_TRACE', None);"
        " sys.path[:0] = [%r, %r]; import conftest; import test_obs;"
        " ff, batch = test_obs._tiny_mlp();"
        " ff._run_train_step(ff.executor.make_train_step(), batch);"
        " from jax._src import monitoring as m;"
        " print(len(m.get_event_duration_listeners()),"
        " len(m.get_event_time_span_listeners()),"
        " len(m.get_event_listeners()),"
        " 'flexflow_tpu.obs.xla_events' in sys.modules,"
        " sorted(ff._compile_phases))" % (os.path.dirname(here), here))
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         stdout=subprocess.PIPE, timeout=300, check=True)
    assert out.stdout.strip().splitlines()[-1] == \
        "0 0 0 False ['compile_s', 'init_s', 'search_s', 'verify_s']"


def test_a_timed_span_gives_its_one_reading_on_or_off(traced):
    with events.timed_span("phase", k=1) as on:
        time.sleep(0.002)
    span, = _of("phase")
    assert span["dur"] == on.dur >= 0.002 and span["attrs"] == {"k": 1}
    events.disable()
    try:
        with events.timed_span("phase") as off:
            time.sleep(0.002)
    finally:
        events.enable()
    assert off.dur >= 0.002 and len(_of("phase")) == 1


@pytest.mark.parametrize("search_budget", [None, 4])
def test_compile_phases_are_the_readings_of_the_compile_spans(
        traced, search_budget):
    import jax
    ff, _ = _tiny_mlp(search_budget=search_budget)
    whole, = _of("model.compile")
    digits = {"search": 3, "verify": 6, "init": 3}
    for key, n in digits.items():
        span, = _of("compile." + key)
        assert ff._compile_phases[key + "_s"] == round(span["dur"], n)
    assert ff._compile_phases["compile_s"] == round(whole["dur"], 6)
    assert list(ff._compile_phases) == ["search_s", "verify_s", "init_s",
                                        "compile_s"]
    # every phase lies in the whole, in the order compile() runs them
    order = ["compile.mesh", "compile.search", "compile.plan",
             "compile.verify", "compile.init", "compile.opt_state"]
    phases = [_of(name)[0] for name in order]
    assert [p["ts"] for p in phases] == sorted(p["ts"] for p in phases)
    assert whole["ts"] <= phases[0]["ts"]
    assert phases[-1]["ts"] + phases[-1]["dur"] \
        <= whole["ts"] + whole["dur"]
    assert whole["attrs"] == {"n_devices": ff.dmesh.num_devices,
                              "n_layers": len(ff.layers)}
    # the draw of the weights is inside compile.init and says its size
    draw, = _of("executor.init_params")
    init = phases[4]
    assert init["ts"] <= draw["ts"] \
        and draw["ts"] + draw["dur"] <= init["ts"] + init["dur"]
    leaves = jax.tree.leaves(ff.params)
    from flexflow_tpu.executor import device_bytes
    assert draw["attrs"] == {
        "parameters": sum(a.size for a in leaves),
        "bytes": sum(a.nbytes for a in leaves),
        # what the fullest device holds once placed (PR 53)
        "device_bytes": device_bytes((ff.params, ff.state))}
    assert 0 < draw["attrs"]["device_bytes"] <= draw["attrs"]["bytes"] \
        + sum(a.nbytes for a in jax.tree.leaves(ff.state))
    assert phases[5]["attrs"] == {
        "device_bytes": device_bytes(ff.opt_state)}
    # a second draw (a benchmark's, from its seed) is a second span
    ff.executor.init_params_and_state(jax.random.key(7))
    assert len(_of("executor.init_params")) == 2


def test_a_given_strategy_is_compiled_without_a_search_phase(traced):
    from flexflow_tpu import SGDOptimizer
    ff, _ = _tiny_mlp()
    events.clear()
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=ff._output_tensor, strategy=ff.strategy)
    assert _of("compile.search") == []
    assert list(ff._compile_phases) == ["verify_s", "init_s", "compile_s"]


def test_a_jit_says_which_function_it_is(traced):
    ff, batch = _tiny_mlp()
    step = ff.executor.make_train_step()
    ff.executor.make_eval_step()
    assert ff.executor.make_train_step() is step          # (kept: no mark)
    marks = {e["attrs"]["name"]: e["attrs"]["fun_name"]
             for e in _of("executor.jit")}
    assert marks == {"train": "step_fn", "eval": "step_fn"}
    assert all(e["kind"] == "instant" for e in _of("executor.jit"))
    assert _of("xla.trace", "step_fn") == []              # nothing yet
    ff._run_train_step(step, batch)
    ff._run_train_step(step, batch)
    first, second = _of("executor.train_step")
    assert first["attrs"]["phase"] == "compile"
    for name in XLA_SPANS:
        got, = _of(name, marks["train"])
        assert first["ts"] - 5e-3 <= got["ts"] and got["ts"] + got["dur"] \
            <= first["ts"] + first["dur"] + 5e-3
    assert events.counters()["xla.compiles/step_fn"] == 1
