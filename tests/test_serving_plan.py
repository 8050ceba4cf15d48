"""Inference-native strategy search (search/serving_plan.py): the
decode-aware cost model, per-(model, batch-class) plan search, KV-cache
envelope verification, serialization round-trip, repository adoption
(ServingPlanSession + measured floor guard), hot swap, and compile-cache
warm-start wiring. Beyond-reference: the reference searches training
strategies only and serves whatever falls out."""
import copy
import json
import os
import re
import tempfile

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.models.nlp import GPTConfig, build_gpt2
from flexflow_tpu.analysis.plan_verifier import (PlanVerificationError,
                                                 serving_envelope,
                                                 verify_serving_plan)
from flexflow_tpu.search.serving_plan import (ServingCostEvaluator,
                                              _serving_cost_model,
                                              bucket_strategy_doc,
                                              kv_cache_bytes,
                                              kv_cache_spec,
                                              optimize_serving_strategy,
                                              save_serving_plan)

BATCH, SEQ = 4, 16


def _compiled(mutate=None):
    cfg = FFConfig()
    cfg.batch_size = BATCH
    cfg.only_data_parallel = True
    if mutate is not None:
        mutate(cfg)
    g = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                  num_heads=4, max_position=SEQ, dropout=0.0)
    ff = FFModel(cfg)
    out = build_gpt2(ff, BATCH, SEQ, g)
    ff.compile(SGDOptimizer(0.0), "identity", [], output_tensor=out)
    return ff


@pytest.fixture(scope="module")
def ff():
    return _compiled()


@pytest.fixture(scope="module")
def cost_model(ff):
    cm = _serving_cost_model(ff, ff.dmesh)
    # pin it so every later search in this module reuses the one
    # calibrated model instead of re-measuring collectives
    ff._search_cost_model = cm
    return cm


@pytest.fixture(scope="module")
def plan(ff, cost_model):
    return optimize_serving_strategy(ff, buckets=(1, 4), budget=30)


# ---------------------------------------------------------------------------
# cost model / evaluator
# ---------------------------------------------------------------------------

def test_kv_cache_spec_reads_attention_geometry(ff):
    mha = [l for l in ff.layers if kv_cache_spec(l) is not None]
    assert mha, "gpt2 graph must carry causal attention layers"
    for l in mha:
        spec = kv_cache_spec(l)
        assert spec["num_kv_heads"] == 4
        assert spec["head_dim"] == 8
        # K + V, fp32: 2 * b * s * kvh * hd * 4, divided by the shard
        assert kv_cache_bytes(l, 4, SEQ, 1) == 2 * 4 * SEQ * 4 * 8 * 4
        assert kv_cache_bytes(l, 4, SEQ, 2) \
            == kv_cache_bytes(l, 4, SEQ, 1) // 2


def test_evaluator_rejects_bucket_indivisible_batch_degree(ff, cost_model):
    ev = ServingCostEvaluator(ff.layers, ff.dmesh, cost_model, 1, SEQ)
    # bucket 1: any batch-dim (sample) degree > 1 cannot divide it
    saw_sample = False
    for l in ff.layers:
        opts = ev.options[l.name]
        for i, opt in enumerate(opts):
            if opt.kind == "sample" and opt.out_dim == 0:
                degs = [1] * len(opts)
                degs[i] = 2
                assert not ev.bucket_feasible(l, degs)
                saw_sample = True
    assert saw_sample


def test_serving_cost_prices_decode_and_prefill(plan):
    for b, p in plan.buckets.items():
        assert np.isfinite(p.cost.prefill) and p.cost.prefill > 0
        assert np.isfinite(p.cost.decode_step) and p.cost.decode_step > 0
        assert p.cost.kv_bytes > 0
        # the serving objective: prefill once + decode per token
        assert p.cost.total >= p.cost.prefill


def test_search_never_loses_to_predicted_baseline(plan):
    """The walk starts FROM the reused-training-plan baseline, so the
    adopted plan can only match or beat it under the model."""
    for b, base in plan.baseline.items():
        assert plan.buckets[b].cost.total <= base.total * (1 + 1e-9)


# ---------------------------------------------------------------------------
# verification: KV soundness + memory envelope
# ---------------------------------------------------------------------------

def test_verify_serving_plan_passes_searched_plan(ff, plan):
    report = verify_serving_plan(plan, ff.layers, ff.dmesh)
    assert report.ok(), [f.format() for f in report.errors]


def test_kv_shard_degree_must_divide_kv_heads(ff, plan):
    block = copy.deepcopy(plan.to_block())
    big = str(max(plan.buckets))
    kv = block["buckets"][big]["kv"]
    name = next(iter(kv))
    kv[name]["shard_degree"] = 3   # num_kv_heads=4: 3 does not divide
    with pytest.raises(PlanVerificationError) as e:
        verify_serving_plan(block, ff.layers, ff.dmesh)
    assert any(f.seam == "serving-kv" for f in e.value.findings)


def test_kv_bytes_must_match_geometry(ff, plan):
    block = copy.deepcopy(plan.to_block())
    big = str(max(plan.buckets))
    next(iter(block["buckets"][big]["kv"].values()))["bytes"] += 1
    with pytest.raises(PlanVerificationError) as e:
        verify_serving_plan(block, ff.layers, ff.dmesh)
    assert any(f.seam == "serving-kv" for f in e.value.findings)


def test_envelope_gate_binds_between_sharded_and_replicated(ff, plan):
    """The acceptance shape: at an HBM budget pinned between the
    sharded-KV and replicated-KV envelopes of the largest bucket, the
    sharded variant verifies and the replicated one fails TYPED."""
    block = copy.deepcopy(plan.to_block())
    big = max(plan.buckets)
    sub = block["buckets"][str(big)]

    def variant(deg):
        v = copy.deepcopy(sub)
        for kv in v["kv"].values():
            kv["shard_degree"] = deg
            kv["bytes"] = (2 * big * block["max_seq"]
                           * kv["num_kv_heads"] * kv["head_dim"]
                           * 4) // deg
        return v

    shard, repl = variant(2), variant(1)
    by_name = {l.name: l for l in ff.layers}
    axes = dict(ff.dmesh.axis_sizes)
    e_s = serving_envelope(shard, big, by_name, axes)
    e_r = serving_envelope(repl, big, by_name, axes)
    assert e_s["envelope_bytes"] < e_r["envelope_bytes"]
    hbm = (e_s["envelope_bytes"] + e_r["envelope_bytes"]) / 2.0

    def doc(v):
        return {"version": 1, "max_seq": block["max_seq"],
                "decode_tokens": block["decode_tokens"],
                "buckets": {str(big): v}}

    ok = verify_serving_plan(doc(shard), ff.layers, ff.dmesh,
                             hbm_bytes=hbm)
    assert ok.ok(), [f.format() for f in ok.errors]
    with pytest.raises(PlanVerificationError) as e:
        verify_serving_plan(doc(repl), ff.layers, ff.dmesh,
                            hbm_bytes=hbm)
    assert any(f.seam == "serving-memory" for f in e.value.findings)
    assert "shard the KV cache" in " ".join(
        f.message for f in e.value.findings)


def test_kv_seq_shard_scored_on_seq_mesh(ff):
    """On a sequence-axis mesh, long-context buckets adopt seq-sharded
    KV: per-device cache bytes drop by the seq degree and the decode
    step picks up the per-token partial-output combine. A flat mesh
    never scores the option. Priced on the machine model's own link
    constants (``cpu-sim``: 5 GB/s, 1 us a hop), not on the module's
    model, whose constants are a timed all-reduce of this host: the
    assertion is about the rule."""
    from flexflow_tpu.parallel.machine import DeviceMesh
    from flexflow_tpu.search.costmodel import OpCostModel
    from flexflow_tpu.search.serving_plan import \
        serving_baseline_assignment
    cost_model = OpCostModel(ff.dmesh.spec)
    assert cost_model.coll_bw is None and cost_model.coll_lat is None
    dm = DeviceMesh(ff.dmesh.spec, seq=4)
    assert dm.seq_degree == 4
    long_seq = 4096
    ev = ServingCostEvaluator(ff.layers, dm, cost_model, 4, long_seq)
    assign = serving_baseline_assignment(ff.layers, dm, ev)
    kv = ev.kv_plan(assign)
    assert kv, "gpt2 graph must carry cache-carrying attention"
    for l in ff.layers:
        if kv_cache_spec(l) is None:
            continue
        e = kv[l.name]
        assert e["seq_shard_degree"] == 4
        assert e["bytes"] == kv_cache_bytes(
            l, 4, long_seq, e["shard_degree"]) // 4
    cost = ev.evaluate(assign)
    assert cost.decode_comm > 0  # the combine is priced, not free
    # flat mesh: no seq axis, option never adopted
    ev0 = ServingCostEvaluator(ff.layers, ff.dmesh, cost_model, 4,
                               long_seq)
    kv0 = ev0.kv_plan(serving_baseline_assignment(ff.layers, ff.dmesh,
                                                  ev0))
    assert all(e["seq_shard_degree"] == 1 for e in kv0.values())


def test_kv_seq_shard_verifies_on_seq_mesh_only(ff, plan):
    """Verifier consistency for the seq-sharded KV option: the bytes
    check honors seq_shard_degree, a seq-sharded entry verifies on a
    mesh whose seq axis carries the degree, and is REJECTED typed on a
    mesh without one (or with stale un-divided bytes)."""
    from flexflow_tpu.parallel.machine import DeviceMesh
    big = str(max(plan.buckets))

    def block(sdeg, fix_bytes=True):
        b = copy.deepcopy(plan.to_block())
        # the flat-mesh op specs name axes the seq mesh lacks — this
        # test exercises the KV check, so verify a replicated layout
        # of the largest bucket only
        b["buckets"] = {big: b["buckets"][big]}
        b["buckets"][big]["ops"] = {}
        b["buckets"][big]["inputs"] = {}
        for kv in b["buckets"][big]["kv"].values():
            kv["seq_shard_degree"] = sdeg
            kv["shard_degree"] = 1
            if fix_bytes:
                kv["bytes"] = (2 * int(big) * b["max_seq"]
                               * kv["num_kv_heads"] * kv["head_dim"]
                               * 4) // sdeg
        return b

    dm_seq = DeviceMesh(ff.dmesh.spec, seq=4)
    ok = verify_serving_plan(block(4), ff.layers, dm_seq)
    assert ok.ok(), [f.format() for f in ok.errors]
    # same block on the flat mesh: no seq axis to rotate over
    with pytest.raises(PlanVerificationError) as e:
        verify_serving_plan(block(4), ff.layers, ff.dmesh)
    assert any(f.seam == "serving-kv"
               and "sequence axis" in f.message for f in e.value.findings)
    # bytes not divided by the seq degree: geometry disagreement
    with pytest.raises(PlanVerificationError) as e:
        verify_serving_plan(block(4, fix_bytes=False), ff.layers, dm_seq)
    assert any(f.seam == "serving-kv" for f in e.value.findings)


def test_optimize_strategy_serving_mode(ff, cost_model):
    from flexflow_tpu.search.optimizer import optimize_strategy
    old_buckets = ff.config.serving_buckets
    old_budget = ff.config.search_budget
    ff.config.serving_buckets = "2"
    ff.config.search_budget = 8
    try:
        strategy, info = optimize_strategy(ff, mode="serving")
    finally:
        ff.config.serving_buckets = old_buckets
        ff.config.search_budget = old_budget
    assert strategy.serving is not None
    assert ff._serving_plan is not None
    assert list(ff._serving_plan.buckets) == [2]
    with pytest.raises(ValueError, match="unknown strategy-search mode"):
        optimize_strategy(ff, mode="nonsense")


# ---------------------------------------------------------------------------
# serialization round-trip
# ---------------------------------------------------------------------------

def test_serving_block_roundtrips_through_save_and_load(ff, plan):
    from flexflow_tpu.search.serialization import load_strategy
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plan.json")
        save_serving_plan(path, plan)
        with open(path) as f:
            doc = json.load(f)
        assert doc["meta"]["mode"] == "serving"
        assert sorted(int(k) for k in doc["serving"]["buckets"]) \
            == sorted(plan.buckets)
        st = load_strategy(path, ff.layers, ff.dmesh)
        assert st.serving is not None
        assert st.serving["max_seq"] == plan.max_seq
        # the reloaded serving block verifies like the in-memory one
        report = verify_serving_plan(st.serving, ff.layers, ff.dmesh)
        assert report.ok(), [f.format() for f in report.errors]


def test_bucket_strategy_doc_extracts_standalone_bucket(ff, plan):
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plan.json")
        save_serving_plan(path, plan)
        with open(path) as f:
            doc = json.load(f)
        sub = bucket_strategy_doc(doc, 1)
        assert sub["meta"]["serving_bucket"] == 1
        assert list(sub["serving"]["buckets"]) == ["1"]
        with pytest.raises(KeyError):
            bucket_strategy_doc(doc, 999)
        with pytest.raises(ValueError):
            bucket_strategy_doc({"ops": {}}, 1)


# ---------------------------------------------------------------------------
# repository adoption + floor guard + hot swap
# ---------------------------------------------------------------------------

def _session_builder():
    """build(sf, buckets=...) closure in the shape the serving-plan
    builder drives (mirrors ModelRepository._load_with_builder)."""
    from flexflow_tpu.serving.session import InferenceSession

    def build(sf, buckets=(1, 4)):
        ff = _compiled(lambda c: (
            setattr(c, "only_data_parallel", not sf),
            setattr(c, "import_strategy_file", sf or "")))
        return InferenceSession(ff, list(buckets))
    return build


def test_serving_plan_session_routes_by_bucket(ff, plan):
    from flexflow_tpu.serving.session import build_serving_plan_session
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plan.json")
        save_serving_plan(path, plan)
        session = build_serving_plan_session(path, _session_builder(),
                                             floor_guard="off")
    assert session.buckets == sorted(plan.buckets)
    assert session.session_for(1).buckets == [1]
    assert session.session_for(3).buckets == [4]
    assert session.session_for(99).buckets == [4]
    # decode through the router matches the baseline model bit-exactly
    rng = np.random.default_rng(0)
    ids = np.zeros((2, SEQ), np.int32)
    ids[:, :3] = rng.integers(1, 60, (2, 3))
    got = np.asarray(session.generate(ids, 3, 5, temperature=0.0))
    want = np.asarray(ff.generate(ids, 3, 5, temperature=0.0))
    np.testing.assert_array_equal(got, want)
    clone = session.clone()
    assert clone.buckets == session.buckets


def test_floor_guard_measures_and_records(ff, cost_model):
    """floor_guard='on' compiles the no-plan baseline, measures both
    sides per bucket, and records an adoption decision. (On the CPU sim
    the decision itself is noise — the contract under test is
    measurement + substitution, not which side wins.)"""
    from flexflow_tpu.serving import session as sess_mod
    small = optimize_serving_strategy(ff, buckets=(2,), budget=8)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plan.json")
        save_serving_plan(path, small)
        session = sess_mod.build_serving_plan_session(
            path, _session_builder(), floor_guard="on")
    assert sorted(session.floor_guard) == [2], session.floor_guard
    rec = session.floor_guard[2]
    assert rec["adopted"] in ("searched", "baseline")
    assert rec["searched_s"] > 0 and rec["baseline_s"] > 0
    # whichever side won, bucket 2 still routes to a bucket-2 session
    assert session.session_for(2).buckets == [2]


def test_floor_guard_auto_skips_on_cpu(ff, plan):
    import jax

    from flexflow_tpu.serving.session import build_serving_plan_session
    if jax.devices()[0].platform != "cpu":
        pytest.skip("accelerator backend: auto mode runs the guard")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "plan.json")
        save_serving_plan(path, plan)
        session = build_serving_plan_session(path, _session_builder(),
                                             floor_guard="auto")
    assert session.floor_guard == {}


def test_repository_adopts_serving_plan_per_bucket(tmp_path, plan):
    import flexflow_tpu.serving.session as sess_mod
    repo = sess_mod.ModelRepository()
    plan_path = str(tmp_path / "plan.json")
    save_serving_plan(plan_path, plan)

    built = []

    def fake_builder(sf, buckets=(1, 4)):
        built.append(sf)
        return _session_builder()(sf, buckets)

    session = sess_mod.build_serving_plan_session(
        plan_path, fake_builder, floor_guard="off")
    repo.register("gpt2", session)
    assert repo.get("gpt2") is session
    assert len(built) == len(plan.buckets)
    assert all(sf for sf in built)   # every bucket imported a strategy

    # a strategy export WITHOUT a serving block is a typed load error
    bare = str(tmp_path / "bare.json")
    with open(bare, "w") as f:
        json.dump({"version": 1, "ops": {}}, f)
    with pytest.raises(ValueError, match="no serving block"):
        sess_mod.build_serving_plan_session(bare, fake_builder)


def test_load_with_builder_rejects_both_strategy_kinds():
    from flexflow_tpu.serving.session import ModelRepository
    repo = ModelRepository()
    with pytest.raises(ValueError, match="not both"):
        repo._load_with_builder(
            "m", lambda ff: None, batch_buckets=(1,), config=None,
            strategy_file="a.json", instances=1,
            serving_strategy_file="b.json")


def test_hot_swap_replaces_instances():
    from flexflow_tpu.serving.session import ModelRepository

    class Fake:
        def __init__(self, tag):
            self.tag = tag

        def clone(self):
            return Fake(self.tag)

    repo = ModelRepository()
    repo.register("m", Fake("old"))
    swapped = repo.hot_swap("m", Fake("new"))
    assert swapped.tag == "new"
    with pytest.raises(KeyError):
        repo.hot_swap("missing", Fake("x"))


def test_scheduler_hot_swap_drains_then_restarts():
    import time

    from flexflow_tpu.serving.scheduler import BatchScheduler

    class Sess:
        input_names = ["x"]

        def __init__(self, tag):
            self.tag = tag
            self.served = 0

        def infer(self, inputs):
            self.served += 1
            time.sleep(0.005)
            return np.zeros((inputs["x"].shape[0], 1), np.float32)

    old, new = Sess("old"), Sess("new")
    sched = BatchScheduler(old, max_batch=2, max_delay_ms=1.0,
                           name="swap_test")
    try:
        x = np.zeros((1, 1), np.float32)
        sched.infer({"x": x}, timeout=5.0)
        assert old.served > 0
        assert sched.hot_swap([new])
        sched.infer({"x": x}, timeout=5.0)
        assert new.served > 0
        assert sched.session is new
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# compile-cache warm start
# ---------------------------------------------------------------------------

def test_repository_load_wires_compilation_cache(monkeypatch):
    """Every repository load path compiles through the persistent
    cache; on the CPU test platform the helper sets nothing, so the
    wiring is witnessed through a recording stub."""
    import flexflow_tpu.utils.compilation_cache as cc
    calls = []
    monkeypatch.setattr(cc, "enable_compilation_cache",
                        lambda: calls.append("enable"))

    from flexflow_tpu.serving.session import ModelRepository
    repo = ModelRepository()

    def graph_build(ff):
        t = ff.create_tensor((4, 8), name="in0")
        return ff.dense(t, 4)

    session = repo._load_with_builder(
        "dense", graph_build, batch_buckets=(4,), config=FFConfig(),
        strategy_file=None, instances=1)
    assert repo.get("dense") is session
    # called from the repository load AND again inside compile()
    assert len(calls) >= 2


def _recorded_cache_config(monkeypatch, backend, env_dir):
    """Run enable_compilation_cache with a scripted platform and
    environment; returns (its answer, the jax.config updates it made)."""
    import jax

    import flexflow_tpu.utils.compilation_cache as cc
    updates = {}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    return cc.enable_compilation_cache(), updates


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_cache_dir_from_environment_is_never_overridden(
        monkeypatch, tmp_path, backend):
    """JAX_COMPILATION_CACHE_DIR set: the directory is JAX's own
    business — no code path names another one."""
    where = str(tmp_path / "placed_from_outside")
    got, updates = _recorded_cache_config(monkeypatch, backend, where)
    assert got == where
    assert "jax_compilation_cache_dir" not in updates


def test_cache_dir_defaults_to_the_checkout_on_an_accelerator(monkeypatch):
    import flexflow_tpu
    import flexflow_tpu.utils.compilation_cache as cc
    got, updates = _recorded_cache_config(monkeypatch, "tpu", None)
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(flexflow_tpu.__file__)))
    assert got == cc.CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == got
    # (kernel bytes, hence cache keys, must not depend on the call
    # chain: one frame a location — and NOT by switching full tracebacks
    # off, which strips every scope name from the compiled step)
    assert updates["jax_traceback_in_locations_limit"] == 1
    # (and an executable is never loaded under another program's names)
    assert updates["jax_compilation_cache_include_metadata_in_key"] is True
    # (but not under the place of the checkout on disk)
    assert re.sub(updates["jax_hlo_source_file_canonicalization_regex"], "",
                  cc.__file__) == "flexflow_tpu/utils/compilation_cache.py"
    assert "jax_include_full_tracebacks_in_locations" not in updates


def test_no_cache_on_the_cpu_platform_unless_placed(monkeypatch):
    """CPU compiles are tests at toy sizes and an XLA:CPU executable is
    tied to the build host's instruction set: nothing is cached unless
    the environment says where."""
    got, updates = _recorded_cache_config(monkeypatch, "cpu", None)
    assert got is None and updates == {}


def test_model_compile_counter_labels_decode_compiles():
    from flexflow_tpu.obs.metrics_registry import REGISTRY
    c = REGISTRY.counter("ff_model_compiles_total",
                         "Model program compiles (trace + XLA build "
                         "events)")
    before = c.value(model="compile_counter_probe")
    ff = _compiled()
    ff._model_name = "compile_counter_probe"
    # the decode-cache miss below is this model's first named compile
    ids = np.zeros((BATCH, SEQ), np.int32)
    ids[:, 0] = 1
    ff.generate(ids, 1, 2, temperature=0.0)
    assert c.value(model="compile_counter_probe") > before
