"""ffcheck: static plan verifier + framework-invariant linter (ISSUE 8).

Covers: every lint rule fires on a minimal bad snippet and is silenced
by the ``# ffcheck: ok(<rule>)`` pragma; the full repo lints clean; the
verifier accepts the checked-in strategies, the presets, and a searched
plan; both known-bad plan fixtures (the two PR 6 miscompile
transitions) are rejected with attributed errors; the memory envelope
binds; and compile-time verification overhead stays <= 5%.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.analysis.lint import (lint_file, lint_paths,
                                        render_json, render_text)
from flexflow_tpu.analysis.plan_verifier import (PlanVerificationError,
                                                 StructMesh, verify_plan,
                                                 verify_strategy_file)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "flexflow_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


# ===========================================================================
# linter: each rule fires on a minimal bad snippet; the pragma silences it
# ===========================================================================

def _rules(findings):
    return [f.rule for f in findings]


def test_bare_assert_fires_and_pragma_suppresses():
    src = "def f(x):\n    assert x > 0, 'nope'\n    return x\n"
    out = lint_file("flexflow_tpu/foo.py", source=src)
    assert _rules(out) == ["bare-assert"]
    assert out[0].line == 2
    ok = src.replace("assert x > 0, 'nope'",
                     "assert x > 0  # ffcheck: ok(bare-assert)")
    assert lint_file("flexflow_tpu/foo.py", source=ok) == []


def test_bare_assert_skips_test_files():
    src = "def f(x):\n    assert x > 0\n"
    assert lint_file("tests/test_foo.py", source=src) == []
    assert lint_file("flexflow_tpu/tests/foo.py", source=src) == []


def test_host_sync_fires_in_hot_module_only():
    src = ("def step(bm):\n"
           "    return float(bm['loss'])\n")
    out = lint_file("flexflow_tpu/executor.py", source=src)
    assert _rules(out) == ["host-sync"]
    # same code outside the hot-path module set: clean
    assert lint_file("flexflow_tpu/search/costmodel.py", source=src) == []
    # conversions inside a flush point are the designated fetch
    flush = ("def flush(bm):\n"
             "    return float(bm['loss'])\n")
    assert lint_file("flexflow_tpu/executor.py", source=flush) == []


def test_host_sync_np_asarray_and_item():
    src = ("import numpy as np\n"
           "def step(v):\n"
           "    a = np.asarray(v)\n"
           "    return a, v.item()\n")
    out = lint_file("flexflow_tpu/runtime/metrics.py", source=src)
    assert sorted(_rules(out)) == ["host-sync", "host-sync"]
    ok = src.replace("np.asarray(v)",
                     "np.asarray(v)  # ffcheck: ok(host-sync)") \
            .replace("v.item()", "v.item()  # ffcheck: ok")
    assert lint_file("flexflow_tpu/runtime/metrics.py", source=ok) == []


def test_host_sync_call_args_and_update_scoping():
    """float(<call>()) is only exempt for host-only producers, and
    "update" is a flush point ONLY in runtime/metrics.py (PerfMetrics'
    host-side fold) — never in the jitted optimizer update."""
    src = ("def step(m, cfg):\n"
           "    a = float(m.mean())\n"              # device call: flag
           "    b = bool(getattr(cfg, 'x', 0))\n"   # config read: ok
           "    return a, b\n")
    out = lint_file("flexflow_tpu/executor.py", source=src)
    assert [(f.rule, f.line) for f in out] == [("host-sync", 2)]
    upd = ("def update(self, g):\n"
           "    return float(g)\n")
    assert _rules(lint_file("flexflow_tpu/runtime/optimizers.py",
                            source=upd)) == ["host-sync"]
    assert lint_file("flexflow_tpu/runtime/metrics.py", source=upd) == []


def test_raw_wait_fires_and_timeout_passes():
    src = ("def drain(t, q, ev):\n"
           "    t.join()\n"
           "    ev.wait()\n"
           "    q.get()\n")
    out = lint_file("flexflow_tpu/serving/x.py", source=src)
    assert _rules(out) == ["raw-wait"] * 3
    ok = ("def drain(t, q, ev):\n"
          "    t.join(timeout=5)\n"
          "    ev.wait(5.0)\n"
          "    q.get(timeout=1)\n")
    assert lint_file("flexflow_tpu/serving/x.py", source=ok) == []
    # out of scope: same code in search/ is not thread-pool plumbing
    assert lint_file("flexflow_tpu/search/x.py", source=src) == []


def test_raw_wait_blocking_get_still_flagged():
    """get(True) / get(block=True) block forever without a timeout —
    only a timeout or a literal block=False bounds the call."""
    src = ("def drain(q):\n"
           "    a = q.get(True)\n"
           "    b = q.get(block=True)\n"
           "    c = q.get(False)\n"
           "    d = q.get(block=False)\n"
           "    e = q.get(True, 5.0)\n")
    out = lint_file("flexflow_tpu/serving/x.py", source=src)
    assert [(f.rule, f.line) for f in out] == [("raw-wait", 2),
                                               ("raw-wait", 3)]


def test_parse_error_reported_as_its_own_rule():
    src = "def f(:\n"
    out = lint_file("flexflow_tpu/foo.py", source=src)
    assert _rules(out) == ["parse-error"]
    # a rules subset does not hide it: an unparseable file cannot be
    # checked for ANY rule
    out = lint_file("flexflow_tpu/foo.py", source=src,
                    rules=["host-sync"])
    assert _rules(out) == ["parse-error"]


def test_scope_matching_is_component_anchored():
    """Package-root-relative paths stay in scope, and lookalike file
    names (batch_executor.py) stay OUT of the hot-path module set."""
    wait_src = "def drain(t):\n    t.join()\n"
    assert _rules(lint_file("serving/x.py", source=wait_src)) \
        == ["raw-wait"]
    sync_src = "def step(v):\n    return float(v)\n"
    assert _rules(lint_file("executor.py", source=sync_src)) \
        == ["host-sync"]
    assert lint_file("flexflow_tpu/serving/batch_executor.py",
                     source=sync_src) == []


def test_raw_rank_wait_fires_outside_coord():
    src = ("def sync(client):\n"
           "    client.wait_at_barrier('b', 1000)\n")
    out = lint_file("flexflow_tpu/parallel/distributed.py", source=src)
    assert _rules(out) == ["raw-rank-wait"]
    assert lint_file("flexflow_tpu/resilience/coord.py", source=src) == []


def test_time_in_jit_fires():
    src = ("import time, jax\n"
           "def step(x):\n"
           "    t = time.time()\n"
           "    return x + t\n"
           "f = jax.jit(step)\n")
    out = lint_file("flexflow_tpu/anywhere.py", source=src)
    assert _rules(out) == ["time-in-jit"]
    # the same clock read in an un-jitted fn is fine
    src_ok = src.replace("f = jax.jit(step)\n", "")
    assert lint_file("flexflow_tpu/anywhere.py", source=src_ok) == []


def test_pragma_on_preceding_line():
    src = ("def f(x):\n"
           "    # ffcheck: ok(bare-assert)\n"
           "    assert x\n")
    assert lint_file("flexflow_tpu/foo.py", source=src) == []


def test_reporters():
    src = "def f(x):\n    assert x\n"
    out = lint_file("flexflow_tpu/foo.py", source=src)
    txt = render_text(out)
    assert "flexflow_tpu/foo.py:2" in txt and "bare-assert" in txt
    doc = json.loads(render_json(out))
    assert doc["count"] == 1
    assert doc["findings"][0]["rule"] == "bare-assert"
    # schema 2 (ISSUE 14): version field + stable per-finding ID
    assert doc["schema"] == 2
    assert len(doc["findings"][0]["id"]) == 12
    assert render_text([]) == "ffcheck: clean"


def test_full_repo_lints_clean():
    """THE gate: the package carries no invariant violations (the
    bare-assert sweep, bounded waits, host-sync-free hot paths)."""
    findings = lint_paths([PKG])
    assert findings == [], render_text(findings)


def test_ffcheck_cli_exit_codes(tmp_path):
    bad = tmp_path / "flexflow_tpu" / "bad.py"
    bad.parent.mkdir()
    bad.write_text("def f(x):\n    assert x\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffcheck.py"),
         "--lint", str(bad)], capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "bare-assert" in r.stdout
    good = tmp_path / "flexflow_tpu" / "good.py"
    good.write_text("def f(x):\n    return x\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffcheck.py"),
         "--lint", str(good), "--json"],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout)["ok"] is True


# ===========================================================================
# verifier: accepts sound plans
# ===========================================================================

def _mlp(cfg=None, hidden=(64,), batch=32):
    from flexflow_tpu.models import build_mlp
    cfg = cfg or FFConfig()
    cfg.batch_size = batch
    ff = FFModel(cfg)
    out = build_mlp(ff, batch, in_dim=64, hidden=hidden, num_classes=10)
    return ff, out


def test_compile_verifies_dp_plan():
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff, out = _mlp(cfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    rep = ff._plan_verify_report
    assert rep.ok() and rep.findings == []
    assert rep.memory["envelope_bytes"] < rep.memory["hbm_bytes"]


def test_compile_verifies_searched_plan():
    cfg = FFConfig()
    cfg.search_budget = 8
    ff, out = _mlp(cfg, hidden=(64, 64))
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    assert ff._plan_verify_report.ok()


def test_compile_verifies_tp_preset():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from flexflow_tpu.models import BertConfig, build_bert
    cfg = FFConfig()
    cfg.batch_size = 32
    cfg.tensor_parallel = 2
    ff = FFModel(cfg)
    out = build_bert(ff, 32, 16, BertConfig.tiny())
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    assert ff._plan_verify_report.ok()


def test_checked_in_strategies_verify():
    """Every strategy artifact in strategies/ passes both structural
    and (via the CLI's builder registry) full shape-level
    verification."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import ffcheck
        reports, failures = ffcheck.verify_strategies(
            os.path.join(REPO, "strategies"))
    finally:
        sys.path.pop(0)
    assert not failures, {
        p: [f.format() for f in r.errors] for p, r in reports.items()}
    assert len(reports) >= 2


def test_verifier_flags_indivisible_pipeline_plan():
    """The verifier catches — at compile, with attribution — a plan
    whose pipeline exit spec shard_map would reject at first trace
    (microbatch 2 over a dp axis of 4)."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device mesh")
    from flexflow_tpu.models import GPTConfig, build_gpt2
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.pipeline_stages = 2
    cfg.pipeline_microbatches = 4
    ff = FFModel(cfg)
    out = build_gpt2(ff, 8, 16, GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=4, num_heads=4,
        max_position=16))
    with pytest.raises(PlanVerificationError) as ei:
        ff.compile(SGDOptimizer(0.05),
                   "sparse_categorical_crossentropy", [],
                   output_tensor=out)
    assert "pipeline-exit" in str(ei.value)


# ===========================================================================
# known-bad fixtures: the two PR 6 miscompile transitions must be flagged
# ===========================================================================

def test_badplan_concat_chain_rejected():
    """Fixture A: the PR 6 4x-values GSPMD miscompile — a sharded
    constraint on a layout-op output with no legal planner lowering.
    The verifier must attribute the seam to the transpose op."""
    from flexflow_tpu.search.serialization import load_strategy
    path = os.path.join(FIXTURES, "badplan_concat_chain.json")
    doc = json.load(open(path))
    ff = FFModel(FFConfig())
    ta = ff.create_tensor((2, 3, 4), name="a")
    tb = ff.create_tensor((2, 3, 4), name="b")
    c = ff.concat([ta, tb], axis=1)
    r = ff.reshape(c, (2, 24))
    ff.transpose(r, (1, 0))
    dmesh = StructMesh(doc["mesh_axes"])
    st = load_strategy(path, ff.layers, dmesh)
    report = verify_plan(st, ff.layers, machine_spec=dmesh.spec,
                         graph_inputs=[ta, tb])
    assert not report.ok()
    errs = [f for f in report.errors if f.op == "op_transpose_2"]
    assert errs, [f.format() for f in report.errors]
    assert any(f.check == "seam" and f.seam == "layout-op-output"
               and "GSPMD" in f.message for f in errs), \
        [f.format() for f in errs]
    with pytest.raises(PlanVerificationError) as ei:
        report.raise_if_failed()
    assert "op_transpose_2" in str(ei.value)


def test_badplan_banks_pipeline_rejected():
    """Fixture B: the PR 6 banks x pipeline NaN miscompile — the bank
    placed on the pipeline's stage axis, composing the rejoin and
    region-entry transitions on one axis. The verifier must attribute
    the collision to the bank."""
    from flexflow_tpu.ffconst import AggrMode
    from flexflow_tpu.parallel.pipeline_lowering import \
        find_pipeline_region
    from flexflow_tpu.search.serialization import load_strategy
    path = os.path.join(FIXTURES, "badplan_banks_pipeline.json")
    doc = json.load(open(path))
    ff = FFModel(FFConfig())
    for i, v in enumerate((50, 60, 70, 80)):
        s = ff.create_tensor((32, 1), name=f"sparse_{i}", dtype="int32")
        ff.embedding(s, v, 16, aggr=AggrMode.AGGR_MODE_SUM,
                     name=f"emb_{i}")
    x = ff.concat([l.outputs[0] for l in ff.layers[:4]], axis=1)
    h = x
    for _ in range(4):
        h = ff.dense(h, 64, activation="relu")
    ff.dense(h, 2)
    dmesh = StructMesh(doc["mesh_axes"])
    st = load_strategy(path, ff.layers, dmesh)
    meta = doc["meta"]["pipeline"]
    region = find_pipeline_region(ff.layers, meta["n_stages"],
                                  meta["n_microbatches"])
    assert region is not None
    region.pp_axis = meta["pp_axis"]
    region.dp_axes = tuple(meta["dp_axes"])
    st.pipeline = region
    report = verify_plan(st, ff.layers, machine_spec=dmesh.spec,
                         graph_inputs=ff.input_tensors)
    assert not report.ok()
    hits = [f for f in report.errors
            if f.check == "collective-order" and "bank" in f.op
            and "x1" in f.message]
    assert hits, [f.format() for f in report.errors]


# ===========================================================================
# memory envelope + audit + overhead
# ===========================================================================

def test_memory_envelope_binds():
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff, out = _mlp(cfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    report = verify_plan(ff.strategy, ff.executor.program.layers,
                         machine_spec=ff.dmesh.spec,
                         graph_inputs=ff.graph_inputs,
                         optimizer=ff.optimizer,
                         hbm_bytes=1024.0)
    assert not report.ok()
    assert any(f.check == "memory" and "envelope" in (f.seam or "")
               for f in report.errors)
    assert report.memory["envelope_bytes"] > 1024.0


def test_device_mem_mb_drives_envelope():
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.device_mem_mb = 1  # 1 MiB: big enough for the tiny MLP
    ff, out = _mlp(cfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    assert ff._plan_verify_report.memory["hbm_bytes"] == 1 << 20


def test_verifier_counters_and_report_json():
    from flexflow_tpu.obs.metrics_registry import REGISTRY
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff, out = _mlp(cfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    text = REGISTRY.render()
    assert "ff_plan_verify_runs_total" in text
    doc = ff._plan_verify_report.to_json()
    assert doc["ok"] is True and "memory" in doc


def test_verify_overhead_under_5_percent_of_compile():
    """ISSUE 8 satellite: the in-compile verification pass costs <= 5%
    of compile/search wall time."""
    cfg = FFConfig()
    cfg.search_budget = 8
    ff, out = _mlp(cfg, hidden=(64, 64))
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    phases = ff._compile_phases
    assert "verify_s" in phases and "compile_s" in phases
    assert phases["verify_s"] <= 0.05 * phases["compile_s"], phases


def test_ff_plan_verify_env_disables(monkeypatch):
    monkeypatch.setenv("FF_PLAN_VERIFY", "0")
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff, out = _mlp(cfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    assert not hasattr(ff, "_plan_verify_report")


def test_verify_strategy_file_structural_errors(tmp_path):
    bad = {"mesh_axes": {"x0": 4},
           "ops": {"dense_0": {"outputs": [[["nope"]]],
                               "weights": {"kernel": [["x0"], ["x0"]]}}}}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    report = verify_strategy_file(str(p))
    assert not report.ok()
    msgs = " ".join(f.message for f in report.errors)
    assert "unknown mesh axis" in msgs and "reuses mesh axis" in msgs


# ===========================================================================
# per-parameter ZeRO: known-bad fixture + envelope + CLI (ISSUE 10)
# ===========================================================================

def test_badplan_zero_overlap_rejected():
    """Fixture C: a per-parameter ZeRO assignment that shards a moment
    over the mesh axis its weight is already column-parallel on — on a
    DIFFERENT dim. The zero check must reject with the axis overlap
    attributed to the op."""
    path = os.path.join(FIXTURES, "badplan_zero_overlap.json")
    report = verify_strategy_file(path)
    assert not report.ok()
    hits = [f for f in report.errors if f.check == "zero"]
    assert hits, [f.format() for f in report.errors]
    assert any(f.op == "op_linear_1" and f.seam == "zero-assignment"
               and "x1" in f.message for f in hits), \
        [f.format() for f in hits]


def test_badplan_zero_overlap_rejected_via_ffcheck_cli(tmp_path):
    """The same fixture through `ffcheck --verify-strategies` (the ci.sh
    gate's entry point): exit 1 with the zero finding printed."""
    import shutil
    d = tmp_path / "strategies"
    d.mkdir()
    shutil.copy(os.path.join(FIXTURES, "badplan_zero_overlap.json"),
                str(d / "badplan_zero_overlap.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffcheck.py"),
         "--verify-strategies", str(d)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "zero" in r.stdout and "op_linear_1" in r.stdout, r.stdout


def test_zero_assignment_moment_may_follow_weight_axes():
    """The NON-bug the overlap check must not flag: the moment spec
    carries the weight's own axis on the weight's own dim (m/v are
    zeros_like the param) plus a free axis elsewhere."""
    from flexflow_tpu.analysis.plan_verifier import _check_zero
    from flexflow_tpu.analysis.plan_verifier import PlanReport
    from flexflow_tpu.runtime.zero import ZeroAssignment
    report = PlanReport()
    za = ZeroAssignment({"op": {"kernel": {
        "spec": [["x0"], ["x1"]], "degree": 2}}})
    _check_zero(report, za, {"op": {"kernel": (None, "x1")}},
                {"op": {"kernel": (64, 64)}}, {"x0": 2, "x1": 4})
    assert report.ok(), [f.format() for f in report.findings]


def test_memory_envelope_per_parameter_zero():
    """A plan that only fits BECAUSE of its ZeRO assignment verifies:
    the envelope's opt-state term shrinks by each sharded leaf's
    degree (and is bit-identical to the flat formula with no
    assignment)."""
    from flexflow_tpu.analysis.plan_verifier import memory_envelope
    from flexflow_tpu.runtime.zero import ZeroAssignment
    from flexflow_tpu import AdamOptimizer
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff, out = _mlp(cfg, hidden=(64, 64))
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
               [], output_tensor=out)
    layers = ff.executor.program.layers
    axis_sizes = dict(ff.dmesh.axis_sizes)
    opt = AdamOptimizer(0.01)
    flat = memory_envelope(ff.strategy, layers, axis_sizes, opt)
    assert flat["opt_state_bytes"] == 2 * flat["params_bytes"]
    assert flat["zero_sharded_params"] == 0
    # shard one kernel's state by 8: its 2-slot term shrinks 8x
    za = ZeroAssignment({"op_linear_1": {"kernel": {
        "spec": [["x0"], None], "degree": 8}}})
    z = memory_envelope(ff.strategy, layers, axis_sizes, opt, zero=za)
    kernel_bytes = 64 * 64 * 4
    saved = 2 * kernel_bytes * (1 - 1 / 8)
    assert abs((flat["opt_state_bytes"] - z["opt_state_bytes"])
               - saved) < 1e-6
    assert z["zero_sharded_params"] == 1
    assert flat["envelope_bytes"] - z["envelope_bytes"] == saved


def test_zero_assignment_on_bank_member_rejected():
    """An (imported) assignment sharding a bank member's moments is an
    error: that state is stacked under the group key at runtime and
    would stay replicated while the envelope counted it sharded."""
    from flexflow_tpu.analysis.plan_verifier import (PlanReport,
                                                     _check_zero)
    from flexflow_tpu.runtime.zero import ZeroAssignment
    report = PlanReport()
    za = ZeroAssignment({"emb_0": {"weight": {
        "spec": [["x0"]], "degree": 2}}})
    _check_zero(report, za, {}, {"emb_0": {"weight": (50, 16)}},
                {"x0": 2, "x1": 4},
                unaddressable={"emb_0": "bank"})
    assert not report.ok()
    assert any(f.check == "zero" and "bank" in f.message
               and "replicated" in f.message for f in report.errors), \
        [f.format() for f in report.errors]


# ===========================================================================
# forced kernel impls: known-bad fixture + seq-aware envelope (ISSUE 19)
# ===========================================================================

def test_badplan_kernel_ring_noseq_rejected():
    """The pinned kernel-check rejection: a strategy assigning the
    'ring' attention impl on a mesh whose axes carry no sequence axis.
    The kernel check must reject with the op attributed; the same doc
    with a seq axis added verifies clean."""
    path = os.path.join(FIXTURES, "badplan_kernel_ring_noseq.json")
    report = verify_strategy_file(path)
    assert not report.ok()
    hits = [f for f in report.errors if f.check == "kernel"]
    assert hits, [f.format() for f in report.errors]
    assert any(f.op == "op_multihead_attention_0"
               and f.seam == "kernel-impl"
               and "sequence axis" in f.message for f in hits), \
        [f.format() for f in hits]
    with open(path) as f:
        doc = json.load(f)
    doc["mesh_axes"] = {"x0": 2, "seq": 4}
    assert verify_strategy_file(path, doc=doc).ok()


def test_badplan_kernel_ring_noseq_rejected_via_ffcheck_cli(tmp_path):
    """The same fixture through `ffcheck --verify-strategies` (the ci.sh
    gate's entry point): exit 1 with the kernel finding printed."""
    import shutil
    d = tmp_path / "strategies"
    d.mkdir()
    shutil.copy(os.path.join(FIXTURES, "badplan_kernel_ring_noseq.json"),
                str(d / "badplan_kernel_ring_noseq.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffcheck.py"),
         "--verify-strategies", str(d)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "kernel" in r.stdout \
        and "op_multihead_attention_0" in r.stdout, r.stdout


def test_kernel_unknown_impl_and_unknown_op_rejected():
    from flexflow_tpu.analysis.plan_verifier import (PlanReport,
                                                     _check_kernel)
    report = PlanReport()
    _check_kernel(report, {"attn_0": "warp", "ghost_op": "flash",
                           "opt_update": "mega"},
                  {"x0": 4}, {}, have_layers=True,
                  known_layers={"attn_0"})
    msgs = " | ".join(f.message for f in report.errors)
    assert "unknown attention impl 'warp'" in msgs
    assert "does not contain" in msgs
    assert "unknown kernel op kind 'opt_update' (impl 'mega'" in msgs


def test_memory_envelope_ring_divides_attention_residency():
    """A ring-assigned attention op's activation residency counts at
    1/seq-degree — the arithmetic that lets a context which only fits
    BECAUSE of ring attention verify."""
    from flexflow_tpu.analysis.plan_verifier import memory_envelope
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    q = ff.create_tensor((2, 256, 64), name="q")
    ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    layers = ff.executor.program.layers
    axis_sizes = {"x0": 1, "seq": 4}
    opt = SGDOptimizer(0.01)
    ff.strategy.kernel_impls = {}
    flat = memory_envelope(ff.strategy, layers, axis_sizes, opt)
    attn = next(l.name for l in layers
                if "attention" in l.op_type.name.lower())
    ff.strategy.kernel_impls = {attn: "ring"}
    ring = memory_envelope(ff.strategy, layers, axis_sizes, opt)
    assert flat["peak_activation_op"] == attn
    assert ring["peak_activation_bytes"] \
        <= flat["peak_activation_bytes"] / 4 + 1e-6
