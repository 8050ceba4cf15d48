"""Quantized gradient collectives (ISSUE 15, arXiv 2506.17615):
kernels, dtype plumbing, plan search, runtime parity, error-feedback
residual state (checkpoint / elastic), serialization, and the plan
verifier's qsync check."""
import json
import os
import tempfile

import numpy as np
import pytest

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


# ---------------------------------------------------------------------------
# dtypes (satellite: DT_INT8 / DT_FLOAT8_* round trip)
# ---------------------------------------------------------------------------

def test_narrow_dtypes_round_trip():
    import jax.numpy as jnp
    from flexflow_tpu.dtypes import from_numpy_dtype, itemsize, to_jnp
    from flexflow_tpu.ffconst import DataType
    assert to_jnp(DataType.DT_INT8) == jnp.int8
    assert to_jnp(DataType.DT_FLOAT8_E4M3) == jnp.float8_e4m3fn
    assert to_jnp(DataType.DT_FLOAT8_E5M2) == jnp.float8_e5m2
    for dt in (DataType.DT_INT8, DataType.DT_FLOAT8_E4M3,
               DataType.DT_FLOAT8_E5M2):
        assert itemsize(dt) == 1
        assert from_numpy_dtype(np.dtype(to_jnp(dt))) == dt
    assert from_numpy_dtype(np.int8) == DataType.DT_INT8
    # string aliases through the enum's _missing_
    assert DataType("int8") == DataType.DT_INT8
    assert DataType("float8_e4m3") == DataType.DT_FLOAT8_E4M3
    assert DataType("e5m2") == DataType.DT_FLOAT8_E5M2
    assert DataType("float8_e4m3fn") == DataType.DT_FLOAT8_E4M3


def test_wire_byte_scale():
    from flexflow_tpu.parallel.placement import (QSYNC_CHUNK,
                                                 wire_byte_scale)
    assert wire_byte_scale(None) == 1.0
    s = wire_byte_scale("int8")
    assert 0.25 < s < 0.26          # 1/4 payload + per-chunk scales
    assert s == (1 + 4.0 / QSYNC_CHUNK) / 4.0
    assert wire_byte_scale("float8_e4m3") == s


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _mesh_and_sizes():
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("x0", "x1"))
    return mesh, {"x0": 4, "x1": 2}


def test_quantize_chunked_exact_on_representable():
    import jax.numpy as jnp
    from flexflow_tpu.ops.quantized_collectives import (
        dequantize_chunked, quantize_chunked)
    # integers with per-chunk amax exactly 127: scale 1, lossless
    rng = np.random.default_rng(0)
    x = rng.integers(-127, 128, size=(4, 1024)).astype(np.float32)
    x[:, 0] = 127.0
    q, s = quantize_chunked(jnp.asarray(x), "int8")
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(dequantize_chunked(q, s)),
                                  x)


def test_quantize_chunked_error_bound():
    import jax.numpy as jnp
    from flexflow_tpu.ops.quantized_collectives import (
        dequantize_chunked, quantize_chunked)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 1024)).astype(np.float32)
    q, s = quantize_chunked(jnp.asarray(x), "int8")
    err = np.abs(np.asarray(dequantize_chunked(q, s)) - x)
    # per-chunk bound: half a quantization step of that chunk's scale
    bound = np.asarray(s) * 0.5 + 1e-7
    assert (err <= bound).all()


def test_quantized_all_reduce_matches_psum_and_residual_mass():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.ops.quantized_collectives import (
        quantized_all_reduce)
    from jax import shard_map
    mesh, sizes = _mesh_and_sizes()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 300)).astype(np.float32)

    def body(xl):
        out, r = quantized_all_reduce(xl[0], ("x0", "x1"), "int8", 8,
                                      sizes)
        ref = jax.lax.psum(xl[0], ("x0", "x1"))
        return out[None], ref[None], r[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(("x0", "x1")),
                          out_specs=P(("x0", "x1")), check_vma=False))
    out, ref, r = f(jnp.asarray(x))
    out, ref, r = map(np.asarray, (out, ref, r))
    assert np.abs(out - ref).max() < np.abs(ref).max() * 0.05
    # error-feedback invariant: the residuals' device-sum is EXACTLY
    # the mass the quantized result withheld from the true sum
    np.testing.assert_allclose(r.sum(axis=0), ref[0] - out[0],
                               atol=1e-3)


def test_phased_sync_staged_dcn_leg():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.ops.quantized_collectives import phased_sync
    from jax import shard_map
    mesh, sizes = _mesh_and_sizes()
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 257)).astype(np.float32)
    r0 = np.zeros((8, 257), np.float32)

    def body(xl, rl):
        out, r = phased_sync(
            xl[0], [(("x0",), None), (("x1",), "int8")], sizes,
            residual=rl[0])
        ref = jax.lax.psum(xl[0], ("x0", "x1")) / 8
        return out[None], ref[None], r[None]

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(("x0", "x1")), P(("x0", "x1"))),
        out_specs=P(("x0", "x1")), check_vma=False))
    out, ref, r = f(jnp.asarray(x), jnp.asarray(r0))
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() \
        < np.abs(np.asarray(ref)).max() * 0.05
    # error feedback drives the ACCUMULATED mean toward the true mean
    tot = np.zeros(257, np.float64)
    ref_tot = np.zeros(257, np.float64)
    r_cur = jnp.asarray(r0)
    for _ in range(20):
        o, rf, r_cur = f(jnp.asarray(x), r_cur)
        tot += np.asarray(o)[0]
        ref_tot += np.asarray(rf)[0]
    drift = np.abs(tot - ref_tot).max() / np.abs(ref_tot).max()
    assert drift < 0.01, drift


def test_phased_sync_full_precision_passthrough():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.ops.quantized_collectives import phased_sync
    from jax import shard_map
    mesh, sizes = _mesh_and_sizes()
    x = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)

    def body(xl):
        out, r = phased_sync(xl[0], [(("x0", "x1"), None)], sizes)
        ref = jax.lax.psum(xl[0], ("x0", "x1")) / 8
        return out[None], ref[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(("x0", "x1")),
                          out_specs=P(("x0", "x1")), check_vma=False))
    out, ref = f(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# residual refit (elastic world changes)
# ---------------------------------------------------------------------------

def test_refit_residual_preserves_mass():
    from flexflow_tpu.ops.quantized_collectives import refit_residual
    rng = np.random.default_rng(3)
    r = rng.normal(size=(8, 5, 3)).astype(np.float32)
    total = r.sum(axis=0)
    shrunk = refit_residual(r, 4)           # 8 -> 4: sum-fold pairs
    assert shrunk.shape == (4, 5, 3)
    np.testing.assert_allclose(shrunk.sum(axis=0), total, atol=1e-5)
    np.testing.assert_allclose(shrunk[0], r[0] + r[1], atol=1e-6)
    grown = refit_residual(r[:4], 8)        # 4 -> 8: zero-fill
    assert grown.shape == (8, 5, 3)
    np.testing.assert_allclose(grown.sum(axis=0), r[:4].sum(axis=0))
    assert (grown[4:] == 0).all()
    odd = refit_residual(r, 3)              # non-divisible: fold to 0
    np.testing.assert_allclose(odd.sum(axis=0), total, atol=1e-5)
    assert (odd[1:] == 0).all()
    same = refit_residual(r, 8)
    np.testing.assert_array_equal(same, r)


# ---------------------------------------------------------------------------
# planning + cost model
# ---------------------------------------------------------------------------

def _dp_model(mode, machine_spec=None, hidden=(128, 128), optimizer=None,
              **cfg_kw):
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models import build_mlp
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.quantized_collectives = mode
    cfg.seed = 5
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    ff = FFModel(cfg)
    out = build_mlp(ff, cfg.batch_size, in_dim=32, hidden=hidden,
                    num_classes=8)
    ff.compile(optimizer or AdamOptimizer(0.01),
               "sparse_categorical_crossentropy", [],
               output_tensor=out, machine_spec=machine_spec)
    return ff


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {"input": rng.normal(size=(16, 32)).astype(np.float32),
            "label": rng.integers(0, 8, size=(16, 1)).astype(np.int32)}


def _run(ff, steps=4, seed=0):
    b = _batch(seed)
    step = ff.executor.make_train_step()
    return [float(np.asarray(ff._run_train_step(step, b)["loss"]))
            for _ in range(steps)]


def _two_slice_spec():
    from flexflow_tpu.parallel.machine import MachineSpec
    spec = MachineSpec.detect()
    spec.num_devices = 8
    spec.num_slices = 2
    spec.num_hosts = 2
    spec.dcn_bandwidth_gbps = 1.0
    spec.dcn_latency_us = 20.0
    return spec


def test_plan_auto_is_per_tensor():
    ff = _dp_model("auto")
    plan = ff.strategy.qsync
    assert plan is not None and plan.quantized_params()
    # auto is a genuine per-tensor trade: big kernels quantize, the
    # latency/overhead-dominated tiny biases do not (records exist
    # only for adopted tensors, and no bias should be among them)
    names = [w for _, w in plan.quantized_params()]
    assert "kernel" in names
    assert ff.executor._qsync is not None
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    assert RESIDUAL_SLOT in ff.opt_state


def test_plan_off_is_none_and_bit_exact():
    ff = _dp_model("off")
    assert ff.strategy.qsync is None
    assert ff.executor._qsync is None
    l1 = _run(ff)
    l2 = _run(_dp_model("off"))
    assert l1 == l2


def test_plan_dcn_only_needs_dcn():
    # flat (single-slice) machine: dcn_only has nothing to narrow
    ff = _dp_model("dcn_only")
    assert ff.strategy.qsync is None


def test_plan_dcn_only_two_slice_quantizes_dcn_leg_only():
    ff = _dp_model("dcn_only", machine_spec=_two_slice_spec())
    plan = ff.strategy.qsync
    assert plan is not None and plan.quantized_params()
    for lname, ws in plan.decisions.items():
        for wname, rec in ws.items():
            for p in rec["phases"]:
                if p["wire"] is not None:
                    assert p["tier"] == "dcn", (lname, wname, p)
                else:
                    assert p["tier"] != "dcn"
    assert ff.strategy.axis_tiers   # self-describing export


def test_quantized_sync_quote_flat():
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    cm = OpCostModel(MachineSpec(num_devices=8, generation="cpu-sim"))
    cm.attach_quantization("auto", "int8")
    base, q, wires = cm.quantized_sync_quote(
        1 << 20, 8, [(("x0",), "ici")])
    assert q < base             # 1 MiB at 1/4 wire bytes wins
    assert wires == ["int8"]
    # tiny tensor: the quantize/dequantize overhead eats the saving
    base2, q2, wires2 = cm.quantized_sync_quote(64, 8,
                                                [(("x0",), "ici")])
    assert wires2 == [None] and q2 == base2


def test_attach_quantization_validates_and_detaches():
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    cm = OpCostModel(MachineSpec(num_devices=8, generation="cpu-sim"))
    with pytest.raises(ValueError):
        cm.attach_quantization("sometimes")
    cm.attach_quantization("all", "int8")
    assert cm.quantization == {"mode": "all", "wire": "int8"}
    t_q = cm.weight_sync_cost(1 << 20, 8)
    assert cm.last_sync_wire == "int8"
    cm.attach_quantization(None)
    t_f = cm.weight_sync_cost(1 << 20, 8)
    assert cm.last_sync_wire == "float32"
    assert t_q < t_f


def test_audit_entries_record_sync_wire():
    # satellite: grad-sync audit entries carry the wire dtype —
    # "float32" by default, the wire name under a quantization policy
    ff = _dp_model("auto", trace="true")
    from flexflow_tpu.search.mcmc import (StrategySimulator,
                                          data_parallel_assignment)
    from flexflow_tpu.search.costmodel import OpCostModel
    cm = OpCostModel(ff.dmesh.spec)
    sim = StrategySimulator(ff.layers, ff.dmesh, cm)
    dp = data_parallel_assignment(ff.layers, ff.dmesh, sim.options)
    _gc, entries = sim.evaluate_breakdown(dp)
    wires = {e.get("sync_wire") for e in entries if e["sync_s"] > 0}
    assert wires == {"float32"}
    cm.attach_quantization("all", "int8")
    _gc, entries = sim.evaluate_breakdown(dp)
    wires = {e.get("sync_wire") for e in entries if e["sync_s"] > 0}
    assert wires == {"int8"}
    # the unity evaluator shares the contract
    from flexflow_tpu.search.unity import (GraphCostEvaluator,
                                           data_parallel_graph)
    g = data_parallel_graph(ff.layers, ff.graph_inputs,
                            [ff._output_tensor], ff.dmesh)
    ev = GraphCostEvaluator(cm, ff.dmesh)
    _gc, u_entries = ev.graph_cost_breakdown(g)
    u_wires = {e.get("sync_wire") for e in u_entries
               if e.get("sync_s", 0) > 0}
    assert u_wires == {"int8"}
    # adopted-plan audit record ("quantized_sync" section) when the
    # compile wrote one
    path = getattr(ff, "_strategy_audit_path", None)
    if path:
        from flexflow_tpu.obs.audit import load_strategy_audit
        rec = load_strategy_audit(path)
        assert rec.get("quantized_sync", {}).get("n_quantized", 0) >= 1


def test_calibration_wire_rows_and_fallback(tmp_path):
    from flexflow_tpu.search.calibration import (CalibrationTable,
                                                 MeshCalibration,
                                                 shape_class)
    tab = CalibrationTable(str(tmp_path))
    calib = MeshCalibration(backend="cpu", table=tab)
    # float32 rows only: a wire-dtype query answers None (strict), the
    # caller falls back to the itemsize-scaled float32 query
    tab.put("cpu", "coll_all_reduce", "float32", shape_class(1 << 20),
            8, 1e-3)
    tab.put("cpu", "coll_all_reduce", "float32", shape_class(1 << 23),
            8, 8e-3)
    assert calib.collective_time("all_reduce", 8, 1 << 21,
                                 dtype="int8") is None
    t_full = calib.collective_time("all_reduce", 8, 1 << 21)
    assert t_full is not None
    # wire rows present: the int8 query answers from THEM
    tab.put("cpu", "coll_all_reduce", "int8", shape_class(1 << 20), 8,
            3e-4)
    calib2 = MeshCalibration(backend="cpu", table=tab)
    t_wire = calib2.collective_time("all_reduce", 8, 1 << 20,
                                    dtype="int8")
    assert t_wire == pytest.approx(3e-4)
    # and a float32 query never reads the int8 row
    assert calib2.collective_time("all_reduce", 8, 1 << 20) \
        == pytest.approx(1e-3)


# ---------------------------------------------------------------------------
# runtime parity + composition
# ---------------------------------------------------------------------------

def test_quantized_training_tracks_baseline():
    lq = _run(_dp_model("auto"), steps=5)
    lb = _run(_dp_model("off"), steps=5)
    assert lq[0] == pytest.approx(lb[0], rel=1e-6)  # pre-update step
    for a, b in zip(lq, lb):
        assert abs(a - b) <= max(abs(b) * 0.05, 2e-3), (lq, lb)
    assert lq[-1] < lq[0]


def test_quantized_composes_with_overlap_schedule(monkeypatch):
    monkeypatch.setenv("FF_OVERLAP", "1")
    ff = _dp_model("auto")
    assert ff.executor._qsync is not None
    assert ff.executor._overlap_schedule is not None
    l_ov = _run(ff, steps=3)
    monkeypatch.delenv("FF_OVERLAP")
    l_plain = _run(_dp_model("auto"), steps=3)
    # overlap is schedule shaping, never math: bit-exact on the same
    # quantized grads
    assert l_ov == l_plain


def test_runtime_falls_back_on_accum():
    ff = _dp_model("auto", gradient_accumulation_steps=2)
    # plan may exist, the runtime schedule must not
    assert ff.executor._qsync is None


def test_two_slice_dcn_quantized_training():
    ff = _dp_model("dcn_only", machine_spec=_two_slice_spec())
    assert ff.executor._qsync is not None
    lq = _run(ff, steps=4)
    lb = _run(_dp_model("off", machine_spec=_two_slice_spec()), steps=4)
    for a, b in zip(lq, lb):
        assert abs(a - b) <= max(abs(b) * 0.05, 2e-3), (lq, lb)


# ---------------------------------------------------------------------------
# residual state: checkpoint round trip, shrunken world, elastic
# ---------------------------------------------------------------------------

def test_residual_checkpoint_round_trip_bit_exact():
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    from flexflow_tpu.runtime.checkpoint import (
        restore_model_checkpoint, save_model_checkpoint)
    ff = _dp_model("auto")
    _run(ff, steps=2)       # residuals now non-zero
    res_before = {l: {w: np.asarray(a) for w, a in ws.items()}
                  for l, ws in ff.opt_state[RESIDUAL_SLOT].items()}
    assert any(np.abs(a).max() > 0
               for ws in res_before.values() for a in ws.values())
    with tempfile.TemporaryDirectory() as d:
        save_model_checkpoint(ff, d)
        ff2 = _dp_model("auto")
        restore_model_checkpoint(ff2, d)
        for lname, ws in res_before.items():
            for wname, arr in ws.items():
                got = np.asarray(
                    ff2.opt_state[RESIDUAL_SLOT][lname][wname])
                np.testing.assert_array_equal(got, arr)
        # continuation is bit-exact vs the uninterrupted run
        l_cont = _run(ff2, steps=1)
        l_ref = _run(ff, steps=1)
        assert l_cont == l_ref


def test_residual_restores_into_smaller_world():
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.runtime.checkpoint import (
        restore_model_checkpoint, save_model_checkpoint)
    ff = _dp_model("auto")
    _run(ff, steps=2)
    res8 = {l: {w: np.asarray(a) for w, a in ws.items()}
            for l, ws in ff.opt_state[RESIDUAL_SLOT].items()}
    with tempfile.TemporaryDirectory() as d:
        save_model_checkpoint(ff, d)
        ff4 = _dp_model("auto", machine_spec=MachineSpec(
            num_devices=4, generation="cpu-sim"))
        assert ff4.dmesh.num_devices == 4
        restore_model_checkpoint(ff4, d)
        # residuals sum-folded 8 -> 4: withheld mass preserved exactly,
        # re-placed via reshard.place_host onto the 4-device sharding
        for lname, ws in res8.items():
            got = ff4.opt_state[RESIDUAL_SLOT][lname]
            for wname, arr in ws.items():
                g = np.asarray(got[wname])
                assert g.shape[0] == 4
                np.testing.assert_allclose(g.sum(axis=0),
                                           arr.sum(axis=0), atol=1e-5)
        l4 = _run(ff4, steps=1)
        assert np.isfinite(l4[0])


def test_restore_without_residuals_zero_fills():
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    from flexflow_tpu.runtime.checkpoint import (
        restore_model_checkpoint, save_model_checkpoint)
    ff_plain = _dp_model("off")
    _run(ff_plain, steps=1)
    with tempfile.TemporaryDirectory() as d:
        save_model_checkpoint(ff_plain, d)
        ff_q = _dp_model("auto")
        _run(ff_q, steps=2)   # dirty residuals
        restore_model_checkpoint(ff_q, d)
        for ws in ff_q.opt_state[RESIDUAL_SLOT].values():
            for a in ws.values():
                assert np.abs(np.asarray(a)).max() == 0.0
        l = _run(ff_q, steps=1)
        assert np.isfinite(l[0])


def test_residual_placement_rides_place_host():
    # the residual leaves are genuinely SHARDED over the sync axes:
    # each device holds exactly its own row
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    ff = _dp_model("auto")
    leaf = next(a for ws in ff.opt_state[RESIDUAL_SLOT].values()
                for a in ws.values())
    assert leaf.shape[0] == 8
    assert not leaf.sharding.is_fully_replicated
    shard_shapes = {s.data.shape for s in leaf.addressable_shards}
    assert shard_shapes == {(1,) + tuple(leaf.shape[1:])}


# ---------------------------------------------------------------------------
# serialization + verifier
# ---------------------------------------------------------------------------

def test_qsync_serialization_round_trip(tmp_path):
    from flexflow_tpu.search.serialization import (load_strategy,
                                                   save_strategy)
    ff = _dp_model("auto")
    path = str(tmp_path / "strategy.json")
    save_strategy(path, ff.strategy)
    with open(path) as f:
        doc = json.load(f)
    assert doc.get("qsync", {}).get("mode") == "auto"
    st2 = load_strategy(path, ff.layers, ff.dmesh)
    assert st2.qsync is not None
    assert st2.qsync.to_json() == ff.strategy.qsync.to_json()


def test_badplan_qsync_tier_rejected():
    from flexflow_tpu.analysis.plan_verifier import verify_strategy_file
    path = os.path.join(FIXTURES, "badplan_qsync_tier.json")
    report = verify_strategy_file(path)
    assert report.errors, report.findings
    msgs = [f.message for f in report.errors]
    assert any("declared tier path" in m or "is placed on tier" in m
               for m in msgs), msgs
    assert any("SHARDED" in m for m in msgs), msgs
    assert all(f.check == "qsync" for f in report.errors), \
        [(f.check, f.message) for f in report.errors]


def test_badplan_qsync_tier_rejected_via_ffcheck_cli(tmp_path):
    import shutil
    import subprocess
    import sys
    d = tmp_path / "strategies"
    d.mkdir()
    shutil.copy(os.path.join(FIXTURES, "badplan_qsync_tier.json"),
                str(d / "badplan_qsync_tier.json"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ffcheck.py"),
         "--verify-strategies", str(d)],
        capture_output=True, text=True)
    assert proc.returncode == 1, (proc.stdout, proc.stderr)
    assert "qsync" in proc.stdout + proc.stderr


def test_explicit_disable_strips_imported_plan(tmp_path):
    # --no-quantized-collectives (the "disable" spelling) must force
    # full precision even for an imported strategy carrying a plan;
    # the plain default "off" honors the import verbatim
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models import build_mlp
    from flexflow_tpu.search.serialization import save_strategy
    ff = _dp_model("auto")
    path = str(tmp_path / "qstrategy.json")
    save_strategy(path, ff.strategy)

    def build_import(mode):
        cfg = FFConfig()
        cfg.batch_size = 16
        cfg.quantized_collectives = mode
        cfg.import_strategy_file = path
        cfg.seed = 5
        m = FFModel(cfg)
        out = build_mlp(m, 16, in_dim=32, hidden=(128, 128),
                        num_classes=8)
        m.compile(AdamOptimizer(0.01),
                  "sparse_categorical_crossentropy", [],
                  output_tensor=out)
        return m

    ff_off = build_import("off")          # default: verbatim
    assert ff_off.strategy.qsync is not None
    assert ff_off.executor._qsync is not None
    ff_dis = build_import("disable")      # explicit: stripped
    assert ff_dis.strategy.qsync is None
    assert ff_dis.executor._qsync is None
    from flexflow_tpu.ops.quantized_collectives import RESIDUAL_SLOT
    assert RESIDUAL_SLOT not in ff_dis.opt_state
    # and --no-quantized-collectives parses to the disable spelling
    cfg = FFConfig.parse_args(["--no-quantized-collectives"])
    assert cfg.quantized_collectives == "disable"


def test_reshape_rescale_scoped_to_local_shape():
    import jax.numpy as jnp
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.ops import EmitCtx, get_op_def
    op = get_op_def(OperatorType.OP_RESHAPE)
    x = jnp.zeros((4, 8), jnp.float32)   # a (1/4)-shard of (16, 8)
    params = {"shape": (16, 4, 2)}
    ctx = EmitCtx(training=False)
    with pytest.raises(TypeError):
        # global emission keeps the historical hard error on any
        # volume-mismatched reshape
        op.emit(params, [x], {}, ctx, "r")
    ctx.local_shape = True
    out = op.emit(params, [x], {}, ctx, "r")[0]
    assert out.shape == (4, 4, 2)


def test_dropout_model_quantized_path_converges():
    # RNG-consuming layers stay eligible: per-device dropout streams
    # decorrelate via the shard index (matching the global path's
    # independent per-row masks in distribution)
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.ffconst import ActiMode

    def build(mode):
        cfg = FFConfig()
        cfg.batch_size = 16
        cfg.only_data_parallel = True
        cfg.quantized_collectives = mode
        cfg.seed = 5
        ff = FFModel(cfg)
        x = ff.create_tensor((16, 32), name="input")
        t = ff.dense(x, 128, ActiMode.AC_MODE_RELU)
        t = ff.dropout(t, 0.2)
        t = ff.dense(t, 8)
        out = ff.softmax(t)
        ff.compile(AdamOptimizer(0.01),
                   "sparse_categorical_crossentropy", [],
                   output_tensor=out)
        return ff

    ff = build("all")
    assert ff.executor._qsync is not None
    losses = _run(ff, steps=5)
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]
    lb = _run(build("off"), steps=5)
    # different mask realizations: compare trend, not bits
    assert abs(losses[-1] - lb[-1]) <= max(abs(lb[-1]), 0.05) * 0.5


def test_verifier_accepts_adopted_plan():
    from flexflow_tpu.analysis.plan_verifier import verify_plan
    ff = _dp_model("auto")
    report = verify_plan(ff.strategy, ff.executor.program.layers,
                         machine_spec=ff.dmesh.spec,
                         graph_inputs=ff.graph_inputs,
                         optimizer=ff.optimizer)
    assert not report.errors, [f.message for f in report.errors]
