"""Calibration v2 (search/calibration.py): persistent on-device
microbenchmark tables. The contract under test:

  - a fresh process (second table instance over the same cache dir)
    serves every term from disk with ZERO re-measurements;
  - a value recorded for one backend/dtype is never served for another;
  - an attached calibration actually changes the cost model's terms
    (host dispatch, memory bandwidth, parallel efficiency, collective
    tables) and the collective lookup interpolates between the
    measured shape classes.
"""
import os

import pytest

from flexflow_tpu.parallel.machine import DeviceMesh, MachineSpec
from flexflow_tpu.search.calibration import (CalibrationTable,
                                             MeshCalibration,
                                             calibrate_mesh,
                                             calibration_enabled,
                                             shape_class)
from flexflow_tpu.search.costmodel import OpCostModel


def test_second_load_hits_persisted_table(tmp_path):
    spec = MachineSpec.detect()
    dm = DeviceMesh(spec)
    tab1 = CalibrationTable(str(tmp_path))
    c1 = calibrate_mesh(dm, table=tab1)
    assert tab1.measured > 0          # cold dir: live microbenchmarks ran
    assert c1.dispatch_s and c1.dispatch_s > 0
    assert c1.mem_bw and c1.mem_bw > 0
    assert os.path.exists(tab1.path)
    # fresh table over the same dir = a fresh process: everything must
    # come from disk, with zero re-measurements
    tab2 = CalibrationTable(str(tmp_path))
    c2 = calibrate_mesh(dm, table=tab2)
    assert tab2.measured == 0
    assert c2.dispatch_s == c1.dispatch_s
    assert c2.mem_bw == c1.mem_bw
    assert c2.parallel_eff == c1.parallel_eff


def test_backend_and_dtype_isolation(tmp_path):
    tab = CalibrationTable(str(tmp_path))
    tab.put("cpu", "coll_all_reduce", "float32", 1 << 20, 8, 0.5)
    assert tab.get("cpu", "coll_all_reduce", "float32", 1 << 20, 8) == 0.5
    # another backend, dtype, shape class or axis size: never served
    assert tab.get("tpu", "coll_all_reduce", "float32", 1 << 20, 8) is None
    assert tab.get("cpu", "coll_all_reduce", "bfloat16", 1 << 20, 8) is None
    assert tab.get("cpu", "coll_all_reduce", "float32", 1 << 21, 8) is None
    assert tab.get("cpu", "coll_all_reduce", "float32", 1 << 20, 4) is None
    # the MeshCalibration lookup inherits the isolation via its key
    other = MeshCalibration(backend="tpu", table=tab)
    assert other.collective_time("all_reduce", 8, 1 << 20) is None
    same = MeshCalibration(backend="cpu", table=tab)
    assert same.collective_time("all_reduce", 8, 1 << 20) \
        == pytest.approx(0.5)


def test_collective_lookup_interpolates(tmp_path):
    tab = CalibrationTable(str(tmp_path))
    tab.put("cpu", "coll_all_reduce", "float32", 1 << 18, 8, 1e-3)
    tab.put("cpu", "coll_all_reduce", "float32", 1 << 22, 8, 16e-3)
    c = MeshCalibration(backend="cpu", table=tab)
    t_mid = c.collective_time("all_reduce", 8, 1 << 20)
    assert 1e-3 < t_mid < 16e-3       # between the measured classes
    # linear-in-log: 2^20 is the geometric midpoint of 2^18..2^22, so
    # the time lands at the geometric mean of the endpoints (4e-3)
    assert t_mid == pytest.approx(4e-3, rel=0.05)
    # below the smallest measured class: CLAMPED to the measured floor
    # (fixed dispatch/rendezvous cost), never extrapolated downward
    assert c.collective_time("all_reduce", 8, 1 << 10) \
        == pytest.approx(1e-3)
    # an unmeasured degree within 2x answers from the nearest curve;
    # farther than 2x falls through to the caller
    assert c.collective_time("all_reduce", 4, 1 << 20) \
        == pytest.approx(t_mid)
    assert c.collective_time("all_reduce", 2, 1 << 20) is None


def test_cost_model_consumes_calibration():
    spec = MachineSpec.detect()
    cm = OpCostModel(spec)
    from flexflow_tpu import FFConfig, FFModel
    ff = FFModel(FFConfig())
    x = ff.create_tensor((32, 256), name="x")
    ff.dense(x, 256)
    lin = ff.layers[-1]
    base = cm.op_cost(lin, {}).forward_time
    calib = MeshCalibration(backend="cpu", dispatch_s=5e-3,
                            mem_bw=1e9, parallel_eff={8: 0.25})
    cm.attach_calibration(calib)
    with_calib = cm.op_cost(lin, {}).forward_time
    # the measured dispatch overhead (5 ms) dominates this tiny op
    assert with_calib >= 5e-3 > base
    # oversubscription: 8 concurrent shards at eff 0.25 stretch the
    # per-shard work 1/0.25 = 4x relative to the same shards at eff 1
    t8 = cm.op_cost(lin, {0: 8}).forward_time
    cm_ideal = OpCostModel(spec)
    cm_ideal.attach_calibration(MeshCalibration(
        backend="cpu", dispatch_s=5e-3, mem_bw=1e9,
        parallel_eff={8: 1.0}))
    t8_ideal = cm_ideal.op_cost(lin, {0: 8}).forward_time
    assert t8 - 5e-3 == pytest.approx((t8_ideal - 5e-3) * 4, rel=1e-6)
    # efficiency interpolation: unmeasured widths between 1 and 8
    assert calib.efficiency(1) == 1.0
    assert 0.25 < calib.efficiency(4) < 1.0
    assert calib.efficiency(16) == 0.25   # wider than measured: worst


def test_xfer_cost_prefers_measured_table(tmp_path):
    spec = MachineSpec.detect()
    cm = OpCostModel(spec)
    analytic = cm.xfer_cost(1 << 20, "all_reduce", 8)
    tab = CalibrationTable(str(tmp_path))
    tab.put("cpu", "coll_all_reduce", "float32", 1 << 20, 8, 0.123)
    cm.attach_calibration(MeshCalibration(backend="cpu", table=tab))
    assert cm.xfer_cost(1 << 20, "all_reduce", 8) == pytest.approx(0.123)
    assert analytic != pytest.approx(0.123)
    # unmeasured degree: falls back to the analytic/fitted path
    assert cm.xfer_cost(1 << 20, "all_reduce", 2) \
        == pytest.approx(OpCostModel(spec).xfer_cost(1 << 20,
                                                     "all_reduce", 2))


def test_a_table_with_kernel_impl_rows_loads_and_ignores_them(tmp_path):
    """A table cached while the search still priced attention by
    implementation holds ``op_attention@<impl>`` and
    ``op_opt_update@<impl>`` rows. Nothing reads them now: the table
    loads, its other rows answer, an attention layer costs what it
    costs without them, and marked stale they are left for nobody to
    re-measure, without an error."""
    import json
    from flexflow_tpu import FFConfig, FFModel
    old = {"cpu|op_attention@flash|float32|65536|0": 3e-4,
           "cpu|op_attention@ring|float32|1048576|4": 9e-4,
           "cpu|op_opt_update@fused|float32|1048576|0": 2e-4}
    kept = {"cpu|coll_all_reduce|float32|1048576|8": 0.123}

    def table(rows, name):
        d = tmp_path / name
        d.mkdir()
        (d / "calibration_v2.json").write_text(json.dumps(rows))
        return CalibrationTable(str(d))

    ff = FFModel(FFConfig())
    q = ff.create_tensor((4, 256, 64), name="q")
    ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    attn = ff.layers[-1]
    spec = MachineSpec.detect()
    costs = []
    for tab in (table({**old, **kept}, "with"), table(kept, "without")):
        assert tab.get("cpu", "coll_all_reduce", "float32",
                       1 << 20, 8) == 0.123
        cm = OpCostModel(spec)
        cm.attach_calibration(MeshCalibration(backend="cpu", table=tab))
        assert cm.xfer_cost(1 << 20, "all_reduce", 8) \
            == pytest.approx(0.123)
        costs.append(cm.op_cost(attn, {0: 4}))
    assert costs[0] == costs[1]
    tab = table(old, "stale")
    assert tab.mark_stale(list(old)) == len(old)
    assert tab.remeasure_stale(DeviceMesh(spec, seq=4)) == {}
    assert sorted(tab.stale_keys()) == sorted(old)


def test_shape_class_buckets():
    assert shape_class(1 << 20) == 1 << 20
    assert shape_class((1 << 20) + 100) == 1 << 20
    assert shape_class(3 << 20) == 1 << 22   # rounds to nearest pow2
    assert shape_class(1) == 1


def test_calibration_enabled_resolution(monkeypatch):
    class Cfg:
        calibration_v2 = "auto"
    monkeypatch.delenv("FF_CALIBRATION_V2", raising=False)
    assert not calibration_enabled(Cfg())
    monkeypatch.setenv("FF_CALIBRATION_V2", "1")
    assert calibration_enabled(Cfg())
    Cfg.calibration_v2 = "false"          # explicit config beats env
    assert not calibration_enabled(Cfg())
    monkeypatch.delenv("FF_CALIBRATION_V2", raising=False)
    Cfg.calibration_v2 = "true"
    assert calibration_enabled(Cfg())
