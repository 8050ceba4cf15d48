"""Parallelism tests on the 8-device CPU mesh: TP/SP/EP strategies give the
same numerics as DP, and shardings are actually applied (reference analog:
verifying parallel ops preserve semantics, §4 of the build plan)."""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from flexflow_tpu import (ActiMode, DeviceMesh, FFConfig, FFModel,
                          MachineSpec, SGDOptimizer, ShardingStrategy)
from flexflow_tpu.models import (MoeConfig, TransformerConfig,
                                 build_moe_mnist, build_transformer)
from flexflow_tpu.parallel.presets import (expert_parallel_strategy,
                                           transformer_strategy)


def _build_tf(strategy_fn=None, mesh_shape=None):
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.mesh_shape = mesh_shape
    ff = FFModel(cfg)
    tcfg = TransformerConfig(hidden_size=32, num_heads=4, num_layers=2,
                             sequence_length=16)
    out = build_transformer(ff, 8, tcfg)
    spec = MachineSpec.detect()
    dmesh = DeviceMesh(spec, mesh_shape=mesh_shape)
    strategy = strategy_fn(ff, dmesh) if strategy_fn else None
    if strategy is None:
        cfg.only_data_parallel = True
    ff.compile(SGDOptimizer(0.01), "mean_squared_error", [],
               strategy=strategy, output_tensor=out)
    return ff, out


def _forward_out(ff):
    rng = np.random.default_rng(0)
    batch = {"input": rng.normal(size=(8, 16, 32)).astype(np.float32)}
    fwd = ff.executor.make_forward()
    return np.asarray(fwd(ff.params, ff.state, batch))


def test_tp_matches_dp_numerics():
    ff_dp, _ = _build_tf(None)
    y_dp = _forward_out(ff_dp)

    def strat(ff, dmesh):
        return transformer_strategy(ff.layers, ff.input_tensors, dmesh,
                                    dp_axes=("x0",), tp_axes=("x1", "x2"))

    ff_tp, _ = _build_tf(strat)
    # same seed → same initial weights; TP forward must equal DP forward
    y_tp = _forward_out(ff_tp)
    np.testing.assert_allclose(y_dp, y_tp, rtol=2e-2, atol=2e-3)
    # weights must actually be sharded
    attn = [l for l in ff_tp.layers
            if l.op_type.name == "OP_MULTIHEAD_ATTENTION"][0]
    wq = ff_tp.params[attn.name]["wq"]
    assert not wq.sharding.is_fully_replicated
    assert len(wq.addressable_shards) == 8
    assert wq.addressable_shards[0].data.shape[1] == wq.shape[1] // 4


def test_sp_matches_dp_numerics():
    ff_dp, _ = _build_tf(None)
    y_dp = _forward_out(ff_dp)

    def strat(ff, dmesh):
        return transformer_strategy(ff.layers, ff.input_tensors, dmesh,
                                    dp_axes=("x0",), tp_axes=("x1", "x2"),
                                    sp=True)

    ff_sp, _ = _build_tf(strat)
    y_sp = _forward_out(ff_sp)
    np.testing.assert_allclose(y_dp, y_sp, rtol=2e-2, atol=2e-3)


def test_tp_train_step_runs():
    def strat(ff, dmesh):
        return transformer_strategy(ff.layers, ff.input_tensors, dmesh,
                                    dp_axes=("x0",), tp_axes=("x1", "x2"))

    ff, out = _build_tf(strat)
    rng = np.random.default_rng(0)
    batch = {"input": rng.normal(size=(8, 16, 32)).astype(np.float32),
             "label": rng.normal(size=(8, 16, 1)).astype(np.float32)}
    step = ff.executor.make_train_step()
    bm = ff._run_train_step(step, batch)
    assert np.isfinite(float(np.asarray(bm["loss"])))


def test_ep_moe_train_step():
    cfg = FFConfig()
    cfg.batch_size = 16
    ff = FFModel(cfg)
    out = build_moe_mnist(ff, 16, MoeConfig.tiny())
    dmesh = DeviceMesh(MachineSpec.detect())
    strat = expert_parallel_strategy(ff.layers, ff.input_tensors, dmesh,
                                     dp_axes=("x0",), ep_axes=("x1",))
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               strategy=strat, output_tensor=out)
    rng = np.random.default_rng(0)
    batch = {"input": rng.normal(size=(16, 64)).astype(np.float32),
             "label": rng.integers(0, 10, size=(16, 1)).astype(np.int32)}
    step = ff.executor.make_train_step()
    bm = ff._run_train_step(step, batch)
    assert np.isfinite(float(np.asarray(bm["loss"])))


def test_strategy_validate_catches_axis_reuse():
    dmesh = DeviceMesh(MachineSpec.detect())
    st = ShardingStrategy(dmesh)
    st.set_op("bad", [P(("x0", "x0"))], {})
    errs = st.validate()
    assert errs and "axis reused" in errs[0]


def test_detect_raises_on_a_device_kind_without_known_peaks():
    """The generations table is the peaks table: a device that is not in
    it is an error, never a silent v5e (CPU stays ``cpu-sim``, for tests)."""
    import types

    import pytest

    def dev(kind, platform="tpu"):
        return types.SimpleNamespace(device_kind=kind, platform=platform)

    assert MachineSpec.detect([dev("TPU v5 lite")] * 4).generation == "v5e"
    assert MachineSpec.detect([dev("cpu", "cpu")]).generation == "cpu-sim"
    with pytest.raises(ValueError, match="TPU v9x"):
        MachineSpec.detect([dev("TPU v9x")])
    with pytest.raises(ValueError, match="not in TPU_GENERATIONS"):
        MachineSpec.detect([dev("NVIDIA H100", "gpu")])
