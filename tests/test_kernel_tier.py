"""Searchable kernel tier (kernels/registry.py): forcing flags +
deprecation shim, availability predicates, fused-optimizer parity, and
the per-op impl dimension in the cost model."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.kernels import registry as kreg


# ---------------------------------------------------------------------------
# forcing: parse/resolve + the use_flash_attention deprecation shim
# ---------------------------------------------------------------------------

def test_parse_forced_rejects_typos():
    with pytest.raises(ValueError, match="unknown kernel op"):
        kreg.parse_forced("attenton:flash")
    with pytest.raises(ValueError, match="unknown impl"):
        kreg.parse_forced("attention:warp")
    with pytest.raises(ValueError, match="<op>:<impl>"):
        kreg.parse_forced("flash")
    assert kreg.parse_forced("auto") == {}
    assert kreg.parse_forced("attention:ring,opt_update:fused") \
        == {"attention": "ring", "opt_update": "fused"}


def test_use_flash_attention_shim_warns_and_forces():
    """The retired tri-state keeps working: "true"/"false" force the
    attention impl through a DeprecationWarning; "auto" forces nothing."""
    cfg = FFConfig()
    cfg.use_flash_attention = "true"
    with pytest.warns(DeprecationWarning, match="use_flash_attention"):
        assert kreg.resolve_forced(cfg) == {"attention": "flash"}
    cfg.use_flash_attention = "false"
    with pytest.warns(DeprecationWarning):
        assert kreg.resolve_forced(cfg) == {"attention": "xla"}
    cfg.use_flash_attention = "auto"
    assert kreg.resolve_forced(cfg) == {}


def test_forcing_precedence_shim_config_env(monkeypatch):
    """Later wins: shim < cfg.kernel_impls < FF_KERNEL_IMPL."""
    cfg = FFConfig()
    cfg.use_flash_attention = "true"
    cfg.kernel_impls = "attention:xla"
    with pytest.warns(DeprecationWarning):
        assert kreg.resolve_forced(cfg)["attention"] == "xla"
    monkeypatch.setenv("FF_KERNEL_IMPL", "attention:ring")
    with pytest.warns(DeprecationWarning):
        assert kreg.resolve_forced(cfg)["attention"] == "ring"


def test_kernel_impl_cli_flag_accumulates():
    cfg = FFConfig.parse_args(["--kernel-impl", "attention:flash",
                               "--kernel-impl", "opt_update:fused"])
    assert kreg.parse_forced(cfg.kernel_impls) \
        == {"attention": "flash", "opt_update": "fused"}


# ---------------------------------------------------------------------------
# availability predicates
# ---------------------------------------------------------------------------

def test_ring_predicate_requires_seq_axis_and_divisibility():
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             128, 128, seq_degree=0)
    assert "sequence axis" in kreg.get_impl("attention", "ring") \
        .available(ctx)
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             130, 130, seq_degree=4)
    assert "divisible" in kreg.get_impl("attention", "ring") \
        .available(ctx)
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             128, 128, seq_degree=4)
    assert kreg.get_impl("attention", "ring").available(ctx) is None


def test_flash_predicate_rejects_causal_cross_attention():
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4,
                              "causal": True}, 64, 128)
    assert kreg.get_impl("attention", "flash").available(ctx)
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             64, 128)
    assert kreg.get_impl("attention", "flash").available(ctx) is None


def test_available_impls_default_first():
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             128, 128, seq_degree=4)
    names = kreg.available_impls(kreg.ATTENTION, ctx)
    assert names[0] == "xla" and set(names) == {"xla", "flash", "ring"}


def test_forced_ring_without_seq_axis_rejected_at_compile():
    """The acceptance fixture's compile-time analog: a forced-`ring`
    plan on a mesh with no sequence axis fails TYPED with the op
    attributed — never silently falls back to xla."""
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:ring"
    ff = FFModel(cfg)
    q = ff.create_tensor((2, 64, 64), name="q")
    ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    with pytest.raises(ValueError, match="sequence axis"):
        ff.compile(SGDOptimizer(0.01), "identity", [])


def test_forced_flash_plans_and_trains():
    """Forced attention:flash lands in the plan, the audit-visible
    kernel record, and the executor — and one train step stays finite."""
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:flash"
    ff = FFModel(cfg)
    q = ff.create_tensor((2, 64, 64), name="q")
    ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    attn = [l.name for l in ff.layers
            if l.op_type.name == "OP_MULTIHEAD_ATTENTION"][0]
    assert ff.strategy.kernel_impls[attn] == "flash"
    assert ff.executor._kernel_impls[attn] == "flash"
    rec = ff._kernel_record
    assert rec["policy"] == "attention:flash"
    op = next(o for o in rec["ops"] if o["name"] == attn)
    assert op["impl"] == "flash" and op["forced"]


# ---------------------------------------------------------------------------
# kernel_impls serialization round trip
# ---------------------------------------------------------------------------

def test_kernel_impls_roundtrip_through_strategy_file(tmp_path):
    from flexflow_tpu.search.serialization import (load_strategy,
                                                   save_strategy)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:flash"
    ff = FFModel(cfg)
    q = ff.create_tensor((2, 64, 64), name="q")
    ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    path = str(tmp_path / "strat.json")
    save_strategy(path, ff.strategy, {})
    st = load_strategy(path, ff.layers, ff.dmesh)
    assert st.kernel_impls == dict(ff.strategy.kernel_impls)


# ---------------------------------------------------------------------------
# fused optimizer update: bit-parity with AdamOptimizer.update
# ---------------------------------------------------------------------------

def test_fused_adam_update_matches_unfused_bitwise():
    from flexflow_tpu.runtime.optimizers import (AdamOptimizer,
                                                 fused_adam_tree_update)
    opt = AdamOptimizer(alpha=1e-3, beta1=0.9, beta2=0.999,
                        weight_decay=0.01, epsilon=1e-8)
    rng = np.random.default_rng(0)
    # ragged leaf sizes exercise the kernel's lane padding
    params = {"w1": jnp.asarray(rng.standard_normal((33, 17)),
                                jnp.float32),
              "w2": jnp.asarray(rng.standard_normal((5,)), jnp.float32)}
    grads = jax.tree.map(
        lambda w: jnp.asarray(rng.standard_normal(w.shape), w.dtype),
        params)
    state = opt.init_state(params)
    step = jnp.asarray(3, jnp.int32)
    p_ref, s_ref = opt.update(params, grads, state, step)
    p_fus, s_fus = fused_adam_tree_update(opt, params, grads, state,
                                          step)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_fus[k]),
                                   np.asarray(p_ref[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
        np.testing.assert_allclose(np.asarray(s_fus["m"][k]),
                                   np.asarray(s_ref["m"][k]),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(s_fus["v"][k]),
                                   np.asarray(s_ref["v"][k]),
                                   rtol=1e-6, atol=1e-7)


def test_forced_fused_update_runs_even_when_the_guard_built_the_step(
        monkeypatch):
    """The floor guard compiles and runs the train step before the
    kernel plan exists. Found on the chip (where the guard is on by
    default): the adopted executor kept replaying that unfused trace,
    so a forced ``opt_update:fused`` never ran."""
    import dataclasses

    from flexflow_tpu import AdamOptimizer
    from flexflow_tpu.kernels import registry as kreg
    from flexflow_tpu.runtime import optimizers as opt_mod
    monkeypatch.setitem(
        kreg.REGISTRY[kreg.OPT_UPDATE], "fused", dataclasses.replace(
            kreg.REGISTRY[kreg.OPT_UPDATE]["fused"],
            predicate=lambda ctx: None))   # let the CPU interpret it
    calls = []
    real = opt_mod.fused_adam_tree_update
    monkeypatch.setattr(
        opt_mod, "fused_adam_tree_update",
        lambda *a, **k: calls.append(1) or real(*a, **k))
    cfg = FFConfig()
    cfg.batch_size = 8
    cfg.search_budget = 2
    cfg.search_floor_guard = "true"
    cfg.kernel_impls = "opt_update:fused"
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 64), name="x")
    out = ff.dense(ff.dense(x, 64), 10)
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    assert "adopted" in ff._floor_guard_record and not calls
    rng = np.random.default_rng(0)
    ff.fit(x=rng.normal(size=(8, 64)).astype(np.float32),
           y=rng.integers(0, 10, size=(8, 1)).astype(np.int32),
           epochs=1, verbose=False)
    assert calls, "the step that trained never traced the fused update"


# ---------------------------------------------------------------------------
# cost model: the per-op impl dimension
# ---------------------------------------------------------------------------

def _attn_layer(b=4, s=2048, e=512, h=8):
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.tensor import Tensor
    from flexflow_tpu.ffconst import OperatorType
    t = Tensor((b, s, e), "float32", name="x")
    l = Layer(OperatorType.OP_MULTIHEAD_ATTENTION, "attn0", [t, t, t],
              params={"embed_dim": e, "num_heads": h})
    l.outputs = [Tensor((b, s, e), "float32", name="attn0_out")]
    return l


def test_op_cost_with_impl_scores_and_records_argmin():
    from flexflow_tpu.parallel.machine import DeviceMesh, MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    dm = DeviceMesh(MachineSpec.detect(), seq=4)
    cm = OpCostModel(dm.spec)
    layer = _attn_layer()
    base = cm.op_cost(layer, {}, 1)
    # no tier attached: op_cost_with_impl is op_cost, nothing recorded
    assert cm.op_cost_with_impl(layer, {}, 1).forward_time \
        == base.forward_time
    assert cm.last_kernel_impl is None
    cm.attach_kernel_tier(dm)
    scored = cm.op_cost_with_impl(layer, {}, 1)
    assert cm.last_kernel_impl in ("xla", "flash", "ring")
    assert cm.kernel_choice["attn0"] == cm.last_kernel_impl
    assert scored.forward_time + scored.backward_time \
        <= base.forward_time + base.backward_time + 1e-12
    # forcing pins the argmin
    cm.attach_kernel_tier(dm, forced={"attention": "xla"})
    cm.op_cost_with_impl(layer, {}, 1)
    assert cm.last_kernel_impl == "xla"


def test_kernel_impl_cost_orders_long_context():
    """At long context the analytic tier must order ring < flash < xla
    (the score-matrix traffic xla re-reads dominates; ring amortizes it
    over the seq axis)."""
    from flexflow_tpu.parallel.machine import DeviceMesh, MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    dm = DeviceMesh(MachineSpec.detect(), seq=4)
    cm = OpCostModel(dm.spec)
    layer = _attn_layer(b=4, s=8192, e=512, h=8)
    t = {}
    for name in ("xla", "flash", "ring"):
        m = cm.kernel_impl_cost(layer, "attention", name, {}, 1,
                                seq_degree=4 if name == "ring" else 0)
        t[name] = m.forward_time + m.backward_time
    assert t["ring"] < t["flash"] < t["xla"]
