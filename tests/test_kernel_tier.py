"""Searchable kernel tier (kernels/registry.py): forcing flags,
availability predicates, fused-optimizer parity, and the per-op impl
dimension in the cost model."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.kernels import registry as kreg


# ---------------------------------------------------------------------------
# forcing: parse/resolve
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


def test_forcing_precedence_config_env(monkeypatch):
    """Later wins: cfg.kernel_impls < FF_KERNEL_IMPL; nothing set forces
    nothing."""
    monkeypatch.delenv("FF_KERNEL_IMPL", raising=False)
    cfg = FFConfig()
    assert kreg.resolve_forced(cfg) == {}
    cfg.kernel_impls = "attention:xla"
    assert kreg.resolve_forced(cfg) == {"attention": "xla"}
    monkeypatch.setenv("FF_KERNEL_IMPL", "attention:ring")
    assert kreg.resolve_forced(cfg) == {"attention": "ring"}


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


@pytest.mark.parametrize("impl", ["flash", "xla"])
@pytest.mark.parametrize("kind", ["multi-head", "latent"])
def test_forced_attention_reaches_every_attention_op(kind, impl):
    """``kernel_impls = "attention:<impl>"`` is the one way to force a
    path, so it has to reach both attention op kinds: the plan carries
    the kind key, every attention layer of the traced train step
    records the forced impl, and a step forced onto XLA holds no Mosaic
    call."""
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.search.optimizer import _synth_batch
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = f"attention:{impl}"
    ff = FFModel(cfg)
    if kind == "multi-head":
        q = ff.create_tensor((2, 64, 64), name="q")
        ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4,
                               causal=True)
        ff.compile(SGDOptimizer(0.01), "identity", [])
    else:
        from flexflow_tpu.models.nlp import (LatentMoEConfig,
                                             build_latent_moe)
        out = build_latent_moe(ff, 2, 32, LatentMoEConfig.tiny())
        ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy",
                   [], output_tensor=out)
    assert ff.strategy.kernel_impls["attention"] == impl
    step = ff.executor.make_train_step().__wrapped__
    lowered = step.lower(ff.params, ff.opt_state, ff.state, jnp.int32(0),
                         _synth_batch(ff))
    attn = {l.name for l in ff.layers if l.op_type in (
        OperatorType.OP_MULTIHEAD_ATTENTION,
        OperatorType.OP_LATENT_ATTENTION)}
    assert attn and ff.executor.resolved_attention_impls \
        == dict.fromkeys(attn, impl)
    if impl == "xla":
        assert "tpu_custom_call" not in lowered.as_text()


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


def test_ring_plan_fits_an_envelope_the_unsharded_plan_fails():
    """What ring attention is for is memory: inside its shard_map every
    live attention tensor is a 1/seq-degree chunk. Same context, same
    mesh, an HBM budget between the two plans' static envelopes: the
    verifier rejects the forced-XLA plan with a typed memory finding and
    passes the ring plan (the smoke, tools/kernel_tier_smoke.py, shows
    the searched tier adopting ring here and the plan training)."""
    from flexflow_tpu.analysis.plan_verifier import (memory_envelope,
                                                     verify_plan)
    b, s, e, h = 4, 2048, 512, 8

    def build(impl):
        cfg = FFConfig()
        cfg.batch_size = b
        cfg.only_data_parallel = True
        cfg.seq_parallel_degree = 4
        cfg.kernel_impls = f"attention:{impl}"
        ff = FFModel(cfg)
        q = ff.create_tensor((b, s, e), name="q")
        ff.multihead_attention(q, q, q, embed_dim=e, num_heads=h)
        ff.compile(SGDOptimizer(0.01), "mean_squared_error", [])
        return ff

    def envelope(ff):
        return memory_envelope(
            ff.strategy, ff.executor.program.layers,
            dict(ff.dmesh.axis_sizes), ff.optimizer)["envelope_bytes"]

    ff_ring, ff_xla = build("ring"), build("xla")
    assert ff_ring.dmesh.seq_degree == 4
    env_ring, env_xla = envelope(ff_ring), envelope(ff_xla)
    assert env_ring < env_xla
    hbm = (env_ring + env_xla) / 2.0

    def report(ff):
        return verify_plan(
            ff.strategy, ff.executor.program.layers,
            machine_spec=ff.dmesh.spec, graph_inputs=ff.graph_inputs,
            optimizer=ff.optimizer, hbm_bytes=hbm,
            context="ring envelope test")

    assert report(ff_ring).ok()
    unsharded = report(ff_xla)
    assert not unsharded.ok()
    assert any(f.check == "memory" for f in unsharded.errors)


# ---------------------------------------------------------------------------
# attention with nothing forced and no plan: the `auto` rule, held to the
# rows of the chip's table (PERF.md section 6, PR 30; flash's share of
# XLA's time for one layer's forward + backward beside each row)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q_len, kv_len, d, dv, rate, want", [
    (512, 512, 64, 64, 0.1, "flash"),     # cell 1's layer: 0.38
    (512, 512, 128, 128, 0.1, "flash"),   # 0.42
    (256, 256, 64, 64, 0.1, "flash"),     # the smallest row that wins in
    (256, 256, 128, 128, 0.1, "flash"),   # every column: 0.70, 0.67
    (384, 384, 64, 64, 0.1, "flash"),     # 0.47
    (768, 768, 128, 128, 0.1, "flash"),   # 0.45-0.48
    (128, 128, 64, 64, 0.1, "xla"),       # one below it: 1.23
    (128, 128, 128, 128, 0.1, "xla"),     # 0.97-0.98, inside the noise
    (1024, 1024, 64, 64, 0.1, "flash"),   # 0.28-0.30
    (1024, 1024, 64, 64, 0.0, "flash"),   # cell 2's length: 0.60-0.65
    (1024, 1024, 128, 128, 0.0, "flash"),
    (4096, 4096, 192, 128, 0.0, "flash"),  # cell 3, as before
    (768, 768, 64, 64, 0.0, "xla"),       # one below 1024: 0.97-1.02
    (512, 512, 128, 128, 0.0, "xla"),     # 1.19, so 512 does not move
    (512, 512, 64, 64, 0.0, "xla"),       # though this column wins: 0.92
    (256, 256, 64, 64, 0.0, "xla"),       # 1.49
    (128, 128, 64, 64, 0.0, "xla"),       # 2.63
    (197, 197, 64, 64, 0.1, "xla"),       # timed at one head size only
    (200, 200, 64, 64, 0.1, "xla"),       # not covered: stays
    (512, 512, 32, 32, 0.1, "xla"),       # head sizes the table has not
    (512, 512, 256, 256, 0.1, "xla"),
    (512, 512, 192, 128, 0.1, "xla"),
    (256, 512, 64, 64, 0.1, "xla"),       # cross-attention: not timed
    (64, 1024, 64, 64, 0.0, "flash"),     # as before: 1024 on either side
])
def test_the_auto_rule_says_what_the_chips_table_says(q_len, kv_len, d, dv,
                                                      rate, want):
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    took = mha.auto_takes_flash(q_len, kv_len, d, dv, rate)
    assert ("flash" if took else "xla") == want


def _ctx(plan=None):
    from flexflow_tpu.ops.registry import EmitCtx
    ctx = EmitCtx(training=True, config=FFConfig())
    ctx.kernel_impls = plan
    return ctx


@pytest.mark.parametrize("kw, compiled_backend, want", [
    # interpret mode (the CPU platform): XLA whatever the shape
    (dict(q=512, rate=0.1), False, False),
    (dict(q=4096), False, False),
    # a backend that compiles the kernel: the rule
    (dict(q=512, rate=0.1), True, True),
    (dict(q=512), True, False),
    # a plan's impl decides either way, on either backend; `ring` is
    # not this call's to take, so the rule answers
    (dict(q=16, impl="flash"), False, True),
    (dict(q=4096, impl="xla"), True, False),
    (dict(q=4096, impl="ring"), True, True),
    (dict(q=16, impl="ring"), True, False),
    # what the kernel cannot mask stays on XLA whoever asks
    (dict(q=4096, window=1024, causal=True), True, False),
    (dict(q=4096, window=1024, causal=True, impl="flash"), True, False),
    (dict(q=512, kv=1024, causal=True, impl="flash"), True, False),
    (dict(q=512, kv=1024), True, True),
])
def test_flash_enabled_backend_forcing_and_masks(monkeypatch, kw,
                                                 compiled_backend, want):
    from flexflow_tpu.kernels import _interpret
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    monkeypatch.setattr(_interpret, "pallas_interpret",
                        lambda: not compiled_backend)
    q = kw["q"]
    got = mha._flash_enabled(
        kw.get("impl"), q, kw.get("kv", q), 64, 64,
        kw.get("rate", 0.0), causal=kw.get("causal", False),
        window=kw.get("window", 0))
    assert got is want


def _dropout_layer(b=1, s=256, e=64, h=1, rate=0.1):
    layer = _attn_layer(b=b, s=s, e=e, h=h)
    layer.params["dropout"] = rate
    return layer


def _emit_attention(layer, ctx, seed=0):
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
    op = MultiHeadAttentionOp()
    b, s, e = layer.inputs[0].shape
    rng = np.random.default_rng(seed)
    specs = op.weights(layer.params, [(b, s, e)] * 3,
                       [layer.inputs[0].dtype] * 3)
    weights = {w.name: jnp.asarray(rng.normal(size=w.shape) * e ** -0.5,
                                   jnp.float32) for w in specs}
    x = jnp.asarray(rng.normal(size=(b, s, e)), jnp.float32)
    ctx.resolved_impls = {}
    (out,) = op.emit(layer.params, [x, x, x], weights, ctx, layer.name)
    return np.asarray(out), ctx.resolved_impls[layer.name]


def _step_rngs(executor_cls, layer, step):
    """The keys the executor gives a step's layers, from its own code."""
    import types
    stand_in = types.SimpleNamespace(
        seed=0, program=types.SimpleNamespace(layers=[layer]))
    return executor_cls._rngs_for_step(stand_in, step)


@pytest.mark.parametrize("plan, compiled_backend, want", [
    (None, True, "flash"),                 # the rule, at a length it moves
    (None, False, "xla"),
    ({"attention": "xla"}, True, "xla"),   # a plan's impl wins over auto,
    ({"attn0": "flash"}, False, "flash"),  # by kind and by layer name
])
def test_a_plans_impl_wins_over_auto_in_emit(monkeypatch, plan,
                                             compiled_backend, want):
    from flexflow_tpu.executor import Executor
    from flexflow_tpu.kernels import _interpret
    monkeypatch.setattr(_interpret, "pallas_interpret",
                        lambda: not compiled_backend)
    layer = _dropout_layer()
    ctx = _ctx(plan=plan)
    ctx.rngs = _step_rngs(Executor, layer, 0)
    assert _emit_attention(layer, ctx)[1] == want


def test_auto_hands_the_kernel_the_rate_and_a_seed_from_the_layers_key(
        monkeypatch):
    """With the backend check stubbed (the kernel itself stays in
    interpret mode), `auto` at a length the table moves calls
    ``flash_attention`` with the layer's dropout rate and a seed drawn
    from ``ctx.rng_for(name)``: one step index gives one mask (what the
    benchmark's ``loss_fell`` relies on), another index another."""
    import flexflow_tpu.kernels as kernels
    from flexflow_tpu.executor import Executor
    from flexflow_tpu.kernels import _interpret
    monkeypatch.setattr(_interpret, "pallas_interpret", lambda: False)
    calls = []
    real = kernels.flash_attention

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(kernels, "flash_attention", spy)
    layer = _dropout_layer()

    def run(step):
        ctx = _ctx()
        ctx.rngs = _step_rngs(Executor, layer, step)
        out, impl = _emit_attention(layer, ctx)
        assert impl == "flash"
        want = jax.random.randint(ctx.rng_for(layer.name), (), 0,
                                  2 ** 31 - 1, jnp.int32)
        assert calls[-1]["dropout_rate"] == 0.1
        assert int(calls[-1]["dropout_seed"]) == int(want)
        return out, int(want)

    (a, seed_a), (again, _), (b, seed_b) = run(0), run(0), run(1)
    assert len(calls) == 3
    np.testing.assert_array_equal(a, again)
    assert seed_a != seed_b and not np.array_equal(a, b)
    # and the mask does something: no dropout gives a third answer
    layer.params["dropout"] = 0.0
    ctx = _ctx(plan={"attention": "flash"})
    assert not np.array_equal(_emit_attention(layer, ctx)[0], a)
    assert calls[-1]["dropout_rate"] == 0.0
    assert calls[-1]["dropout_seed"] is None


def test_an_eval_trace_does_not_overwrite_the_train_steps_record(
        monkeypatch):
    """At 256 positions a train step (dropout) takes the kernels and an
    eval step (no mask drawn) XLA: ``resolved_attention_impls`` keeps
    what the train step runs whichever is traced last (``chip_smoke.py``
    leg A reads it after ``fit`` and ``eval``), and an eval-only model
    still gets its record."""
    from flexflow_tpu.executor import Executor
    from flexflow_tpu.kernels import _interpret
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    monkeypatch.setattr(_interpret, "pallas_interpret", lambda: False)
    layer = _dropout_layer()
    record = {}
    for training, want in ((False, "xla"), (True, "flash"), (False, "flash")):
        ctx = _ctx()
        ctx.training = training
        ctx.rngs = _step_rngs(Executor, layer, 0)
        ctx.resolved_impls = record
        mha._note_impl(ctx, layer.name, "flash" if mha._flash_enabled(
            None, 256, 256, 64, 64, 0.1 if training else 0.0) else "xla")
        assert record == {layer.name: want}
