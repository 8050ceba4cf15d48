"""Which kernel an op runs: the forcing flags and their availability
predicates (kernels/registry.py), the attention ops' own `auto` rule,
and a search that prices nothing by implementation."""
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
    assert kreg.parse_forced("attention:ring, attention:xla") \
        == {"attention": "xla"}


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
                               "--kernel-impl", "attention:ring"])
    assert cfg.kernel_impls == "attention:flash,attention:ring"
    assert kreg.parse_forced(cfg.kernel_impls) == {"attention": "ring"}


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


def test_every_impl_is_available_to_self_attention_on_a_seq_axis():
    ctx = kreg.attention_ctx({"embed_dim": 64, "num_heads": 4},
                             128, 128, seq_degree=4)
    names = kreg.impl_names(kreg.ATTENTION)
    assert names == ["xla", "flash", "ring"]
    assert [kreg.get_impl(kreg.ATTENTION, n).available(ctx)
            for n in names] == [None, None, None]
    # latent attention has the first two only, whatever the mesh
    ctx = kreg.attention_ctx({}, 128, 128, seq_degree=4, latent=True)
    assert [kreg.get_impl(kreg.ATTENTION, n).available(ctx) is None
            for n in names] == [True, True, False]


def _attention_model(kind, cfg, causal=True):
    """One attention layer of ``kind`` (or the tiny latent model), not
    yet compiled: ``(ff, compile)``."""
    ff = FFModel(cfg)
    if kind == "multi-head":
        q = ff.create_tensor((2, 64, 64), name="q")
        ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4,
                               causal=causal)
        return ff, lambda: ff.compile(SGDOptimizer(0.01),
                                      "mean_squared_error", [])
    from flexflow_tpu.models.nlp import LatentMoEConfig, build_latent_moe
    out = build_latent_moe(ff, 2, 32, LatentMoEConfig.tiny())
    return ff, lambda: ff.compile(
        SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
        output_tensor=out)


@pytest.mark.parametrize("kind, seq, refusal", [
    # the one way to reach ring: forced, on a mesh with a sequence axis
    ("multi-head", 4, None),
    # the acceptance fixture's compile-time analog: no sequence axis
    ("multi-head", 0, "sequence axis"),
    ("latent", 0, "sequence axis"),
    # and the latent op has no ring path on any mesh
    ("latent", 4, "latent attention has no ring path"),
])
def test_forced_ring_by_attention_kind_and_mesh(kind, seq, refusal):
    """A forced ``ring`` is held to its predicate at compile: where it
    cannot run the failure is TYPED and names the op — never a silent
    fall back to the op's own rule — and where it can, the traced train
    step records ring for the layer and one step stays finite."""
    from flexflow_tpu.ffconst import OperatorType
    cfg = FFConfig()
    cfg.batch_size = 2
    cfg.only_data_parallel = True
    cfg.seq_parallel_degree = seq
    cfg.kernel_impls = "attention:ring"
    ff, compile_ = _attention_model(kind, cfg, causal=False)
    if refusal is not None:
        attn = next(l.name for l in ff.layers if l.op_type in (
            OperatorType.OP_MULTIHEAD_ATTENTION,
            OperatorType.OP_LATENT_ATTENTION))
        with pytest.raises(ValueError, match=refusal) as e:
            compile_()
        assert str(e.value).startswith(f"{attn}: forced kernel impl "
                                       f"attention:ring")
        return
    compile_()
    assert ff.dmesh.seq_degree == seq
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 64, 64)).astype(np.float32)
    hist = ff.fit(x=x, y=np.zeros((2, 64, 64), np.float32), epochs=1,
                  verbose=False)
    assert np.isfinite(hist[-1]["loss"])
    (attn,) = ff.executor.resolved_attention_impls
    assert ff.executor.resolved_attention_impls == {attn: "ring"}
    assert ff.strategy.kernel_impls == {"attention": "ring",
                                        attn: "ring"}


def test_forced_flash_on_causal_cross_attention_rejected_at_compile():
    """The flash predicate at compile: the kernel has no causal mask for
    ``q_len != kv_len``, and a forced choice says so with the op's
    name."""
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:flash"
    ff = FFModel(cfg)
    q = ff.create_tensor((2, 32, 64), name="q")
    kv = ff.create_tensor((2, 64, 64), name="kv")
    ff.multihead_attention(q, kv, kv, embed_dim=64, num_heads=4,
                           causal=True)
    with pytest.raises(ValueError, match="causal cross-attention") as e:
        ff.compile(SGDOptimizer(0.01), "identity", [])
    assert "forced kernel impl attention:flash" in str(e.value)


@pytest.mark.parametrize("where", ["forced spec", "imported strategy"])
def test_an_opt_update_kind_is_a_typed_error(where, tmp_path,
                                             monkeypatch):
    """There is no optimizer-update kernel to choose any more: a spec
    or a strategy file that still names the kind is refused with the
    kind's name, at compile and in the verifier, not ignored."""
    from flexflow_tpu.analysis.plan_verifier import PlanVerificationError
    from flexflow_tpu.search.serialization import save_strategy
    monkeypatch.delenv("FF_KERNEL_IMPL", raising=False)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    if where == "forced spec":
        cfg.kernel_impls = "attention:flash,opt_update:fused"
        ff, compile_ = _attention_model("multi-head", cfg)
        with pytest.raises(ValueError,
                           match="unknown kernel op 'opt_update'"):
            compile_()
        return
    ff, compile_ = _attention_model("multi-head", cfg)
    compile_()
    ff.strategy.kernel_impls = {"opt_update": "fused"}
    path = str(tmp_path / "strat.json")
    save_strategy(path, ff.strategy, {})
    cfg2 = FFConfig()
    cfg2.import_strategy_file = path
    ff2, compile2 = _attention_model("multi-head", cfg2)
    with pytest.raises(PlanVerificationError,
                       match="unknown kernel op kind 'opt_update'"):
        compile2()


@pytest.mark.parametrize("policy", ["off", "none"])
def test_off_ignores_a_forced_spec(policy, monkeypatch):
    """``off`` / ``none`` keep meaning "ignore what is forced", the
    environment's spec included: no plan, no record, the rule's path."""
    monkeypatch.setenv("FF_KERNEL_IMPL", "attention:flash")
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = policy
    ff, compile_ = _attention_model("multi-head", cfg)
    compile_()
    assert ff.strategy.kernel_impls == {}
    assert not hasattr(ff, "_kernel_record")
    assert ff.executor._kernel_impls == {}


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
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.kernel_impls = f"attention:{impl}"
    ff, compile_ = _attention_model(kind, cfg)
    compile_()
    assert ff.strategy.kernel_impls["attention"] == impl
    attn, lowered = _attention_layers_and_lowered_step(ff)
    assert attn and ff.executor.resolved_attention_impls \
        == dict.fromkeys(attn, impl)
    # every attention layer was held to the predicate and is in the
    # audit-visible record, of whichever kind
    assert {o["name"] for o in ff._kernel_record["ops"]} == attn
    if impl == "xla":
        assert "tpu_custom_call" not in lowered.as_text()


def _attention_layers_and_lowered_step(ff):
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.search.optimizer import _synth_batch
    step = ff.executor.make_train_step().__wrapped__
    lowered = step.lower(ff.params, ff.opt_state, ff.state, jnp.int32(0),
                         _synth_batch(ff))
    return {l.name for l in ff.layers if l.op_type in (
        OperatorType.OP_MULTIHEAD_ATTENTION,
        OperatorType.OP_LATENT_ATTENTION)}, lowered


@pytest.mark.parametrize("seq", [0, 4])
def test_calibration_on_and_nothing_forced_adopts_no_plan(seq,
                                                          monkeypatch):
    """With measured calibration asked for (``calibration_v2``) and
    nothing forced, on the 8-device mesh with and without a sequence
    axis: compile prices nothing and adopts no kernel plan, and the
    traced step runs what the op's own rule says (on the CPU platform:
    XLA; never ring, which only a forced choice reaches)."""
    monkeypatch.delenv("FF_KERNEL_IMPL", raising=False)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.calibration_v2 = "true"
    cfg.seq_parallel_degree = seq
    ff, compile_ = _attention_model("multi-head", cfg)
    compile_()
    assert ff.dmesh.seq_degree == max(seq, 1)
    assert ff.strategy.kernel_impls == {}
    assert ff.executor._kernel_impls == {}
    assert not hasattr(ff, "_kernel_record")
    attn, _ = _attention_layers_and_lowered_step(ff)
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    assert not mha._flash_enabled(None, 64, 64, 16, 16, causal=True)
    assert ff.executor.resolved_attention_impls \
        == dict.fromkeys(attn, "xla")


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
# the search: no implementation dimension
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


# the parent's totals for this graph with no kernel tier attached
# (PR 47: ``git archive`` of ea08918, the same lines), which is what the
# search priced in every benchmark cell: an attention layer costs
# ``op_cost`` whatever implementations exist
@pytest.mark.parametrize("search, total", [
    ("mcmc", 6.505876395604396e-05),
    ("unity", 1.6533643956043958e-05),
])
def test_the_search_prices_attention_with_op_cost(search, total):
    from flexflow_tpu.parallel.machine import DeviceMesh, MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    cfg = FFConfig()
    cfg.batch_size = 8
    ff = FFModel(cfg)
    q = ff.create_tensor((8, 256, 64), name="q")
    out = ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
    dmesh = DeviceMesh(MachineSpec(num_devices=8), seq=2)
    cm = OpCostModel(dmesh.spec)
    if search == "mcmc":
        from flexflow_tpu.search.mcmc import (StrategySimulator,
                                              data_parallel_assignment)
        sim = StrategySimulator(ff.layers, dmesh, cm)
        gc, entries = sim.evaluate_breakdown(
            data_parallel_assignment(ff.layers, dmesh, sim.options))
    else:
        from flexflow_tpu.search.unity import (GraphCostEvaluator,
                                               data_parallel_graph)
        g = data_parallel_graph(ff.layers, ff.input_tensors, [out], dmesh)
        gc, entries = GraphCostEvaluator(cm, dmesh).graph_cost_breakdown(g)
    assert gc.total == pytest.approx(total, rel=1e-12)
    (e,) = [e for e in entries
            if e["op_type"] == "OP_MULTIHEAD_ATTENTION"]
    priced = cm.op_cost(ff.layers[-1], {0: 8}, 1)
    assert (e["fwd_s"], e["bwd_s"]) \
        == (priced.forward_time, priced.backward_time)
    assert gc.compute == priced.forward_time + priced.backward_time
    assert "kernel_impl" not in e


def test_ring_plan_fits_an_envelope_the_unsharded_plan_fails():
    """What ring attention is for is memory: inside its shard_map every
    live attention tensor is a 1/seq-degree chunk. Same context, same
    mesh, an HBM budget between the two plans' static envelopes: the
    verifier rejects the forced-XLA plan with a typed memory finding and
    passes the ring plan."""
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
    # a window on the full forward is the kernels' own band arithmetic
    # (PR 51); what they cannot mask stays on XLA whoever asks
    (dict(q=4096, window=1024, causal=True), True, True),
    (dict(q=4096, window=1024, causal=True, impl="flash"), True, True),
    (dict(q=512, window=256, causal=True), True, False),
    (dict(q=512, kv=1024, window=256, causal=True, impl="flash"), True,
     False),
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
