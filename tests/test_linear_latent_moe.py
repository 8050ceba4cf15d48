"""The hybrid linear-attention / latent-attention decoder with sparse
experts (gated delta-rule layers among NoPE latent-attention layers,
sigmoid-routed experts with a shared one) against its plain reference
(``benchmarks/reference/linear_latent_moe_ref.py``), at a small size on
the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``. The two share no algebra in the recurrence:
the program solves a triangular system a chunk and scans over chunks,
the reference walks the tokens. ``TOL`` = 2e-4 relative to the largest
entry is some forty times what they read (1e-6 to 5e-6; with decays of
5 a token the gradient of ``g`` reads 5e-5) and a thousand times under
a decay applied after the write, a mask off by one row or a state handed
over one chunk late, each of which moves the result by 1e-1 or more.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.analysis.plan_verifier import verify_plan
from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.models.nlp import (JoyAIFlashRankConfig,
                                     KimiLinearRankConfig, LatentMoEConfig,
                                     build_latent_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.nn_ops import LatentAttentionOp
from flexflow_tpu.ops.recurrent_ops import (GatedDeltaRuleOp,
                                            gated_delta_rule)
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from flexflow_tpu.search import opshard
from rank_family import B, close, f32_ctx

ref = rf.reference("linear_latent_moe_ref")
older = rf.reference("latent_moe_ref")
S = 40
H, D = 3, 8
build = functools.partial(rf.build, KimiLinearRankConfig, build_latent_moe,
                          seq=S, attention="xla")
data = functools.partial(rf.data, seq=S)


tiny, tiny_step = rf.fixtures(build, data)


# ----------------------------------------------------------------------
# the recurrence alone
# ----------------------------------------------------------------------
def recurrence_inputs(length, decay=0.3, seed=0):
    """q and k of length one a head, v, a log-decay in
    ``-decay x (0.1, 1)`` a channel, a step size in (0.05, 0.95)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, k = normal(B, length, H, D), normal(B, length, H, D)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * rng.uniform(0.1, 1, (B, length, H, D)).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, (B, length, H)).astype(np.float32)
    return [jnp.asarray(a) for a in (q, k, normal(B, length, H, D), g,
                                     beta)]


@functools.partial(jax.jit, static_argnames="chunk")
def chunked(q, k, v, g, beta, chunk):
    """The op's recurrence takes heads before positions, the reference
    positions before heads."""
    out, least = gated_delta_rule(
        *(jnp.swapaxes(a, 1, 2) for a in (q, k, v, g, beta)), chunk=chunk)
    return jnp.swapaxes(out, 1, 2), least


def both_ways(args, chunk):
    """Values and the five gradients of a weighted sum of the outputs,
    chunked and token by token."""
    mix = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(chunked(*a, chunk=chunk)[0] * mix),
            argnums=range(5)))(*args)
        want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(ref.delta_rule_by_token(*a) * mix),
            argnums=range(5)))(*args)
        out = chunked(*args, chunk=chunk)
    return got, want, out


@pytest.mark.parametrize("length", [1, 5, 32, 33, 103])
def test_the_chunked_recurrence_is_the_token_by_token_one(length):
    """Lengths 1, 5, one chunk, one chunk + 1 and three chunks + 7, in
    chunks of 32 (two sub-blocks of 16, so a pair of different
    sub-blocks and a sub-block against itself both occur): the outputs
    and the gradients of q, k, v, g and beta."""
    args = recurrence_inputs(length)
    (got, d_got), (want, d_want), (out, _) = both_ways(args, 32)
    with jax.default_matmul_precision("highest"):
        close(out, jax.jit(ref.delta_rule_by_token)(*args))
    close(got, want)
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        # (one token decays a state that is still zero)
        assert float(jnp.max(jnp.abs(b))) > 0 or (name, length) == ("g", 1)
        close(a, b)


@pytest.mark.parametrize("chunk", [8, 32, 64, 128])
def test_the_chunk_size_does_not_change_the_result(chunk):
    """8 is one sub-block of its own, 128 is longer than the 100
    tokens."""
    args = recurrence_inputs(100, seed=3)
    with jax.default_matmul_precision("highest"):
        close(chunked(*args, chunk=chunk)[0],
              chunked(*args, chunk=16)[0], 2e-5)


@pytest.mark.parametrize("decay", [5.0, 20.0])
def test_decays_past_float32s_exponent_stay_finite_and_right(decay):
    """``g`` down to -5 (and -20) a token: inside a chunk of 64 the
    running sum passes -190 (-780), and ``exp(-G)`` is not a float32
    beyond 88. Every exponent the op takes is a difference <= 0, so
    values and gradients are finite and the reference's."""
    args = recurrence_inputs(150, decay=decay, seed=4)
    (got, d_got), (want, d_want), (out, least) = both_ways(args, 64)
    assert float(least) < -150 and not np.isfinite(np.exp(
        np.float32(-float(least))))
    assert bool(jnp.all(jnp.isfinite(out)))
    close(got, want)
    for a, b in zip(d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a)))
        close(a, b, 5e-4)


@pytest.mark.parametrize("t", [0, 15, 16, 40])
def test_an_output_does_not_move_when_later_inputs_change(t):
    """Within a sub-block, across sub-blocks and across chunks."""
    args = recurrence_inputs(70, seed=5)
    other = recurrence_inputs(70, seed=6)
    moved = [jnp.concatenate([a[:, :t + 1], b[:, t + 1:]], 1)
             for a, b in zip(args, other)]
    base = chunked(*args, chunk=32)[0]
    after = chunked(*moved, chunk=32)[0]
    np.testing.assert_array_equal(np.asarray(base[:, :t + 1]),
                                  np.asarray(after[:, :t + 1]))
    assert float(jnp.max(jnp.abs(base[:, t + 1:] - after[:, t + 1:]))) > 0


def test_the_log_decay_counter_reads_a_hand_count():
    """One channel decays by 0.5 a token, the rest by 0.1: in chunks of
    32 over 70 tokens the running sum restarts at each chunk, so its
    least value is 32 x -0.5, and in chunks of 64 it is 64 x -0.5."""
    q, k, v, g, beta = recurrence_inputs(70)
    g = jnp.full_like(g, -0.1).at[1, :, 2, 5].set(-0.5)
    for chunk, least in ((32, -16.0), (64, -32.0)):
        assert float(chunked(q, k, v, g, beta, chunk=chunk)[1]) \
            == pytest.approx(least, rel=1e-6)


# ----------------------------------------------------------------------
# the layer
# ----------------------------------------------------------------------
KDA_PARAMS = {"num_heads": H, "head_dim": D, "taps": 4, "eps": 1e-5,
              "chunk": 32}


def kda_weights(e=24, seed=0):
    op = GatedDeltaRuleOp()
    rng = np.random.default_rng(seed)
    w = {}
    for s in op.weights(KDA_PARAMS, [(B, S, e)], [DataType.DT_FLOAT]):
        w[s.name] = jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                                * (1.0 if s.name == "o_norm" else 0.4))
    # a head's A in (1, 16), as the layer's initialisation draws it
    w["A_log"] = jnp.log(jnp.asarray(rng.uniform(1, 16, (H,)),
                                     jnp.float32))
    return w


@pytest.mark.parametrize("length", [1, 5, 32, 33, 103])
def test_the_layer_is_the_equations_values_and_every_gradient(length):
    op, w = GatedDeltaRuleOp(), kda_weights()
    u = jnp.asarray(np.random.default_rng(2).normal(
        size=(B, length, 24)).astype(np.float32))
    mix = jnp.asarray(np.random.default_rng(3).normal(
        size=(B, length, 24)).astype(np.float32))

    def program(u, w):
        ctx = f32_ctx()
        (y,) = op.emit(KDA_PARAMS, [u], w, ctx, "kda")
        return jnp.sum(y * mix), (y, ctx.counters)

    def reference(u, w):
        with jax.default_matmul_precision("highest"):
            y = ref.kda(u, w, {"rms_norm_eps": 1e-5})
        return jnp.sum(y * mix), y

    (got, (y, counters)), d_got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(u, w)
    (want, want_y), d_want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(u, w)
    close(y, want_y)
    close(got, want)
    close(d_got[0], d_want[0])
    assert set(d_got[1]) == set(ref.KDA)
    decays = ("wf_a", "wf_b", "A_log", "dt_bias")
    for name in ref.KDA:
        # (one token decays a state that is still zero)
        assert float(jnp.max(jnp.abs(d_want[1][name]))) > 0 \
            or (length == 1 and name in decays), name
        close(d_got[1][name], d_want[1][name])
    assert float(counters["kda.scans"]) == 1
    with jax.default_matmul_precision("highest"):
        g = jax.jit(lambda u, w: ref.kda_inputs(u, w)[3])(u, w)
    pad = -length % 32
    sums = jnp.cumsum(jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0))
                              ).reshape(B, -1, 32, H, D), axis=2)
    assert float(counters["kda.log_decay_min"]) == pytest.approx(
        float(jnp.min(sums)), rel=1e-5)


def test_the_layers_initialisation_is_the_familys():
    """A in (1, 16) a head, softplus(dt_bias) in (1e-3, 1e-1) a channel,
    taps Glorot over (4, 4); a cost row by the recurrent form."""
    ff = FFModel(FFConfig())
    build_latent_moe(ff, 1, 16, KimiLinearRankConfig.tiny())
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=ff.layers[-1].outputs[0])
    w = ff.params["kda_0"]
    a = np.exp(np.asarray(w["A_log"]))
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"])))
    assert a.shape == (4,) and 1 < a.min() and a.max() < 16
    assert dt.shape == (4, 8) and 1e-3 < dt.min() and dt.max() < 1e-1
    assert float(np.abs(w["conv_k"]).max()) <= np.sqrt(6 / 8)
    assert np.all(np.asarray(w["o_norm"]) == 1)
    layer = next(l for l in ff.layers if l.name == "kda_0")
    tokens, e, h, d = 16, 64, 4, 8
    proj = 4 * e * h * d + 2 * (e * d + d * h * d) + e * h
    assert GatedDeltaRuleOp().flops(
        layer.params, [(1, 16, e)], [(1, 16, e)]) == tokens * (
        2 * proj + 3 * 9 * h * d + 7 * h * d * d)


def test_there_is_no_decode_path():
    ctx = f32_ctx(False)
    ctx.kv_mode = "prefill"
    u = jnp.zeros((B, 4, 24))
    with pytest.raises(NotImplementedError, match="decode"):
        GatedDeltaRuleOp().emit(KDA_PARAMS, [u], kda_weights(), ctx, "kda")


def test_the_layer_is_sharded_by_batch_and_head_not_sequence(tiny):
    ff, _, _ = tiny
    layer = next(l for l in ff.layers
                 if l.op_type == OperatorType.OP_GATED_DELTA_RULE)
    kinds = [(o.kind, o.out_dim, dict(o.weight_dims))
             for o in opshard.options_for(layer)]
    assert kinds == [("sample", 0, {}), ("parameter", -1, {
        "wq": 1, "wk": 1, "wv": 1, "conv_q": 0, "conv_k": 0, "conv_v": 0,
        "wf_b": 1, "A_log": 0, "dt_bias": 0, "wb": 1, "wg_b": 1, "wo": 0})]
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    axis = next(iter(ff.dmesh.axis_sizes))
    for spec, ok in ((P(axis, None, None), True),
                     (P(None, axis, None), False)):
        st = ShardingStrategy(ff.dmesh)
        st.set_op(layer.name, [spec], {})
        report = verify_plan(st, ff.layers)
        state = [f for f in report.errors if "state" in f.message]
        assert (not state) == ok, report.errors


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_a_sharded_layer_is_the_unsharded_one(by):
    """8 sequences, or 8 heads, one a device of the CPU mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    params = dict(KDA_PARAMS, num_heads=8)
    op = GatedDeltaRuleOp()
    rng = np.random.default_rng(7)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * 0.4)
         for s in op.weights(params, [(8, S, 24)], [DataType.DT_FLOAT])}
    u = jnp.asarray(rng.normal(size=(8, S, 24)).astype(np.float32))

    def layer(u, w):
        return op.emit(params, [u], w, f32_ctx(), "kda")[0]

    want = jax.jit(layer)(u, w)
    mesh = Mesh(np.array(jax.devices()), ("x",))
    option = opshard.options_for(type("L", (), {
        "op_type": OperatorType.OP_GATED_DELTA_RULE,
        "outputs": [type("T", (), {"shape": (8, S, 24)})]})())[1]

    def put(x, dim):
        spec = [None] * x.ndim
        if dim is not None:
            spec[dim] = "x"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    if by == "batch":
        got = jax.jit(layer)(put(u, 0), {n: put(x, None)
                                         for n, x in w.items()})
    else:
        dims = dict(option.weight_dims)
        got = jax.jit(layer)(put(u, None), {n: put(x, dims.get(n))
                                            for n, x in w.items()})
    close(got, want, 2e-5)


# ----------------------------------------------------------------------
# latent attention without a q latent, without the rotation
# ----------------------------------------------------------------------
@pytest.mark.parametrize("q_rank", [None, 24])
@pytest.mark.parametrize("rope", [True, False])
def test_latent_attention_with_each_part_on_and_off(q_rank, rope):
    """4 heads of 16 + 8 / 16 over a kv latent of 32. Without the
    rotation the op is the reference's NoPE attention (no q latent) or
    the older reference's at positions all zero, where its rotation
    turns nothing (a q latent); with it, the older reference's, or, with
    no q latent, itself unrotated once the positions are zero and
    something else once they are not."""
    e, heads = 48, 4
    params = dict(num_heads=heads, q_rank=q_rank, kv_rank=32, nope_dim=16,
                  rope_dim=8, v_dim=16, rope_theta=10000.0, eps=1e-5)
    if not rope:
        params["rope"] = False
    op = LatentAttentionOp()
    specs = {s.name: s.shape for s in op.weights(
        params, [(B, S, e), (B, S)], [DataType.DT_FLOAT, DataType.DT_INT32])}
    assert set(specs) == set(ref.LATENT if q_rank is None else older.ATTN)
    rng = np.random.default_rng(11)
    w = {n: jnp.asarray(rng.normal(size=s).astype(np.float32)
                        * (1.0 if "norm" in n else 0.3))
         for n, s in specs.items()}
    u = jnp.asarray(rng.normal(size=(B, S, e)).astype(np.float32))
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    sizes = {"rms_norm_eps": 1e-5, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "kv_lora_rank": 32,
             "rope_theta": 10000.0}

    @jax.jit
    def emit(pos):
        return op.emit(params, [u, pos], w, f32_ctx(False), "attn")[0]

    @jax.jit
    def reference(pos):
        with jax.default_matmul_precision("highest"):
            if q_rank is None:
                return ref.latent_attention(u, w, sizes)
            return older.latent_attention(u, pos, w, sizes)

    got, want = emit(pos), reference(pos if rope else 0 * pos)
    if q_rank is None and rope:
        close(emit(0 * pos), want)
        assert float(jnp.max(jnp.abs(got - want))) > 1e-2
    else:
        close(got, want)
    flops = op.flops(params, [(B, S, e)], [(B, S, e)])
    q_proj = e * heads * 24 if q_rank is None \
        else e * q_rank + q_rank * heads * 24
    assert flops == 2.0 * B * S * (
        q_proj + e * 40 + 32 * heads * 32 + heads * 16 * e
        + S * heads * (24 + 16))


# ----------------------------------------------------------------------
# the whole model
# ----------------------------------------------------------------------
@pytest.mark.parametrize("config,kinds", [
    (KimiLinearRankConfig.tiny(), "kkkak"),
    (KimiLinearRankConfig(), "kkkak"),
    (dataclasses.replace(
        KimiLinearRankConfig.tiny(), num_hidden_layers=8,
        linear_attn_config={"kda_layers": [1, 2, 3, 5, 6, 7],
                            "full_attn_layers": [4, 8], "num_heads": 4,
                            "head_dim": 8, "short_conv_kernel_size": 2}),
     "kkkakkka"),
    (JoyAIFlashRankConfig(), "aaaaa"),
    (LatentMoEConfig.tiny(), "aaa")])
def test_the_layout_follows_the_two_lists(config, kinds):
    """The operator of each built layer, in order (k: gated delta rule,
    a: latent attention), one dense feed-forward and then experts; a
    configuration without ``linear_attn_config`` is latent attention
    throughout, with its q latent and its rotation."""
    ff = FFModel(FFConfig())
    build_latent_moe(ff, 1, 16, config)
    trunk = [l for l in ff.layers if l.op_type in (
        OperatorType.OP_GATED_DELTA_RULE,
        OperatorType.OP_LATENT_ATTENTION) and "mtp" not in l.name]
    assert [l.name for l in trunk] == [
        f"{'kda' if k == 'k' else 'attn'}_{i}" for i, k in enumerate(kinds)]
    assert "".join(
        "k" if l.op_type == OperatorType.OP_GATED_DELTA_RULE else "a"
        for l in trunk) == kinds
    experts = [l.name for l in ff.layers
               if l.op_type == OperatorType.OP_ROUTED_EXPERTS
               and "mtp" not in l.name]
    assert experts == [f"experts_{i}" for i in range(1, len(kinds))]
    assert sum(l.name.startswith("down_proj_") for l in ff.layers) == 1
    lin = getattr(config, "linear_attn_config", None)
    for l in trunk:
        if l.op_type == OperatorType.OP_GATED_DELTA_RULE:
            assert (l.params["num_heads"], l.params["head_dim"],
                    l.params["taps"]) == (
                lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"])
        else:
            assert l.params["q_rank"] == config.q_lora_rank
            assert l.params.get("rope", True) == (
                not getattr(config, "mla_use_nope", False))
    factor = getattr(config, "expert_rows_factor", 2)
    assert all(l.params.get("rows_factor", 2) == factor for l in ff.layers
               if l.op_type == OperatorType.OP_ROUTED_EXPERTS)
    if lin:
        shared = config.moe_intermediate_size * config.num_shared_experts
        assert all(l.params["shared_dim"] == shared
                   and l.params["experts_held"] == config.num_experts
                   and l.params["top_k"] == config.num_experts_per_token
                   for l in ff.layers
                   if l.op_type == OperatorType.OP_ROUTED_EXPERTS)


@pytest.mark.parametrize("kda,full", [
    ([1, 2, 3], [4]),              # layer 5 in neither
    ([1, 2, 3, 4, 5], [4]),        # layer 4 in both
    ([1, 2, 3, 5], [4, 6])])       # a sixth layer of five
def test_the_builder_refuses_lists_that_do_not_name_each_layer_once(kda,
                                                                    full):
    bad = dataclasses.replace(
        KimiLinearRankConfig.tiny(),
        linear_attn_config={"kda_layers": kda, "full_attn_layers": full,
                            "num_heads": 4, "head_dim": 8,
                            "short_conv_kernel_size": 4})
    with pytest.raises(ValueError, match="kda_layers"):
        build_latent_moe(FFModel(FFConfig()), 1, 16, bad)


@pytest.mark.parametrize("factor,budget", [(None, 2048), (2, 2048),
                                           (4, 4096), (3, 3072),
                                           (64, 32768)])
def test_the_row_budget_follows_the_factor_the_configuration_gives(factor,
                                                                  budget):
    """8 of 256 experts held at 4096 tokens, 8 a token: a uniform share
    is 1024 of the 32768 sorted rows. The op's default is 2 shares (the
    older configurations' graphs say nothing and keep it); this
    configuration asks for 4; no factor asks for more than every row."""
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    params = dict(num_experts=256, experts_held=8, top_k=8)
    if factor is not None:
        params["rows_factor"] = factor
    assert RoutedExpertsOp.rows_multiplied(4096, params) == budget
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    kw = {} if factor is None else {"rows_factor": factor}
    ff.routed_experts(x, 256, 8, 16, experts_held=8, **kw)
    assert ff.layers[-1].params.get("rows_factor") == (
        None if factor in (None, 2) else factor)
    assert KimiLinearRankConfig().expert_rows_factor == 4


def test_the_rank_config_takes_the_published_names():
    mc = KimiLinearRankConfig(num_experts=4, num_experts_published=64,
                              num_experts_per_token=2, num_shared_experts=2)
    assert (mc.n_routed_experts, mc.n_routed_experts_published,
            mc.num_experts_per_tok, mc.n_shared_experts) == (4, 64, 2, 2)
    cut = KimiLinearRankConfig()
    assert (cut.n_routed_experts, cut.n_routed_experts_published,
            cut.num_experts_per_tok, cut.q_lora_rank, cut.mla_use_nope,
            cut.num_nextn_predict_layers) == (8, 256, 8, None, True, 0)


def test_log_probabilities_and_loss_match_the_reference(tiny):
    ff, mc, batch = tiny
    loss, _, probs = rf.program(ff, ff.params, batch, training=False)
    want = rf.reference_call(ref.linear_latent_moe_decoder, ff, mc,
                             ff.params, batch)
    close(jnp.log(probs), want)
    close(loss, rf.reference_loss(ref, ff, mc, ff.params, batch))


def test_every_weights_gradient_matches_the_reference(tiny, tiny_step):
    ff, mc, batch = tiny
    _, got = tiny_step
    want = rf.reference_gradients(ref, ff, mc, ff.params, batch)
    assert set(got) == set(want)
    for name in got:
        for key in got[name]:
            assert float(jnp.max(jnp.abs(want[name][key]))) > 0 \
                or key == "bias", (name, key)
            close(got[name][key], want[name][key])
    # the decays, the taps, the step size, a router, a held expert and
    # the kv latent's second half were among them; the routers' bias
    # decides the choice and gets no gradient
    assert set(ref.KDA) == set(got["kda_2"])
    assert set(ref.LATENT) == set(got["attn_3"])
    assert {"wg", "w_down", "ws_down"} <= set(got["experts_4"])
    assert not np.any(np.asarray(got["experts_4"]["bias"]))


def test_the_reference_refuses_a_graph_it_does_not_know(tiny):
    ff, mc, batch = tiny
    sizes = dataclasses.asdict(mc)
    lin = sizes["linear_attn_config"]
    for wrong, match in (
            (dict(sizes, linear_attn_config=dict(
                lin, kda_layers=[1, 2, 5], full_attn_layers=[3, 4])),
             "expects"),
            (dict(sizes, first_k_dense_replace=2), "expects"),
            (dict(sizes, num_hidden_layers=4), "expects"),
            (dict(sizes, linear_attn_config=dict(lin, kda_layers=[1, 2])),
             "neither")):
        rf.refuses(ref, ref.linear_latent_moe_decoder, match, ff, wrong,
                   batch)


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4, at this configuration's shape in
    small: 64 experts in 32 shares of 2, 8 a token, a shared expert, the
    gates' sum times 2.446. The routed parts of all 32 shares plus the
    shared expert counted once are the uncut reference's layer."""
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    e, f, n, held = 32, 16, 64, 2
    base = dict(num_experts=n, top_k=8, expert_dim=f, scale=2.446)
    op = RoutedExpertsOp()
    rng = np.random.default_rng(13)
    whole = dict(base, shared_dim=f, experts_held=n, first_held=0)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * (0.02 if s.name == "bias" else 0.3))
         for s in op.weights(whole, [(B, S, e)], [DataType.DT_FLOAT])}
    x = jnp.asarray(rng.normal(size=(B, S, e)).astype(np.float32))

    def share(first, shared):
        ws = {k: (v[first:first + held]
                  if k in ("w_gate", "w_up", "w_down") else v)
              for k, v in w.items() if shared or not k.startswith("ws_")}
        p = dict(base, shared_dim=f if shared else 0, experts_held=held,
                 first_held=first)
        return op.emit(p, [x], ws, f32_ctx(), "experts")[0]

    routed = sum(share(first, False) for first in range(0, n, held))
    once = share(0, True) - share(0, False)
    sixth = share(6, False)
    sizes = {"num_experts_per_token": 8, "routed_scaling_factor": 2.446,
             "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, w: ref.routed(x, w, sizes)
                      + ref.shared(x, w))(x, w)
        one = jax.jit(lambda x, w: ref.routed(
            x, w, dict(sizes, first_held_expert=6)))(x, {
                k: (v[6:8] if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in w.items()})
    close(routed + once, want)
    close(sixth, one)
    assert float(jnp.max(jnp.abs(one - want))) > 0.1


# ----------------------------------------------------------------------
# rematerialisation, fit, instants and counters
# ----------------------------------------------------------------------
def test_the_repeated_run_is_two_whole_layers():
    """The layout is linear + dense, linear + experts, linear + experts,
    latent + experts, linear + experts. The earliest maximal run of
    equal blocks is layers 2 and 3 whole (norm, linear attention, add,
    norm, experts, add); ``remat = "blocks"`` wraps those two, and the
    dense layer, the latent layer and the last layer stay outside."""
    ff, _ = build(remat="blocks")
    assert ff.executor._remat is not None
    start, unit, reps = ff.executor._remat[:3]
    layers = ff.executor.program.layers
    assert reps == 2
    assert [l.name for l in layers[start:start + unit]] == [
        "input_norm_1", "kda_1", "attn_res_1", "post_norm_1", "experts_1",
        "mlp_res_1"]
    assert layers[start + 2 * unit].name == "input_norm_3"


def test_one_step_of_fit_is_the_same_with_and_without_remat():
    events.enable()
    events.clear()
    try:
        losses = []
        for remat in ("none", "blocks"):
            ff, mc = build(remat=remat)
            batch = data(mc)
            x = [np.asarray(batch["input_ids"]),
                 np.asarray(batch["position_ids"])]
            hist = ff.fit(x=x, y=np.asarray(batch["label"]), epochs=2,
                          verbose=False)
            losses.append([h["loss"] for h in hist])
        assert losses[0][1] < losses[0][0]
        np.testing.assert_allclose(losses[1], losses[0], rtol=1e-6)
        scans = [e["attrs"] for e in events.events()
                 if e["name"] == "kda.scan"]
        assert {c["layer"] for c in scans} == {
            "kda_0", "kda_1", "kda_2", "kda_4"}
        assert all(c["heads"] == 4 and c["head_dim"] == 8
                   and c["taps"] == 4 and c["tokens"] == B * S
                   and c["chunk"] == 64 and c["chunks"] == 1
                   and c["state_bytes"] == 4 * B * 4 * 8 * 8
                   for c in scans)
        latent = [e["attrs"] for e in events.events()
                  if e["name"] == "attn.latent"]
        assert {n["layer"] for n in latent} == {"attn_3"}
        assert all(n["heads"] == 4 and n["q_rank"] is None
                   and n["kv_rank"] == 32 and n["rope"] is False
                   and n["tokens"] == B * S for n in latent)
        routes = [e["attrs"] for e in events.events()
                  if e["name"] == "moe.route"]
        assert {r["layer"] for r in routes} == {
            f"experts_{i}" for i in range(1, 5)}
        c = events.counters()
        # 2 fits x 2 steps x 4 linear-attention layers
        assert c["kda.scans"] == 2 * 2 * 4
        assert c["kda.log_decay_min"] < 0
        assert c["moe.local_assignments"] == 2 * 2 * 4 * B * S * 4
        assert c["moe.dropped"] == 0 == c["moe.overflow"]
    finally:
        events.disable()
        events.clear()


def _forward_scans(ff, mc):
    """The chunk scans that run FORWARD (a layer's forward pass has one,
    its backward runs in reverse) in the train step's jaxpr, gradient
    and sub-jaxprs included, by the linear-attention layer in whose
    name scope they stand."""
    batch = data(mc)
    batch = next(iter(ff._combined_loader(
        [np.asarray(batch["input_ids"]), np.asarray(batch["position_ids"])],
        np.asarray(batch["label"]), shuffle=False)))
    step = jax.make_jaxpr(ff.executor.make_train_step())(
        ff.params, ff.opt_state, ff.state, jnp.int32(0), batch)
    seen = {}

    def walk(jaxpr, scope):
        for eqn in jaxpr.eqns:
            inner = f"{scope}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "scan" and not eqn.params["reverse"]:
                (layer,) = {part for part in inner.split("/")
                            if part.startswith("kda_")}
                seen[layer] = seen.get(layer, 0) + 1
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inner)

    walk(step.jaxpr, "")
    return seen


@pytest.mark.parametrize("remat", ["none", "blocks"])
def test_a_layer_runs_forward_twice_a_backward_inside_a_block_or_not(remat):
    """The layer keeps its input and runs itself again for its own
    backward: two forward scans a layer. A block around it (``kda_1``,
    ``kda_2``) keeps the layer's marked output, so the block's second
    run does not call the layer a third time (3 without the policy)."""
    events.enable()
    events.clear()
    try:
        ff, mc = build(remat=remat)
        assert _forward_scans(ff, mc) == {
            "kda_0": 2, "kda_1": 2, "kda_2": 2, "kda_4": 2}
        kept = [e["attrs"] for e in events.events()
                if e["name"] == "remat.kept"]
    finally:
        events.disable()
        events.clear()
    # once a marked layer a block, with the array's bytes: float32
    # (batch, positions, hidden); none outside a block
    assert kept == ([] if remat == "none" else [
        {"block": b, "layer": f"kda_{b + 1}",
         "bytes": 4 * B * S * mc.hidden_size} for b in range(2)])


def test_without_the_policy_a_block_runs_its_layer_a_third_time(monkeypatch):
    """What the count above is held against: the same step with the
    block's ``jax.checkpoint`` given no policy, as before PR 46."""
    monkeypatch.setattr("flexflow_tpu.executor.KEEP_MARKED", None)
    ff, mc = build(remat="blocks")
    assert _forward_scans(ff, mc) == {
        "kda_0": 2, "kda_1": 3, "kda_2": 3, "kda_4": 2}


def test_the_counters_leave_rematerialised_blocks(tiny_step):
    ff, mc = build(remat="blocks")
    _, bm, _ = rf.program(ff, ff.params, data(mc))
    (_, want), _ = tiny_step
    assert float(bm[COUNTER_PREFIX + "kda.scans"]) == 4
    assert float(bm[COUNTER_PREFIX + "kda.log_decay_min"]) == pytest.approx(
        float(want[COUNTER_PREFIX + "kda.log_decay_min"]), rel=1e-6)
