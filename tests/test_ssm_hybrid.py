"""The state-space / attention hybrid decoder (nine Mamba-2 mixers to one
NoPE grouped-query layer with the model's own softmax scale, a dense
SwiGLU in every layer, four scalar multipliers;
``GraniteHybridRankConfig``) against its plain reference
(``benchmarks/reference/ssm_hybrid_ref.py``), at a small size on the CPU
with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(a chunk's products and the chunk states against one token after
another). ``TOL`` = 2e-4 relative to the largest entry is a hundred
times what they read and far under what a lost multiplier (12, 0.22,
1/64 against 1/8, 8), a gate on the wrong side of the norm, a lost
convolution bias, ``D`` or a decay of another size moves.
"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.analysis.plan_verifier import verify_plan
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.models.nlp import (GraniteHybridRankConfig,
                                     HybridConvMoEConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
from flexflow_tpu.ops.recurrent_ops import (StateSpaceMixerOp,
                                            state_space_scan)
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from flexflow_tpu.search import opshard
from rank_family import (B, TOL, apart, close, f32_ctx, named, program,
                         sizes_of)
from test_lowered_steps import LOWERED

ref = rf.reference("ssm_hybrid_ref")
S = 40                    # tiny(): chunks of 16, so two and a half
build = functools.partial(rf.build, GraniteHybridRankConfig,
                          build_hybrid_conv_moe, seq=S)
data = functools.partial(rf.data, seq=S)


def spread(params):
    """The seed's weights with every norm's scale and ``D`` off 1, a
    convolution bias off 0 and the attention layer's projections four
    times as large (scores that the softmax does not flatten), so that
    a wrong scale, a lost norm, a lost skip and a lost bias all show."""
    def rule(name, k, w, rng):
        if k in ("scale", "norm", "D"):
            return rf.scaled(w, rng)
        if k == "conv_b":
            return rf.shifted(w, rng)
        if k in ("wq", "wk", "wv", "wo"):
            return w * 4.0
    return rf.spread(params, rule)


tiny, tiny_step = rf.fixtures(build, data, spread)


@pytest.fixture(scope="module")
def tiny_reference(tiny):
    """The reference's log-probabilities at the tiny model's weights."""
    ff, mc, batch, params = tiny
    return rf.reference_call(ref.ssm_hybrid_decoder, ff, mc, params, batch)


# ----------------------------------------------------------------------
# the recurrence alone
# ----------------------------------------------------------------------
HM, P, N = 4, 16, 8


def scan_inputs(seq, seed=0, strength=1.0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, bm, cm = draw(B, seq, HM, P), draw(B, seq, N), draw(B, seq, N)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (B, seq, HM)), jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1.0, 16.0, HM) * strength),
                        jnp.float32)
    return x, dt, a_log, bm, cm


@jax.jit
def reference_scan(x, dt, a_log, bm, cm):
    with jax.default_matmul_precision("highest"):
        return ref.recurrence(x, dt, a_log, bm, cm, jnp.zeros(HM))


@pytest.mark.parametrize("seq", [32, 40])
@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_scan_is_the_recurrence_token_by_token(chunk, seq):
    """Two chunk sizes; 40 positions are two and a half chunks of 16:
    the padded positions write nothing and decay nothing."""
    x, dt, a_log, bm, cm = scan_inputs(seq)
    y, least = jax.jit(lambda *a: state_space_scan(
        a[0], a[1], -jnp.exp(a[2]), a[3], a[4], chunk))(x, dt, a_log, bm, cm)
    close(y, reference_scan(x, dt, a_log, bm, cm))
    # the most negative log-decay summed over one chunk, by hand
    g = np.asarray(dt) * -np.exp(np.asarray(a_log))
    g = np.pad(g, ((0, 0), (0, -seq % chunk), (0, 0)))
    want = g.reshape(B, -1, chunk, HM).sum(2).min()
    assert abs(float(least) - want) <= 1e-4 * abs(want)


def test_the_scans_gradients_are_the_recurrences():
    args = scan_inputs(40)

    def got(*a):
        y, _ = state_space_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 16)
        return jnp.sum(y * jnp.cos(y))

    def want(*a):
        y = reference_scan(*a)
        return jnp.sum(y * jnp.cos(y))

    g1 = jax.jit(jax.grad(got, range(5)))(*args)
    g2 = jax.jit(jax.grad(want, range(5)))(*args)
    for a, b in zip(g1, g2):
        close(a, b, 1e-3)


def test_decays_that_overflow_when_formed_apart_still_agree():
    """``A`` a hundred times the published range: a chunk's log-decays
    sum to -2,000 and further, so ``exp(-G_j)`` alone is infinite in
    float32 and ``exp(G_i) * exp(-G_j)`` would be nan. Every exponent
    the scan takes is a difference <= 0: it agrees, values and
    gradients, and everything is finite."""
    args = scan_inputs(32, strength=100.0)
    x, dt, a_log = args[:3]
    g = np.cumsum(np.asarray(dt) * -np.exp(np.asarray(a_log)), 1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-g[:, :16].astype(np.float32))).any()

    def got(*a):
        y, _ = state_space_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 16)
        return jnp.sum(y * jnp.cos(y)), y

    def want(*a):
        y = reference_scan(*a)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), g1 = jax.jit(jax.value_and_grad(got, (0, 1, 3, 4),
                                            has_aux=True))(*args)
    (_, y2), g2 = jax.jit(jax.value_and_grad(want, (0, 1, 3, 4),
                                            has_aux=True))(*args)
    assert np.isfinite(np.asarray(y1)).all()
    close(y1, y2)
    for a, b in zip(g1, g2):
        assert np.isfinite(np.asarray(a)).all()
        close(a, b, 1e-3)


# ----------------------------------------------------------------------
# one state-space layer
# ----------------------------------------------------------------------
E, TAPS = 32, 4
LAYER = {"num_heads": HM, "head_dim": P, "state": N, "taps": TAPS,
         "chunk": 16, "eps": 1e-5}
SIZES = {"mamba_n_heads": HM, "mamba_d_head": P, "mamba_d_state": N,
         "mamba_d_conv": TAPS, "mamba_n_groups": 1, "rms_norm_eps": 1e-5}
INNER = HM * P


def mixer_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    return {"in_proj": w(E, 2 * INNER + 2 * N + HM),
            "conv_w": u(-0.7, 0.7, INNER + 2 * N, TAPS),
            "conv_b": u(-0.5, 0.5, INNER + 2 * N),
            "dt_bias": u(-3.0, 0.0, HM),
            "A_log": jnp.log(u(1.0, 16.0, HM)),
            "D": u(0.5, 1.5, HM), "norm": u(0.5, 1.5, INNER),
            "out_proj": w(INNER, E)}


def mixer_input(seq=S, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(B, seq, E)),
                       jnp.float32)


def run_mixer(x, w, **over):
    """``(output, counters)`` of the layer."""
    def layer(x, w):
        ctx = f32_ctx()
        (y,) = StateSpaceMixerOp().emit(dict(LAYER, **over), [x], w, ctx,
                                        "mamba")
        return y, ctx.counters
    return jax.jit(layer)(x, w)


@jax.jit
def reference_mixer(x, w):
    with jax.default_matmul_precision("highest"):
        return ref.mixer(x, w, SIZES)


def test_a_layers_output_and_every_gradient_are_the_references():
    """Output, and the gradient of a scalar of it for the input and
    every weight: ``in_proj``, the taps, the convolution's bias,
    ``dt_bias``, ``A_log``, ``D``, the norm's scale, ``out_proj``."""
    x, w = mixer_input(), mixer_weights()

    def got(x, w):
        y, _ = run_mixer(x, w)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        y = reference_mixer(x, w)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) == set(ref.MIXER)
    for k in gw2:
        assert float(jnp.max(jnp.abs(gw2[k]))) > 0, k
        close(gw1[k], gw2[k], 1e-3)


@pytest.mark.parametrize("chunk", [8, 40, 64])
def test_the_layer_is_the_same_in_chunks_of_any_size(chunk):
    x, w = mixer_input(), mixer_weights()
    close(run_mixer(x, w, chunk=chunk)[0], run_mixer(x, w)[0], 1e-5)


@pytest.mark.parametrize("what", ["conv_b", "D", "A_log", "dt_bias",
                                  "norm"])
def test_a_layer_that_lost_one_weight_is_apart_from_the_reference(what):
    """The reference given a neutral value where the program holds a
    drawn one: no bias, no skip, a decay and a step size of another
    size, a norm of ones."""
    x, w = mixer_input(), mixer_weights()
    neutral = {"conv_b": 0.0, "D": 0.0, "A_log": 0.0, "dt_bias": 0.0,
               "norm": 1.0}[what]
    other = dict(w, **{what: jnp.full_like(w[what], neutral)})
    apart(run_mixer(x, w)[0], reference_mixer(x, other))


def test_the_gate_comes_before_the_norm():
    """``RMS(y * silu(z))`` and ``RMS(y) * silu(z)`` are two functions;
    the program is the first."""
    x, w = mixer_input(), mixer_weights()

    @jax.jit
    def both_orders(x, w):
        zxbcdt = jnp.einsum("bte,ec->btc", x, w["in_proj"])
        z = zxbcdt[..., :INNER]
        xbc = jax.nn.silu(ref.causal_conv(
            zxbcdt[..., INNER:2 * INNER + 2 * N], w["conv_w"], w["conv_b"]))
        xs = xbc[..., :INNER].reshape(B, S, HM, P)
        dt = jax.nn.softplus(zxbcdt[..., 2 * INNER + 2 * N:] + w["dt_bias"])
        y = ref.recurrence(xs, dt, w["A_log"], xbc[..., INNER:INNER + N],
                           xbc[..., INNER + N:], w["D"]).reshape(B, S, INNER)
        gate_first = ref.rms_norm(y * jax.nn.silu(z), w["norm"], 1e-5) \
            @ w["out_proj"]
        gate_last = (ref.rms_norm(y, w["norm"], 1e-5) * jax.nn.silu(z)) \
            @ w["out_proj"]
        return gate_first, gate_last

    with jax.default_matmul_precision("highest"):
        gate_first, gate_last = both_orders(x, w)
    got = run_mixer(x, w)[0]
    apart(gate_first, gate_last)
    close(got, gate_first)
    apart(got, gate_last)


def test_the_layer_raises_under_a_key_value_cache():
    x, w = mixer_input(), mixer_weights()
    ctx = f32_ctx(training=False)
    ctx.kv_mode = "prefill"
    with pytest.raises(NotImplementedError, match="no decode path"):
        StateSpaceMixerOp().emit(LAYER, [x], w, ctx, "mamba")


def test_what_the_front_refuses():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, E), name="x")
    with pytest.raises(ValueError, match="groups"):
        ff.state_space_mixer(x, HM, P, N, TAPS, 16, groups=3)
    with pytest.raises(ValueError, match="taps"):
        ff.state_space_mixer(x, HM, P, N, 0, 16)
    with pytest.raises(ValueError, match="sm_scale"):
        ff.multihead_attention(x, x, x, E, 4, causal=True, sm_scale=0.0)
    for field, value in (("position_embedding_type", "rope"),
                         ("num_local_experts", 8), ("mamba_expand", 3),
                         ("shared_intermediate_size", 48)):
        with pytest.raises(ValueError):
            dataclasses.replace(GraniteHybridRankConfig.tiny(),
                                **{field: value})
    with pytest.raises(ValueError, match="mamba_"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, dataclasses.replace(
            HybridConvMoEConfig.tiny(), layer_types=["mamba"] * 5))
    with pytest.raises(ValueError, match="attention_multiplier"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, dataclasses.replace(
            HybridConvMoEConfig.tiny(), layer_types=["attention"] * 5))


# ----------------------------------------------------------------------
# the attention layer's own scale
# ----------------------------------------------------------------------
H, KV, D = 4, 2, 8
ATTN = {"embed_dim": E, "num_heads": H, "num_kv_heads": KV, "kdim": H * D,
        "vdim": H * D, "bias": False, "causal": True}
ATTN_SIZES = {"num_attention_heads": H, "num_key_value_heads": KV,
              "attention_multiplier": 0.25}


def attn_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    return {"wq": w(E, H, D) * 3, "wk": w(E, KV, D) * 3, "wv": w(E, KV, D),
            "wo": w(H, D, E)}


def attention_layer(x, w, impl, **over):
    """The layer's output, traced where it is called."""
    (y,) = MultiHeadAttentionOp().emit(dict(ATTN, **over), [x, x, x], w,
                                       f32_ctx(impl=impl), "attn")
    return y


def run_attention(x, w, impl, **over):
    return jax.jit(lambda x, w: attention_layer(x, w, impl, **over))(x, w)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_the_scaled_layer_and_its_gradients_are_the_references(impl):
    """Grouped, causal, no positions, scores times 0.25 and not
    ``1 / sqrt(8)``, down the XLA path and through the flash kernels."""
    x, w = mixer_input(), attn_weights()

    def got(x, w):
        y = run_attention(x, w, impl, sm_scale=0.25)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        with jax.default_matmul_precision("highest"):
            y = ref.attention(x, w, ATTN_SIZES)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    for k in gw2:
        close(gw1[k], gw2[k], 1e-3)
    # the default scale is another function, and 1 / sqrt(d) given by
    # hand is the default
    apart(run_attention(x, w, impl), y2)
    close(run_attention(x, w, impl, sm_scale=8 ** -0.5),
          run_attention(x, w, impl), 1e-6)


def test_the_scale_reaches_the_decode_path_and_the_recorder():
    """A prefill and a decode step at the model's scale agree with the
    full forward's last position; the layer says its scale once a
    trace, and a layer with none says nothing."""
    x, w = mixer_input(seq=16), attn_weights()
    full = run_attention(x, w, "xla", sm_scale=0.25)
    params = dict(ATTN, sm_scale=0.25)

    @jax.jit
    def decoded(x, w):
        ctx = f32_ctx(training=False)
        ctx.kv_mode = "prefill"
        MultiHeadAttentionOp().emit(params, [x] * 3, w, ctx, "attn")
        cache = {"attn": {k: v.at[:, 15:].set(0.0)
                          for k, v in ctx.new_kv["attn"].items()}}
        ctx = f32_ctx(training=False)
        ctx.kv_mode, ctx.kv_cache, ctx.kv_index = "decode", cache, \
            jnp.int32(15)
        return MultiHeadAttentionOp().emit(params, [x[:, 15:]] * 3, w, ctx,
                                           "attn")[0]

    close(decoded(x, w)[:, 0], full[:, 15], 1e-5)
    events.enable()
    events.clear()
    try:
        run_attention(x, w, "xla", sm_scale=0.25)
        run_attention(x, w, "xla")
        said = [e for e in events.events() if e["name"] == "attn.sm_scale"]
    finally:
        events.disable()
        events.clear()
    assert len(said) == 1 and said[0]["attrs"]["sm_scale"] == 0.25 \
        and abs(said[0]["attrs"]["default"] - 8 ** -0.5) < 1e-12


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_model_is_the_reference_log_probabilities_and_loss(
        tiny, tiny_reference):
    ff, mc, batch, params = tiny
    loss, _, probs = program(ff, params, batch, False)
    close(jnp.log(probs), tiny_reference)
    close(loss, rf.reference_loss(ref, ff, mc, params, batch), 1e-5)


def test_every_gradient_is_the_references(tiny, tiny_step):
    ff, mc, batch, params = tiny
    _, got = tiny_step
    _, want = jax.jit(lambda p: ref.loss_and_gradients(
        named(ff, p), sizes_of(mc), batch["input_ids"],
        batch["position_ids"], batch["label"][..., 0]))(params)
    kinds = set()
    for (name, _), ws in zip(named(ff, params), want):
        for k, g in ws.items():
            close(got[name][k], g, 1e-3)
            kinds.add(k)
    assert kinds >= set(ref.MIXER) | set(ref.ATTN) | {"kernel", "scale"}


def test_the_graph_has_what_the_equations_have(tiny):
    ff, mc, _, _ = tiny
    by_name = {l.name: l for l in ff.layers}
    assert by_name["embedding_multiplier"].params["scalar"] == 12.0
    assert by_name["logits_scaling"].params["scalar"] == 8.0
    assert by_name["logits_scaling"].op_type \
        == OperatorType.OP_SCALAR_TRUE_DIV
    kinds = [l.op_type.name for l in ff.layers]
    assert kinds.count("OP_STATE_SPACE_MIXER") == 5
    assert kinds.count("OP_MULTIHEAD_ATTENTION") == 1
    assert kinds.count("OP_ROUTED_EXPERTS") == 0
    for i in range(mc.num_hidden_layers):
        for name in (f"operator_scale_{i}", f"ffn_scale_{i}"):
            assert by_name[name].params["scalar"] == 0.22
    attn = by_name["attn_3"].params
    assert attn["sm_scale"] == 0.25 and attn["num_kv_heads"] == 2 \
        and not attn.get("rope") and not attn.get("qk_norm") \
        and len(by_name["attn_3"].inputs) == 3
    # nothing turns by a position: the input is declared, fed and unread
    assert [t.name for t in ff.graph_inputs] == ["input_ids"]
    assert ff.label_tensor is None or ff.label_tensor.name != "position_ids"


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 8 ** -0.5), ("logits_scaling", 1.0)])
def test_a_model_without_one_scalar_is_apart_from_the_reference(
        tiny, tiny_reference, field, value):
    """12, 0.22, the scores' 0.25 against ``1 / sqrt(8)``, 8: a program
    built with one of them at its neutral value is another function."""
    ff, mc, batch, params = tiny
    other, _ = build(model_cfg=dataclasses.replace(mc, **{field: value}))
    _, _, probs = program(other, params, batch, False)
    apart(jnp.log(probs), tiny_reference, 10 * TOL)


# ----------------------------------------------------------------------
# fit, rematerialisation, sharding
# ----------------------------------------------------------------------
def test_fit_takes_the_unread_positions_and_the_loss_falls():
    ff, mc = build(remat="blocks")
    batch = data(mc, batch=4 * B)
    x = [np.asarray(batch["input_ids"]), np.asarray(batch["position_ids"])]
    hist = ff.fit(x=x, y=np.asarray(batch["label"]), epochs=3,
                  verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"]
    with pytest.raises(ValueError, match="arrays for"):
        ff.fit(x=x + x[:1], y=np.asarray(batch["label"]), epochs=1,
               verbose=False)


def test_the_remat_finder_takes_the_ten_layers_as_ten_blocks():
    """[mamba x5, attention, mamba x4] with scalar multiplies inside: one
    run of ten blocks, a state-space mixer standing where the attention
    layer does; the mixers' blocks keep their mixer's output."""
    mc = dataclasses.replace(
        GraniteHybridRankConfig.tiny(), num_hidden_layers=10,
        layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    ff, _ = build(remat="blocks", model_cfg=mc)
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    assert (unit, reps) == (13, 10)
    block = ff.layers[start:start + unit]
    assert [l.op_type.name for l in block].count("OP_SCALAR_MULTIPLY") == 2
    assert ff.executor._remat[:3] == (start, unit, reps)
    mixers = [ff.layers[start + b * unit + 1].op_type.name
              for b in range(reps)]
    assert mixers == ["OP_STATE_SPACE_MIXER"] * 5 \
        + ["OP_MULTIHEAD_ATTENTION"] + ["OP_STATE_SPACE_MIXER"] * 4


def test_a_rematerialised_step_is_the_step(tiny, tiny_step):
    _, _, batch, params = tiny
    remat, _ = build(remat="blocks")
    rf.same_step(rf.step_and_gradients(remat, params, batch), tiny_step)
    (_, bm), _ = tiny_step
    assert float(bm[COUNTER_PREFIX + "ssm.layers"]) == 5.0
    assert float(bm[COUNTER_PREFIX + "ssm.min_chunk_log_decay"]) < 0.0


def test_the_layer_says_its_sizes_and_the_scan_has_its_scope():
    events.enable()
    events.clear()
    try:
        ff, mc = build()
        batch = data(mc)
        text = jax.jit(lambda p: rf.forward(ff, p, batch)[0]).lower(
            ff.params).as_text(debug_info=True)
        said = [e["attrs"] for e in events.events()
                if e["name"] == "ssm.layer"]
    finally:
        events.disable()
        events.clear()
    assert len(said) == 5
    # (chunks of 16 and a state of 8: the plain path, which is what the
    # kernels of ``kernels/state_space.py`` are held to; the published
    # shape's own instant and scopes are in tests/test_tpu_aot_compile.py
    # and tests/test_state_space_kernel.py)
    assert {k: said[0][k] for k in ("heads", "head_dim", "state", "groups",
                                    "chunk", "chunks", "impl")} == {
        "heads": 4, "head_dim": 16, "state": 8, "groups": 1, "chunk": 16,
        "chunks": 3, "impl": "plain"}
    assert "mamba_0/remat.ssm.layer/" in text.replace("checkpoint/", "") \
        and "ssm.scan" in text and "remat.ssm.chunk" in text


def test_eight_data_parallel_devices_step_as_one():
    """The same step on a mesh of the 8 virtual CPU devices, the batch
    divided over them, and on one device."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual CPU devices of tests/conftest.py")
    one, mc = build(batch=8, devices=1)
    eight, _ = build(batch=8, devices=8)
    assert one.dmesh.mesh.size == 1 and eight.dmesh.mesh.size == 8
    batch = data(mc, batch=8)
    out, host = [], jax.device_get(one.params)     # the step donates
    for ff in (one, eight):
        ff.params = jax.tree.map(
            lambda a, b: jax.device_put(a, b.sharding), host, ff.params)
        loader = ff._combined_loader(
            [np.asarray(batch["input_ids"]),
             np.asarray(batch["position_ids"])],
            np.asarray(batch["label"]), shuffle=False)
        p, _, _, bm = ff.executor.make_train_step()(
            ff.params, ff.opt_state, ff.state, jnp.int32(0),
            next(iter(loader)))
        out.append((float(bm["loss"]), jax.device_get(p)))
    (l1, p1), (l8, p8) = out
    assert abs(l1 - l8) <= 1e-5 * abs(l1)
    for name, ws in p1.items():
        for k in ws:
            close(p8[name][k], ws[k], 1e-4)


def test_the_search_offers_batch_and_heads_and_the_verifier_refuses_the_sequence():
    ff, _ = build()
    layer = next(l for l in ff.layers
                 if l.op_type == OperatorType.OP_STATE_SPACE_MIXER)
    opts = opshard.options_for(layer)
    assert [o.kind for o in opts] == ["sample", "parameter"]
    assert dict(opts[1].weight_dims) == {"norm": 0, "out_proj": 0}
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    axis = next(iter(ff.dmesh.axis_sizes))
    for spec, ok in ((P(axis, None, None), True),
                     (P(None, axis, None), False)):
        st = ShardingStrategy(ff.dmesh)
        st.set_op(layer.name, [spec], {})
        halo = [f for f in verify_plan(st, ff.layers).errors
                if "halo" in f.message]
        assert (not halo) == ok
        assert ok or "state-space mixer" in halo[0].message


# (last in the file: ``lowered_text`` clears JAX's caches)
def lowered_attention(impl, **over):
    x, w = mixer_input(), attn_weights()
    return rf.lowered_text(jax.grad(
        lambda x, w: jnp.sum(attention_layer(x, w, impl, **over)), (0, 1)),
        x, w)


@pytest.mark.parametrize("impl", ["flash", "xla"])
def test_without_a_scale_the_layer_lowers_as_at_the_parent(impl):
    """A layer that names no ``sm_scale`` lowers, value and gradients,
    to the text it lowered to at the parent, the ``1 / sqrt(d)`` layer
    (``tests/test_lowered_steps.py::LOWERED`` holds the two hashes: the
    tiny models' steps there take the XLA path on the CPU, so this is
    the one pin of the layer through the flash kernels), and the scale
    given is another text."""
    text = lowered_attention(impl)
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == LOWERED["attention layer", impl], \
        f'\n    ("attention layer", "{impl}"):\n        "{got}",'
    assert text != lowered_attention(impl, sm_scale=0.25)
