"""The gated-delta-rule / gated-attention mixture-of-experts decoder
(three linear layers with a decay a HEAD and 2 value heads a key head to
one gated grouped-query layer that turns a quarter of each head,
zero-centred norms, softmax routing beside a sigmoid-gated shared
expert; ``Qwen3NextRankConfig``) against its plain reference
(``benchmarks/reference/gdn_gated_moe_ref.py``), at a small size on the
CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(chunks with one triangular solve each against a token-by-token walk;
the sorted grouped product against a loop over experts). ``TOL`` = 2e-4
relative to the largest entry is a hundred times what they read and far
under what a wrong pairing of heads, one decay for all heads, a whole
head turned or a lost gate moves.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.models.nlp import (HybridConvMoEConfig, KeyeRankConfig,
                                     Qwen3NextRankConfig, TrinityRankConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp, RMSNormOp
from flexflow_tpu.ops.recurrent_ops import (GatedDeltaRuleOp,
                                            gated_delta_rule)
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from rank_family import B, apart, close, f32_ctx, program

ref = rf.reference("gdn_gated_moe_ref")
S = 48
build = functools.partial(rf.build, Qwen3NextRankConfig,
                          build_hybrid_conv_moe, seq=S)
data = functools.partial(rf.data, seq=S)


def spread(params):
    """The seed's weights with every zero-centred scale off 0, the gated
    norm's off 1, the attention gate's projection three times as large
    and the shared expert's scalar gate off a half, so that a plain
    scale where ``1 + w`` belongs, a lost gate and a gate that is not
    0.5 all show."""
    def rule(name, k, w, rng):
        if k in ("scale", "q_norm", "k_norm"):
            return rf.shifted(w, rng)
        if k == "o_norm":
            return rf.scaled(w, rng)
        if k in ("wg", "wz") and not name.startswith("experts_"):
            return w * 3.0
        if k == "ws_scalar":
            return w * 8.0
    return rf.spread(params, rule)


tiny, tiny_step = rf.fixtures(build, data, spread)


# ----------------------------------------------------------------------
# the recurrence with a decay a head
# ----------------------------------------------------------------------
E, HK, HV, D, TAPS = 32, 2, 4, 16, 4
GDN = {"num_heads": HV, "num_key_heads": HK, "head_dim": D, "taps": TAPS,
       "eps": 1e-6, "decay": "head"}
SIZES = {"rms_norm_eps": 1e-6}


def gdn_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)

    def taps(heads):
        return jnp.asarray(rng.normal(size=(heads, D, TAPS)) * 0.5,
                           jnp.float32)
    return {"wq": w(E, HK, D), "conv_q": taps(HK), "wk": w(E, HK, D),
            "conv_k": taps(HK), "wv": w(E, HV, D), "conv_v": taps(HV),
            "wa": w(E, HV), "wb": w(E, HV), "wz": w(E, HV, D) * 3,
            # every head a decay of its own, from slow to fast
            "A_log": jnp.log(jnp.asarray([0.05, 0.5, 2.0, 8.0],
                                         jnp.float32)),
            "dt_bias": jnp.asarray(rng.uniform(-3.0, 0.0, HV), jnp.float32),
            "o_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32),
            "wo": w(HV, D, E) * 4}


def gdn_input(seq=S, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(B, seq, E)), jnp.float32)


def gdn_layer(x, w, chunk=16):
    """``(output, counters)`` of the layer, traced where it is called."""
    ctx = f32_ctx()
    (y,) = GatedDeltaRuleOp().emit(dict(GDN, chunk=chunk), [x], w, ctx,
                                   "linear_attn")
    return y, ctx.counters


run_gdn = jax.jit(gdn_layer, static_argnames="chunk")


@jax.jit
def want_gdn(x, w):
    with jax.default_matmul_precision("highest"):
        return ref.linear_attention(x, w, SIZES)


@pytest.mark.parametrize("chunk", [16, 20, 64])
def test_the_layer_and_every_gradient_are_the_token_by_token_references(
        chunk):
    """48 positions in chunks of 16 (three whole chunks), 20 (the last
    one padded) and 64 (one chunk, padded): the output, and the gradient
    of a scalar of it for the input and every weight, against the walk
    over single tokens, at 2 value heads a key head."""
    x, w = gdn_input(), gdn_weights()

    def got(x, w):
        y, _ = run_gdn(x, w, chunk)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        y = want_gdn(x, w)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) == set(ref.LINEAR)
    for k in gw2:
        close(gw1[k], gw2[k], 1e-3)
        assert np.any(np.asarray(gw1[k])), k


def test_the_op_has_the_head_forms_weights_and_the_channel_forms_as_before():
    shapes = {s.name: s.shape for s in GatedDeltaRuleOp().weights(
        GDN, [(B, S, E)], [DataType.DT_FLOAT])}
    assert shapes == {
        "wq": (E, HK, D), "conv_q": (HK, D, TAPS), "wk": (E, HK, D),
        "conv_k": (HK, D, TAPS), "wv": (E, HV, D), "conv_v": (HV, D, TAPS),
        "wa": (E, HV), "A_log": (HV,), "dt_bias": (HV,), "wb": (E, HV),
        "wz": (E, HV, D), "o_norm": (D,), "wo": (HV, D, E)}
    older = [s.name for s in GatedDeltaRuleOp().weights(
        {"num_heads": HV, "head_dim": D, "taps": TAPS}, [(B, S, E)],
        [DataType.DT_FLOAT])]
    assert older == ["wq", "conv_q", "wk", "conv_k", "wv", "conv_v", "wf_a",
                     "wf_b", "A_log", "dt_bias", "wb", "wg_a", "wg_b",
                     "o_norm", "wo"]


@pytest.mark.parametrize("what", ["pairing", "one_decay", "sigmoid_gate",
                                  "gate_before_norm"])
def test_a_reference_of_another_form_is_apart(what):
    """What the comparison holds: value head ``j`` reads key head ``j //
    2`` (not ``j % 2``), each head decays by its own ``A_log``, the gate
    is a SiLU (not a sigmoid) and multiplies AFTER the norm."""
    x, w = gdn_input(), gdn_weights()
    got, _ = run_gdn(x, w)
    close(got, want_gdn(x, w))

    @jax.jit
    def another_form(x, w):
        q, k, v, g, beta = ref.linear_inputs(x, w)
        z = jnp.einsum("bse,ehd->bshd", x, w["wz"])
        gate = jax.nn.silu(z)
        if what == "pairing":                   # 0 1 0 1 for 0 0 1 1
            q, k = (a[:, :, jnp.asarray([0, 2, 1, 3])] for a in (q, k))
        elif what == "one_decay":
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        elif what == "sigmoid_gate":
            gate = jax.nn.sigmoid(z)
        o = ref.delta_rule_by_token(q, k, v, g, beta)
        if what == "gate_before_norm":
            y = ref.rms_norm(o * gate, w["o_norm"], 1e-6)
        else:
            y = ref.rms_norm(o, w["o_norm"], 1e-6) * gate
        return jnp.einsum("bshd,hde->bse", y, w["wo"])

    with jax.default_matmul_precision("highest"):
        apart(got, another_form(x, w))


def test_decays_that_overflow_when_formed_apart():
    """A head whose log-decays sum to -400 inside a chunk: ``exp(G_i)``
    times ``exp(-G_j)`` is 0 times inf; the differences are finite."""
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = ref.unit(arr(1, HK, 64, D)), ref.unit(arr(1, HK, 64, D))
    v, beta = arr(1, HV, 64, D), jax.nn.sigmoid(arr(1, HV, 64))
    g = -jnp.asarray(rng.uniform(4.0, 9.0, (1, HV, 64)), jnp.float32)
    got, least = jax.jit(lambda *a: gated_delta_rule(*a, 64))(
        q, k, v, g, beta)
    assert float(least) < -250 and np.all(np.isfinite(np.asarray(got)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.delta_rule_by_token)(*(
            jnp.moveaxis(a, 1, 2) for a in (
                jnp.repeat(q, 2, 1), jnp.repeat(k, 2, 1), v, g, beta)))
    close(got, jnp.moveaxis(want, 2, 1), 1e-5)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(
        gated_delta_rule(*a, 64)[0] ** 2), (0, 1, 2, 3, 4)))(
        q, k, v, g, beta)
    assert all(np.all(np.isfinite(np.asarray(a))) for a in grads)


def test_the_layers_span_and_counters():
    events.enable()
    events.clear()
    try:
        # (a trace of its own: the instant is said while tracing)
        _, counters = jax.jit(lambda x, w: gdn_layer(x, w, 16))(
            gdn_input(), gdn_weights())
        (scan,) = [e["attrs"] for e in events.events()
                   if e["name"] == "gdn.scan"]
        assert not [e for e in events.events() if e["name"] == "kda.scan"]
    finally:
        events.clear()
        events.disable()
    assert scan == {
        "layer": "linear_attn", "key_heads": HK, "value_heads": HV,
        "key_head_dim": D, "head_dim": D, "taps": TAPS, "tokens": B * S,
        "chunk": 16, "chunks": 3, "state_bytes": 4 * B * 3 * HV * D * D,
        "impl": "plain", "scan": "plain", "mix": "plain"}
    assert float(counters["gdn.scans"]) == 1.0
    assert float(counters["gdn.log_decay_min"]) < 0.0
    assert not [k for k in counters if k.startswith("kda.")]


@pytest.mark.parametrize("fields,match", [
    ({"decay": "head", "num_key_heads": 3}, "do not divide"),
    ({"decay": "row"}, "decay 'row'"),
    ({"num_key_heads": 2}, "as many key heads")])
def test_what_the_front_refuses_of_the_delta_rule(fields, match):
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    with pytest.raises(ValueError, match=match):
        ff.gated_delta_rule(x, 4, 16, 4, **fields)


# ----------------------------------------------------------------------
# the attention layer: a quarter of each head turns
# ----------------------------------------------------------------------
H, KV, DA = 4, 2, 16
ATTN = {"embed_dim": E, "num_heads": H, "num_kv_heads": KV, "kdim": H * DA,
        "vdim": H * DA, "bias": False, "causal": True, "qk_norm": True,
        "qk_norm_eps": 1e-6, "qk_norm_zero_centered": True,
        "output_gate": True, "rope": True, "rope_theta": 10000.0,
        "rotary_dim": 4}
ATTN_SIZES = {"rms_norm_eps": 1e-6, "head_dim": DA, "rope_theta": 10000.0,
              "partial_rotary_factor": 0.25}


def attn_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    return {"wq": w(E, H, DA), "wk": w(E, KV, DA), "wv": w(E, KV, DA),
            "wo": w(H, DA, E) * 4, "wg": w(E, H, DA) * 3,
            "q_norm": jnp.asarray(rng.uniform(-0.5, 0.5, DA), jnp.float32),
            "k_norm": jnp.asarray(rng.uniform(-0.5, 0.5, DA), jnp.float32)}


def run_attn(x, pos, w, impl="xla", **over):
    return jax.jit(lambda x, pos, w: MultiHeadAttentionOp().emit(
        dict(ATTN, **over), [x, x, x, pos], w, f32_ctx(impl=impl),
        "attn")[0])(x, pos, w)


def whole_turn(x, pos, w):
    """The layer without ``rotary_dim``: the whole head turns."""
    whole = {k: v for k, v in ATTN.items() if k != "rotary_dim"}
    return jax.jit(lambda x, pos, w: MultiHeadAttentionOp().emit(
        whole, [x, x, x, pos], w, f32_ctx(), "attn")[0])(x, pos, w)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_the_partial_turn_and_every_gradient_are_the_references(impl):
    x, w = gdn_input(), attn_weights()
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32) + 5, (B, 1))

    def got(x, w):
        y = run_attn(x, pos, w, impl)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        with jax.default_matmul_precision("highest"):
            y = ref.attention(x, pos, w, ATTN_SIZES)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    for k in gw2:
        close(gw1[k], gw2[k], 1e-3)


def test_a_whole_turn_a_plain_scale_and_no_turn_are_apart():
    x, w = gdn_input(), attn_weights()
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    part = run_attn(x, pos, w)
    turned = whole_turn(x, pos, w)
    apart(part, turned)
    with jax.default_matmul_precision("highest"):
        close(turned, jax.jit(lambda x, w: ref.attention(x, pos, w, dict(
            ATTN_SIZES, partial_rotary_factor=1.0)))(x, w))
    apart(part, run_attn(x, pos, w, qk_norm_zero_centered=False))
    # turning by position 0 is no turn, of a part or of the whole
    close(run_attn(x, jnp.zeros_like(pos), w),
          whole_turn(x, jnp.zeros_like(pos), w), 1e-6)


def test_the_norm_instant_says_what_turned_and_by_which_path():
    x, w = gdn_input(), attn_weights()
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    events.enable()
    events.clear()
    try:
        run_attn(x, pos, w, "flash")
        (norm,) = [e["attrs"] for e in events.events()
                   if e["name"] == "attn.qk_norm"]
    finally:
        events.clear()
        events.disable()
    assert norm["rotary_dim"] == 4 and norm["head_dim"] == DA
    assert norm["impl"] == "xla"


def test_a_partial_turn_never_takes_the_norm_rope_kernel():
    """At heads in whole lanes on one device the predicate says yes to
    a whole turn and no to a partial one, by name."""
    op, ctx = MultiHeadAttentionOp(), f32_ctx()
    ctx.kernel_impls = {"attention": "flash"}
    q = jnp.zeros((1, 1024, 16, 256))
    k = jnp.zeros((1, 1024, 2, 256))
    whole = {"qk_norm": True, "rope": True, "causal": True}
    assert op._takes_norm_rope_kernel(whole, ctx, "a", q, k, k, 0.0,
                                      jnp.bfloat16)
    assert not op._takes_norm_rope_kernel(dict(whole, rotary_dim=64), ctx,
                                          "a", q, k, k, 0.0, jnp.bfloat16)


@pytest.mark.parametrize("fields,match", [
    ({"rotary_dim": 4}, "rope=True"),
    ({"rope": True, "rotary_dim": 3}, "even share"),
    ({"rope": True, "rotary_dim": 10}, "even share"),
    ({"rope": True, "rotary_dim": 4, "qk_norm": True,
      "indexer": {"heads": 2, "head_dim": 8, "topk": 8, "q_chunk": 8}},
     "no indexer"),
    ({"qk_norm_zero_centered": True}, "qk_norm=True")])
def test_what_the_front_refuses_of_the_turn(fields, match):
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    with pytest.raises(ValueError, match=match):
        ff.multihead_attention(x, x, x, 32, 4, causal=True, **fields)


def test_a_turn_of_the_whole_head_is_the_layer_without_the_parameter():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    ff.multihead_attention(x, x, x, 32, 4, causal=True, rope=True,
                           rotary_dim=8, name="whole")
    ff.multihead_attention(x, x, x, 32, 4, causal=True, rope=True,
                           rotary_dim=2, name="part")
    by_name = {l.name: l.params for l in ff.layers}
    assert "rotary_dim" not in by_name["whole"]
    assert by_name["part"]["rotary_dim"] == 2


def test_a_zero_centred_norm():
    x = gdn_input()
    w = {"scale": jnp.asarray(np.random.default_rng(0).uniform(
        -0.5, 0.5, E), jnp.float32)}
    op = RMSNormOp()

    def norm(**params):
        return jax.jit(lambda x, w: op.emit(dict(eps=1e-6, **params), [x], w,
                                           f32_ctx(), "norm")[0])(x, w)

    y, plain = norm(zero_centered=True), norm()
    close(y, ref.rms_norm(x, 1.0 + w["scale"], 1e-6), 1e-6)
    apart(y, plain)
    (spec,) = op.weights({"zero_centered": True}, [(B, S, E)],
                         [DataType.DT_FLOAT])
    (one,) = op.weights({}, [(B, S, E)], [DataType.DT_FLOAT])
    assert spec.initializer.name == "ZERO" and one.initializer.name == "ONE"


# ----------------------------------------------------------------------
# the experts: softmax routing beside a gated shared expert
# ----------------------------------------------------------------------
def expert_weights(n=16, e=32, f=16, seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    return {"wg": w(e, n) * 3, "w_gate": w(n, e, f), "w_up": w(n, e, f),
            "w_down": w(n, f, e), "ws_gate": w(e, f), "ws_up": w(e, f),
            "ws_down": w(f, e), "ws_scalar": w(e, 1) * 4}


EXPERTS = {"num_experts": 16, "top_k": 4, "expert_dim": 16, "shared_dim": 16,
           "experts_held": 16, "first_held": 0, "scale": 1.0,
           "bias_std": 0.0, "scoring": "softmax", "shared_gate": True,
           "choice_bias": False}
EXPERT_SIZES = {"num_experts_per_tok": 4}


def test_the_gated_shared_expert_forward_and_backward():
    w = expert_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)

    def got(x, w):
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(EXPERTS, [x], w, ctx, "experts")
        return jnp.sum(y * jnp.cos(y)), (y, ctx.counters)

    def want(x, w):
        with jax.default_matmul_precision("highest"):
            y = ref.routed(x, w, EXPERT_SIZES) + ref.shared(x, w)
        return jnp.sum(y * jnp.cos(y)), y

    (_, (y1, counters)), (gx1, gw1) = jax.jit(jax.value_and_grad(
        got, (0, 1), has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(
        want, (0, 1), has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    for k in gw2:
        close(gw1[k], gw2[k], 1e-3)
    assert np.any(np.asarray(gw1["ws_scalar"]))
    with jax.default_matmul_precision("highest"):
        opened = jax.nn.sigmoid(x @ w["ws_scalar"])
        # an ungated shared expert is another function
        apart(y1, ref.routed(x, w, EXPERT_SIZES) + ref.swiglu(
            x, w["ws_gate"], w["ws_up"], w["ws_down"]))
    close(counters["moe.shared_gate_mean"], jnp.mean(opened), 1e-5)
    assert float(counters["moe.dropped"]) == 0.0


def test_the_weight_lists_with_and_without_the_new_parameters():
    def names(params):
        return [s.name for s in RoutedExpertsOp().weights(
            params, [(B, S, E)], [DataType.DT_FLOAT])]
    assert names(EXPERTS) == ["wg", "w_gate", "w_up", "w_down", "ws_gate",
                              "ws_up", "ws_down", "ws_scalar"]
    older = {k: v for k, v in EXPERTS.items()
             if k not in ("shared_gate", "choice_bias")}
    assert names(older) == ["wg", "bias", "w_gate", "w_up", "w_down",
                            "ws_gate", "ws_up", "ws_down"]
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    with pytest.raises(ValueError, match="without a shared expert"):
        ff.routed_experts(x, 16, 4, 16, shared_gate=True)
    with pytest.raises(ValueError, match="read a choice bias"):
        ff.routed_experts(x, 16, 4, 16, choice_bias=False)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Expert 0, 1, ... of 16, one share a chip, each chip routing over
    all 16 (softmax, top-4, the gates normalised over the chosen) and
    computing the whole gated shared expert: the shares' ROUTED parts
    and the shared expert counted once add up to the uncut reference's
    layer."""
    w = expert_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        own = ref.shared(x, w)
        want = ref.routed(x, w, EXPERT_SIZES) + own
    total = own
    for r in range(16):
        held = slice(r, r + 1)
        mine = dict(w, w_gate=w["w_gate"][held], w_up=w["w_up"][held],
                    w_down=w["w_down"][held])
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(
            dict(EXPERTS, experts_held=1, first_held=r), [x], mine, ctx,
            "experts")
        with jax.default_matmul_precision("highest"):
            close(y, ref.routed(x, mine, dict(
                EXPERT_SIZES, first_held_expert=r)) + own)
        assert float(ctx.counters["moe.dropped"]) == 0.0
        total = total + (y - own)        # every chip computes it alike
    close(total, want)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_model_is_the_reference_log_probabilities_and_loss(tiny):
    ff, mc, batch, params = tiny
    loss, bm, probs = program(ff, params, batch, training=False)
    want = rf.reference_call(ref.gdn_gated_moe_decoder, ff, mc, params,
                             batch)
    close(jnp.log(probs), want)
    close(loss, rf.reference_loss(ref, ff, mc, params, batch))
    assert float(bm[COUNTER_PREFIX + "gdn.scans"]) == 3.0
    assert float(bm[COUNTER_PREFIX + "attn.gate_layers"]) == 1.0
    assert float(bm[COUNTER_PREFIX + "moe.dropped"]) == 0.0
    assert 0.0 < float(bm[COUNTER_PREFIX + "moe.shared_gate_mean"]) < 4.0


def test_the_graph_has_what_the_equations_have(tiny):
    ff, mc, _, _ = tiny
    assert mc.layer_types == ["linear_attention"] * 3 + ["full_attention"]
    linear = [l for l in ff.layers
              if l.op_type.name == "OP_GATED_DELTA_RULE"]
    assert len(linear) == 3 and all(
        l.params["decay"] == "head" and l.params["num_key_heads"] == 2
        and l.params["num_heads"] == 4 and l.params["taps"] == 4
        for l in linear)
    (attn,) = [l for l in ff.layers
               if l.op_type.name == "OP_MULTIHEAD_ATTENTION"]
    assert attn.params["rotary_dim"] == 4 and attn.params["output_gate"] \
        and attn.params["qk_norm_zero_centered"] and len(attn.inputs) == 4
    norms = [l for l in ff.layers if l.op_type.name == "OP_RMSNORM"]
    assert len(norms) == 9 and all(l.params["zero_centered"] for l in norms)
    experts = [l for l in ff.layers
               if l.op_type.name == "OP_ROUTED_EXPERTS"]
    assert len(experts) == 4 and all(
        l.params["scoring"] == "softmax" and l.params["shared_gate"]
        and l.params["choice_bias"] is False
        and l.params["shared_dim"] == mc.shared_expert_intermediate_size
        for l in experts)
    assert all("bias" not in ff.params[l.name] for l in experts)


@pytest.mark.parametrize("field,value", [
    ("partial_rotary_factor", 1.0), ("zero_centered_norms", False),
    ("shared_expert_gate", False), ("attention_output_gate", False)])
def test_a_model_without_one_form_is_apart_from_the_reference(field, value):
    """Each form of the equations is held by the comparison: a model
    that turns the whole head, multiplies its norms by ``w`` alone,
    leaves the shared expert or the attention ungated reads otherwise
    (where its parameter list still fits the reference's walk) or does
    not fit it at all. (The pairing of value heads with key heads is
    read from the weights' shapes by both, and held by
    ``test_a_reference_of_another_form_is_apart``.)"""
    mc = dataclasses.replace(Qwen3NextRankConfig.tiny(), **{field: value})
    ff, _ = build(model_cfg=mc)
    batch = data(mc)
    params = spread(ff.params)
    _, _, probs = program(ff, params, batch, False)
    try:
        want = rf.reference_call(ref.gdn_gated_moe_decoder, ff,
                                 Qwen3NextRankConfig.tiny(), params, batch)
    except ref.ReferenceMismatch:
        assert field in ("shared_expert_gate", "attention_output_gate")
        return
    assert field in ("partial_rotary_factor", "zero_centered_norms")
    apart(jnp.log(probs), want, 1e-3)


def test_every_gradient_is_the_references(tiny, tiny_step):
    """The cross-entropy's gradient for every weight: the decay's, the
    full-rank gate's, both kinds of norm and the shared expert's scalar
    gate among them."""
    ff, mc, batch, params = tiny
    _, got = tiny_step
    want = rf.reference_gradients(ref, ff, mc, params, batch)
    seen = set()
    for name, ws in params.items():
        for k in ws:
            close(got[name][k], want[name][k], 1e-3)
            assert np.any(np.asarray(got[name][k])), (name, k)
            seen.add((name.rstrip("0123456789"), k))
    assert {("linear_attn_", "A_log"), ("linear_attn_", "dt_bias"),
            ("linear_attn_", "wa"), ("linear_attn_", "wz"),
            ("linear_attn_", "o_norm"), ("attn_", "wg"),
            ("attn_", "q_norm"), ("experts_", "ws_scalar"),
            ("operator_norm_", "scale"), ("final_norm", "scale")} <= seen


# ----------------------------------------------------------------------
# rematerialised blocks, a train step, the older configurations
# ----------------------------------------------------------------------
def test_the_remat_finder_takes_the_period_for_four_blocks():
    """[linear, linear, linear, full], each with experts: the delta rule
    stands where the attention layer does (``executor._MIXES_LIKE``), so
    the four layers are four blocks of one op sequence."""
    ff, mc = build(remat="blocks")
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    kinds = [l.op_type.name for l in ff.layers[start:start + unit]]
    assert (unit, reps) == (6, 4)
    assert sorted(kinds) == sorted([
        "OP_RMSNORM", "OP_GATED_DELTA_RULE", "OP_EW_ADD", "OP_RMSNORM",
        "OP_ROUTED_EXPERTS", "OP_EW_ADD"])
    assert ff.executor._remat[:3] == (start, unit, reps)


def test_a_rematerialised_step_is_the_step_and_trains(tiny, tiny_step):
    _, _, batch, params = tiny
    remat, _ = build(remat="blocks")
    rf.same_step(rf.step_and_gradients(remat, params, batch), tiny_step)
    step = remat.executor.make_train_step()
    before = jax.tree.map(np.asarray, remat.params["linear_attn_0"])
    losses = []
    p, o, st = remat.params, remat.opt_state, remat.state
    for _ in range(4):
        p, o, st, bm = step(p, o, st, jnp.int32(0), batch)
        losses.append(float(bm["loss"]))
    assert losses[-1] < losses[0]
    for k in ("A_log", "dt_bias", "wa", "wz", "conv_q"):
        assert np.any(np.asarray(p["linear_attn_0"][k]) != before[k]), k


@pytest.mark.parametrize("cls", [HybridConvMoEConfig, KeyeRankConfig,
                                 TrinityRankConfig])
def test_the_older_graphs_name_none_of_the_new_parameters(cls):
    """A graph built from the classes the older cells use has the layers
    and parameters it had: the new fields live on
    ``Qwen3NextRankConfig`` alone. (``tests/test_lowered_steps.py``
    pins the sha256 of every rank configuration's lowered step.)"""
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, 1, 32, cls.tiny())
    for l in ff.layers:
        for key in ("rotary_dim", "qk_norm_zero_centered", "zero_centered",
                    "shared_gate", "choice_bias", "decay",
                    "num_key_heads"):
            assert key not in l.params, (l.name, key)
    for field in ("partial_rotary_factor", "zero_centered_norms",
                  "shared_expert_gate", "linear_num_value_heads"):
        assert not hasattr(cls(), field), field


def test_a_linear_attention_layer_needs_its_sizes_and_its_interval():
    mc = dataclasses.replace(HybridConvMoEConfig.tiny(),
                             layer_types=["linear_attention"] * 5)
    with pytest.raises(ValueError, match="linear_\\* sizes"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, mc)
    with pytest.raises(ValueError, match="full_attention_interval"):
        dataclasses.replace(Qwen3NextRankConfig.tiny(),
                            layer_types=["full_attention"] * 4)
    with pytest.raises(ValueError, match="not built"):
        dataclasses.replace(Qwen3NextRankConfig.tiny(),
                            decoder_sparse_step=2)


def test_the_head_form_is_offered_by_batch_and_head_not_sequence(tiny):
    """The search's options for the op in its head form: the batch, and
    the heads with every weight that has a head axis (the q/k heads
    co-shard with the value heads they serve); no sequence option, and
    the plan verifier refuses a sequence shard by name."""
    from flexflow_tpu.analysis.plan_verifier import (PlanReport,
                                                     _check_conv_sequence)
    from flexflow_tpu.search import opshard
    ff = tiny[0]
    layer = next(l for l in ff.layers
                 if l.op_type.name == "OP_GATED_DELTA_RULE")
    kinds = [(o.kind, o.out_dim, dict(o.weight_dims))
             for o in opshard.options_for(layer)]
    assert kinds == [("sample", 0, {}), ("parameter", -1, {
        "wq": 1, "wk": 1, "wv": 1, "conv_q": 0, "conv_k": 0, "conv_v": 0,
        "wa": 1, "A_log": 0, "dt_bias": 0, "wb": 1, "wz": 1, "wo": 0})]
    assert set(kinds[1][2]) <= set(ff.params[layer.name])
    report = PlanReport()
    _check_conv_sequence(report, {"data": 2, "seq": 2}, layer,
                         ("data", "seq", None))
    assert [f.severity for f in report.findings] == ["error"]
    assert "gated delta rule" in report.findings[0].message
