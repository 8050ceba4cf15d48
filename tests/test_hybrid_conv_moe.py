"""The hybrid convolution/attention decoder with sparse experts (gated
short convolutions among grouped-query attention layers with q/k norms,
sigmoid-routed experts with no shared one) against its plain reference
(``benchmarks/reference/hybrid_conv_moe_ref.py``), at a small size on
the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(three shifted slices of a padded sequence against a loop of shifts, a
fused softmax over repeated K/V against an explicit grouped one, the
sorted grouped product against a loop over experts). ``TOL`` = 2e-4
relative to the largest entry is some forty times what they read (5e-6)
and a thousand times under a tap applied at the wrong offset, a norm
after the rotation or a query head on the wrong kv head, each of which
moves the result by 1e-1 or more.
"""
import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.analysis.plan_verifier import verify_plan
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.models.nlp import (HybridConvMoEConfig, LFM2RankConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.nn_ops import GatedShortConvOp, MultiHeadAttentionOp
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from flexflow_tpu.search import opshard
from rank_family import B, close, f32_ctx

ref = rf.reference("hybrid_conv_moe_ref")
S = 32
build = functools.partial(rf.build, HybridConvMoEConfig,
                          build_hybrid_conv_moe, seq=S, attention="xla")
data = functools.partial(rf.data, seq=S)


tiny, tiny_step = rf.fixtures(build, data)


# ----------------------------------------------------------------------
# the gated short convolution
# ----------------------------------------------------------------------
def conv_by_the_equations(u, w):
    """``[B ; C ; x] = u W_in``, ``c_t = sum_j w_j (B x)_{t-2+j}``,
    ``y = (C c) W_out``, position by position."""
    bcx = np.einsum("bse,egc->bsgc", u, w["w_in"])
    z = bcx[:, :, 0] * bcx[:, :, 2]
    k = w["taps"].shape[1]
    c = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(k):
            if t - (k - 1) + j >= 0:
                c[:, t] += w["taps"][:, j] * z[:, t - (k - 1) + j]
    return (bcx[:, :, 1] * c) @ w["w_out"]


def conv_weights(e=16, k=3, seed=0):
    rng = np.random.default_rng(seed)
    return {"w_in": rng.normal(size=(e, 3, e)).astype(np.float32) / 4,
            "taps": rng.normal(size=(e, k)).astype(np.float32),
            "w_out": rng.normal(size=(e, e)).astype(np.float32) / 4}


@jax.jit
def conv_op(u, w):
    (y,) = GatedShortConvOp().emit({"taps": w["taps"].shape[1]}, [u], w,
                                   f32_ctx(), "conv")
    return y


@pytest.mark.parametrize("length", [1, 2, 3, 17])
def test_the_conv_op_is_the_equations_values_and_gradients(length):
    w = conv_weights()
    u = np.random.default_rng(length).normal(
        size=(2, length, 16)).astype(np.float32)
    close(conv_op(jnp.asarray(u), w), conv_by_the_equations(u, w), 1e-5)

    def by_jnp(u, w):                    # the same equations, for autodiff
        bcx = jnp.einsum("bse,egc->bsgc", u, w["w_in"],
                         precision="highest")
        return jnp.dot(bcx[:, :, 1] * ref.short_conv(
            bcx[:, :, 0] * bcx[:, :, 2], w["taps"]), w["w_out"],
            precision="highest")
    probe = jnp.asarray(np.random.default_rng(7).normal(
        size=(2, length, 16)).astype(np.float32))
    got = jax.jit(jax.grad(lambda u, w: jnp.sum(conv_op(u, w) * probe),
                          argnums=(0, 1)))(jnp.asarray(u), w)
    want = jax.jit(jax.grad(lambda u, w: jnp.sum(by_jnp(u, w) * probe),
                           argnums=(0, 1)))(jnp.asarray(u), w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 1e-5)


@pytest.mark.parametrize("t", [0, 5, 16])
def test_an_output_does_not_move_when_later_inputs_change(t):
    w = conv_weights()
    rng = np.random.default_rng(3)
    u = rng.normal(size=(2, 17, 16)).astype(np.float32)
    later = u.copy()
    later[:, t + 1:] = rng.normal(size=later[:, t + 1:].shape)
    a, b = conv_op(jnp.asarray(u), w), conv_op(jnp.asarray(later), w)
    assert np.array_equal(np.asarray(a[:, :t + 1]), np.asarray(b[:, :t + 1]))
    if t + 1 < 17:
        assert not np.allclose(np.asarray(a[:, t + 1:]),
                               np.asarray(b[:, t + 1:]))


def test_the_conv_op_is_sharded_by_batch_and_channel_not_sequence(tiny):
    ff, _, _ = tiny
    layer = next(l for l in ff.layers
                 if l.op_type == OperatorType.OP_GATED_SHORT_CONV)
    kinds = [(o.kind, o.out_dim, dict(o.weight_dims))
             for o in opshard.options_for(layer)]
    assert kinds == [("sample", 0, {}),
                     ("parameter", -1, {"w_in": 2, "taps": 0, "w_out": 0})]
    # a cost row the search can price: the two projections dominate
    op = GatedShortConvOp()
    flops = op.flops(layer.params, [(B, S, 64)], [(B, S, 64)])
    assert flops == B * S * (2 * 64 * 192 + 2 * 64 * 64 + 8 * 64)
    # and the verifier refuses a plan that shards its sequence
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    axis = next(iter(ff.dmesh.axis_sizes))
    for spec, ok in ((P(axis, None, None), True),
                     (P(None, axis, None), False)):
        st = ShardingStrategy(ff.dmesh)
        st.set_op(layer.name, [spec], {})
        report = verify_plan(st, ff.layers)
        halo = [f for f in report.errors if "halo" in f.message]
        assert (not halo) == ok, report.errors


# ----------------------------------------------------------------------
# grouped-query attention with q/k norms
# ----------------------------------------------------------------------
@pytest.mark.parametrize("qk_norm", [True, False])
def test_qk_norm_before_the_rotation_is_the_references_attention(qk_norm):
    """4 query heads on 1 kv head of 16. With the norms the op is the
    reference's attention; without them it is another function (the
    norms decide something at these weights)."""
    e, heads, kv, d = 32, 4, 1, 16
    params = {"embed_dim": e, "num_heads": heads, "kdim": heads * d,
              "vdim": heads * d, "bias": False, "causal": True,
              "rope": True, "rope_theta": 10000.0, "num_kv_heads": kv}
    if qk_norm:
        params.update(qk_norm=True, qk_norm_eps=1e-5)
    op = MultiHeadAttentionOp()
    specs = {w.name: w.shape for w in op.weights(
        params, [(B, S, e)] * 3, [DataType.DT_FLOAT] * 3)}
    assert ("q_norm" in specs) == qk_norm
    rng = np.random.default_rng(11)
    w = {n: jnp.asarray(rng.normal(size=s).astype(np.float32)
                        * (1.0 if "norm" in n else 0.3))
         for n, s in specs.items()}
    u = jnp.asarray(rng.normal(size=(B, S, e)).astype(np.float32))
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    sizes = {"norm_eps": 1e-5, "rope_parameters": {"rope_theta": 10000.0}}
    normed = dict(w) if qk_norm else dict(
        w, q_norm=jnp.ones(d), k_norm=jnp.ones(d))

    @jax.jit
    def emit(*positions):
        return op.emit(params, [u, u, u, *positions], w, f32_ctx(False),
                       "attn")[0]

    @jax.jit
    def want_at(pos):
        with jax.default_matmul_precision("highest"):
            return ref.attention(u, pos, normed, sizes)

    got, want = emit(pos), want_at(pos)
    if qk_norm:
        close(got, want)
        # the positions given are what the rotation turns by
        close(emit(pos + 3), want_at(pos + 3))
        close(emit(), got, 1e-6)
    else:
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) > 0.1 * scale


def test_positions_without_rope_are_refused():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, 32))
    pos = ff.create_tensor((B, S), name="p")
    with pytest.raises(ValueError, match="rope"):
        ff.multihead_attention(x, x, x, 32, 4, positions=pos)
    with pytest.raises(ValueError, match="taps"):
        ff.gated_short_conv(x, 0)


# ----------------------------------------------------------------------
# the whole model
# ----------------------------------------------------------------------
@pytest.mark.parametrize("config,kinds,dense", [
    (HybridConvMoEConfig.tiny(), "cacCC", 1),
    (LFM2RankConfig(), "cacCC", 1),
    (HybridConvMoEConfig(), "cc" + "acCC" * 9 + "ac", 2)])
def test_the_layout_follows_layer_types(config, kinds, dense):
    """The operator of each built layer, in order, and how many of the
    feed-forwards are dense (c: convolution, a: attention)."""
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, 1, 16, config)
    ops = [l for l in ff.layers if l.op_type in (
        OperatorType.OP_GATED_SHORT_CONV,
        OperatorType.OP_MULTIHEAD_ATTENTION)]
    assert [l.name for l in ops] == [
        f"{'conv' if k in 'cC' else 'attn'}_{i}"
        for i, k in enumerate(kinds)]
    assert "".join("c" if l.op_type == OperatorType.OP_GATED_SHORT_CONV
                   else "a" for l in ops) == kinds.lower()
    experts = [l.name for l in ff.layers
               if l.op_type == OperatorType.OP_ROUTED_EXPERTS]
    assert experts == [f"experts_{i}" for i in range(dense, len(kinds))]
    assert sum(l.name.startswith("down_proj_") for l in ff.layers) == dense
    attn = next(l for l in ops if l.name.startswith("attn_"))
    assert attn.params["qk_norm"] and attn.params["rope"]
    assert len(attn.inputs) == 4 and attn.inputs[3].name == "position_ids"
    assert all(l.params["shared_dim"] == 0 for l in ff.layers
               if l.op_type == OperatorType.OP_ROUTED_EXPERTS)
    assert len(config.layer_types) == config.num_hidden_layers


def test_the_builder_refuses_a_layout_it_cannot_lay_out():
    bad = dataclasses.replace(HybridConvMoEConfig.tiny(),
                              layer_types=["conv", "sliding"] * 2 + ["conv"])
    with pytest.raises(ValueError, match="layer_types"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 16, bad)
    short = dataclasses.replace(HybridConvMoEConfig.tiny(),
                                layer_types=["conv"])
    with pytest.raises(ValueError, match="layer_types"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 16, short)


@pytest.mark.parametrize("attention", ["flash", "xla"])
def test_log_probabilities_and_loss_match_the_reference(attention):
    ff, mc = build(attention=attention)
    batch = data(mc)
    loss, _, probs = rf.program(ff, ff.params, batch, training=False)
    want = rf.reference_call(ref.hybrid_conv_moe_decoder, ff, mc, ff.params,
                             batch)
    close(jnp.log(probs), want)
    close(loss, rf.reference_loss(ref, ff, mc, ff.params, batch))
    assert set(ff.executor.resolved_attention_impls.values()) == {
        attention}


def test_every_weights_gradient_matches_the_reference(tiny):
    ff, mc, batch = tiny
    _, got = rf.step_and_gradients(ff, ff.params, batch)
    want = rf.reference_gradients(ref, ff, mc, ff.params, batch)
    assert set(got) == set(want)
    for name in got:
        for key in got[name]:
            assert float(jnp.max(jnp.abs(want[name][key]))) > 0 \
                or key == "bias", (name, key)
            close(got[name][key], want[name][key])
    # the taps, both q/k norm weights and a router were among them; the
    # routers' bias decides the choice and gets no gradient
    assert {"w_in", "taps", "w_out"} == set(got["conv_0"])
    assert {"q_norm", "k_norm"} <= set(got["attn_1"])
    assert "wg" in got["experts_4"]
    assert not np.any(np.asarray(got["experts_4"]["bias"]))


@pytest.fixture(scope="module")
def share():
    """One rank's model under ``remat = "blocks"`` at 256 tokens, and
    its step's and the reference's gradients as functions of the weights
    (compiled once for both cases below)."""
    mc = dataclasses.replace(HybridConvMoEConfig.tiny(), num_experts=4,
                             num_experts_published=32,
                             first_held_expert=8)
    ff, mc = build(remat="blocks", model_cfg=mc, seq=4 * S)
    batch = data(mc, seq=4 * S)
    return ff, mc, rf.stepper(ff, batch), rf.reference_grader(ref, ff, mc,
                                                              batch)


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["inside_the_budget", "over_it"])
def test_a_share_of_the_experts_under_remat_is_the_reference_too(share,
                                                                 overflow):
    """One rank's model (experts 8 to 11 of 32, as the benchmark's cell
    holds 8 of 64) at 256 tokens: 1,024 sorted rows a layer against a
    budget of 512, so every expert layer has its loop, three of the four
    inside rematerialised blocks. With the routers as drawn the steps
    fit; with 2 added to the held experts' bias every choice is theirs
    and every layer runs a second chunk. Either way every weight's
    gradient is the reference's and the counters leave the blocks."""
    ff, mc, step, reference = share
    assert ff.executor._remat is not None
    params = ff.params
    if overflow:
        params = {n: dict(w, bias=w["bias"].at[8:12].add(2.0))
                  if n.startswith("experts_") else w
                  for n, w in params.items()}
    (_, bm), got = step(params)
    want = reference(params)
    for name in got:
        for key in got[name]:
            close(got[name][key], want[name][key])
    assert float(jnp.max(jnp.abs(want["experts_2"]["w_down"]))) > 0
    layers, rows = 4, B * 4 * S * mc.num_experts_per_tok
    assert float(bm[COUNTER_PREFIX + "moe.overflow"]) == overflow * layers
    assert float(bm[COUNTER_PREFIX + "moe.dropped"]) == 0
    local = float(bm[COUNTER_PREFIX + "moe.local_assignments"])
    assert (local == layers * rows) if overflow else (
        0 < local <= layers * 512)


def test_the_reference_refuses_a_graph_it_does_not_know(tiny):
    ff, mc, batch = tiny
    sizes = dataclasses.asdict(mc)
    for wrong, match in (
            (dict(sizes, layer_types=["full_attention"] * 5), "expects"),
            (dict(sizes, num_dense_layers=2), "expects"),
            (dict(sizes, layer_types=["conv"] * 4), "layer_types")):
        rf.refuses(ref, ref.hybrid_conv_moe_decoder, match, ff, wrong, batch)


# ----------------------------------------------------------------------
# rematerialisation on a graph whose repeated unit is a period
# ----------------------------------------------------------------------
def test_the_repeated_run_is_three_blocks_of_experts_and_a_convolution():
    """The layout is a dense conv layer, then one period attention,
    conv, conv, conv of expert layers. No whole layer repeats from the
    start of the period (attention, then convolutions), but the search
    is over ops, not layers: the earliest maximal run is three blocks of
    [an expert feed-forward, the NEXT layer's convolution], from the
    attention layer's experts to the last convolution. ``remat =
    "blocks"`` wraps those (it neither crashes on the unequal layers nor
    silently does nothing); the attention operator and the last expert
    feed-forward stay outside."""
    ff, _ = build(remat="blocks")
    assert ff.executor._remat is not None
    start, unit, reps = ff.executor._remat[:3]
    layers = ff.executor.program.layers
    assert reps == 3
    assert [l.name for l in layers[start:start + unit]] == [
        "ffn_norm_1", "experts_1", "ffn_res_1",
        "operator_norm_2", "conv_2", "operator_res_2"]
    assert layers[start + 3 * unit].name == "ffn_norm_4"


def test_a_layout_with_no_repeated_run_says_so(caplog):
    """Alternating kinds repeat nothing layer by layer: the period
    [conv, attention] of expert layers IS a repeated unit and is found;
    a layout with no period at all is run without rematerialisation, and
    the log says so."""
    period = dataclasses.replace(
        HybridConvMoEConfig.tiny(), num_hidden_layers=5,
        layer_types=["conv", "conv", "full_attention", "conv",
                     "full_attention"])
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, B, S, period)
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    assert reps == 2 and [l.name for l in ff.layers[start:start + unit]][
        1::3] == ["conv_1", "experts_1", "attn_2", "experts_2"]
    none = dataclasses.replace(
        HybridConvMoEConfig.tiny(), num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"])
    with caplog.at_level(logging.WARNING, logger="flexflow_tpu"):
        ff, _ = build(remat="blocks", model_cfg=none)
    assert ff.executor._remat is None
    assert "no eligible repeated-block region" in caplog.text


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
        convs = [e["attrs"] for e in events.events()
                 if e["name"] == "conv.short"]
        assert {c["layer"] for c in convs} == {
            "conv_0", "conv_2", "conv_3", "conv_4"}
        assert all(c["channels"] == 64 and c["taps"] == 3
                   and c["tokens"] == B * S for c in convs)
        norms = [e["attrs"] for e in events.events()
                 if e["name"] == "attn.qk_norm"]
        assert {n["layer"] for n in norms} == {"attn_1"}
        assert all(n["heads"] == 4 and n["kv_heads"] == 2
                   and n["head_dim"] == 16 for n in norms)
        routes = [e["attrs"] for e in events.events()
                  if e["name"] == "moe.route"]
        assert {r["layer"] for r in routes} == {
            f"experts_{i}" for i in range(1, 5)}
        c = events.counters()
        # 2 fits x 2 steps x 4 expert layers x every assignment
        assert c["moe.local_assignments"] == 2 * 2 * 4 * B * S * 4
        assert c["moe.dropped"] == 0 == c["moe.overflow"]
    finally:
        events.disable()
        events.clear()


def test_decode_through_the_cache_norms_its_keys_too():
    """``qk_norm`` sits ahead of the decode branch: a prefill of t
    tokens and one decoded token give the full forward's row t."""
    e, heads, kv, d = 32, 4, 2, 8
    params = {"embed_dim": e, "num_heads": heads, "kdim": heads * d,
              "vdim": heads * d, "bias": False, "causal": True,
              "rope": True, "rope_theta": 10000.0, "num_kv_heads": kv,
              "qk_norm": True, "qk_norm_eps": 1e-5}
    op = MultiHeadAttentionOp()
    rng = np.random.default_rng(5)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * 0.3)
         for s in op.weights(params, [(1, 8, e)] * 3, [DataType.DT_FLOAT] * 3)}
    u = jnp.asarray(rng.normal(size=(1, 8, e)).astype(np.float32))
    @jax.jit
    def full_and_decoded(u, w):
        (full,) = op.emit(params, [u, u, u], w, f32_ctx(False), "attn")
        ctx = f32_ctx(False)
        ctx.kv_mode = "prefill"
        op.emit(params, [u, u, u], w, ctx, "attn")
        cache = {"attn": {k: v.at[:, 7:].set(0.0)
                          for k, v in ctx.new_kv["attn"].items()}}
        dec = f32_ctx(False)
        dec.kv_mode, dec.kv_cache, dec.kv_index = "decode", cache, \
            jnp.int32(7)
        (row,) = op.emit(params, [u[:, 7:], u[:, 7:], u[:, 7:]], w, dec,
                         "attn")
        return full, row

    full, row = full_and_decoded(u, w)
    close(row[:, 0], full[:, 7], 1e-5)
