"""The window/full-attention mixture-of-experts decoder (three window
layers to one NoPE full layer, a sigmoid output gate on every attention
layer, four norms a layer, a scaled embedding, sigmoid routing beside a
shared expert; ``TrinityRankConfig``) against its plain reference
(``benchmarks/reference/window_gated_moe_ref.py``), at a small size on
the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(XLA's whole rows or the kernels' tiles against blocks of rows; the
sorted grouped product against a loop over experts). ``TOL`` = 2e-4
relative to the largest entry is a hundred times what they read and far
under what one key more or fewer in a window of 24, a lost gate (a
factor of two), a lost norm or a lost scale of 8 moves.
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
                                     LFM2RankConfig, TrinityRankConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from rank_family import B, apart, close, f32_ctx, program

ref = rf.reference("window_gated_moe_ref")
S = 48                    # tiny(): a window of 24
build = functools.partial(rf.build, TrinityRankConfig,
                          build_hybrid_conv_moe, seq=S)
data = functools.partial(rf.data, seq=S)


def spread(params):
    """The seed's weights with every norm's scale off 1 and the gate's
    projection three times as large, so that a wrong scale, a lost norm
    and a gate that is not 0.5 all show."""
    def rule(name, k, w, rng):
        if k in ("scale", "q_norm", "k_norm"):
            return rf.scaled(w, rng)
        if k == "wg" and name.startswith("attn_"):
            return w * 3.0
    return rf.spread(params, rule)


tiny, tiny_step = rf.fixtures(build, data, spread)


# ----------------------------------------------------------------------
# one attention layer
# ----------------------------------------------------------------------
E, H, KV, D = 32, 4, 2, 16
LAYER = {"embed_dim": E, "num_heads": H, "num_kv_heads": KV, "kdim": H * D,
         "vdim": H * D, "bias": False, "causal": True, "qk_norm": True,
         "qk_norm_eps": 1e-5, "output_gate": True}
KIND = {"sliding_attention": dict(LAYER, rope=True, rope_theta=10000.0,
                                  sliding_window=24),
        "full_attention": LAYER}
SIZES = {"rms_norm_eps": 1e-5, "rope_theta": 10000.0, "sliding_window": 24}


def attn_weights(seed=0, gate=True):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    out = {"wq": w(E, H, D), "wk": w(E, KV, D), "wv": w(E, KV, D),
           "wo": w(H, D, E) * 4,
           "q_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32),
           "k_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32)}
    if gate:
        out["wg"] = w(E, H, D) * 3
    return out


def attn_inputs(seq=S, seed=1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, seq, E)), jnp.float32)
    pos = jnp.tile(jnp.arange(seq, dtype=jnp.int32), (B, 1))
    return x, pos


def run_layer(kind, x, pos, w, impl=None, **over):
    """``(output, counters)`` of one layer of ``kind``; traced where it
    is called, for the gradients' sake."""
    params = dict(KIND[kind], **over)
    ctx = f32_ctx(impl=impl)
    ins = [x, x, x] + ([pos] if params.get("rope") else [])
    (y,) = MultiHeadAttentionOp().emit(params, ins, w, ctx, "attn")
    return y, ctx.counters


def layer(kind, x, pos, w, impl=None, **over):
    return jax.jit(lambda x, pos, w: run_layer(kind, x, pos, w, impl,
                                              **over))(x, pos, w)


def reference_layer(kind, x, pos, w, **sizes):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda x, pos, w: ref.attention(
            x, pos, w, dict(SIZES, **sizes), kind))(x, pos, w)


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("kind", sorted(KIND))
def test_a_layers_output_and_gradients_are_the_references(kind, impl):
    """S = 48 > window = 24: output, and the gradient of a scalar of it
    for the input and every weight, ``wg`` among them, down the XLA path
    and through the kernels (a window layer: their band arithmetic)."""
    x, pos = attn_inputs()
    w = attn_weights()

    def got(x, w):
        y, _ = run_layer(kind, x, pos, w, impl)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        with jax.default_matmul_precision("highest"):
            y = ref.attention(x, pos, w, SIZES, kind)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) and "wg" in gw1
    for k in gw2:
        close(gw1[k], gw2[k], 1e-3)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_a_window_of_at_least_the_sequence_is_the_full_layer_with_rotation(
        impl):
    x, pos = attn_inputs()
    w = attn_weights()
    wide, _ = layer("sliding_attention", x, pos, w, impl, sliding_window=S)
    none, _ = layer("sliding_attention", x, pos, w, impl, sliding_window=0)
    close(wide, none, 1e-6)
    narrow, _ = layer("sliding_attention", x, pos, w, impl)
    apart(narrow, none)


def test_the_window_moves_what_a_query_sees_by_one_key():
    """The band's lower edge is ``s > t - window``: the reference at a
    window of 23 or 25 is another function."""
    x, pos = attn_inputs()
    w = attn_weights()
    got, _ = layer("sliding_attention", x, pos, w)
    close(got, reference_layer("sliding_attention", x, pos, w))
    for other in (23, 25):
        apart(got, reference_layer("sliding_attention", x, pos, w,
                                   sliding_window=other), 1e-3)


@pytest.mark.parametrize("rows", [8, 16, 24])
@pytest.mark.parametrize("kind", sorted(KIND))
def test_the_reference_in_blocks_of_rows_is_the_reference_whole(
        kind, rows, monkeypatch):
    """48 positions in blocks of 8, 16 or 24 query rows (a window
    layer's block reads the 24 + rows - 1 keys its band reaches) against
    one block of all 48, and against a loop over the pairs' mask."""
    x, pos = attn_inputs()
    w = attn_weights()
    whole = reference_layer(kind, x, pos, w)
    monkeypatch.setattr(ref, "QUERY_ROWS", rows)
    close(reference_layer(kind, x, pos, w), whole, 1e-6)
    mask = np.array([[s <= t and (kind == "full_attention" or s > t - 24)
                      for s in range(S)] for t in range(S)])
    @jax.jit
    def by_the_mask(x, w):
        q = ref.rms_norm(jnp.einsum("bse,ehd->bshd", x, w["wq"]),
                         w["q_norm"], 1e-5)
        k = ref.rms_norm(jnp.einsum("bse,ehd->bshd", x, w["wk"]),
                         w["k_norm"], 1e-5)
        if kind == "sliding_attention":
            q, k = ref.rope(q, pos, 1e4), ref.rope(k, pos, 1e4)
        v = jnp.einsum("bse,ehd->bshd", x, w["wv"])
        k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v) * jax.nn.sigmoid(
            jnp.einsum("bse,ehd->bshd", x, w["wg"]))
        return jnp.einsum("bqhd,hde->bqe", o, w["wo"])

    with jax.default_matmul_precision("highest"):
        close(whole, by_the_mask(x, w), 1e-5)


@pytest.mark.parametrize("kind", sorted(KIND))
def test_without_a_gate_the_op_is_the_parents(kind):
    """No ``output_gate``: no ``wg`` in the weight list, and the jaxpr
    of the layer is the one a layer with the gate's lines cut out gives
    (the parent's op: projections, norms, rotation, attention, ``wo``)."""
    params = {k: v for k, v in KIND[kind].items() if k != "output_gate"}
    names = [s.name for s in MultiHeadAttentionOp().weights(
        params, [(B, S, E)] * 3, [DataType.DT_FLOAT] * 3)]
    assert "wg" not in names
    assert "wg" in [s.name for s in MultiHeadAttentionOp().weights(
        KIND[kind], [(B, S, E)] * 3, [DataType.DT_FLOAT] * 3)]
    x, pos = attn_inputs()
    w = attn_weights(gate=False)

    @jax.jit
    def ungated(x, pos, w):
        ctx = f32_ctx()
        ins = [x, x, x] + ([pos] if params.get("rope") else [])
        (y,) = MultiHeadAttentionOp().emit(params, ins, w, ctx, "attn")
        return y, ctx.counters

    y, counters = ungated(x, pos, w)
    assert not any(k.startswith("attn.gate") for k in counters)
    # the gate at 0 is half the ungated layer: sigmoid(0) = 0.5
    gated, _ = layer(kind, x, pos, dict(
        w, wg=jnp.zeros((E, H, D), jnp.float32)))
    close(2.0 * gated, y, 1e-6)


def test_the_gates_counters_and_the_windows_pairs():
    x, pos = attn_inputs()
    w = dict(attn_weights(), wg=jnp.zeros((E, H, D), jnp.float32))
    _, counters = layer("sliding_attention", x, pos, w)
    assert float(counters["attn.gate_mean"]) == 0.5
    assert float(counters["attn.gate_layers"]) == 1.0
    by_loop = sum(1 for t in range(S) for s in range(S)
                  if s <= t and s > t - 24)
    assert float(counters["attn.window_pairs"]) == B * by_loop \
        == B * ref.band_pairs(S, 24)
    assert float(counters["attn.causal_pairs"]) == B * S * (S + 1) / 2
    _, counters = layer("full_attention", x, pos, w)
    assert "attn.window_pairs" not in counters
    _, counters = layer("sliding_attention", x, pos, w, sliding_window=64)
    assert float(counters["attn.window_pairs"]) \
        == float(counters["attn.causal_pairs"])


@pytest.mark.parametrize("what,fields,match", [
    ("an indexer", {"indexer": {"heads": 2, "head_dim": 8, "topk": 8,
                                "q_chunk": 8}}, "indexer"),
    ("positions without rope", {"positions": True}, "rope=True"),
])
def test_what_the_front_refuses(what, fields, match):
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    if fields.pop("positions", False):
        fields["positions"] = ff.create_tensor((1, 16), DataType.DT_INT32,
                                               name="pos")
    with pytest.raises(ValueError, match=match):
        ff.multihead_attention(x, x, x, 32, 4, causal=True, qk_norm=True,
                               output_gate=True, **fields)


def test_qk_norm_without_rope_is_a_causal_layer_the_front_builds():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((1, 16, 32), name="x")
    ff.multihead_attention(x, x, x, 32, 4, causal=True, qk_norm=True,
                           bias=False, output_gate=True, name="nope")
    (layer,) = [l for l in ff.layers if l.name == "nope"]
    assert layer.params.get("qk_norm") and not layer.params.get("rope")
    assert layer.params["output_gate"] is True and len(layer.inputs) == 3


# ----------------------------------------------------------------------
# the experts' share
# ----------------------------------------------------------------------
def expert_weights(n=16, e=32, f=16, seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    return {"wg": w(e, n) * 3,
            "bias": jnp.asarray(rng.normal(size=n) * 0.05, jnp.float32),
            "w_gate": w(n, e, f), "w_up": w(n, e, f), "w_down": w(n, f, e),
            "ws_gate": w(e, f), "ws_up": w(e, f), "ws_down": w(f, e)}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, ... of 16, one share a chip, each chip routing
    over all 16 and computing the whole shared expert: the shares'
    ROUTED parts and the shared expert counted once add up to the uncut
    reference's layer."""
    w = expert_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    sizes = {"num_experts_per_tok": 4, "route_scale": 2.826}
    with jax.default_matmul_precision("highest"):
        own = ref.shared(x, w)
        want = ref.routed(x, w, sizes) + own
    total = own
    for r in range(8):
        held = slice(2 * r, 2 * r + 2)
        mine = dict(w, w_gate=w["w_gate"][held], w_up=w["w_up"][held],
                    w_down=w["w_down"][held])
        params = {"num_experts": 16, "top_k": 4, "expert_dim": 16,
                  "shared_dim": 16, "experts_held": 2, "first_held": 2 * r,
                  "scale": 2.826, "bias_std": 0.05}
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(params, [x], mine, ctx, "experts")
        with jax.default_matmul_precision("highest"):
            close(y, ref.routed(x, mine, dict(
                sizes, first_held_expert=2 * r)) + own)
        assert float(ctx.counters["moe.dropped"]) == 0.0
        total = total + (y - own)        # every chip computes it alike
    close(total, want)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_model_is_the_reference_log_probabilities_and_loss(tiny):
    ff, mc, batch, params = tiny
    loss, bm, probs = program(ff, params, batch, training=False)
    want = rf.reference_call(ref.window_gated_moe_decoder, ff, mc, params,
                             batch)
    close(jnp.log(probs), want)
    close(loss, rf.reference_loss(ref, ff, mc, params, batch))
    # four window layers of five, S = 48 > window = 24
    pairs = float(bm[COUNTER_PREFIX + "attn.window_pairs"])
    assert pairs == 4 * B * ref.band_pairs(S, mc.sliding_window)
    assert pairs < float(bm[COUNTER_PREFIX + "attn.causal_pairs"])
    assert float(bm[COUNTER_PREFIX + "attn.gate_layers"]) == 5.0
    assert float(bm[COUNTER_PREFIX + "moe.dropped"]) == 0.0


def test_the_graph_has_what_the_equations_have(tiny):
    ff, mc, _, _ = tiny
    attn = [l for l in ff.layers
            if l.op_type.name == "OP_MULTIHEAD_ATTENTION"]
    assert [bool(l.params.get("sliding_window")) for l in attn] \
        == [k == "sliding_attention" for k in mc.layer_types]
    # the window layers turn, the full layer has no rotary embedding
    assert [bool(l.params.get("rope")) for l in attn] \
        == [k == "sliding_attention" for k in mc.layer_types]
    assert [len(l.inputs) for l in attn] == [4, 4, 3, 4, 4]
    assert all(l.params["output_gate"] and l.params["qk_norm"]
               for l in attn)
    norms = [l.name for l in ff.layers if l.op_type.name == "OP_RMSNORM"]
    assert len(norms) == 4 * mc.num_hidden_layers + 1
    experts = [l for l in ff.layers if l.op_type.name == "OP_ROUTED_EXPERTS"]
    assert len(experts) == 4 and all(
        l.params["shared_dim"] == mc.moe_intermediate_size
        and l.params["scale"] == 2.826 and "scoring" not in l.params
        for l in experts)
    (scale,) = [l for l in ff.layers if l.name == "embed_scale"]
    assert scale.params["scalar"] == 8.0          # sqrt(64)


@pytest.mark.parametrize("field,value", [
    ("mup_enabled", False), ("sandwich_norms", False),
    ("attention_output_gate", False), ("full_attention_rope", True),
    ("sliding_window", 23), ("num_shared_experts", 0)])
def test_a_model_without_one_form_is_apart_from_the_reference(field, value):
    """Each form of the equations is held by the comparison: a model
    built without the embedding's scale, the two post-norms, the gate,
    the full layer's NoPE, one key of the window or the shared expert
    reads otherwise (where its parameter list still fits the
    reference's walk) or does not fit it at all."""
    mc = dataclasses.replace(TrinityRankConfig.tiny(), **{field: value})
    ff, _ = build(model_cfg=mc)
    batch = data(mc)
    params = spread(ff.params)
    _, _, probs = program(ff, params, batch, False)
    try:
        want = rf.reference_call(ref.window_gated_moe_decoder, ff,
                                 TrinityRankConfig.tiny(), params, batch)
    except ref.ReferenceMismatch:
        assert field in ("sandwich_norms", "attention_output_gate",
                         "num_shared_experts")
        return
    assert field in ("mup_enabled", "full_attention_rope", "sliding_window")
    apart(jnp.log(probs), want, 1e-3)


def test_every_gradient_is_the_references(tiny, tiny_step):
    """The cross-entropy's gradient for every weight: ``wg``, the four
    norms a layer and the shared expert among them."""
    ff, mc, batch, params = tiny
    _, got = tiny_step
    want = rf.reference_gradients(ref, ff, mc, params, batch)
    seen = set()
    for name, ws in params.items():
        for k in ws:
            if k == "bias":              # no gradient by construction
                continue
            close(got[name][k], want[name][k], 1e-3)
            assert np.any(np.asarray(got[name][k])), (name, k)
            seen.add((name.rstrip("0123456789"), k))
    assert {("attn_", "wg"), ("post_operator_norm_", "scale"),
            ("post_ffn_norm_", "scale"), ("operator_norm_", "scale"),
            ("ffn_norm_", "scale"), ("experts_", "ws_down"),
            ("attn_", "q_norm")} <= seen


def test_the_kernel_paths_step_is_the_xla_paths():
    """The whole model with the flash kernels forced (the window layers
    through the band arithmetic) against the XLA path: loss and every
    gradient, and the record says which ran."""
    xla, mc = build(attention="xla", devices=1)
    flash, _ = build(attention="flash", devices=1)
    batch = data(mc)
    params = spread(xla.params)
    ((l1, _), g1), ((l2, _), g2) = (
        rf.step_and_gradients(ff, params, batch) for ff in (xla, flash))
    close(l2, l1, 1e-5)
    for name, ws in g1.items():
        for k in ws:
            close(g2[name][k], ws[k], 1e-3)
    assert set(flash.executor.resolved_attention_impls.values()) == {"flash"}
    assert set(xla.executor.resolved_attention_impls.values()) == {"xla"}


def test_the_kernels_say_their_window_and_the_full_layer_says_none():
    events.enable()
    events.clear()
    try:
        ff, mc = build(attention="flash", devices=1)
        jax.eval_shape(lambda p: rf.forward(ff, p, data(mc))[0], ff.params)
        grids = [e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"]
        norms = [e["attrs"] for e in events.events()
                 if e["name"] == "attn.qk_norm"]
    finally:
        events.clear()
        events.disable()
    fwd = [g.get("window") for g in grids
           if g["kernel"] == "flash_attention_fwd"]
    assert fwd == [24, 24, None, 24, 24]
    assert len(norms) == 5 and {n["impl"] for n in norms} == {"xla"}


# ----------------------------------------------------------------------
# rematerialised blocks
# ----------------------------------------------------------------------
def test_the_remat_finder_on_a_dense_layer_and_four_unequal_expert_layers():
    """[dense, expert x 4] whose attention layers differ in their
    parameters (window and rotation against neither): the four expert
    layers are four blocks of one op sequence all the same."""
    ff, mc = build(remat="blocks")
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    kinds = [l.op_type.name for l in ff.layers[start:start + unit]]
    assert (unit, reps) == (8, 4)
    assert sorted(kinds) == sorted([
        "OP_RMSNORM", "OP_MULTIHEAD_ATTENTION", "OP_RMSNORM", "OP_EW_ADD",
        "OP_RMSNORM", "OP_ROUTED_EXPERTS", "OP_RMSNORM", "OP_EW_ADD"])
    assert ff.executor._remat[:3] == (start, unit, reps)


def test_a_rematerialised_step_is_the_step(tiny, tiny_step):
    _, _, batch, params = tiny
    remat, _ = build(remat="blocks")
    rf.same_step(rf.step_and_gradients(remat, params, batch), tiny_step)


def test_a_train_step_moves_the_gate_and_lowers_the_loss():
    ff, mc = build(remat="blocks")
    batch = data(mc)
    step = ff.executor.make_train_step()
    before = jax.tree.map(np.asarray, ff.params["attn_2"])
    losses = []
    p, o, st = ff.params, ff.opt_state, ff.state
    for _ in range(4):
        p, o, st, bm = step(p, o, st, jnp.int32(0), batch)
        losses.append(float(bm["loss"]))
    assert losses[-1] < losses[0]
    for k in ("wg", "wq", "wo", "q_norm"):
        assert np.any(np.asarray(p["attn_2"][k]) != before[k]), k
    # untrained weights: the mean gate is a half
    mean = float(bm[COUNTER_PREFIX + "attn.gate_mean"]) \
        / float(bm[COUNTER_PREFIX + "attn.gate_layers"])
    assert abs(mean - 0.5) < 0.02


# ----------------------------------------------------------------------
# the older configurations
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cls", [HybridConvMoEConfig, LFM2RankConfig,
                                 KeyeRankConfig])
def test_the_older_graphs_name_no_gate_no_window_and_no_new_layer(cls):
    """A graph built from the classes ``lfm2_24b_a2b`` and
    ``keye_vl2_30b_a3b`` use has the layers and parameters it had: the
    new fields live on ``TrinityRankConfig`` alone. (``tests/
    test_lowered_steps.py`` pins the sha256 of every rank configuration's
    lowered step.)"""
    ff = FFModel(FFConfig())
    mc = KeyeRankConfig.tiny() if cls is KeyeRankConfig \
        else HybridConvMoEConfig.tiny()
    build_hybrid_conv_moe(ff, 1, 32, mc)
    for l in ff.layers:
        assert "output_gate" not in l.params, l.name
        assert "sliding_window" not in l.params, l.name
        assert not l.name.startswith(("post_", "embed_scale")), l.name
        if l.op_type.name == "OP_ROUTED_EXPERTS":
            assert l.params["shared_dim"] == 0
    for field in ("sliding_window", "attention_output_gate",
                  "sandwich_norms", "mup_enabled", "num_shared_experts"):
        assert not hasattr(cls(), field), field


def test_a_sliding_attention_layer_needs_a_window():
    mc = dataclasses.replace(HybridConvMoEConfig.tiny(),
                             layer_types=["sliding_attention"] * 5)
    with pytest.raises(ValueError, match="sliding_window"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, mc)
