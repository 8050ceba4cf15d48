"""Blocks of ONE sub-layer (``NemotronHRankConfig``, ISSUE 66): a
Mamba-2 mixer with several groups of B and C, grouped-query attention
with no positions, and a LatentMoE feed-forward (a sigmoid router and a
shared expert on the stream, ReLU-squared experts in a latent between
two projections), against ``benchmarks/reference/nemotron_h_ref.py`` at a
small size on the CPU, float32: log-probabilities, the loss, every
weight's gradient at 2 and 4 groups with 4 of 16 experts held; the head
shares' and the expert shares' parts against the uncut layer; the block
finder's pairs; what the recorder is told. (The scan kernels at groups >
1 are held to the plain path in ``tests/test_state_space_kernel.py``.)
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.models.nlp import (HybridConvMoEConfig,
                                     NemotronHRankConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
from flexflow_tpu.ops.recurrent_ops import StateSpaceMixerOp
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from rank_family import B, close, f32_ctx, named, program, sizes_of

ref = rf.reference("nemotron_h_ref")
S = 40                    # tiny(): chunks of 16, so two and a half


def held(groups=2, **over):
    """``tiny()`` with experts 4 to 7 of the 16 held (3 a token) and the
    mixers' 8 heads in ``groups`` groups."""
    return dataclasses.replace(
        NemotronHRankConfig.tiny(), n_routed_experts=4,
        num_experts_published=16, first_held_expert=4, n_groups=groups,
        n_groups_published=groups, **over)


build = functools.partial(rf.build, NemotronHRankConfig,
                          build_hybrid_conv_moe, seq=S)
data = functools.partial(rf.data, seq=S)


def spread(params):
    """The seed's weights with every norm's scale and ``D`` off 1, the
    convolution's and the routers' bias off 0 and the attention layer's
    projections four times as large, so that a wrong group, a lost norm,
    a lost skip and a lost bias all show."""
    def rule(name, k, w, rng):
        if k in ("scale", "norm", "D"):
            return rf.scaled(w, rng)
        if k == "conv_b":
            return rf.shifted(w, rng)
        if k in ("wq", "wk", "wv", "wo"):
            return w * 4.0
    return rf.spread(params, rule)


def model(groups):
    ff, mc = build(model_cfg=held(groups))
    return ff, mc, data(mc), spread(ff.params)


@pytest.fixture(scope="module")
def tiny():
    return model(2)


@pytest.fixture(scope="module")
def tiny_step(tiny):
    ff, _, batch, params = tiny
    return rf.step_and_gradients(ff, params, batch)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_model_is_the_reference_log_probabilities_and_loss(tiny):
    ff, mc, batch, params = tiny
    loss, _, probs = program(ff, params, batch, False)
    close(jnp.log(probs), rf.reference_call(ref.nemotron_h_decoder, ff, mc,
                                            params, batch))
    close(loss, rf.reference_loss(ref, ff, mc, params, batch), 1e-5)


def gradients_are_the_references(tiny, tiny_step):
    ff, mc, batch, params = tiny
    _, got = tiny_step
    _, want = jax.jit(lambda p: ref.loss_and_gradients(
        named(ff, p), sizes_of(mc), batch["input_ids"],
        batch["position_ids"], batch["label"][..., 0]))(params)
    kinds = set()
    for (name, _), ws in zip(named(ff, params), want):
        for k, g in ws.items():
            if k == "bias":
                # corrects the choice: none from the loss; the balancing
                # rule's is each published expert's assignments over the
                # uniform share (held to the reference's count below)
                loads = np.asarray(got[name][k]) + B * S * 3 / 16
                assert not np.any(g) and loads.sum() == B * S * 3
                assert np.all(loads == np.round(loads)) and loads.min() >= 0
                continue
            close(got[name][k], g, 1e-3)
            kinds.add(k)
    assert kinds >= (set(ref.MIXER) | set(ref.ATTN) | set(ref.EXPERTS)
                     | {"kernel", "scale"}) - {"bias"}


def test_every_gradient_is_the_references(tiny, tiny_step):
    gradients_are_the_references(tiny, tiny_step)


def test_at_four_groups_too():
    """Log-probabilities, loss and every gradient with the mixers' 8
    heads in 4 groups of 2."""
    four = model(4)
    test_the_model_is_the_reference_log_probabilities_and_loss(four)
    gradients_are_the_references(four, rf.step_and_gradients(
        four[0], four[3], four[2]))


def test_the_graph_is_one_sub_layer_a_block(tiny):
    ff, mc, _, _ = tiny
    kinds = [l.op_type.name for l in ff.layers]
    assert mc.layer_types == ["moe", "mamba", "moe", "mamba", "attention"]
    # a norm, ONE sub-layer and an add a layer, five times
    assert kinds[1:16] == [
        "OP_RMSNORM", "OP_ROUTED_EXPERTS", "OP_EW_ADD",
        "OP_RMSNORM", "OP_STATE_SPACE_MIXER", "OP_EW_ADD"] * 2 + [
        "OP_RMSNORM", "OP_MULTIHEAD_ATTENTION", "OP_EW_ADD"]
    by_name = {l.name: l for l in ff.layers}
    experts = by_name["experts_0"].params
    assert (experts["latent"], experts["activation"], experts["top_k"],
            experts["experts_held"], experts["first_held"],
            experts["scale"]) == (32, "relu2", 3, 4, 4, 5.0)
    assert set(ff.params["experts_0"]) == set(ref.EXPERTS)
    assert ff.params["experts_0"]["w_up"].shape == (4, 32, 48) \
        and ff.params["experts_0"]["ws_up"].shape == (64, 96)
    assert by_name["mamba_1"].params["groups"] == mc.n_groups
    attn = by_name["attn_4"].params
    assert not attn.get("rope") and not attn.get("qk_norm") \
        and attn["sm_scale"] == 16 ** -0.5


def test_what_the_front_refuses():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, 64), name="x")
    with pytest.raises(ValueError, match="groups"):
        ff.state_space_mixer(x, 8, 16, 8, 4, 16, groups=3)
    with pytest.raises(ValueError, match="activation"):
        ff.routed_experts(x, 16, 3, 48, activation="gelu")
    for field, value in (("mlp_hidden_act", "silu"), ("n_group", 2),
                         ("hybrid_override_pattern", "EM-M*"),
                         ("use_conv_bias", False), ("n_groups", 3),
                         ("tie_word_embeddings", True)):
        with pytest.raises(ValueError):
            dataclasses.replace(NemotronHRankConfig.tiny(),
                                **{field: value})
    with pytest.raises(ValueError, match="sublayers_per_block"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, dataclasses.replace(
            HybridConvMoEConfig.tiny(), layer_types=["moe"] * 5))


# ----------------------------------------------------------------------
# the share tied to the model
# ----------------------------------------------------------------------
E, HM, P, N, G, TAPS = 64, 8, 16, 8, 4, 4
INNER = HM * P


def draw(rng, *shape):
    return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                       jnp.float32)


def layer_input(seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(B, S, E)),
                       jnp.float32)


def mixer_weights(seed=0):
    rng = np.random.default_rng(seed)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    return {"in_proj": draw(rng, E, 2 * INNER + 2 * G * N + HM),
            "conv_w": u(-0.7, 0.7, INNER + 2 * G * N, TAPS),
            "conv_b": u(-0.5, 0.5, INNER + 2 * G * N),
            "dt_bias": u(-3.0, 0.0, HM), "A_log": jnp.log(u(1.0, 16.0, HM)),
            "D": u(0.5, 1.5, HM), "norm": u(0.5, 1.5, INNER),
            "out_proj": draw(rng, INNER, E)}


def mixer_share(w, rank, ranks):
    """Rank ``rank`` of ``ranks``: its heads and their whole groups, as
    columns of ``in_proj`` ([z | x | B | C | dt]), as the taps' rows and
    as ``out_proj``'s rows."""
    h, g = HM // ranks, G // ranks
    heads = np.arange(rank * h, (rank + 1) * h)
    chans = np.arange(rank * h * P, (rank + 1) * h * P)
    states = np.arange(rank * g * N, (rank + 1) * g * N)
    conv = np.concatenate([chans, INNER + states, INNER + G * N + states])
    cols = np.concatenate([chans, INNER + conv, 2 * INNER + 2 * G * N + heads])
    return {"in_proj": w["in_proj"][:, cols], "conv_w": w["conv_w"][conv],
            "conv_b": w["conv_b"][conv], "dt_bias": w["dt_bias"][heads],
            "A_log": w["A_log"][heads], "D": w["D"][heads],
            "norm": w["norm"][chans], "out_proj": w["out_proj"][chans]}


def run_mixer(x, w, heads, groups):
    params = {"num_heads": heads, "head_dim": P, "state": N, "taps": TAPS,
              "chunk": 16, "eps": 1e-5, "groups": groups}
    return jax.jit(lambda x, w: StateSpaceMixerOp().emit(
        params, [x], w, f32_ctx(), "mamba")[0])(x, w)


def test_the_head_shares_of_a_mixer_sum_to_the_whole_mixer(ranks=4):
    """8 heads in 4 groups over 4 ranks (one group a rank, the cell's
    4-way share): each rank's part of the output projection from its own heads,
    B, C and norm statistics alone; the parts add up to the uncut
    reference's output."""
    x, w = layer_input(), mixer_weights()
    sizes = {"mamba_num_heads": HM, "mamba_head_dim": P, "ssm_state_size": N,
             "n_groups": G, "conv_kernel": TAPS, "norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x, w: ref.mixer(x, w, sizes))(x, w)
    close(run_mixer(x, w, HM, G), whole)
    parts = [run_mixer(x, mixer_share(w, r, ranks), HM // ranks, G // ranks)
             for r in range(ranks)]
    close(sum(parts), whole)
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)


def test_the_head_shares_of_attention_sum_to_the_whole_layer():
    """4 query heads on 2 key/value heads over 4 ranks: a rank holds one
    query head and the key/value head it reads (two ranks hold each)."""
    rng = np.random.default_rng(0)
    h, kv, d = 4, 2, 16
    w = {"wq": draw(rng, E, h, d), "wk": draw(rng, E, kv, d),
         "wv": draw(rng, E, kv, d), "wo": draw(rng, h, d, E) * 0.25}
    x = layer_input()
    sizes = {"num_attention_heads": h, "num_key_value_heads": kv,
             "head_dim": d}
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x, w: ref.attention(x, w, sizes))(x, w)

    def share(x, w, heads, kv_heads):
        params = {"embed_dim": E, "num_heads": heads,
                  "num_kv_heads": kv_heads, "kdim": heads * d,
                  "vdim": heads * d, "bias": False, "causal": True,
                  "sm_scale": d ** -0.5}
        return MultiHeadAttentionOp().emit(params, [x, x, x], w,
                                           f32_ctx(impl="xla"), "attn")[0]

    close(jax.jit(lambda x, w: share(x, w, h, kv))(x, w), whole)
    parts = [jax.jit(lambda x, w: share(x, w, 1, 1))(x, {
        "wq": w["wq"][:, r:r + 1], "wk": w["wk"][:, r // 2:r // 2 + 1],
        "wv": w["wv"][:, r // 2:r // 2 + 1], "wo": w["wo"][r:r + 1]})
        for r in range(h)]
    close(sum(parts), whole)


EXPERTS = {"num_experts": 16, "top_k": 3, "expert_dim": 48,
           "shared_dim": 96, "scale": 5.0, "bias_std": 0.05, "latent": 32,
           "activation": "relu2"}


def expert_weights(seed=0):
    rng = np.random.default_rng(seed)
    return {"wg": draw(rng, E, 16),
            "bias": jnp.asarray(rng.normal(size=16) * 0.05, jnp.float32),
            "w_latent_in": draw(rng, E, 32),
            "w_latent_out": draw(rng, 32, E),
            "w_up": draw(rng, 16, 32, 48) * 4.0,
            "w_down": draw(rng, 16, 48, 32) * 4.0,
            "ws_up": draw(rng, E, 96), "ws_down": draw(rng, 96, E)}


def run_experts(x, w, first, count, shared=True):
    params = dict(EXPERTS, experts_held=count, first_held=first)
    w = dict(w, w_up=w["w_up"][first:first + count],
             w_down=w["w_down"][first:first + count])
    if not shared:
        params["shared_dim"] = 0
        w = {k: v for k, v in w.items() if not k.startswith("ws_")}

    def layer(x, w):
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(params, [x], w, ctx, "experts")
        return y, ctx.counters
    return jax.jit(layer)(x, w)


def test_the_expert_shares_sum_to_the_uncut_layer():
    """Four shares of 4 experts, the shared expert counted once, against
    the reference holding all 16: the latent projections are linear, so
    each share's ``W_b`` part adds; nothing is dropped in any share."""
    x, w = layer_input(), expert_weights()
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x, w: ref.latent_moe(x, w, SIZES))(x, w)
    y, counters = run_experts(x, w, 0, 16)
    close(y, whole)
    assert float(counters["moe.dropped"]) == 0.0 \
        and float(counters["moe.local_assignments"]) == B * S * 3
    parts = [run_experts(x, w, 4 * r, 4, shared=r == 0) for r in range(4)]
    close(sum(p[0] for p in parts), whole)
    assert sum(float(p[1]["moe.local_assignments"]) for p in parts) \
        == B * S * 3
    assert all(float(p[1]["moe.dropped"]) == 0.0 for p in parts)


def test_a_gated_expert_in_the_latent_is_apart_from_the_reference():
    """The same layer with SwiGLU experts (a gate matrix beside ``w_up``:
    its negative, since ``silu(a) a`` is near ``relu(a)^2`` for large
    ``|a|``) is another function: the activation is read."""
    x, w = layer_input(), expert_weights()
    y, _ = run_experts(x, w, 0, 16)
    params = dict(EXPERTS, experts_held=16, first_held=0,
                  activation="swiglu")
    gated = dict(w, w_gate=-w["w_up"], ws_gate=-w["ws_up"])
    other = jax.jit(lambda x, w: RoutedExpertsOp().emit(
        params, [x], w, f32_ctx(), "experts")[0])(x, gated)
    rf.apart(other, y)


SIZES = {"num_experts_per_tok": 3, "n_routed_experts": 16,
         "num_experts_published": 16, "routed_scaling_factor": 5.0,
         "moe_latent_size": 32, "moe_intermediate_size": 48,
         "first_held_expert": 0}


def test_the_balancing_rule_hands_the_loads_over_as_the_bias_gradient():
    """With a ``bias_step`` the layer's output is what it was, the term
    it adds to the loss is zero, and that term's gradient by the bias is
    every published expert's assignments over the uniform share, whatever
    share is held: what the reference's rule takes the sign of."""
    x, w = layer_input(), expert_weights()
    params = dict(EXPERTS, experts_held=4, first_held=4, bias_step=1e-3)
    w = dict(w, w_up=w["w_up"][4:8], w_down=w["w_down"][4:8])

    def layer(bias, training=True):
        ctx = f32_ctx(training)
        (y,) = RoutedExpertsOp().emit(params, [x], dict(w, bias=bias), ctx,
                                      "experts")
        return sum(ctx.aux_losses, jnp.float32(0.0)), (y, ctx.aux_losses)

    (term, (y, aux)), excess = jax.jit(
        jax.value_and_grad(layer, has_aux=True))(w["bias"])
    assert len(aux) == 1 and float(term) == 0.0
    assert not jax.jit(lambda b: layer(b, False))(w["bias"])[1][1]  # eval
    plain, _ = run_experts(x, expert_weights(), 4, 4)
    close(y, plain, 1e-6)
    with jax.default_matmul_precision("highest"):
        moved = ref.bias_after_step(x, w, SIZES, 1e-3)
    assert np.abs(excess).max() > 10 and abs(float(excess.sum())) < 1e-3
    close(w["bias"] - 1e-3 * jnp.sign(excess), moved, 1e-7)
    assert len(set(np.round((moved - w["bias"]) * 1e3).tolist())) > 1
    # no step, no parameter and no weight that moves so: the graphs of
    # the classes that have none are what they were
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, B, S, dataclasses.replace(
        held(), router_bias_update_rate=0.0))
    assert all("bias_step" not in l.params and not any(
        v.sign_step for v in l.weights) for l in ff.layers)


def test_the_train_step_moves_the_bias_by_the_sign_and_not_by_adam(
        tiny, tiny_step):
    """``Executor._apply_update``: every routers' bias goes down a
    ``router_bias_update_rate`` where the step sent its expert more than
    the uniform share and up where less; the other weights are Adam's."""
    ff, mc, _, params = tiny
    _, grads = tiny_step
    ex = ff.executor
    assert sorted(ex._sign_steps) == [("experts_0", "bias"),
                                      ("experts_2", "bias")]
    assert set(ex._sign_steps.values()) == {mc.router_bias_update_rate}
    layers = ("experts_0", "experts_2")
    params, grads = ({n: tree[n] for n in layers} for tree in (params, grads))
    new, _ = ex._apply_update(params, grads,
                              ex.optimizer.init_state(params),
                              jnp.int32(1))
    for name in layers:
        close(new[name]["bias"], params[name]["bias"]
              - 1e-3 * jnp.sign(grads[name]["bias"]), 1e-7)
        assert np.any(new[name]["bias"] != params[name]["bias"])
        rf.apart(new[name]["wg"] - params[name]["wg"],
                 jnp.zeros_like(params[name]["wg"]), 1e-4)


# ----------------------------------------------------------------------
# rematerialised blocks, the recorder
# ----------------------------------------------------------------------
def test_the_remat_finder_pairs_an_expert_layer_with_its_mixer():
    """``EMEMEMEMEM*``: five blocks of [moe, mamba]; the attention layer
    that follows has no partner and is held whole, though the finder
    takes a mixer for an attention layer elsewhere."""
    mc = dataclasses.replace(held(), num_hidden_layers=11, layer_types=None,
                             hybrid_override_pattern="EMEMEMEMEM*")
    ff, _ = build(remat="blocks", model_cfg=mc)
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    assert (start, unit, reps) == (1, 6, 5)
    assert ff.executor._remat[:3] == (start, unit, reps)
    block = [l.op_type.name for l in ff.layers[start:start + unit]]
    assert block == ["OP_RMSNORM", "OP_ROUTED_EXPERTS", "OP_EW_ADD",
                     "OP_RMSNORM", "OP_STATE_SPACE_MIXER", "OP_EW_ADD"]
    after = [l.name for l in ff.layers[start + unit * reps:]]
    assert after[:3] == ["operator_norm_10", "attn_10", "operator_res_10"]


def test_a_rematerialised_step_is_the_step(tiny, tiny_step):
    _, mc, batch, params = tiny
    remat, _ = build(remat="blocks", model_cfg=mc)
    assert remat.executor._remat[:3] == (1, 6, 2)
    rf.same_step(rf.step_and_gradients(remat, params, batch), tiny_step)
    (_, bm), _ = tiny_step
    assert float(bm[COUNTER_PREFIX + "ssm.layers"]) == 2.0
    assert float(bm[COUNTER_PREFIX + "moe.dropped"]) == 0.0


def test_the_layers_say_their_sizes_and_the_scopes_are_there():
    """The instants' fields, and the scopes in the lowered step of the
    rematerialised model, forward AND backward: the transposes of
    ``_rows_for``, ``_combine`` and ``_sorted_domain`` (``custom_vjp``
    backwards, traced when the step is transposed) come out under the
    scope their forward call was made in, so a reader that sums
    ``moe.latent`` has the backward's gathers and grouped products
    too."""
    events.enable()
    events.clear()
    try:
        ff, mc = build(model_cfg=held(), remat="blocks")
        batch = data(mc)
        text = jax.jit(jax.grad(lambda p: rf.forward(ff, p, batch)[0])
                       ).lower(ff.params).as_text(debug_info=True)
        said = {name: {e["attrs"]["layer"]: e["attrs"]
                       for e in events.events() if e["name"] == name}
                for name in ("ssm.layer", "moe.route")}
    finally:
        events.disable()
        events.clear()
    assert sorted(said["ssm.layer"]) == ["mamba_1", "mamba_3"] \
        and sorted(said["moe.route"]) == ["experts_0", "experts_2"]
    assert {k: said["ssm.layer"]["mamba_1"][k] for k in (
        "heads", "groups", "chunk", "impl")} == {
        "heads": 8, "groups": 2, "chunk": 16, "impl": "plain"}
    assert {k: said["moe.route"]["experts_0"][k] for k in (
        "latent", "activation", "top_k", "experts_published",
        "experts_held", "first_held", "bias_step")} == {
        "latent": 32, "activation": "relu2", "top_k": 3,
        "experts_published": 16, "experts_held": 4, "first_held": 4,
        "bias_step": 1e-3}
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("experts_0/moe.route", "experts_0/moe.latent",
                  "experts_0/moe.shared", "ssm.scan"):
        assert any(scope in l for l in locs), scope
    back = [l for l in locs if "transpose(" in l and "/experts_" in l]
    for op in ("gather", "ragged_dot_general"):
        assert sum(l.endswith(f"/moe.latent/{op}") for l in back) >= 2, op
    assert {l.rsplit("/", 1)[1] for l in back
            if "/moe." not in l} <= {"reshape"}
