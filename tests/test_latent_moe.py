"""The DeepSeek-V3-shaped decoder (latent attention, routed experts with
a shared one, a multi-token-prediction module) against its plain
reference (``benchmarks/reference/latent_moe_ref.py``), at a small size
on the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(the sorted grouped product against a loop over experts, a fused
softmax against an explicit one). ``TOL`` = 2e-4 relative to the largest
entry is forty times what they read (5e-6) and a thousand times under a
wrong gate, a dropped assignment or a rotation by the wrong pair, each
of which moves the result by 1e-1 or more.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.nlp import LatentMoEConfig, build_latent_moe
from flexflow_tpu.obs import events
from flexflow_tpu.ops import moe_ops
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX, is_count
from rank_family import B, close, f32_ctx

ref = rf.reference("latent_moe_ref")
S = 32
build = functools.partial(rf.build, LatentMoEConfig, build_latent_moe, seq=S,
                          attention="xla")
data = functools.partial(rf.data, seq=S)


tiny, tiny_step = rf.fixtures(build, data)


def test_the_bias_changes_some_tokens_choice(tiny):
    """The tests below would not see a bias that is ignored unless it
    decides something: with these weights it does."""
    ff, mc, batch = tiny
    w = ff.params["experts_1"]
    assert float(jnp.max(jnp.abs(w["bias"]))) > 0
    x = jax.random.normal(jax.random.key(3), (64, mc.hidden_size))
    s = jax.nn.sigmoid(x @ w["wg"])
    k = mc.num_experts_per_tok
    with_b = jnp.sort(jax.lax.top_k(s + w["bias"], k)[1], -1)
    without = jnp.sort(jax.lax.top_k(s, k)[1], -1)
    assert bool(jnp.any(with_b != without))


@pytest.mark.parametrize("attention", ["flash", "xla"])
def test_heads_and_loss_match_the_reference(attention):
    ff, mc = build(attention=attention)
    batch = data(mc)
    mtp_loss = next(l for l in ff.executor.program.layers
                    if l.name == "mtp_loss")

    def heads(params):
        loss, _, outs, _, capture = rf.forward(ff, params, batch,
                                               training=False)
        return loss, outs[0], capture.get(mtp_loss.inputs[0].guid)

    loss, probs, mtp_logits = jax.jit(heads)(rf.on_one_device(ff.params))
    main, mtp = rf.reference_call(ref.heads, ff, mc, ff.params, batch)
    close(jnp.log(probs), main)
    close(jax.nn.log_softmax(mtp_logits, -1), mtp)
    close(loss, rf.reference_loss(ref, ff, mc, ff.params, batch))
    assert set(ff.executor.resolved_attention_impls.values()) == {
        attention}


def test_every_weights_gradient_matches_the_reference(tiny, tiny_step):
    ff, mc, batch = tiny
    _, got = tiny_step
    want = rf.reference_gradients(ref, ff, mc, ff.params, batch)
    assert {n for n in got} == {n for n in want}
    for name in got:
        for key in got[name]:
            assert float(jnp.max(jnp.abs(want[name][key]))) > 0 \
                or key == "bias", (name, key)
            close(got[name][key], want[name][key])
    # the correction bias decides the choice and gets no gradient
    for name in got:
        if name.startswith("experts_"):
            assert not np.any(np.asarray(got[name]["bias"]))
    # the norms on the two latents and the router were among them
    assert {"q_norm", "kv_norm"} <= set(got["attn_0"])
    assert "wg" in got["experts_mtp"]


def _op_params(mc, first, held):
    return dict(num_experts=mc.n_routed_experts,
                top_k=mc.num_experts_per_tok,
                expert_dim=mc.moe_intermediate_size,
                shared_dim=mc.moe_intermediate_size, experts_held=held,
                first_held=first, scale=mc.routed_scaling_factor)


def _experts_layer(mc, x, weights, first, held, with_shared=True):
    """One routed-experts op holding experts ``first .. first + held``."""
    w = {k: v for k, v in _share(weights, first, held).items()
         if with_shared or not k.startswith("ws_")}

    def layer(x, w):
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(_op_params(mc, first, held), [x], w,
                                      ctx, "experts")
        return y, ctx.counters

    return jax.jit(layer)(x, w)


def _share(weights, first, held):
    """The weights one share holds: its block of the stacked experts."""
    return {k: (v[first:first + held] if k in ("w_gate", "w_up", "w_down")
                else v) for k, v in weights.items()}


def _layer_sizes(mc, held, first):
    return dict(dataclasses.asdict(mc), n_routed_experts=held,
                first_held_expert=first)


@pytest.mark.parametrize("shared", [True, False])
def test_the_shares_add_up_to_the_uncut_layer(tiny, shared):
    """model-configs guide, section 4: the routed parts that the four
    shares of four experts each compute, plus the shared expert counted
    once, are the uncut reference's layer output. Without a shared
    expert (the hybrid convolution/attention configuration's layer, held
    to ITS reference's ``routed``) the shares alone add up to it."""
    ff, mc, _ = tiny
    w = ff.params["experts_2"]
    x = jax.random.normal(jax.random.key(5), (B, S, mc.hidden_size))
    routed = sum(_experts_layer(mc, x, w, first, 4, with_shared=False)[0]
                 for first in (0, 4, 8, 12))
    if shared:
        whole, _ = _experts_layer(mc, x, w, 0, 4)
        once = whole - _experts_layer(mc, x, w, 0, 4, with_shared=False)[0]
        sizes, plain = _layer_sizes, ref
    else:
        once = 0.0
        plain = rf.reference("hybrid_conv_moe_ref")
        w = {k: v for k, v in w.items() if not k.startswith("ws_")}

        def sizes(mc, held, first):
            return {"num_experts_per_tok": mc.num_experts_per_tok,
                    "routed_scaling_factor": mc.routed_scaling_factor,
                    "first_held_expert": first}
    with jax.default_matmul_precision("highest"):
        want = plain.routed(x, w, sizes(mc, 16, 0))
        if shared:
            want = want + ref.shared(x, w)
        one_share = plain.routed(x, _share(w, 4, 4), sizes(mc, 4, 4))
    close(routed + once, want)
    # and one share alone is the reference's same share, not the whole
    close(_experts_layer(mc, x, w, 4, 4, with_shared=False)[0], one_share)
    assert float(jnp.max(jnp.abs(one_share - want))) > 0.1


@pytest.mark.parametrize("first,chosen,tokens,bound,busiest,whole", [
    (0, [3, 8, 9, 10], B * S, B * S, B * S, 0),
    (4, [3, 8, 9, 10], B * S, 0, 0, 0),
    # every choice of every token at this share: twice the budget's rows
    (0, [0, 1, 2, 3], 8 * S, 4 * 8 * S, 8 * S, 1)])
def test_nothing_is_dropped_under_the_worst_imbalance(
        tiny, first, chosen, tokens, bound, busiest, whole):
    """A router that sends every token to experts 3, 8, 9 and 10: the
    share holding 0-3 gets every token at ONE expert, the share holding
    4-7 gets none. One that sends every token to 0, 1, 2 and 3 hands
    the first share a row for EVERY assignment, over any budget: the
    step runs a second chunk and says so. All are the reference, and
    nothing is dropped."""
    ff, mc, _ = tiny
    w = dict(ff.params["experts_2"])
    w["wg"] = jnp.zeros_like(w["wg"])
    w["bias"] = jnp.zeros_like(w["bias"]).at[jnp.array(chosen)].set(1.)
    x = jax.random.normal(jax.random.key(7), (1, tokens, mc.hidden_size))
    y, counters = _experts_layer(mc, x, w, first, 4)
    with jax.default_matmul_precision("highest"):
        want = ref.routed(x, _share(w, first, 4),
                          _layer_sizes(mc, 4, first)) + ref.shared(x, w)
    close(y, want)
    assert float(counters["moe.dropped"]) == 0
    assert float(counters["moe.local_assignments"]) == bound
    assert float(counters["moe.load_max"]) == busiest
    assert float(counters["moe.overflow"]) == whole
    assert (RoutedExpertsOp.rows_multiplied(tokens, _op_params(mc, first, 4))
            < bound) == bool(whole)


@pytest.mark.parametrize("lost", [1, 7])
def test_a_miscounted_group_shows_as_dropped(tiny, monkeypatch, lost):
    """``moe.dropped`` compares what the router chose with the rows the
    grouped products met under the chosen expert's weights: group sizes
    that lose ``lost`` rows of expert 1 leave at least those unreached
    (and shift every later group onto its neighbour's weights)."""
    ff, mc, _ = tiny
    x = jax.random.normal(jax.random.key(9), (B, S, mc.hidden_size))
    _, sound = _experts_layer(mc, x, ff.params["experts_2"], 0, 4)
    assert float(sound["moe.dropped"]) == 0
    assert float(sound["moe.load_max"]) > lost
    sizes = moe_ops.group_sizes
    monkeypatch.setattr(moe_ops, "group_sizes", lambda g, held: sizes(
        g, held).at[1].add(-lost))
    _, short = _experts_layer(mc, x, ff.params["experts_2"], 0, 4)
    assert float(short["moe.local_assignments"]) == \
        float(sound["moe.local_assignments"])
    assert float(short["moe.dropped"]) >= lost


def _wide_layer(shared, overflow, seed=13):
    """A share of 4 of 32 experts under 256 tokens x top 4: 1,024 sorted
    rows against a budget of 512, two chunks. ``overflow`` adds
    2 to the held experts' bias: every choice of every token is theirs,
    and the router still has a gradient (the gates are its scores)."""
    mc = dataclasses.replace(LatentMoEConfig.tiny(), n_routed_experts=32,
                             routed_scaling_factor=2.5 if shared else 1.0)
    e, f, first, held = mc.hidden_size, mc.moe_intermediate_size, 8, 4
    ks = iter(jax.random.split(jax.random.key(seed), 9))

    def draw(*shape, scale):
        return scale * jax.random.normal(next(ks), shape)
    w = {"wg": draw(e, 32, scale=0.3), "bias": draw(32, scale=0.05),
         "w_gate": draw(32, e, f, scale=0.2), "w_up": draw(32, e, f, scale=0.2),
         "w_down": draw(32, f, e, scale=0.2)}
    if shared:
        w.update(ws_gate=draw(e, f, scale=0.2), ws_up=draw(e, f, scale=0.2),
                 ws_down=draw(f, e, scale=0.2))
    if overflow:
        w["bias"] = w["bias"].at[first:first + held].add(2.0)
    x = draw(2, 128, e, scale=1.0)
    return mc, x, w, first, held


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["inside_the_budget", "over_it"])
@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared_expert", "no_shared_expert"])
def test_one_chunk_or_two_are_the_reference_in_value_and_gradient(
        shared, overflow):
    """At both configurations' shapes of layer (a shared expert and a
    scale; neither) the step that fits the budget and the step that
    overflows it give the reference's output and its gradient for the
    input and every weight, say whether a second chunk ran, and drop
    nothing."""
    mc, x, w, first, held = _wide_layer(shared, overflow)
    plain = ref if shared else rf.reference("hybrid_conv_moe_ref")
    sizes = {"num_experts_per_tok": mc.num_experts_per_tok,
             "routed_scaling_factor": mc.routed_scaling_factor,
             "first_held_expert": first}

    def program(x, w):
        y, counters = _experts_layer(mc, x, w, first, held, shared)
        return jnp.sum(jnp.sin(y)), (y, counters)

    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            y = plain.routed(x, _share(w, first, held), sizes)
            if shared:
                y = y + ref.shared(x, w)
        return jnp.sum(jnp.sin(y)), y

    assert RoutedExpertsOp.rows_multiplied(
        256, _op_params(mc, first, held)) == 512
    (_, (y, counters)), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(x, w)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(x, w)
    close(y, want_y)
    close(got[0], want[0])
    for key in w:
        assert float(jnp.max(jnp.abs(want[1][key]))) > 0 or key == "bias"
        close(got[1][key], want[1][key])
    for key in ("w_gate", "w_up", "w_down"):    # and none for the absent
        assert not np.any(np.asarray(got[1][key][:first]))
    assert float(counters["moe.overflow"]) == overflow
    assert float(counters["moe.dropped"]) == 0
    bound = float(counters["moe.local_assignments"])
    assert (bound == 1024) if overflow else (0 < bound <= 512)


def test_a_budget_forced_on_an_overflowing_step_shows_as_dropped(
        monkeypatch):
    """The fallback broken: a loop over the further chunks that runs
    none of them. The loop counts the chunks it ran, so the rows past
    the budget read as dropped, and not as an overflow handled."""
    mc, x, w, first, held = _wide_layer(True, overflow=True)
    _, sound = _experts_layer(mc, x, w, first, held)
    assert float(sound["moe.dropped"]) == 0 == 1 - float(
        sound["moe.overflow"])
    monkeypatch.setattr(jax.lax, "fori_loop",
                        lambda lower, upper, body, start: start)
    jax.clear_caches()           # the loops' traces are cached by shape
    _, forced = _experts_layer(mc, x, w, first, held)
    jax.clear_caches()
    assert float(forced["moe.local_assignments"]) == 1024
    assert float(forced["moe.overflow"]) == 0
    assert float(forced["moe.dropped"]) == 1024 - 512


@pytest.mark.parametrize("tokens,k,held,published,budget", [
    (4096, 8, 16, 256, 4096),        # joyai_llm_flash.train.1chip: 1/8
    (8192, 4, 8, 64, 8192),          # lfm2_24b_a2b.train.1chip: 1/4
    (4096, 8, 8, 256, 2048),         # a 32nd held, at the default 2 shares
    (4096, 8, 256, 256, 32768),      # every expert held: every row
    (4096, 8, 128, 256, 32768),      # half of them: twice that is all
    (1000, 6, 5, 160, 512),          # 375 rows wanted: rounded up
    (64, 4, 4, 16, 256)])            # fewer rows than one round of 512
def test_the_row_budget_is_read_from_the_shapes(tokens, k, held,
                                                published, budget):
    params = dict(num_experts=published, experts_held=held, top_k=k)
    assert RoutedExpertsOp.rows_multiplied(tokens, params) == budget


@pytest.mark.parametrize("held,loops", [(16, 0), (8, 0), (4, 1)])
def test_a_layer_loops_only_where_the_budget_is_not_every_row(
        tiny, held, loops):
    """With every expert held, or half of them, the budget is the whole
    sort and the traced layer holds neither a loop nor a branch; a
    quarter of them under 1,024 rows holds the one loop over the chunks
    past the first."""
    ff, mc, _ = tiny
    x = jnp.zeros((1, 256, mc.hidden_size))
    text = str(jax.make_jaxpr(lambda x, w: _experts_layer(
        mc, x, w, 0, held)[0])(x, ff.params["experts_2"]))
    assert text.count(" while[") == loops
    assert " cond[" not in text


def test_rematerialised_blocks_give_the_same_step_and_their_counters(
        tiny, tiny_step):
    """``remat = "blocks"`` wraps the expert layers in ``jax.checkpoint``:
    same loss, same gradients, and the layers' counters come out of the
    blocks (summed over the two in the run and the module's one)."""
    _, mc, batch = tiny
    remat, _ = build(remat="blocks")
    assert remat.executor._remat is not None
    start, unit, reps = remat.executor._remat[:3]
    block = remat.executor.program.layers[start:start + unit]
    assert [l.name for l in block][:2] == ["input_norm_1", "attn_1"]
    assert reps == 2 and block[-2].name == "experts_1"
    (l0, bm0), g0 = tiny_step
    (l1, bm1), g1 = rf.step_and_gradients(remat, remat.params, batch)
    close(l1, l0, 1e-6)
    for name in g0:
        for key in g0[name]:
            close(g1[name][key], g0[name][key], 1e-5)
    for key in ("moe.local_assignments", "moe.dropped", "moe.overflow",
                "moe.load_max", "moe.load_mean"):
        assert float(bm1[COUNTER_PREFIX + key]) == \
            float(bm0[COUNTER_PREFIX + key])
    # every assignment is local when all 16 experts are held: 3 layers
    assert float(bm1[COUNTER_PREFIX + "moe.local_assignments"]) == \
        3 * B * S * mc.num_experts_per_tok
    assert float(bm1[COUNTER_PREFIX + "moe.dropped"]) == 0
    # the ops own their counters' names: the runtime lists none
    assert {k for k in bm1 if is_count(k)} == {
        COUNTER_PREFIX + "moe." + k for k in (
            "local_assignments", "dropped", "overflow", "load_max",
            "load_mean")}


def test_fit_records_instants_and_counters_and_leaves_the_bias():
    events.enable()
    events.clear()
    try:
        ff, mc = build(remat="blocks", attention="flash")
        batch = data(mc)
        x = [np.asarray(batch["input_ids"]),
             np.asarray(batch["position_ids"])]
        bias = np.asarray(ff.params["experts_1"]["bias"]).copy()
        gate = np.asarray(ff.params["experts_1"]["wg"]).copy()
        hist = ff.fit(x=x, y=np.asarray(batch["label"]), epochs=3,
                      verbose=False)
        assert hist[-1]["loss"] < hist[0]["loss"]
        # Adam from zeroed moments leaves what gets no gradient where the
        # seed drew it: the correction bias, and not the router beside it
        assert np.array_equal(ff.params["experts_1"]["bias"], bias)
        assert not np.array_equal(ff.params["experts_1"]["wg"], gate)
        routes = [e["attrs"] for e in events.events()
                  if e["name"] == "moe.route"]
        assert {r["layer"] for r in routes} == {
            "experts_1", "experts_2", "experts_mtp"}
        # every expert held: the budget the products are handed is
        # every row of the sort
        assert all(r["experts_published"] == 16 and r["experts_held"] == 16
                   and r["rows_budget"] == r["rows_multiplied"] == B * S * 4
                   for r in routes)
        grids = [e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"]
        assert {g["kernel"] for g in grids} == {
            "flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"}
        c = events.counters()
        # 3 steps x 3 expert layers x every assignment
        assert c["moe.local_assignments"] == 3 * 3 * B * S * 4
        assert c["moe.dropped"] == 0 == c["moe.overflow"]
        assert c["moe.load_max"] >= c["moe.load_mean"] > 0
    finally:
        events.disable()
        events.clear()


def test_stacked_experts_take_their_fans_per_expert(tiny):
    """Glorot's limit for a stacked weight is one expert's
    sqrt(6 / (in + out)), not the stack's: the initial loss of the
    benchmark's cell depends on it."""
    ff, mc, _ = tiny
    w = np.asarray(ff.params["experts_1"]["w_gate"])
    limit = np.sqrt(6.0 / (mc.hidden_size + mc.moe_intermediate_size))
    assert 0.9 * limit < np.abs(w).max() <= limit
    wq_b = np.asarray(ff.params["attn_0"]["wq_b"])
    limit = np.sqrt(6.0 / (mc.q_lora_rank + mc.num_attention_heads * (
        mc.qk_nope_head_dim + mc.qk_rope_head_dim)))
    assert 0.9 * limit < np.abs(wq_b).max() <= limit


def test_the_builder_refuses_what_it_cannot_hold():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, 64))
    with pytest.raises(ValueError, match="are not among"):
        ff.routed_experts(x, 16, 4, 32, experts_held=8, first_held=12)
    with pytest.raises(ValueError, match="top_k"):
        ff.routed_experts(x, 4, 8, 32)


@pytest.mark.parametrize("tokens", [B * S, 16 * S],
                         ids=["every_row", "budget_of_half"])
def test_rows_the_grouped_products_leave_unwritten_reach_nothing(
        tiny, monkeypatch, tokens):
    """On the TPU ``jax.lax.ragged_dot`` leaves the rows past its groups
    unwritten, in its output and in the cotangent of its left operand;
    the CPU's writes zeros there, which hid that the op once summed
    those rows' cotangents into the tokens' gradients (found on the
    chip: gradients 1e5 times the reference's). Here the product is
    made to leave NaN where the chip leaves whatever was in memory: the
    layer's output and every gradient must not notice, whether the
    products are handed every row or a budget whose tail is unwritten
    (and whose last row the clipped token-side gathers read)."""
    ff, mc, _ = tiny
    w = ff.params["experts_2"]
    x = jax.random.normal(jax.random.key(11), (1, tokens, mc.hidden_size))
    assert RoutedExpertsOp.rows_multiplied(
        tokens, _op_params(mc, 4, 4)) == min(4 * tokens, 1024)
    real = jax.lax.ragged_dot

    faked = []

    def leaves_rows_unwritten(lhs, rhs, sizes, **kw):
        faked.append(lhs.shape)

        def written(sizes):
            return (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]

        # the sizes are an argument: inside the loop over chunks they are
        # that trace's values, which a custom rule may not close over
        @jax.custom_vjp
        def product(lhs, rhs, sizes):
            return jnp.where(written(sizes), real(lhs, rhs, sizes, **kw),
                             jnp.nan)

        def bwd(res, g):
            # the transposed products read and write the groups' rows only
            lhs, rhs, sizes = res
            d_lhs, d_rhs = jax.vjp(
                lambda a, b: real(a, b, sizes, **kw), lhs, rhs)[1](
                    jnp.where(written(sizes), g, 0))
            return jnp.where(written(sizes), d_lhs, jnp.nan), d_rhs, None

        product.defvjp(lambda *a: (product(*a), a), bwd)
        return product(lhs, rhs, sizes)

    def loss(x, w):
        y, _ = _experts_layer(mc, x, w, 4, 4)     # 12 of 16 experts absent
        return jnp.sum(jnp.sin(y)), y

    def both():
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True))(x, w)

    (_, y0), g0 = both()
    monkeypatch.setattr(jax.lax, "ragged_dot", leaves_rows_unwritten)
    jax.clear_caches()           # the chunk's trace is cached by shape
    (_, y1), g1 = both()
    jax.clear_caches()
    assert len(faked) >= 3 and np.array_equal(np.asarray(y0), np.asarray(y1))
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert np.all(np.isfinite(np.asarray(b)))
        assert np.array_equal(np.asarray(a), np.asarray(b))
