"""The latent-attention decoder with sparse experts whose residual is four
streams under manifold-constrained hyper-connections and whose rotary
embedding is rescaled by YaRN, against its plain reference
(``benchmarks/reference/mhc_latent_moe_ref.py``), at a small size on the
CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``. The two share no code: the program holds
its maps tokens-last and iterates in a ``lax.scan`` inside a
rematerialised function, the reference writes the 20 iterations out on
(tokens, n, n) matrices. ``TOL`` = 2e-4 relative to the largest entry is
some twenty times what they read (1e-6 to 8e-6) and five hundred times
under the model without its token-dependent maps or without YaRN (1e-1
of the log-probabilities), or with 3 iterations for 20 (3e-2).
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
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.models.nlp import (LatentMoEConfig, XingRankConfig,
                                     build_latent_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops import hyper_ops
from flexflow_tpu.ops.hyper_ops import HyperConnectionOp
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.nn_ops import (LatentAttentionOp, rope_frequencies,
                                     yarn_correction_range, yarn_mscale)
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from flexflow_tpu.search import opshard
from rank_family import B, TOL, close, f32_ctx

ref = rf.reference("mhc_latent_moe_ref")
S = 40
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"}
build = functools.partial(rf.build, XingRankConfig, build_latent_moe, seq=S,
                          attention="xla")
data = functools.partial(rf.data, seq=S)


tiny, tiny_step = rf.fixtures(build, data)


@pytest.fixture(scope="module")
def both_gradients(tiny, tiny_step):
    ff, mc, batch = tiny
    return tiny_step[1], rf.reference_gradients(ref, ff, mc, ff.params,
                                                batch)


@pytest.fixture(scope="module")
def tiny_eval(tiny):
    """``(loss, metrics, probabilities)`` of the tiny model's forward."""
    ff, _, batch = tiny
    return rf.program(ff, ff.params, batch, training=False)


# ----------------------------------------------------------------------
# the whole model against the reference
# ----------------------------------------------------------------------
def test_log_probabilities_and_both_losses(tiny, tiny_eval):
    ff, mc, batch = tiny
    loss, bm, probs = tiny_eval
    main, mtp = rf.reference_call(ref.heads, ff, mc, ff.params, batch)
    close(jnp.log(probs), main)
    assert mtp is not None
    want = rf.reference_loss(ref, ff, mc, ff.params, batch)
    close(loss, want, 1e-5)
    ce_main = -jnp.mean(jnp.take_along_axis(main, batch["label"], -1))
    ce_mtp = -jnp.mean(jnp.take_along_axis(
        mtp[:, :-2], batch["input_ids"][:, 2:, None], -1))
    close(want, ce_main + mc.mtp_loss_weight * ce_mtp, 1e-6)
    # 3 trunk layers and the module's: two sub-layers each
    assert float(bm[COUNTER_PREFIX + "mhc.sublayers"]) == 8
    assert float(bm[COUNTER_PREFIX + "mhc.clamped"]) == 0
    assert 0 < float(bm[COUNTER_PREFIX + "mhc.sum_err"]) < 8 * 0.1


GRADIENTS = [  # (layer, weight): every kind of weight the model has
    ("attn_res_1_pre", "phi"), ("attn_res_1_pre", "b_pre"),
    ("attn_res_1_pre", "b_post"), ("attn_res_1_pre", "b_res"),
    ("attn_res_1_pre", "alpha"), ("mlp_res_0_pre", "phi"),
    ("mlp_res_1_pre", "b_res"), ("mlp_res_mtp_pre", "alpha"),
    ("attn_res_mtp_pre", "phi"), ("embed_tokens", "kernel"),
    ("input_norm_0", "scale"), ("attn_0", "wq_a"), ("attn_1", "wq_b"),
    ("attn_1", "q_norm"), ("attn_2", "wkv_a"), ("attn_2", "wkv_b"),
    ("attn_mtp", "wo"), ("gate_proj_0", "kernel"),
    ("down_proj_0", "kernel"), ("experts_1", "wg"),
    ("experts_1", "w_gate"), ("experts_2", "w_down"),
    ("experts_mtp", "ws_up"), ("mtp_eh_proj", "kernel"),
    ("final_norm", "scale"), ("lm_head", "kernel")]


@pytest.mark.parametrize("layer,weight", GRADIENTS)
def test_gradients_against_the_references(both_gradients, layer, weight):
    got, want = both_gradients
    assert float(jnp.max(jnp.abs(want[layer][weight]))) > 1e-5
    close(got[layer][weight], want[layer][weight])


def test_every_gradient_against_the_references(both_gradients):
    """All of them at once, those that are zero but for rounding too
    (the first sub-layer's ``b_res``: its streams are copies, and a
    matrix with unit row sums maps copies to themselves; a first
    sub-layer's ``b_pre``: the sub-layer's norm undoes ``Hpre``'s scale):
    against a floor of 1e-2 of the typical gradient."""
    got, want = both_gradients
    assert set(got) == set(want)
    for name in got:
        for key in got[name]:
            close(got[name][key], want[name][key], floor=1e-4)
    for name in got:
        if name.startswith("experts_"):     # no gradient by construction
            assert not np.any(np.asarray(got[name]["bias"]))


@pytest.mark.parametrize("wrong", ["no_dynamic_maps", "no_yarn",
                                   "three_iterations"])
def test_a_wrong_model_is_told_apart(tiny, tiny_eval, wrong):
    """The comparison that passes the program fails each of: the maps
    without their token-dependent part, plain rotary frequencies and
    scale, 3 Sinkhorn iterations for 20."""
    ff, mc, batch = tiny
    _, _, probs = tiny_eval
    knobs = {"no_dynamic_maps": {"dynamic_maps": True},
             "no_yarn": {"yarn": True}, "three_iterations": {}}[wrong]
    if wrong == "three_iterations":
        mc = dataclasses.replace(mc, hc_sinkhorn_iters=3)
    with ref.without(**knobs):
        other, _ = rf.reference_call(ref.heads, ff, mc, ff.params, batch)
    with pytest.raises(AssertionError, match="relative error"):
        close(jnp.log(probs), other, 50 * TOL)


# ----------------------------------------------------------------------
# the maps and the projection
# ----------------------------------------------------------------------
HC_PARAMS = dict(stage="pre", iters=20, eps=1e-6, norm_eps=1e-6,
                 clamp=[-30.0, 30.0])


def hc_weights(n=4, c=24, seed=3, **over):
    op = HyperConnectionOp()
    rng = np.random.default_rng(seed)
    w = {}
    for s in op.weights(HC_PARAMS, [(B, S, n, c)], [DataType.DT_FLOAT]):
        w[s.name] = jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                                * (0.1 if s.name == "phi" else 0.5))
    w["alpha"] = jnp.ones(3, jnp.float32)
    w["b_res"] = w["b_res"] + 2 * jnp.eye(n)
    w.update(over)
    return w


@pytest.mark.parametrize("iters", [1, 5, 20])
def test_the_scan_is_the_literal_iterations(iters):
    """(n, n, tokens) in a ``lax.scan`` against (tokens, n, n) written
    out, rows and columns where each says they are."""
    logits = jnp.asarray(np.random.default_rng(0).normal(
        size=(50, 4, 4)).astype(np.float32) * 1.5)
    got = hyper_ops.sinkhorn(jnp.moveaxis(logits, 0, -1), iters, 1e-6)
    want = ref.sinkhorn_knopp(logits, iters, 1e-6)
    close(jnp.moveaxis(got, -1, 0), want, 1e-5)
    # the rows were normalised last
    close(want.sum(-1), jnp.ones((50, 4)), 2e-5)


def test_twenty_iterations_are_doubly_stochastic():
    """At logits of spread 0.5 no row or column sum of 4,096 tokens is
    1e-5 from 1 after 20 iterations. At the configuration's draw (spread
    1.5 around twice the identity: ``b_res`` and the token-dependent
    part at ``a_res`` = 1) the median token is, and the worst of them is
    1e-2 off: a near-permutation converges slowly. The counter
    ``mhc.sum_err`` reports that worst (PERF.md section 6). 3
    iterations leave the median token 1e-3 off at either."""
    rng = np.random.default_rng(1)

    def off(spread, diagonal, iters):
        logits = spread * jnp.asarray(rng.normal(
            size=(4, 4, 4096)).astype(np.float32)) \
            + diagonal * jnp.eye(4)[..., None]
        m = hyper_ops.sinkhorn(logits, iters, 1e-6)
        assert float(jnp.min(m)) > 0
        return np.asarray(jnp.maximum(jnp.abs(m.sum(0) - 1).max(0),
                                      jnp.abs(m.sum(1) - 1).max(0)))

    assert off(0.5, 0.0, 20).max() < 1e-5
    drawn = off(1.5, 2.0, 20)
    assert np.median(drawn) < 1e-5 and 1e-4 < drawn.max() < 0.2
    assert np.median(off(0.5, 0.0, 3)) > 1e-4
    assert np.median(off(1.5, 2.0, 3)) > 1e-3


def test_the_nodes_are_the_references_sub_layer():
    """``pre`` then ``post`` around a stand-in sub-layer, with every
    token's streams different."""
    n, c = 4, 24
    w = hc_weights(n, c)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(B, S, n, c)).astype(np.float32))
    op = HyperConnectionOp()

    @jax.jit
    def nodes(x, w):
        ctx = f32_ctx()
        u, maps, xs = op.emit(HC_PARAMS, [x], w, ctx, "res_pre")
        assert xs is x  # the third output is the input: nothing is copied
        out, = op.emit({"stage": "post"}, [xs, jnp.tanh(u), maps], {}, ctx,
                       "res")
        return u, maps, out, ctx.counters

    u, maps, out, counters = nodes(x, w)
    sizes = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
             "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    pre, post, res = jax.jit(lambda x, w: ref.stream_maps(x, w, sizes))(x, w)
    close(u, jnp.einsum("bsn,bsnc->bsc", pre, x))
    close(maps[..., :n], post)
    close(maps[..., n:], res.reshape(B, S, n * n))
    close(out, jax.jit(lambda x, w: ref.hyper_connected(
        x, w, sizes, jnp.tanh))(x, w))
    assert float(counters["mhc.sublayers"]) == 1
    assert float(counters["mhc.clamped"]) == 0


def test_the_clamp_is_reached_on_a_forced_input():
    """``b_res`` of 40 on the diagonal and -40 off it: all 16 entries of
    every token's ``Hres~`` meet the clamp, ``Hres`` stays finite and
    near the identity, and no gradient passes a clamped entry."""
    n, c = 4, 24
    forced = 80.0 * jnp.eye(n) - 40.0
    w = hc_weights(n, c, b_res=forced)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(B, S, n, c)).astype(np.float32))
    op = HyperConnectionOp()

    @jax.jit
    def pre_node(x, w):
        ctx = f32_ctx()
        _, maps, _ = op.emit(HC_PARAMS, [x], w, ctx, "res_pre")
        return maps, ctx.counters

    maps, counters = pre_node(x, w)
    assert float(counters["mhc.clamped"]) == B * S * n * n
    res = maps[..., n:].reshape(B, S, n, n)
    assert bool(jnp.all(jnp.isfinite(res)))
    close(res, jnp.broadcast_to(jnp.eye(n), res.shape), 1e-5)
    sizes = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
             "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
    close(res, jax.jit(lambda x, w: ref.stream_maps(x, w, sizes)[2])(x, w),
          1e-5)

    def through(b_res):
        return jnp.sum(op.emit(HC_PARAMS, [x], dict(w, b_res=b_res),
                               f32_ctx(), "res_pre")[1][..., n:] ** 2)
    assert not np.any(np.asarray(jax.jit(jax.grad(through))(forced)))


def test_there_is_no_decode_path():
    ctx = f32_ctx(False)
    ctx.kv_mode = "prefill"
    with pytest.raises(NotImplementedError, match="decode"):
        HyperConnectionOp().emit(HC_PARAMS, [jnp.zeros((B, S, 4, 24))],
                                 hc_weights(), ctx, "res_pre")


def test_the_draw_of_the_maps_weights(tiny):
    """``a_* = 1``, ``b_res`` around twice the identity, ``phi`` Glorot
    over its own fans: ``x phi`` has spread about 1.4."""
    ff, mc, _ = tiny
    w = ff.params["mlp_res_1_pre"]
    n, c = mc.hc_mult, mc.hidden_size
    assert w["phi"].shape == (n * c, n * (n + 2))
    assert hyper_ops.MAPS_DRAW == {"alpha": 1.0, "bias_std": 0.5,
                                   "res_diagonal": 2.0}
    assert np.all(np.asarray(w["alpha"]) == 1.0)
    b_res = np.asarray(w["b_res"])
    assert 1.0 < np.mean(np.diag(b_res)) < 3.0
    assert abs(np.mean(b_res - np.diag(np.diag(b_res)))) < 0.5
    limit = np.sqrt(6.0 / (n * c + n * (n + 2)))
    assert np.max(np.abs(w["phi"])) <= limit
    x = np.random.default_rng(0).normal(size=(500, n * c))
    assert 1.2 < np.std(x @ np.asarray(w["phi"])) < 1.6


# ----------------------------------------------------------------------
# YaRN
# ----------------------------------------------------------------------
def test_the_published_keys_give_the_published_constants():
    assert yarn_correction_range(64, 10000.0, PUBLISHED_YARN) == (10, 23)
    m = yarn_mscale(64, 1)
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert m * m == pytest.approx(2.00474, abs=1e-5)
    plain = np.asarray(rope_frequencies(64, 10000.0))
    got = np.asarray(rope_frequencies(64, 10000.0, PUBLISHED_YARN))
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        got[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 64 * ramp,
        rtol=1e-6)
    np.testing.assert_allclose(
        got, np.asarray(ref.rope_frequencies(64, 10000.0, PUBLISHED_YARN)),
        rtol=1e-6)


@pytest.mark.parametrize("scaling", ["tiny", "published"])
def test_latent_attention_under_yarn(scaling):
    """4 heads of 16 + 8 / 16 (tiny: factor 4 over 16 positions) and of
    16 + 64 / 16 under the published group, against the reference; and
    not the same as without it."""
    e, heads = 48, 4
    yarn, dr = (XingRankConfig.tiny().rope_scaling, 8) \
        if scaling == "tiny" else (PUBLISHED_YARN, 64)
    params = dict(num_heads=heads, q_rank=24, kv_rank=32, nope_dim=16,
                  rope_dim=dr, v_dim=16, rope_theta=10000.0, eps=1e-6,
                  rope_scaling=yarn)
    op = LatentAttentionOp()
    rng = np.random.default_rng(11)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * (1.0 if "norm" in s.name else 0.3))
         for s in op.weights(params, [(B, S, e), (B, S)],
                             [DataType.DT_FLOAT, DataType.DT_INT32])}
    u = jnp.asarray(rng.normal(size=(B, S, e)).astype(np.float32))
    # positions past the original context, where the blend matters
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32) * 97, (B, 1))
    sizes = {"rms_norm_eps": 1e-6, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": dr, "kv_lora_rank": 32,
             "rope_theta": 10000.0, "rope_scaling": yarn}
    def emit(params):
        return jax.jit(lambda u, w: op.emit(
            params, [u, pos], w, f32_ctx(False), "attn")[0])(u, w)

    def reference():
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda u, w: ref.latent_attention(
                u, pos, w, sizes))(u, w)

    got, want = emit(params), reference()
    with ref.without(yarn=True):
        plain = reference()
    close(got, want)
    del params["rope_scaling"]
    close(emit(params), plain)
    assert float(jnp.max(jnp.abs(plain - want))) > 0.1


def test_only_yarn_on_a_rotary_embedding_is_built():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, 48), name="x")
    pos = ff.create_tensor((B, S), DataType.DT_INT32, name="pos")
    with pytest.raises(ValueError, match="yarn"):
        ff.latent_attention(x, pos, 4, 24, 32, 16, 8, 16,
                            rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="yarn"):
        ff.latent_attention(x, pos, 4, 24, 32, 16, 8, 16, rope=False,
                            rope_scaling=PUBLISHED_YARN)


# ----------------------------------------------------------------------
# the share of an 8-chip group
# ----------------------------------------------------------------------
def test_the_head_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the parts of ``wo``'s output that
    eight shares of two heads each compute (``wq_b``, ``wkv_b`` and
    ``wo`` by head; both down-projections and their norms whole on every
    share) add up to the uncut reference's layer of 16 heads."""
    e, heads, dr = 48, 16, 8
    yarn = XingRankConfig.tiny().rope_scaling
    params = dict(num_heads=heads, q_rank=24, kv_rank=32, nope_dim=16,
                  rope_dim=dr, v_dim=16, rope_theta=10000.0, eps=1e-6,
                  rope_scaling=yarn)
    op = LatentAttentionOp()
    rng = np.random.default_rng(12)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * (1.0 if "norm" in s.name else 0.3))
         for s in op.weights(params, [(B, S, e), (B, S)],
                             [DataType.DT_FLOAT, DataType.DT_INT32])}
    u = jnp.asarray(rng.normal(size=(B, S, e)).astype(np.float32))
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))

    @functools.partial(jax.jit, static_argnums=1)
    def emit(cut, held):    # one compiled layer for the eight shares
        return op.emit(dict(params, num_heads=held), [u, pos], cut,
                       f32_ctx(False), "attn")[0]

    def share(first, held):
        cut = dict(w, wq_b=w["wq_b"][:, first:first + held],
                   wkv_b=w["wkv_b"][:, first:first + held],
                   wo=w["wo"][first:first + held])
        return emit(cut, held), cut

    parts = [share(first, 2)[0] for first in range(0, heads, 2)]
    sizes = {"rms_norm_eps": 1e-6, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": dr, "kv_lora_rank": 32,
             "rope_theta": 10000.0, "rope_scaling": yarn}
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(lambda w: ref.latent_attention(u, pos, w, sizes))
        want, one = reference(w), reference(share(2, 2)[1])
    close(sum(parts), want)
    close(parts[1], one)
    assert float(jnp.max(jnp.abs(one - want))) > 0.1


def test_the_expert_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts that eight shares of two experts each compute,
    plus the shared expert counted once, are the uncut reference's
    layer output (top 4 of 16, scale 2)."""
    ff, mc, _ = tiny
    w = rf.on_one_device(ff.params["experts_2"])
    x = jax.random.normal(jax.random.key(5), (B, S, mc.hidden_size))

    def share(first, held, with_shared):
        cut = {k: (v[first:first + held]
                   if k in ("w_gate", "w_up", "w_down") else v)
               for k, v in w.items()
               if with_shared or not k.startswith("ws_")}
        return RoutedExpertsOp().emit(
            dict(num_experts=mc.n_routed_experts,
                 top_k=mc.num_experts_per_tok,
                 expert_dim=mc.moe_intermediate_size,
                 shared_dim=mc.moe_intermediate_size, experts_held=held,
                 first_held=first, scale=mc.routed_scaling_factor),
            [x], cut, f32_ctx(), "experts")[0]

    routed = sum(share(first, 2, False) for first in range(0, 16, 2))
    once = share(0, 2, True) - share(0, 2, False)
    sizes = dataclasses.asdict(mc)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, w: ref.routed(x, w, sizes)
                      + ref.shared(x, w))(x, w)
    close(routed + once, want)
    assert float(jnp.max(jnp.abs(share(0, 2, True) - want))) > 0.1


# ----------------------------------------------------------------------
# sharding, cost and rematerialisation rows of the new op
# ----------------------------------------------------------------------
def hc_layers(ff):
    pre = next(l for l in ff.layers if l.name == "mlp_res_1_pre")
    post = next(l for l in ff.layers if l.name == "mlp_res_1")
    assert pre.op_type == post.op_type == OperatorType.OP_HYPER_CONNECTION
    return pre, post


def test_the_nodes_are_sharded_by_batch_and_sequence_alone(tiny):
    ff, _, _ = tiny
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    axis = next(iter(ff.dmesh.axis_sizes))
    for layer in hc_layers(ff):
        assert [(o.kind, o.out_dim, o.weight_dims)
                for o in opshard.options_for(layer)] == [
                    ("sample", 0, ()), ("attribute", 1, ())]
    pre, post = hc_layers(ff)
    for layer, spec, ok in (
            (post, P(axis, None, None, None), True),
            (post, P(None, axis, None, None), True),
            (post, P(None, None, axis, None), False),      # the streams
            (post, P(None, None, None, axis), False),      # the channels
            (pre, P(None, axis, None), True),
            (pre, P(None, None, axis), False)):
        st = ShardingStrategy(ff.dmesh)
        st.set_op(layer.name, [spec], {})
        found = [f for f in verify_plan(st, ff.layers).errors
                 if "hyper-connection" in f.message]
        assert (not found) == ok, (layer.name, spec, found)


@pytest.mark.parametrize("by", ["batch", "sequence"])
def test_sharded_nodes_are_the_unsharded_ones(by):
    """8 sequences, or 8 spans of positions, one a device of the CPU
    mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n, c = 4, 24
    w = hc_weights(n, c)
    x = jnp.asarray(np.random.default_rng(8).normal(
        size=(8, S, n, c)).astype(np.float32))
    op = HyperConnectionOp()

    def sublayer(x, w):
        u, maps, x = op.emit(HC_PARAMS, [x], w, f32_ctx(), "res_pre")
        return op.emit({"stage": "post"}, [x, jnp.tanh(u), maps], {},
                       f32_ctx(), "res")[0]

    want = jax.jit(sublayer)(x, w)
    mesh = Mesh(np.array(jax.devices()), ("x",))
    spec = P("x") if by == "batch" else P(None, "x")
    got = jax.jit(sublayer)(
        jax.device_put(x, NamedSharding(mesh, spec)),
        jax.device_put(w, NamedSharding(mesh, P())))
    close(got, want, 2e-5)


def test_the_cost_row_is_by_bytes(tiny):
    """At this width (64 channels: no whole lane) the plain path runs:
    ``pre`` reads the streams three times (norm, product, ``Hpre X``),
    ``post`` once; both are bound by memory in the cost model. ``pre``'s
    third output is its input and moves nothing; where the kernels run
    it reads them once (``tests/test_mhc_kernel.py``)."""
    ff, mc, _ = tiny
    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.search.costmodel import OpCostModel
    pre, post = hc_layers(ff)
    n, c = mc.hc_mult, mc.hidden_size
    tokens = B * S
    op = HyperConnectionOp()
    shapes = lambda l: ([t.shape for t in l.inputs],
                        [t.shape for t in l.outputs])
    assert op.bytes_moved(pre.params, *shapes(pre)) == 4 * tokens * (
        3 * n * c + c + n + n * n)
    assert op.bytes_moved(post.params, *shapes(post)) == 4 * tokens * (
        n * c + c + n + n * n + n * c)
    assert op.flops(pre.params, *shapes(pre)) == tokens * (
        2 * n * c * n * (n + 2) + 5 * n * c + 20 * 4 * n * n)
    assert op.flops(post.params, *shapes(post)) == tokens * (
        2 * n * n * c + 2 * n * c)
    cm = OpCostModel(MachineSpec(num_devices=1, generation="v5e"))
    for layer in (pre, post):
        cost = cm.op_cost(layer, {})
        moved = op.bytes_moved(layer.params, *shapes(layer)) + sum(
            4 * int(np.prod(w.shape)) for w in layer.weights)
        assert cost.forward_time == pytest.approx(
            moved / cm.spec.hbm_bandwidth + cm.overhead_s, rel=1e-6)
        assert cost.backward_time == pytest.approx(2 * cost.forward_time)


def test_the_remat_finder_on_the_new_graph():
    """[dense, expert x 4]: the run is the four expert layers, eight
    graph nodes each (two ``pre``, two ``post``, two norms, attention,
    experts), entered and left by the one stream tensor; what is stored
    across a block is that tensor alone."""
    mc = dataclasses.replace(XingRankConfig.tiny(), num_hidden_layers=5)
    ff, _ = build(model_cfg=mc)
    start, unit, reps, entries, exits = _find_remat_blocks(ff.layers)
    assert (unit, reps) == (8, 4)
    assert [l.name for l in ff.layers[start:start + unit]] == [
        "attn_res_1_pre", "input_norm_1", "attn_1", "attn_res_1",
        "mlp_res_1_pre", "post_norm_1", "experts_1", "mlp_res_1"]
    shapes = {t.guid: t.shape for l in ff.layers for t in l.outputs}
    for guid in entries + exits:
        assert shapes[guid] == (B, S, mc.hc_mult, mc.hidden_size)
    assert exits[-1] == next(l for l in ff.layers
                             if l.name == "mlp_res_4").outputs[0].guid
    # the streams that enter a sub-layer have ONE consumer, its ``pre``
    # node: ``post`` takes them from that node's third output
    by_name = {l.name: l for l in ff.layers}
    for name in ("attn_res_1", "mlp_res_3", "mlp_res_0"):
        pre, post = by_name[name + "_pre"], by_name[name]
        assert post.inputs[0].guid == pre.outputs[2].guid
        assert post.inputs[2].guid == pre.outputs[1].guid
        readers = [l.name for l in ff.layers
                   if any(t.guid == pre.inputs[0].guid for t in l.inputs)]
        assert readers == [pre.name]


def test_rematerialised_blocks_give_the_same_step_and_counters(tiny,
                                                               tiny_step):
    ff, mc = build(remat="blocks")
    assert ff.executor._remat[1:3] == (8, 2)
    (loss, bm), grads = rf.step_and_gradients(ff, ff.params, tiny[2])
    (want, want_bm), want_grads = tiny_step
    close(loss, want, 1e-6)
    for name in grads:
        for key in grads[name]:
            close(grads[name][key], want_grads[name][key], 1e-4,
                  floor=1e-4)
    assert float(bm[COUNTER_PREFIX + "mhc.sublayers"]) == 8
    assert float(bm[COUNTER_PREFIX + "mhc.sum_err"]) == pytest.approx(
        float(want_bm[COUNTER_PREFIX + "mhc.sum_err"]), rel=1e-4)


def test_the_loop_is_one_scan_a_pass_in_the_steps_text():
    """8 sub-layers x (forward, backward) and, in the two rematerialised
    blocks' 4 and each ``pre`` node's own recomputation, forward again:
    ``while`` loops, not 20 unrolled copies a sub-layer."""
    ff, mc = build(remat="blocks")
    batch = data(mc)
    text = ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state, jnp.int32(0),
        {k: v for k, v in batch.items()}).as_text()
    loops = text.count("stablehlo.while")
    assert 16 <= loops <= 40, loops
    assert text.count("stablehlo.exponential") < 200


def test_spans_and_counters_in_a_fit():
    events.clear()
    events.enable()
    try:
        cfg = FFConfig()
        cfg.batch_size = B
        cfg.only_data_parallel = True
        cfg.trace = "true"
        ff = FFModel(cfg)
        mc = XingRankConfig.tiny()
        out = build_latent_moe(ff, B, S, mc)
        ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy",
                   [], output_tensor=out)
        batch = data(mc)
        ff.fit(x=[np.asarray(batch["input_ids"]),
                  np.asarray(batch["position_ids"])],
               y=np.asarray(batch["label"]), epochs=2, verbose=False)
        maps = [e for e in events.events() if e["name"] == "mhc.maps"]
        maps = [m["attrs"] for m in maps]
        assert {m["layer"] for m in maps} == {
            f"{kind}_res_{tag}_pre" for kind in ("attn", "mlp")
            for tag in ("0", "1", "2", "mtp")}
        assert all(m["streams"] == 4 and m["iters"] == 20 for m in maps)
        latent = [e for e in events.events() if e["name"] == "attn.latent"]
        assert len({e["attrs"]["layer"] for e in latent}) == 4
        c = events.counters()
        assert c["mhc.sublayers"] == 2 * 8          # 2 steps
        assert c["mhc.clamped"] == 0
        assert 0 < c["mhc.sum_err"] / c["mhc.sublayers"] < 0.1
        step = ff.executor.make_train_step().lower(
            ff.params, ff.opt_state, ff.state, jnp.int32(0),
            next(iter(ff._combined_loader(
                [np.asarray(batch["input_ids"]),
                 np.asarray(batch["position_ids"])],
                np.asarray(batch["label"]), shuffle=False)))
        ).as_text(debug_info=True)
        for scope in ("mhc.maps", "mhc.sinkhorn", "mhc.mix"):
            # under the layer's name, the wrap's own scope (PR 53) and
            # JAX's ``checkpoint``
            assert f"attn_res_1_pre/{scope}" in step or \
                f"attn_res_1_pre/remat.mhc.plain/checkpoint/{scope}" \
                in step, scope
        assert "mlp_res_2/mhc.mix" in step
    finally:
        events.disable()
        events.clear()


def test_one_stream_and_no_scaling_is_the_older_graph():
    """The new configuration class with ``hc_mult`` 1 and
    ``rope_scaling`` None builds the parent class's graph: the residual
    rule is chosen from the configuration, not from the class."""
    def lowered(mc):
        return rf.lowered_step(mc, build_latent_moe, "blocks")
    older = lowered(dataclasses.replace(LatentMoEConfig.tiny(),
                                        routed_scaling_factor=2.0))
    assert lowered(dataclasses.replace(XingRankConfig.tiny(), hc_mult=1,
                                       rope_scaling=None)) == older
    assert lowered(XingRankConfig.tiny()) != older
