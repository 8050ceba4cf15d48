"""The SambaY decoder-hybrid-decoder with differential attention (a
Mamba-1 selective scan, differential attention in a window, whole and
over another layer's keys and values, a gated memory unit that reads
another layer's scan output; ``Phi4FlashRankConfig``) against its plain
reference (``benchmarks/reference/sambay_ref.py``), at a small size on
the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums.
``TOL`` = 2e-4 relative to the largest entry is a hundred times what
they read and far under what one decay a channel for all state entries,
the memory taken after the gate or from the wrong layer, plain attention
for differential, heads paired otherwise, a lost window or a cross layer
on keys of its own moves.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.analysis.plan_verifier import verify_plan
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.models.nlp import (HybridConvMoEConfig,
                                     Phi4FlashRankConfig,
                                     build_hybrid_conv_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
from flexflow_tpu.ops.recurrent_ops import (SelectiveScanMixerOp,
                                            selective_scan)
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from flexflow_tpu.search import opshard
from rank_family import B, TOL, apart, close, f32_ctx, program

ref = rf.reference("sambay_ref")
S = 40                    # tiny(): chunks of 16, so two and a half
build = functools.partial(rf.build, Phi4FlashRankConfig,
                          build_hybrid_conv_moe, seq=S)
data = functools.partial(rf.data, seq=S)
KINDS = ["mamba1", "diff_sliding_attention", "mamba1_memory",
         "diff_attention_kv", "gated_memory", "diff_cross_attention"]


def spread(params):
    """The seed's weights with every norm's scale, ``D`` and the pair
    norm off 1, the norms', projections' and convolution's biases off 0,
    ``A_log`` moved a channel and an entry at a time and the lambda
    vectors large enough that each layer's lambda is its own."""
    def rule(name, k, w, rng):
        if k in ("scale", "subln", "D"):
            return rf.scaled(w, rng)
        if k in ("bias", "bq", "bk", "bv", "bo", "conv_b", "A_log") \
                or k.startswith("lambda_"):
            return rf.shifted(w, rng)
    return rf.spread(params, rule)


tiny, tiny_step = rf.fixtures(build, data, spread)


@pytest.fixture(scope="module")
def tiny_reference(tiny):
    """The reference's log-probabilities at the tiny model's weights."""
    ff, mc, batch, params = tiny
    return rf.reference_call(ref.sambay_decoder, ff, mc, params, batch)


# ----------------------------------------------------------------------
# the selective scan alone
# ----------------------------------------------------------------------
D, N = 24, 4


def scan_inputs(seq, seed=0, strength=1.0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, bm, cm = draw(B, seq, D), draw(B, seq, N), draw(B, seq, N)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (B, seq, D)), jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1.0, 16.0, (N, D)) * strength),
                        jnp.float32)
    return x, dt, a_log, bm, cm


@jax.jit
def reference_scan(x, dt, a_log, bm, cm):
    return ref.recurrence(x, dt, a_log, bm, cm, jnp.zeros(D))


def scan(chunk):
    return jax.jit(lambda *a: selective_scan(
        a[0], a[1], -jnp.exp(a[2]), a[3], a[4], chunk))


@pytest.mark.parametrize("seq,chunk", [(32, 8), (32, 16), (40, 16),
                                       (37, 5), (12, 16), (40, 64)])
def test_the_scan_is_the_recurrence_token_by_token(chunk, seq):
    """Chunks that divide the sequence, chunks that do not (the padded
    positions write nothing and decay nothing) and a sequence shorter
    than a chunk; the counter is the least ``dt A`` of any step."""
    args = scan_inputs(seq)
    y, least = scan(chunk)(*args)
    close(y, reference_scan(*args))
    want = (np.asarray(args[1])[:, :, None, :]
            * -np.exp(np.asarray(args[2]))).min()
    assert abs(float(least) - want) <= 1e-5 * abs(want)


@pytest.mark.parametrize("seq,chunk", [(32, 16), (40, 16), (12, 16)])
def test_the_scans_gradients_are_the_recurrences(chunk, seq):
    args = scan_inputs(seq)

    def got(*a):
        y, _ = selective_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4], chunk)
        return jnp.sum(y * jnp.cos(y))

    def want(*a):
        y = reference_scan(*a)
        return jnp.sum(y * jnp.cos(y))

    g1 = jax.jit(jax.grad(got, range(5)))(*args)
    g2 = jax.jit(jax.grad(want, range(5)))(*args)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(b))) > 0
        close(a, b, 1e-3)


def test_a_decay_a_channel_for_all_state_entries_is_another_scan():
    """``A`` with every entry of a channel at the entries' mean: the
    scan a Mamba-2 head would run. It is apart from this one."""
    x, dt, a_log, bm, cm = scan_inputs(32)
    one = jnp.broadcast_to(jnp.log(jnp.mean(jnp.exp(a_log), 0)), a_log.shape)
    apart(scan(16)(x, dt, one, bm, cm)[0], reference_scan(x, dt, a_log, bm,
                                                          cm))
    close(scan(16)(x, dt, one, bm, cm)[0], reference_scan(x, dt, one, bm,
                                                          cm))


def test_steps_whose_decay_underflows_still_agree():
    """``A`` a thousand times the published range: ``exp(dt A)`` is 0 in
    float32 for most steps. Every exponent is ``dt A`` <= 0, so nothing
    overflows: values and gradients are finite and the recurrence's."""
    args = scan_inputs(32, strength=1000.0)
    assert float(scan(16)(*args)[1]) < -88.0

    def got(*a):
        y, _ = selective_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 16)
        return jnp.sum(y * jnp.cos(y)), y

    def want(*a):
        y = reference_scan(*a)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), g1 = jax.jit(jax.value_and_grad(got, (0, 1, 3, 4),
                                            has_aux=True))(*args)
    (_, y2), g2 = jax.jit(jax.value_and_grad(want, (0, 1, 3, 4),
                                            has_aux=True))(*args)
    close(y1, y2)
    for a, b in zip(g1, g2):
        assert np.isfinite(np.asarray(a)).all()
        close(a, b, 1e-3)


# ----------------------------------------------------------------------
# one selective-scan layer
# ----------------------------------------------------------------------
E, TAPS, R = 16, 4, 3
INNER = 2 * E
LAYER = {"inner": INNER, "state": N, "dt_rank": R, "taps": TAPS,
         "chunk": 16}
SIZES = {"hidden_size": E, "mamba_expand": 2, "mamba_d_state": N,
         "mamba_dt_rank": R, "mamba_d_conv": TAPS}


def mixer_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    return {"in_proj": w(E, 2 * INNER), "conv_w": u(-0.7, 0.7, INNER, TAPS),
            "conv_b": u(-0.5, 0.5, INNER), "x_proj": w(INNER, R + 2 * N),
            "dt_proj": w(R, INNER), "dt_bias": u(-3.0, 0.0, INNER),
            "A_log": jnp.log(u(1.0, 16.0, N, INNER)),
            "D": u(0.5, 1.5, INNER), "out_proj": w(INNER, E)}


def layer_input(seq=S, seed=1, width=E):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(B, seq, width)), jnp.float32)


def run_mixer(x, w, **over):
    """``(outputs, counters)`` of the layer."""
    def layer(x, w):
        ctx = f32_ctx()
        outs = SelectiveScanMixerOp().emit(dict(LAYER, **over), [x], w, ctx,
                                           "ssm")
        return outs, ctx.counters
    return jax.jit(layer)(x, w)


@jax.jit
def reference_mixer(x, w):
    with jax.default_matmul_precision("highest"):
        return ref.mixer(x, w, SIZES)


def test_a_layers_outputs_and_every_gradient_are_the_references():
    """Both outputs, and the gradient of a scalar of both for the input
    and every weight: ``in_proj``, the taps and their bias, ``x_proj``,
    ``dt_proj`` and its bias, ``A_log``, ``D``, ``out_proj``."""
    x, w = layer_input(), mixer_weights()

    def scalar(y, m):
        return jnp.sum(y * jnp.cos(y)) + jnp.sum(m * jnp.sin(m))

    def got(x, w):
        (y, m), _ = run_mixer(x, w, memory_out=True)
        return scalar(y, m), (y, m)

    def want(x, w):
        y, m = reference_mixer(x, w)
        return scalar(y, m), (y, m)

    (_, (y1, m1)), (gx1, gw1) = jax.jit(jax.value_and_grad(
        got, (0, 1), has_aux=True))(x, w)
    (_, (y2, m2)), (gx2, gw2) = jax.jit(jax.value_and_grad(
        want, (0, 1), has_aux=True))(x, w)
    close(y1, y2)
    close(m1, m2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) == set(ref.MIXER)
    for k in gw2:
        assert float(jnp.max(jnp.abs(gw2[k]))) > 0, k
        close(gw1[k], gw2[k], 1e-3)


def test_the_memory_is_the_scan_with_the_skip_before_the_gate():
    """The second output against three things it is not: the scan
    without ``D x``, the gated ``m * silu(z)`` and the layer's output."""
    x, w = layer_input(), mixer_weights()
    (y, m), _ = run_mixer(x, w, memory_out=True)
    _, want = reference_mixer(x, w)
    close(m, want)
    assert m.shape == (B, S, INNER) and y.shape == (B, S, E)
    _, no_skip = reference_mixer(x, dict(w, D=jnp.zeros_like(w["D"])))
    apart(m, no_skip)
    z = jnp.einsum("bte,ec->btc", x, w["in_proj"])[..., INNER:]
    apart(m, want * jax.nn.silu(z))
    # without ``memory_out`` the layer has one output, the same one
    (only,), _ = run_mixer(x, w)
    close(only, y, 1e-6)


@pytest.mark.parametrize("chunk", [8, 40, 64])
def test_the_layer_is_the_same_in_chunks_of_any_size(chunk):
    x, w = layer_input(), mixer_weights()
    close(run_mixer(x, w, chunk=chunk)[0][0], run_mixer(x, w)[0][0], 1e-5)


@pytest.mark.parametrize("what", ["conv_b", "D", "A_log", "dt_bias"])
def test_a_layer_that_lost_one_weight_is_apart_from_the_reference(what):
    x, w = layer_input(), mixer_weights()
    other = dict(w, **{what: jnp.zeros_like(w[what])})
    apart(run_mixer(x, w)[0][0], reference_mixer(x, other)[0])


def test_the_step_size_comes_from_the_convolved_x():
    """B, C and the step size read ``silu(conv(x))``, not the input
    projection: the reference fed the unconvolved x there is apart."""
    x, w = layer_input(), mixer_weights()
    identity = jnp.zeros_like(w["conv_w"]).at[:, -1].set(1.0)
    apart(run_mixer(x, w)[0][0], reference_mixer(
        x, dict(w, conv_w=identity, conv_b=jnp.zeros_like(w["conv_b"])))[0])


def test_the_layer_raises_under_a_key_value_cache():
    ctx = f32_ctx(training=False)
    ctx.kv_mode = "prefill"
    with pytest.raises(NotImplementedError, match="no decode path"):
        SelectiveScanMixerOp().emit(LAYER, [layer_input()], mixer_weights(),
                                    ctx, "ssm")


# ----------------------------------------------------------------------
# one differential attention layer
# ----------------------------------------------------------------------
H, KV, HD = 8, 4, 8          # four query pairs on two key pairs: group 2
AE = H * HD
DEPTH = 15
ATTN = {"embed_dim": AE, "num_heads": H, "num_kv_heads": KV, "kdim": AE,
        "vdim": AE, "bias": True, "causal": True, "differential": True,
        "lambda_init": ref.lambda_init(DEPTH), "subln_eps": 1e-5}
ATTN_SIZES = {"num_attention_heads": H, "num_key_value_heads": KV,
              "layer_norm_eps": 1e-5}


def attn_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 2.0 / np.sqrt(shape[0]),
                           jnp.float32)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    return {"wq": w(AE, H, HD), "wk": w(AE, KV, HD), "wv": w(AE, KV, HD),
            "wo": w(H, HD, AE) / 2.0, "bq": u(-0.5, 0.5, H, HD),
            "bk": u(-0.5, 0.5, KV, HD), "bv": u(-0.5, 0.5, KV, HD),
            "bo": u(-0.5, 0.5, AE), "subln": u(0.5, 1.5, 2 * HD),
            **{k: u(-0.4, 0.4, HD) for k in ref.LAMBDAS}}


def attention_layer(x, w, impl="xla", **over):
    """The layer's outputs, down the path ``impl``."""
    def layer(x, w):
        return MultiHeadAttentionOp().emit(
            dict(ATTN, **over), [x, x, x], w, f32_ctx(impl=impl), "attn")
    return jax.jit(layer)(x, w)


def reference_attention(x, w, window=0, sizes=ATTN_SIZES):
    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            k, v = ref.keys_and_values(x, w)
            return ref.differential_attention(x, w, k, v, sizes, DEPTH,
                                              window)
    return layer(x, w)


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("window", [0, 8, 13])
def test_the_layer_and_every_gradient_are_the_references(window, impl):
    """With and without a window, at two query pairs a key pair, down
    XLA and down the flash kernels (interpret mode: two grouped calls at
    8 / 16): the output and the gradient of a scalar of it for the input
    and every weight, the four lambda vectors and the pair norm among
    them."""
    x, w = layer_input(width=AE), attn_weights()
    over = {"sliding_window": window} if window else {}

    def got(x, w):
        (y,) = attention_layer(x, w, impl, **over)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w):
        y = reference_attention(x, w, window)
        return jnp.sum(y * jnp.cos(y)), y

    (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(got, (0, 1),
                                                    has_aux=True))(x, w)
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(want, (0, 1),
                                                    has_aux=True))(x, w)
    close(y1, y2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) == set(ref.ATTN)
    for k in gw2:
        if k == "bk":
            # a bias on the keys moves no softmax: rounding, both sides
            assert max(float(jnp.max(jnp.abs(g[k]))) for g in (gw1, gw2)) \
                < 1e-4
            continue
        assert float(jnp.max(jnp.abs(gw2[k]))) > 1e-4, k
        close(gw1[k], gw2[k], 1e-3)


def test_the_window_is_not_the_whole_and_a_wide_one_is():
    x, w = layer_input(width=AE), attn_weights()
    whole = attention_layer(x, w)[0]
    apart(attention_layer(x, w, sliding_window=8)[0], whole)
    close(attention_layer(x, w, sliding_window=S)[0], whole, 1e-6)
    apart(attention_layer(x, w, "flash", sliding_window=8)[0], whole)


def plain_attention(x, w, lam=None):
    """Grouped causal softmax attention on the same weights: what a
    layer that lost its differential form would run (``lam`` None), or
    the differential form written head by head with ``pairing`` left to
    the caller."""
    q = jnp.einsum("bse,ehd->bshd", x, w["wq"]) + w["bq"]
    k, v = ref.keys_and_values(x, w)
    k, v = (jnp.repeat(t, H // KV, 2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(HD)
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bqhd,hde->bqe", o, w["wo"]) + w["bo"]


def test_plain_attention_is_another_layer():
    x, w = layer_input(width=AE), attn_weights()
    with jax.default_matmul_precision("highest"):
        apart(attention_layer(x, w)[0], jax.jit(plain_attention)(x, w))


def test_lambda_at_zero_is_another_layer():
    x, w = layer_input(width=AE), attn_weights()
    with ref.variant(lambda_zero=True):
        apart(attention_layer(x, w)[0], reference_attention(x, w))
    close(attention_layer(x, w)[0], reference_attention(x, w))


def _permuted(w, keys, order):
    order = np.asarray(order)
    return dict(w, **{k: w[k][:, order] if k.startswith("w")
                      else w[k][order] for k in keys})


@pytest.mark.parametrize("what", ["swapped_pairs", "wrong_key_pair",
                                  "heads_paired_across"])
def test_another_pairing_of_the_heads_is_another_layer(what):
    """The reference on weights whose heads are permuted is the layer a
    program with that pairing would be: q1 and q2 swapped in every pair
    (the difference's sign), query pairs reading the OTHER key pair, and
    heads paired (0, 2), (1, 3) instead of adjacently."""
    x, w = layer_input(width=AE), attn_weights()
    if what == "swapped_pairs":
        other = _permuted(w, ("wq", "bq"), [1, 0, 3, 2, 5, 4, 7, 6])
    elif what == "wrong_key_pair":
        other = _permuted(w, ("wk", "bk", "wv", "bv"), [2, 3, 0, 1])
    else:
        other = _permuted(w, ("wq", "bq"), [0, 2, 1, 3, 4, 6, 5, 7])
    apart(attention_layer(x, w)[0], reference_attention(x, other))
    apart(attention_layer(x, w, "flash")[0], reference_attention(x, other))


def cross_layer(x, k, v, w, impl="xla"):
    def layer(x, k, v, w):
        return MultiHeadAttentionOp().emit(
            dict(ATTN, kv_projected=True, kv_source="attn_3"), [x, k, v],
            {n: w[n] for n in ref.CROSS}, f32_ctx(impl=impl), "attn")[0]
    return jax.jit(layer)(x, k, v, w)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_keys_and_values_leave_one_layer_and_enter_another(impl):
    """``kv_out``: the second and third outputs are k and v in heads
    after the bias. ``kv_projected``: a layer with no ``wk``, ``wv``
    attends over them, and its own input moves only its queries."""
    x, w = layer_input(width=AE), attn_weights()
    y, k, v = attention_layer(x, w, impl, kv_out=True)
    want_k, want_v = jax.jit(ref.keys_and_values)(x, w)
    close(k, want_k)
    close(v, want_v)
    close(y, attention_layer(x, w, impl)[0], 1e-6)
    other_x, other_w = layer_input(seed=5, width=AE), attn_weights(seed=7)
    specs = MultiHeadAttentionOp().weights(
        dict(ATTN, kv_projected=True), [(B, S, AE), (B, S, KV, HD),
                                        (B, S, KV, HD)],
        [DataType.DT_FLOAT] * 3)
    assert sorted(s.name for s in specs) == sorted(ref.CROSS)
    got = cross_layer(other_x, k, v, other_w, impl)

    @jax.jit
    def want(x, k, v, w):
        with jax.default_matmul_precision("highest"):
            return ref.differential_attention(x, w, k, v, ATTN_SIZES, DEPTH,
                                              0)
    close(got, want(other_x, k, v, other_w))
    # keys and values of its OWN input are another layer
    own_k, own_v = jax.jit(ref.keys_and_values)(other_x, w)
    apart(got, want(other_x, own_k, own_v, other_w))


def test_what_the_front_refuses():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((B, S, AE), name="x")
    diff = {"lambda_init": 0.8}
    for more in ({"rope": True}, {"qk_norm": True}, {"output_gate": True},
                 {"sm_scale": 0.5}, {"dropout": 0.1}, {"causal": False},
                 {"indexer": {"heads": 2, "head_dim": 8, "topk": 4,
                              "q_chunk": 8}}):
        with pytest.raises(ValueError):
            ff.multihead_attention(x, x, x, AE, H, num_kv_heads=KV,
                                   differential=diff,
                                   **{"causal": True, **more})
    with pytest.raises(ValueError, match="pairs"):        # 3 kv heads
        ff.multihead_attention(x, x, x, AE, 6, num_kv_heads=3, causal=True,
                               differential=diff)
    with pytest.raises(ValueError, match="differential attention only"):
        ff.multihead_attention(x, x, x, AE, H, causal=True, kv_out=True)
    with pytest.raises(ValueError, match="in heads"):
        ff.multihead_attention(x, x, x, AE, H, num_kv_heads=KV, causal=True,
                               differential=diff, kv_projected=True)
    with pytest.raises(ValueError, match="channels"):
        ff.selective_scan_mixer(x, INNER, N, 0, TAPS, 16)
    for field, value in (("mlp_bias", True), ("lm_head_bias", True),
                         ("resid_pdrop", 0.1), ("mb_per_layer", 3),
                         ("first_layer_index", 30),
                         ("layer_types", ["mamba1"] * 6)):
        with pytest.raises(ValueError):
            dataclasses.replace(Phi4FlashRankConfig.tiny(),
                                **{field: value})
    with pytest.raises(ValueError, match="mamba_"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, dataclasses.replace(
            HybridConvMoEConfig.tiny(), layer_types=["mamba1"] * 5))
    # a reader before its source
    late = dataclasses.replace(Phi4FlashRankConfig.tiny(), layer_types=None,
                               first_layer_index=18, num_hidden_layers=2)
    assert late.layer_types == ["gated_memory", "diff_cross_attention"]
    with pytest.raises(ValueError, match="no earlier 'mamba1_memory'"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, late)


@pytest.mark.parametrize("more,match", [
    ({"rope": True}, "not built beside"), ({"qk_norm": True}, "beside"),
    ({"sm_scale": 0.5}, "beside")])
def test_what_the_op_refuses_by_name(more, match):
    x, w = layer_input(width=AE), attn_weights()
    with pytest.raises(ValueError, match=match):
        MultiHeadAttentionOp().emit(dict(ATTN, **more), [x, x, x], w,
                                    f32_ctx(impl="xla"), "attn")


def test_the_op_refuses_the_ring_path_and_a_key_value_cache():
    x, w = layer_input(width=AE), attn_weights()
    with pytest.raises(ValueError, match="ring path or a KV cache"):
        MultiHeadAttentionOp().emit(ATTN, [x, x, x], w,
                                    f32_ctx(impl="ring"), "attn")
    ctx = f32_ctx(training=False, impl="xla")
    ctx.kv_mode = "prefill"
    with pytest.raises(ValueError, match="ring path or a KV cache"):
        MultiHeadAttentionOp().emit(ATTN, [x, x, x], w, ctx, "attn")
    plain = {k: v for k, v in ATTN.items() if k not in (
        "differential", "lambda_init", "subln_eps")}
    with pytest.raises(ValueError, match="differential attention only"):
        MultiHeadAttentionOp().emit(dict(plain, kv_out=True), [x, x, x], w,
                                    f32_ctx(impl="xla"), "attn")


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_configuration_lays_the_published_layers_out():
    full = Phi4FlashRankConfig(first_layer_index=0, num_hidden_layers=32)
    kinds = full.layer_types
    assert kinds[14:20] == KINDS == Phi4FlashRankConfig().layer_types \
        == Phi4FlashRankConfig.tiny().layer_types
    assert kinds[:16] == ["mamba1", "diff_sliding_attention"] * 8
    assert kinds[18:] == ["gated_memory", "diff_cross_attention"] * 7
    assert [kinds.count(k) for k in KINDS] == [8, 8, 1, 1, 7, 7]
    mc = Phi4FlashRankConfig()
    assert (mc.hidden_size, mc.intermediate_size, mc.num_attention_heads,
            mc.num_key_value_heads, mc.head_dim, mc.sliding_window,
            mc.mamba_expand * mc.hidden_size, mc.mamba_d_state,
            mc.mamba_dt_rank, mc.mamba_d_conv, mc.vocab_size) \
        == (2560, 10240, 40, 20, 64, 512, 5120, 16, 160, 4, 25008)
    assert mc.mamba_dt_rank == -(-mc.hidden_size // 16)


def test_the_graph_has_what_the_equations_have(tiny):
    ff, mc, _, _ = tiny
    by_name = {l.name: l for l in ff.layers}
    kinds = [l.op_type.name for l in ff.layers]
    assert kinds.count("OP_SELECTIVE_SCAN_MIXER") == 2
    assert kinds.count("OP_MULTIHEAD_ATTENTION") == 3
    assert kinds.count("OP_LAYERNORM") == 13 and "OP_RMSNORM" not in kinds
    assert kinds.count("OP_ROUTED_EXPERTS") == 0
    # lambda starts from the PUBLISHED index
    for i, depth in ((1, 15), (3, 17), (5, 19)):
        p = by_name[f"attn_{i}"].params
        assert p["differential"] and p["bias"] and p["causal"] \
            and not p.get("rope") and not p.get("qk_norm")
        assert p["lambda_init"] == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * depth))
    assert by_name["attn_1"].params["lambda_init"] \
        == pytest.approx(0.79333, abs=1e-5)
    assert by_name["attn_1"].params["sliding_window"] == mc.sliding_window
    assert "sliding_window" not in by_name["attn_3"].params
    assert by_name["attn_3"].params["kv_out"] \
        and len(by_name["attn_3"].outputs) == 3
    cross = by_name["attn_5"]
    assert cross.params["kv_projected"] \
        and cross.params["kv_source"] == "attn_3" \
        and [t.guid for t in cross.inputs[1:]] \
        == [t.guid for t in by_name["attn_3"].outputs[1:]]
    assert sorted(w.name for w in cross.weights) == sorted(ref.CROSS)
    # the gated unit reads layer 16's SECOND output
    assert not by_name["ssm_0"].params.get("memory_out") \
        and by_name["ssm_2"].params["memory_out"]
    assert by_name["gmu_gate_4"].inputs[0].guid \
        == by_name["ssm_2"].outputs[1].guid
    assert by_name["gmu_in_4"].params["use_bias"] is False \
        and by_name["gmu_out_4"].params["use_bias"] is False
    # nothing turns by a position: the input is declared, fed and unread
    assert [t.name for t in ff.graph_inputs] == ["input_ids"]


def test_the_models_log_probabilities_are_the_references(tiny,
                                                         tiny_reference):
    ff, _, batch, params = tiny
    _, _, probs = program(ff, params, batch, False)
    close(jnp.log(probs), tiny_reference)


def test_the_loss_and_every_gradient_are_the_references(tiny, tiny_step):
    ff, mc, batch, params = tiny
    (loss, _), grads = tiny_step
    close(loss, rf.reference_loss(ref, ff, mc, params, batch), 1e-5)
    want = rf.reference_gradients(ref, ff, mc, params, batch)
    assert set(grads) == set(want)
    for name, ws in want.items():
        for k in ws:
            if k == "bk":           # rounding on both sides, as above
                assert float(jnp.max(jnp.abs(grads[name][k]))) < 1e-6
                continue
            assert float(jnp.max(jnp.abs(ws[k]))) > 0, (name, k)
            close(grads[name][k], ws[k], 2e-3)


@pytest.mark.parametrize("which", sorted(ref._VARIANT))
def test_each_departure_is_apart_from_the_model(tiny, tiny_reference,
                                                which):
    """The reference computing ANOTHER model (lambda at 0, no window,
    the memory from layer 14, the cross layer on keys and values of its
    own input) is apart from the program; so the program is none of
    them."""
    ff, mc, batch, params = tiny
    with ref.variant(**{which: True}):
        other = rf.reference_call(ref.sambay_decoder, ff, mc, params, batch)
    apart(tiny_reference, other, 10 * TOL)
    _, _, probs = program(ff, params, batch, False)
    apart(jnp.log(probs), other, 10 * TOL)


def test_the_cross_layer_reads_layer_17s_keys_and_not_its_own_inputs(tiny):
    """Perturb layer 17's key projection: the cross layer's gradient
    path says so. ``wk`` of layer 17 has TWO readers, and its gradient
    is their sum: with the cross layer's output projection at zero it is
    another (the first reader's alone), and both are the reference's."""
    ff, mc, batch, params = tiny
    step = rf.stepper(ff, batch)
    grader = rf.reference_grader(ref, ff, mc, batch)
    cut = {n: dict(ws) for n, ws in params.items()}
    cut["attn_5"]["wo"] = jnp.zeros_like(cut["attn_5"]["wo"])
    cut["attn_5"]["bo"] = jnp.zeros_like(cut["attn_5"]["bo"])
    (_, _), both = step(params)
    (_, _), one = step(cut)
    for k in ("wk", "wv", "bv"):
        apart(both["attn_3"][k], one["attn_3"][k], 10 * TOL)
        close(both["attn_3"][k], grader(params)["attn_3"][k], 2e-3)
        close(one["attn_3"][k], grader(cut)["attn_3"][k], 2e-3)
    # and the cross layer has no keys of its own to move
    assert not {"wk", "wv", "bk", "bv"} & set(params["attn_5"])


def test_the_gated_unit_reads_layer_16s_memory(tiny):
    """``D`` of layer 16 reaches the loss through the gated unit too:
    with the unit's output projection at zero its gradient is another,
    and layer 14's mixer (no reader) does not move with it."""
    ff, mc, batch, params = tiny
    step = rf.stepper(ff, batch)
    cut = {n: dict(ws) for n, ws in params.items()}
    cut["gmu_out_4"]["kernel"] = jnp.zeros_like(cut["gmu_out_4"]["kernel"])
    (_, _), both = step(params)
    (_, _), one = step(cut)
    apart(both["ssm_2"]["D"], one["ssm_2"]["D"], 10 * TOL)
    want = rf.reference_grader(ref, ff, mc, batch)(cut)
    close(one["ssm_2"]["D"], want["ssm_2"]["D"], 2e-3)
    assert float(jnp.max(jnp.abs(both["gmu_in_4"]["kernel"]))) > 0


def test_the_reference_refuses_another_architecture(tiny):
    ff, mc, batch, _ = tiny
    sizes = rf.sizes_of(mc)
    for bad, match in ((dict(sizes, mamba_d_state=8), "A_log"),
                       (dict(sizes, num_key_value_heads=4), "query heads"),
                       (dict(sizes, layer_types=KINDS[:5]), "layer_types"),
                       (dict(sizes, layer_types=KINDS[4:] + KINDS[:4]),
                        "no memory")):
        rf.refuses(ref, ref.sambay_decoder, match, ff, bad, batch)


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


def test_the_remat_finder_takes_the_six_layers_as_six_blocks():
    """Six blocks of unlike interior (a mixer, an attention layer or a
    gated unit of five plain ops, each with its norms, adds and the
    SwiGLU that closes it) along one stream, and what crosses their
    edges beside it: ``m`` from the third to the fifth, K and V from
    the fourth to the sixth."""
    ff, _ = build(remat="blocks")
    start, units, reps, entries, exits = _find_remat_blocks(ff.layers)
    assert (start, units, reps) == (1, (11, 11, 11, 11, 15, 11), 6)
    assert ff.executor._remat[:3] == (start, units, reps)
    assert entries[1:] == exits[:-1] and len(set(entries + exits)) == 7
    edges = np.cumsum((start,) + units)
    first = [ff.layers[e + 1].name for e in edges[:-1]]
    assert first == ["ssm_0", "attn_1", "ssm_2", "attn_3", "gmu_in_4",
                     "attn_5"]
    assert [ff.layers[e - 1].name for e in edges[1:]] \
        == [f"ffn_res_{i}" for i in range(6)]
    by_guid = {t.guid: t for l in ff.layers for t in l.outputs}
    assert {tuple(by_guid[g].shape) for g in entries + exits} \
        == {(B, S, 32)}


def test_the_full_depth_is_thirty_two_blocks():
    mc = dataclasses.replace(Phi4FlashRankConfig.tiny(), first_layer_index=0,
                             num_hidden_layers=32, layer_types=None)
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, B, 32, mc)
    start, units, reps, _, _ = _find_remat_blocks(ff.layers)
    assert (start, reps) == (1, 32) and set(units) == {11, 15} \
        and units.count(15) == 7


def test_a_graph_that_hands_nothing_on_finds_the_run_it_found():
    """No op of an older layout says ``hands_on``: its run is
    ``find_repeated_run``'s, ``unit`` one number."""
    from flexflow_tpu.models.nlp import GraniteHybridRankConfig
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, B, 32, GraniteHybridRankConfig.tiny())
    assert _find_remat_blocks(ff.layers)[1:3] == (13, 6)


def _but_the_key_bias(result):
    """A step's result without the gradients of ``bk``: a bias on the
    keys moves no softmax, so they are rounding (1e-8), another on every
    path."""
    first, grads = result
    return first, {n: {k: g for k, g in ws.items() if k != "bk"}
                   for n, ws in grads.items()}


def test_a_rematerialised_step_is_the_step(tiny, tiny_step):
    _, _, batch, params = tiny
    remat, _ = build(remat="blocks")
    rf.same_step(_but_the_key_bias(rf.step_and_gradients(remat, params,
                                                         batch)),
                 _but_the_key_bias(tiny_step))
    (_, bm), _ = tiny_step
    assert float(bm[COUNTER_PREFIX + "ssm1.scans"]) == 2.0
    assert float(bm[COUNTER_PREFIX + "ssm1.log_decay_min"]) < 0.0
    assert float(bm[COUNTER_PREFIX + "attn.diff_layers"]) == 3.0
    # one window layer of B sequences: the band's pairs and the causal
    w = 8
    assert float(bm[COUNTER_PREFIX + "attn.window_pairs"]) \
        == B * (w * S - w * (w - 1) / 2)
    assert float(bm[COUNTER_PREFIX + "attn.causal_pairs"]) \
        == B * S * (S + 1) / 2


def test_the_blocks_hold_what_is_handed_on_and_say_so():
    """The ``remat.kept`` instants name ``m`` (block 2), K and V (block
    3) with their bytes; the readers' wraps count them among what they
    hold (``entry_bytes``: the stream's 10,240 bytes and the tensors
    handed in)."""
    events.enable()
    events.clear()
    try:
        ff, mc = build(remat="blocks")
        jax.jit(lambda p: rf.forward(ff, p, data(mc))[0]).lower(ff.params)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    kept = {(e["attrs"]["block"], e["attrs"]["layer"], e["attrs"]["bytes"])
            for e in seen if e["name"] == "remat.kept"}
    stream = B * S * 32 * 4
    assert kept == {(2, "ssm_2", B * S * 64 * 4),
                    (3, "attn_3", B * S * 2 * 8 * 4)}
    assert all(e["attrs"]["handed_on"] for e in seen
               if e["name"] == "remat.kept")
    wraps = {e["attrs"]["block"]: e["attrs"] for e in seen
             if e["name"] == "remat.wrap" and e["attrs"]["site"] == "block"}
    assert [wraps[b]["entry_bytes"] for b in range(6)] == [
        stream, stream, stream, stream, stream + B * S * 64 * 4,
        stream + 2 * B * S * 2 * 8 * 4]
    assert all(w["policy"] == "none" and w["kept_bytes"] == 0
               for w in wraps.values())


def test_the_layers_say_what_they_ran_and_the_scan_has_its_scope():
    events.enable()
    events.clear()
    try:
        ff, mc = build()
        batch = data(mc)
        text = jax.jit(lambda p: rf.forward(ff, p, batch)[0]).lower(
            ff.params).as_text(debug_info=True)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    scans = [e["attrs"] for e in seen if e["name"] == "ssm1.scan"]
    assert [(s["layer"], s["memory_out"]) for s in scans] \
        == [("ssm_0", False), ("ssm_2", True)]
    assert {k: scans[0][k] for k in ("channels", "state", "dt_rank", "taps",
                                     "tokens", "chunk", "chunks",
                                     "state_bytes", "impl")} == {
        "channels": 64, "state": 4, "dt_rank": 2, "taps": 4,
        "tokens": B * S, "chunk": 16, "chunks": 3,
        "state_bytes": B * 4 * 64 * 4, "impl": "plain"}
    diffs = {e["attrs"]["layer"]: e["attrs"] for e in seen
             if e["name"] == "attn.diff"}
    assert {n: (d["window"], d["kv_source"], d["kv_out"], d["impl"],
                d["calls"]) for n, d in diffs.items()} == {
        "attn_1": (8, "own", False, "xla", 2),
        "attn_3": (0, "own", True, "xla", 2),
        "attn_5": (0, "attn_3", False, "xla", 2)}
    assert diffs["attn_1"]["pairs"] == 2 and diffs["attn_1"]["key_pairs"] == 1
    assert "ssm_0/ssm1.scan" in text and "remat.ssm1.chunk" in text \
        and "attn_1/attn.diff" in text


def test_generate_does_not_take_the_key_value_path():
    """No cache holds a selective scan's state or keys that layers
    share: ``generate`` decodes by the re-forward path and says so."""
    ff, mc = build(batch=1)
    names = {t.name for t in ff.graph_inputs}
    assert not ff._kv_decode_eligible(names | {"position_ids"}, None)


def test_eight_data_parallel_devices_step_as_one():
    """The same rematerialised step on a mesh of the 8 virtual CPU
    devices, the batch divided over them, and on one device."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual CPU devices of tests/conftest.py")
    one, mc = build(batch=8, devices=1, remat="blocks")
    eight, _ = build(batch=8, devices=8, remat="blocks")
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
            if k != "bk":       # Adam steps by the sign of its rounding
                close(p8[name][k], ws[k], 1e-4)


def test_what_the_search_offers_and_the_verifier_refuses():
    ff, _ = build()
    from jax.sharding import PartitionSpec as P
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    axis = next(iter(ff.dmesh.axis_sizes))
    mixer = next(l for l in ff.layers
                 if l.op_type == OperatorType.OP_SELECTIVE_SCAN_MIXER)
    assert [o.kind for o in opshard.options_for(mixer)] == ["sample"]
    for spec, ok in ((P(axis, None, None), True),
                     (P(None, axis, None), False)):
        st = ShardingStrategy(ff.dmesh)
        st.set_op(mixer.name, [spec], {})
        halo = [f for f in verify_plan(st, ff.layers).errors
                if "halo" in f.message]
        assert (not halo) == ok
        assert ok or "selective-scan mixer" in halo[0].message
    # differential attention: batch only; a shard of the heads is refused
    attn = next(l for l in ff.layers if l.name == "attn_3")
    assert [o.kind for o in opshard.options_for(attn)] == ["sample"]
    st = ShardingStrategy(ff.dmesh)
    st.set_op(attn.name, [P(None, None, None)], {"wq": P(None, axis, None)})
    paired = [f for f in verify_plan(st, ff.layers).errors
              if "pair" in f.message]
    assert paired and "differential" in paired[0].message
