"""The sparse-attention mixture-of-experts decoder (every layer
grouped-query attention over the keys a learned indexer selects, trained
by its alignment loss, then softmax-routed experts) against its plain
reference (``benchmarks/reference/sparse_index_moe_ref.py``), at a small
size on the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums
(a chunk of queries against the keys up to its end and a threshold
search, against whole rows and ``jax.lax.top_k``; the sorted grouped
product against a loop over experts). ``TOL`` = 2e-4 relative to the
largest entry is a hundred times what they read (2e-7 to 2e-6) and far
under one key of 24 selected otherwise, which moves a query's output by
1e-2 or more. The random scores have no two equal entries among a row's
causal keys unless a test forces them, so program and reference select
the same sets exactly.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.executor import _find_remat_blocks
from flexflow_tpu.models.nlp import (HybridConvMoEConfig, KeyeRankConfig,
                                     LFM2RankConfig, build_hybrid_conv_moe)
from flexflow_tpu.ops import sparse_attention as dsa
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp, route
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from rank_family import B, close, f32_ctx, named, sizes_of

ref = rf.reference("sparse_index_moe_ref")
S = 48                    # tiny(): 24 keys a query in chunks of 16
build = functools.partial(rf.build, KeyeRankConfig, build_hybrid_conv_moe,
                          seq=S)
data = functools.partial(rf.data, seq=S)


def spread(params):
    """The seed's weights with the norms' scales off 1 and the indexer's
    three matrices four times as large, so that a wrong scale and a
    selection that follows the scores both show."""
    def rule(name, k, w, rng):
        if k in ("scale", "q_norm", "k_norm"):
            return rf.scaled(w, rng)
        if k in ("wq_idx", "wk_idx", "w_idx"):
            return w * 4.0
    return rf.spread(params, rule)


def program_terms(ff, params, batch, training=True):
    """``(cross-entropy, sum of L_I, metrics, probabilities)`` of the
    program's step: the loss less its auxiliary terms, and those."""
    loss, bm, outs, aux, _ = rf.forward(ff, params, batch, training)
    kl = sum(aux)
    return loss - kl, kl, bm, outs[0]


def reference_terms(ff, mc, params, batch):
    return ref.losses(named(ff, params), sizes_of(mc), batch["input_ids"],
                      batch["position_ids"], batch["label"][..., 0])


tiny, tiny_step = rf.fixtures(build, data, spread)


@functools.cache
def terms_and_gradients(remat, attention):
    """``((loss, (sum of L_I, metrics)), gradients)`` of the model built
    with ``remat`` and every attention layer forced down ``attention``,
    at the spread weights. Each of the four is a compile of the whole
    step; the test of the kernel path and the test of rematerialisation
    read the same ones (the chunked path is what an unforced layer takes
    here: the kernels are not chosen in interpret mode)."""
    ff, mc = build(remat=remat, attention=attention, devices=1)
    batch = data(mc)

    def f(p):
        ce, kl, bm, _ = program_terms(ff, p, batch)
        return ce + kl, (kl, bm)
    return jax.jit(jax.value_and_grad(f, has_aux=True))(spread(ff.params))


# ----------------------------------------------------------------------
# the selection
# ----------------------------------------------------------------------
def rows_at(start, rows):
    return jnp.arange(start, start + rows)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("start,rows,keys,topk", [
    (0, 16, 16, 4), (16, 16, 32, 8), (40, 8, 48, 24), (3, 5, 8, 7)])
def test_select_is_top_k_on_the_same_scores(seed, start, rows, keys, topk):
    scores = jnp.asarray(np.random.default_rng(seed).normal(
        size=(2, rows, keys)), jnp.float32)
    at = rows_at(start, rows)
    causal = jnp.arange(keys)[None, :] <= at[:, None]
    got, ties = dsa.select(scores, causal,
                           jnp.minimum(at + 1, topk)[:, None])
    want = ref.selected(scores, at, topk)[..., :keys]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(ties) == 0
    kept = np.asarray(got).sum(-1)
    assert np.array_equal(kept, np.broadcast_to(
        np.minimum(np.asarray(at) + 1, topk), kept.shape))


@pytest.mark.parametrize("value", [0.0, -0.0, 1.5, -2.25])
def test_equal_scores_at_the_threshold_go_to_the_lower_key(value):
    """Half of a row's scores are one value (ReLU's zeros in the real
    layer; -0.0 is +0.0's equal): the threshold falls among them, and
    the first of them by position are taken, as ``top_k`` takes them."""
    rng = np.random.default_rng(5)
    scores = rng.normal(size=(1, 8, 32)).astype(np.float32)
    same = rng.random((1, 8, 32)) < 0.5
    scores = np.where(same, np.float32(value), scores)
    if value == 0.0:
        # both zeros in one row: equal, whatever their sign bit
        scores = np.where(same & (rng.random((1, 8, 32)) < 0.5),
                          np.float32(-0.0), scores)
    scores = jnp.asarray(scores)
    at = rows_at(24, 8)
    causal = jnp.arange(32)[None, :] <= at[:, None]
    got, ties = dsa.select(scores, causal, jnp.full((8, 1), 12, jnp.int32))
    want = ref.selected(jnp.where(scores == 0, 0.0, scores), at, 12)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.all(np.asarray(got).sum(-1) == 12)
    # a tie at the threshold: the row's 12th largest value occurs twice
    # or more among its causal keys, counted from the scores themselves
    s, c = np.asarray(scores)[0], np.asarray(causal)
    s = np.where(s == 0, 0.0, s)
    tied = 0
    for t in range(8):
        vals = np.sort(s[t][c[t]])[::-1]
        tied += int((vals == vals[11]).sum() > 1)
    assert int(ties) == tied and tied > 0


def test_kth_largest_is_the_sorted_rows_kth_entry():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 5, 40)) * 1e3, jnp.float32)
    bits = dsa._ordered_bits(x)
    order = np.argsort(np.asarray(x), -1)
    assert np.array_equal(np.argsort(np.asarray(bits), -1), order)
    for k in (1, 7, 40):
        t = dsa.kth_largest(bits, jnp.full((5, 1), k, jnp.int32))
        want = np.take_along_axis(np.asarray(bits), order[..., -k:][..., :1],
                                  -1)
        assert np.array_equal(np.asarray(t), want)


# ----------------------------------------------------------------------
# one attention layer: the op against the reference
# ----------------------------------------------------------------------
HEADS, KV, D, J, C = 4, 2, 16, 2, 8
ATTN_PARAMS = {"embed_dim": 32, "num_heads": HEADS, "kdim": HEADS * D,
               "vdim": HEADS * D, "dropout": 0.0, "bias": False,
               "causal": True, "num_kv_heads": KV, "rope": True,
               "rope_theta": 10000.0, "qk_norm": True, "qk_norm_eps": 1e-6}
ATTN_SIZES = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0}


def attn_weights(seed=0, e=32):
    rng = np.random.default_rng(seed)

    def w(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale / np.sqrt(shape[0]),
                           jnp.float32)
    return {"wq": w(e, HEADS, D), "wk": w(e, KV, D), "wv": w(e, KV, D),
            "wo": w(HEADS, D, e, scale=0.5),
            "q_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32),
            "k_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32),
            "wq_idx": w(e, J, C, scale=4.0), "wk_idx": w(e, C, scale=4.0),
            "w_idx": w(e, J, scale=4.0)}


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def attn_op(x, pos, w, topk, q_chunk, training=True, impl=None):
    """``(y, L_I, counters)`` of the op with an indexer; ``impl``
    forces its path (on the CPU it takes the chunked one by itself)."""
    ctx = f32_ctx(training, impl)
    params = dict(ATTN_PARAMS, indexer_heads=J, indexer_head_dim=C,
                  indexer_topk=topk, indexer_q_chunk=q_chunk)
    (y,) = MultiHeadAttentionOp().emit(params, [x, x, x, pos], w, ctx,
                                       "attn")
    (kl,) = ctx.aux_losses
    return y, kl, ctx.counters


def attn_inputs(seq, seed=1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, seq, 32)), jnp.float32)
    pos = jnp.tile(jnp.arange(seq, dtype=jnp.int32), (B, 1))
    return x, pos


@pytest.mark.parametrize("seq,topk,q_chunk", [
    (48, 12, 16), (40, 12, 16), (64, 24, 8), (33, 5, 7)])
def test_the_layer_is_the_reference_output_loss_and_selection(seq, topk,
                                                              q_chunk):
    x, pos = attn_inputs(seq)
    w = attn_weights()
    y, kl, counted = attn_op(x, pos, w, topk, q_chunk)
    sizes = dict(ATTN_SIZES, sa_config={"topk": topk})
    with jax.default_matmul_precision("highest"):
        want_y, want_kl, want_set = jax.jit(
            lambda x, w: ref.sparse_attention(x, pos, w, sizes))(x, w)
    close(y, want_y)
    close(kl, want_kl)
    assert float(kl) > 1e-3
    got_set = jax.jit(lambda x, w: dsa.selection(
        *ref.indexer(x, w), topk, q_chunk, jnp.float32))(x, w)
    assert np.array_equal(np.asarray(got_set), np.asarray(want_set))
    per_row = np.minimum(np.arange(seq) + 1, topk)
    assert float(counted["dsa.kept_pairs"]) == B * per_row.sum() \
        == np.asarray(want_set).sum()
    assert float(counted["dsa.causal_pairs"]) == B * seq * (seq + 1) / 2
    assert float(counted["dsa.layers"]) == 1.0
    close(counted["dsa.index_kl"], want_kl)


def test_the_reference_in_blocks_of_rows_is_the_reference_whole(
        monkeypatch):
    """The benchmark's 8,192 positions go through the reference 256 rows
    at a time (``jax.lax.map``); every other test here is one block."""
    x, pos = attn_inputs(48)
    w = attn_weights()
    sizes = dict(ATTN_SIZES, sa_config={"topk": 12})

    def run():
        def f(x, w):
            with jax.default_matmul_precision("highest"):
                y, kl, keep = ref.sparse_attention(x, pos, w, sizes)
            return jnp.sum(y * jnp.cos(y)) + kl, (y, keep)
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))(x, w)

    ((v1, (y1, keep1)), (gx1, gw1)) = run()
    monkeypatch.setattr(ref, "QUERY_ROWS", 16)
    ((v2, (y2, keep2)), (gx2, gw2)) = run()
    assert np.array_equal(np.asarray(keep1), np.asarray(keep2))
    close(y2, y1, 1e-6)
    close(v2, v1, 1e-6)
    close(gx2, gx1, 1e-5)
    for k in gw1:
        close(gw2[k], gw1[k], 1e-5)


def test_the_chunked_path_is_the_unchunked_one():
    x, pos = attn_inputs(48)
    w = attn_weights()

    def both(q_chunk):
        def f(x, w):
            y, kl, _ = attn_op(x, pos, w, 12, q_chunk)
            return jnp.sum(y * jnp.cos(y)) + kl
        return jax.jit(jax.value_and_grad(f, (0, 1)))(x, w)

    (v1, (gx1, gw1)), (v2, (gx2, gw2)) = both(48), both(16)
    close(v2, v1, 1e-6)
    close(gx2, gx1, 1e-5)
    for k in gw1:
        close(gw2[k], gw1[k], 1e-5)


@pytest.mark.parametrize("seq,topk", [(32, 32), (24, 64)])
def test_no_more_positions_than_topk_is_the_plain_causal_path(seq, topk):
    """Every causal key is selected: the output is what the layer
    without an indexer gives on its plain path for the same weights
    (and the alignment loss is still there to train the indexer)."""
    x, pos = attn_inputs(seq)
    w = attn_weights()
    y, kl, counted = attn_op(x, pos, w, topk, 16)
    plain = {k: v for k, v in w.items() if not k.endswith("_idx")}
    (want,) = jax.jit(lambda x, w: MultiHeadAttentionOp().emit(
        ATTN_PARAMS, [x, x, x, pos], w, f32_ctx(impl="xla"), "attn"))(
            x, plain)
    close(y, want, 1e-6)
    assert float(counted["dsa.kept_pairs"]) \
        == float(counted["dsa.causal_pairs"])
    assert float(counted["dsa.threshold_ties"]) == 0.0
    assert float(kl) > 0


def test_the_two_losses_reach_disjoint_weights_exactly():
    """``L_I`` moves the indexer's three matrices and nothing else, not
    the layer's input either; what follows the output moves everything
    but them: zeros to the last bit, by the stop-gradients."""
    x, pos = attn_inputs(48)
    w = attn_weights()
    index_keys = {"wq_idx", "wk_idx", "w_idx"}

    def kl_of(x, w):
        return attn_op(x, pos, w, 12, 16)[1]

    def out_of(x, w):
        y = attn_op(x, pos, w, 12, 16)[0]
        return jnp.sum(y * jnp.sin(y))

    gx, gw = jax.jit(jax.grad(kl_of, (0, 1)))(x, w)
    gw_kl = gw
    assert not np.any(np.asarray(gx))
    for k, g in gw.items():
        assert bool(np.any(np.asarray(g))) == (k in index_keys), k
    gx, gw = jax.jit(jax.grad(out_of, (0, 1)))(x, w)
    assert np.any(np.asarray(gx))
    for k, g in gw.items():
        assert bool(np.any(np.asarray(g))) == (k not in index_keys), k
    # and the indexer's gradients are the reference's
    sizes = dict(ATTN_SIZES, sa_config={"topk": 12})

    def ref_kl(w):
        with jax.default_matmul_precision("highest"):
            return ref.sparse_attention(x, pos, w, sizes)[1]
    want = jax.jit(jax.grad(ref_kl))(w)
    got = gw_kl
    for k in index_keys:
        close(got[k], want[k])


@pytest.mark.parametrize("what,kwargs", [
    ("dropout", {"dropout": 0.1}), ("not causal", {"causal": False}),
    ("window", {"sliding_window": 8})])
def test_an_indexer_is_refused_where_it_is_not_built(what, kwargs):
    ff = FFModel(FFConfig())
    x = ff.create_tensor((2, 16, 32), name="x")
    with pytest.raises(ValueError):
        ff.multihead_attention(
            x, x, x, 32, 4, **dict({"causal": True}, **kwargs),
            indexer={"heads": 2, "head_dim": 8, "topk": 4, "q_chunk": 8})


@pytest.mark.parametrize("impl", ["flash", "ring"])
def test_a_kernel_forced_on_a_layer_with_an_indexer(impl):
    """``ring`` takes no mask and is refused; ``flash`` runs (in
    interpret mode here) and its step is the chunked path's."""
    if impl == "ring":
        cfg = FFConfig()
        cfg.batch_size = B
        cfg.only_data_parallel = True
        cfg.kernel_impls = "attention:ring"
        ff = FFModel(cfg)
        out = build_hybrid_conv_moe(ff, B, 32, KeyeRankConfig.tiny())
        with pytest.raises(Exception, match="selected keys|sequence axis"):
            ff.compile(AdamOptimizer(1e-3),
                       "sparse_categorical_crossentropy", [],
                       output_tensor=out)
        return
    steps = {}
    for forced in ("xla", "flash"):
        ff, mc = build(remat="blocks", attention=forced, devices=1)
        step, batch = ff.executor.make_train_step(), data(mc)
        p, o, st, first = step(ff.params, ff.opt_state, ff.state,
                               jnp.int32(0), batch)
        _, _, _, second = step(p, o, st, jnp.int32(1), batch)
        assert set(ff.executor.resolved_attention_impls.values()) == {forced}
        steps[forced] = (first, second)
    # the second step's loss is a function of every gradient of the first
    for chunked, kernels in zip(steps["xla"], steps["flash"]):
        close(kernels["loss"], chunked["loss"], 1e-5)
        assert float(chunked[COUNTER_PREFIX + "dsa.kernel_layers"]) == 0.0
        assert float(kernels[COUNTER_PREFIX + "dsa.kernel_layers"]) \
            == float(kernels[COUNTER_PREFIX + "dsa.layers"]) == 4.0
        close(kernels[COUNTER_PREFIX + "dsa.index_kl"],
              chunked[COUNTER_PREFIX + "dsa.index_kl"], 1e-5)
    assert float(steps["flash"][1]["loss"]) < float(steps["flash"][0]["loss"])


# ----------------------------------------------------------------------
# the kernel path (the flash kernels under the selection as their mask)
# against the chunked path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seq,topk,q_chunk", [
    (48, 12, 16), (40, 12, 16), (64, 24, 8), (33, 5, 7), (24, 64, 16)])
def test_the_kernel_path_is_the_chunked_path(seq, topk, q_chunk):
    """Output, ``L_I``, the counters and both families of gradients, for
    sequences that fill their chunks and tiles and ones that do not, and
    one no longer than ``topk`` (every causal key selected)."""
    x, pos = attn_inputs(seq)
    w = attn_weights()

    def both(impl):
        def f(x, w):
            y, kl, counted = attn_op(x, pos, w, topk, q_chunk, True, impl)
            return jnp.sum(y * jnp.cos(y)) + kl, (y, kl, counted)
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))(x, w)

    ((v1, (y1, kl1, c1)), (gx1, gw1)) = both("xla")
    ((v2, (y2, kl2, c2)), (gx2, gw2)) = both("flash")
    close(y2, y1, 1e-5)
    close(kl2, kl1, 1e-5)
    close(v2, v1, 1e-5)
    for key in ("dsa.kept_pairs", "dsa.causal_pairs", "dsa.threshold_ties",
                "dsa.layers"):
        assert float(c2[key]) == float(c1[key]), key
    assert (float(c1["dsa.kernel_layers"]),
            float(c2["dsa.kernel_layers"])) == (0.0, 1.0)
    close(gx2, gx1, 1e-5)
    for k in gw1:
        close(gw2[k], gw1[k], 1e-5)


@pytest.mark.parametrize("heads_first", [False, True])
@pytest.mark.parametrize("kv", [1, 2])
def test_the_kernel_path_reads_grouped_keys_and_values_in_place(kv,
                                                                heads_first):
    """``sparse_index_attention_flash`` with k and v at ``kv`` heads is
    the call on heads repeated to the 4 query heads (what the layer
    handed it before PR 52): output, ``L_I``, the counts and every
    gradient, k's and v's summed over their group; ``heads_first`` as
    ``kernels/qk_norm_rope`` hands q and k."""
    rng = np.random.default_rng(52)
    seq, topk, q_chunk = 40, 12, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, seq, n, D)), jnp.float32)
               for n in (HEADS, kv, kv))
    qi = jnp.asarray(rng.normal(size=(B, seq, J, C)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(B, seq, C)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(B, seq, J)), jnp.float32)

    def run(repeat):
        def f(q, k, v, qi, ki, wi):
            if repeat:
                k, v = (jnp.repeat(x, HEADS // kv, axis=2) for x in (k, v))
            if heads_first:
                q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
            o, kl, kept, ties = dsa.sparse_index_attention_flash(
                q, k, v, qi, ki, wi, topk, q_chunk, jnp.float32,
                qk_heads_first=heads_first)
            return jnp.sum(jnp.sin(o)) + kl, (o, kl, kept, ties)
        return jax.jit(jax.value_and_grad(f, (0, 1, 2, 3, 4, 5),
                                         has_aux=True))(q, k, v, qi, ki, wi)

    (_, got), g_got = run(False)
    (_, want), g_want = run(True)
    for a, b in zip(got, want):
        close(a, b, 1e-6)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        close(a, b, 1e-5)


@pytest.mark.parametrize("impl,counted", [("flash", 1.0), ("xla", None)])
def test_a_layer_whose_kernels_read_grouped_heads_says_so(impl, counted):
    """``attn.grouped_kv_layers``: 1 where the flash kernels got fewer
    k/v heads than query heads, absent on the chunked path (which
    contracts on repeated heads)."""
    x, pos = attn_inputs(48)
    _, _, counters = attn_op(x, pos, attn_weights(), 12, 16, True, impl)
    got = counters.get("attn.grouped_kv_layers")
    assert (got if got is None else float(got)) == counted


def test_the_kernel_paths_losses_reach_disjoint_weights_exactly():
    """As on the chunked path: ``L_I`` moves the indexer alone and the
    output everything but the indexer, zeros to the last bit."""
    x, pos = attn_inputs(48)
    w = attn_weights()
    index_keys = {"wq_idx", "wk_idx", "w_idx"}
    gx, gw = jax.jit(jax.grad(
        lambda x, w: attn_op(x, pos, w, 12, 16, True, "flash")[1],
        (0, 1)))(x, w)
    assert not np.any(np.asarray(gx))
    for k, g in gw.items():
        assert bool(np.any(np.asarray(g))) == (k in index_keys), k
    gx, gw = jax.jit(jax.grad(
        lambda x, w: jnp.sum(jnp.sin(
            attn_op(x, pos, w, 12, 16, True, "flash")[0])), (0, 1)))(x, w)
    assert np.any(np.asarray(gx))
    for k, g in gw.items():
        assert bool(np.any(np.asarray(g))) == (k not in index_keys), k


@pytest.mark.parametrize("remat", ["none", "blocks"])
def test_the_kernel_paths_step_is_the_chunked_paths(remat):
    """The model's loss with its four ``L_I``, the counters and every
    gradient, alone and inside a rematerialised step."""
    (l1, (kl1, bm1)), g1 = terms_and_gradients(remat, "xla")
    (l2, (kl2, bm2)), g2 = terms_and_gradients(remat, "flash")
    assert float(kl1) > 0.1
    close(l2, l1, 1e-6)
    close(kl2, kl1, 1e-5)
    for key in bm1:
        if key.startswith(COUNTER_PREFIX) and "kernel_layers" not in key:
            close(bm2[key], bm1[key], 1e-6)
    assert float(bm2[COUNTER_PREFIX + "dsa.kernel_layers"]) == 4.0
    for name, ws in g1.items():
        for k in ws:
            close(g2[name][k], ws[k], 2e-5)


def test_the_path_taken_is_on_the_record():
    """``resolved_attention_impls`` and each layer's ``attn.sparse_index``
    instant name the path the trace emitted."""
    from flexflow_tpu.obs import events
    events.enable()
    try:
        for impl in ("xla", "flash"):
            events.clear()
            ff, mc = build(attention=impl, devices=1)
            jax.eval_shape(lambda p: program_terms(ff, p, data(mc)),
                           ff.params)
            seen = [e["attrs"]["impl"] for e in events.events()
                    if e["name"] == "attn.sparse_index"]
            assert seen and set(seen) == {impl}
            assert set(ff.executor.resolved_attention_impls.values()) \
                == {impl}
    finally:
        events.clear()
        events.disable()


def test_a_rematerialised_block_keeps_what_the_kernel_path_names():
    """``remat = "blocks"``: the differentiated step calls the forward
    kernel once a layer (the block keeps its output and log-sum-exp, and
    the mask: no second selection either), dq and dkv once, and the
    head-mean kernel for the loss's value and for its backward. The
    jaxpr holds a third head-mean call a layer, the block's second run
    of the loss's value behind the layer's optimization barrier; nothing
    reads it and XLA drops it (the chip's compiled step holds 5 kernel
    calls a layer, PERF.md section 5)."""
    ff, mc = build(remat="blocks", attention="flash", devices=1)
    batch = data(mc)

    def f(p):
        ce, kl, _, _ = program_terms(ff, p, batch)
        return ce + kl
    txt = str(jax.make_jaxpr(jax.grad(f))(ff.params))
    layers = mc.num_hidden_layers

    def calls(kernel):
        return len(re.findall(rf"name=flash_attention_{kernel}\b", txt))
    assert [calls(k) for k in ("fwd", "bwd_dq", "bwd_dkv")] == [layers] * 3
    assert calls("head_mean") == 3 * layers


def test_an_indexer_has_no_key_value_cache():
    x, pos = attn_inputs(16)
    ctx = f32_ctx(False)
    ctx.kv_mode = "prefill"
    params = dict(ATTN_PARAMS, indexer_heads=J, indexer_head_dim=C,
                  indexer_topk=4, indexer_q_chunk=8)
    with pytest.raises(ValueError, match="key/value cache"):
        MultiHeadAttentionOp().emit(params, [x, x, x, pos], attn_weights(),
                                    ctx, "attn")


# ----------------------------------------------------------------------
# the router and the experts' shares
# ----------------------------------------------------------------------
def test_softmax_route_is_the_references_gates():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(40, 16)) * 2, jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 10, jnp.float32)  # not read
    idx, g = route(logits, bias, 4, 1.0, "softmax")
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(g), -1)
    x = jnp.asarray(rng.normal(size=(40, 8)), jnp.float32)
    wg = jnp.asarray(np.linalg.lstsq(np.asarray(x), np.asarray(logits),
                                     rcond=None)[0], jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.gates(x, {"wg": wg}, {"num_experts_per_tok": 4})
        again = route(x @ wg, bias, 4, 1.0, "softmax")
    dense2 = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense2, np.asarray(again[0]), np.asarray(again[1]), -1)
    close(dense2, want, 1e-5)
    close(dense.sum(-1), np.ones(40), 1e-6)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(
        jax.lax.top_k(logits, 4)[1]), -1))


def test_sigmoid_route_is_what_it_was():
    """The bias corrects the choice only; the gates are the chosen
    experts' own sigmoid scores over their sum, times the scale."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(30, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=16), jnp.float32)
    idx, g = route(logits, bias, 4, 2.5)
    s = jax.nn.sigmoid(logits)
    want_idx = jax.lax.top_k(s + bias, 4)[1]
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    chosen = jnp.take_along_axis(s, want_idx, -1)
    close(g, 2.5 * chosen / chosen.sum(-1, keepdims=True), 1e-6)
    assert np.array_equal(np.asarray(route(logits, bias, 4, 2.5,
                                           "sigmoid")[1]), np.asarray(g))


def expert_weights(n=16, e=32, f=16, seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    return {"wg": w(e, n) * 3, "bias": jnp.zeros((n,), jnp.float32),
            "w_gate": w(n, e, f), "w_up": w(n, e, f), "w_down": w(n, f, e)}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, ... of 16, one share a chip, each chip routing
    over all 16: the shares' outputs add up to the uncut reference's
    layer; the router, which every chip computes alike, is counted
    once (it adds nothing of its own to the output)."""
    w = expert_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    sizes = {"num_experts_per_tok": 4}
    with jax.default_matmul_precision("highest"):
        want = ref.routed(x, w, sizes)
    total = jnp.zeros_like(x)
    for r in range(8):
        held = slice(2 * r, 2 * r + 2)
        mine = dict(w, w_gate=w["w_gate"][held], w_up=w["w_up"][held],
                    w_down=w["w_down"][held])
        params = {"num_experts": 16, "top_k": 4, "expert_dim": 16,
                  "shared_dim": 0, "experts_held": 2, "first_held": 2 * r,
                  "scale": 1.0, "bias_std": 0.0, "scoring": "softmax"}
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(params, [x], mine, ctx, "experts")
        with jax.default_matmul_precision("highest"):
            close(y, ref.routed(x, mine, dict(sizes,
                                              first_held_expert=2 * r)))
        assert float(ctx.counters["moe.dropped"]) == 0.0
        total = total + y
    close(total, want)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def test_the_model_is_the_reference_log_probabilities_and_losses(tiny):
    ff, mc, batch, params = tiny
    ce, kl, bm, probs = jax.jit(lambda p: program_terms(
        ff, p, batch, training=False))(params)
    want = jax.jit(lambda p: ref.sparse_index_moe_decoder(
        named(ff, p), sizes_of(mc), batch["input_ids"],
        batch["position_ids"]))(params)
    close(jnp.log(probs), want)
    want_ce, want_kl = jax.jit(
        lambda p: reference_terms(ff, mc, p, batch))(params)
    close(ce, want_ce)
    close(kl, want_kl)
    assert float(kl) > 0.05 * mc.num_hidden_layers
    # S > topk: the selection is live in three of the four chunks
    kept = float(bm[COUNTER_PREFIX + "dsa.kept_pairs"])
    per_row = np.minimum(np.arange(S) + 1, mc.sa_config["topk"])
    assert kept == mc.num_hidden_layers * B * per_row.sum()
    assert kept < float(bm[COUNTER_PREFIX + "dsa.causal_pairs"])
    assert float(bm[COUNTER_PREFIX + "moe.dropped"]) == 0.0


def test_the_models_selections_are_the_references(tiny):
    """Layer by layer, from each attention layer's own input (taken
    from the reference's walk, which the test above holds the program
    to): the program's mask is the reference's."""
    ff, mc, batch, params = tiny
    sizes = sizes_of(mc)

    @jax.jit
    def masks(params):
        with jax.default_matmul_precision("highest"):
            want = ref.selections(named(ff, params), sizes,
                                  batch["input_ids"], batch["position_ids"])
            x = params["embed_tokens"]["kernel"][batch["input_ids"]]
            got = []
            for i in range(mc.num_hidden_layers):
                norm1 = params[f"operator_norm_{i}"]["scale"]
                qi, ki, wi = ref.indexer(
                    ref.rms_norm(x, norm1, mc.rms_norm_eps),
                    params[f"attn_{i}"])
                got.append(dsa.selection(
                    qi, ki, wi, mc.sa_config["topk"],
                    mc.sa_config["q_chunk_size"], jnp.float32))
                x, _, _ = ref.decoder_layer(
                    x, batch["position_ids"], norm1, params[f"attn_{i}"],
                    params[f"ffn_norm_{i}"]["scale"],
                    params[f"experts_{i}"], sizes)
            return got, want

    got, want = masks(params)
    for i in range(mc.num_hidden_layers):
        assert np.array_equal(np.asarray(got[i]), np.asarray(want[i])), i
        assert np.asarray(got[i]).sum() < B * S * (S + 1) / 2


def test_both_families_of_gradients_are_the_references(tiny):
    """The cross-entropy's gradient for every weight but the indexer's
    and ``L_I``'s for the indexer's, against ``jax.grad`` of the
    reference; each is exactly zero where the other lives."""
    ff, mc, batch, params = tiny
    index_keys = {"wq_idx", "wk_idx", "w_idx"}

    def terms(p):
        ce, kl, _, _ = program_terms(ff, p, batch)
        return ce, kl
    g_ce, g_kl = jax.jit(lambda p: (
        jax.grad(lambda p: terms(p)[0])(p),
        jax.grad(lambda p: terms(p)[1])(p)))(params)
    want_ce, want_kl = jax.jit(lambda p: (
        jax.grad(lambda p: reference_terms(ff, mc, p, batch)[0])(p),
        jax.grad(lambda p: reference_terms(ff, mc, p, batch)[1])(p)))(
            params)
    for name, ws in params.items():
        for k in ws:
            if k == "bias":              # no gradient by construction
                continue
            if k in index_keys:
                assert not np.any(np.asarray(g_ce[name][k])), (name, k)
                close(g_kl[name][k], want_kl[name][k], 1e-3)
                assert np.any(np.asarray(g_kl[name][k]))
            else:
                assert not np.any(np.asarray(g_kl[name][k])), (name, k)
                close(g_ce[name][k], want_ce[name][k], 1e-3)


def test_four_equal_layers_are_four_rematerialised_blocks():
    ff, mc = build(remat="blocks")
    start, unit, reps = _find_remat_blocks(ff.layers)[:3]
    kinds = [l.op_type.name for l in ff.layers[start:start + unit]]
    assert (unit, reps) == (6, 4)
    assert sorted(kinds) == sorted([
        "OP_RMSNORM", "OP_MULTIHEAD_ATTENTION", "OP_EW_ADD", "OP_RMSNORM",
        "OP_ROUTED_EXPERTS", "OP_EW_ADD"])
    assert ff.executor._remat[:3] == (start, unit, reps)


def test_a_rematerialised_step_is_the_step_loss_aux_and_gradients():
    """``remat = "blocks"``: every block holds an op with an auxiliary
    loss, which leaves ``jax.checkpoint`` as an output of the block;
    loss (with the four ``L_I``), counters and every gradient equal the
    step's without rematerialisation."""
    (l1, (kl1, bm1)), g1 = terms_and_gradients("none", "xla")
    (l2, (kl2, bm2)), g2 = terms_and_gradients("blocks", "xla")
    assert float(kl1) > 0.1
    close(l2, l1, 1e-6)
    close(kl2, kl1, 1e-6)
    for key in bm1:
        if key.startswith(COUNTER_PREFIX):
            close(bm2[key], bm1[key], 1e-6)
    for name, ws in g1.items():
        for k in ws:
            close(g2[name][k], ws[k], 1e-5)


def test_a_train_step_moves_the_indexer_and_lowers_the_loss():
    ff, mc = build(remat="blocks")
    batch = data(mc)
    step = ff.executor.make_train_step()
    before = jax.tree.map(np.asarray, ff.params["attn_0"])
    losses = []
    p, o, st = ff.params, ff.opt_state, ff.state
    for _ in range(4):
        p, o, st, bm = step(p, o, st, jnp.int32(0), batch)
        losses.append(float(bm["loss"]))
    assert losses[-1] < losses[0]
    for k in ("wq_idx", "wk_idx", "w_idx", "wq", "wo"):
        assert np.any(np.asarray(p["attn_0"][k]) != before[k]), k
    assert float(bm[COUNTER_PREFIX + "dsa.layers"]) == 4.0


# ----------------------------------------------------------------------
# the older configurations
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cls", [HybridConvMoEConfig, LFM2RankConfig])
def test_the_older_graph_names_no_indexer_and_no_score_function(cls):
    """A graph built from the classes ``lfm2_24b_a2b`` uses has the
    parameters it had: the new fields live on ``KeyeRankConfig`` alone.
    (``tests/test_lowered_steps.py`` pins the sha256 of every rank
    configuration's lowered step.)"""
    ff = FFModel(FFConfig())
    build_hybrid_conv_moe(ff, 1, 32, cls.tiny() if cls is HybridConvMoEConfig
                          else dataclasses.replace(
                              HybridConvMoEConfig.tiny()))
    for l in ff.layers:
        assert not any(k.startswith("indexer_") for k in l.params), l.name
        assert "scoring" not in l.params, l.name
    assert not hasattr(cls(), "sa_config")


def test_a_sparse_attention_layer_needs_an_sa_config():
    mc = dataclasses.replace(HybridConvMoEConfig.tiny(),
                             layer_types=["sparse_attention"] * 5)
    with pytest.raises(ValueError, match="sa_config"):
        build_hybrid_conv_moe(FFModel(FFConfig()), 1, 32, mc)
