"""The block-diffusion training step (a noising op on the step's key, a
decoder over 2 L positions under the block-diffusion mask, a loss
weighted by masked position and 1 / t; ``SDARRankConfig``) against its
plain reference (``benchmarks/reference/block_diffusion_moe_ref.py``),
at a small size on the CPU with seeded random weights.

Precision: the program computes in float32 here (``use_bf16_compute``
off) and the CPU's float32 matrix product is exact to rounding, as is
the reference's ``highest``; the two differ in the order of their sums.
``TOL`` = 2e-4 relative to the largest entry is far under what one key
more or fewer in a block of 4, positions that run on, a loss that
ignores its weights or a mask drawn from the ids moves.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu.kernels.flash_attention import (BD_SUB, _bd_live,
                                                  _bd_live_k, _bd_live_q,
                                                  _bd_sub, _fwd_piece,
                                                  block_diffusion_mask,
                                                  block_diffusion_visited,
                                                  flash_attention,
                                                  grid_steps)
from flexflow_tpu.models.nlp import SDARRankConfig, build_hybrid_conv_moe
from flexflow_tpu.obs import events
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.nn_ops import (BlockDiffusionNoiseOp,
                                     MultiHeadAttentionOp)
from flexflow_tpu.runtime import losses
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX
from rank_family import B, apart, close, f32_ctx, program

ref = rf.reference("block_diffusion_moe_ref")
S = 32                    # tiny(): eight blocks of 4
build = functools.partial(rf.build, SDARRankConfig, build_hybrid_conv_moe,
                          seq=S)


def data(mc, seq=S, seed=1, batch=B):
    """``rf.data`` with the mask id among the ids of every sequence, at
    places the eval draw masks and at places it leaves: a mask drawn by
    comparing ids shows."""
    out = rf.data(mc, seq, seed, batch)
    ids = np.asarray(out["input_ids"]).copy()
    ids[:, [2, 7, 13, 21]] = mc.mask_token_id
    return dict(out, input_ids=jnp.asarray(ids),
                label=jnp.asarray(np.roll(ids, -1, 1)[..., None]))


def spread(params):
    """The seed's weights with every norm's scale off 1."""
    def rule(name, k, w, rng):
        if k in ("scale", "q_norm", "k_norm"):
            return rf.scaled(w, rng)
    return rf.spread(params, rule)


tiny, tiny_step = rf.fixtures(build, data, spread)


# ----------------------------------------------------------------------
# the mask
# ----------------------------------------------------------------------
# L 16, B 4: four blocks a half, a block a character ([noised | clean])
BY_BLOCK = ["1000 0000",      # noised block 0: itself
            "0100 1000",      # noised block 1: itself, clean block 0
            "0010 1100",
            "0001 1110",
            "0000 1000",      # clean block 0: itself
            "0000 1100",
            "0000 1110",
            "0000 1111"]


def test_the_mask_is_the_table_written_by_hand():
    table = np.array([[c == "1" for c in row.replace(" ", "")]
                      for row in BY_BLOCK])
    want = np.kron(table, np.ones((4, 4), bool))
    got = block_diffusion_mask(16, 4)
    assert got.shape == (32, 32) and (got == want).all()
    assert got.sum() == 16 * 16 + 16 * 4          # L L + L B
    assert got.any(axis=1).all()                  # every query has a key
    # the reference writes the same table from the halves and the blocks
    i = jnp.arange(32)
    assert (np.asarray(ref.allowed(i, i, 16, 4)) == want).all()


@pytest.mark.parametrize("length,block", [(24, 3), (24, 8), (12, 12)])
def test_the_mask_at_other_blocks(length, block):
    got = block_diffusion_mask(length, block)
    for q in range(2 * length):
        for k in range(2 * length):
            qb, kb = (q % length) // block, (k % length) // block
            want = (kb == qb if k < length else kb < qb) if q < length \
                else (k >= length and kb <= qb)
            assert got[q, k] == want, (q, k)


# ----------------------------------------------------------------------
# the flash kernels (interpreted) against the explicit mask
# ----------------------------------------------------------------------
def masked_softmax(q, k, v, mask):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, 1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def blocks_of(q, k, bwd_q=None, bwd_k=None):
    return dict(block_q=q, block_k=k, bwd_block_q=bwd_q or q,
                bwd_block_k=bwd_k or k)


SQUARE = blocks_of(128, 128)
WIDE = 2 * BD_SUB          # a tile of two sub-blocks a side
KERNEL_CASES = {       # (L, B, blocks): a block that does not divide a tile,
    "one_tile_a_half": (128, 4, {}),          # tiles of several shapes
    "two_tiles_a_half": (256, 4, SQUARE),
    "block_24_in_tiles_of_128": (384, 24, SQUARE),
    "unequal_tiles": (256, 32, dict(block_q=64, block_k=256,
                                    bwd_block_q=64, bwd_block_k=128)),
    # the noised x noised tiles walked along their diagonal (_bd_sub) ...
    "walked_one_tile_a_half": (WIDE, 4, blocks_of(WIDE, WIDE)),
    "walked_two_tiles_a_half": (2 * WIDE, 4, blocks_of(WIDE, WIDE)),
    "walked_a_block_of_the_sub_blocks_side": (WIDE, BD_SUB,
                                              blocks_of(WIDE, WIDE)),
    "walked_a_wider_k_side": (2 * WIDE, 4, blocks_of(BD_SUB, 2 * WIDE,
                                                 BD_SUB, WIDE)),
    "walked_a_wider_q_side": (2 * WIDE, 4, blocks_of(WIDE, BD_SUB)),
    # the cell's forward: pieces of 512 keys under a q block of 1,024 in
    # a k block of whole q blocks, the rows' places static
    "walked_pieces_of_a_k_block_of_whole_q_blocks": (
        2048, 4, blocks_of(1024, 2048, 512, 1024)),
    # ... and scored whole: a block that does not divide the sub-block
    "whole_a_block_of_6": (3 * BD_SUB, 6, blocks_of(3 * BD_SUB, 3 * BD_SUB)),
    "whole_a_block_wider_than_the_sub_block": (
        2 * WIDE, WIDE, blocks_of(WIDE, WIDE)),
    "whole_a_block_wider_at_one_tile_a_half": (
        WIDE, WIDE, blocks_of(WIDE, WIDE)),
}
WALKED = {c for c in KERNEL_CASES if c.startswith("walked")}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernels_draw_the_mask(case):
    length, block, blocks = KERNEL_CASES[case]
    ks = jax.random.split(jax.random.key(length + block), 4)
    q, k, v, do = (jax.random.normal(ks[i], (1, n, 2 * length, 16))
                   for i, n in enumerate((4, 2, 2, 4)))
    mask = jnp.asarray(block_diffusion_mask(length, block))

    def flash(q, k, v):
        return flash_attention(q, k, v, block_diffusion=(length, block),
                               interpret=True, **blocks)

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * do), (0, 1, 2)))

    close(jax.jit(flash)(q, k, v), masked_softmax(q, k, v, mask), 1e-5)
    events.enable()
    events.clear()
    try:
        (_, got) = graded(flash)(q, k, v)
        subs = {e["attrs"]["kernel"][len("flash_attention_"):]:
                e["attrs"]["bd_sub"] for e in events.events()
                if e["name"] == "flash.grid"}
    finally:
        events.disable()
        events.clear()
    (_, want) = graded(functools.partial(masked_softmax, mask=mask))(q, k, v)
    for g, w in zip(got, want):
        close(g, w, 1e-5)
    # each call says whether its diagonal tiles were walked
    assert subs == dict.fromkeys(("fwd", "bwd_dq", "bwd_dkv"),
                                 BD_SUB if case in WALKED else 0), subs


def entered_pairs(mask, length, block_q, wide, sub):
    """The pairs a kernel's body is entered with over one head, counted
    from the table: a live tile's ``block_q x wide``, but, ``sub`` not 0,
    of a live tile of the noised x noised quadrant those of its ``sub x
    sub`` blocks that hold an attended pair."""
    n = 2 * length
    live = mask.reshape(n // block_q, block_q, n // wide, wide).any((1, 3))
    pairs = int(live.sum()) * block_q * wide
    if sub:
        noised = mask[:length, :length]
        tiles = noised.reshape(length // block_q, block_q, length // wide,
                               wide).any((1, 3))
        blocks = noised.reshape(length // sub, sub, length // sub,
                                sub).any((1, 3))
        pairs += int(blocks.sum()) * sub * sub \
            - int(tiles.sum()) * block_q * wide
    return pairs


@pytest.mark.parametrize("length,block,block_q,block_k", [
    (4096, 4, 1024, 512), (4096, 4, 1024, 1024), (1536, 24, 128, 256),
    (512, 4, 256, 128), (256, 128, 128, 128), (4096, 4, 1024, 4096),
    (1024, 128, 256, 512), (1024, 256, 512, 512), (768, 6, 384, 384),
    (2048, 4, 512, 1024), (1536, 4, 384, 256)])
def test_the_grids_visit_the_live_tiles_and_name_them(length, block,
                                                      block_q, block_k):
    """A tile is live where the mask attends any of its pairs; a live
    step names its own block in both index maps; a dead one names a
    block in range; ``visited_pairs`` are the pairs the kernel's body is
    entered with, a walked diagonal tile's sub-blocks alone."""
    mask = block_diffusion_mask(length, block)
    nq, nk = 2 * length // block_q, 2 * length // block_k
    tiles = mask.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))
    name_k = _bd_live_k(block_q, block_k, (length, block))
    name_q = _bd_live_q(block_q, block_k, (length, block))
    for i in range(nq):
        for j in range(nk):
            live = bool(_bd_live(i, j, block_q, block_k, (length, block)))
            assert live == tiles[i, j], (i, j)
            if live:
                assert name_k(i, j) == j and name_q(j, i) == i, (i, j)
            assert 0 <= name_k(i, j) < nk and 0 <= name_q(j, i) < nq
    for kernel in ("bwd_dq", "bwd_dkv"):
        g = grid_steps(kernel, 2, 2 * length, 2 * length, block_q, block_k,
                       False, 0, 1, (length, block))
        assert g["live_steps"] == 2 * tiles.sum() <= g["fetched_steps"]
        # nothing is copied for a dead step (but where a block of tokens
        # is a whole tile and a row's half of the keys has no live tile)
        assert block >= block_q or g["fetched_steps"] == g["live_steps"]
        assert g["bd_sub"] == _bd_sub(block_q, block_k, (length, block))
        assert g["visited_pairs"] == 2 * entered_pairs(
            mask, length, block_q, block_k, g["bd_sub"])
    piece = _fwd_piece(block_k)
    g = grid_steps("fwd", 2, 2 * length, 2 * length, block_q, block_k, False,
                   0, 1, (length, block))
    assert g["bd_sub"] == _bd_sub(block_q, piece, (length, block))
    assert g["visited_pairs"] == 2 * entered_pairs(mask, length, block_q,
                                                   piece, g["bd_sub"])
    assert g["live_pieces"] == 2 * mask.reshape(
        nq, block_q, 2 * length // piece, piece).any(axis=(1, 3)).sum()


@pytest.mark.parametrize("block_q,block_k,block,sub", [
    (1024, 1024, 4, BD_SUB), (1024, 512, 4, BD_SUB), (512, 1024, 4, BD_SUB),
    (2 * BD_SUB, BD_SUB, 4, BD_SUB), (BD_SUB, 2 * BD_SUB, BD_SUB, BD_SUB),
    (BD_SUB, BD_SUB, 4, 0),               # the tile is one sub-block
    (1024, 1024, 6, 0), (1024, 1024, 2 * BD_SUB, 0),      # B and the side
    (3 * BD_SUB, 2 * BD_SUB, 4, 0),       # tiles that do not nest
    (BD_SUB + BD_SUB // 2, 3 * BD_SUB, 4, 0)])    # a side it does not divide
def test_which_tiles_are_walked_along_their_diagonal(block_q, block_k,
                                                     block, sub):
    assert _bd_sub(block_q, block_k, (12 * 1024, block)) == sub
    assert _bd_sub(block_q, block_k, ()) == 0


def test_the_cells_grids_skip_the_dead_quadrant_and_triangle():
    """At the cell's shapes (L 4,096, B 4): 48 of 128 pieces, 24 of 64
    tiles, the four on the noised x noised diagonal as their sub-blocks:
    (20 + 4 sub / 1,024) / 64 of the square for the mask's 0.2502."""
    share = (20 + 4 * BD_SUB / 1024) / 64
    assert share == {128: 0.3203125, 256: 0.328125, 512: 0.34375}[BD_SUB]
    fwd = grid_steps("fwd", 32, 8192, 8192, 1024, 4096, False, 0, 8,
                     (4096, 4))
    assert (fwd["live_pieces"], fwd["piece_k"]) == (32 * 48, 512)
    for kernel in ("bwd_dq", "bwd_dkv"):
        g = grid_steps(kernel, 32, 8192, 8192, 1024, 1024, False, 0, 8,
                       (4096, 4))
        assert g["live_steps"] == 32 * 24 and g["steps"] == 32 * 64
        assert g["bd_sub"] == fwd["bd_sub"] == BD_SUB
        assert g["visited_pairs"] == fwd["visited_pairs"] \
            == 32 * share * 8192 ** 2
    # the tiles the call derives from the cell's shapes, and the quotient
    # of the program's counters attn.bd_visited_pairs / attn.bd_pairs
    visited = block_diffusion_visited(32, 4096, 4, 128, jnp.bfloat16, 8)
    assert sum(visited.values()) / (3 * 32 * 8192 ** 2) == share


@pytest.mark.parametrize("what,kw", [
    ("causal", dict(causal=True)), ("window", dict(causal=True, window=8)),
    ("dropout", dict(dropout_rate=0.1, dropout_seed=1)),
    ("a length that is no multiple of 128", dict()),
    ("a block that does not divide", dict())])
def test_the_kernels_refuse_what_is_not_built_beside_the_mask(what, kw):
    length = 96 if "128" in what else 128
    bd = (length, 5 if "divide" in what else 4)
    q = jnp.zeros((1, 2, 2 * length, 16))
    with pytest.raises(NotImplementedError, match="block.diffusion"):
        flash_attention(q, q, q, block_diffusion=bd, interpret=True, **kw)


# ----------------------------------------------------------------------
# one attention layer
# ----------------------------------------------------------------------
E, H, KV, D = 32, 4, 2, 16
LAYER = {"embed_dim": E, "num_heads": H, "num_kv_heads": KV, "kdim": H * D,
         "vdim": H * D, "bias": False, "causal": False, "qk_norm": True,
         "qk_norm_eps": 1e-6, "rope": True, "rope_theta": 10000.0,
         "block_diffusion_block": 4}
SIZES = {"rms_norm_eps": 1e-6, "rope_theta": 10000.0, "block_length": 4}


def attn_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    return {"wq": w(E, H, D), "wk": w(E, KV, D), "wv": w(E, KV, D),
            "wo": w(H, D, E) * 4,
            "q_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32),
            "k_norm": jnp.asarray(rng.uniform(0.5, 1.5, D), jnp.float32)}


def layer(x, pos, w, impl, params=LAYER):
    return MultiHeadAttentionOp().emit(params, [x, x, x, pos], w,
                                       f32_ctx(impl=impl), "attn")[0]


@pytest.mark.parametrize("impl,length", [("xla", 16), ("xla", 128),
                                         ("flash", 128)])
def test_a_layer_is_the_references(impl, length):
    """Down XLA's explicit mask and down the flash kernels, forward and
    every gradient; the positions are the L given ones, used twice."""
    w = attn_weights()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 2 * length, E)), jnp.float32)
    pos = jnp.asarray(np.tile(np.arange(length, dtype=np.int32), (2, 1)))
    ct = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def want(x, w):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.attention(
                x, jnp.concatenate([pos, pos], 1), w, SIZES) * ct)

    def got(x, w):
        return jnp.sum(layer(x, pos, w, impl) * ct)

    (gv, gg), (wv, wg) = (jax.jit(jax.value_and_grad(f, (0, 1)))(x, w)
                          for f in (got, want))
    close(gv, wv)
    close(gg[0], wg[0])
    for key in w:
        close(gg[1][key], wg[1][key])
    # positions that run on through the clean half are another model's
    with ref.perturbed("consecutive_positions"), \
            jax.default_matmul_precision("highest"):
        other = ref.attention(x, jnp.concatenate([pos, pos + length], 1), w,
                              SIZES)
    apart(layer(x, pos, w, impl), other)


def test_a_layer_announces_the_mask_and_who_draws_it():
    w, length = attn_weights(), 128
    x = jnp.zeros((1, 2 * length, E))
    pos = jnp.arange(length, dtype=jnp.int32)[None]
    events.enable()
    events.clear()
    try:
        for impl in ("xla", "flash"):
            jax.eval_shape(lambda: layer(x, pos, w, impl))
        noted = [e["attrs"] for e in events.events()
                 if e["name"] == "attn.block_diffusion"]
    finally:
        events.disable()
        events.clear()
    assert [a["impl"] for a in noted] == ["xla", "flash"]
    xla, flash = noted
    assert xla["live_pairs"] == flash["live_pairs"] == 128 * 128 + 128 * 4
    assert xla["visited_pairs_fwd"] == xla["pairs"] == 4 * 128 * 128
    # one tile a half: the dead quadrant alone is skipped
    assert flash["visited_pairs_fwd"] == 3 * 128 * 128
    assert (xla["flash_calls"], flash["flash_calls"]) == (0, 3)


@pytest.mark.parametrize("beside", [
    {"causal": True}, {"sliding_window": 8}, {"output_gate": True},
    {"dropout": 0.1}, {"indexer_heads": 2}, {"differential": True}])
def test_a_layer_refuses_what_is_not_built_beside_the_mask(beside):
    x = jnp.zeros((1, 32, E))
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    w = dict(attn_weights(), wg=jnp.zeros((E, H, D)))
    with pytest.raises(ValueError, match="block-diffusion mask"):
        layer(x, pos, w, "xla", dict(LAYER, **beside))


# ----------------------------------------------------------------------
# the noising op
# ----------------------------------------------------------------------
NOISE = {"block_length": 4, "mask_token_id": 95, "t_min": 1e-3,
         "eval_noise_seed": 23}


def noise(ids, training=False, key=None, params=NOISE):
    ctx = f32_ctx(training=training)
    ctx.rngs = {"noise": key}
    z, w = BlockDiffusionNoiseOp().emit(params, [ids], {}, ctx, "noise")
    return np.asarray(z), np.asarray(w), ctx.counters


def test_the_noising_op_draws_what_the_reference_draws():
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 95, (3, 32)),
                      jnp.int32)
    z, w, counters = noise(ids)
    masked, t = (np.asarray(a) for a in ref.noise(NOISE, ids))
    assert z.shape == (3, 64) and (z[:, 32:] == np.asarray(ids)).all()
    assert (z[:, :32] == np.where(masked, 95, np.asarray(ids))).all()
    np.testing.assert_allclose(w, masked / t, rtol=1e-6)
    np.testing.assert_allclose(w, np.asarray(ref.weights(NOISE, ids)),
                               rtol=1e-6)
    # one t a block, clipped at t_min; weights of unmasked tokens are 0
    assert (t.reshape(3, 8, 4) == t.reshape(3, 8, 4)[..., :1]).all()
    assert t.min() >= 1e-3 and (w[~masked] == 0).all() and masked.any()
    assert float(counters["diffusion.tokens"]) == 96
    assert float(counters["diffusion.masked_tokens"]) == masked.sum()
    np.testing.assert_allclose(float(counters["diffusion.weight_sum"]),
                               w.sum(), rtol=1e-6)


def test_the_mask_is_the_draws_and_not_a_comparison_of_ids():
    """Every id the mask id: the noised half says nothing of the mask,
    the weights still do."""
    ids = jnp.full((2, 32), 95, jnp.int32)
    z, w, _ = noise(ids)
    masked, _ = ref.noise(NOISE, ids)
    assert (z == 95).all() and ((w > 0) == np.asarray(masked)).all()
    assert 0 < (w > 0).sum() < w.size


def test_a_new_mask_a_step_index_and_one_mask_at_one(tiny):
    ff, _, _, _ = tiny
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 95, (2, 32)),
                      jnp.int32)
    keys = [ff.executor._rngs_for_step(jnp.int32(i))["noise"]
            for i in (0, 0, 1)]
    (z0, w0, _), (z0_again, w0_again, _), (z1, w1, _) = (
        noise(ids, True, k) for k in keys)
    assert (z0 == z0_again).all() and (w0 == w0_again).all()
    assert (z0 != z1).any() and (w0 != w1).any()
    # eval mode: the configuration's key, whatever the step's is
    ze, we, _ = noise(ids, False, keys[0])
    assert (ze != z0).any()
    assert (noise(ids, False, keys[2])[0] == ze).all()
    assert (noise(ids, False, None, dict(NOISE, eval_noise_seed=1))[0]
            != ze).any()


def test_the_noising_op_announces_itself():
    ids = jnp.zeros((2, 32), jnp.int32)
    events.enable()
    events.clear()
    try:
        noise(ids)
        noise(ids, True, jax.random.key(0))
        noted = [e["attrs"] for e in events.events()
                 if e["name"] == "diffusion.noise"]
    finally:
        events.disable()
        events.clear()
    assert [a["key"] for a in noted] == ["eval", "step"]
    assert noted[0] == {"layer": "noise", "tokens": 64, "block_length": 4,
                        "blocks": 16, "mask_token_id": 95, "t_min": 1e-3,
                        "key": "eval"}


# ----------------------------------------------------------------------
# the weighted loss
# ----------------------------------------------------------------------
def test_the_weighted_loss_is_the_sum_written_out():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 8, 12)), jnp.float32)
    label = jnp.asarray(rng.integers(0, 12, (2, 8, 1)), jnp.int32)
    w = jnp.asarray(rng.uniform(0, 3, (2, 8)) * (rng.random((2, 8)) < 0.5),
                    jnp.float32)
    kind = "sparse_categorical_crossentropy"
    got = losses.compute_loss(losses.LossType[
        "LOSS_" + kind.upper()], logits, label, logits=True, weights=w)
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    want = sum(-w[b, i] * logp[b, i, int(label[b, i, 0])]
               for b in range(2) for i in range(8)) / 16     # ROWS, not masked
    close(got, want, 1e-6)
    # weights of all ones: the plain mean, which is the loss without them
    plain = losses.compute_loss(losses.LossType["LOSS_" + kind.upper()],
                                logits, label, logits=True)
    ones = losses.compute_loss(losses.LossType["LOSS_" + kind.upper()],
                               logits, label, logits=True,
                               weights=jnp.ones((2, 8)))
    close(ones, plain, 1e-6)
    apart(got, plain)
    with pytest.raises(NotImplementedError, match="takes no weights"):
        losses.compute_loss(losses.LossType.LOSS_IDENTITY, logits, label,
                            weights=w)


def test_the_rolled_rows_meet_the_runners_labels():
    """``sum_i w_out[i] nll(out[i], roll(ids, -1)[i]) = sum_j w[j]
    nll(P[j], ids[j])``, the last row included."""
    rng = np.random.default_rng(1)
    logp = np.log(rng.dirichlet(np.ones(12), (2, 8)))
    ids = rng.integers(0, 12, (2, 8))
    w = rng.uniform(0.1, 3, (2, 8))            # the last row weighs too
    want = -sum(w[b, j] * logp[b, j, ids[b, j]]
                for b in range(2) for j in range(8))
    out, w_out = np.roll(logp, -1, 1), np.roll(w, -1, 1)
    labels = np.roll(ids, -1, 1)
    got = -sum(w_out[b, i] * out[b, i, labels[b, i]]
               for b in range(2) for i in range(8))
    np.testing.assert_allclose(got, want, rtol=1e-12)


# ----------------------------------------------------------------------
# the experts' share
# ----------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, ... of 16, one share a chip, each chip routing
    over all 16: the shares add up to the uncut reference's layer."""
    rng = np.random.default_rng(0)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    full = {"wg": w(32, 16) * 3, "w_gate": w(16, 32, 16),
            "w_up": w(16, 32, 16), "w_down": w(16, 16, 32)}
    x = jnp.asarray(rng.normal(size=(2, 24, 32)), jnp.float32)
    sizes = {"num_experts_per_tok": 4}
    with jax.default_matmul_precision("highest"):
        want = ref.routed(x, full, sizes)
    total = jnp.zeros_like(x)
    for r in range(8):
        held = slice(2 * r, 2 * r + 2)
        mine = dict(full, **{k: full[k][held]
                             for k in ("w_gate", "w_up", "w_down")})
        params = {"num_experts": 16, "top_k": 4, "expert_dim": 16,
                  "shared_dim": 0, "experts_held": 2, "first_held": 2 * r,
                  "scale": 1.0, "bias_std": 0.0, "scoring": "softmax",
                  "choice_bias": False}
        ctx = f32_ctx()
        (y,) = RoutedExpertsOp().emit(params, [x], mine, ctx, "experts")
        with jax.default_matmul_precision("highest"):
            close(y, ref.routed(x, mine, dict(sizes,
                                              first_held_expert=2 * r)))
        assert float(ctx.counters["moe.dropped"]) == 0.0
        total = total + y
    close(total, want)


@pytest.mark.parametrize("first", [0, 4, 12])
def test_repeated_router_columns_send_every_share_the_tokens(first):
    """``router_repeats`` 4 over 16 experts, top-4: the router is drawn
    as 4 columns repeated for each of 4 shares, so every token's top-4
    are one expert (the same one) in every share, and a share of 2 or 4
    experts is sent the tokens' count of rows whatever the input, lumps
    of alike rows included; the plain draw is not."""
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.runtime.initializers import (initialize,
                                                   initialize_host)
    params = {"num_experts": 16, "top_k": 4, "expert_dim": 16,
              "shared_dim": 0, "experts_held": 4, "first_held": first,
              "scale": 1.0, "bias_std": 0.0, "scoring": "softmax",
              "choice_bias": False, "router_repeats": 4}
    op = RoutedExpertsOp()
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 24, 32))
    x[:, 5:17] = x[0, 5]                     # a lump: 24 alike rows
    x = jnp.asarray(x, jnp.float32)
    for seed in (1, 2147486411):
        rows = {}
        for r in (4, 1):
            specs = {w.name: w for w in op.weights(
                dict(params, router_repeats=r), [x.shape],
                [DataType.DT_FLOAT])}
            w = {k: jnp.asarray(initialize_host(v, (seed, 1, first, i),
                                                np.float32))
                 for i, (k, v) in enumerate(specs.items())}
            if r == 4:
                wg = np.asarray(w["wg"])
                assert wg.shape == (32, 16)
                for share in range(1, 4):
                    np.testing.assert_array_equal(
                        wg[:, 4 * share:4 * share + 4], wg[:, :4])
                assert len(np.unique(wg[0, :4])) == 4
                limit = np.sqrt(6.0 / (32 + 16))     # the whole shape's fans
                assert 0.8 * limit < np.abs(wg).max() <= limit
                np.testing.assert_array_equal(np.asarray(initialize(
                    specs["wg"], jax.random.key(seed), jnp.float32))[:, 4:8],
                    np.asarray(initialize(specs["wg"], jax.random.key(seed),
                                          jnp.float32))[:, :4])
            ctx = f32_ctx()
            (y,) = op.emit(dict(params, router_repeats=r), [x], w, ctx,
                           "experts")
            with jax.default_matmul_precision("highest"):
                close(y, ref.routed(x, w, {
                    "num_experts_per_tok": 4,
                    "first_held_expert": params["first_held"]}))
            rows[r] = float(ctx.counters["moe.local_assignments"])
            assert float(ctx.counters["moe.dropped"]) == 0.0
        assert rows[4] == 48.0               # the tokens, at every seed
    assert rows[1] != 48.0


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def unrolled(rows):
    """``P`` from the program's ``out[i] = P[(i + 1) % L]``."""
    return np.roll(np.asarray(rows), 1, axis=1)


def test_the_model_is_the_reference_log_probabilities_and_loss(tiny):
    ff, mc, batch, params = tiny
    loss, bm, probs = program(ff, params, batch, training=False)
    close(jnp.log(probs), rf.reference_call(
        ref.block_diffusion_moe_decoder, ff, mc, params, batch))
    close(loss, rf.reference_loss(ref, ff, mc, params, batch))
    masked, _ = ref.noise(rf.sizes_of(mc), batch["input_ids"])
    count = {k[len(COUNTER_PREFIX):]: float(v) for k, v in bm.items()
             if k.startswith(COUNTER_PREFIX)}
    assert count["diffusion.masked_tokens"] == float(masked.sum())
    assert count["diffusion.tokens"] == B * S
    # three layers, three kernels' worth each; off the kernels all pairs
    assert count["attn.bd_pairs"] == count["attn.bd_visited_pairs"] \
        == 3 * 3 * B * mc.num_attention_heads * (2 * S) ** 2
    assert count["moe.dropped"] == 0.0
    assert count["moe.local_assignments"] \
        == 3 * B * 2 * S * mc.num_experts_per_tok     # all 2 L are routed


@pytest.mark.parametrize("rule", ref.PERTURBATIONS)
def test_a_wrong_rule_is_another_model(tiny, rule):
    """What a program that ran a causal mask on the clean half alone,
    let a noised query see its own block's clean keys, numbered the 2 L
    positions on or ignored the loss's weights would compute."""
    ff, mc, batch, params = tiny
    loss, _, probs = program(ff, params, batch, training=False)
    with ref.perturbed(rule):
        other = rf.reference_call(ref.block_diffusion_moe_decoder, ff, mc,
                                  params, batch)
        other_loss = rf.reference_loss(ref, ff, mc, params, batch)
    apart(loss, other_loss, 1e-3)
    if rule != "unit_weights":          # which moves no log-probability
        apart(jnp.log(probs), other)


def test_the_loss_divides_by_the_tokens_and_not_by_the_masked(tiny):
    ff, mc, batch, params = tiny
    loss, bm, probs = program(ff, params, batch, training=False)
    w = np.roll(np.asarray(ref.weights(rf.sizes_of(mc),
                                       batch["input_ids"])), -1, 1)
    nll = -np.take_along_axis(np.log(np.asarray(probs)),
                              np.asarray(batch["label"]), -1)[..., 0]
    close(loss, (w * nll).sum() / (B * S), 1e-5)
    apart(loss, (w * nll).sum() / (w > 0).sum())


def test_no_answer_leaks_and_no_clean_row_sees_a_noised_key(tiny):
    """Changing the clean token at a MASKED position of block b leaves
    the predictions of blocks <= b as they were and changes those of
    later blocks; changing what the noised half reads (another mask id)
    changes no clean row of the decoder's last state."""
    ff, mc, batch, params = tiny
    masked = np.asarray(ref.noise(rf.sizes_of(mc), batch["input_ids"])[0])
    at = int(np.flatnonzero(masked[0, 8:24])[0]) + 8     # a middle block
    block = at // mc.block_length
    ids = np.asarray(batch["input_ids"]).copy()
    ids[0, at] = (ids[0, at] + 1) % (mc.vocab_size - 1)
    moved = dict(batch, input_ids=jnp.asarray(ids))
    before, after = (unrolled(jnp.log(program(ff, params, b_, False)[2]))
                     for b_ in (batch, moved))
    upto = (block + 1) * mc.block_length
    assert (before[0, :upto] == after[0, :upto]).all()
    assert (before[1] == after[1]).all()            # the other sequence
    assert all((before[0, i] != after[0, i]).any() for i in range(upto, S))

    def states(ff_, batch_):
        (last,) = [l for l in ff_.layers if l.name == "ffn_res_2"]
        fn = jax.jit(lambda p: rf.forward(ff_, p, batch_, False)[4][
            last.outputs[0].guid])
        return np.asarray(fn(rf.on_one_device(params)))
    other, _ = build(model_cfg=dataclasses.replace(mc, mask_token_id=94))
    a, b_ = states(ff, batch), states(other, batch)
    assert (a[:, S:] == b_[:, S:]).all()            # the clean half
    rows = masked.any(axis=1)
    assert (a[rows, :S] != b_[rows, :S]).any()      # the noised half


@pytest.mark.parametrize("remat", ["none", "blocks"])
def test_every_gradient_is_the_references_under_the_steps_mask(tiny, remat):
    """A training step draws from the STEP's key; the reference is handed
    that key. Blocks rematerialised or not, one step."""
    _, mc, batch, params = tiny
    ff, _ = build(remat=remat)
    assert (ff.executor._remat is not None) == (remat == "blocks")
    if remat == "blocks":
        assert ff.executor._remat[1:3] == (6, 3)      # three blocks of six
    (loss, bm), grads = rf.step_and_gradients(ff, params, batch)
    key = ff.executor._rngs_for_step(jnp.int32(0))["noise"]
    sizes = dict(rf.sizes_of(mc), noise_key=key)

    def want(p):
        return ref.loss(rf.named(ff, p), sizes, batch["input_ids"],
                        batch["position_ids"], batch["label"][..., 0])
    want_loss, want_grads = jax.jit(jax.value_and_grad(want))(
        rf.on_one_device(params))
    close(loss, want_loss)
    for name, ws in want_grads.items():
        for k in ws:
            close(grads[name][k], ws[k], floor=1e-5)
    # the eval draw is another: a step that trained on it would show
    apart(loss, rf.reference_loss(ref, ff, mc, params, batch), 1e-3)


def test_the_graph_has_what_the_equations_have(tiny):
    ff, mc, _, _ = tiny
    kinds = [l.op_type.name for l in ff.layers]
    assert kinds[0] == "OP_BLOCK_DIFFUSION_NOISE"
    attn = [l for l in ff.layers
            if l.op_type.name == "OP_MULTIHEAD_ATTENTION"]
    assert len(attn) == 3 and all(
        l.params["block_diffusion_block"] == 4 and not l.params["causal"]
        and l.params["rope"] and l.params["qk_norm"]
        and l.inputs[0].shape == (B, 2 * S, 64)
        and l.inputs[3].shape == (B, S) for l in attn)
    experts = [l for l in ff.layers if l.op_type.name == "OP_ROUTED_EXPERTS"]
    assert all(l.params["scoring"] == "softmax"
               and l.params["choice_bias"] is False
               and l.inputs[0].shape == (B, 2 * S, 64) for l in experts)
    assert "bias" not in ff.params["experts_0"]
    head = [l for l in ff.layers if l.name == "lm_head"][0]
    assert head.inputs[0].shape == (B, S, 64)
    assert ff.executor._loss_weights_tensor.shape == (B, S)


def test_the_new_parts_are_offered_batch_and_heads_not_sequence(tiny):
    """The search's options: the batch for the noising op, the batch and
    the heads for an attention layer under the mask; the plan verifier
    refuses a sequence shard of either by name, and the ring path the
    mask."""
    from flexflow_tpu.analysis.plan_verifier import (
        PlanReport, _check_block_diffusion_sequence)
    from flexflow_tpu.kernels import registry as kreg
    from flexflow_tpu.search import opshard
    ff = tiny[0]
    noise_layer, attn = ff.layers[0], next(
        l for l in ff.layers if l.op_type.name == "OP_MULTIHEAD_ATTENTION")
    assert [(o.kind, o.out_dim) for o in opshard.options_for(noise_layer)] \
        == [("sample", 0)]
    assert [o.kind for o in opshard.options_for(attn)][:2] \
        == ["sample", "parameter"]
    for layer in (noise_layer, attn):
        report = PlanReport()
        _check_block_diffusion_sequence(report, {"data": 2, "seq": 2},
                                        layer, ("data", "seq", None))
        assert [f.severity for f in report.findings] == ["error"]
        assert "block-diffusion step" in report.findings[0].message
        report = PlanReport()
        _check_block_diffusion_sequence(report, {"data": 2, "seq": 2},
                                        layer, ("data", None, None))
        assert not report.findings
    ctx = kreg.attention_ctx(attn.params, 2 * S, 2 * S, seq_degree=2)
    assert "block-diffusion" in kreg.get_impl("attention", "ring") \
        .available(ctx)
    assert "tiles of 128" in kreg.get_impl("attention", "flash") \
        .available(ctx)
    assert kreg.get_impl("attention", "flash").available(
        kreg.attention_ctx(attn.params, 512, 512)) is None


def test_generate_says_it_cannot_decode_such_a_model(tiny):
    ff, _, batch, _ = tiny
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        ff.generate(np.asarray(batch["input_ids"]), 4, 4)


def test_the_reference_refuses_another_architecture(tiny):
    ff, mc, batch, _ = tiny
    sizes = rf.sizes_of(mc)
    fn = ref.block_diffusion_moe_decoder
    rf.refuses(ref, fn, "layer_types", ff,
               dict(sizes, layer_types=["full_attention"] * 3), batch)
    rf.refuses(ref, fn, "expects", ff, dict(
        sizes, num_hidden_layers=2,
        layer_types=["block_diffusion_attention"] * 2), batch)
    rf.refuses(ref, fn, "whole blocks", ff, dict(sizes, block_length=5),
               batch)
