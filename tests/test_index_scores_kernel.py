"""The index-score kernels (``kernels/index_scores.py``) in interpret
mode against the plain ``ops/sparse_attention.py::index_scores`` and its
``jax.vjp``, which stay the path of every shape the kernels do not take;
then the indexer's loss, whose backward runs them, against the same loss
with the predicate stubbed to no."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import index_scores as isk
from flexflow_tpu.ops import sparse_attention as dsa


def l2(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def operands(rows, keys, j, c, seed=0, causal=False):
    ks = jax.random.split(jax.random.key(seed), 4)
    qi = jax.random.normal(ks[0], (2, rows, j, c), jnp.float32)
    ki = jax.random.normal(ks[1], (2, keys, c), jnp.float32)
    wi = jax.random.normal(ks[2], (2, rows, j), jnp.float32)
    d = jax.random.normal(ks[3], (2, rows, keys), jnp.float32)
    if causal:
        d = jnp.where(jnp.tril(jnp.ones((rows, keys), bool)), d, 0.0)
    assert float(wi.min()) < 0 < float(wi.max())        # both signs
    return qi, ki, wi, d


def plain(qi, ki, wi, d, mdt):
    @jax.jit
    def scores_and_pulled(qi, ki, wi, d):
        scores, pull = jax.vjp(lambda *a: dsa.index_scores(*a, mdt), qi, ki,
                               wi)
        return scores, pull(d)
    return scores_and_pulled(qi, ki, wi, d)


# (query rows, keys): one tile; three key tiles; a chunk whose keys end
# inside a tile
SHAPES = [(512, 512), (512, 1536), (512, 1100)]
TOLERANCE = {jnp.float32: 2e-6, jnp.bfloat16: 8e-3}


@pytest.mark.parametrize("mdt", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows,keys", SHAPES)
def test_the_forward_kernel_is_index_scores(rows, keys, mdt):
    """The same operands rounded to ``mdt`` give the same float32
    products; only the order of the heads' sum differs."""
    qi, ki, wi, d = operands(rows, keys, 4, 64)
    want = dsa.index_scores(qi, ki, wi, mdt)
    got = isk.index_scores_fwd(qi, ki, wi, mdt)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert l2(got, want) <= 2e-6


@pytest.mark.parametrize("mdt", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("rows,keys", SHAPES)
def test_the_backward_kernel_is_the_vjp_of_index_scores(rows, keys, mdt):
    """``dqi``, ``dki`` and ``dwi`` in their operands' shapes and types.
    With bf16 operands both sides round what a product multiplies (the
    plain path ``d * w * [raw > 0]``, the kernel ``d * [raw > 0]`` and
    ``w * qi``): the distance is that rounding's."""
    qi, ki, wi, d = operands(rows, keys, 4, 64, seed=1)
    _, want = plain(qi, ki, wi, d, mdt)
    got = isk.index_scores_bwd(qi, ki, wi, d, mdt)
    for g, w, x in zip(got, want, (qi, ki, wi)):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert l2(g, w) <= TOLERANCE[mdt]


@pytest.mark.parametrize("j,c", [(16, 64), (2, 128), (8, 32), (1, 256)])
def test_heads_of_any_whole_lane_width(j, c):
    """Cell 7's 16 heads of 64 (two a vreg), heads of 128 and 256 (one a
    group) and of 32 (four)."""
    qi, ki, wi, d = operands(256, 384, j, c, seed=2)
    scores, pulled = plain(qi, ki, wi, d, jnp.float32)
    assert l2(isk.index_scores_fwd(qi, ki, wi, jnp.float32), scores) <= 2e-6
    for g, w in zip(isk.index_scores_bwd(qi, ki, wi, d, jnp.float32),
                    pulled):
        assert l2(g, w) <= 2e-6


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128)])
def test_the_tiles_past_the_diagonal_are_not_visited(block_q, block_k):
    """``causal``: the forward writes the plain scores on every tile
    with a pair at or under the diagonal and nothing on the others; the
    backward reads no cotangent past the diagonal (garbage there counts
    as 0) and is the plain pull-back of the cotangent under it, its
    ``dki`` summed over the query tiles."""
    qi, ki, wi, d = operands(512, 512, 4, 64, seed=3, causal=True)
    scores, pulled = plain(qi, ki, wi, d, jnp.float32)
    got = isk.index_scores_fwd(qi, ki, wi, jnp.float32, causal=True,
                               block_q=block_q, block_k=block_k)
    t = np.arange(512)
    visited = (t[None, :] // block_k) * block_k \
        <= (t[:, None] // block_q + 1) * block_q - 1
    assert l2(np.where(visited, got, 0), np.where(visited, scores, 0)) <= 2e-6
    garbage = jnp.where(t[None, :] <= t[:, None], d, jnp.nan)
    for g, w in zip(isk.index_scores_bwd(
            qi, ki, wi, garbage, jnp.float32, causal=True, block_q=block_q,
            block_k=block_k), pulled):
        assert l2(g, w) <= 2e-6


def test_relus_derivative_is_0_at_0():
    """Small integers and a scale of 1/16: every product and sum is
    exact, and a third of the raw scores are exactly 0. There
    ``jax.nn.relu`` passes nothing back, and neither does the kernel:
    the gradients are EQUAL."""
    rng = np.random.default_rng(4)
    qi = jnp.asarray(rng.integers(-1, 2, (1, 256, 4, 64)), jnp.float32)
    ki = jnp.asarray(rng.integers(-1, 2, (1, 256, 64)), jnp.float32)
    ki = ki.at[:, ::3].set(0.0)
    wi = jnp.asarray(rng.integers(-2, 3, (1, 256, 4)), jnp.float32)
    d = jnp.asarray(rng.integers(-2, 3, (1, 256, 256)), jnp.float32)
    raw = jnp.einsum("bqjc,bkc->bjqk", qi, ki)
    assert float(jnp.mean(raw == 0)) > 0.3
    scores, pulled = plain(qi, ki, wi, d, jnp.float32)
    np.testing.assert_array_equal(
        isk.index_scores_fwd(qi, ki, wi, jnp.float32), scores)
    for g, w in zip(isk.index_scores_bwd(qi, ki, wi, d, jnp.float32),
                    pulled):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("q_rows,keys,j,c,mdt,want", [
    (8192, 8192, 16, 64, jnp.bfloat16, True),     # cell 7's sequence
    (4096, 4096, 16, 64, jnp.bfloat16, True),     # the chip validation's
    (512, 8192, 16, 64, jnp.bfloat16, True),      # a chunk of it
    (512, 8192, 16, 64, jnp.float32, True),
    (512, 8192, 16, 48, jnp.bfloat16, False),     # heads across vregs
    (512, 8192, 3, 64, jnp.bfloat16, False),      # half a group
    (512, 8192, 16, 64, jnp.float16, False),
    (16, 32, 2, 8, jnp.float32, False),           # ``KeyeRankConfig.tiny``
    (512, 8200, 16, 64, jnp.bfloat16, False),     # no tile divides the keys
    (500, 8192, 16, 64, jnp.bfloat16, False)])
def test_the_shapes_tell_which_path_runs(q_rows, keys, j, c, mdt, want):
    assert isk.takes_kernel(q_rows, keys, j, c, mdt) is want


def test_cell_7s_tiles():
    """512 x 512, forward and backward, over the whole sequence and over
    any chunk of it."""
    for rows, keys in [(8192, 8192), (4096, 4096)] + [
            (512, keys) for keys in range(512, 8192 + 1, 512)]:
        for kernel in ("fwd", "bwd"):
            assert isk.tiles(kernel, rows, keys, 16, 64, jnp.bfloat16) \
                == (512, 512)
    assert isk.tiles("bwd", 512, 640, 16, 64, jnp.bfloat16) == (512, 128)


def test_a_shape_the_kernels_do_not_take_is_refused():
    qi, ki, wi, d = operands(128, 128, 3, 48)
    with pytest.raises(ValueError, match="take no 3 heads of 48"):
        isk.index_scores_fwd(qi, ki, wi, jnp.float32)


# ----------------------------------------------------------------------
# the loss whose backward runs them
# ----------------------------------------------------------------------
def loss_and_grads(monkeypatch, kernels, seq, q_chunk, topk, j, c, mdt):
    ks = jax.random.split(jax.random.key(5), 6)
    q = jax.random.normal(ks[0], (1, seq, 2, 64))
    k, v = (jax.random.normal(ks[i], (1, seq, 1, 64)) for i in (1, 2))
    qi = jax.random.normal(ks[3], (1, seq, j, c))
    ki = jax.random.normal(ks[4], (1, seq, c))
    wi = jax.random.normal(ks[5], (1, seq, j))
    if not kernels:
        monkeypatch.setattr(isk, "takes_kernel", lambda *a: False)
    assert dsa._index_kernels(qi, mdt) is kernels
    # a function of its own each time: ``jax.jit`` keeps traces by function
    return jax.jit(jax.value_and_grad(
        lambda qi, ki, wi: dsa.sparse_index_attention_flash(
            q, k, v, qi, ki, wi, topk, q_chunk, mdt)[1], (0, 1, 2)))(
                qi, ki, wi)


@pytest.mark.parametrize("seq,q_chunk,topk,j,c", [
    (256, 128, 64, 2, 64),          # one tile; the second chunk selects
    (384, 128, 512, 4, 32),         # 3 x 3 tiles, every causal key selected
    (1024, 256, 96, 1, 128)])       # 2 x 2 tiles of 512, one past the diagonal
def test_the_losses_gradient_on_the_kernel_path_is_the_plain_paths(
        monkeypatch, seq, q_chunk, topk, j, c):
    """``L_I`` and its gradient for the indexer's ``qi``, ``ki`` and
    ``wi`` through ``sparse_index_attention_flash``: the value is made
    by the same code on both paths, the gradient by the kernels on one
    (the whole sequence in one causal call each, the loss's passes
    between them a chunk at a time) and by ``jax.vjp`` of
    ``index_scores`` a chunk at a time on the other."""
    got = loss_and_grads(monkeypatch, True, seq, q_chunk, topk, j, c,
                         jnp.float32)
    want = loss_and_grads(monkeypatch, False, seq, q_chunk, topk, j, c,
                          jnp.float32)
    assert float(got[0]) == float(want[0]) > 0
    for g, w in zip(got[1], want[1]):
        assert l2(g, w) <= 5e-6


def test_a_width_that_is_not_whole_lanes_stays_on_the_plain_path(
        monkeypatch):
    """The tests' tiny indexer (2 heads of 8): no kernel is called."""
    def refuse(*a, **kw):
        raise AssertionError("the kernels were called")
    monkeypatch.setattr(isk, "index_scores_fwd", refuse)
    monkeypatch.setattr(isk, "index_scores_bwd", refuse)
    value, grads = loss_and_grads(monkeypatch, False, 64, 16, 24, 2, 8,
                                  jnp.float32)
    assert np.isfinite(float(value)) and all(
        np.isfinite(np.asarray(g)).all() for g in grads)


def test_the_path_taken_is_on_the_record():
    """One ``dsa.index_kernel`` instant a traced layer, with the tiles
    where the kernels run."""
    from flexflow_tpu.obs import events
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = v = jax.random.normal(ks[1], (1, 256, 1, 64))
    events.enable()
    events.clear()
    try:
        for j, c in ((2, 64), (2, 8)):
            qi = jax.random.normal(ks[2], (1, 256, j, c))
            jax.eval_shape(lambda qi: dsa.sparse_index_attention_flash(
                q, k, v, qi, qi[:, :, 0], qi[..., 0], 64, 128, jnp.float32,
                layer=f"attn_{c}"), qi)
        noted = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
                 if e["name"] == "dsa.index_kernel"}
    finally:
        events.disable()
        events.clear()
    assert noted["attn_64"]["impl"] == "kernel" \
        and tuple(noted["attn_64"]["bwd_tile"]) == (256, 256) \
        and noted["attn_64"]["chunks"] == 2
    assert noted["attn_8"]["impl"] == "plain" \
        and noted["attn_8"]["q_chunk"] == 128 \
        and "bwd_tile" not in noted["attn_8"]
