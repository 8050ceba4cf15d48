"""The flash kernels' window (PR 51): ``s <= t and s > t - window`` as
index arithmetic inside the three kernels, held in interpret mode to a
plain masked softmax: outputs, ``dq``, ``dk``, ``dv``, with dropout, with
grouped K/V, at windows of 1, a tile, a tile and a half, no multiple of
the forward's piece and at least the sequence; the grid's count against
a loop over the tiles; ``window=0`` traces to the call without one."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import flash_attention
from flexflow_tpu.kernels.flash_attention import (
    NEG_INF, dropout_keep_mask, grid_steps)
from flexflow_tpu.obs import events

S, BLOCK = 256, 128
#: 1; a tile; a tile and a half; no multiple of the piece; the sequence
WINDOWS = (1, 128, 192, 100, 256, 300)


def _qkv(b=1, h=2, s=S, d=32, seed=0, kvh=None):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, h, s, d), dtype=np.float32)
    k = rng.standard_normal((b, kvh or h, s, d), dtype=np.float32)
    v = rng.standard_normal((b, kvh or h, s, d), dtype=np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def band(s, window):
    t = np.arange(s)[:, None]
    k = np.arange(s)[None, :]
    return (k <= t) & (k > t - window)


def _plain(q, k, v, window, keep=None, rate=0.0):
    """Masked softmax in XLA, float32 at highest precision."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(band(q.shape[2], window), s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _flash(q, k, v, window, **kw):
    return flash_attention(q, k, v, causal=True, window=window,
                           interpret=True, **kw)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
def test_windowed_kernels_match_a_plain_banded_softmax(window, what):
    q, k, v = _qkv()
    ct = jnp.asarray(np.random.default_rng(9).standard_normal(
        q.shape, dtype=np.float32))
    blocks = dict(block_q=BLOCK, block_k=BLOCK)
    if what == "o":
        got, want = _flash(q, k, v, window, **blocks), _plain(q, k, v, window)
    else:
        i = "dq dk dv".split().index(what)
        got = jax.grad(lambda *a: jnp.sum(_flash(*a, window, **blocks) * ct),
                       argnums=i)(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(_plain(*a, window) * ct),
                        argnums=i)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [300, S])
def test_a_window_of_at_least_the_sequence_is_the_causal_call(window):
    q, k, v = _qkv(seed=1)
    f = lambda w: jax.make_jaxpr(jax.grad(                    # noqa: E731
        lambda *a: jnp.sum(flash_attention(*a, causal=True, window=w,
                                           interpret=True)),
        argnums=(0, 1, 2)))(q, k, v)
    assert str(f(window)) == str(f(0))


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (256, 128)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("window", [1, 100, 192])
def test_tiles_unlike_the_forwards_draw_the_same_band(blocks, window):
    """The backward kernels at their own tiles (dq queries-major, dkv
    keys-major) and the forward's pieces skip both edges alike."""
    q, k, v = _qkv(h=1, seed=2)
    kw = dict(block_q=128, block_k=128, bwd_block_q=blocks[0],
              bwd_block_k=blocks[1])
    got = jax.grad(lambda *a: jnp.sum(_flash(*a, window, **kw) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain(*a, window) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [100, 192])
def test_a_wide_k_block_walked_in_pieces_skips_the_dead_ones(window):
    """1,024 keys in one block, pieces of 512: the piece left of the band
    is dead for the later q blocks, the piece right of it for the first."""
    q, k, v = _qkv(h=1, s=1024, seed=3)
    got = _flash(q, k, v, window, block_q=256, block_k=1024)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, k, v, window)),
                               rtol=2e-4, atol=2e-5)
    steps = grid_steps("fwd", 1, 1024, 1024, 256, 1024, True, window)
    assert steps["piece_k"] == 512 and steps["live_pieces"] == 5 \
        and grid_steps("fwd", 1, 1024, 1024, 256, 1024, True)[
            "live_pieces"] == 6


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
def test_windowed_kernels_with_dropout(what):
    rate, seed, window = 0.25, 11, 100
    q, k, v = _qkv(seed=4)
    keep = dropout_keep_mask(q.shape[0], q.shape[1], S, S, rate, seed)
    kw = dict(dropout_rate=rate, dropout_seed=jnp.int32(seed),
              block_q=BLOCK, block_k=BLOCK)
    if what == "o":
        got = _flash(q, k, v, window, **kw)
        want = _plain(q, k, v, window, keep, rate)
    else:
        i = "dq dk dv".split().index(what)
        got = jax.grad(lambda *a: jnp.sum(_flash(*a, window, **kw) ** 2),
                       argnums=i)(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(
            _plain(*a, window, keep, rate) ** 2), argnums=i)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [1, 192])
def test_windowed_kernels_with_grouped_keys_and_values(window):
    """4 query heads on 2 k/v heads, repeated to the query heads as the
    attention op hands them to the kernels; the gradients of the k/v
    heads sum their group's."""
    q, k, v = _qkv(h=4, kvh=2, seed=5)
    rep = lambda x: jnp.repeat(x, 2, axis=1)                  # noqa: E731
    got = jax.grad(lambda q, k, v: jnp.sum(
        _flash(q, rep(k), rep(v), window) ** 2), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _plain(q, rep(k), rep(v), window) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [1, 100, 192])
@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("kvh", [1, 2])
def test_windowed_kernels_read_grouped_keys_and_values_in_place(window, what,
                                                                kvh):
    """4 query heads on 1 or 2 k/v heads handed as they are (PR 52): a
    query head names its group's row, ``bwd_dkv`` walks the k/v heads
    and a dead step of the band names its own member's nearest live
    block; against the plain banded softmax on the heads repeated."""
    q, k, v = _qkv(h=4, kvh=kvh, seed=6)
    ct = jnp.asarray(np.random.default_rng(8).standard_normal(
        q.shape, dtype=np.float32))
    rep = lambda x: jnp.repeat(x, 4 // kvh, axis=1)           # noqa: E731
    blocks = dict(block_q=BLOCK, block_k=BLOCK, bwd_block_q=64)
    flash = lambda q, k, v: _flash(q, k, v, window, **blocks)  # noqa: E731
    plain = lambda q, k, v: _plain(q, rep(k), rep(v), window)  # noqa: E731
    if what == "o":
        got, want = flash(q, k, v), plain(q, k, v)
    else:
        i = "dq dk dv".split().index(what)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=i)(
            q, k, v) for f in (flash, plain))
        assert got.shape == (q, k, v)[i].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def _by_hand(kernel, s, bq, bk, window, piece=None):
    """Tiles (or forward pieces) with at least one pair in the band, and
    the blocks a row of the grid names, from the band itself."""
    m = band(s, window) if window else np.tril(np.ones((s, s), bool))
    nq, nk = s // bq, s // bk
    live = np.array([[m[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
                      for j in range(nk)] for i in range(nq)])
    if kernel == "bwd_dkv":
        live = live.T
    pieces = None
    if piece:
        pieces = sum(m[i * bq:(i + 1) * bq, p * piece:(p + 1) * piece].any()
                     for i in range(nq) for p in range(s // piece))
    return int(live.sum()), pieces


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
@pytest.mark.parametrize("s,bq,bk,window", [
    (1024, 128, 128, 1), (1024, 128, 256, 128), (1024, 256, 128, 192),
    (1024, 256, 256, 100), (2048, 512, 1024, 700), (1024, 128, 128, 0),
    (8192, 1024, 1024, 2048), (8192, 1024, 4096, 2048)],
    ids=lambda v: str(v))
def test_grid_steps_under_a_window_against_a_count_by_hand(kernel, s, bq, bk,
                                                           window):
    bh = 3
    got = grid_steps(kernel, bh, s, s, bq, bk, True, window)
    piece = got.get("piece_k")
    live, pieces = _by_hand(kernel, s, bq, bk, window, piece)
    assert got["live_steps"] == bh * live
    # contiguous live steps a row: one fetch each, none for a dead step
    assert got["fetched_steps"] == got["live_steps"]
    assert got["steps"] == bh * (s // bq) * (s // bk)
    assert got.get("window", 0) == window
    if kernel == "fwd":
        assert got["live_pieces"] == bh * pieces
    if window and window < s:
        causal = grid_steps(kernel, bh, s, s, bq, bk, True)
        assert got["live_steps"] <= causal["live_steps"]


def test_the_band_at_the_cells_shape_is_under_the_causal_count():
    """8,192 positions, a window of 2,048: 43.75% of the causal pairs,
    and by the tile 0.44 to 0.6 of the causal tiles live."""
    s, w = 8192, 2048
    assert w * s - w * (w - 1) // 2 == 14_681_088
    assert s * (s + 1) // 2 == 33_558_528
    for kernel, bq, bk in [("fwd", 1024, 4096), ("bwd_dq", 1024, 1024),
                           ("bwd_dkv", 1024, 1024), ("bwd_dq", 512, 512)]:
        got = grid_steps(kernel, 32, s, s, bq, bk, True, w)
        causal = grid_steps(kernel, 32, s, s, bq, bk, True)
        key = "live_pieces" if kernel == "fwd" else "live_steps"
        assert 0.44 <= got[key] / causal[key] <= 0.6, (kernel, got, causal)


def test_index_maps_name_the_bands_nearest_block():
    """A dead step's index map names the block of its row's nearest live
    step: left of the band the first, above the diagonal the last."""
    import importlib
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    s, bq, bk, w = 1024, 128, 256, 192
    m = band(s, w)
    nq, nk = s // bq, s // bk
    ik = fa._live_k(bq, bk, True, w)
    iq = fa._dkv_live_q(bq, bk, True, w)
    for i, j in itertools.product(range(nq), range(nk)):
        live = [jj for jj in range(nk)
                if m[i * bq:(i + 1) * bq, jj * bk:(jj + 1) * bk].any()]
        assert int(ik(i, j)) == min(max(j, live[0]), live[-1])
        live_q = [ii for ii in range(nq)
                  if m[ii * bq:(ii + 1) * bq, j * bk:(j + 1) * bk].any()]
        assert int(iq(j, i)) == min(max(i, live_q[0]), live_q[-1])


def test_a_windowed_call_says_so_in_its_grid_instants():
    q, k, v = _qkv(h=1)
    events.enable()
    events.clear()
    try:
        jax.grad(lambda *a: jnp.sum(_flash(*a, 100)), argnums=(0, 1, 2))(
            q, k, v)
        jax.grad(lambda *a: jnp.sum(_flash(*a, 0)), argnums=(0, 1, 2))(
            q, k, v)
        grids = [e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"]
    finally:
        events.clear()
        events.disable()
    assert [g.get("window") for g in grids] == [100] * 3 + [None] * 3
    assert all("masked" not in g for g in grids)


def test_a_window_wants_causal_and_is_never_a_mask_operand():
    q, k, v = _qkv(h=1)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8, interpret=True)
    with pytest.raises(NotImplementedError, match="window"):
        flash_attention(q, k, v, causal=True, window=8, interpret=True,
                        mask=jnp.ones((1, S, S), jnp.int8))
    jaxpr = str(jax.make_jaxpr(lambda *a: _flash(*a, 100))(q, k, v))
    assert "i8[" not in jaxpr          # no mask operand anywhere
