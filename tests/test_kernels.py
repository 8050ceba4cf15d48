"""Numerics tests for the Pallas kernels (interpret mode on CPU) and the
sequence-parallel attention schemes (shard_map over virtual devices)."""
import functools
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from flexflow_tpu.kernels import (flash_attention, mha_reference,
                                  ring_attention)
from flexflow_tpu.obs import events
from jax import shard_map

# (the package binds the name ``flash_attention`` to the function)
fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")


def _rand_qkv(b=2, h=4, s=256, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_forward_unpadded_shapes():
    # seq not a block multiple, head_dim < 128
    q, k, v = _rand_qkv(b=1, h=2, s=200, d=48)
    out = flash_attention(q, k, v, interpret=True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    q, k, v = _rand_qkv(b=1, h=2, s=128, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


# ---------------------------------------------------------------------------
def _seq_mesh():
    devs = np.asarray(jax.devices()[:4])
    return Mesh(devs, ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = _seq_mesh()
    q, k, v = _rand_qkv(b=1, h=2, s=128, d=32)

    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(fn)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients(causal):
    mesh = _seq_mesh()
    q, k, v = _rand_qkv(b=1, h=2, s=64, d=16)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


# ---------------------------------------------------------------------------
# kernel numerics satellites: ragged lengths, GQA head layouts,
# ring at both supported seq degrees
# ---------------------------------------------------------------------------
def test_flash_ragged_cross_lengths_match_reference():
    """Ragged q/kv lengths (cross-attention), neither a block multiple:
    the kv_len mask must keep padded keys out of the softmax."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, 4, 96, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 4, 200, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 4, 200, 64)), jnp.float32)
    out = flash_attention(q, k, v, interpret=True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _gqa_qkv(b=1, h=8, kvh=2, s=128, d=32, seed=3):
    """GQA layout the op layer feeds the kernels: kv projected at kvh
    heads, repeated up to h query heads (ops/nn_ops.py _repeat_kv)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    rep = h // kvh
    return q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_head_layout_matches_reference(causal):
    q, k, v = _gqa_qkv()

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v, causal=causal)),
        atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("degree", [2, 4])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_seq_degrees(degree, causal):
    """Ring attention at both supported seq degrees, fwd + grad."""
    mesh = Mesh(np.asarray(jax.devices()[:degree]), ("sp",))
    q, k, v = _rand_qkv(b=1, h=2, s=32 * degree, d=16, seed=degree)

    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(ring)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


def test_ring_attention_gqa_head_layout():
    mesh = _seq_mesh()
    q, k, v = _gqa_qkv(h=4, kvh=2, s=128, d=16, seed=9)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None))
    out = jax.jit(ring)(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
def test_mha_op_flash_path_matches_xla_path():
    """The MultiHeadAttention op emits the Pallas flash kernel when
    ``attention:flash`` is forced; numerics must match the XLA path."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

    def build(impl):
        cfg = FFConfig()
        cfg.only_data_parallel = True
        cfg.kernel_impls = f"attention:{impl}"
        ff = FFModel(cfg)
        q = ff.create_tensor((2, 64, 64), name="q")
        ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
        ff.compile(SGDOptimizer(0.01), "identity", [])
        return ff

    batch = {"q": np.random.default_rng(1).normal(size=(2, 64, 64))
             .astype(np.float32)}
    ff_flash = build("flash")
    ff_xla = build("xla")
    # identical init (same seed)
    y_flash = ff_flash.executor.make_forward()(ff_flash.params,
                                               ff_flash.state, batch)
    y_xla = ff_xla.executor.make_forward()(ff_xla.params, ff_xla.state,
                                           batch)
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_xla),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# in-kernel counter-based dropout (interpret mode; the compiled path is
# covered on hardware by examples/tpu_validate_kernels.py)
# ---------------------------------------------------------------------------
def test_flash_dropout_deterministic_and_seed_varying():
    q, k, v = _rand_qkv(s=128)
    kw = dict(dropout_rate=0.2, interpret=True,
              block_q=64, block_k=64, bwd_block_q=64, bwd_block_k=64)
    o1 = flash_attention(q, k, v, dropout_seed=7, **kw)
    o2 = flash_attention(q, k, v, dropout_seed=7, **kw)
    o3 = flash_attention(q, k, v, dropout_seed=8, **kw)
    assert jnp.array_equal(o1, o2)
    assert not jnp.array_equal(o1, o3)


def test_flash_dropout_mask_independent_of_blocking():
    """Regression: the r4 on-chip run found the per-TILE-seeded mask was
    unreproducible by the differently-blocked backward kernel (silently
    corrupt dq). The counter-based mask must be identical under any
    block decomposition."""
    q, k, v = _rand_qkv(s=128)
    kw = dict(dropout_rate=0.3, dropout_seed=11, interpret=True)
    o_small = flash_attention(q, k, v, block_q=32, block_k=32, **kw)
    o_big = flash_attention(q, k, v, block_q=128, block_k=128, **kw)
    np.testing.assert_allclose(np.asarray(o_small), np.asarray(o_big),
                               rtol=1e-5, atol=1e-5)


def test_flash_dropout_keep_rate():
    rate = 0.25
    q, k, _ = _rand_qkv(s=128)
    ones_v = jnp.ones((2, 4, 128, 64), jnp.float32)
    # with all-ones v each output row is sum(keep*p/(1-r))/sum(p);
    # its expectation over the mask is exactly 1
    od = flash_attention(q, k, ones_v, dropout_rate=rate, dropout_seed=3,
                         interpret=True, block_q=64, block_k=64)
    assert abs(float(jnp.mean(od)) - 1.0) < 0.05


def test_flash_dropout_grads_match_finite_difference():
    """The custom VJP under dropout>0 against a directional finite
    difference of the kernel itself (mask is regenerated identically on
    both sides of the difference)."""
    q, k, v = _rand_qkv(s=64)
    rng = np.random.default_rng(5)
    probe = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    # bwd blocking deliberately differs from fwd blocking — the r4
    # regression only corrupted grads when the two disagreed
    kw = dict(dropout_rate=0.2, dropout_seed=11, interpret=True,
              block_q=64, block_k=64, bwd_block_q=32, bwd_block_k=32)

    def f(qv):
        return jnp.sum(flash_attention(qv, k, v, **kw) * probe)

    g = jax.grad(f)(q)
    u = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    u = u / jnp.linalg.norm(u.reshape(-1))
    eps = 1e-2
    fd = (f(q + eps * u) - f(q - eps * u)) / (2 * eps)
    an = jnp.sum(g * u)
    assert abs(float(fd - an)) / (abs(float(fd)) + 1e-6) < 2e-2


def test_dropout_keep_mask_matches_kernel():
    """The plain-XLA dropout_keep_mask must reproduce the in-kernel mask
    bit-for-bit: flash output == explicit-masked golden (same hash of
    the same absolute coordinates)."""
    from flexflow_tpu.kernels import dropout_keep_mask
    import math
    b, h, s, d = 2, 4, 128, 64
    rate, seed = 0.2, 11
    q, k, v = _rand_qkv(b, h, s, d)
    o = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=seed,
                        interpret=True, block_q=64, block_k=64)
    sc = 1.0 / math.sqrt(d)
    p = jax.nn.softmax(
        jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sc, -1)
    keep = dropout_keep_mask(b, h, s, s, rate, seed)
    golden = jnp.einsum("bhqk,bhkd->bhqd",
                        jnp.where(keep, p / (1 - rate), 0.0), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(golden),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# backward tiles derived from the shapes, and no fetch for a dead causal
# block (the compiled kernels: tests/test_tpu_aot_compile.py, chip_smoke.py)
# ---------------------------------------------------------------------------
def _grids_of(fn, *args):
    """The ``flash.grid`` instants that tracing ``fn(*args)`` records."""
    was_on = events.enabled()
    events.enable()
    events.clear()
    try:
        jax.eval_shape(fn, *args)
        return {e["attrs"]["kernel"]: e["attrs"] for e in events.events()
                if e["name"] == "flash.grid"}
    finally:
        events.clear()
        if not was_on:
            events.disable()


def _qkv(sq, sk, d, h=1, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, h, s, d)), jnp.float32)
                 for s in (sq, sk, sk))


DERIVED_CASES = {
    # cell 2's shapes: one forward step a head, in two pieces
    "s1024_d64_causal": dict(sq=1024, sk=1024, d=64, causal=True),
    "s512": dict(sq=512, sk=512, d=64, causal=False),
    "s380_padded_causal": dict(sq=380, sk=380, d=64, causal=True),
    "ragged_256_640": dict(sq=256, sk=640, d=64, causal=False),
    "d128_causal": dict(sq=256, sk=256, d=128, causal=True),
    "short_100": dict(sq=100, sk=100, d=32, causal=True),
}


@pytest.mark.parametrize("case", sorted(DERIVED_CASES))
def test_flash_gradients_at_derived_tiles(case):
    c = DERIVED_CASES[case]
    q, k, v = _qkv(c["sq"], c["sk"], c["d"])

    def loss(f, **kw):
        return lambda q, k, v: jnp.sum(f(q, k, v, causal=c["causal"],
                                         **kw) ** 2)

    grad_flash = jax.grad(loss(flash_attention, interpret=True),
                          argnums=(0, 1, 2))
    assert sorted(_grids_of(grad_flash, q, k, v)) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]
    g = grad_flash(q, k, v)
    g_ref = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_gradients_at_derived_tiles(causal):
    q, k, v = _gqa_qkv(s=256)
    loss = lambda f, **kw: (lambda q, k, v: jnp.sum(  # noqa: E731
        f(q, k, v, causal=causal, **kw) ** 2))
    g = jax.grad(loss(flash_attention, interpret=True, block_q=128,
                      block_k=128), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_gradients_with_bwd_tiles_unlike_fwd(causal):
    """Dropout 0.1, forward at 128-wide blocks, backward at the derived
    ones (256): the position hash has to give both the same mask. Golden:
    the explicit mask in plain XLA."""
    b, h, s, d, rate, seed = 1, 2, 256, 64, 0.1, 5
    q, k, v = _rand_qkv(b, h, s, d)
    kw = dict(causal=causal, dropout_rate=rate, dropout_seed=seed,
              interpret=True, block_q=128, block_k=128)
    grids = _grids_of(jax.grad(lambda *x: jnp.sum(
        flash_attention(*x, **kw))), q, k, v)
    assert (grids["flash_attention_fwd"]["block_q"],
            grids["flash_attention_bwd_dq"]["block_q"],
            grids["flash_attention_bwd_dkv"]["block_k"]) == (128, 256, 256)

    def golden(q, k, v):
        return _fwd_golden(q, k, v, causal, rate, seed)

    g = jax.grad(lambda *x: jnp.sum(flash_attention(*x, **kw) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *x: jnp.sum(golden(*x) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-3, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("s,block", [(1024, 512), (512, 128), (384, 128)])
def test_flash_forward_bit_identical_without_fetch_elision(s, block,
                                                           monkeypatch):
    """The index maps decide which block a dead step names, never what a
    live step computes: output and log-sum-exp to the last bit."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((2, s, 64)), jnp.float32)
               for _ in range(3))
    seed = jnp.zeros((1, 1), jnp.int32)
    args = (q, k, v, seed, s, 0.125, True, block, block, 0.0, True)
    o, lse = fa._fwd_call(*args)
    plain = fa._k_spec
    monkeypatch.setattr(
        fa, "_k_spec", lambda bq, bk, d, causal: plain(bq, bk, d, False))
    o0, lse0 = fa._fwd_call(*args)
    assert jnp.array_equal(o, o0) and jnp.array_equal(lse, lse0)


TILE_RULE_CASES = [(sq, sk, d, dt, drop)
                   for sq, sk in ((1024, 1024), (512, 512), (384, 384),
                                  (256, 1024), (2048, 2048), (4096, 4096),
                                  (1536, 1536), (104, 128), (400, 512))
                   for d in (64, 128, 256)
                   for dt in ("bfloat16", "float32")
                   for drop in (False, True)]


@pytest.mark.parametrize("sq,sk,d,dtype,dropout", TILE_RULE_CASES)
def test_bwd_tile_rule(sq, sk, d, dtype, dropout):
    tiles = fa.bwd_tiles(sq, sk, d, jnp.dtype(dtype), dropout)
    assert len(tiles) == 2
    for kernel, (bq, bk) in zip(("bwd_dq", "bwd_dkv"), tiles):
        assert sq % bq == 0 and sk % bk == 0
        assert bq % 128 == 0 or bq == sq
        assert bk % 128 == 0 or bk == sk
        assert max(bq, bk) <= max(fa.MAX_BWD_TILE, min(sq, sk))
        assert fa._bwd_vmem_bytes(
            kernel, bq, bk, d, jnp.dtype(dtype).itemsize,
            dropout) <= fa.BWD_VMEM_BUDGET
        # the largest that fits: no admissible tile holds more pairs
        for t in itertools.product(fa._tile_sizes(sq), fa._tile_sizes(sk)):
            if t[0] * t[1] > bq * bk:
                assert fa._bwd_vmem_bytes(
                    kernel, *t, d, jnp.dtype(dtype).itemsize,
                    dropout) > fa.BWD_VMEM_BUDGET


def test_bwd_tile_rule_at_the_shapes_timed_on_the_chip():
    """PERF.md section 6, PR 28: the tile the rule picks is the fastest
    or within 5% of it at each (padded s, d) of the table, bf16."""
    big = ((1024, 1024), (1024, 1024))
    assert fa.bwd_tiles(1024, 1024, 64, jnp.bfloat16, False) == big
    assert fa.bwd_tiles(2048, 2048, 64, jnp.bfloat16, False) == big
    assert fa.bwd_tiles(1024, 1024, 128, jnp.bfloat16, False) == big
    assert fa.bwd_tiles(512, 512, 64, jnp.bfloat16, False) == (
        (512, 512), (512, 512))
    # equal pairs: dq takes the taller tile, dkv the wider
    assert fa.bwd_tiles(2048, 2048, 256, jnp.bfloat16, True) == (
        (1024, 512), (512, 1024))


@pytest.mark.parametrize("explicit,want", [
    (dict(), None),
    (dict(bwd_block_q=128, bwd_block_k=128), (128, 128)),
    (dict(bwd_block_q=256), (256, None)),
    (dict(bwd_block_k=128), (None, 128)),
    # one that does not divide the forward's block falls to it, as before
    (dict(block_q=256, block_k=256, bwd_block_q=96, bwd_block_k=384),
     (256, 256)),
])
def test_explicit_bwd_blocks_win(explicit, want):
    q, k, v = _qkv(512, 512, 64)
    grids = _grids_of(jax.grad(lambda *x: jnp.sum(flash_attention(
        *x, causal=True, interpret=True, **explicit))), q, k, v)
    derived = fa.bwd_tiles(512, 512, 64, q.dtype, False)
    for name, rule in zip(("bwd_dq", "bwd_dkv"), derived):
        g = grids["flash_attention_" + name]
        exp = tuple(r if w is None else w
                    for r, w in zip(rule, want or (None, None)))
        assert (g["block_q"], g["block_k"]) == exp


GRIDS = [(1024, 128, 128), (1024, 512, 512), (1024, 256, 128),
         (1024, 128, 512), (2048, 512, 256)]


@pytest.mark.parametrize("s,bq,bk", GRIDS)
def test_causal_index_maps_name_the_nearest_live_block(s, bq, bk):
    nq, nk = s // bq, s // bk
    k_map = fa._k_spec(bq, bk, 64, True).index_map
    q_spec, k_spec2, stat_spec = fa._dkv_specs(bq, bk, 64, True)
    assert q_spec.block_shape == (1, bq, 64)
    # a q block's statistics: one float32 a row, the rows along the lanes
    assert stat_spec.block_shape == (1, 1, bq)
    assert fa._stat_spec(bq).block_shape == (1, 1, bq)
    fetched_k = fetched_q = live = 0
    for i in range(nq):
        seen = set()
        for j in range(nk):
            is_live = j * bk <= (i + 1) * bq - 1        # the kernels' test
            live += is_live
            b, blk, z = (int(x) for x in k_map(3, i, j))
            assert (b, z) == (3, 0)
            last_live = max(jj for jj in range(nk)
                            if jj * bk <= (i + 1) * bq - 1)
            assert blk == (j if is_live else last_live)
            seen.add(blk)
        fetched_k += len(seen)
    for j in range(nk):
        seen = set()
        for i in range(nq):
            is_live = (i + 1) * bq - 1 >= j * bk
            first_live = min(ii for ii in range(nq)
                             if (ii + 1) * bq - 1 >= j * bk)
            want = i if is_live else first_live
            assert tuple(int(x) for x in q_spec.index_map(3, j, i)) \
                == (3, want, 0)
            assert tuple(int(x) for x in stat_spec.index_map(3, j, i)) \
                == (3, 0, want)
            blk = want
            assert tuple(int(x) for x in k_spec2.index_map(3, j, i)) \
                == (3, j, 0)
            # dq's grid: the statistics follow the resident q block
            assert tuple(int(x) for x in fa._stat_spec(bq).index_map(
                3, i, j)) == (3, 0, i)
            seen.add(blk)
        fetched_q += len(seen)
    assert fetched_k == fetched_q == live < nq * nk
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        g = fa.grid_steps(kernel, 5, s, s, bq, bk, True)
        assert g["steps"] == 5 * nq * nk
        assert g["fetched_steps"] == g["live_steps"] == 5 * live
        g = fa.grid_steps(kernel, 5, s, s, bq, bk, False)
        assert g["fetched_steps"] == g["live_steps"] == g["steps"]


def test_grid_steps_of_cell_2_before_and_after():
    """ISSUE 28's arithmetic: 9,216 steps a call at 128 x 128, 5,184 of
    them live, every one fetched; the same call at the derived tiles."""
    old = fa.grid_steps("bwd_dq", 144, 1024, 1024, 128, 128, True)
    assert (old["steps"], old["live_steps"]) == (9216, 5184)
    for kernel, (bq, bk) in zip(("bwd_dq", "bwd_dkv"), fa.bwd_tiles(
            1024, 1024, 64, jnp.bfloat16, False)):
        new = fa.grid_steps(kernel, 144, 1024, 1024, bq, bk, True)
        assert new["steps"] <= 9216 // 4
        assert new["fetched_steps"] == new["live_steps"]


# ---------------------------------------------------------------------------
# the forward's blocks derived from the shapes, its keys walked in pieces,
# dead pieces skipped (PR 32; the compiled kernel:
# tests/test_tpu_aot_compile.py, chip_smoke.py)
# ---------------------------------------------------------------------------
# (padded sq, padded sk, d, dv, dtype, dropout, causal: the rule does not
# read it)
FWD_RULE_CASES = {
    "cell1": (512, 512, 64, None, "bfloat16", True, False),
    "cell2": (1024, 1024, 64, None, "bfloat16", False, True),
    "cell3": (4096, 4096, 192, 128, "bfloat16", False, True),
    "s197": (200, 256, 64, None, "float32", False, False),
    "s393_causal": (400, 512, 64, None, "float32", False, True),
    "s768_causal": (768, 768, 64, None, "bfloat16", False, True),
    "cross_256_640": (256, 640, 64, None, "float32", False, False),
    "cross_2048_512": (2048, 512, 128, None, "bfloat16", True, False),
    "short_100": (104, 128, 64, None, "float32", False, True),
    "s1152": (1152, 1152, 64, None, "bfloat16", False, True),
    "f32_d256_dropout": (2048, 2048, 256, None, "float32", True, True),
    "s8192": (8192, 8192, 128, None, "bfloat16", False, True),
    "s16384_f32_d256": (16384, 16384, 256, None, "float32", True, False),
}


@pytest.mark.parametrize("case", sorted(FWD_RULE_CASES))
def test_fwd_tile_rule(case):
    sq, sk, d, dv, dtype, dropout, causal = FWD_RULE_CASES[case]
    bq, bk = fa.fwd_tiles(sq, sk, d, jnp.dtype(dtype), dropout, dv)
    assert sq % bq == 0 and sk % bk == 0
    assert bq % 128 == 0 or bq == sq
    assert bk % 128 == 0 or bk == sk
    assert bq <= max(fa.MAX_FWD_BLOCK_Q, 0 if sq % 128 == 0 else sq)
    assert bk <= fa.MAX_FWD_BLOCK_K
    piece = fa._fwd_piece(bk)
    assert piece == (fa.FWD_PIECE if bk % fa.FWD_PIECE == 0 else bk)
    used = fa._fwd_vmem_bytes(bq, bk, d, jnp.dtype(dtype).itemsize,
                              dropout, dv)
    assert used <= fa.BWD_VMEM_BUDGET
    # the widest k block that fits beside any q block
    for t in itertools.product(fa._tile_sizes(sq, fa.MAX_FWD_BLOCK_Q),
                               fa._tile_sizes(sk, fa.MAX_FWD_BLOCK_K)):
        if t[1] > bk:
            assert fa._fwd_vmem_bytes(
                *t, d, jnp.dtype(dtype).itemsize, dropout,
                dv) > fa.BWD_VMEM_BUDGET


@pytest.mark.parametrize("case,want", [
    ("cell1", (512, 512, 512)),       # one tile, one piece
    ("cell2", (1024, 1024, 512)),     # the whole sequence a step
    ("cell3", (1024, 4096, 512)),     # every key resident, 4 steps a head
    ("s768_causal", (768, 768, 768)),             # faster whole than 2 x 384
    ("s8192", (1024, 4096, 512)),     # keys streamed twice: the taller q
    ("f32_d256_dropout", (512, 2048, 512)),
])
def test_fwd_tile_rule_at_the_shapes_timed_on_the_chip(case, want):
    """PERF.md section 6, PR 32: the block the rule picks was timed on
    the chip at each of these shapes and is the swept winner at cells 1
    and 2 and at 768 and 8,192 positions; at cell 3's, 512 rows beside
    the same 4,096 keys were 5% faster in the kernel alone, 0.3% of the
    step, which no pair of runs resolves: not a case of the rule."""
    sq, sk, d, dv, dtype, dropout, causal = FWD_RULE_CASES[case]
    bq, bk = fa.fwd_tiles(sq, sk, d, jnp.dtype(dtype), dropout, dv)
    assert (bq, bk, fa._fwd_piece(bk)) == want


def _fwd_golden(q, k, v, causal, rate=0.0, seed=0):
    """Plain-XLA attention with the kernels' own keep mask."""
    import math
    from flexflow_tpu.kernels import dropout_keep_mask
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        s_ = jnp.where(np.tril(np.ones((sq, sk), bool)), s_, -1e30)
    p = jax.nn.softmax(s_, -1)
    if rate:
        p = jnp.where(dropout_keep_mask(b, h, sq, sk, rate, seed),
                      p / (1 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (sq, sk, d, dv, causal, dropout, explicit blocks) -> the forward's
# (block_q, block_k, piece_k)
FWD_CASES = {
    "s1024_64": (1024, 1024, 64, 64, False, 0.0, {}, (1024, 1024, 512)),
    "s1024_64_causal": (1024, 1024, 64, 64, True, 0.0, {},
                        (1024, 1024, 512)),
    "s1024_192_128": (1024, 1024, 192, 128, False, 0.0, {},
                      (1024, 1024, 512)),
    "s1024_192_128_causal": (1024, 1024, 192, 128, True, 0.0, {},
                             (1024, 1024, 512)),
    "s1024_dropout": (1024, 1024, 64, 64, False, 0.1, {},
                      (1024, 1024, 512)),
    "s1024_dropout_causal": (1024, 1024, 64, 64, True, 0.1, {},
                             (1024, 1024, 512)),
    # several q blocks under the diagonal, every key in each step
    "s2048_causal": (2048, 2048, 64, 64, True, 0.0, {}, (1024, 2048, 512)),
    # padded keys: 600 -> 640 and 1100 -> 1152, each walked in one piece
    "s600_padded": (600, 600, 64, 64, False, 0.0, {}, (640, 640, 640)),
    "s600_padded_causal": (600, 600, 64, 64, True, 0.0, {},
                           (640, 640, 640)),
    "s1100_padded": (1100, 1100, 64, 64, False, 0.1, {},
                     (384, 1152, 1152)),
    "cross_300_700": (300, 700, 64, 64, False, 0.0, {}, (304, 768, 768)),
    "explicit_256x1024": (1024, 1024, 64, 64, True, 0.0,
                          dict(block_q=256, block_k=1024),
                          (256, 1024, 512)),
    "explicit_128x256": (1024, 1024, 64, 64, True, 0.1,
                         dict(block_q=128, block_k=256), (128, 256, 256)),
    # 1100 keys padded to 2048: the last piece is padding alone
    "explicit_padding_piece": (1100, 1100, 64, 64, False, 0.1,
                               dict(block_q=512, block_k=1024),
                               (512, 1024, 512)),
    "explicit_k_only": (1024, 1024, 64, 64, False, 0.0,
                        dict(block_k=512), (1024, 512, 512)),
}


@pytest.mark.parametrize("case", sorted(FWD_CASES))
def test_flash_forward_at_derived_blocks(case):
    sq, sk, d, dv, causal, rate, blocks, want = FWD_CASES[case]
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, s, w)), jnp.float32)
               for s, w in ((sq, d), (sk, d), (sk, dv)))
    kw = dict(causal=causal, dropout_rate=rate, dropout_seed=9,
              interpret=True, **blocks)
    g = _grids_of(lambda *x: flash_attention(*x, **kw), q, k, v)[
        "flash_attention_fwd"]
    assert (g["block_q"], g["block_k"], g["piece_k"]) == want
    assert g["fetched_steps"] == g["live_steps"] <= g["steps"]
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_fwd_golden(q, k, v, causal, rate, 9)),
        atol=2e-5, rtol=2e-4)


# (block_q, block_k, padded s, kv_len, causal), block_q != block_k among
# them; block_k 1152 and 640 walk in one piece
PIECE_GRIDS = [(512, 512, 1024, 1024, True), (1024, 1024, 1024, 1024, True),
               (512, 2048, 2048, 2048, True), (256, 1024, 2048, 2048, True),
               (1024, 512, 2048, 2048, True), (384, 1152, 1152, 1100, True),
               (384, 1152, 1152, 1100, False), (640, 640, 640, 600, False),
               (512, 1024, 2048, 1990, False), (512, 1024, 1024, 1024, False)]


@pytest.mark.parametrize("bq,bk,s,kv_len,causal", PIECE_GRIDS)
def test_fwd_piece_classes_against_the_mask_itself(bq, bk, s, kv_len,
                                                   causal):
    """Every (q block, k block, piece) of the grid: a piece the kernel
    skips has an all-false mask, and a piece it computes has a valid
    pair (every live piece is masked, so none is classed "no mask")."""
    piece_k = fa._fwd_piece(bk)
    pieces = bk // piece_k
    dead = 0
    for iq, ik, c in itertools.product(range(s // bq), range(s // bk),
                                       range(pieces)):
        piece = ik * pieces + c
        mask = np.asarray(fa._key_mask(iq, piece, bq, piece_k, kv_len,
                                       causal))
        if causal and not fa._piece_live(iq, piece, bq, piece_k):
            dead += 1
            assert not mask.any()
        else:
            assert mask.any()
    g = fa.grid_steps("fwd", 1, s, s, bq, bk, causal)
    assert g["live_pieces"] == (s // bq) * (s // bk) * pieces - dead


@pytest.mark.parametrize("cell,bh,s,d,dv,want", [
    ("cell2", 144, 1024, 64, None, dict(
        block_q=1024, block_k=1024, piece_k=512, steps=144, live_steps=144,
        fetched_steps=144, live_pieces=288)),
    ("cell3", 32, 4096, 192, 128, dict(
        block_q=1024, block_k=4096, piece_k=512, steps=128, live_steps=128,
        fetched_steps=128, live_pieces=640)),
])
def test_grid_steps_of_the_forward_at_the_cells_shapes(cell, bh, s, d, dv,
                                                       want):
    """Cell 2 ran 576 steps a call (432 live), cell 3 2,048 (1,152 live):
    144 and 128 now, none dead, the dead PIECES skipped inside a step."""
    bq, bk = fa.fwd_tiles(s, s, d, jnp.bfloat16, False, dv)
    g = fa.grid_steps("fwd", bh, s, s, bq, bk, True)
    assert {k: g[k] for k in want} == want
    old = fa.grid_steps("fwd", bh, s, s, 512, 512, True)
    assert old["steps"] == {"cell2": 576, "cell3": 2048}[cell]
    assert old["live_steps"] == old["live_pieces"] == {
        "cell2": 432, "cell3": 1152}[cell]
    # cell 1: not causal, one piece a step
    g1 = fa.grid_steps("fwd", 128, 512, 512, 512, 512, False)
    assert (g1["steps"], g1["live_steps"], g1["live_pieces"]) == (
        128, 128, 128)


# ---------------------------------------------------------------------------
# q.k over one head size, p.v over another (latent attention: 192 / 128)
# ---------------------------------------------------------------------------
def _rand_qk_v(d, dv, b=1, h=2, s=256, seed=3):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, h, s, w)), jnp.float32)
                 for w in (d, d, dv))


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128)])
def test_flash_with_unequal_head_sizes_matches_reference(d, dv, causal,
                                                         what):
    """The three kernels with v and the output narrower than q and k, at
    128-wide tiles so that every kernel accumulates over several blocks.
    f32 operands in interpret mode: the tolerances are those of the
    equal-size tests above."""
    q, k, v = _rand_qk_v(d, dv)

    def run(fn):
        def out(q, k, v):
            return fn(q, k, v)
        if what == "out":
            return out(q, k, v)
        return jax.grad(lambda *a: jnp.sum(out(*a) ** 2),
                        argnums="qkv".index(what[1]))(q, k, v)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=128, block_k=128,
        bwd_block_q=128, bwd_block_k=128))
    want = run(lambda q, k, v: mha_reference(
        q, k, v, causal=causal, precision=jax.lax.Precision.HIGHEST))
    assert got.shape == (v.shape if what in ("out", "dv") else q.shape)
    tol = 2e-5 if what == "out" else 5e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_unequal_head_sizes_scale_by_the_key_size_and_pad_neither():
    q, k, v = _rand_qk_v(24, 16, s=128)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(mha_reference(q, k, v, sm_scale=1 / np.sqrt(24))),
        atol=2e-5, rtol=2e-5)
    events.enable()
    events.clear()
    try:
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True)))(q, k, v)
        grids = [e["attrs"]["kernel"] for e in events.events()
                 if e["name"] == "flash.grid"]
    finally:
        events.disable()
        events.clear()
    assert sorted(grids) == ["flash_attention_bwd_dkv",
                             "flash_attention_bwd_dq",
                             "flash_attention_fwd"]


@pytest.mark.parametrize("sq,sk,d,dtype,dropout", [
    c for c in TILE_RULE_CASES if c[2] == 256])
def test_bwd_tile_rule_counts_both_head_sizes(sq, sk, d, dtype, dropout):
    """A narrower v needs no more room than an equal one, an equal one
    exactly what it needed before there were two sizes, and the tile the
    rule hands out for (d, dv) fits the budget as counted for both."""
    item = jnp.dtype(dtype).itemsize
    for kernel in ("bwd_dq", "bwd_dkv"):
        same = fa._bwd_vmem_bytes(kernel, 512, 512, d, item, dropout)
        assert same == fa._bwd_vmem_bytes(kernel, 512, 512, d, item,
                                          dropout, d)
        assert fa._bwd_vmem_bytes(kernel, 512, 512, d, item, dropout,
                                  128) < same
    narrow = fa.bwd_tiles(sq, sk, d, jnp.dtype(dtype), dropout, 128)
    equal = fa.bwd_tiles(sq, sk, d, jnp.dtype(dtype), dropout)
    for kernel, (bq, bk), (eq, ek) in zip(("bwd_dq", "bwd_dkv"), narrow,
                                          equal):
        assert bq * bk >= eq * ek
        assert fa._bwd_vmem_bytes(kernel, bq, bk, d, item, dropout,
                                  128) <= fa.BWD_VMEM_BUDGET \
            or (bq, bk) == (fa._tile_sizes(sq)[0], fa._tile_sizes(sk)[0])


def test_layers_of_one_shape_trace_each_kernel_body_once(monkeypatch):
    """BERT-large's step has 24 attention layers of one shape. Traced a
    layer at a time the three kernels cost the chip's host 85 ms a layer,
    6 s of a benchmark run's set-up (PERF.md, PR 30); the calls are
    jitted and inlined, so a step traces each body once, every layer's
    call is still its own equation, and each still records its grid."""
    import sys

    from flexflow_tpu.obs import events
    # (the package's attribute of this name is the function)
    fa = sys.modules["flexflow_tpu.kernels.flash_attention"]
    jax.clear_caches()
    traced = {"fwd": 0, "bwd_dq": 0, "bwd_dkv": 0}

    def counting(kind, body):
        def kernel(*a, **kw):
            traced[kind] += 1
            return body(*a, **kw)
        return kernel

    for kind in traced:
        monkeypatch.setattr(fa, f"_{kind}_kernel",
                            counting(kind, getattr(fa, f"_{kind}_kernel")))
    q, k, v = _rand_qkv(b=1, h=2, s=128, d=64)

    def loss(q, k, v):
        for seed in range(4):
            q = flash_attention(q, k, v, interpret=True, dropout_rate=0.1,
                                dropout_seed=jnp.int32(seed))
        return jnp.sum(q)

    events.enable()
    try:
        events.clear()
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        grids = [e for e in events.events() if e["name"] == "flash.grid"]
    finally:
        events.disable()
    assert traced == {"fwd": 1, "bwd_dq": 1, "bwd_dkv": 1}
    assert str(jaxpr).count("pallas_call[") == 12     # inlined, not shared
    assert len(grids) == 12
    jax.clear_caches()           # the counting bodies are in the jit cache


# ---------------------------------------------------------------------------
# the backward kernels' row statistics: one float32 a row from the residual
# to the use, and the tile held whichever way needs no (rows, 1) column
# (PR 39; the compiled kernels: tests/test_tpu_aot_compile.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("iq,ik,bq,bk", [(0, 0, 128, 128), (1, 0, 128, 256),
                                         (2, 3, 64, 128), (0, 1, 256, 128)])
def test_keys_major_masks_are_the_transposed_masks(iq, ik, bq, bk):
    """Both masks are functions of absolute positions: a kernel that
    holds the tile keys-major draws the transpose, bit for bit, and the
    dropout mask is ``dropout_keep_mask``'s."""
    from flexflow_tpu.kernels import dropout_keep_mask
    rate, seed, bh = 0.1, 11, 3
    seed_ref = jnp.full((1, 1), seed, jnp.int32)
    for causal in (False, True):
        kv_len = (ik + 1) * bk - 40
        qm = fa._key_mask(iq, ik, bq, bk, kv_len, causal)
        km = fa._key_mask(iq, ik, bq, bk, kv_len, causal, keys_major=True)
        assert km.shape == (bk, bq) and jnp.array_equal(km, qm.T)
    keep_q = fa._tile_keep_mask(seed_ref, bh, iq, ik, bq, bk, rate)
    keep_k = fa._tile_keep_mask(seed_ref, bh, iq, ik, bq, bk, rate,
                                keys_major=True)
    whole = dropout_keep_mask(1, bh + 1, (iq + 1) * bq, (ik + 1) * bk, rate,
                              seed)[0, bh]
    assert jnp.array_equal(keep_q, whole[iq * bq:, ik * bk:])
    assert keep_k.shape == (bk, bq) and jnp.array_equal(keep_k, keep_q.T)
    assert 0.8 < float(jnp.mean(keep_k)) < 0.97


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":       # not a kernel's body
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _all_eqns(sub)


@pytest.mark.parametrize("case", ["cell2_like", "dropout", "unequal_heads"])
def test_no_row_statistic_is_replicated_over_128_lanes(case):
    """Forward and backward traced with bf16 operands, so that what is
    float32 is the statistics: the forward writes its log-sum-exp as
    (bh, 1, sq), each backward call is handed two such operands and none
    with a trailing 128 that is not a head size, no (bh, sq, 128) float32
    array exists anywhere in the step, and each backward call's
    ``flash.grid`` says so (``stat_bytes`` = 2 x bh x sq x 4)."""
    b, h, s, d, dv, kw = {
        "cell2_like": (2, 3, 256, 64, 64, dict(causal=True)),
        "dropout": (1, 2, 256, 64, 64, dict(dropout_rate=0.1,
                                            dropout_seed=3)),
        # (v narrower than 128: ``do * o`` in float32 has v's width)
        "unequal_heads": (1, 2, 256, 192, 64, dict(causal=True)),
    }[case]
    q, k, v = (jax.ShapeDtypeStruct((b, h, s, w), jnp.bfloat16)
               for w in (d, d, dv))
    events.enable()
    events.clear()
    try:
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *x: jnp.sum(flash_attention(*x, interpret=True, **kw)
                               .astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)
        grids = {e["attrs"]["kernel"]: e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"}
    finally:
        events.clear()
        events.disable()
    bh = b * h
    eqns = list(_all_eqns(jaxpr.jaxpr))
    calls = {e.params["name"]: e for e in eqns
             if e.primitive.name == "pallas_call"}
    assert sorted(calls) == ["flash_attention_bwd_dkv",
                             "flash_attention_bwd_dq", "flash_attention_fwd"]
    assert [tuple(x.aval.shape) for x in
            calls["flash_attention_fwd"].outvars] == [(bh, s, dv),
                                                      (bh, 1, s)]
    for name in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        handed = [(tuple(x.aval.shape), x.aval.dtype)
                  for x in calls[name].invars]
        assert handed.count(((bh, 1, s), jnp.float32)) == 2, handed
        assert not [sh for sh, dt in handed
                    if dt == jnp.float32 and sh[-1] == 128], handed
        assert grids[name]["stat_bytes"] == 2 * bh * s * 4
    assert grids["flash_attention_bwd_dq"]["tile"] == "queries_major"
    assert grids["flash_attention_bwd_dkv"]["tile"] == "keys_major"
    assert "stat_bytes" not in grids["flash_attention_fwd"]
    held = {tuple(x.aval.shape) for e in eqns
            for x in list(e.invars) + list(e.outvars)
            if getattr(getattr(x, "aval", None), "dtype", None)
            == jnp.float32}
    assert (bh, s, 128) not in held and (b, h, s, 128) not in held


# dq (queries-major, statistics from scratch by lanes) and dk, dv
# (keys-major, statistics as rows) against plain XLA with the kernels'
# own keep mask: causal x dropout x head sizes x (square with tiles
# smaller than the sequence | sq != sk | a padded kv_len and q length)
BWD_FORM_LAYOUTS = {
    "tiled_256": dict(sq=256, sk=256, bwd_block_q=128, bwd_block_k=128),
    "cross_128_384": dict(sq=128, sk=384, bwd_block_q=128, bwd_block_k=128),
    "padded_200": dict(sq=200, sk=200),
}
BWD_FORM_CASES = [
    (layout, d, dv, causal, rate)
    for layout in BWD_FORM_LAYOUTS for d, dv in ((64, 64), (192, 128))
    for causal in (False, True) for rate in (0.0, 0.1)
    if not (causal and layout == "cross_128_384")]


@functools.lru_cache(maxsize=None)
def _bwd_form_grads(layout, d, dv, causal, rate):
    lay = dict(BWD_FORM_LAYOUTS[layout])
    sq, sk = lay.pop("sq"), lay.pop("sk")
    rng = np.random.default_rng(39)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, s, w)), jnp.float32)
               for s, w in ((sq, d), (sk, d), (sk, dv)))
    kw = dict(causal=causal, interpret=True, **lay)
    if rate:
        kw.update(dropout_rate=rate, dropout_seed=5)
    grids = _grids_of(jax.grad(lambda *x: jnp.sum(
        flash_attention(*x, **kw))), q, k, v)
    got = jax.grad(lambda *x: jnp.sum(flash_attention(*x, **kw) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *x: jnp.sum(
        _fwd_golden(*x, causal, rate, 5) ** 2), argnums=(0, 1, 2))(q, k, v)
    return grids, got, want


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("layout,d,dv,causal,rate", BWD_FORM_CASES)
def test_flash_backward_forms_match_reference(layout, d, dv, causal, rate,
                                              what):
    grids, got, want = _bwd_form_grads(layout, d, dv, causal, rate)
    kernel = "flash_attention_bwd_" + ("dq" if what == "dq" else "dkv")
    assert grids[kernel]["tile"] == (
        "queries_major" if what == "dq" else "keys_major")
    if layout == "tiled_256":      # several blocks on both sides
        assert grids[kernel]["steps"] == 2 * 2 * 2
    i = ("dq", "dk", "dv").index(what)
    assert got[i].shape == want[i].shape
    np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# the mask operand (PR 49): a (b, sq, sk) int8 array shared by the heads
# ---------------------------------------------------------------------------
def _topk_causal_mask(b, s, topk, seed=0):
    """A row's ``min(t + 1, topk)`` keys of largest random score among
    its causal ones: what a sparse-attention indexer hands the kernels."""
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((b, s, s))
    causal = np.tril(np.ones((s, s), bool))
    scores = np.where(causal, scores, -np.inf)
    kth = np.sort(scores, -1)[..., ::-1][..., topk - 1:topk]
    return jnp.asarray(causal & (scores >= kth), jnp.int8)


def _random_mask(b, sq, sk, seed=0):
    """A third of the pairs, and key 0 for every row (no empty row)."""
    rng = np.random.default_rng(seed)
    mask = rng.random((b, sq, sk)) < 0.3
    mask[..., 0] = True
    return jnp.asarray(mask, jnp.int8)


# name -> (b, h, sq, sk, d, causal, the mask)
MASK_CASES = {
    "random": (2, 3, 256, 256, 64, False, lambda: _random_mask(2, 256, 256)),
    "random_ragged": (1, 2, 200, 328, 48, False,
                      lambda: _random_mask(1, 200, 328)),
    "topk_causal": (2, 4, 256, 256, 64, True,
                    lambda: _topk_causal_mask(2, 256, 64)),
    # the mask's tile is counted: at this shape the forward's derived
    # blocks are narrower than an unmasked call's
    "narrower_blocks": (1, 1, 2048, 2048, 128, True,
                        lambda: _topk_causal_mask(1, 2048, 256)),
}


def _masked_softmax(q, k, mask, causal):
    """``(probabilities, lse)`` of a plain masked softmax at HIGHEST."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    valid = mask[:, None] != 0
    if causal:
        valid = valid & np.tril(np.ones(s.shape[-2:], bool))
    s = jnp.where(valid, s, -1e30)
    return jax.nn.softmax(s, -1), jax.nn.logsumexp(s, -1), valid


def _masked_reference(q, k, v, mask, causal):
    p = _masked_softmax(q, k, mask, causal)[0]
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _mask_case(case):
    b, h, sq, sk, d, causal, make = MASK_CASES[case]
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    return q, k, v, make(), causal


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_masked_flash_matches_a_plain_masked_softmax(case, what):
    q, k, v, mask, causal = _mask_case(case)
    if case == "narrower_blocks":
        sq, sk, d = q.shape[2], k.shape[2], q.shape[3]
        masked = fa.fwd_tiles(sq, sk, d, q.dtype, False, None, True)
        plain = fa.fwd_tiles(sq, sk, d, q.dtype, False)
        assert masked[0] * masked[1] < plain[0] * plain[1]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, mask=mask,
                               interpret=True)

    def ref(q, k, v):
        return _masked_reference(q, k, v, mask, causal)

    if what == "out":
        got, want = flash(q, k, v), ref(q, k, v)
    else:
        i = "qkv".index(what[1])
        got, want = (jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), i)(q, k, v)
                     for f in (flash, ref))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_a_mask_equal_to_the_causal_one_is_the_causal_call_bit_for_bit(
        what):
    """ANDed into what ``_key_mask`` gives, the diagonal changes nothing:
    the same blocks, the same arithmetic, the same bits."""
    q, k, v = _rand_qkv(b=2, h=2, s=256, d=64)
    tril = jnp.asarray(np.tril(np.ones((2, 256, 256), np.int8)))
    blocks = dict(block_q=128, block_k=128, bwd_block_q=128,
                  bwd_block_k=128, interpret=True)

    def run(**kw):
        f = lambda q, k, v: flash_attention(q, k, v, causal=True, **blocks,
                                            **kw)
        if what == "out":
            return f(q, k, v)
        return jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                        "qkv".index(what[1]))(q, k, v)

    assert np.array_equal(np.asarray(run(mask=tril)), np.asarray(run()))


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_a_masked_calls_lse_is_the_masked_rows_own(case):
    """The forward's row statistic is over the attended keys alone, and
    the output beside it is the differentiable call's."""
    q, k, v, mask, causal = _mask_case(case)
    o, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                        interpret=True)
    assert lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    want = _masked_softmax(q, k, mask, causal)[1]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert np.array_equal(
        np.asarray(o), np.asarray(flash_attention(
            q, k, v, causal=causal, mask=mask, interpret=True)))
    # rows that attend fewer keys have the smaller statistic than the
    # unmasked call's, every one of them
    full = fa.flash_attention_forward(
        q, k, v, jnp.ones_like(mask), causal=causal, interpret=True)[1]
    assert np.all(np.asarray(lse) <= np.asarray(full) + 1e-5)


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_head_mean_is_the_mean_of_the_heads_softmax(case):
    q, k, v, mask, causal = _mask_case(case)
    _, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                        interpret=True)
    got = fa.flash_attention_head_mean(q, k, lse, mask, causal=causal,
                                       interpret=True)
    p, _, valid = _masked_softmax(q, k, mask, causal)
    assert got.shape == mask.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(p.mean(1)),
                               atol=2e-6, rtol=2e-5)
    # exactly 0 off the attended pairs, and each row sums to 1
    assert not np.any(np.asarray(got)[~np.asarray(valid[:, 0])])
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, atol=1e-5)


def test_head_mean_passes_no_gradient():
    q, k, v, mask, causal = _mask_case("topk_causal")
    _, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                        interpret=True)
    g = jax.grad(lambda q, k, lse: jnp.sum(fa.flash_attention_head_mean(
        q, k, lse, mask, causal=causal, interpret=True) ** 2),
        (0, 1, 2))(q, k, lse)
    assert not any(np.any(np.asarray(x)) for x in g)


@pytest.mark.parametrize("sq,sk,d,dtype", [
    (8192, 8192, 128, "bfloat16"), (4096, 4096, 128, "bfloat16"),
    (2048, 2048, 128, "float32"), (1024, 1024, 64, "float32")])
def test_the_tile_rules_count_the_mask(sq, sk, d, dtype):
    """A masked call's blocks fit the budget with the mask's tile
    counted, and are no larger than the unmasked call's; the head-mean
    kernel's tile fits too."""
    dtype = jnp.dtype(dtype)
    it = dtype.itemsize
    bq, bk = fa.fwd_tiles(sq, sk, d, dtype, False, None, True)
    assert fa._fwd_vmem_bytes(bq, bk, d, it, False, None, True) \
        <= fa.BWD_VMEM_BUDGET
    plain = fa.fwd_tiles(sq, sk, d, dtype, False)
    assert bq * bk <= plain[0] * plain[1]
    assert fa._fwd_vmem_bytes(bq, bk, d, it, False, None, True) \
        - fa._fwd_vmem_bytes(bq, bk, d, it, False) >= 2 * bq * bk
    for kernel, tile, was in zip(
            ("bwd_dq", "bwd_dkv"),
            fa.bwd_tiles(sq, sk, d, dtype, False, None, True),
            fa.bwd_tiles(sq, sk, d, dtype, False)):
        assert fa._bwd_vmem_bytes(kernel, *tile, d, it, False, None, True) \
            <= fa.BWD_VMEM_BUDGET
        assert tile[0] * tile[1] <= was[0] * was[1]
    tile = fa.head_mean_tiles(sq, sk, d, dtype)
    assert fa._head_mean_vmem_bytes(*tile, d, it) <= fa.BWD_VMEM_BUDGET
    assert sq % tile[0] == 0 and sk % tile[1] == 0


def test_a_block_policy_that_keeps_o_and_lse_skips_the_forward_kernel():
    """``flash_attention_from_forward`` takes the forward's output and
    log-sum-exp as ARGUMENTS: named for a ``jax.checkpoint`` policy they
    are kept, and the differentiated step holds one forward call, not
    two; unnamed, the forward runs again for the backward."""
    from jax.ad_checkpoint import checkpoint_name
    q, k, v, mask, causal = _mask_case("topk_causal")

    def layer(named, q, k, v):
        o, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                            interpret=True)
        if named:
            o, lse = (checkpoint_name(x, "kept") for x in (o, lse))
        return fa.flash_attention_from_forward(q, k, v, mask, o, lse,
                                               causal=causal, interpret=True)

    def forward_calls(named):
        block = jax.checkpoint(
            functools.partial(layer, named),
            policy=jax.checkpoint_policies.save_only_these_names("kept"))
        txt = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(jnp.sin(block(*a))), (0, 1, 2)))(q, k, v))
        return txt.count("name=flash_attention_fwd")

    assert (forward_calls(True), forward_calls(False)) == (1, 2)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(jax.checkpoint(
        functools.partial(layer, True),
        policy=jax.checkpoint_policies.save_only_these_names("kept"))(*a))),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_masked_reference(
        *a, mask, causal))), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-5)


def test_a_masked_call_checks_its_mask_and_runs_on_one_device():
    q, k, v = _rand_qkv(b=2, h=2, s=128, d=64)
    with pytest.raises(ValueError, match="batch, sq, sk"):
        flash_attention(q, k, v, mask=jnp.ones((2, 2, 128, 128), jnp.int8),
                        interpret=True)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    with pytest.raises(NotImplementedError, match="one device"):
        flash_attention(q, k, v, mask=jnp.ones((2, 128, 128), jnp.int8),
                        interpret=True, mesh=mesh, spec=P("x"))


def test_a_masked_call_says_so_in_its_grid_instants():
    q, k, v, mask, causal = _mask_case("topk_causal")
    events.enable()
    try:
        events.clear()
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=causal, mask=mask, interpret=True)))(q)
        _, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                            interpret=True)
        fa.flash_attention_head_mean(q, k, lse, mask, causal=causal,
                                     interpret=True)
        grids = [e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"]
    finally:
        events.disable()
    by_kernel = {g["kernel"]: g for g in grids}
    assert set(by_kernel) == {
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv", "flash_attention_head_mean"}
    assert all(g.get("masked") for name, g in by_kernel.items()
               if name != "flash_attention_head_mean")


# ---------------------------------------------------------------------------
# grouped-query attention (PR 52): k and v at their own head count, read
# in place; ``bwd_dkv`` sums a group's query heads in its accumulator
# ---------------------------------------------------------------------------
# name -> (b, h, kvh, sq, sk, d, dv, causal, the call's further options)
GROUPED = dict(block_q=128, block_k=128)          # several q and k blocks
GQA_CASES = {
    "causal_group4": (2, 8, 2, 256, 256, 64, 64, True, GROUPED),
    "causal_group8": (1, 8, 1, 256, 256, 32, 32, True, GROUPED),
    "derived_tiles": (1, 8, 2, 384, 384, 64, 64, True, {}),
    "window": (1, 8, 2, 256, 256, 32, 32, True, dict(GROUPED, window=96)),
    "window_group8": (2, 8, 1, 256, 256, 32, 32, True,
                      dict(GROUPED, window=160, bwd_block_q=64)),
    "mask": (2, 4, 1, 256, 256, 64, 64, True, {"mask": "topk"}),
    "mask_not_causal": (2, 6, 2, 256, 256, 64, 64, False,
                        dict(GROUPED, mask="random")),
    "not_causal": (2, 8, 2, 256, 384, 64, 64, False, GROUPED),
    "dropout": (2, 8, 2, 256, 256, 64, 64, True,
                dict(GROUPED, dropout_rate=0.25, dropout_seed=11)),
    "dropout_group8": (1, 8, 1, 128, 256, 32, 32, False,
                       dict(dropout_rate=0.4, dropout_seed=5, block_k=128,
                            bwd_block_q=64)),
    "unequal_head_sizes": (1, 8, 2, 256, 256, 24, 16, True, GROUPED),
    "latent_sizes": (1, 4, 1, 256, 256, 192, 128, True, {}),
    "pads": (2, 8, 2, 200, 200, 48, 48, True, {}),
    "pads_cross": (1, 8, 4, 100, 328, 64, 64, False, {}),
}


def _gqa_case(case):
    b, h, kvh, sq, sk, d, dv, causal, opts = GQA_CASES[case]
    rng = np.random.default_rng(52)
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, sk, dv)), jnp.float32)
    opts = dict(opts)
    if opts.get("mask"):
        opts["mask"] = _topk_causal_mask(b, sq, 48) \
            if opts["mask"] == "topk" else _random_mask(b, sq, sk)
    return q, k, v, causal, opts


def _repeated_reference(q, k, v, causal, opts):
    """A plain softmax at HIGHEST on k and v REPEATED to the query
    heads (``jnp.repeat``: what the layers did before the kernels read
    the heads in place), under the call's window, mask and dropout."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    if set(opts) <= {"block_q", "block_k", "bwd_block_q", "bwd_block_k"}:
        return mha_reference(q, k, v, causal=causal, precision="highest")
    sq, sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    t, u = np.arange(sq)[:, None], np.arange(sk)[None, :]
    valid = np.ones((sq, sk), bool)
    if causal:
        valid &= u <= t
    if opts.get("window"):
        valid &= u > t - opts["window"]
    valid = jnp.asarray(valid)[None, None]
    if opts.get("mask") is not None:
        valid = valid & (opts["mask"][:, None] != 0)
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), -1)
    rate = opts.get("dropout_rate", 0.0)
    if rate:
        keep = fa.dropout_keep_mask(q.shape[0], q.shape[1], sq, sk, rate,
                                    opts["dropout_seed"])
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(GQA_CASES))
def test_grouped_flash_is_the_reference_on_repeated_keys_and_values(case,
                                                                    what):
    q, k, v, causal, opts = _gqa_case(case)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=True,
                               **opts)

    def ref(q, k, v):
        return _repeated_reference(q, k, v, causal, opts)

    if what == "out":
        got, want = flash(q, k, v), ref(q, k, v)
    else:
        i = "qkv".index(what[1])
        got, want = (jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), i)(q, k, v)
                     for f in (flash, ref))
        assert got.shape == (q, k, v)[i].shape      # dk, dv at kvh heads
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", ["causal_group4", "window_group8",
                                  "dropout", "mask"])
def test_grouped_flash_is_the_call_on_repeated_operands(case):
    """The same tiles, the same products and the same dropout counters
    (the QUERY head's): the output and ``dq`` to the bit, ``dk`` and
    ``dv`` the group's sum (float32 here, so to rounding)."""
    q, k, v, causal, opts = _gqa_case(case)
    group = q.shape[1] // k.shape[1]

    def grads(repeat):
        def f(q, k, v):
            if repeat:
                k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
            o = flash_attention(q, k, v, causal=causal, interpret=True,
                                **opts)
            return jnp.sum(jnp.sin(o)), o
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)

    ((_, o1), (dq1, dk1, dv1)), ((_, o2), (dq2, dk2, dv2)) = \
        grads(False), grads(True)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(dq1), np.asarray(dq2))
    for got, want in ((dk1, dk2), (dv1, dv2)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("h,kvh", [(4, 1), (6, 2), (8, 8)])
@pytest.mark.parametrize("case", ["topk_causal", "random_ragged"])
def test_head_mean_reads_grouped_keys_in_place(case, h, kvh):
    b, _, sq, sk, d, causal, make = MASK_CASES[case]
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, sk, d)), jnp.float32)
    mask = make()
    _, lse = fa.flash_attention_forward(q, k, v, mask, causal=causal,
                                        interpret=True)
    got = fa.flash_attention_head_mean(q, k, lse, mask, causal=causal,
                                       interpret=True)
    wide = jnp.repeat(k, h // kvh, axis=1)
    p, want_lse, _ = _masked_softmax(q, wide, mask, causal)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(p.mean(1)),
                               atol=2e-6, rtol=2e-5)
    assert np.array_equal(np.asarray(got), np.asarray(
        fa.flash_attention_head_mean(q, wide, lse, mask, causal=causal,
                                     interpret=True)))


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0),
                                           (True, 2048)])
@pytest.mark.parametrize("group", [4, 8])
def test_grid_steps_of_a_grouped_call_are_the_ungrouped_calls(kernel, causal,
                                                              window, group):
    """Cells 4, 7 and 8's (32 query heads over 8,192 positions): the
    group changes which blocks a step names, not how many steps there
    are nor which compute."""
    args = (kernel, 32, 8192, 8192, 1024, 1024, causal, window)
    plain, grouped = fa.grid_steps(*args), fa.grid_steps(*args, group)
    assert "kv_group" not in plain and grouped.pop("kv_group") == group
    assert grouped == plain
    assert fa.grid_steps(*args, 1) == plain


def test_a_grouped_call_says_so_in_its_grid_instants():
    q, k, v, causal, opts = _gqa_case("mask")
    events.enable()
    try:
        events.clear()
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True, **opts)))(q)
        _, lse = fa.flash_attention_forward(q, k, v, opts["mask"],
                                            causal=causal, interpret=True)
        fa.flash_attention_head_mean(q, k, lse, opts["mask"], causal=causal,
                                     interpret=True)
        events.instant("mark")
        wide = jnp.repeat(k, 4, axis=1)
        flash_attention(q, wide, jnp.repeat(v, 4, axis=1), causal=causal,
                        interpret=True, **opts)
        grids = [e for e in events.events()
                 if e["name"] in ("flash.grid", "mark")]
    finally:
        events.disable()
    cut = [e["name"] for e in grids].index("mark")
    grouped = [e["attrs"] for e in grids[:cut]]
    assert {g["kernel"] for g in grouped} == {
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv", "flash_attention_head_mean"}
    assert all(g["kv_group"] == 4 for g in grouped)
    assert grids[cut + 1:] and all(
        "kv_group" not in e["attrs"] for e in grids[cut + 1:])


@pytest.mark.parametrize("h,kvh,vh", [(8, 3, 3), (8, 2, 4), (4, 8, 8)])
def test_heads_that_do_not_form_groups_are_refused(h, kvh, vh):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, n, 128, 32)), jnp.float32)
               for n in (h, kvh, vh))
    with pytest.raises(ValueError, match="groups"):
        flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("head_axis,kvh,in_place", [
    ("x", 4, True), ("x", 2, True), ("x", 1, False), (None, 1, True)])
def test_under_a_mesh_the_head_axis_shards_the_key_value_heads_too(
        head_axis, kvh, in_place):
    """Two devices on the head axis: 8 query heads on 4 or 2 k/v heads
    are sharded group by group and read in place on each device; 1 k/v
    head cannot be, and the layer repeats it as before (the op asks
    ``_kernel_shard_spec`` with the k/v heads and compares)."""
    import types
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    ctx = types.SimpleNamespace(mesh=mesh, op_sharding=types.SimpleNamespace(
        outputs=[P(None)], weights={"wq": P(None, head_axis)}))
    _, spec = MultiHeadAttentionOp._kernel_shard_spec(ctx, 2, 8)
    assert spec == P(None, head_axis)
    assert (MultiHeadAttentionOp._kernel_shard_spec(ctx, 2, kvh)[1]
            == spec) is in_place
    if not in_place:
        return
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 8, 128, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, kvh, 128, 32)), jnp.float32)
            for _ in range(2))
    f = lambda **kw: jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(   # noqa
        flash_attention(*a, causal=True, interpret=True, **kw))),
        (0, 1, 2)))(q, k, v)
    for got, want in zip(f(mesh=mesh, spec=spec), f()):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
