"""Numerics tests for the Pallas kernels (interpret mode on CPU) and the
sequence-parallel attention schemes (shard_map over virtual devices)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from flexflow_tpu.kernels import (flash_attention, mha_reference,
                                  ring_attention, ulysses_attention)
from jax import shard_map


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


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    mesh = _seq_mesh()
    q, k, v = _rand_qkv(b=1, h=4, s=128, d=32)

    fn = shard_map(
        functools.partial(ulysses_attention, axis_name="sp", causal=causal,
                          interpret=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
        check_vma=False)  # pallas_call outputs carry no vma info
    out = jax.jit(fn)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# kernel-tier numerics satellites: ragged lengths, GQA head layouts,
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
    use_flash_attention is on; numerics must match the XLA path."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

    def build(flash_mode):
        cfg = FFConfig()
        cfg.only_data_parallel = True
        cfg.use_flash_attention = flash_mode
        ff = FFModel(cfg)
        q = ff.create_tensor((2, 64, 64), name="q")
        ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4)
        ff.compile(SGDOptimizer(0.01), "identity", [])
        return ff

    batch = {"q": np.random.default_rng(1).normal(size=(2, 64, 64))
             .astype(np.float32)}
    ff_flash = build("true")
    ff_xla = build("false")
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
