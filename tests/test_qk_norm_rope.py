"""q/k norm, rotary embedding, cast and the turn to heads-first by the
Pallas kernels (``kernels/qk_norm_rope.py``, interpret mode on the CPU)
against the plain chain they stand in for (``ops/nn_ops.py::_rms``,
``_apply_rope`` and ``ops/sparse_attention``'s ``heads_first``), then the
attention op and a small model of cell 7's kind down both paths.

Both sides compute in float32 here and round once; they differ in the
order of a head's sum of squares and of the scale's sum over the rows.
Values and gradients are held to 1e-5 of the largest entry (they read
1e-7 to 5e-7).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu import FFConfig
from flexflow_tpu.kernels import qk_norm_rope as kernel
from flexflow_tpu.models.nlp import KeyeRankConfig, build_hybrid_conv_moe
from flexflow_tpu.obs import events
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp, _apply_rope, _rms
from flexflow_tpu.ops.registry import EmitCtx
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX

TOL = 1e-5
EPS, THETA = 1e-6, 1e4
B = 2


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def plain(x, scale, pos, repeat, dtype):
    """The chain as the op and ``sparse_index_attention_flash`` run it."""
    y = _apply_rope(_rms(x, scale, EPS), pos, THETA)
    y = jnp.repeat(y, repeat, axis=2) if repeat > 1 else y
    return jnp.swapaxes(y, 1, 2).astype(dtype)


def by_kernel(x, scale, pos, repeat, dtype, **tiles):
    tables = kernel.rope_tables(pos, x.shape[-1], THETA)
    return kernel.qk_norm_rope(x, scale, tables, eps=EPS, dtype=dtype,
                               repeat=repeat, **tiles)


def operands(s, heads, d, repeat, per_row, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(3.0 * rng.standard_normal((B, s, heads, d)), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, (d,)), jnp.float32)
    pos = rng.integers(0, 4096, (B, s)) if per_row else np.arange(s)
    ct = jnp.asarray(rng.standard_normal((B, heads * repeat, s, d)),
                     jnp.float32)
    return x, scale, jnp.asarray(pos, jnp.int32), ct


def values_and_gradients(fn, x, scale, pos, repeat, dtype, ct):
    def loss(x, scale):
        y = fn(x, scale, pos, repeat, dtype)
        return jnp.sum(y.astype(jnp.float32) * ct), y
    (_, y), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
        x, scale)
    return (y,) + grads


# 40 positions in tiles of 16: two whole tiles and half a one
@pytest.mark.parametrize("positions", ["shared", "per_row"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("heads,kv", [(8, 2), (32, 4)],
                         ids=["8on2", "32on4"])
@pytest.mark.parametrize("side", ["q", "k"])
def test_the_kernels_are_the_plain_chain(side, heads, kv, d, positions):
    """Forward values, ``dx`` and ``dscale``: q (every head its own) and
    k (a head read by its whole group, whose cotangents the backward
    kernel sums), positions shared by the batch and a row's own, over a
    length that is no multiple of the tile."""
    n, repeat = (heads, 1) if side == "q" else (kv, heads // kv)
    x, scale, pos, ct = operands(40, n, d, repeat, positions == "per_row")
    want = values_and_gradients(plain, x, scale, pos, repeat, jnp.float32,
                                ct)
    got = values_and_gradients(
        lambda *a: by_kernel(*a, block_s=16), x, scale, pos, repeat,
        jnp.float32, ct)
    assert got[0].shape == (B, heads, 40, d)
    for g, w in zip(got, want):
        close(g, w)


@pytest.mark.parametrize("s", [16, 24, 100, 128])
def test_the_derived_tiles_cover_any_length_from_16(s):
    """Whole tiles, a tail of 8 rows and of 4: the rows past a tail are
    in no sum."""
    x, scale, pos, ct = operands(s, 4, 128, 2, True, seed=s)
    want = values_and_gradients(plain, x, scale, pos, 2, jnp.float32, ct)
    got = values_and_gradients(by_kernel, x, scale, pos, 2, jnp.float32, ct)
    for g, w in zip(got, want):
        close(g, w)


@pytest.mark.parametrize("repeat", [1, 4])
def test_bf16_is_rounded_once_from_float32(repeat):
    """The output differs from the plain chain's by a unit in the last
    place of bf16 at most (both round one float32 value); the gradients
    of bf16 cotangents are float32 sums on both sides."""
    x, scale, pos, ct = operands(64, 2, 128, repeat, True, seed=3)
    ct = ct.astype(jnp.bfloat16).astype(jnp.float32)
    want = values_and_gradients(plain, x, scale, pos, repeat, jnp.bfloat16,
                                ct)
    got = values_and_gradients(by_kernel, x, scale, pos, repeat,
                               jnp.bfloat16, ct)
    assert got[0].dtype == jnp.bfloat16
    close(got[0], want[0], 2 ** -7)
    close(got[1], want[1])
    close(got[2], want[2])


@pytest.mark.parametrize("kernel_name,s,heads,d,repeat,dtype,want", [
    ("fwd", 8192, 32, 128, 1, "bfloat16", (1024, 4)),
    ("bwd", 8192, 32, 128, 1, "bfloat16", (512, 4)),
    ("bwd", 8192, 4, 128, 8, "bfloat16", (256, 4)),
    ("fwd", 40, 6, 128, 1, "float32", (32, 2)),
    ("fwd", 8, 4, 128, 1, "float32", (0, 0)),
])
def test_tiles_from_the_shapes(kernel_name, s, heads, d, repeat, dtype,
                               want):
    got = kernel.tiles(kernel_name, s, heads, d, repeat, dtype)
    assert got == want
    if got[0]:
        assert kernel.vmem_bytes(kernel_name, *got, d, repeat, dtype) \
            <= kernel.VMEM_BUDGET


@pytest.mark.parametrize("s,heads,d,repeat,dtype,want", [
    (8192, 32, 128, 1, "bfloat16", True), (8192, 4, 128, 8, "bfloat16", True),
    (64, 2, 256, 1, "float32", True), (8192, 32, 64, 1, "bfloat16", False),
    (8192, 32, 192, 1, "bfloat16", False), (8, 4, 128, 1, "float32", False),
    (64, 4, 128, 1, "float16", False)])
def test_takes_kernel_from_the_shapes(s, heads, d, repeat, dtype, want):
    assert kernel.takes_kernel(s, heads, d, repeat, dtype) is want


# ----------------------------------------------------------------------
# the attention op
# ----------------------------------------------------------------------
E, HEADS, KV, D, SEQ = 64, 4, 2, 128, 48
ATTN = {"embed_dim": E, "num_heads": HEADS, "num_kv_heads": KV,
        "kdim": HEADS * D, "vdim": HEADS * D, "bias": False, "causal": True,
        "rope": True, "rope_theta": THETA, "qk_norm": True,
        "qk_norm_eps": EPS}
INDEXER = {"indexer_heads": 2, "indexer_head_dim": 8, "indexer_topk": 12,
           "indexer_q_chunk": 16}


def attn_weights(d=D, seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)
    return {"wq": w(E, HEADS, d), "wk": w(E, KV, d), "wv": w(E, KV, d),
            "wo": w(HEADS, d, E),
            "q_norm": jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32),
            "k_norm": jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32),
            "wq_idx": 4 * w(E, 2, 8), "wk_idx": 4 * w(E, 8),
            "w_idx": 4 * w(E, 2)}


def attn_ctx(impl="flash", **fields):
    cfg = FFConfig()
    cfg.use_bf16_compute = False
    ctx = EmitCtx(training=True, config=cfg)
    ctx.kernel_impls = {"attention": impl}
    for k, v in fields.items():
        setattr(ctx, k, v)
    return ctx


def attn_inputs(seq=SEQ):
    rng = np.random.default_rng(1)
    return (jnp.asarray(rng.normal(size=(B, seq, E)), jnp.float32),
            jnp.tile(jnp.arange(seq, dtype=jnp.int32), (B, 1)))


def takes(params, ctx, d=D):
    q = jnp.zeros((B, SEQ, HEADS, d))
    kv = jnp.zeros((B, SEQ, KV, d))
    return MultiHeadAttentionOp()._takes_norm_rope_kernel(
        params, ctx, "attn", q, kv, kv, 0.0, jnp.float32)


def mesh_of(size):
    return type("Mesh", (), {"size": size})()


@pytest.mark.parametrize("why,params,fields,d,want", [
    ("heads of 128 on the flash path", {}, {}, 128, True),
    ("with an indexer", INDEXER, {}, 128, True),
    ("a mesh of one device", {}, {"mesh": mesh_of(1)}, 128, True),
    ("heads of 64", {}, {}, 64, False),
    ("a key/value cache", {}, {"kv_mode": "prefill"}, 128, False),
    ("decoding", {}, {"kv_mode": "decode"}, 128, False),
    ("no q/k norm", {"qk_norm": False}, {}, 128, False),
    ("no rotary embedding", {"rope": False}, {}, 128, False),
    ("a mesh of several devices", {}, {"mesh": mesh_of(4)}, 128, False),
    ("inside a manual region", {}, {"local_shape": True}, 128, False),
    ("a sliding window", {"sliding_window": 8}, {}, 128, True),
    ("the XLA path forced", {}, {"impl": "xla"}, 128, False),
    ("ring forced", {}, {"impl": "ring"}, 128, False),
], ids=lambda v: v.replace(" ", "_").replace("/", "_")
    if isinstance(v, str) else "")
def test_who_takes_the_kernel(why, params, fields, d, want):
    ctx = attn_ctx(**fields)
    assert takes(dict(ATTN, **params), ctx, d) is want, why


def test_on_the_cpu_auto_takes_no_kernel():
    """Without a forced path the CPU platform runs XLA's attention and
    the plain chain with it."""
    ctx = attn_ctx()
    ctx.kernel_impls = None
    assert takes(ATTN, ctx) is False


def run_op(impl, indexer, x, pos, w):
    ctx = attn_ctx(impl)
    params = dict(ATTN, **(INDEXER if indexer else {}))
    (y,) = MultiHeadAttentionOp().emit(params, [x, x, x, pos], w, ctx,
                                       "attn")
    kl = sum(ctx.aux_losses)
    return jnp.sum(y * jnp.cos(y)) + kl, (y, ctx.counters)


@pytest.mark.parametrize("indexer", [False, True],
                         ids=["plain_layer", "with_indexer"])
def test_the_op_through_the_kernel_is_the_op_on_xla(indexer):
    """Output and every weight's gradient, the kernel path (flash
    forced: the chain fused) against XLA's attention and the plain
    chain; the counter says which ran."""
    x, pos = attn_inputs()
    w = attn_weights()
    if not indexer:
        w = {k: v for k, v in w.items() if "idx" not in k}
    out = {}
    for impl in ("xla", "flash"):
        out[impl] = jax.jit(jax.value_and_grad(
            lambda x, w, impl=impl: run_op(impl, indexer, x, pos, w),
            (0, 1), has_aux=True))(x, w)
    ((v1, (y1, c1)), (gx1, gw1)), ((v2, (y2, c2)), (gx2, gw2)) = \
        out["xla"], out["flash"]
    close(y2, y1)
    close(v2, v1)
    close(gx2, gx1, 2e-5)
    for k in gw1:
        close(gw2[k], gw1[k], 2e-5)
    assert "attn.norm_rope_kernel_layers" not in c1
    assert float(c2["attn.norm_rope_kernel_layers"]) == 1.0


@pytest.mark.parametrize("impl,want", [("flash", "kernel"), ("xla", "xla")])
def test_the_instant_names_the_path(impl, want):
    events.enable()
    try:
        events.clear()
        x, pos = attn_inputs()
        jax.eval_shape(lambda x, w: run_op(impl, True, x, pos, w)[0], x,
                       attn_weights())
        (note,) = [e for e in events.events() if e["name"] == "attn.qk_norm"]
    finally:
        events.disable()
        events.clear()
    assert note["attrs"]["impl"] == want
    assert note["attrs"]["head_dim"] == D and note["attrs"]["kv_heads"] == KV


# ----------------------------------------------------------------------
# a small model of cell 7's kind, heads of 128
# ----------------------------------------------------------------------
def small_keye():
    return dataclasses.replace(KeyeRankConfig.tiny(), head_dim=128)


def build(impl, remat):
    return rf.build(KeyeRankConfig, build_hybrid_conv_moe, remat,
                    model_cfg=small_keye(), batch=B, seq=SEQ, attention=impl,
                    devices=1)


#: what ``examples/tpu_validate_sparse_index_moe.py`` holds on the chip
PICKED = (("attn_1", "wq"), ("attn_1", "q_norm"), ("attn_1", "wk"),
          ("attn_1", "k_norm"), ("experts_2", "wg"), ("experts_2", "w_gate"),
          ("attn_1", "wq_idx"), ("attn_1", "wk_idx"), ("attn_1", "w_idx"),
          ("attn_3", "w_idx"))


@pytest.mark.parametrize("remat", ["none", "blocks"])
def test_a_small_model_through_the_kernel_is_the_model_on_xla(remat):
    """The step's loss with its four ``L_I`` and the gradients the chip
    validation holds (and the k side's), the kernel path against the
    forced XLA path, alone and inside rematerialised blocks (where the
    forward kernel runs twice and the backward reads the second run's
    ``x``)."""
    plain_ff, mc = build("xla", remat)
    kernel_ff, _ = build("flash", remat)
    batch = rf.data(mc, SEQ, batch=B)
    (l1, bm1), g1 = rf.step_and_gradients(plain_ff, plain_ff.params, batch)
    (l2, bm2), g2 = rf.step_and_gradients(kernel_ff, plain_ff.params, batch)
    key = COUNTER_PREFIX + "attn.norm_rope_kernel_layers"
    assert key not in bm1 and float(bm2[key]) == 4.0
    assert float(bm2[COUNTER_PREFIX + "dsa.kernel_layers"]) == 4.0
    close(l2, l1, 1e-6)
    close(bm2[COUNTER_PREFIX + "dsa.index_kl"],
          bm1[COUNTER_PREFIX + "dsa.index_kl"])
    for layer, name in PICKED:
        assert np.any(np.asarray(g1[layer][name])), (layer, name)
        close(g2[layer][name], g1[layer][name], 2e-5)
