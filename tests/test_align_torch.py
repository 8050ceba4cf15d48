"""Numerical alignment vs PyTorch (reference ``tests/align/``).

The reference runs each op in FlexFlow and in PyTorch (separate env) and
asserts allclose on saved tensors (``align_create_tensor_ff.py`` /
``align_test.py``); deterministic inputs via seeded gen_tensor
(``align_utils.py:14``). Here torch (CPU) is in-process: each case runs
one op through the full framework path (builder → compile → jitted
forward (+ gradients where weighted) ) and compares against the equivalent
torch module, including backward/weight-grad alignment the reference
checks for linear/conv.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402

ATOL = 2e-4
RTOL = 2e-4


def _gen(shape, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(np.float32)


def _t(a):
    """torch tensor from a framework array: ``get_weights``/device
    arrays are non-writable views, and ``torch.from_numpy`` warns on
    every tier-1 run — copy first."""
    return torch.from_numpy(np.array(a, copy=True))


def _forward(build, inputs):
    """Build a single-op model, return its jitted forward output."""
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.use_bf16_compute = False
    cfg.kernel_impls = "attention:xla"
    ff = FFModel(cfg)
    out = build(ff)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=out)
    fwd = ff.executor.make_forward()
    y = fwd(ff.params, ff.state, inputs)
    return ff, np.asarray(y)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("act,torch_fn", [
    ("relu", torch.relu),
    ("sigmoid", torch.sigmoid),
    ("tanh", torch.tanh),
    # jax.nn.gelu defaults to the tanh approximation
    ("gelu", lambda x: torch.nn.functional.gelu(x, approximate="tanh")),
])
def test_align_activations(act, torch_fn):
    x = _gen((4, 33), 0)
    ff = FFModel(FFConfig())
    t = ff.create_tensor((4, 33), name="x")
    out = getattr(ff, act)(t)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))
    ref = torch_fn(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_linear_fwd_bwd():
    x = _gen((8, 16), 1)
    ff, y = _forward(
        lambda ff: ff.dense(ff.create_tensor((8, 16), name="x"), 24),
        {"x": x})
    lname = ff.layers[0].name
    w = ff.get_weights(lname, "kernel")
    b = ff.get_weights(lname, "bias")

    tl = torch.nn.Linear(16, 24)
    with torch.no_grad():
        tl.weight.copy_(_t(w.T))
        tl.bias.copy_(_t(b))
    xt = torch.from_numpy(x).requires_grad_(True)
    yt = tl(xt)
    np.testing.assert_allclose(y, yt.detach().numpy(), atol=ATOL, rtol=RTOL)

    # gradient alignment: d/dparams sum(y^2)
    def loss_jax(params):
        ctx_out = ff.executor.make_forward()(params, ff.state, {"x": x})
        return jnp.sum(ctx_out ** 2)

    gj = jax.grad(loss_jax)(ff.params)[lname]
    yt.pow(2).sum().backward()
    np.testing.assert_allclose(np.asarray(gj["kernel"]),
                               tl.weight.grad.numpy().T,
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gj["bias"]),
                               tl.bias.grad.numpy(), atol=1e-3, rtol=1e-3)


def test_align_conv2d():
    x = _gen((2, 3, 16, 16), 2)
    ff, y = _forward(
        lambda ff: ff.conv2d(ff.create_tensor((2, 3, 16, 16), name="x"),
                             out_channels=8, kernel_h=3, kernel_w=3,
                             stride_h=1, stride_w=1, padding_h=1,
                             padding_w=1),
        {"x": x})
    lname = ff.layers[0].name
    w = ff.get_weights(lname, "kernel")
    b = ff.get_weights(lname, "bias")
    tc = torch.nn.Conv2d(3, 8, 3, padding=1)
    with torch.no_grad():
        tc.weight.copy_(_t(w))
        tc.bias.copy_(_t(b))
    ref = tc(torch.from_numpy(x)).detach().numpy()
    np.testing.assert_allclose(y, ref, atol=1e-3, rtol=1e-3)


def test_align_pool2d():
    x = _gen((2, 4, 8, 8), 3)
    ff = FFModel(FFConfig())
    t = ff.create_tensor((2, 4, 8, 8), name="x")
    ff.pool2d(t, kernel_h=2, kernel_w=2, stride_h=2, stride_w=2,
              padding_h=0, padding_w=0)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))
    ref = torch.nn.functional.max_pool2d(torch.from_numpy(x), 2).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_layernorm():
    x = _gen((4, 10, 32), 4)
    ff, y = _forward(
        lambda ff: ff.layer_norm(ff.create_tensor((4, 10, 32), name="x"),
                                 axes=[2]),
        {"x": x})
    ref = torch.nn.functional.layer_norm(torch.from_numpy(x), (32,)).numpy()
    np.testing.assert_allclose(y, ref, atol=1e-3, rtol=1e-3)


def test_align_batchnorm_inference_stats():
    x = _gen((8, 6, 5, 5), 5)
    ff = FFModel(FFConfig())
    t = ff.create_tensor((8, 6, 5, 5), name="x")
    ff.batch_norm(t, relu=False)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))
    bn = torch.nn.BatchNorm2d(6, eps=1e-5)
    bn.eval()
    ref = bn(torch.from_numpy(x)).detach().numpy()
    np.testing.assert_allclose(y, ref, atol=1e-3, rtol=1e-3)


def test_align_softmax():
    x = _gen((5, 17), 6)
    ff = FFModel(FFConfig())
    t = ff.create_tensor((5, 17), name="x")
    ff.softmax(t)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))
    ref = torch.softmax(torch.from_numpy(x), dim=-1).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_embedding():
    ids = np.random.default_rng(7).integers(0, 50, size=(4, 9))
    ff = FFModel(FFConfig())
    t = ff.create_tensor((4, 9), name="ids", dtype="int32")
    ff.embedding(t, num_entries=50, out_dim=12)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    lname = ff.layers[0].name
    y = np.asarray(ff.executor.make_forward()(
        ff.params, ff.state, {"ids": ids.astype(np.int32)}))
    w = ff.get_weights(lname, "kernel" if "kernel" in ff.params[lname]
                       else list(ff.params[lname])[0])
    emb = torch.nn.Embedding(50, 12)
    with torch.no_grad():
        emb.weight.copy_(_t(w))
    ref = emb(torch.from_numpy(ids)).detach().numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_multihead_attention():
    b, s, e, h = 2, 7, 16, 4
    x = _gen((b, s, e), 8, scale=0.5)
    cfg = FFConfig()
    cfg.use_bf16_compute = False
    cfg.kernel_impls = "attention:xla"
    ff = FFModel(cfg)
    t = ff.create_tensor((b, s, e), name="x")
    ff.multihead_attention(t, t, t, embed_dim=e, num_heads=h, bias=True)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    lname = ff.layers[0].name
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))

    mha = torch.nn.MultiheadAttention(e, h, batch_first=True, bias=True)
    p = ff.params[lname]
    d = e // h
    wq = np.asarray(p["wq"]).reshape(e, e)   # (e_in, h, d) -> (e_in, e)
    wk = np.asarray(p["wk"]).reshape(e, e)
    wv = np.asarray(p["wv"]).reshape(e, e)
    wo = np.asarray(p["wo"]).reshape(e, e)   # (h, d, e) -> (e, e)
    bq = np.asarray(p["bq"]).reshape(e)
    bk = np.asarray(p["bk"]).reshape(e)
    bv = np.asarray(p["bv"]).reshape(e)
    bo = np.asarray(p["bo"])
    with torch.no_grad():
        mha.in_proj_weight.copy_(_t(
            np.concatenate([wq.T, wk.T, wv.T], axis=0)))
        mha.in_proj_bias.copy_(_t(
            np.concatenate([bq, bk, bv])))
        mha.out_proj.weight.copy_(_t(wo.T))
        mha.out_proj.bias.copy_(_t(bo))
    xt = torch.from_numpy(x)
    ref, _ = mha(xt, xt, xt, need_weights=False)
    np.testing.assert_allclose(y, ref.detach().numpy(), atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("op,torch_fn", [
    ("add", torch.add), ("subtract", torch.sub), ("multiply", torch.mul),
    ("divide", torch.div), ("max", torch.maximum), ("min", torch.minimum),
])
def test_align_elementwise_binary(op, torch_fn):
    a = _gen((3, 8), 10)
    b = _gen((3, 8), 11) + 2.0   # offset avoids divide-by-near-zero
    ff = FFModel(FFConfig())
    ta = ff.create_tensor((3, 8), name="a")
    tb = ff.create_tensor((3, 8), name="b")
    getattr(ff, op)(ta, tb)
    ff.compile(SGDOptimizer(0.01), "identity", [])
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"a": a, "b": b}))
    ref = torch_fn(torch.from_numpy(a), torch.from_numpy(b)).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_concat_split_reshape_transpose():
    a = _gen((2, 3, 4), 12)
    b = _gen((2, 3, 4), 13)
    ff = FFModel(FFConfig())
    ta = ff.create_tensor((2, 3, 4), name="a")
    tb = ff.create_tensor((2, 3, 4), name="b")
    c = ff.concat([ta, tb], axis=1)          # (2, 6, 4)
    r = ff.reshape(c, (2, 24))
    tr = ff.transpose(r, (1, 0))             # (24, 2)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=tr)
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"a": a, "b": b}))
    ref = torch.cat([torch.from_numpy(a), torch.from_numpy(b)], dim=1) \
        .reshape(2, 24).T.numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_mse_loss_gradient():
    """Loss-level alignment: MSE grads through a dense layer match torch
    (reference align: loss scale 2/volume for MSE)."""
    x = _gen((6, 10), 14)
    label = _gen((6, 4), 15)
    cfg = FFConfig()
    cfg.use_bf16_compute = False
    ff = FFModel(cfg)
    t = ff.create_tensor((6, 10), name="x")
    ff.dense(t, 4, use_bias=False)
    ff.compile(SGDOptimizer(0.01), "mean_squared_error", [])
    lname = ff.layers[0].name
    w = ff.get_weights(lname)

    from flexflow_tpu.runtime import losses as L
    from flexflow_tpu.ffconst import LossType

    def loss_jax(params):
        y = ff.executor.make_forward()(params, ff.state, {"x": x})
        return L.compute_loss(LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
                              y, jnp.asarray(label))

    gj = np.asarray(jax.grad(loss_jax)(ff.params)[lname]["kernel"])

    wt = _t(w).requires_grad_(True)
    yt = torch.from_numpy(x) @ wt
    torch.nn.functional.mse_loss(yt, torch.from_numpy(label)).backward()
    np.testing.assert_allclose(gj, wt.grad.numpy(), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# op long tail (reference tests/align/test_all_operators.sh: 27 ops —
# cos sin exp flat getitem identity reducesum scalar_* view_embedding
# max min gather were the uncovered remainder)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op,torch_fn", [
    ("cos", torch.cos),
    ("sin", torch.sin),
    ("exp", torch.exp),
    ("identity", lambda x: x),
    ("rsqrt", torch.rsqrt),
])
def test_align_unary(op, torch_fn):
    x = _gen((4, 17), 20)
    if op == "rsqrt":
        x = np.abs(x) + 1.5   # positive domain
    y = _forward(lambda ff: getattr(ff, op)(
        ff.create_tensor((4, 17), name="x")), {"x": x})[1]
    ref = torch_fn(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("op,torch_fn", [
    ("scalar_add", lambda x: x + 1.5),
    ("scalar_sub", lambda x: x - 1.5),
    ("scalar_multiply", lambda x: x * 1.5),
    ("scalar_true_divide", lambda x: x / 1.5),
])
def test_align_scalar_ops(op, torch_fn):
    x = _gen((3, 9), 21)
    y = _forward(lambda ff: getattr(ff, op)(
        ff.create_tensor((3, 9), name="x"), 1.5), {"x": x})[1]
    np.testing.assert_allclose(y, torch_fn(torch.from_numpy(x)).numpy(),
                               atol=ATOL, rtol=RTOL)


def test_align_pow():
    x = np.abs(_gen((3, 9), 22)) + 0.5
    y = _forward(lambda ff: ff.pow(
        ff.create_tensor((3, 9), name="x"), 2.5), {"x": x})[1]
    np.testing.assert_allclose(
        y, torch.pow(torch.from_numpy(x), 2.5).numpy(),
        atol=ATOL, rtol=RTOL)


def test_align_flat():
    x = _gen((4, 3, 5, 2), 23)
    y = _forward(lambda ff: ff.flat(
        ff.create_tensor((4, 3, 5, 2), name="x")), {"x": x})[1]
    ref = torch.flatten(torch.from_numpy(x), start_dim=1).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_reduce_sum_and_mean():
    x = _gen((4, 6, 5), 24)
    y = _forward(lambda ff: ff.reduce_sum(
        ff.create_tensor((4, 6, 5), name="x"), axes=[1]), {"x": x})[1]
    np.testing.assert_allclose(
        y, torch.from_numpy(x).sum(dim=1).numpy(), atol=ATOL, rtol=RTOL)
    m = _forward(lambda ff: ff.mean(
        ff.create_tensor((4, 6, 5), name="x"), dims=[2]), {"x": x})[1]
    np.testing.assert_allclose(
        m, torch.from_numpy(x).mean(dim=2).numpy(), atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("op,torch_fn", [
    ("max", torch.maximum),
    ("min", torch.minimum),
])
def test_align_binary_max_min(op, torch_fn):
    a = _gen((5, 7), 25)
    b = _gen((5, 7), 26)

    def build(ff):
        ta = ff.create_tensor((5, 7), name="a")
        tb = ff.create_tensor((5, 7), name="b")
        return getattr(ff, op)(ta, tb)

    y = _forward(build, {"a": a, "b": b})[1]
    ref = torch_fn(torch.from_numpy(a), torch.from_numpy(b)).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_gather():
    """torch.gather semantics along dim=1."""
    x = _gen((4, 6), 27)
    idx = np.random.default_rng(28).integers(
        0, 6, size=(4, 3)).astype(np.int32)

    def build(ff):
        tx = ff.create_tensor((4, 6), name="x")
        ti = ff.create_tensor((4, 3), name="i", dtype="int32")
        return ff.gather(tx, ti, dim=1)

    y = _forward(build, {"x": x, "i": idx})[1]
    ref = torch.gather(torch.from_numpy(x), 1,
                       torch.from_numpy(idx.astype(np.int64))).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_view_embedding():
    """The reference's view_embedding case: ids reshaped through a view
    before the table lookup."""
    vocab, dim = 30, 8
    ids = np.random.default_rng(29).integers(
        0, vocab, size=(4, 5)).astype(np.int32)
    table = _gen((vocab, dim), 30)

    def build(ff):
        ti = ff.create_tensor((4, 5), name="ids", dtype="int32")
        flat = ff.reshape(ti, (20,))
        e = ff.embedding(flat, vocab, dim)
        return ff.reshape(e, (4, 5 * dim))

    ff, y = _forward(build, {"ids": ids})
    emb_layer = [l for l in ff.layers
                 if l.op_type.name == "OP_EMBEDDING"][0]
    ff.set_weights(emb_layer.name, "kernel", table)
    y = np.asarray(ff.executor.make_forward()(
        ff.params, ff.state, {"ids": ids}))
    ref = torch.nn.functional.embedding(
        torch.from_numpy(ids.astype(np.int64)).reshape(-1),
        torch.from_numpy(table)).reshape(4, 5 * dim).numpy()
    np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)


def test_align_getitem_slice():
    """The reference's getitem case: static slicing via split."""
    x = _gen((4, 10), 31)

    def build(ff):
        tx = ff.create_tensor((4, 10), name="x")
        parts = ff.split(tx, [3, 7], axis=1)
        return parts[0]

    y = _forward(build, {"x": x})[1]
    np.testing.assert_allclose(y, x[:, :3], atol=ATOL, rtol=RTOL)
