"""The residual streams' mixes by the Pallas kernels
(``kernels/hyper_connection.py``, interpret mode on the CPU) against the
plain functions of ``ops/hyper_ops.py`` and their autodiff, at four
streams of 128 and 256 channels (the kernels take channels in whole
lanes) and a few dozen tokens: a kernel traced in interpret mode is some
hundred XLA ops a grid step.

The plain path is the one ``tests/test_mhc_latent_moe.py`` holds to the
reference of the literal iterations; here the two paths of one operator
are held to each other, outputs to 1e-6 and gradients to 1e-5 of the
largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.kernels import hyper_connection as kernel
from flexflow_tpu.obs import events
from flexflow_tpu.ops import hyper_ops
from flexflow_tpu.ops.hyper_ops import HyperConnectionOp
from flexflow_tpu.ops.registry import EmitCtx

N = 4
PRE = dict(stage="pre", iters=20, eps=1e-6, norm_eps=1e-6,
           clamp=[-30.0, 30.0])
POST = {"stage": "post"}
WEIGHTS = ("phi", "b_pre", "b_post", "b_res", "alpha")
# (batch, positions, channels): whole tiles; 42 tokens padded to 64; two
# grid steps of the backward kernels' tile at 256 channels' budget
CASES = [(2, 32, 128), (2, 21, 128), (1, 40, 256)]


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def ctx():
    cfg = FFConfig()
    cfg.use_bf16_compute = False
    return EmitCtx(training=True, config=cfg)


def weights(c, seed=3):
    """The draw of ``tests/test_mhc_latent_moe.py::hc_weights``, with
    three different scalars so that each one's gradient is its own."""
    rng = np.random.default_rng(seed)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * (0.1 if s.name == "phi" else 0.5))
         for s in HyperConnectionOp().weights(PRE, [(1, 8, N, c)],
                                              [DataType.DT_FLOAT])}
    w["alpha"] = jnp.asarray([1.0, 0.8, 1.2], jnp.float32)
    w["b_res"] = w["b_res"] + 2 * jnp.eye(N)
    return w


def streams(b, s, c, seed=5):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(b, s, N, c)).astype(np.float32))


def sub_layer(x, w, context=None, params=PRE):
    """Both nodes around a stand-in sub-layer: ``u``, the maps, the new
    streams."""
    op, context = HyperConnectionOp(), context or ctx()
    u, maps, xs = op.emit(params, [x], w, context, "res_pre")
    out, = op.emit(POST, [xs, 1.3 * jnp.tanh(u), maps], {}, context, "res")
    return u, maps, out


@pytest.fixture
def plain(monkeypatch):
    """The same operator with the kernels refused, whatever the shapes."""
    def run(fn, *args):
        with monkeypatch.context() as m:
            m.setattr(kernel, "takes_kernel", lambda *a: False)
            return fn(*args)
    return run


@pytest.fixture(scope="module")
def both():
    """Outputs and gradients of a case down both paths, computed once."""
    kept = {}

    def get(case, plain):
        if case not in kept:
            b, s, c = case
            x, w = streams(b, s, c), weights(c)
            cot = streams(b, s, c, seed=6)

            def loss(x, w):
                u, maps, out = sub_layer(x, w)
                return jnp.sum(out * cot) + jnp.sum(u * u), (u, maps, out)

            def run():
                # (a function of its own each time: jit keeps traces by
                # function, and the path is chosen while tracing)
                return jax.jit(jax.value_and_grad(
                    lambda x, w: loss(x, w), (0, 1), has_aux=True))(x, w)
            assert kernel.takes_kernel(N, c, b * s)
            kept[case] = run(), plain(run)
            # the ``post`` node alone, both paths on the plain ``pre``
            # node's outputs (through both nodes the second kernel also
            # carries the first one's rounding)
            u, maps, _ = kept[case][1][0][1]

            def post():
                return jax.jit(lambda *a: HyperConnectionOp().emit(
                    POST, list(a), {}, ctx(), "res")[0])(
                        x, 1.3 * jnp.tanh(u), maps)
            kept[case] += (post(), plain(post))
        return kept[case]
    return get


@pytest.mark.parametrize("output", range(3), ids=["u", "maps", "streams"])
@pytest.mark.parametrize("case", CASES, ids=str)
def test_an_output_by_the_kernels_is_the_plain_codes(both, plain, case,
                                                     output):
    got, want, post, plain_post = both(case, plain)
    if output == 2:
        close(post, plain_post, 1e-6)
        close(got[0][1][2], post, 5e-6)
    else:
        close(got[0][1][output], want[0][1][output], 1e-6)


@pytest.mark.parametrize("name", ("streams",) + WEIGHTS)
@pytest.mark.parametrize("case", CASES, ids=str)
def test_a_gradient_through_the_kernels_is_autodiffs(both, plain, case,
                                                     name):
    """``jax.grad`` of the plain code, the Sinkhorn scan's transpose and
    all: the streams' (the one ``dX`` the ``pre`` backward writes, what
    ``post`` left for it included) and each of the five weights'."""
    (_, (gx, gw)), (_, (wx, ww)) = both(case, plain)[:2]
    if name == "streams":
        close(gx, wx, 1e-5)
    else:
        close(gw[name], ww[name], 1e-5)


def test_tokens_are_padded_to_whole_grid_steps():
    """42 tokens: a forward tile of 64, padded; the kernels' calls alone
    on the padded rows read what the unpadded rows' plain code reads, and
    a padded token adds nothing to ``dphi``."""
    b, s, c = 2, 21, 128
    x, w = streams(b, s, c), weights(c)
    phi_t, gate = kernel.pre_operands(w["phi"], w["alpha"][0], w["b_pre"])
    assert phi_t.shape == (32, N * c) and gate.shape == (2, 32)
    assert kernel.tile_tokens("pre_fwd", N, c, b * s) == 64

    def loss(x, phi_t):
        u, stats, xs = kernel.read_streams(x, phi_t, gate, 1e-6)
        return jnp.sum(u ** 2) + jnp.sum(stats ** 2) + jnp.sum(xs)

    def plain_loss(x, phi_t):
        k = N * (N + 2)
        t = hyper_ops.stream_products(x, phi_t[:k].T, 1e-6)
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=(-2, -1)) + 1e-6)
        hpre = jax.nn.sigmoid(w["alpha"][0] * t[:N]
                              + w["b_pre"][:, None, None])
        u = hyper_ops.read_streams(x, jnp.moveaxis(hpre, 0, -1))
        # the statistics are the products BEFORE the norm, and the norm
        return jnp.sum(u ** 2) + jnp.sum((t / r) ** 2) + jnp.sum(r ** 2) \
            + jnp.sum(x)

    got = jax.value_and_grad(loss, (0, 1))(x, phi_t)
    want = jax.value_and_grad(plain_loss, (0, 1))(x, phi_t)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b_, 1e-5)


@pytest.mark.parametrize("n,c,tokens,takes", [
    (4, 3584, 4096, True),      # the benchmark's sixth cell
    (4, 128, 42, True), (2, 256, 8, True), (4, 7168, 1 << 20, True),
    (4, 64, 4096, False),       # channels in part of a lane
    (4, 24, 80, False), (4, 200, 64, False),
    (4, 1 << 20, 64, False),    # eight tokens' streams are over the budget
    (4, 128, 0, False)])
def test_the_shapes_decide_which_path_runs(n, c, tokens, takes):
    assert kernel.takes_kernel(n, c, tokens) is takes
    if not takes:
        return
    tiles = [kernel.tile_tokens(k, n, c, tokens) for k in kernel.KERNELS]
    for k, t in zip(kernel.KERNELS, tiles):
        assert t in kernel.TILES
        assert kernel.vmem_bytes(k, n, c, t) <= kernel.VMEM_BUDGET \
            < kernel.VMEM_LIMIT
        # the next larger tile is over the budget, or covers no token
        if t < kernel.TILES[0]:
            assert 2 * t >= 2 * tokens or kernel.vmem_bytes(
                k, n, c, 2 * t) > kernel.VMEM_BUDGET
    assert max(tiles) % min(tiles) == 0


def test_the_cells_tiles():
    """1 x 4096 tokens of 4 x 3584: the tiles the sandbox's compile for
    a described v5e took (``tests/test_tpu_aot_compile.py``)."""
    assert [kernel.tile_tokens(k, 4, 3584, 4096) for k in kernel.KERNELS] \
        == [256, 128, 128, 128]
    assert kernel.stats_width(4) == 32


@pytest.mark.parametrize("c,impl,calls", [
    (128, "kernel", ["post_bwd", "post_fwd", "pre_bwd", "pre_fwd"]),
    (24, "plain", [])])
def test_the_layer_announces_the_path_it_took(c, impl, calls):
    """``impl`` on the ``pre`` node's ``mhc.maps`` instant, and one
    ``mhc.kernel`` instant a kernel call traced under ``jax.grad``."""
    x, w = streams(2, 20, c), weights(c)
    events.enable()
    events.clear()
    try:
        jax.grad(lambda x, w: jnp.sum(sub_layer(x, w)[2] ** 2))(x, w)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    (maps,) = [e["attrs"] for e in seen if e["name"] == "mhc.maps"]
    assert maps["impl"] == impl and maps["layer"] == "res_pre"
    noted = [e["attrs"] for e in seen if e["name"] == "mhc.kernel"]
    assert sorted(k["kernel"] for k in noted) == calls
    for k in noted:
        assert k["layer"] == ("res_pre" if k["kernel"].startswith("pre")
                              else "res")
        # 40 tokens: one grid step of 64
        assert (k["tile"], k["tokens"], k["grid_steps"]) == (64, 64, 1)
        assert 0 < k["vmem_bytes"] <= kernel.VMEM_BUDGET


def test_the_residuals_are_the_streams_and_a_tokens_statistics():
    """What the two ``custom_vjp`` functions keep for the backward pass:
    their inputs and, of ``pre``, ``KP`` floats a token; no map, no
    product and no second copy of the streams."""
    tokens, c = 64, 128
    kp = kernel.stats_width(N)
    x = kernel._stream_major(streams(1, tokens, c))
    assert x.shape == (N, tokens, c)
    phi_t, gate = jnp.ones((kp, N * c)) * 0.01, jnp.ones((2, kp))
    static = (N, 1e-6, (64, 64), None, True)
    (u, stats, out), res = kernel._pre_fwd_rule(x, phi_t, gate, *static)
    assert out is x and res[0] is x and res[1] is phi_t and res[2] is gate
    assert res[3] is stats and stats.shape == (tokens, kp)
    assert len(res) == 4 and u.shape == (tokens, c)
    y, maps = jnp.ones((tokens, c)), jnp.ones((tokens, N + N * N))
    new, res = kernel._post_fwd_rule(x, y, maps, N, (64, 64), None, True)
    assert new.shape == x.shape
    assert [r is a for r, a in zip(res, (x, y, maps))] == [True] * 3


def test_the_streams_have_one_consumer():
    """The ``post`` node takes its streams from the ``pre`` node: the
    cotangent it leaves for them reaches the ``pre`` backward kernel as
    an operand (the jaxpr of the gradient has no add of two stream-sized
    arrays), where two consumers of ``X`` would meet in one."""
    b, s, c = 1, 64, 128
    x, w = streams(b, s, c), weights(c)

    def adds(fn):
        text = str(jax.make_jaxpr(jax.grad(fn))(x))
        big = (f"f32[{b},{s},{N},{c}]", f"f32[{N},{b * s},{c}]",
               f"f32[{N},{b},{s},{c}]")
        return [l for l in text.splitlines()
                if " add_any " in l and any(t in l for t in big)]

    assert not adds(lambda x: jnp.sum(sub_layer(x, w)[2] ** 2))

    def two_consumers(x):
        op, c_ = HyperConnectionOp(), ctx()
        u, maps, _ = op.emit(PRE, [x], w, c_, "res_pre")
        return jnp.sum(op.emit(POST, [x, jnp.tanh(u), maps], {}, c_,
                               "res")[0] ** 2)
    assert adds(two_consumers)


@pytest.mark.parametrize("by", ["batch", "sequence"])
def test_the_kernels_under_a_mesh_are_the_unsharded_ones(by):
    """Two sequences, or two spans of positions, one a device: the calls
    run under ``shard_map`` over the batch and sequence entries of the
    spec, and ``dphi`` is summed over the shards."""
    from jax.sharding import Mesh, PartitionSpec as P
    b, s, c = 2, 32, 128
    x, w = streams(b, s, c), weights(c)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")

    def loss(mesh, spec, x, w):
        context = ctx()
        if mesh is not None:
            context.mesh = mesh
            context.op_sharding = type("S", (), {"outputs": [spec]})()
        u, _, out = sub_layer(x, w, context)
        return jnp.sum(out ** 2) + jnp.sum(u ** 2)

    want = jax.jit(jax.value_and_grad(
        lambda x, w: loss(None, None, x, w), (0, 1)))(x, w)
    got = jax.jit(jax.value_and_grad(
        lambda x, w: loss(mesh, spec, x, w), (0, 1)))(x, w)
    text = jax.jit(lambda x, w: loss(mesh, spec, x, w)).lower(x, w).as_text()
    assert "shard_map" in text or "manual" in text
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b_, 2e-5)


def test_the_cost_row_follows_the_path():
    """``pre`` reads the streams once where the kernels run and three
    times where they do not; its third output is its input and moves
    nothing."""
    op = HyperConnectionOp()
    for c, reads in ((128, 1), (24, 3)):
        shape = (2, 40, N, c)
        outs = [s for s, _ in op.infer(PRE, [shape], [DataType.DT_FLOAT])]
        assert outs == [(2, 40, c), (2, 40, N + N * N), shape]
        assert op.bytes_moved(PRE, [shape], outs) == 4 * 80 * (
            reads * N * c + c + N + N * N)
