"""The causal taps, the SiLU, the unit length and the turn to heads-first
by the Pallas kernels (``kernels/delta_mix.py``, interpret mode on the
CPU) against the plain chain they stand in for (``short_conv``,
``jax.nn.silu`` and ``_unit`` as ``GatedDeltaRuleOp.projections`` writes
them), then the layer of both forms of the decay down both paths.

Both sides compute in float32; they differ in the order of a head's sum
of squares and of the taps' sums over the tokens. Values and gradients
are held to 1e-5 of the largest entry (they read 1e-7 to 5e-7).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.kernels import delta_mix as kernel
from flexflow_tpu.obs import events
from flexflow_tpu.ops import recurrent_ops
from flexflow_tpu.ops.nn_ops import short_conv
from flexflow_tpu.ops.recurrent_ops import (NORM_EPS, GatedDeltaRuleOp,
                                            _unit, mix_impl)
from flexflow_tpu.ops.registry import EmitCtx, checkpointed

TOL = 1e-5
B, D = 2, 128


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def plain(p, taps, unit, scale):
    """The chain as ``projections`` runs it, from the product
    tokens-first."""
    z = jax.nn.silu(jax.vmap(short_conv, (1, 0), 1)(
        jnp.moveaxis(p, 2, 1), taps))
    return _unit(z) * scale if unit else z


def by_kernel(p, taps, unit, scale, **tiles):
    return kernel.delta_mix(p, taps, unit=unit, scale=scale, eps=NORM_EPS,
                            **tiles)


def operands(t, heads, k, seed=0, d=D):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(2.0 * rng.standard_normal((B, t, heads, d)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((heads, d, k)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((B, heads, t, d)), jnp.float32)
    return p, taps, ct


def values_and_gradients(fn, p, taps, ct, unit, scale):
    def loss(p, taps):
        y = fn(p, taps, unit, scale)
        return jnp.sum(y * ct), y
    (_, y), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
        p, taps)
    return (y,) + grads


# tiles of 16 tokens: one tile, three whole tiles (the halo across a
# tile's edge, the rows before a tile for the values and the rows after
# it for the gradients) and two tiles and a half
@pytest.mark.parametrize("tokens", [16, 48, 40])
@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("heads", [1, 2, 4])
@pytest.mark.parametrize("unit,scale", [(True, D ** -0.5), (True, 1.0),
                                        (False, 1.0)], ids=["q", "k", "v"])
def test_the_kernels_are_the_plain_chain(unit, scale, heads, taps, tokens):
    """Forward values, ``dp`` and ``dtaps``: q (unit length and its
    scale), k (unit length) and v (no norm)."""
    p, w, ct = operands(tokens, heads, taps, seed=tokens + taps)
    want = values_and_gradients(plain, p, w, ct, unit, scale)
    got = values_and_gradients(
        lambda *a: by_kernel(*a, block_t=16), p, w, ct, unit, scale)
    assert got[0].shape == (B, heads, tokens, D)
    for g, v in zip(got, want):
        close(g, v)


@pytest.mark.parametrize("tokens", [8, 13, 24, 100, 128])
def test_the_derived_tiles_cover_any_length_from_8(tokens):
    """Whole tiles, a tail of 8 rows, of 5 and of 4: the rows past a
    tail are in no sum and reach no row before them."""
    p, w, ct = operands(tokens, 2, 4, seed=tokens)
    want = values_and_gradients(plain, p, w, ct, True, 0.5)
    got = values_and_gradients(by_kernel, p, w, ct, True, 0.5)
    for g, v in zip(got, want):
        close(g, v)


@pytest.mark.parametrize("what", ["out", "dp"])
def test_left_of_position_0_there_are_zeros(what):
    """The first K - 1 positions read nothing before the sequence (not
    the rows the halo's block holds at the first tile), and the batch's
    second sequence nothing of the first."""
    p, w, ct = operands(32, 2, 4, seed=5)
    got = values_and_gradients(
        lambda *a: by_kernel(*a, block_t=16), p, w, ct, False, 1.0)
    want = values_and_gradients(plain, p, w, ct, False, 1.0)
    i = ("out", "dp").index(what)
    at = (slice(None), slice(None), slice(0, 3)) if what == "out" \
        else (slice(None), slice(0, 3))
    close(got[i][at], want[i][at])
    # the first position is its own tap alone
    if what == "out":
        c = p[:, 0] * w[None, :, :, -1]
        close(got[0][:, :, 0], c * jax.nn.sigmoid(c))


@pytest.mark.parametrize("t", [0, 15, 16, 31])
def test_an_output_does_not_move_when_later_inputs_change(t):
    """Causal: positions up to ``t`` read nothing after ``t``, across a
    tile's edge too."""
    p, w, _ = operands(48, 2, 4, seed=7)
    later = p.at[:, t + 1:].set(-p[:, t + 1:] + 1.0)
    a = by_kernel(p, w, True, 1.0, block_t=16)
    b = by_kernel(later, w, True, 1.0, block_t=16)
    np.testing.assert_array_equal(np.asarray(a[:, :, :t + 1]),
                                  np.asarray(b[:, :, :t + 1]))
    assert np.any(np.asarray(a[:, :, t + 1:]) != np.asarray(b[:, :, t + 1:]))


@pytest.mark.parametrize("kernel_name,tokens,heads,want", [
    ("fwd", 4096, 32, (1024, 4)), ("bwd", 4096, 32, (512, 4)),
    ("fwd", 8192, 16, (1024, 4)), ("bwd", 8192, 32, (512, 4)),
    ("fwd", 40, 6, (32, 2)), ("bwd", 8, 1, (8, 1)), ("fwd", 7, 4, (0, 0))])
def test_tiles_from_the_shapes(kernel_name, tokens, heads, want):
    got = kernel.tiles(kernel_name, tokens, heads, D, 4)
    assert got == want
    if got[0]:
        assert kernel.vmem_bytes(kernel_name, *got, D, 4) \
            <= kernel.VMEM_BUDGET


@pytest.mark.parametrize("d,taps,tokens,dtype,want", [
    (128, 4, 4096, "float32", True), (128, 4, 8192, "float32", True),
    (256, 2, 8, "float32", True), (128, 9, 64, "float32", True),
    (64, 4, 4096, "float32", False), (192, 4, 4096, "float32", False),
    (128, 1, 4096, "float32", False), (128, 10, 4096, "float32", False),
    (128, 4, 7, "float32", False), (128, 4, 4096, "bfloat16", False),
    (16, 4, 32, "float32", False)])
def test_takes_kernel_from_the_shapes(d, taps, tokens, dtype, want):
    assert kernel.takes_kernel(d, taps, tokens, dtype) is want


def test_the_residuals_are_the_two_operands():
    """What the ``custom_vjp`` keeps for the backward pass: the
    projection's product and the taps, nothing of ``c``, ``z`` or the
    norm."""
    p, w, _ = operands(32, 2, 4)
    _, pullback = jax.vjp(lambda p, w: by_kernel(p, w, True, 0.5), p, w)
    sizes = sorted(int(np.prod(x.shape)) for x in jax.tree.leaves(pullback)
                   if hasattr(x, "shape"))
    assert sizes == sorted([p.size, w.size])


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_the_kernels_under_a_mesh_are_the_unsharded_ones(by):
    """Two sequences, or two heads, one a device: the call runs under
    ``shard_map`` over the batch and head entries of the spec, and the
    taps' gradient is summed over the batch's devices."""
    from jax.sharding import Mesh, PartitionSpec as P
    p, w, ct = operands(24, 2, 4, seed=11)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")
    want = values_and_gradients(by_kernel, p, w, ct, True, 0.5)
    got = values_and_gradients(
        lambda *a: by_kernel(*a, mesh=mesh, spec=spec), p, w, ct, True, 0.5)
    for g, v in zip(got, want):
        close(g, v, 1e-6)


def mesh_of(**sizes):
    size = int(np.prod(list(sizes.values())))
    return type("Mesh", (), {"size": size, "shape": sizes})()


@pytest.mark.parametrize("why,d,key_heads,heads,tokens,shard,want", [
    ("cell 5's layer", 128, 32, 32, 4096, (None, None), "kernel"),
    ("cell 10's layer", 128, 16, 32, 8192, (None, None), "kernel"),
    ("heads of 64", 64, 4, 4, 64, (None, None), "plain"),
    ("seven tokens", 128, 2, 2, 7, (None, None), "plain"),
    ("a mesh of one device", 128, 2, 4, 64, (mesh_of(x=1), ("x", None)),
     "kernel"),
    ("four devices by batch", 128, 2, 4, 64, (mesh_of(x=4), ("x", None)),
     "kernel"),
    ("whole heads of q and k a device", 128, 2, 4, 64,
     (mesh_of(x=2), (None, "x")), "kernel"),
    ("half a head of q and k a device", 128, 2, 4, 64,
     (mesh_of(x=4), (None, "x")), "plain"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) and " " in v
    else "")
def test_who_takes_the_kernels(why, d, key_heads, heads, tokens, shard,
                               want):
    weights = {"conv_q": jnp.zeros((key_heads, d, 4)),
               "conv_k": jnp.zeros((key_heads, d, 4)),
               "conv_v": jnp.zeros((heads, d, 4))}
    assert mix_impl(weights, tokens, *shard) == want, why


# ----------------------------------------------------------------------
# the layer, both forms of the decay
# ----------------------------------------------------------------------
FORMS = {
    "channel": ({"num_heads": 2, "head_dim": D, "taps": 4, "eps": 1e-5,
                 "chunk": 16}, "kda"),
    "head": ({"num_heads": 4, "num_key_heads": 2, "head_dim": D, "taps": 4,
              "eps": 1e-6, "chunk": 16, "decay": "head"}, "gdn"),
}
E, SEQ = 24, 40


def layer(form, in_block):
    """The layer's loss as a function of its input and weights, alone
    or as a rematerialised block runs it."""
    params, _ = FORMS[form]
    op = GatedDeltaRuleOp()
    rng = np.random.default_rng(1)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * 0.4)
         for s in op.weights(params, [(B, SEQ, E)], [DataType.DT_FLOAT])}
    u = jnp.asarray(rng.normal(size=(B, SEQ, E)).astype(np.float32))
    cfg = FFConfig()
    cfg.use_bf16_compute = False

    def run(u, w):
        ctx = EmitCtx(training=True, config=cfg)
        return op.emit(params, [u], w, ctx, "linear_3")[0]

    def loss(u, w):
        y = checkpointed(run, site="block")(u, w) if in_block else run(u, w)
        return jnp.sum(y * jnp.cos(y)), y
    return loss, u, w


@pytest.mark.parametrize("in_block", [False, True],
                         ids=["alone", "in_a_block"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_layer_through_the_kernels_is_the_layer_on_xla(
        monkeypatch, form, in_block):
    """Output and every weight's gradient, q, k and v through the
    kernels against ``short_conv``, ``silu`` and ``_unit`` (the
    predicate stubbed: the recurrence takes the same path on both
    sides)."""
    loss, u, w = layer(form, in_block)
    out = {}
    for path in ("kernel", "plain"):
        if path == "plain":
            monkeypatch.setattr(recurrent_ops.mix_kernels, "takes_kernel",
                                lambda *a: False)
        out[path] = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            u, w)
    ((v1, y1), (gu1, gw1)), ((v2, y2), (gu2, gw2)) = \
        out["plain"], out["kernel"]
    close(y2, y1)
    close(v2, v1)
    close(gu2, gu1, 2e-5)
    assert sorted(gw1) == sorted(gw2)
    for name in gw1:
        assert np.any(np.asarray(gw1[name])), name
        close(gw2[name], gw1[name], 2e-5)


@pytest.mark.parametrize("want", ["kernel", "plain"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_instants_name_the_path(monkeypatch, form, want):
    """``mix`` on the layer's ``kda.scan`` / ``gdn.scan`` instant, and
    one ``kda.kernel`` / ``gdn.kernel`` instant a traced call of a
    branch, forward and backward, with what it ran on."""
    params, scope = FORMS[form]
    loss, u, w = layer(form, False)
    if want == "plain":
        monkeypatch.setattr(recurrent_ops.mix_kernels, "takes_kernel",
                            lambda *a: False)
    events.enable()
    events.clear()
    try:
        jax.eval_shape(jax.grad(lambda u, w: loss(u, w)[0], argnums=1), u, w)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    (scan,) = [e["attrs"] for e in seen if e["name"] == scope + ".scan"]
    assert scan["mix"] == want and scan["impl"] == "kernel"
    said = [e["attrs"] for e in seen if e["name"] == scope + ".kernel"
            and e["attrs"]["kernel"].startswith("mix_")]
    if want == "plain":
        assert not said
        return
    heads = {"wq": params.get("num_key_heads") or params["num_heads"],
             "wv": params["num_heads"]}
    heads["wk"] = heads["wq"]
    assert {(a["kernel"], a["part"]) for a in said} == {
        (k, part) for k in ("mix_fwd", "mix_bwd") for part in heads}
    for a in said:
        assert a["layer"] == "linear_3" and a["tokens"] == B * SEQ
        assert a["heads"] == heads[a["part"]] and a["tile"] == 32
        assert a["grid_steps"] == B * 2
        assert 0 < a["vmem_bytes"] <= kernel.VMEM_BUDGET
