"""The gated delta rule's in-chunk terms by the Pallas kernels
(``kernels/gated_delta_rule.py``, interpret mode on the CPU) against the
plain ``_chunk_terms`` and its autodiff, and the recurrence through them
against the token-by-token reference in float64-free float32 at
``highest``, at the head size the kernels take (128) and chunks of 64.

Sizes are kept to a few chunks of one or two heads: a kernel traced in
interpret mode is some hundred XLA ops a grid step.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import delta_rule_scan as drs
from benchmarks.harness import cells
from flexflow_tpu import FFConfig
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.kernels import gated_delta_rule as kernel
from flexflow_tpu.obs import events
from flexflow_tpu.ops import recurrent_ops
from flexflow_tpu.ops.recurrent_ops import (GatedDeltaRuleOp,
                                            gated_delta_rule)
from flexflow_tpu.ops.registry import EmitCtx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = cells.load_module(os.path.join(ROOT, "benchmarks"), "reference",
                        "linear_latent_moe_ref")
TOL = 2e-4          # tests/test_linear_latent_moe.py's, and its reasons
B, H, D, CHUNK = 1, 2, 128, 64
TERMS = ("W", "U0", "B", "q_decayed", "k_decayed", "decay")


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def inputs(length, decay=0.3, seed=0, heads=H, d=D):
    """Heads leading: q and k of length one a head, v, a log-decay in
    ``-decay x (0.1, 1)`` a channel, a step size in (0.05, 0.95)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, k = normal(B, heads, length, d), normal(B, heads, length, d)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * rng.uniform(0.1, 1, (B, heads, length, d))
    beta = rng.uniform(0.05, 0.95, (B, heads, length))
    return [jnp.asarray(a, jnp.float32)
            for a in (q, k, normal(B, heads, length, d), g, beta)]


def plain_terms(q, k, v, g, beta, chunk=CHUNK):
    """``_chunk_terms`` on the padded chunks, chunk leading as the
    kernels return them."""
    *terms, least = recurrent_ops._chunk_terms(
        *(recurrent_ops._in_chunks(x, chunk) for x in (q, k, v, g, beta)),
        jnp.float32)
    return [jnp.moveaxis(x, 2, 0) for x in terms], least


def kernel_terms(q, k, v, g, beta, chunk=CHUNK):
    *terms, least = kernel.chunk_terms(q, k, v, g, beta, chunk, jnp.float32)
    return terms, jnp.min(least)


def mixes(length, seed=9):
    rng = np.random.default_rng(seed)
    n = -(-length // CHUNK)
    shapes = [(n, B, H, CHUNK, D)] * 2 + [(n, B, H, CHUNK, CHUNK)] \
        + [(n, B, H, CHUNK, D)] * 2 + [(n, B, H, D)]
    return [jnp.asarray(rng.normal(size=s).astype(np.float32))
            for s in shapes]


@pytest.fixture(scope="module")
def both():
    """(length, decay) -> the six terms, the least log-decay and the five
    gradients of a weighted sum of the terms, by the kernels and by the
    plain code; computed once a case."""
    cache = {}

    def get(length, decay):
        if (length, decay) not in cache:
            args = inputs(length, decay, seed=length)
            mix = mixes(length)

            def graded(fn):
                def loss(*a):
                    terms, least = fn(*a)
                    return sum(jnp.sum(x * m) for x, m in zip(terms, mix)
                               ), (terms, least)
                return jax.jit(jax.grad(loss, argnums=range(5),
                                        has_aux=True))(*args)

            with jax.default_matmul_precision("highest"):
                cache[length, decay] = graded(kernel_terms), \
                    graded(plain_terms)
        return cache[length, decay]
    return get


CASES = [(length, decay) for length in (64, 103, 256)
         for decay in (5.0, 20.0)]


@pytest.mark.parametrize("term", range(6), ids=TERMS)
@pytest.mark.parametrize("length,decay", CASES)
def test_a_term_by_the_kernel_is_the_plain_codes(both, length, decay, term):
    """One chunk, two with a padded tail, four; decays of up to 5 and up
    to 20 a token, whose running sums pass -88.7 inside a chunk."""
    (_, (got, least)), (_, (want, least_plain)) = both(length, decay)
    close(got[term], want[term])
    assert float(least) == pytest.approx(float(least_plain), rel=1e-6)
    if decay == 20.0:
        assert float(least) < -88.7


@pytest.mark.parametrize("name", range(5), ids="q k v g beta".split())
@pytest.mark.parametrize("length,decay", CASES)
def test_a_gradient_by_the_kernel_is_autodiffs(both, length, decay, name):
    (got, _), (want, _) = both(length, decay)
    assert float(jnp.max(jnp.abs(want[name]))) > 0
    close(got[name], want[name], 5e-4 if decay == 20.0 else TOL)


def by_token(q, k, v, g, beta):
    """The reference walks positions before heads."""
    return jnp.swapaxes(ref.delta_rule_by_token(
        *(jnp.swapaxes(a, 1, 2) for a in (q, k, v, g, beta))), 1, 2)


@pytest.mark.parametrize("length,decay", [(64, 0.3), (103, 0.3), (256, 0.3),
                                          (150, 5.0), (150, 20.0)])
def test_the_recurrence_through_the_kernels_is_the_token_by_token_one(
        length, decay):
    """Values and the five gradients of ``gated_delta_rule`` at a head
    size the kernels take, against the reference's walk over the
    tokens; finite where ``exp(-G)`` is not a float32."""
    args = inputs(length, decay, seed=4)
    mix = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        (got, least), d_got = jax.jit(jax.value_and_grad(
            lambda *a: (lambda o, l: (jnp.sum(o * mix), l))(
                *gated_delta_rule(*a)), argnums=range(5),
            has_aux=True))(*args)
        want, d_want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(by_token(*a) * mix),
            argnums=range(5)))(*args)
        close(jax.jit(lambda *a: gated_delta_rule(*a)[0])(*args),
              jax.jit(by_token)(*args))
    close(got, want)
    if decay >= 5.0:
        assert not np.isfinite(np.exp(np.float32(-float(least))))
    for a, b in zip(d_got, d_want):
        close(a, b, 5e-4 if decay >= 5.0 else TOL)


@pytest.mark.parametrize("t", [0, 15, 16, 63, 64, 100])
def test_an_output_through_the_kernels_does_not_move_when_later_inputs_change(
        t):
    """Within a sub-block, across sub-blocks, across the halves of a
    span and across chunks."""
    args, other = inputs(130, seed=5), inputs(130, seed=6)
    moved = [jnp.concatenate([a[:, :, :t + 1], b[:, :, t + 1:]], 2)
             for a, b in zip(args, other)]
    rule = jax.jit(lambda *a: gated_delta_rule(*a)[0])
    base, after = rule(*args), rule(*moved)
    np.testing.assert_array_equal(np.asarray(base[:, :, :t + 1]),
                                  np.asarray(after[:, :, :t + 1]))
    assert float(jnp.max(jnp.abs(base[:, :, t + 1:]
                                 - after[:, :, t + 1:]))) > 0


def test_many_chunks_are_padded_to_whole_grid_steps():
    """Ten chunks run as two grid steps of eight; the padded six write
    nothing and the outputs are the ten chunks'."""
    args = inputs(10 * CHUNK - 3, seed=7, heads=1)
    *terms, _ = jax.jit(lambda *a: kernel.chunk_terms(
        *a, CHUNK, jnp.float32))(*args)
    assert [x.shape[0] for x in terms] == [16] * 6
    want, _ = jax.jit(plain_terms)(*args)
    with jax.default_matmul_precision("highest"):
        for got, w in zip(terms, want):
            close(got[:10], w)
    assert float(jnp.max(jnp.abs(terms[0][10:]))) == 0      # W: beta is 0
    assert float(jnp.min(terms[5][10:])) == 1               # nothing decays


# ----------------------------------------------------------------------
# which path runs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk,dk,dv,takes", [
    (64, 128, 128, True), (16, 128, 128, True), (128, 256, 128, True),
    (32, 128, 256, True), (8, 128, 128, False), (48, 128, 128, False),
    (64, 16, 16, False), (64, 128, 64, False), (64, 192, 128, False)])
def test_the_shapes_decide_which_path_runs(chunk, dk, dv, takes):
    assert kernel.takes_kernel(chunk, dk, dv) is takes


def kda_layer(d, chunk, heads=2, e=24, length=40):
    params = {"num_heads": heads, "head_dim": d, "taps": 4, "eps": 1e-5,
              "chunk": chunk}
    op = GatedDeltaRuleOp()
    rng = np.random.default_rng(0)
    w = {s.name: jnp.asarray(rng.normal(size=s.shape).astype(np.float32)
                             * 0.4)
         for s in op.weights(params, [(B, length, e)], [DataType.DT_FLOAT])}
    u = jnp.asarray(rng.normal(size=(B, length, e)).astype(np.float32))
    cfg = FFConfig()
    cfg.use_bf16_compute = False

    def loss(u, w):
        ctx = EmitCtx(training=True, config=cfg)
        return jnp.sum(op.emit(params, [u], w, ctx, "kda_7")[0] ** 2)
    return loss, u, w


@pytest.mark.parametrize("d,chunk,impl,calls", [
    (128, 64, "kernel", ["fwd", "bwd", "scan_fwd", "scan_bwd"]),
    (128, 16, "kernel", ["fwd", "bwd", "scan_fwd", "scan_bwd"]),
    (16, 64, "plain", []), (128, 8, "plain", [])])
def test_the_layer_announces_the_path_it_took(d, chunk, impl, calls):
    """``impl`` and ``scan`` on the layer's ``kda.scan`` instant, and
    one ``kda.kernel`` instant a kernel traced under ``jax.grad``: the
    forward rule's call and the backward's, of the terms and of the
    scan."""
    loss, u, w = kda_layer(d, chunk)
    events.enable()
    events.clear()
    try:
        # (the instants are said while tracing: no value is wanted)
        jax.eval_shape(jax.grad(loss, argnums=1), u, w)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    (scan,) = [e["attrs"] for e in seen if e["name"] == "kda.scan"]
    assert scan["impl"] == impl and scan["layer"] == "kda_7"
    assert scan["scan"] == impl
    # (q, k and v reach the recurrence through ``kernels/delta_mix.py``
    # wherever a head is whole lanes, whatever the chunk:
    # ``tests/test_delta_mix_kernel.py`` reads those instants)
    assert scan["mix"] == ("kernel" if d == 128 else "plain")
    kernels = [e["attrs"] for e in seen if e["name"] == "kda.kernel"]
    assert any(k["kernel"].startswith("mix_") for k in kernels) \
        is (d == 128)
    kernels = [k for k in kernels if not k["kernel"].startswith("mix_")]
    # (the layer is rematerialised whole: jax.checkpoint traces its
    # forward once more before the rules run)
    assert sorted({k["kernel"] for k in kernels}) == sorted(calls)
    for k in kernels:
        assert k["layer"] == "kda_7" and k["chunk"] == chunk
        if k["kernel"].startswith("scan"):
            # two heads a step, all of a head's chunks
            assert k["chunks"] == 2 * -(-40 // chunk)
            assert k["heads_per_step"] == 2 and k["grid_steps"] == 1
            assert k["chunks_per_step"] == -(-40 // chunk)
            assert 0 < k["vmem_bytes"] < kernel.SCAN_VMEM_LIMIT
            continue
        # 40 tokens: one chunk of 64 or three of 16 a head, a head a step
        assert k["sub"] == kernel.SUB and k["chunks"] == 2 * -(-40 // chunk)
        assert k["chunks_per_step"] == -(-40 // chunk)
        assert k["grid_steps"] == 2
        assert 0 < k["vmem_bytes"] < kernel.VMEM_LIMIT


def test_the_residuals_are_the_five_inputs():
    """What the ``custom_vjp`` keeps for the backward pass: five arrays
    of the inputs' sizes, no (., C, C) matrix and no decayed copy."""
    args = inputs(2 * CHUNK, heads=1)
    static = (CHUNK, 2, jnp.dtype(jnp.float32), None, True)
    flat = [a.reshape((B,) + a.shape[2:]) for a in args[:4]] \
        + [args[4].reshape(B, 2, 1, CHUNK)]
    out, res = kernel._terms_fwd(*flat, *static)
    assert len(out) == 7
    assert [r.shape for r in res] == [a.shape for a in flat]
    for r, a in zip(res, flat):
        assert r is a
    # and under autodiff: what the pullback holds between the forward and
    # the backward pass is of the inputs' shapes only
    _, pullback = jax.vjp(lambda *a: kernel.chunk_terms(
        *a, CHUNK, jnp.float32)[:6], *args)
    shapes = {tuple(x.shape) for x in jax.tree.leaves(pullback)
              if hasattr(x, "shape")}
    assert shapes and not any(
        len(s) >= 2 and s[-2:] == (CHUNK, CHUNK) for s in shapes), shapes
    assert all(int(np.prod(s)) <= int(np.prod(args[0].shape))
               for s in shapes), shapes


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_the_kernels_under_a_mesh_are_the_unsharded_ones(by):
    """Two sequences, or two heads, one a device: the call runs under
    ``shard_map`` over the batch and head entries of the spec."""
    from jax.sharding import Mesh, PartitionSpec as P
    rng = np.random.default_rng(3)
    shape = (2, 2, CHUNK, D)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           / D ** 0.5) for _ in range(3))
    g = jnp.asarray(-rng.uniform(0.1, 1, shape).astype(np.float32))
    beta = jnp.asarray(rng.uniform(0.05, 0.95, shape[:3]
                                   ).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")

    def loss(mesh, spec, *a):
        out, _ = gated_delta_rule(*a, mesh=mesh, spec=spec)
        return jnp.sum(out ** 2)

    want = jax.jit(jax.value_and_grad(
        lambda *a: loss(None, None, *a), argnums=range(5)))(q, k, v, g, beta)
    got = jax.jit(jax.value_and_grad(
        lambda *a: loss(mesh, spec, *a), argnums=range(5)))(q, k, v, g, beta)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 2e-5)


# ----------------------------------------------------------------------
# the scan kernel pair: the state from chunk to chunk in VMEM (the
# checks' bodies are ``tests/delta_rule_scan.py``'s, here with a decay
# a channel)
# ----------------------------------------------------------------------
SHAPE_IDS = ["x".join(map(str, s)) for s in drs.SHAPES]


@pytest.fixture(scope="module")
def scanned():
    return drs.both_paths(by_head=False)


@pytest.mark.parametrize("what", range(7), ids=drs.NAMES)
@pytest.mark.parametrize("mdt", sorted(drs.MDTS))
@pytest.mark.parametrize("shape", drs.SHAPES, ids=SHAPE_IDS)
def test_the_scan_kernels_are_the_plain_scan_on_the_same_terms(
        scanned, shape, mdt, what):
    """The output and the six terms' cotangents, float32 and bf16 terms:
    two groups of chunks over two blocks of (batch x head) rows with a
    batch of two, a sequence of five chunks (no whole number of groups
    of four), two groups of four."""
    drs.check_against_the_plain_scan(scanned, shape, mdt, what)


@pytest.mark.parametrize("mdt", sorted(drs.MDTS))
@pytest.mark.parametrize("shape", drs.SHAPES, ids=SHAPE_IDS)
def test_the_scan_keeps_the_state_each_chunk_starts_from(scanned, shape,
                                                         mdt):
    drs.check_the_starting_states(scanned, shape, mdt)


@pytest.mark.parametrize("heads,chunks,steps", drs.STEPS)
def test_the_scan_says_what_it_ran(heads, chunks, steps):
    """One ``kda.kernel`` instant a call, forward and backward."""
    drs.check_what_the_scan_says(False, "kda", heads, chunks, steps)


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_the_scan_under_a_mesh_is_the_unsharded_one(by):
    drs.check_under_a_mesh(False, by)
