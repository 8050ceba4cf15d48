"""The head-decay delta rule's in-chunk terms by the Pallas kernels (the
head form of ``kernels/gated_delta_rule.py``, interpret mode on the CPU)
against the plain ``_chunk_terms_head`` and its autodiff, and the
recurrence through them against the token-by-token reference in float32
at ``highest``, at the head size the kernels take (128) and chunks of 64,
one and two value heads a q/k head.

Sizes are kept to a few chunks of one q/k head: a kernel traced in
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
                                            gated_delta_rule,
                                            head_decay_impl)
from flexflow_tpu.ops.registry import EmitCtx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = cells.load_module(os.path.join(ROOT, "benchmarks"), "reference",
                        "gdn_gated_moe_ref")
TOL = 2e-4          # tests/test_gdn_gated_moe.py's, and its reasons
B, D, CHUNK = 1, 128, 64
TERMS = ("W", "U0", "B", "q_decayed", "k_decayed", "decay")


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def inputs(length, decay=0.3, seed=0, group=2, key_heads=1, d=D, batch=B):
    """Heads leading: q and k of length one at ``key_heads`` heads, v, a
    log-decay in ``-decay x (0.1, 1)`` and a step size in (0.05, 0.95) at
    ``group`` times as many."""
    rng = np.random.default_rng(seed)
    heads = key_heads * group

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, k = normal(batch, key_heads, length, d), \
        normal(batch, key_heads, length, d)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * rng.uniform(0.1, 1, (batch, heads, length))
    beta = rng.uniform(0.05, 0.95, (batch, heads, length))
    return [jnp.asarray(a, jnp.float32)
            for a in (q, k, normal(batch, heads, length, d), g, beta)]


def plain_terms(q, k, v, g, beta, chunk=CHUNK):
    """``_chunk_terms_head`` on the padded chunks, chunk leading as the
    kernels return them."""
    *terms, least = recurrent_ops._chunk_terms_head(
        *(recurrent_ops._in_chunks(x, chunk) for x in (q, k, v, g, beta)),
        jnp.float32)
    return [jnp.moveaxis(x, 2, 0) for x in terms], least


def kernel_terms(q, k, v, g, beta, chunk=CHUNK):
    *terms, least = kernel.head_chunk_terms(q, k, v, g, beta, chunk,
                                            jnp.float32)
    return terms, jnp.min(least)


def mixes(length, heads, seed=9):
    rng = np.random.default_rng(seed)
    n = -(-length // CHUNK)
    shapes = [(n, B, heads, CHUNK, D)] * 2 + [(n, B, heads, CHUNK, CHUNK)] \
        + [(n, B, heads, CHUNK, D)] * 2 + [(n, B, heads, 1)]
    return [jnp.asarray(rng.normal(size=s).astype(np.float32))
            for s in shapes]


@pytest.fixture(scope="module")
def both():
    """(length, decay, group) -> the six terms, the least log-decay and
    the five gradients of a weighted sum of the terms, by the kernels
    and by the plain code; computed once a case."""
    cache = {}

    def get(*case):
        if case not in cache:
            length, decay, group = case
            args = inputs(length, decay, seed=length, group=group)
            mix = mixes(length, group)
            n = mix[0].shape[0]

            def graded(fn):
                def loss(*a):
                    terms, least = fn(*a)
                    # (the kernels' chunks are padded to whole grid steps)
                    return sum(jnp.sum(x[:n] * m)
                               for x, m in zip(terms, mix)), (terms, least)
                return jax.jit(jax.grad(loss, argnums=range(5),
                                        has_aux=True))(*args)

            with jax.default_matmul_precision("highest"):
                cache[case] = graded(kernel_terms), graded(plain_terms)
        return cache[case]
    return get


# one chunk, two with a padded tail, five (no whole number of grid steps
# of four at two heads a q/k head); decays of up to 2 and up to 5 a
# token, whose running sums pass -88.7 inside a chunk
CASES = [(length, decay, group) for length in (64, 103, 320)
         for decay, group in ((2.0, 2), (5.0, 1), (5.0, 2))]


@pytest.mark.parametrize("term", range(6), ids=TERMS)
@pytest.mark.parametrize("length,decay,group", CASES)
def test_a_term_by_the_kernel_is_the_plain_codes(both, length, decay, group,
                                                 term):
    (_, (got, least)), (_, (want, least_plain)) = both(length, decay, group)
    n = want[term].shape[0]
    close(got[term][:n], want[term])
    assert float(least) == pytest.approx(float(least_plain), rel=1e-6)
    if decay == 5.0:
        assert float(least) < -88.7


@pytest.mark.parametrize("name", range(5), ids="q k v g beta".split())
@pytest.mark.parametrize("length,decay,group", CASES)
def test_a_gradient_by_the_kernel_is_autodiffs(both, length, decay, group,
                                               name):
    (got, _), (want, _) = both(length, decay, group)
    assert got[name].shape == want[name].shape
    assert float(jnp.max(jnp.abs(want[name]))) > 0
    close(got[name], want[name], 5e-4 if decay == 5.0 else TOL)


def by_token(q, k, v, g, beta):
    """The reference walks positions before heads, q and k at the value
    heads."""
    group = v.shape[1] // k.shape[1]
    q, k = jnp.repeat(q, group, 1), jnp.repeat(k, group, 1)
    return jnp.swapaxes(ref.delta_rule_by_token(
        *(jnp.swapaxes(a, 1, 2) for a in (q, k, v, g, beta))), 1, 2)


@pytest.mark.parametrize("length,decay,group", [
    (64, 0.3, 2), (103, 0.3, 1), (320, 0.3, 2), (150, 3.0, 2),
    (150, 5.0, 1)])
def test_the_recurrence_through_the_kernels_is_the_token_by_token_one(
        length, decay, group):
    """Values and the five gradients of ``gated_delta_rule`` at a head
    size the kernels take, against the reference's walk over the
    tokens; finite where ``exp(-G)`` is not a float32."""
    args = inputs(length, decay, seed=4, group=group)
    assert head_decay_impl(CHUNK, 1, group, D, D) == "kernel"
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
    if decay >= 3.0:
        assert float(least) < -88.7
        with np.errstate(over="ignore"):
            assert not np.isfinite(np.exp(np.float32(-float(least))))
    for a, b in zip(d_got, d_want):
        assert a.shape == b.shape
        close(a, b, 5e-4 if decay >= 3.0 else TOL)


@pytest.mark.parametrize("t", [0, 15, 16, 63, 64, 100])
def test_an_output_through_the_kernels_does_not_move_when_later_inputs_change(
        t):
    """Within a sub-block of the inverse, across sub-blocks, across the
    halves of a span and across chunks."""
    args, other = inputs(130, seed=5), inputs(130, seed=6)
    moved = [jnp.concatenate([a[:, :, :t + 1], b[:, :, t + 1:]], 2)
             for a, b in zip(args, other)]
    rule = jax.jit(lambda *a: gated_delta_rule(*a)[0])
    base, after = rule(*args), rule(*moved)
    np.testing.assert_array_equal(np.asarray(base[:, :, :t + 1]),
                                  np.asarray(after[:, :, :t + 1]))
    assert float(jnp.max(jnp.abs(base[:, :, t + 1:]
                                 - after[:, :, t + 1:]))) > 0


@pytest.mark.parametrize("group,steps", [(1, 2), (2, 3)])
def test_many_chunks_are_padded_to_whole_grid_steps(group, steps):
    """Ten chunks run as two grid steps of eight chunks of one head, or
    three of four chunks of two; the padded ones write nothing and the
    outputs are the ten chunks'."""
    args = inputs(10 * CHUNK - 3, seed=7, group=group)
    *terms, _ = jax.jit(lambda *a: kernel.head_chunk_terms(
        *a, CHUNK, jnp.float32))(*args)
    padded = steps * (kernel.HEAD_CHUNKS_PER_STEP // group)
    assert [x.shape[0] for x in terms] == [padded] * 6
    want, _ = jax.jit(plain_terms)(*args)
    with jax.default_matmul_precision("highest"):
        for got, w in zip(terms, want):
            close(got[:10], w)
    assert float(jnp.max(jnp.abs(terms[0][10:]))) == 0      # W: beta is 0
    assert float(jnp.min(terms[5][10:])) == 1               # nothing decays


def test_a_chunk_whose_decays_sum_past_float32s_range_gives_finite_terms():
    """Log-decays of -4 to -9 a token sum to -400 inside a chunk:
    ``exp(G_i)`` times ``exp(-G_j)`` is 0 times inf; the kernels take
    differences only, and so do their gradients."""
    rng = np.random.default_rng(0)
    args = inputs(2 * CHUNK, seed=8)
    args[3] = -jnp.asarray(rng.uniform(4.0, 9.0, args[3].shape),
                           jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, least = jax.jit(kernel_terms)(*args)
        want, _ = jax.jit(plain_terms)(*args)
        for a, b in zip(got, want):
            close(a, b)
        out, _ = jax.jit(gated_delta_rule)(*args)
        close(out, jax.jit(by_token)(*args), 1e-5)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(
            gated_delta_rule(*a)[0] ** 2), range(5)))(*args)
    assert float(least) < -250
    assert all(np.all(np.isfinite(np.asarray(a))) for a in grads)


# ----------------------------------------------------------------------
# which path runs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk,dk,dv,group,takes", [
    (64, 128, 128, 2, True), (64, 128, 128, 1, True),
    (16, 128, 128, 4, True), (128, 256, 128, 2, True),
    (32, 128, 256, 8, True), (8, 128, 128, 2, False),
    (48, 128, 128, 2, False), (64, 16, 16, 2, False),
    (64, 128, 64, 1, False), (64, 192, 128, 2, False),
    (64, 128, 128, 128, False)])
def test_the_shapes_decide_which_path_runs(chunk, dk, dv, group, takes):
    """Head sizes in whole lanes, a chunk the inverse's levels divide, a
    group whose backward step fits the kernels' VMEM limit."""
    assert kernel.takes_head_kernel(chunk, dk, dv, group) is takes
    assert head_decay_impl(chunk, 2, 2 * group, dk, dv) \
        == ("kernel" if takes else "plain")


@pytest.mark.parametrize("by,key_heads,impl", [
    ("heads", 2, "kernel"), ("heads", 1, "plain"), ("batch", 1, "kernel")])
def test_under_a_mesh_every_device_has_to_hold_whole_groups(by, key_heads,
                                                            impl):
    """Two devices over the heads: two q/k heads under four value heads
    split into whole groups, one q/k head under two does not."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")
    assert head_decay_impl(CHUNK, key_heads, 2 * key_heads, D, D, mesh,
                           spec) == impl


def gdn_layer(d, chunk, key_heads=1, heads=2, e=24, length=40):
    params = {"num_heads": heads, "num_key_heads": key_heads, "head_dim": d,
              "taps": 4, "eps": 1e-6, "chunk": chunk, "decay": "head"}
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
        return jnp.sum(op.emit(params, [u], w, ctx, "gdn_7")[0] ** 2)
    return loss, u, w


@pytest.mark.parametrize("d,chunk,impl,calls", [
    (128, 64, "kernel", ["fwd", "bwd", "scan_fwd", "scan_bwd"]),
    (128, 16, "kernel", ["fwd", "bwd", "scan_fwd", "scan_bwd"]),
    (16, 64, "plain", []), (128, 8, "plain", [])])
def test_the_layer_announces_the_path_it_took(d, chunk, impl, calls):
    """``impl`` and ``scan`` on the layer's ``gdn.scan`` instant, and
    one ``gdn.kernel`` instant a kernel traced under ``jax.grad``: the
    forward rule's call and the backward's, of the terms and of the
    scan; no ``kda.*`` name."""
    loss, u, w = gdn_layer(d, chunk)
    events.enable()
    events.clear()
    try:
        # (the instants are said while tracing: no value is wanted)
        jax.eval_shape(jax.grad(loss, argnums=1), u, w)
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    (scan,) = [e["attrs"] for e in seen if e["name"] == "gdn.scan"]
    assert scan["impl"] == impl and scan["layer"] == "gdn_7"
    assert scan["scan"] == impl
    assert not [e for e in seen if e["name"].startswith("kda.")]
    # (q, k and v reach the recurrence through ``kernels/delta_mix.py``
    # wherever a head is whole lanes, whatever the chunk:
    # ``tests/test_delta_mix_kernel.py`` reads those instants)
    assert scan["mix"] == ("kernel" if d == 128 else "plain")
    kernels = [e["attrs"] for e in seen if e["name"] == "gdn.kernel"]
    assert any(k["kernel"].startswith("mix_") for k in kernels) \
        is (d == 128)
    kernels = [k for k in kernels if not k["kernel"].startswith("mix_")]
    wraps = [e["attrs"]["site"] for e in seen if e["name"] == "remat.wrap"]
    assert ("gdn.terms" in wraps) is (impl == "plain")
    # (the layer is rematerialised whole: jax.checkpoint traces its
    # forward once more before the rules run)
    assert sorted({k["kernel"] for k in kernels}) == sorted(calls)
    for k in kernels:
        assert k["layer"] == "gdn_7" and k["chunk"] == chunk
        # 40 tokens: one chunk of 64 or three of 16 a head, two heads of
        # one q/k head a step
        n = -(-40 // chunk)
        if k["kernel"].startswith("scan"):
            assert k["chunks"] == 2 * n and k["heads_per_step"] == 2
            assert k["chunks_per_step"] == n and k["grid_steps"] == 1
            assert 0 < k["vmem_bytes"] < kernel.SCAN_VMEM_LIMIT
            continue
        assert k["group"] == 2 and k["chunks"] == 2 * n
        assert k["chunks_per_step"] == 2 * n and k["grid_steps"] == 1
        assert 0 < k["vmem_bytes"] < kernel.VMEM_LIMIT


def test_the_residuals_are_the_five_inputs():
    """What the ``custom_vjp`` keeps for the backward pass: five arrays
    of the inputs' sizes, no (., C, C) matrix and no decayed copy."""
    args = inputs(2 * CHUNK)
    static = (CHUNK, 2, jnp.dtype(jnp.float32), None, True)
    q, k, v, g, beta = args
    flat = [q.reshape(B, -1, D), k.reshape(B, -1, D),
            v.reshape(B, 2, -1, D), g.reshape(B, 2, 2, 1, CHUNK),
            beta.reshape(B, 2, 2, 1, CHUNK)]
    out, res = kernel._head_terms_fwd(*flat, *static)
    assert len(out) == 7
    assert [r.shape for r in res] == [a.shape for a in flat]
    for r, a in zip(res, flat):
        assert r is a
    # and under autodiff: what the pullback holds between the forward and
    # the backward pass is of the inputs' shapes only
    _, pullback = jax.vjp(lambda *a: kernel.head_chunk_terms(
        *a, CHUNK, jnp.float32)[:6], *args)
    shapes = {tuple(x.shape) for x in jax.tree.leaves(pullback)
              if hasattr(x, "shape")}
    assert shapes and not any(
        len(s) >= 2 and s[-2:] == (CHUNK, CHUNK) for s in shapes), shapes
    assert all(int(np.prod(s)) <= int(np.prod(v.shape))
               for s in shapes), shapes


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_the_kernels_under_a_mesh_are_the_unsharded_ones(by):
    """Two sequences, or two q/k heads with the two value heads each
    serves, one a device: the call runs under ``shard_map`` over the
    batch and head entries of the spec."""
    from jax.sharding import Mesh, PartitionSpec as P
    q, k, v, g, beta = inputs(CHUNK, seed=3, key_heads=2, batch=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")
    assert head_decay_impl(CHUNK, 2, 4, D, D, mesh, spec) == "kernel"

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
# a head)
# ----------------------------------------------------------------------
SHAPE_IDS = ["x".join(map(str, s)) for s in drs.SHAPES]


@pytest.fixture(scope="module")
def scanned():
    return drs.both_paths(by_head=True)


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
    """One ``gdn.kernel`` instant a call, forward and backward."""
    drs.check_what_the_scan_says(True, "gdn", heads, chunks, steps)


@pytest.mark.parametrize("by", ["batch", "heads"])
def test_the_scan_under_a_mesh_is_the_unsharded_one(by):
    drs.check_under_a_mesh(True, by)
