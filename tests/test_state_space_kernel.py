"""The state-space recurrence's kernels (``kernels/state_space.py``) in
interpret mode on the CPU: a chunk's own terms against the plain
``_ssm_chunks`` they stand in for, the whole scan and every gradient on
the kernel path against the plain path and against the recurrence token
by token (``benchmarks/reference/ssm_hybrid_ref.py``), and the predicate
that chooses the path from the shapes.

Precision: at ``mdt`` float32 every product of the kernels is exact
(``Precision.HIGHEST``) and so is the CPU's; the two paths then differ
in the order of a few sums. At bf16 they round the same operands in the
forward (the results agree to float32 rounding) and the kernels'
backward keeps ``dY X^T`` float32 where autodiff rounds it: 2e-2 of the
largest entry is three times what they read.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells
from flexflow_tpu import FFConfig
from flexflow_tpu.kernels import state_space as ssk
from flexflow_tpu.obs import events
from flexflow_tpu.ops.recurrent_ops import (StateSpaceMixerOp, _ssm_chunks,
                                            state_space_scan)
from flexflow_tpu.ops.registry import EmitCtx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = cells.load_module(os.path.join(ROOT, "benchmarks"), "reference",
                        "ssm_hybrid_ref")

#: (batch, chunks, chunk, heads, head size, state): all heads one block;
#: two tiles a chunk; heads of a sublane tile, a state of two vectors;
#: two blocks of eight heads (each carries its own state)
SHAPES = [(2, 2, 128, 2, 64, 128), (1, 2, 256, 4, 32, 128),
          (1, 2, 128, 3, 16, 256), (1, 2, 128, 16, 64, 128)]
MDTS = pytest.mark.parametrize("mdt", [jnp.float32, jnp.bfloat16],
                               ids=["float32", "bfloat16"])
BY_SHAPE = pytest.mark.parametrize(
    "shape", SHAPES, ids=lambda s: "x".join(map(str, s)))


def close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


@MDTS
@BY_SHAPE
def test_a_chunks_own_terms_are_the_plain_ones(shape, mdt):
    """``inside`` and ``added`` of ``_ssm_chunks`` out of the kernel
    that does the whole scan: every chunk a sequence of its own with an
    empty chunk after it; from a zero state its outputs are ``inside``,
    and the state the empty chunk starts from is ``added``."""
    b, m, c, h, p, n = shape
    rng = np.random.default_rng(0)

    def draw(*dims):
        return jnp.asarray(rng.normal(size=dims), jnp.float32)
    dtx, bm, cm = draw(b, m, c, h, p), draw(b, m, c, n), draw(b, m, c, n)
    big_g = -jnp.cumsum(jnp.asarray(
        rng.uniform(0.001, 0.3, (b, m, c, h)), jnp.float32), 2)

    def alone(v):       # (B, M, C, ..) -> (B M, 2 C, ..), the rest zeros
        v = v.reshape((b * m, c) + v.shape[3:])
        return jnp.concatenate([v, jnp.zeros_like(v)], axis=1)

    def tokens_last(v):
        return jnp.moveaxis(v, 1, -1)

    with jax.default_matmul_precision("highest"):
        inside, added = jax.jit(lambda *a: _ssm_chunks(mdt, *a))(
            dtx, bm, cm, big_g)
        y, starts = jax.jit(lambda *a: ssk.scan_chunks(*a, c, mdt))(
            tokens_last(alone(dtx)), jnp.ones((b * m, h, 2 * c)),
            tokens_last(alone(big_g)), alone(bm), alone(cm))
    close(jnp.moveaxis(y, -1, 1)[:, :c].reshape(inside.shape), inside, 1e-6)
    close(starts[:, 1].reshape(added.shape), added, 1e-6)
    assert not np.asarray(starts[:, 0]).any()


def scan_inputs(seq, heads, p, n, seed=0, strength=1.0, batch=2):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (batch, seq, heads)),
                     jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1.0, 16.0, heads) * strength),
                        jnp.float32)
    return (draw(batch, seq, heads, p), dt, a_log, draw(batch, seq, n),
            draw(batch, seq, n))


def losses(chunk, heads):
    def got(*a):
        y, least = state_space_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4],
                                    chunk)
        return jnp.sum(y * jnp.cos(y)), (y, least)

    def want(*a):
        with jax.default_matmul_precision("highest"):
            y = ref.recurrence(*a, jnp.zeros(heads))
        return jnp.sum(y * jnp.cos(y)), (y, None)
    return got, want


@MDTS
@BY_SHAPE
def test_the_scan_and_every_pull_back_are_the_plain_paths(shape, mdt):
    b, m, c, h, p, n = shape
    args = scan_inputs(m * c - 40, h, p, n, batch=b)
    ct = jnp.asarray(np.random.default_rng(3).normal(
        size=args[0].shape), jnp.float32)

    def run(kernels):
        def scan(*a):
            return state_space_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4],
                                    c, mdt, kernels=kernels)[0]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: (
                scan(*a), jax.vjp(scan, *a)[1](ct)))(*args)
    (y1, g1), (y2, g2) = run(True), run(False)
    close(y1, y2, 1e-6)
    # (``A_log``'s gradient is a number a head, a sum over every position
    # of terms of both signs: float32 against float32 reads 6e-4 there)
    tol = 5e-5 if mdt == jnp.float32 else 2e-2
    for a, b_, t in zip(g1, g2, (tol, tol, max(tol, 5e-3), tol, tol)):
        close(a, b_, t)


#: (positions, chunk, heads, head size): one and a half chunks of 128
#: (the padded positions write nothing and decay nothing); two tiles a
#: chunk and four heads a vector
SCANS = [(192, 128, 2, 64), (256, 256, 4, 32)]


@pytest.mark.parametrize("seq,chunk,heads,p", SCANS)
def test_the_scan_and_every_gradient_on_the_kernel_path_are_the_recurrences(
        seq, chunk, heads, p):
    assert ssk.takes_kernel(chunk, heads, p, 128)
    args = scan_inputs(seq, heads, p, 128)
    got, want = losses(chunk, heads)
    events.enable()
    events.clear()
    try:
        (_, (y1, least)), g1 = jax.jit(jax.value_and_grad(
            got, range(5), has_aux=True))(*args)
        said = [e["attrs"]["kernel"] for e in events.events()
                if e["name"] == "ssm.kernel"]
    finally:
        events.disable()
        events.clear()
    assert sorted(said) == ["bwd", "fwd"]
    (_, (y2, _)), g2 = jax.jit(jax.value_and_grad(
        want, range(5), has_aux=True))(*args)
    close(y1, y2, 2e-4)
    # ``A_log``'s gradient is a number a head, the sum over every
    # position of terms of both signs: the plain path reads 6e-3 against
    # the token-by-token recurrence there too, float32 against float32
    for a, b, tol in zip(g1, g2, (1e-3, 1e-3, 2e-2, 1e-3, 1e-3)):
        close(a, b, tol)
    g = np.asarray(args[1]) * -np.exp(np.asarray(args[2]))
    g = np.pad(g, ((0, 0), (0, -seq % chunk), (0, 0)))
    least_by_hand = g.reshape(2, -1, chunk, heads).sum(2).min()
    assert abs(float(least) - least_by_hand) <= 1e-4 * abs(least_by_hand)


def test_decays_that_overflow_when_formed_apart_still_agree_on_the_kernel_path():
    """``tests/test_ssm_hybrid.py``'s case through the kernels: ``A`` a
    hundred times the published range, a chunk's log-decays past -2,000
    (the cell's read -373), so ``exp(-G_j)`` alone is infinite in
    float32; every exponent the kernels take is a difference <= 0."""
    args = scan_inputs(256, 2, 64, 128, strength=100.0)
    g = np.cumsum(np.asarray(args[1]) * -np.exp(np.asarray(args[2])), 1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-g[:, :128].astype(np.float32))).any()
    got, want = losses(128, 2)
    (_, (y1, _)), g1 = jax.jit(jax.value_and_grad(
        got, (0, 1, 3, 4), has_aux=True))(*args)
    (_, (y2, _)), g2 = jax.jit(jax.value_and_grad(
        want, (0, 1, 3, 4), has_aux=True))(*args)
    close(y1, y2, 2e-4)
    for a, b in zip(g1, g2):
        close(a, b, 1e-3)


def test_a_layer_the_kernels_take_and_every_gradient_are_the_references():
    """One mixer at 2 heads of 64 x 128 in chunks of 128 over a chunk
    and a half, through the op: the kernels, the skip on the channels as
    they lie, the gate and the norm against the reference's layer, the
    output and the gradient of a scalar of it for the input and every
    weight; the ``ssm.layer`` instant says ``impl="kernel"``."""
    e, h, p, n, taps, seq = 32, 2, 64, 128, 4, 192
    inner = h * p
    rng = np.random.default_rng(0)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    weights = {"in_proj": w(e, 2 * inner + 2 * n + h),
               "conv_w": u(-0.7, 0.7, inner + 2 * n, taps),
               "conv_b": u(-0.5, 0.5, inner + 2 * n),
               "dt_bias": u(-3.0, 0.0, h), "A_log": jnp.log(u(1.0, 16.0, h)),
               "D": u(0.5, 1.5, h), "norm": u(0.5, 1.5, inner),
               "out_proj": w(inner, e)}
    x = jnp.asarray(rng.normal(size=(2, seq, e)), jnp.float32)
    params = {"num_heads": h, "head_dim": p, "state": n, "taps": taps,
              "chunk": 128, "eps": 1e-5}
    sizes = {"mamba_n_heads": h, "mamba_d_head": p, "mamba_d_state": n,
             "mamba_d_conv": taps, "mamba_n_groups": 1, "rms_norm_eps": 1e-5}
    cfg = FFConfig()
    cfg.use_bf16_compute = False

    def got(x, weights):
        (y,) = StateSpaceMixerOp().emit(
            params, [x], weights, EmitCtx(training=True, config=cfg),
            "mamba")
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, weights):
        with jax.default_matmul_precision("highest"):
            y = ref.mixer(x, weights, sizes)
        return jnp.sum(y * jnp.cos(y)), y

    events.enable()
    events.clear()
    try:
        (_, y1), (gx1, gw1) = jax.jit(jax.value_and_grad(
            got, (0, 1), has_aux=True))(x, weights)
        said = [ev["attrs"]["impl"] for ev in events.events()
                if ev["name"] == "ssm.layer"]
    finally:
        events.disable()
        events.clear()
    assert said == ["kernel"]
    (_, y2), (gx2, gw2) = jax.jit(jax.value_and_grad(
        want, (0, 1), has_aux=True))(x, weights)
    close(y1, y2, 2e-4)
    close(gx1, gx2, 1e-3)
    for k in gw2:
        assert float(jnp.max(jnp.abs(gw2[k]))) > 0, k
        close(gw1[k], gw2[k], 1e-3)


def test_a_caller_on_a_mesh_gets_the_plain_path():
    args = scan_inputs(128, 2, 64, 128, batch=1)

    def scan(kernels):
        return jax.make_jaxpr(lambda *a: state_space_scan(
            a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 128,
            kernels=kernels))(*args)
    assert "pallas_call" in str(scan(True))
    plain = str(scan(False))
    assert "pallas_call" not in plain and "remat" in plain


@pytest.mark.parametrize("shape,takes", [
    ((256, 64, 64, 128), True),       # granite_4_0_h_micro
    ((128, 2, 64, 128), True),        # all the heads one block
    ((256, 4, 32, 128), True),
    ((128, 16, 128, 256), False),     # eight heads of 128: 1,024 channels
    ((128, 4, 128, 256), True),       # four of them: one block
    ((256, 24, 64, 128), True),       # three blocks of eight
    ((256, 20, 64, 128), False),      # no eights of heads, 1,280 channels
    ((16, 4, 16, 8), False),          # GraniteHybridRankConfig.tiny()
    ((16, 4, 16, 128), False),
    ((64, 64, 64, 128), False),       # a chunk under a tile
    ((192, 64, 64, 128), False),      # a chunk of a tile and a half
    ((512, 64, 64, 128), False),      # past what a backward step holds
    ((256, 3, 64, 128), True),        # H P = 192: channels lie in rows
    ((256, 64, 24, 128), False),      # a head no whole bf16 tiles
    ((256, 64, 64, 64), False),       # the state under a vector
    ((256, 64, 64, 192), False),
])
def test_the_predicate_reads_the_shapes(shape, takes):
    assert ssk.takes_kernel(*shape) is takes


@pytest.mark.parametrize("heads,p,want", [
    (64, 64, 8),        # 512 channels: the cell's
    (2, 64, 2), (4, 32, 4), (24, 64, 8), (20, 64, 20), (6, 64, 6),
    (48, 32, 16), (32, 16, 32), (4, 128, 4), (16, 128, 16)])
def test_a_step_takes_eights_of_heads_or_all(heads, p, want):
    n = ssk.heads_per_block(heads, p)
    assert n == want and heads % n == 0 and (n % 8 == 0 or n == heads)


# ----------------------------------------------------------------------
# several groups of B and C (PR 66): a block of heads lies in ONE group
# ----------------------------------------------------------------------
#: (positions, chunk, heads, head size, groups), operand type: two
#: groups of two blocks of eight heads (``block // 2`` picks B and C,
#: ``dB`` and ``dC`` are summed over a group's two blocks; the cell's
#: block, over a ragged length, in both operand types); four groups of
#: one block
GROUPED = [((216, 128, 32, 64, 2), jnp.float32),
           ((216, 128, 32, 64, 2), jnp.bfloat16),
           ((128, 128, 32, 16, 4), jnp.float32)]


@pytest.mark.parametrize("shape,mdt", GROUPED,
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_grouped_scan_and_every_pull_back_are_the_plain_paths(
        shape, mdt):
    """Output and the five cotangents on the kernel path, where head
    ``h`` reads group ``h // (heads / groups)`` by the block index,
    against the plain path (one ungrouped scan a group) and, in float32,
    against the recurrence token by token a group."""
    (seq, chunk, heads, p, groups), n = shape, 128
    x, dt, a_log, _, _ = scan_inputs(seq, heads, p, n, batch=1)
    rng = np.random.default_rng(5)
    bm, cm = (jnp.asarray(rng.normal(size=(1, seq, groups, n)), jnp.float32)
              for _ in range(2))
    ct = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    assert ssk.takes_kernel(chunk, heads, p, n, groups)
    assert ssk.heads_per_block(heads, p, groups) == 8

    def run(kernels):
        def scan(*a):
            return state_space_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4],
                                    chunk, mdt, kernels=kernels)[0]
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: (
                scan(*a), jax.vjp(scan, *a)[1](ct)))(x, dt, a_log, bm, cm)
    (y1, g1), (y2, g2) = run(True), run(False)
    close(y1, y2, 1e-6)
    tol = 5e-5 if mdt == jnp.float32 else 2e-2
    for a, b_, t in zip(g1, g2, (tol, tol, max(tol, 5e-3), tol, tol)):
        assert a.shape == b_.shape
        close(a, b_, t)
    if mdt == jnp.float32:
        per = heads // groups
        with jax.default_matmul_precision("highest"):
            want = jnp.concatenate([ref.recurrence(
                x[:, :, g * per:(g + 1) * per], dt[:, :, g * per:(g + 1) * per],
                a_log[g * per:(g + 1) * per], bm[:, :, g], cm[:, :, g],
                jnp.zeros(per)) for g in range(groups)], axis=2)
        close(y1, want, 2e-4)


def test_a_wrong_group_is_apart():
    """The same heads reading ONE group's B and C are another function:
    the index map's ``block // blocks a group`` is read."""
    x, dt, a_log, _, _ = scan_inputs(128, 32, 16, 128, batch=1)
    rng = np.random.default_rng(5)
    bm, cm = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
              for _ in range(2))

    def scan(bm, cm):
        return jax.jit(lambda *a: state_space_scan(
            a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 128)[0])(
            x, dt, a_log, bm, cm)
    grouped, one = scan(bm, cm), scan(bm[:, :, 0], cm[:, :, 0])
    close(grouped[:, :, :16], one[:, :, :16], 1e-6)
    assert float(jnp.max(jnp.abs(grouped[:, :, 16:] - one[:, :, 16:]))) > 1.0


@pytest.mark.parametrize("shape,takes", [
    ((128, 32, 64, 128, 2), True),    # nemotron3_super_120b_a12b: 2 x 16
    ((128, 128, 64, 128, 8), True),   # the whole mixer
    ((128, 16, 64, 128, 8), False),   # two heads a group: no eights
    ((128, 32, 64, 128, 3), False),   # heads in no whole groups
    ((128, 48, 64, 128, 2), True),    # 24 a group: three blocks of eight
    ((256, 64, 64, 128, 1), True),    # granite_4_0_h_micro, as it was
])
def test_the_predicate_reads_the_groups(shape, takes):
    assert ssk.takes_kernel(*shape) is takes
