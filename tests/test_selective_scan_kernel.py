"""The selective scan's kernels (``kernels/selective_scan.py``) in
interpret mode on the CPU: ``y`` and all five gradients on the kernel
path against the plain path of ``selective_scan`` and against the
recurrence token by token (``benchmarks/reference/sambay_ref.py``), the
states the forward keeps for the backward, one layer through the op down
both paths, and the predicate that chooses the path from the shapes.

Both paths are float32 and exact but for the order of a few sums (the
sixteen terms of ``y`` pairwise in the kernels, ``jnp.sum`` on the plain
path; the sums over the channels a block at a time), so they are held to
the tolerances ``tests/test_sambay.py`` holds the plain path to the
recurrence with.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rank_family as rf
from flexflow_tpu.kernels import selective_scan as ssk
from flexflow_tpu.obs import events
from flexflow_tpu.ops.recurrent_ops import (SelectiveScanMixerOp,
                                            selective_scan)
from rank_family import close, f32_ctx

ref = rf.reference("sambay_ref")

#: (sequence, chunk, channels, state, batch): two chunks of one group of
#: tokens; of two groups at the published state; a sequence that is no
#: whole number of chunks (40 = 2.5 x 16); two channel blocks (each
#: carries its own state, and the sums over the channels add up over
#: them); batch 2 (the state starts from zero in each row); a sequence
#: shorter than its chunk (one chunk of 24); three chunks of 32
SCANS = [(16, 8, 1024, 16, 1), (32, 16, 1024, 16, 1), (40, 16, 1024, 8, 1),
         (32, 16, 2048, 8, 1), (32, 16, 1024, 8, 2), (24, 64, 1024, 16, 1),
         (96, 32, 1024, 8, 1)]
BY_SCAN = pytest.mark.parametrize(
    "shape", SCANS, ids=lambda s: "x".join(map(str, s)))


def scan_inputs(seq, channels, state, batch, seed=0, strength=1.0):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, bm, cm = (draw(batch, seq, channels), draw(batch, seq, state),
                 draw(batch, seq, state))
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (batch, seq, channels)),
                     jnp.float32)
    a_log = jnp.asarray(
        np.log(rng.uniform(1.0, 16.0, (state, channels)) * strength),
        jnp.float32)
    return x, dt, a_log, bm, cm


def scalar(y):
    return jnp.sum(y * jnp.cos(y))


def by_scan(chunk, kernels):
    def f(*a):
        y, _ = selective_scan(a[0], a[1], -jnp.exp(a[2]), a[3], a[4], chunk,
                              kernels=kernels)
        return scalar(y), y
    return jax.jit(jax.value_and_grad(f, range(5), has_aux=True))


@jax.jit
def by_recurrence(*a):
    def f(*a):
        y = ref.recurrence(*a, jnp.zeros(a[0].shape[-1]))
        return scalar(y), y
    return jax.value_and_grad(f, range(5), has_aux=True)(*a)


@BY_SCAN
def test_the_scan_and_every_gradient_are_the_plain_paths(shape):
    seq, chunk, channels, state, batch = shape
    assert ssk.takes_kernel(min(chunk, seq), channels, state)
    args = scan_inputs(seq, channels, state, batch)
    (_, y1), g1 = by_scan(chunk, True)(*args)
    (_, y2), g2 = by_scan(chunk, False)(*args)
    close(y1, y2)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(b))) > 0
        close(a, b, 1e-3)


@BY_SCAN
def test_the_scan_and_every_gradient_are_the_recurrences(shape):
    seq, chunk, channels, state, batch = shape
    args = scan_inputs(seq, channels, state, batch, seed=1)
    (_, y1), g1 = by_scan(chunk, True)(*args)
    (_, y2), g2 = by_recurrence(*args)
    close(y1, y2)
    for a, b in zip(g1, g2):
        close(a, b, 1e-3)


@pytest.mark.parametrize("kernels", [True, False], ids=["kernel", "plain"])
def test_steps_whose_decay_underflows_still_agree(kernels):
    """``A`` a thousand times the published range: ``exp(dt A)`` is 0 in
    float32 for most steps (``dt A`` below -88). Every exponent is ``dt
    A`` <= 0, so nothing overflows: values and gradients are finite and
    the recurrence's."""
    args = scan_inputs(32, 1024, 8, 1, strength=1000.0)
    _, least = selective_scan(args[0], args[1], -jnp.exp(args[2]), args[3],
                              args[4], 16, kernels=kernels)
    assert float(least) < -88.0
    (_, y1), g1 = by_scan(16, kernels)(*args)
    (_, y2), g2 = by_recurrence(*args)
    close(y1, y2)
    for i in (0, 1, 3, 4):
        assert np.isfinite(np.asarray(g1[i])).all()
        close(g1[i], g2[i], 1e-3)


def test_the_least_exponent_is_the_plain_paths():
    args = scan_inputs(32, 1024, 8, 1)
    got = [selective_scan(args[0], args[1], -jnp.exp(args[2]), args[3],
                          args[4], 16, kernels=k)[1] for k in (True, False)]
    assert float(got[0]) == float(got[1]) < 0


def test_the_forward_keeps_the_state_each_chunk_starts_from():
    """Under differentiation the forward call also writes the state of
    every block at the start of each chunk (zeros at the first), a
    register a state entry; the call outside differentiation writes
    ``y`` alone."""
    seq, chunk, channels, state = 48, 16, 2048, 8
    x, dt, a_log, bm, cm = scan_inputs(seq, channels, state, 1)
    a = -jnp.exp(a_log)
    y, starts = ssk._fwd_call(x, dt, a, bm, cm, chunk, True, True)
    assert starts.shape == (1, 3, 2, state, 8, 128)
    assert float(jnp.max(jnp.abs(starts[:, 0]))) == 0.0

    def state_after(tokens):        # (N, D) by the recurrence
        def step(h, now):
            x_t, dt_t, b_t = now
            return jnp.exp(dt_t * a) * h + (dt_t * x_t) * b_t[:, None], None
        return jax.lax.scan(step, jnp.zeros((state, channels)),
                            (x[0, :tokens], dt[0, :tokens],
                             bm[0, :tokens]))[0]
    for m in (1, 2):
        want = state_after(m * chunk).reshape(state, 2, 8, 128)
        close(jnp.swapaxes(starts[0, m], 0, 1), want)
    (alone,) = ssk._fwd_call(x, dt, a, bm, cm, chunk, False, True)
    assert float(jnp.max(jnp.abs(alone - y))) == 0.0
    primal = str(jax.make_jaxpr(lambda *v: ssk.scan_chunks(*v, chunk))(
        x, dt, a, bm, cm))
    assert primal.count("pallas_call") == 1


def test_the_tiled_view_is_the_array_a_token_group_at_a_time():
    """Row ``8 g + r`` of group ``q`` is lanes ``128 g ..`` of token ``8
    q + r``, and back."""
    v = jnp.arange(2 * 16 * 2048, dtype=jnp.float32).reshape(2, 16, 2048)
    t = ssk._tiled(v)
    assert t.shape == (2, 2, 128, 128)
    for q, g, r in ((0, 0, 0), (1, 3, 5), (0, 15, 7)):
        want = v[:, 8 * q + r, 128 * g:128 * (g + 1)]
        assert float(jnp.max(jnp.abs(t[:, q, 8 * g + r] - want))) == 0.0
    assert float(jnp.max(jnp.abs(ssk._untiled(t) - v))) == 0.0


# ----------------------------------------------------------------------
# one layer through the op, down both paths
# ----------------------------------------------------------------------
E, INNER, N, R, TAPS, S = 16, 1024, 8, 3, 4, 40
LAYER = {"inner": INNER, "state": N, "dt_rank": R, "taps": TAPS,
         "chunk": 16, "memory_out": True}


def mixer_weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]),
                           jnp.float32)

    def u(lo, hi, *shape):
        return jnp.asarray(rng.uniform(lo, hi, shape), jnp.float32)
    return {"in_proj": w(E, 2 * INNER), "conv_w": u(-0.7, 0.7, INNER, TAPS),
            "conv_b": u(-0.5, 0.5, INNER), "x_proj": w(INNER, R + 2 * N),
            "dt_proj": w(R, INNER), "dt_bias": u(-3.0, 0.0, INNER),
            "A_log": jnp.log(u(1.0, 16.0, N, INNER)),
            "D": u(0.5, 1.5, INNER), "out_proj": w(INNER, E)}


def run_layer():
    """The layer's two outputs, the gradient of a scalar of both for the
    input and every weight, and what its instants said."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, S, E)),
                    jnp.float32)

    def layer(x, w):
        y, m = SelectiveScanMixerOp().emit(LAYER, [x], w, f32_ctx(), "ssm")
        return scalar(y) + jnp.sum(m * jnp.sin(m)), (y, m)

    events.enable()
    events.clear()
    try:
        (_, outs), grads = jax.jit(jax.value_and_grad(
            layer, (0, 1), has_aux=True))(x, mixer_weights())
        said = [ev for ev in events.events()
                if ev["name"] in ("ssm1.scan", "ssm1.kernel")]
    finally:
        events.disable()
        events.clear()
    return outs, grads, said


def test_a_layer_is_the_same_down_both_paths(monkeypatch):
    """One mixer at 1,024 channels of 8 state entries over two and a
    half chunks of 16: the outputs and the gradient of the input and of
    every weight with ``impl="kernel"`` against ``impl="plain"`` (the
    predicate stubbed to no), and what each path's instants say."""
    (y1, m1), (gx1, gw1), said = run_layer()
    assert [(ev["name"], ev["attrs"].get("impl"), ev["attrs"].get("kernel"))
            for ev in said] == [("ssm1.scan", "kernel", None),
                                ("ssm1.kernel", None, "fwd"),
                                ("ssm1.kernel", None, "bwd")]
    grid = said[1]["attrs"]
    assert (grid["layer"], grid["chunk"], grid["chunks"], grid["grid_steps"],
            grid["blocks"], grid["block_channels"], grid["state"]) == (
        "ssm", 16, 6, 6, 1, 1024, N)
    monkeypatch.setattr(ssk, "takes_kernel", lambda *a: False)
    jax.clear_caches()
    (y2, m2), (gx2, gw2), said = run_layer()
    assert [(ev["name"], ev["attrs"].get("impl")) for ev in said] == [
        ("ssm1.scan", "plain")]
    close(y1, y2)
    close(m1, m2)
    close(gx1, gx2, 1e-3)
    assert set(gw1) == set(gw2) == set(ref.MIXER)
    for k in gw2:
        assert float(jnp.max(jnp.abs(gw2[k]))) > 0, k
        close(gw1[k], gw2[k], 1e-3)


def test_the_op_on_a_mesh_of_several_devices_takes_the_plain_path():
    """The ``kernels=False`` convention of ``state_space_scan``: on a
    mesh of more than one device the layer stays plain XLA, which GSPMD
    partitions; a mesh of one device is no mesh."""
    x = jnp.zeros((2, S, E), jnp.float32)

    def traced(devices):
        ctx = f32_ctx()
        ctx.mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]),
                                     ("x",))
        events.enable()
        events.clear()
        try:
            jaxpr = str(jax.make_jaxpr(lambda x, w: SelectiveScanMixerOp(
            ).emit(LAYER, [x], w, ctx, "ssm"))(x, mixer_weights()))
            (said,) = [ev["attrs"]["impl"] for ev in events.events()
                       if ev["name"] == "ssm1.scan"]
        finally:
            events.disable()
            events.clear()
        return said, "pallas_call" in jaxpr
    assert traced(2) == ("plain", False)
    assert traced(1) == ("kernel", True)


def test_a_caller_on_a_mesh_gets_the_plain_path():
    args = scan_inputs(32, 1024, 8, 1)

    def scan(kernels):
        return str(jax.make_jaxpr(lambda *a: selective_scan(
            a[0], a[1], -jnp.exp(a[2]), a[3], a[4], 16,
            kernels=kernels))(*args))
    assert "pallas_call" in scan(True)
    plain = scan(False)
    assert "pallas_call" not in plain and "remat" in plain


@pytest.mark.parametrize("shape,takes", [
    ((64, 5120, 16), True),         # phi4_mini_flash_reasoning
    ((8, 1024, 16), True),          # one group of tokens, one block
    ((16, 1024, 8), True),
    ((64, 2048, 32), True),
    ((16, 64, 4), False),           # Phi4FlashRankConfig.tiny()
    ((16, 24, 4), False),           # tests/test_sambay.py's scan
    ((64, 5120 + 128, 16), False),  # whole lanes, no whole blocks
    ((64, 512, 16), False),         # half a block
    ((64, 5120, 4), False),         # a state under eight
    ((64, 5120, 12), False),
    ((64, 5120, 64), False),        # more registers than a block's walk
    ((60, 5120, 16), False),        # a chunk of no whole sublane tiles
    ((5, 1024, 16), False),
    ((8, 1024, 8), False),          # 64 sums a chunk: half a row of lanes
    ((256, 5120, 16), False),       # past what a backward step holds
    ((128, 2048, 16), True),
    ((0, 1024, 16), False),
])
def test_the_predicate_reads_the_shapes(shape, takes):
    assert ssk.takes_kernel(*shape) is takes
