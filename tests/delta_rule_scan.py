"""What ``tests/test_kda_kernel.py`` (a decay a channel) and
``tests/test_gdn_kernel.py`` (a decay a head) share to hold the scan
kernel pair of ``kernels/gated_delta_rule.py`` (interpret mode on the
CPU) to ``jax.lax.scan`` over ``recurrent_ops._chunk_step`` on the SAME
six terms: drawn terms, the plain scan, both paths' output, starting
states and six cotangents, computed once a case, and the bodies of the
four checks each file runs for its form of the decay.
"""
import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels import gated_delta_rule as kernel
from flexflow_tpu.obs import events
from flexflow_tpu.ops import recurrent_ops

D, CHUNK = 128, 64
NAMES = ("o", "d_W", "d_U0", "d_B", "d_q_decayed", "d_k_decayed", "d_decay")
#: (chunks, batch, heads): two groups of three chunks over two blocks of
#: five (batch x head) rows; five chunks, which no group but one divides;
#: two groups of four chunks of three rows
SHAPES = [(6, 2, 5), (5, 1, 2), (8, 1, 3)]
MDTS = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
#: float32 products are the same sums in another order; at bf16 the plain
#: scan's autodiff rounds each cotangent of a rounded operand to bf16
#: where the kernel keeps the float32 sum
TOLS = {"float32": 2e-5, "bfloat16": 2e-2}


def terms(n, b, h, mdt, by_head, seed=0):
    """Six terms of the kernels' shapes and types, chunk leading, with
    decays in (0.2, 1): a channel's (N, B, H, dk) or a head's (N, B, H,
    1)."""
    rng = np.random.default_rng(seed)

    def normal(*last, scale=0.1):
        return jnp.asarray(rng.normal(size=(n, b, h) + last).astype(
            np.float32) * scale)

    dec = rng.uniform(0.2, 1.0, (n, b, h, 1 if by_head else D))
    return [normal(CHUNK, D).astype(mdt), normal(CHUNK, D, scale=1.0),
            normal(CHUNK, CHUNK).astype(mdt), normal(CHUNK, D).astype(mdt),
            normal(CHUNK, D).astype(mdt), jnp.asarray(dec, jnp.float32)]


def plain_scan(mdt, *terms):
    """``gated_delta_rule``'s fallback on the terms: ``O`` (B, H, N C,
    dv) and the state each chunk starts from (N, B, H, dk, dv)."""
    w, u0 = terms[0], terms[1]

    def step(state, xs):
        after, out = recurrent_ops._chunk_step(mdt, state, xs)
        return after, (out, state)

    _, (out, starts) = jax.lax.scan(
        step, jnp.zeros(w.shape[1:3] + (w.shape[-1], u0.shape[-1]),
                        jnp.float32), terms)
    out = jnp.moveaxis(out, 0, 2)
    return out.reshape(out.shape[:2] + (-1,) + out.shape[4:]), starts


def both_paths(by_head):
    """(shape, mdt name) -> ((o, starts, six cotangents) by the kernels,
    the same by the plain scan), the cotangents those of a weighted sum
    of ``O``."""
    cache = {}

    def get(shape, mdt_name):
        if (shape, mdt_name) not in cache:
            mdt = MDTS[mdt_name]
            args = terms(*shape, mdt, by_head, seed=sum(shape))
            n, b, h = shape
            mix = jnp.asarray(np.random.default_rng(9).normal(
                size=(b, h, n * CHUNK, D)).astype(np.float32))

            def graded(fn):
                def loss(*a):
                    o, starts = fn(*a)
                    return jnp.sum(o * mix), (o, starts)
                grads, (o, starts) = jax.jit(jax.grad(
                    loss, argnums=range(6), has_aux=True))(*args)
                return (o, starts) + tuple(grads)

            cache[shape, mdt_name] = (
                graded(lambda *a: kernel.scan_chunks(*a)),
                graded(lambda *a: plain_scan(mdt, *a)))
        return cache[shape, mdt_name]
    return get


def close(got, want, tol):
    got, want = (np.asarray(x.astype(jnp.float32), np.float64)
                 for x in (got, want))
    assert np.all(np.isfinite(got))
    err = float(np.max(np.abs(got - want))) \
        / max(float(np.max(np.abs(want))), 1e-6)
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def check_against_the_plain_scan(scanned, shape, mdt, what):
    """Entry ``what`` of ``NAMES``: shape, type and values."""
    got, want = scanned(shape, mdt)
    got, want = (x[:1] + x[2:] for x in (got, want))
    assert got[what].shape == want[what].shape
    assert got[what].dtype == want[what].dtype
    assert float(jnp.max(jnp.abs(want[what].astype(jnp.float32)))) > 0
    close(got[what], want[what], TOLS[mdt])


def check_the_starting_states(scanned, shape, mdt):
    """What the backward reads: the plain scan's carried states,
    transposed (the kernel holds ``S^T``, the decay along the lanes);
    the first is zero."""
    (_, got, *_), (_, want, *_) = scanned(shape, mdt)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got[0]))) == 0
    close(jnp.swapaxes(got, -1, -2), want, TOLS[mdt])


#: (batch x head rows, chunks) -> (rows, chunks) a grid step takes
STEPS = [(8, 4, (8, 4)), (10, 6, (5, 3)), (3, 5, (3, 1)),
         (32, 128, (8, 4))]


def check_what_the_scan_says(by_head, scope, heads, chunks, steps):
    """One ``<scope>.kernel`` instant a call of the scan, forward and
    backward: the rows and chunks a grid step takes (divisors of what
    there is), the grid, a working set within the kernels' limit; the
    residuals are the six terms and the starting states."""
    one = terms(1, 1, 1, jnp.bfloat16, by_head)
    args = [jax.ShapeDtypeStruct((chunks, 1, heads) + a.shape[3:], a.dtype)
            for a in one]
    events.enable()
    events.clear()
    try:
        jax.eval_shape(jax.grad(
            lambda *a: jnp.sum(kernel.scan_chunks(
                *a, scope=scope, layer="l_3")[0]), argnums=range(6)), *args)
        seen = [e["attrs"] for e in events.events()
                if e["name"] == scope + ".kernel"]
    finally:
        events.disable()
        events.clear()
    assert [k["kernel"] for k in seen] == ["scan_fwd", "scan_bwd"]
    for k in seen:
        assert k["layer"] == "l_3" and k["chunk"] == CHUNK
        assert (k["heads_per_step"], k["chunks_per_step"]) == steps
        assert k["chunks"] == heads * chunks
        assert k["grid_steps"] == heads * chunks // (steps[0] * steps[1])
        assert 0 < k["vmem_bytes"] < kernel.SCAN_VMEM_LIMIT
    if heads * chunks > 64:         # the counts alone: nothing is run
        return
    flat = [a.reshape((chunks, heads) + a.shape[3:])
            for a in terms(chunks, 1, heads, jnp.bfloat16, by_head)]
    flat[5] = flat[5].reshape(chunks, heads, 1, -1)
    (o, starts), res = kernel._scan_fwd(*flat, *steps, scope, None, True)
    assert o.shape == (heads, chunks * CHUNK, D)
    assert len(res) == 7 and res[6] is starts
    for r, a in zip(res, flat):
        assert r is a


def check_under_a_mesh(by_head, by):
    """Two sequences, or two heads, one a device: the pair runs under
    ``shard_map`` over the batch and head entries of the spec, every
    (batch, head) carrying a state of its own."""
    from jax.sharding import Mesh, PartitionSpec as P
    args = terms(3, 2, 2, jnp.float32, by_head, seed=11)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    spec = P("x", None) if by == "batch" else P(None, "x")

    def loss(mesh, spec, *a):
        o, _ = kernel.scan_chunks(*a, mesh=mesh, spec=spec)
        return jnp.sum(o ** 2)

    want = jax.jit(jax.value_and_grad(
        lambda *a: loss(None, None, *a), argnums=range(6)))(*args)
    got = jax.jit(jax.value_and_grad(
        lambda *a: loss(mesh, spec, *a), argnums=range(6)))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 2e-5)
