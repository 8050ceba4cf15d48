"""The state-space recurrence timed ALONE on the chip (run on a real
TPU), piece by piece, down the plain path
(``ops/recurrent_ops.py::_ssm_chunks`` on XLA) and down
``kernels/state_space.py``, at the shape of the benchmark's cell that
runs it: ``granite_4_0_h_micro`` (1 x 4,096 tokens, 64 heads of 64, a
state of 128, chunks of 256, bf16 operands).

Each piece is jitted by itself twice, the forward alone and the forward
with its backward from given float32 cotangents:

  terms     the plain path's ``_ssm_chunks``: ``inside`` and ``added``
  added     its ``added`` alone
  states    its ``lax.scan`` over the chunk states and the product that
            reads them
  chunks    the kernels alone (``scan_chunks``: all of the three above),
            on operands that lie channels first as the kernels read them
  scan      all of ``state_space_scan``, either path, from ``x`` (B, T,
            H, P): on the kernel path with the turns to channels first
            and back, which the compiled layer does not run (its
            projection writes ``x`` tokens last)

Times are the host's clock over ``--calls`` calls after one that
compiles, and the device's own clock (a profiler trace of the same
calls: all device ops' durations, and by op name). One JSON line a
timing, then the largest differences between the paths' results.

    python3 examples/tpu_time_state_space_scan.py
"""
import argparse
import json
import os
import sys
import tempfile
import time

#: (cell, batch, tokens, heads, head size, state, chunk)
SHAPES = (("granite_4_0_h_micro", 1, 4096, 64, 64, 128, 256),)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.kernels import state_space as ssk
    from flexflow_tpu.ops.recurrent_ops import (_in_chunks, _ssm_chunks,
                                                state_space_scan)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = SHAPES if on_chip else (("rehearsal", 1, 256, 2, 64, 128, 128),)
    mdt = jnp.bfloat16
    f32 = jnp.float32

    def timed(fn, operands):
        """(result, host ms a call, device ms a call, ms by op name)."""
        out = jax.block_until_ready(fn(*operands))          # compiles
        t0 = time.perf_counter()
        for _ in range(args.calls):
            jax.block_until_ready(fn(*operands))
        host = (time.perf_counter() - t0) / args.calls * 1e3
        if not on_chip:
            return out, host, None, {}
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                for _ in range(args.calls):
                    jax.block_until_ready(fn(*operands))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        by_name = {}
        for ops in ev["devices"].values():
            for name, _, dur in ops:
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                by_name[name] = by_name.get(name, 0.0) \
                    + dur / args.calls / 1e6
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:8])
        return out, host, sum(by_name.values()), {
            k: round(v, 4) for k, v in top.items()}

    rng = np.random.default_rng(56)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), f32)

    for cell, b, t, h, p, n, chunk in shapes:
        # the step sizes and decays of the cell's seeds: 0.01-0.15 a
        # token under A in (-16, -1), a chunk's log-decays to -370
        x, bm, cm = draw(b, t, h, p), draw(b, t, n), draw(b, t, n)
        dt = jnp.asarray(rng.uniform(0.01, 0.15, (b, t, h)), f32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), f32)
        dt_c = _in_chunks(dt, chunk, axis=1)
        big_g = jnp.cumsum(dt_c * a, axis=2)
        terms_in = (_in_chunks(x, chunk, axis=1) * dt_c[..., None],
                    _in_chunks(bm, chunk, axis=1),
                    _in_chunks(cm, chunk, axis=1), big_g)
        m = big_g.shape[1]

        def plain_terms(*v):
            return _ssm_chunks(mdt, *v)

        def kernel_chunks(*v):
            return (ssk.scan_chunks(*v, chunk, mdt)[0],)

        def plain_added(dtx, bm_c, big_g):
            return (jnp.einsum(
                "bmjhp,bmjn->bmhpn",
                (dtx * jnp.exp(big_g[:, :, -1:] - big_g)[..., None]
                 ).astype(mdt), bm_c.astype(mdt),
                preferred_element_type=f32),)

        def states(added, cm_c, big_g):
            whole = jnp.exp(big_g[:, :, -1])

            def step(state, now):
                keeps, adds = now
                return keeps[..., None, None] * state + adds, state
            _, starts = jax.lax.scan(
                step, jnp.zeros(added.shape[:1] + added.shape[2:], f32),
                (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
            return (jnp.einsum("bmin,mbhpn->bmihp", cm_c.astype(mdt),
                               starts.astype(mdt),
                               preferred_element_type=f32)
                    * jnp.exp(big_g)[..., None],)

        def scan(kernels):
            def fn(x, dt, a, bm, cm):
                return (state_space_scan(x, dt, a, bm, cm, chunk, mdt,
                                         kernels=kernels)[0],)
            return fn

        def tokens_last(v):     # (B, M, C, ..) -> (B, .., T)
            return jnp.moveaxis(v.reshape((b, t) + v.shape[3:]), 1, -1)

        pieces = [
            ("terms", "plain", plain_terms, terms_in),
            ("added", "plain", plain_added, terms_in[:2] + terms_in[3:]),
            ("states", "plain", states, (draw(b, m, h, p, n),)
             + terms_in[2:]),
            ("scan", "plain", scan(False), (x, dt, a, bm, cm))]
        if ssk.takes_kernel(chunk, h, p, n):
            pieces += [
                ("chunks", "kernel", kernel_chunks,
                 (jnp.moveaxis(x, 1, -1), tokens_last(dt_c),
                  tokens_last(big_g), bm, cm)),
                ("scan", "kernel", scan(True), (x, dt, a, bm, cm))]
        got, drawn = {}, {}
        for piece, path, fn, operands in pieces:
            if piece not in drawn:      # the paths of a piece: the same
                drawn[piece] = tuple(
                    draw(*o.shape) for o in jax.eval_shape(fn, *operands))
            n_ops = len(operands)

            def both(*ops, _fn=fn, _n=n_ops):
                out, pull = jax.vjp(_fn, *ops[:_n])
                return out, pull(tuple(ops[_n:]))
            for what, f, ops in (
                    ("forward", fn, operands),
                    ("forward+backward", both, operands + drawn[piece])):
                out, host, device, by_name = timed(jax.jit(f), ops)
                print(json.dumps(dict(
                    cell=cell, piece=piece, path=path, what=what,
                    tokens=b * t, heads=h, head_dim=p, state=n, chunk=chunk,
                    heads_per_step=ssk.heads_per_block(h, p),
                    device=dev.device_kind, host_ms_a_call=host,
                    device_ms_a_call=device, by_name=by_name)), flush=True)
            got[piece, path] = out
        if ("scan", "kernel") in got:
            (y1, g1), (y2, g2) = got["scan", "plain"], got["scan", "kernel"]
            far = {}
            for name, u, v in zip(("y", "d_x", "d_dt", "d_a", "d_B", "d_C"),
                                  tuple(y1) + tuple(g1),
                                  tuple(y2) + tuple(g2)):
                u, v = (np.asarray(w, np.float64) for w in (u, v))
                far[name] = float(np.max(np.abs(u - v))
                                  / max(np.max(np.abs(u)), 1e-9))
            print(json.dumps(dict(cell=cell,
                                  kernel_against_plain_relative=far)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
