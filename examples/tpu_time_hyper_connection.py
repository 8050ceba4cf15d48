"""The hyper-connection nodes timed ALONE on the chip, at the width the
benchmark's sixth cell runs them at (run on a real TPU).

One sub-layer's ``pre`` node and ``post`` node of ``ops/hyper_ops.py``
over ``--tokens`` tokens of 4 x 3584 float32 streams, each jitted by
itself, forward and forward + backward (fixed cotangents for every
output, gradients of every input and weight), down the plain path and
down the kernels'; then each of the four Pallas calls of
``kernels/hyper_connection.py`` by itself on ``(n, tokens, C)`` streams,
at the tile the shapes give and at each of ``--tiles``. Times are the
device's own clock (a profiler trace of ``--calls`` calls: all device
ops' durations, and by op name), not the host's: a stand-alone jit of a
node also turns the four-axis operand it is handed into the kernels'
stream-major view and back, which the model's step does not (there XLA
lays the four-axis array stream-major itself), so the nodes' lines say
``copy`` apart. One JSON line a timing, and the largest difference
between the two paths' outputs and gradients.

    python3 examples/tpu_time_hyper_connection.py [--tiles 64 128 256]
"""
import argparse
import json
import os
import sys
import tempfile

N, C = 4, 3584
PARAMS = dict(stage="pre", iters=20, eps=1e-6, norm_eps=1e-6,
              clamp=[-30.0, 30.0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tiles", type=int, nargs="*", default=[])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.kernels import hyper_connection as hck
    from flexflow_tpu.ops.hyper_ops import HyperConnectionOp

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    n, c, tokens = (N, C, args.tokens) if on_chip else (4, 128, 64)
    kp = hck.stats_width(n)

    def device_ms(fn, operands):
        """ms a call on the device's own clock: all ops, and by name."""
        if not on_chip:
            return None, {}
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
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:6])
        return sum(by_name.values()), {k: round(v, 4) for k, v in
                                       top.items()}

    def line(what, path, fn, operands, **more):
        jax.block_until_ready(fn(*operands))            # compiles
        total, by_name = device_ms(fn, operands)
        print(json.dumps(dict(what=what, path=path, tokens=tokens,
                              device=dev.device_kind,
                              device_ms_a_call=total, by_name=by_name,
                              **more)), flush=True)

    rng = np.random.default_rng(42)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    op = HyperConnectionOp()
    w = {s.name: arr(*s.shape, scale=0.02 if s.name == "phi" else 0.5)
         for s in op.weights(PARAMS, [(1, tokens, n, c)],
                             [DataType.DT_FLOAT])}
    w["alpha"] = jnp.ones(3, jnp.float32)
    w["b_res"] = w["b_res"] + 2 * jnp.eye(n)
    x, cx = arr(1, tokens, n, c), arr(1, tokens, n, c)
    y, cu = arr(1, tokens, c), arr(1, tokens, c)
    cm = arr(1, tokens, n + n * n)

    class Ctx:
        kv_mode = None
        mesh = None

        def count(self, key, value):
            pass

    def nodes():
        """The two nodes and a loss over every output of each: made anew
        for each path, because ``jax.jit`` keeps its traces by
        function."""
        def pre(x, w):
            return tuple(op.emit(PARAMS, [x], w, Ctx(), "res_pre"))

        def pre_loss(x, w):
            u, maps, xs = pre(x, w)
            return jnp.sum(u * cu) + jnp.sum(maps * cm) + jnp.sum(xs * cx)

        def post(x, y, maps):
            return op.emit({"stage": "post"}, [x, y, maps], {}, Ctx(),
                           "res")[0]

        def post_loss(x, y, maps):
            return jnp.sum(post(x, y, maps) * cx)
        return pre, pre_loss, post, post_loss

    maps = jax.jit(nodes()[0])(x, w)[1]
    results = {}
    takes = hck.takes_kernel
    for path in ("plain", "kernel"):
        hck.takes_kernel = takes if path == "kernel" \
            else (lambda *a: False)
        pre, pre_loss, post, post_loss = nodes()
        try:
            fns = {"pre fwd": (jax.jit(pre), (x, w)),
                   "pre fwd+bwd": (jax.jit(jax.grad(pre_loss, (0, 1))),
                                   (x, w)),
                   "post fwd": (jax.jit(post), (x, y, maps)),
                   "post fwd+bwd": (jax.jit(jax.grad(post_loss, (0, 1, 2))),
                                    (x, y, maps))}
            for what, (fn, operands) in fns.items():
                line(what, path, fn, operands)
                results[what, path] = jax.tree.leaves(fn(*operands))
        finally:
            hck.takes_kernel = takes
    for what in ("pre fwd", "pre fwd+bwd", "post fwd", "post fwd+bwd"):
        far = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(results[what, "kernel"],
                               results[what, "plain"])]
        print(json.dumps(dict(what=what, kernel_against_plain=far)),
              flush=True)
    del results

    # each call alone on the kernels' own view, no edge to turn
    x2, g2 = hck._stream_major(x), hck._stream_major(cx)
    y2, m2 = y.reshape(tokens, c), maps.reshape(tokens, -1)
    phi_t, gate = hck.pre_operands(w["phi"], w["alpha"][0], w["b_pre"])
    interpret = not on_chip
    stats = hck._pre_fwd_call(x2, phi_t, gate, n, 1e-6, hck.tile_tokens(
        "pre_fwd", n, c, tokens), interpret)[1]
    ds = arr(tokens, kp)
    calls = {
        "pre_fwd": (lambda t: jax.jit(lambda *a: hck._pre_fwd_call(
            *a, n, 1e-6, t, interpret)), (x2, phi_t, gate)),
        "post_fwd": (lambda t: jax.jit(lambda *a: hck._post_fwd_call(
            *a, n, t, interpret)), (x2, y2, m2)),
        "post_bwd": (lambda t: jax.jit(lambda *a: hck._post_bwd_call(
            *a, n, t, interpret)), (g2, x2, y2, m2)),
        "pre_bwd": (lambda t: jax.jit(lambda *a: hck._pre_bwd_call(
            *a, n, t, interpret)), (x2, y2, g2, stats, ds, phi_t, gate))}
    for kernel, (make, operands) in calls.items():
        own = hck.tile_tokens(kernel, n, c, tokens)
        for tile in [own] + [t for t in args.tiles
                             if t != own and tokens % t == 0]:
            try:
                line("hyper_connection_" + kernel, "kernel", make(tile),
                     operands, tile=tile, own_tile=tile == own,
                     vmem_bytes=hck.vmem_bytes(kernel, n, c, tile))
            except Exception as e:      # noqa: BLE001 - Mosaic's refusal
                print(json.dumps(dict(
                    what="hyper_connection_" + kernel, tile=tile,
                    refused=str(e)[:300])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
