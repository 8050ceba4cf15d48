"""The chain between a delta-rule layer's q / k / v projections and the
recurrence's kernels timed ALONE on the chip (run on a real TPU): the
causal taps, the SiLU, the unit length with its scale and the turn to
heads-first, from the projection's float32 product, down the plain path
(``ops/nn_ops.py::short_conv``, ``jax.nn.silu`` and
``ops/recurrent_ops.py::_unit``, as ``GatedDeltaRuleOp.projections``
writes them) and down ``kernels/delta_mix.py``, at the shapes of the
benchmark's two cells that run the layer: ``--cell 5``
(``kimi_linear_48b_a3b``: 4,096 tokens, 32 heads of 128 for q, k and v)
and ``--cell 10`` (``qwen3_next_80b_a3b``: 8,192 tokens, 16 heads of 128
for q and k, 32 for v).

Each path runs ``--calls`` (sixteen; eight where the array is 128 MiB:
sixteen chains' temporaries do not fit beside their results) calls on
operands of their own in ONE jit, twice: the forward alone (the layer's first and second run)
and the forward with its backward from a given heads-first cotangent
(value and gradient). Times are the device's own clock (a profiler
trace of the jit: all device ops' durations, and by op name) beside the
host's; one JSON line a timing with ms a call, the GB/s against the
bytes' floor (a forward reads and writes the array once, a backward
reads two and writes one) and the largest differences between the paths.
``--tiles`` times the kernels at other tiles than the derived ones
(``block_t:heads`` pairs).

    python3 examples/tpu_time_delta_mix.py --cell 5
"""
import argparse
import functools
import json
import os
import sys
import tempfile
import time

#: cell -> (tokens, taps, ((part, heads, head size, unit), ..))
SHAPES = {
    "5": ("kimi_linear_48b_a3b", 4096, 4,
          (("wq", 32, 128, True), ("wv", 32, 128, False))),
    "10": ("qwen3_next_80b_a3b", 8192, 4,
           (("wq", 16, 128, True), ("wv", 32, 128, False))),
}
EPS = 1e-6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(SHAPES, key=int), nargs="*",
                    default=sorted(SHAPES, key=int))
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="block_t:heads_per_step pairs to time besides "
                         "the derived tiles")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.kernels import delta_mix as dmk
    from flexflow_tpu.ops.nn_ops import short_conv
    from flexflow_tpu.ops.recurrent_ops import _unit

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = [SHAPES[c] for c in args.cell] if on_chip else [
        ("rehearsal", 40, 4, (("wq", 2, 128, True), ("wv", 2, 128, False)))]
    calls = args.calls if on_chip else 2

    def timed(fn, operands, n):
        """(host ms, device ms, device ms by op name a call) of ONE run
        of the jit of ``n`` calls, after one that compiles."""
        # (the first call's results alone are kept: sixteen calls' are
        # gigabytes)
        first = jax.block_until_ready(fn(*operands))[0]     # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        host = (time.perf_counter() - t0) * 1e3
        if not on_chip:
            return first, host, None, {}
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                jax.block_until_ready(fn(*operands))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        by_name = {}
        for ops in ev["devices"].values():
            for name, _, dur in ops:
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                by_name[name] = by_name.get(name, 0.0) + dur / 1e6
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:8])
        return first, host, sum(by_name.values()), {
            k: round(v / n, 4) for k, v in top.items()}

    for cell, t, k, parts in shapes:
        for part, h, d, unit in parts:
            scale = d ** -0.5 if unit else 1.0
            size = 4 * t * h * d
            n = max(1, min(calls, 2 ** 30 // size))

            def plain(p, taps):
                z = jax.nn.silu(jax.vmap(short_conv, (1, 0), 1)(
                    jnp.moveaxis(p, 2, 1), taps))
                return _unit(z) * scale if unit else z

            def kernel(tile, p, taps):
                return dmk.delta_mix(p, taps, unit=unit, scale=scale,
                                     eps=EPS, block_t=tile[0],
                                     heads_per_step=tile[1])

            # the operands made on the device, each call its own
            keys = jax.random.split(jax.random.PRNGKey(63), n + 2)
            ps = [jax.jit(lambda key: jax.random.normal(
                key, (1, t, h, d), jnp.float32))(key) for key in keys[2:]]
            taps = jax.random.normal(keys[0], (h, d, k), jnp.float32) * 0.5
            ct = jax.random.normal(keys[1], (1, h, t, d), jnp.float32)
            paths = [("plain", plain)]
            if dmk.takes_kernel(d, k, t, jnp.float32):
                tiles = [(None, None)] + [
                    tuple(int(v) for v in s.split(":")) for s in args.tiles]
                paths += [("kernel" if s == (None, None) else
                           f"kernel@{s[0]}:{s[1]}",
                           functools.partial(kernel, s)) for s in tiles]
            got = {}
            for path, fn in paths:
                def forward(taps, ct, *ps, _fn=fn):
                    return [_fn(p, taps) for p in ps]

                def both(taps, ct, *ps, _fn=fn):
                    out = []
                    for p in ps:
                        y, pull = jax.vjp(_fn, p, taps)
                        out.append((y,) + pull(ct))
                    return out
                for what, f, passes in (("forward", forward, 2),
                                        ("forward+backward", both, 5)):
                    out, host, device, by_name = timed(
                        jax.jit(f), (taps, ct, *ps), n)
                    ms = device / n if device is not None else None
                    print(json.dumps(dict(
                        cell=cell, part=part, path=path, what=what,
                        tokens=t, heads=h, head_dim=d, taps=k, unit=unit,
                        calls=n, device=dev.device_kind,
                        tiles=[dmk.tiles(kk, t, h, d, k)
                               for kk in ("fwd", "bwd")],
                        array_mib=size / 2 ** 20,
                        host_ms_a_call=host / n, device_ms_a_call=ms,
                        floor_ms_a_call=passes * size / 819e9 * 1e3,
                        gb_per_s_against_floor=(
                            passes * size / ms / 1e6 if ms else None),
                        by_name_ms_a_call=by_name)), flush=True)
                    if what == "forward+backward":
                        got[path] = out
            if "kernel" in got:
                far = {}
                for name, a, c in zip(("out", "dp", "dtaps"),
                                      got["plain"], got["kernel"]):
                    a, c = (np.asarray(v, np.float64) for v in (a, c))
                    far[name] = float(np.max(np.abs(a - c))
                                      / max(np.max(np.abs(a)), 1e-9))
                print(json.dumps(dict(
                    cell=cell, part=part,
                    kernel_against_plain_relative=far)), flush=True)
            del ps, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
