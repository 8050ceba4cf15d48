"""The chain between an attention layer's q / k projections and the flash
kernels timed ALONE on the chip (run on a real TPU): the projections'
products, the heads' RMSNorm, the rotary embedding, the cast to bf16 and
the turn to heads-first, down the plain path (``ops/nn_ops.py::_rms``,
``_apply_rope`` and ``ops/sparse_attention``'s ``heads_first``) and down
``kernels/qk_norm_rope.py``, at the shapes of the benchmark's cells that
have q/k norms and a rotary embedding: ``keye_vl2_30b_a3b`` (8,192
tokens, 32 heads on 4 of 128: the kernels take it) and ``lfm2_24b_a2b``
(8,192 tokens, 32 on 8 of 64: plain only, the kernels take no head of 64).

Each path is jitted by itself twice: the forward alone (q and k
heads-first, as the block's first run makes them) and the forward with
its backward from given bf16 heads-first cotangents, as the flash
backward kernels hand them over (the block's second run and backward).
Times are the host's clock over ``--calls`` calls after one that
compiles, and the device's own clock (a profiler trace of the same
calls: all device ops' durations, and by op name). ``--tiles`` times the
kernels at other tiles than the derived ones (``block_s:heads`` pairs).
One JSON line a timing, and the largest differences between the paths.

    python3 examples/tpu_time_qk_norm_rope.py
"""
import argparse
import functools
import json
import os
import sys
import tempfile
import time

#: (cell, batch, tokens, hidden, heads, kv heads, head size, theta)
SHAPES = (("keye_vl2_30b_a3b", 1, 8192, 2048, 32, 4, 128, 1e7),
          ("lfm2_24b_a2b", 1, 8192, 2048, 32, 8, 64, 1e6))
EPS = 1e-6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="block_s:heads_per_step pairs to time besides "
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
    from flexflow_tpu.kernels import qk_norm_rope as nrk
    from flexflow_tpu.ops.nn_ops import _apply_rope, _rms

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = SHAPES if on_chip else (
        ("rehearsal", 2, 64, 64, 4, 2, 128, 1e4),
        ("rehearsal_64", 2, 64, 64, 4, 2, 64, 1e4))
    mdt = jnp.bfloat16

    def timed(fn, operands):
        """(host ms a call, device ms a call, device ms by op name)."""
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

    rng = np.random.default_rng(50)
    for cell, b, s, e, h, kv, d, theta in shapes:
        def proj(x, w):
            return jnp.einsum("ble,ehd->blhd", x.astype(mdt), w.astype(mdt),
                              preferred_element_type=jnp.float32)

        def plain(x, wq, wk, qn, kn, pos):
            def heads_first(y):
                y = jnp.repeat(y, h // y.shape[2], axis=2) \
                    if y.shape[2] != h else y
                return jnp.swapaxes(y, 1, 2).astype(mdt)
            return tuple(
                heads_first(_apply_rope(_rms(proj(x, w), n, EPS), pos, theta))
                for w, n in ((wq, qn), (wk, kn)))

        def kernel(tile, x, wq, wk, qn, kn, pos):
            tables = nrk.rope_tables(pos, d, theta)
            return tuple(
                nrk.qk_norm_rope(proj(x, w), n, tables, eps=EPS, dtype=mdt,
                                 repeat=h // w.shape[1], block_s=tile[0],
                                 heads_per_step=tile[1])
                for w, n in ((wq, qn), (wk, kn)))

        x = jnp.asarray(rng.standard_normal((b, s, e)), jnp.float32)
        wq = jnp.asarray(rng.standard_normal((e, h, d)) * e ** -0.5,
                         jnp.float32)
        wk = jnp.asarray(rng.standard_normal((e, kv, d)) * e ** -0.5,
                         jnp.float32)
        qn, kn = (jnp.asarray(rng.uniform(0.5, 1.5, (d,)), jnp.float32)
                  for _ in range(2))
        pos = jnp.asarray(np.tile(np.arange(s, dtype=np.int32), (b, 1)))
        cts = tuple(jnp.asarray(rng.standard_normal((b, h, s, d)), mdt)
                    for _ in range(2))
        operands = (x, wq, wk, qn, kn)

        paths = [("plain", plain)]
        if nrk.takes_kernel(s, h, d, 1, mdt) \
                and nrk.takes_kernel(s, kv, d, h // kv, mdt):
            tiles = [(None, None)] + [tuple(int(v) for v in t.split(":"))
                                      for t in args.tiles]
            paths += [("kernel" if t == (None, None) else
                       f"kernel@{t[0]}:{t[1]}",
                       functools.partial(kernel, t)) for t in tiles]
        got = {}
        for path, fn in paths:
            def forward(*ops, _fn=fn):
                return _fn(*ops, pos)

            def both(*ops, _fn=fn):
                out, pull = jax.vjp(lambda *o: _fn(*o, pos), *ops)
                return out, pull(cts)
            for what, f in (("forward", forward),
                            ("forward+backward", both)):
                out, host, device, by_name = timed(jax.jit(f), operands)
                print(json.dumps(dict(
                    cell=cell, path=path, what=what, tokens=b * s, heads=h,
                    kv_heads=kv, head_dim=d, device=dev.device_kind,
                    tiles_q=[nrk.tiles(k, s, h, d, 1, mdt)
                             for k in ("fwd", "bwd")],
                    tiles_k=[nrk.tiles(k, s, kv, d, h // kv, mdt)
                             for k in ("fwd", "bwd")],
                    host_ms_a_call=host, device_ms_a_call=device,
                    by_name=by_name)), flush=True)
            got[path] = out
        if "kernel" in got:
            (y1, g1), (y2, g2) = got["plain"], got["kernel"]
            names = ("q", "k", "dx", "dwq", "dwk", "dq_norm", "dk_norm")
            far = {}
            for name, a, c in zip(names, y1 + g1, y2 + g2):
                a, c = (np.asarray(v, np.float64) for v in (a, c))
                far[name] = float(np.max(np.abs(a - c))
                                  / max(np.max(np.abs(a)), 1e-9))
            print(json.dumps(dict(cell=cell,
                                  kernel_against_plain_relative=far)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
