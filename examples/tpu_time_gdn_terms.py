"""The gated delta rule's pieces timed ALONE on the chip (run on a real
TPU), down the plain path (``ops/recurrent_ops.py`` on XLA: the chunks'
terms with four ``(C, C)`` float32 matrices a head-chunk in HBM and a
triangular solve, then a ``lax.scan`` over the chunk states) and down the
kernels of ``kernels/gated_delta_rule.py``, at the shapes of the
benchmark's two cells that run it: ``qwen3_next_80b_a3b`` (1 x 8,192
tokens, 16 q/k heads under 32 value heads of 128, 128 chunks of 64, a
decay a head) and ``kimi_linear_48b_a3b`` (1 x 4,096 tokens, 32 heads of
128, 64 chunks, a decay a channel), bf16 operands.

Each piece is jitted by itself twice, the forward alone and the forward
with its backward from given cotangents:

  scan         the recurrence over the chunk states ALONE, on the six
               terms the kernels left: the ``lax.scan`` over
               ``_chunk_step`` (its body rematerialised, as the op runs
               it) against the scan kernel pair
  terms        the seven terms of every chunk (``W``, ``U0``, ``B``, ``q
               exp(G)``, ``k exp(G_C - G)``, ``exp(G_C)``, the least
               ``G``), either path
  recurrence   all of ``gated_delta_rule``: the terms and the scan,
               either path

Times are the host's clock over ``--calls`` calls after one that
compiles, and the device's own clock (a profiler trace of the same
calls: all device ops' durations, and by op name). One JSON line a
timing, then the largest differences between the paths' results.

    python3 examples/tpu_time_gdn_terms.py [--pieces scan] \
        [--chunks-per-step 8 16] [--scan-steps 8:2 4:4]
"""
import argparse
import json
import os
import sys
import tempfile
import time

#: (cell, batch, tokens, q/k heads, value heads, head size, chunk, whose
#: the decay is)
SHAPES = (("qwen3_next_80b_a3b", 1, 8192, 16, 32, 128, 64, "head"),
          ("kimi_linear_48b_a3b", 1, 4096, 32, 32, 128, 64, "channel"))
PIECES = ("scan", "terms", "recurrence")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--chunks-per-step", type=int, nargs="*", default=[],
                    help="further values of HEAD_CHUNKS_PER_STEP to time "
                         "the kernels' terms at")
    ap.add_argument("--pieces", nargs="*", default=list(PIECES),
                    choices=PIECES)
    ap.add_argument("--scan-steps", nargs="*", default=[],
                    help="further HEADS:CHUNKS a grid step of the scan "
                         "kernels to time them at")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.kernels import gated_delta_rule as gdk
    from flexflow_tpu.ops import recurrent_ops
    from flexflow_tpu.ops.recurrent_ops import (_chunk_terms,
                                                _chunk_terms_head,
                                                _in_chunks, _plain_scan,
                                                gated_delta_rule)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = SHAPES if on_chip else (
        ("rehearsal", 1, 256, 1, 2, 128, 64, "head"),
        ("rehearsal", 1, 256, 2, 2, 128, 64, "channel"))
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

    rng = np.random.default_rng(58)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), f32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    for cell, b, t, hk, h, d, chunk, decay in shapes:
        # the decays of the cell's seeds: A in (1e-4, 16) a head, a step
        # of 1e-3 to 1e-1 a token; a chunk's log-decays to -100 and past
        by_head = decay == "head"
        q, k, v = unit(draw(b, hk, t, d)) * d ** -0.5, \
            unit(draw(b, hk, t, d)), draw(b, h, t, d)
        a_head = rng.uniform(1e-4 if by_head else 1.0, 16.0, (1, h, 1))
        g = jnp.asarray(-a_head * np.exp(rng.uniform(
            np.log(1e-3), np.log(1e-1), (b, h, t))), f32)
        if not by_head:
            g = g[..., None] * jnp.asarray(
                rng.uniform(0.5, 1.0, (b, h, t, d)), f32)
        beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, h, t)), f32)
        operands = (q, k, v, g, beta)
        group = h // hk
        takes = gdk.takes_head_kernel(chunk, d, d, group) if by_head \
            else gdk.takes_kernel(chunk, d, d)
        plain_fn = _chunk_terms_head if by_head else _chunk_terms
        kernel_fn = gdk.head_chunk_terms if by_head else gdk.chunk_terms
        predicate = "takes_head_kernel" if by_head else "takes_kernel"
        scope = "gdn" if by_head else "kda"

        def plain_terms(*a):
            *terms, _ = plain_fn(*(_in_chunks(x, chunk) for x in a), mdt)
            return tuple(jnp.moveaxis(x, 2, 0) for x in terms)

        def kernel_terms(*a):
            return tuple(kernel_fn(*a, chunk, mdt)[:6])

        def plain_scan(*terms):     # as ``gated_delta_rule`` runs it
            return (_plain_scan(terms, mdt),)

        def kernel_scan(*terms):
            return (gdk.scan_chunks(*terms, scope=scope)[0],)

        def recurrence(*a):
            return (gated_delta_rule(*a, chunk, mdt)[0],)

        def run(piece, path, fn, drawn, operands=operands, **more):
            n_ops = len(operands)

            def both(*ops):
                out, pull = jax.vjp(fn, *ops[:n_ops])
                return out, pull(tuple(ops[n_ops:]))
            outs = []
            for what, f, ops in (
                    ("forward", fn, operands),
                    ("forward+backward", both, operands + drawn)):
                out, host, device, by_name = timed(jax.jit(f), ops)
                print(json.dumps(dict(
                    cell=cell, piece=piece, path=path, what=what,
                    tokens=b * t, key_heads=hk, value_heads=h, head_dim=d,
                    chunk=chunk, device=dev.device_kind,
                    host_ms_a_call=host, device_ms_a_call=device,
                    by_name=by_name, **more)), flush=True)
                outs.append(out)
            return outs[1]

        def cotangents(fn, operands=operands):
            return tuple(draw(*o.shape).astype(o.dtype)
                         for o in jax.eval_shape(fn, *operands))

        got = {}
        if "scan" in args.pieces and takes:
            terms = jax.block_until_ready(jax.jit(kernel_terms)(*operands))
            d_rows = cotangents(plain_scan, terms)
            got["scan", "plain"] = run("scan", "plain", plain_scan, d_rows,
                                       terms)
            default = (gdk.SCAN_HEADS_PER_STEP, gdk.SCAN_CHUNKS_PER_STEP)
            steps = [tuple(int(n) for n in s.split(":"))
                     for s in args.scan_steps]
            for step in [default] + [s for s in steps if s != default]:
                gdk.SCAN_HEADS_PER_STEP, gdk.SCAN_CHUNKS_PER_STEP = step
                jax.clear_caches()
                out = run("scan", "kernel", kernel_scan, d_rows, terms,
                          heads_per_step=step[0], chunks_per_step=step[1],
                          vmem_bytes=gdk.scan_vmem_bytes(
                              "scan_bwd", chunk, d, d, 2, *step))
                if step == default:
                    got["scan", "kernel"] = out
            gdk.SCAN_HEADS_PER_STEP, gdk.SCAN_CHUNKS_PER_STEP = default
            jax.clear_caches()
            del terms, d_rows
        d_terms, d_out = cotangents(plain_terms), cotangents(recurrence)
        if "terms" in args.pieces:
            got["terms", "plain"] = run("terms", "plain", plain_terms,
                                        d_terms)
        if takes and "terms" in args.pieces and by_head:
            default = gdk.HEAD_CHUNKS_PER_STEP
            for per_step in [default] + [
                    p for p in args.chunks_per_step if p != default]:
                gdk.HEAD_CHUNKS_PER_STEP = per_step
                jax.clear_caches()
                out = run("terms", "kernel", kernel_terms, d_terms,
                          chunks_per_step=per_step,
                          vmem_bytes=gdk.head_vmem_bytes(
                              "bwd", chunk, d, d, group, 2,
                              gdk._head_per_step(t // chunk, group)))
                if per_step == default:
                    got["terms", "kernel"] = out
            gdk.HEAD_CHUNKS_PER_STEP = default
            jax.clear_caches()
        elif takes and "terms" in args.pieces:
            got["terms", "kernel"] = run("terms", "kernel", kernel_terms,
                                         d_terms)
        if takes and "recurrence" in args.pieces:
            got["recurrence", "kernel"] = run("recurrence", "kernel",
                                              recurrence, d_out)
        if "recurrence" in args.pieces:
            # the path is chosen at trace time: stub the predicate and
            # build the jitted function anew
            keep = getattr(recurrent_ops, predicate)
            setattr(recurrent_ops, predicate, lambda *a: False)
            jax.clear_caches()
            try:
                got["recurrence", "plain"] = run("recurrence", "plain",
                                                 recurrence, d_out)
            finally:
                setattr(recurrent_ops, predicate, keep)
        term_names = ("W", "U0", "B", "q_decayed", "k_decayed", "decay")
        inputs = ("d_q", "d_k", "d_v", "d_g", "d_beta")
        for piece, names in (
                ("scan", ("o",) + tuple("d_" + n for n in term_names)),
                ("terms", term_names + inputs),
                ("recurrence", ("o",) + inputs)):
            if (piece, "kernel") not in got or (piece, "plain") not in got:
                continue
            (y1, g1), (y2, g2) = got[piece, "plain"], got[piece, "kernel"]
            far = {}
            for name, u, w in zip(names, tuple(y1) + tuple(g1),
                                  tuple(y2) + tuple(g2)):
                u, w = (np.asarray(x, np.float64) for x in (u, w))
                far[name] = float(np.max(np.abs(u - w[:u.shape[0]]))
                                  / max(np.max(np.abs(u)), 1e-9))
            print(json.dumps(dict(cell=cell, piece=piece,
                                  kernel_against_plain_relative=far)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
