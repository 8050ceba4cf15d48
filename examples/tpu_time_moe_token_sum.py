"""The routed experts' way back to tokens timed ALONE on the chip, at the
four shapes the benchmark's expert cells run it at (run on a real TPU).

Both uses of ``kernels/moe_token_sum.py``, each jitted by itself, down
the plain path (``ops/moe_ops.py::_of_each_choice``: a gather a choice
and their sum) and down the kernel: ``combine`` (the forward: float32
rows times their gates) and ``rows_for_bwd`` (the transpose of the row
gather: bf16 cotangent rows, ones for gates, summed in float32 and
rounded to bf16). The rows are a stable sort by expert of ``tokens x top_k``
assignments, the held experts' leading, ``budget`` of them, zeros past
the live ones, as ``RoutedExpertsOp`` hands them over; the router is
uniform, or skewed until the held experts' largest load is ``--skew``
times their mean (the cells read 3.2 at untrained routers). Times are
the device's own clock (a profiler trace of ``--calls`` calls: all
device ops' durations, and by op name). One JSON line a timing, with
the largest difference between the two paths.

    python3 examples/tpu_time_moe_token_sum.py
"""
import argparse
import json
import os
import sys
import tempfile

#: (cell, tokens, hidden, top_k, budget, experts held, experts published)
SHAPES = (("joyai_llm_flash", 4096, 2048, 8, 4096, 16, 256),
          ("lfm2_24b_a2b", 8192, 2048, 4, 8192, 8, 64),
          ("kimi_linear_48b_a3b", 4096, 2304, 8, 4096, 8, 256),
          ("xing4_29b_a4b", 4096, 3584, 4, 4096, 8, 64))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--skew", type=float, default=3.2)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.kernels import moe_token_sum as mts
    from flexflow_tpu.ops import moe_ops

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = SHAPES if on_chip else (("rehearsal", 64, 128, 4, 64, 2, 16),)

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
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:5])
        return sum(by_name.values()), {k: round(v, 4) for k, v in
                                       top.items()}

    rng = np.random.default_rng(45)

    def routed(tokens, k, held, n, skew):
        """(mine, at, live, max over mean): the sort of one layer's
        assignments as the op makes it, the budget's first chunk."""
        p = np.ones(n)
        if skew:
            # the held experts' shares a ramp whose top is ``skew``
            # times their mean, the others' left as they were
            ramp = np.linspace(0.0, 1.0, held)
            p[:held] = 1 + (skew - 1) * (ramp - ramp.mean()) / (
                1 - ramp.mean())
        idx = np.argsort(-(np.log(np.maximum(p, 1e-9))
                           + rng.gumbel(size=(tokens, n))), axis=1)[:, :k]
        group = np.where(idx.reshape(-1) < held, idx.reshape(-1), held)
        order = np.argsort(group, kind="stable").astype(np.int32)
        loads = np.bincount(group, minlength=held + 1)[:held]
        return order, np.argsort(order).astype(np.int32), loads

    for cell, tokens, hidden, k, budget, held, n in shapes:
        for skew in (0.0, args.skew):
            order, inverse, loads = routed(tokens, k, held, n, skew)
            live = int(min(loads.sum(), budget))
            mine, at = jnp.asarray(order[:budget]), jnp.asarray(inverse)
            # the held groups' rows inside the budget's first chunk
            ends = np.minimum(np.cumsum(loads), budget)
            inside = jnp.asarray(np.diff(ends, prepend=0), jnp.int32)
            gates = jnp.asarray(rng.random((tokens, k)), jnp.float32)
            rows = rng.standard_normal((budget, hidden)).astype(np.float32)
            rows[live:] = 0.0
            uses = {
                "combine": (jnp.asarray(rows), gates),
                "rows_for_bwd": (jnp.asarray(rows, jnp.bfloat16), None)}
            for use, (src, w) in uses.items():
                def plain(src, w, mine, at):
                    each = moe_ops._of_each_choice(src, at, k)
                    if w is None:
                        return sum(r.astype(jnp.float32)
                                   for r in each).astype(src.dtype)
                    return sum(w[:, j:j + 1] * r
                               for j, r in enumerate(each))

                def kernel(src, w, mine, at):
                    out = mts.token_sum(src, mine, inside, tokens, k, w)
                    return out if w is not None else out.astype(src.dtype)
                out = {}
                for path, fn in (("plain", plain), ("kernel", kernel)):
                    fn = jax.jit(fn)
                    out[path] = jax.block_until_ready(
                        fn(src, w, mine, at))           # compiles
                    total, by_name = device_ms(fn, (src, w, mine, at))
                    print(json.dumps(dict(
                        cell=cell, use=use, path=path, tokens=tokens,
                        hidden=hidden, top_k=k, rows=budget,
                        live_rows=live, dtype=str(src.dtype),
                        load_max_over_mean=round(
                            float(loads.max() / loads.mean()), 3),
                        takes_kernel=mts.takes_kernel(
                            tokens, hidden, k, budget, held, src.dtype),
                        tile=mts.tile_tokens(tokens, hidden, src.dtype),
                        device=dev.device_kind, device_ms_a_call=total,
                        by_name=by_name)), flush=True)
                far = float(jnp.max(jnp.abs(
                    out["kernel"].astype(jnp.float32)
                    - out["plain"].astype(jnp.float32))))
                print(json.dumps(dict(
                    cell=cell, use=use, skew=skew,
                    kernel_against_plain=far,
                    largest=float(jnp.max(jnp.abs(
                        out["plain"].astype(jnp.float32)))))),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
