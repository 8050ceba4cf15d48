"""The flash kernels timed ALONE on the chip, at the shapes the
benchmark's five cells run them at (run on a real TPU).

A layer's backward is ``delta = sum(do * o)`` in XLA and two Pallas
calls, ``flash_attention_bwd_dq`` and ``flash_attention_bwd_dkv``. This
script builds one layer's operands (the log-sum-exp from the tree's own
forward call), jits the forward call and each backward call by itself,
and times it twice: by the host clock over ``--calls`` calls behind one
``block_until_ready``, and on the device's own clock (a profiler trace
of five calls, by op name). Trust the device's: at these shapes it
matches the call's time inside the benchmark's step to 1% (0.656 and
0.910 ms alone, 0.659 and 0.912 in cell 2's step), while the host clock
also pays for whatever else the stand-alone jit runs, 0.06 to 0.8 ms of
copies a call (PERF.md section 6, PR 39). One JSON line a (shape,
kernel): both times, the tile and its form, what the call is handed for
the row statistics, and the sums of its outputs, so that two trees'
lines can be laid side by side.

``--tree DIR`` times the package of another checkout (a ``git archive``
of the parent in ``_parent/``): the operands follow what that tree's
calls take, (bh, sq, 128) float32 statistics before PR 39 and one
float32 a row, (bh, 1, sq), since.

    python3 examples/tpu_time_flash_backward.py [--tree _parent]
"""
import argparse
import importlib
import json
import os
import sys
import tempfile
import time

#: cell -> (bh, s, d, dv, causal, dropout); bf16 operands. Cell 5 runs
#: cell 3's shape (one attention layer for cell 3's six).
SHAPES = {
    "cell1_bert_large": (128, 512, 64, 64, False, 0.1),
    "cell2_gpt2_124m": (144, 1024, 64, 64, True, 0.0),
    "cell3_joyai_cell5_kimi": (32, 4096, 192, 128, True, 0.0),
    "cell4_lfm2": (32, 8192, 64, 64, True, 0.0),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="tiny shapes in interpret mode: a rehearsal")
    args = ap.parse_args()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.abspath(args.tree), repo]

    import jax
    import jax.numpy as jnp
    import numpy as np
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    from benchmarks.harness import trace_reduce

    def device_ms(fn, operands, calls=5):
        """ms a call on the device's own clock, by op name."""
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                for _ in range(calls):
                    jax.block_until_ready(fn(*operands))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        by_name = {}
        for ops in ev["devices"].values():
            for name, _, dur in ops:
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                by_name[name] = by_name.get(name, 0.0) + dur / calls / 1e6
        return by_name

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = SHAPES if on_chip else {
        "tiny_dropout": (2, 256, 64, 64, False, 0.1),
        "tiny_causal": (2, 256, 192, 128, True, 0.0)}
    one_a_row = "stat_bytes" in fa.grid_steps("bwd_dq", 1, 128, 128, 128,
                                              128, False)
    for cell, (bh, s, d, dv, causal, rate) in shapes.items():
        rng = np.random.default_rng(39)
        q, k, v, do = (jnp.asarray(rng.standard_normal((bh, s, w)),
                                   jnp.bfloat16) for w in (d, d, dv, dv))
        seed = jnp.full((1, 1), 7, jnp.int32)
        scale = 1.0 / d ** 0.5
        o, lse = fa._fwd_call(q, k, v, seed, s, scale, causal,
                              *fa.fwd_tiles(s, s, d, q.dtype, rate > 0, dv),
                              rate, not on_chip)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        if one_a_row:
            stats = lse[:, None, :], delta[:, None, :]
        else:
            stats = tuple(jnp.broadcast_to(x[:, :, None], (bh, s, 128))
                          for x in (lse, delta))
        stats = jax.block_until_ready(stats)
        tiles = fa.bwd_tiles(s, s, d, q.dtype, rate > 0, dv)
        fwd_tile = fa.fwd_tiles(s, s, d, q.dtype, rate > 0, dv)
        for kernel, call, tile in (("fwd", fa._fwd_call, fwd_tile),
                                   ("bwd_dq", fa._bwd_dq_call, tiles[0]),
                                   ("bwd_dkv", fa._bwd_dkv_call, tiles[1])):
            fn = jax.jit(lambda *a, call=call, tile=tile: call(
                *a, s, scale, causal, *tile, rate, not on_chip))
            operands = (seed, q, k, v, do, *stats)
            if kernel == "fwd":
                operands = (q, k, v, seed)
            out = jax.block_until_ready(fn(*operands))      # compiles
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = fn(*operands)
                jax.block_until_ready(out)
                times.append((time.perf_counter() - t0) / args.calls * 1e3)
            outs = out if isinstance(out, (list, tuple)) else [out]
            print(json.dumps({
                "cell": cell, "kernel": "flash_attention_" + kernel,
                "device": dev.device_kind, "tile": list(tile),
                "form": fa.grid_steps(kernel, bh, s, s, *tile, causal).get(
                    "tile", "queries_major"),
                "stat_operand": list(stats[0].shape),
                "stat_bytes": int(sum(x.size * 4 for x in stats)),
                "ms_a_call": min(times), "ms_a_call_all": times,
                "device_ms_a_call": device_ms(fn, operands) if on_chip
                else None,
                "abs_sum": [float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                            for x in outs],
                "finite": all(bool(jnp.all(jnp.isfinite(
                    x.astype(jnp.float32)))) for x in outs),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
