"""The flash kernels timed ALONE on the chip, at the shapes the
benchmark's cells run them at (run on a real TPU).

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
the row statistics, the heads of its k/v operands and the sums of its
outputs, so that two trees' lines can be laid side by side.

Grouped-query cells (4, 7 and 8) hand k and v at the model's own
key/value head count where the tree's kernels read them in place
(``grid_steps`` takes ``kv_group``, PR 52) and repeated to the query
heads where they do not, as that tree's layers did. ``--kv-heads N``
gives every shape N key/value heads (0: the cell's own), ``--window W``
a window of W keys under every causal shape (0: the cell's own), and
``--cells`` names the shapes to run.

``--tree DIR`` times the package of another checkout (a ``git archive``
of the parent in ``_parent/``): the operands follow what that tree's
calls take, (bh, sq, 128) float32 statistics before PR 39 and one
float32 a row, (bh, 1, sq), since.

``--chain`` times instead what stands between a grouped-query layer's
projections and the kernels, forward and transpose in one jit, on the
device's clock: a float32 (1, s, kv_heads, d) array repeated to the
query heads, turned heads-first and cast to bf16 (v, and a k without
rotary embedding), against the turn and cast alone, which is what is
left when the kernels read the key/value heads in place; and the bf16
heads-first repeat with its group sum (a k out of ``qk_norm_rope``).

    python3 examples/tpu_time_flash_backward.py [--tree _parent]
    python3 examples/tpu_time_flash_backward.py --chain
"""
import argparse
import importlib
import inspect
import json
import os
import sys
import tempfile
import time

#: cell -> (batch, heads, key/value heads, s, d, dv, causal, dropout,
#: window, masked); bf16 operands. Cell 5 runs cell 3's shape (one
#: attention layer for cell 3's six); cell 7's calls read a mask of the
#: selected keys, cell 8 has four windowed layers and one full.
SHAPES = {
    "cell1_bert_large": (8, 16, 16, 512, 64, 64, False, 0.1, 0, False),
    "cell2_gpt2_124m": (12, 12, 12, 1024, 64, 64, True, 0.0, 0, False),
    "cell3_joyai_cell5_kimi": (1, 32, 32, 4096, 192, 128, True, 0.0, 0,
                               False),
    "cell4_lfm2": (1, 32, 8, 8192, 64, 64, True, 0.0, 0, False),
    "cell7_keye_masked": (1, 32, 4, 8192, 128, 128, True, 0.0, 0, True),
    "cell8_trinity_window": (1, 32, 4, 8192, 128, 128, True, 0.0, 2048,
                             False),
    "cell8_trinity_full": (1, 32, 4, 8192, 128, 128, True, 0.0, 0, False),
}
TINY = {
    "tiny_dropout": (1, 2, 2, 256, 64, 64, False, 0.1, 0, False),
    "tiny_grouped": (1, 4, 2, 256, 192, 128, True, 0.0, 128, False),
    "tiny_masked": (1, 4, 1, 256, 64, 64, True, 0.0, 0, True),
}
#: --chain: (s, heads, key/value heads, d) of cells 7 and 8, and cell 4
CHAIN_SHAPES = {"cells_7_8": (8192, 32, 4, 128), "cell4": (8192, 32, 8, 64)}


def device_ms(jax, trace_reduce, fn, operands, calls=5):
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


def time_chains(jax, jnp, np, trace_reduce, on_chip):
    """One JSON line a chain: its forward and transpose, ms a call on
    the device's clock by op and in all."""
    shapes = CHAIN_SHAPES if on_chip else {"tiny": (256, 4, 2, 128)}
    for cell, (s, h, kvh, d) in shapes.items():
        group = h // kvh

        def turn(x):                     # what is left: the turn and cast
            return jnp.swapaxes(x, 1, 2).astype(jnp.bfloat16)

        def repeat_turn(x):              # _expand_kv / heads_first
            return turn(jnp.repeat(x, group, axis=2))

        def repeat_heads_first(y):       # qk_norm_rope's repeat of its k
            return jnp.repeat(y, group, axis=1)

        rng = np.random.default_rng(52)
        x32 = jnp.asarray(rng.standard_normal((1, s, kvh, d)), jnp.float32)
        y16 = jnp.asarray(rng.standard_normal((1, kvh, s, d)), jnp.bfloat16)
        wide = jnp.asarray(rng.standard_normal((1, h, s, d)), jnp.bfloat16)
        for name, chain, x, ct in (
                ("f32_repeat_turn_cast", repeat_turn, x32, wide),
                ("f32_turn_cast", turn, x32, y16),
                ("bf16_heads_first_repeat", repeat_heads_first, y16, wide)):
            def both(x, ct, chain=chain):
                y, pull = jax.vjp(chain, x)
                return y, pull(ct)[0]
            fn = jax.jit(both)
            jax.block_until_ready(fn(x, ct))
            ops = device_ms(jax, trace_reduce, fn, (x, ct)) if on_chip \
                else {}
            print(json.dumps({
                "chain": name, "cell": cell, "x": list(x.shape),
                "cotangent": list(ct.shape), "group": group,
                "device_ms_a_call": ops,
                "device_ms_in_all": sum(ops.values()) if on_chip else None,
            }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="key/value heads of every shape (0: the cell's)")
    ap.add_argument("--window", type=int, default=0,
                    help="window of every causal shape (0: the cell's)")
    ap.add_argument("--chain", action="store_true",
                    help="time the repeat / turn / cast chains instead")
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

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    if args.chain:
        time_chains(jax, jnp, np, trace_reduce, on_chip)
        return 0
    shapes = SHAPES if on_chip else TINY
    one_a_row = "stat_bytes" in fa.grid_steps("bwd_dq", 1, 128, 128, 128,
                                              128, False)
    # whether this tree's kernels read the key/value heads in place
    in_place = "kv_group" in inspect.signature(fa.grid_steps).parameters
    for cell in args.cells or shapes:
        b, h, kvh, s, d, dv, causal, rate, window, masked = shapes[cell]
        kvh = args.kv_heads or kvh
        window = (args.window or window) if causal and not masked else 0
        bh, group = b * h, h // kvh
        rng = np.random.default_rng(39)
        q, do = (jnp.asarray(rng.standard_normal((bh, s, w)), jnp.bfloat16)
                 for w in (d, dv))
        k, v = (jnp.asarray(rng.standard_normal((b * kvh, s, w)),
                            jnp.bfloat16) for w in (d, dv))
        if not in_place:
            k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
        more = {"window": window} if window else {}
        if masked:       # the selection's density under the causal edge
            chosen = rng.random((b, s, s)) < 0.44
            chosen |= np.eye(s, dtype=bool)[None]
            more.update(mask=jnp.asarray(chosen, jnp.int8), heads=h)
        seed = jnp.full((1, 1), 7, jnp.int32)
        scale = 1.0 / d ** 0.5
        fwd_tile = fa.fwd_tiles(s, s, d, q.dtype, rate > 0, dv,
                                *((True,) if masked else ()))
        tiles = fa.bwd_tiles(s, s, d, q.dtype, rate > 0, dv,
                             *((True,) if masked else ()))
        o, lse = fa._fwd_call(q, k, v, seed, s, scale, causal, *fwd_tile,
                              rate, not on_chip, **more)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        if one_a_row:
            stats = lse[:, None, :], delta[:, None, :]
        else:
            stats = tuple(jnp.broadcast_to(x[:, :, None], (bh, s, 128))
                          for x in (lse, delta))
        stats = jax.block_until_ready(stats)
        for kernel, call, tile in (("fwd", fa._fwd_call, fwd_tile),
                                   ("bwd_dq", fa._bwd_dq_call, tiles[0]),
                                   ("bwd_dkv", fa._bwd_dkv_call, tiles[1])):
            kw = dict(more)
            if masked and kernel == "bwd_dkv":      # its mask transposed
                kw["mask"] = jnp.swapaxes(kw["mask"], 1, 2)
            fn = jax.jit(lambda *a, call=call, tile=tile, kw=kw: call(
                *a, s, scale, causal, *tile, rate, not on_chip, **kw))
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
                "kv_operand": list(k.shape), "window": window,
                "masked": masked,
                "stat_operand": list(stats[0].shape),
                "stat_bytes": int(sum(x.size * 4 for x in stats)),
                "ms_a_call": min(times), "ms_a_call_all": times,
                "device_ms_a_call": device_ms(
                    jax, trace_reduce, fn, operands) if on_chip else None,
                "abs_sum": [float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                            for x in outs],
                "finite": all(bool(jnp.all(jnp.isfinite(
                    x.astype(jnp.float32)))) for x in outs),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
