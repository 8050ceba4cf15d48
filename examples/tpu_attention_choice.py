"""On-chip table behind ``MultiHeadAttentionOp``'s ``auto`` rule.

One attention layer's forward + backward (``value_and_grad`` through the
op's ``emit``: projections, attention, output projection; float32
activations and weights, bf16 operands on the MXU as the program's
default) down each of its two paths, the Pallas flash kernels and XLA's
materialised s² attention, over the shapes the rule decides on:
sequence length, head size, dropout rate, causal or not. Time is the
device's own (profiler trace, union of the "XLA Ops" intervals of each
call, median of ``--calls`` calls), so the host's dispatch is not in it.

Each row also says what ``auto`` resolves to for that shape. The script
exits 1 where ``auto`` takes the kernels and the chip shows XLA faster by
more than ``--tolerance``: the rule is held to its own table (PERF.md
section 6, PR 30). A row where the kernels win and ``auto`` stays on XLA
is marked ``left`` and fails nothing: the rule moves a length only where
every measured column agrees. One process; it holds the chip itself.

Run on the chip:
  python examples/tpu_attention_choice.py [--rows cell1,...] [--out f.json]
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import trace_reduce  # noqa: E402
from flexflow_tpu import FFConfig  # noqa: E402
from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp  # noqa: E402
from flexflow_tpu.ops.registry import EmitCtx  # noqa: E402

EMBED = 1024          # heads = EMBED // head size: 16 of 64, 8 of 128
NAME = "attn"


def rows():
    """(tag, batch, seq, head size, dropout, causal): 4k to 16k tokens a
    batch. ``cell1`` is ``bert_large.train.1chip``'s layer; the rows at
    1024 have ``gpt2_124m.train.1chip``'s length at this width."""
    batch = {128: 64, 197: 32, 256: 32, 384: 16, 512: 8, 768: 8, 1024: 8}
    out = []
    for s, d, rate, causal in itertools.product(
            (128, 256, 384, 512, 768, 1024), (64, 128), (0.0, 0.1),
            (False, True)):
        tag = f"s{s}.d{d}.p{rate:g}.{'causal' if causal else 'full'}"
        if (s, d, rate, causal) == (512, 64, 0.1, False):
            tag = "cell1"
        out.append((tag, batch[s], s, d, rate, causal))
    # a length that is no multiple of 128 (ViT's 196 patches + 1): the
    # kernel pads q to 200 and k to 256
    out += [(f"s197.d64.p{rate:g}.full", batch[197], 197, 64, rate, False)
            for rate in (0.0, 0.1)]
    # cell 1's layer, training and not, at twice and four times its batch
    out += [(f"s512.d64.p{rate:g}.full.b{b}", b, 512, 64, rate, False)
            for b in (16, 32) for rate in (0.0, 0.1)]
    return out


def make_step(impl, batch, seq, d, rate, causal):
    """Jitted ``(x, weights, probe, step) -> (loss, grads)`` of one layer
    under the plan a forced ``kernel_impls = "attention:<impl>"`` gives
    the executor (none for ``auto``), and the path it emitted."""
    op = MultiHeadAttentionOp()
    heads = EMBED // d
    params = {"embed_dim": EMBED, "num_heads": heads, "dropout": rate,
              "causal": causal, "bias": True}
    cfg = FFConfig()
    plan = None if impl == "auto" else {"attention": impl}
    resolved = {}

    def loss(x, weights, probe, step):
        key = jax.random.fold_in(jax.random.key(1), step)
        ctx = EmitCtx(training=True, rngs={NAME: key}, config=cfg)
        ctx.kernel_impls = plan
        ctx.resolved_impls = resolved
        (out,) = op.emit(params, [x, x, x], weights, ctx, NAME)
        return jnp.sum(out * probe)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))), resolved


def operands(batch, seq, d, seed=0):
    rng = np.random.default_rng(seed)
    heads = EMBED // d

    def normal(shape, scale):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    x = normal((batch, seq, EMBED), 1.0)
    w = {n: normal((EMBED, heads, d), EMBED ** -0.5)
         for n in ("wq", "wk", "wv")}
    w["wo"] = normal((heads, d, EMBED), EMBED ** -0.5)
    w.update({n: jnp.zeros((heads, d), jnp.float32)
              for n in ("bq", "bk", "bv")})
    w["bo"] = jnp.zeros((EMBED,), jnp.float32)
    return x, w, normal((batch, seq, EMBED), 1.0)


def device_ms_per_call(fn, args, calls):
    """Median device time of ``calls`` runs of ``fn(*args, i)`` (already
    compiled): the events of the device's "XLA Ops" line, in order, cut
    into ``calls`` equal runs of ops (one executable, so every call runs
    the same ops), each run's time the union of its intervals."""
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            for i in range(calls):
                jax.block_until_ready(fn(*args, i + 1))
        finally:
            jax.profiler.stop_trace()
        ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
    (ops,) = ev["devices"].values()
    ops = sorted(ops, key=lambda o: o[1])

    def busy(some):
        return trace_reduce.total(trace_reduce.union(
            (s, s + dur) for _, s, dur in some))

    n, stray = divmod(len(ops), calls)
    if not n:
        raise RuntimeError(f"{len(ops)} device ops in {calls} calls")
    if stray:       # cannot tell the calls apart: their mean, and say so
        print(f"  ({len(ops)} ops in {calls} calls: mean, not median)")
        return busy(ops) / calls / 1e6, n
    return statistics.median(
        busy(ops[i * n:(i + 1) * n]) for i in range(calls)) / 1e6, n


def measure(row, calls):
    tag, batch, seq, d, rate, causal = row
    args = operands(batch, seq, d)
    line = {"row": tag, "batch": batch, "seq": seq, "head_dim": d,
            "dropout": rate, "causal": causal}
    for impl in ("flash", "xla", "auto"):
        fn, resolved = make_step(impl, batch, seq, d, rate, causal)
        if impl == "auto":      # traced only: which path the rule takes
            jax.eval_shape(fn, *args, 0)
            line["auto"] = resolved[NAME]
            continue
        jax.block_until_ready(fn(*args, 0))           # compile, warm up
        assert resolved[NAME] == impl, resolved
        line[f"{impl}_ms"], line[f"{impl}_ops"] = device_ms_per_call(
            fn, args, calls)
    line["flash_over_xla"] = line["flash_ms"] / line["xla_ms"]
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="",
                    help="comma-separated row tags (default: all)")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--tolerance", type=float, default=0.03,
                    help="where auto takes the kernels they may be this "
                         "share slower than XLA before the script fails")
    ap.add_argument("--out", default="",
                    help="also write the rows as JSON lines here")
    args = ap.parse_args()

    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()   # no FFModel.compile here to do it
    print(f"backend={jax.default_backend()} devices={jax.devices()}",
          flush=True)
    if jax.default_backend() != "tpu":
        print("not a TPU: a time from this host says nothing of the chip")
        return 2

    want = set(filter(None, args.rows.split(",")))
    table = [r for r in rows() if not want or r[0] in want]
    if want - {r[0] for r in table}:
        ap.error(f"no such row: {sorted(want - {r[0] for r in table})}")
    wrong, left = [], []
    out = open(args.out, "w") if args.out else None
    print("| row | batch x seq | d | dropout | causal | flash ms | xla ms "
          "| flash / xla | auto |\n| --- | --- | --- | --- | --- | --- | --- "
          "| --- | --- |")
    for row in table:
        line = measure(row, args.calls)
        other = "xla" if line["auto"] == "flash" else "flash"
        slower = line[f"{line['auto']}_ms"] > line[f"{other}_ms"] * (
            1.0 + args.tolerance)
        if slower:
            (wrong if line["auto"] == "flash" else left).append(line["row"])
        mark = "" if not slower else (
            " SLOWER" if line["auto"] == "flash" else " (left)")
        print("| {row} | {batch} x {seq} | {head_dim} | {dropout:g} | "
              "{causal} | {flash_ms:.3f} | {xla_ms:.3f} | "
              "{flash_over_xla:.3f} | {auto}{mark} |".format(
                  mark=mark, **line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    if out:
        out.close()
    print(f"\n{len(table)} rows; auto took the kernels where XLA is "
          f"faster in {len(wrong)}: {wrong}; it left a win of the "
          f"kernels on XLA in {len(left)}: {left}")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
