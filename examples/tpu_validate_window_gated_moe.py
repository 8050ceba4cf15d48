"""On-chip validation of the window/full-attention mixture-of-experts
decoder at published widths (run on a real TPU): what the benchmark's
``reference`` check cannot see, and the readings its tolerance is set
from. Run it after a change to the flash kernels' window, the attention
op's output gate or ``build_hybrid_conv_moe``'s ``"sliding_attention"``
kind, sandwich norms or embedding scale.

    python3 examples/tpu_validate_window_gated_moe.py [--seeds 1 2 3]
        [--seq 8192] [--load-seeds 5100101 ...] [--skip-kernels]
        [--skip-gradients]

The model is ``benchmarks/configs/trinity_mini.json`` through the normal
path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``), the
reference ``benchmarks/reference/window_gated_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. Checks
(each prints PASS/FAIL, exit code 1 on any failure):

  1. the three flash kernels under a window of 2,048 at (4 query heads
     on 1 key/value head, read in place, s 8192, d 128, bf16: half a kv
     head's group of query heads; the golden's s x s scores fit for no
     more), forward and the three gradients,
     against a plain banded softmax at ``highest`` precision, and the
     same without the window against the causal one; the ``flash.grid``
     instants say the band's live steps beside the causal call's;
  2. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), and the eval-mode loss;
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last. And what the embedding's
     scale does to them: the same bf16 reading of the reference with
     ``mup_enabled`` off;
  4. at 2048 positions under a window of 512 in program and reference
     alike (the reference's backward keeps every layer's probabilities:
     at 4096 positions under the published window of 2048 it asked for
     16.2 GB of the chip's 15.75, PR 51; a quarter of the sequence is
     the cell's own ratio, and the kernels' band arithmetic is the same
     code): the loss and its gradient for a
     window layer's and the full layer's ``wg``, ``wq`` and q/k norm
     weights, the four norms of a layer, one held expert's weights, the
     shared expert's and a router's, against ``jax.grad`` of the
     reference's loss, each held to twice what the reference itself
     reads with bf16 operands; each expert layer's row budget beside
     what its router sent this share. ``correct`` sees no gradient;
  5. the same with the overflow forced (2 added to the held experts'
     bias, in program and reference alike);
  6. per ``--load-seeds`` seed: every expert layer's rows against its
     budget on a batch of the seed's own pool (the eval step; no layer
     may be over: a layer that is loops in every step of that seed's
     run).
"""
import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
# the other configuration's validation has the helpers: PASS/FAIL lines,
# the runner's measure, the model through the normal path, its batch
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, compare_gradients,
    named, rel)
from flexflow_tpu.kernels import flash_attention  # noqa: E402
from flexflow_tpu.kernels.flash_attention import grid_steps  # noqa: E402
from flexflow_tpu.obs import events  # noqa: E402
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX  # noqa: E402

ROUNDED = (("bf16, routers float32", dict(matmul=jnp.bfloat16)),
           ("bf16, routers too", dict(matmul=jnp.bfloat16,
                                      router=jnp.bfloat16)),
           ("float8_e4m3, routers float32",
            dict(matmul=jnp.float8_e4m3fn)))


def banded(q, k, v, window):
    """Plain softmax over ``s <= t and s > t - window`` (0: causal),
    float32 at highest precision."""
    s = q.shape[2]
    t = jnp.arange(s)[:, None]
    keys = jnp.arange(s)[None, :]
    allowed = keys <= t
    if window:
        allowed = allowed & (keys > t - window)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) \
        / np.sqrt(q.shape[-1])
    a = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", a, v,
                      precision=jax.lax.Precision.HIGHEST)


def kernels(seq, window, kv_heads=1):
    """4 query heads on ``kv_heads`` key/value heads, which the kernels
    read in place (PR 52) and the plain softmax reads repeated."""
    ks = jax.random.split(jax.random.key(51), 4)
    q, k, v = (jax.random.normal(ks[i], (1, n, seq, 128), jnp.bfloat16)
               for i, n in enumerate((4, kv_heads, kv_heads)))
    w = jax.random.normal(ks[3], (1, 4, seq, 128), jnp.float32)

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    for win in (window, 0):
        tag = f"flash 128/128 at {seq}, window {win}"

        def gold(q, k, v, win=win):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            return banded(q, *(jnp.repeat(x, 4 // kv_heads, axis=1)
                               for x in (k, v)), win)

        def flash(q, k, v, win=win):
            return flash_attention(q, k, v, causal=True, window=win)

        events.enable()
        events.clear()
        (_, gf) = graded(flash)(q, k, v)
        grids = {e["attrs"]["kernel"]: e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"}
        events.clear()
        events.disable()
        (_, gg) = graded(gold)(q, k, v)
        out = float(rel(flash(q, k, v), gold(q, k, v)))
        READINGS[f"{tag} fwd"] = out
        check(f"{tag} forward", out < 2e-2, f"rel {out:.3e}")
        for name, a, b in zip(("dq", "dk", "dv"), gf, gg):
            e = float(rel(a, b))
            READINGS[f"{tag} {name}"] = e
            check(f"{tag} {name}", e < 4e-2, f"rel {e:.3e}")
        for kernel, g in sorted(grids.items()):
            causal = grid_steps(kernel.replace("flash_attention_", ""), 4,
                                seq, seq, g["block_q"], g["block_k"], True)
            print(f"  {kernel}: {g}", flush=True)
            key = "live_pieces" if "live_pieces" in g else "live_steps"
            check(f"{tag} {kernel} skips the band's outside",
                  (g[key] < causal[key]) if win else (g[key] == causal[key]),
                  f"{key} {g[key]} against the causal call's {causal[key]} "
                  f"of {g['steps']} steps")


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)
    unscaled = dict(conf, mup_enabled=False)

    def parts(params, batch):
        outs, _, _, _ = ff.executor._forward(params, ff.state, batch, False,
                                             jnp.int32(0))
        got = jnp.log(jnp.clip(outs[0], 1e-30))
        args = (named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        return got, args, ref.window_gated_moe_decoder(*args)

    @jax.jit
    def program(params, batch):
        got, _, want = parts(params, batch)
        loss = -jnp.mean(jnp.take_along_axis(got, batch["label"], -1))
        return {"program": rel(got, want)}, loss

    def rounded(label, kw):
        @jax.jit
        def f(params, batch):
            got, args, want = parts(params, batch)
            with ref.rounded_operands(**kw):
                low = ref.window_gated_moe_decoder(*args)
            out = {label: rel(low, want)}
            if label == ROUNDED[0][0]:
                # the program against the reference at its OWN precision
                out["program, against bf16 reference"] = rel(got, low)
            return out
        return f

    @jax.jit
    def without_scale(params, batch):
        """The reference with no embedding scale, bf16 operands against
        float32: what the scale does to the precision's reading."""
        args = (named(ff, params), unscaled, batch["input_ids"],
                batch["position_ids"])
        want = ref.window_gated_moe_decoder(*args)
        with ref.rounded_operands(matmul=jnp.bfloat16):
            low = ref.window_gated_moe_decoder(*args)
        return {"bf16, routers float32, no embedding scale": rel(low, want)}

    fns = [program] + [rounded(label, kw) for label, kw in ROUNDED] \
        + [without_scale]
    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        batch = batch_of(conf, seq, seed)
        errs = {}
        for fn in fns:
            out = fn(ff.params, batch)
            if isinstance(out, tuple):
                out, loss = out
                errs["loss"] = float(loss)
            errs.update({n: float(v) for n, v in out.items()})
        READINGS[f"seed {seed}"] = errs
        print(f"seed {seed}: " + ", ".join(
            f"{n} {v:.4e}" for n, v in errs.items()), flush=True)
        check(f"seed {seed} within the cell's tolerance",
              errs["program"] <= tol, f"{errs['program']:.3e} <= {tol}")
        check(f"seed {seed} as near as bf16 operands allow",
              errs["program"] <= 2 * errs["bf16, routers float32"],
              f"{errs['program']:.3e} against "
              f"{errs['bf16, routers float32']:.3e}")
        check(f"seed {seed} 8-bit operands would be caught",
              errs["float8_e4m3, routers float32"] > tol,
              f"{errs['float8_e4m3, routers float32']:.3e} > {tol}")
        check(f"seed {seed} loss inside the band",
              lo <= errs["loss"] <= hi, f"{errs['loss']:.4f} in [{lo}, {hi}]")
    return ff


def load_checks(ff, conf, seq, seeds):
    """Check 6: the held experts' loads at seeds of their own."""
    @jax.jit
    def counters(params, batch):
        outs, _, aux, capture = ff.executor._forward(
            params, ff.state, batch, False, jnp.int32(0))
        _, bm = ff.executor._loss_and_metrics(outs, capture, batch["label"],
                                              aux)
        return {k: v for k, v in bm.items() if k.startswith(COUNTER_PREFIX)}

    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    layers = [l for l in ff.executor.program.layers
              if l.op_type.name == "OP_ROUTED_EXPERTS"]
    budget = sum(RoutedExpertsOp.rows_multiplied(seq, l.params)
                 for l in layers)
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        c = {k[len(COUNTER_PREFIX):]: float(v) for k, v in counters(
            ff.params, batch_of(conf, seq, seed)).items()}
        READINGS[f"load seed {seed}"] = {
            k: c.get(k) for k in ("moe.overflow", "moe.dropped",
                                  "moe.local_assignments")}
        check(f"load seed {seed}: no expert layer over its row budget",
              c.get("moe.overflow") == 0.0 and c.get("moe.dropped") == 0.0,
              f"moe.overflow {c.get('moe.overflow')}, dropped "
              f"{c.get('moe.dropped')}, {c.get('moe.local_assignments')} "
              f"rows sent here against {budget} budgeted over "
              f"{len(layers)} layers")


def gradient_checks(conf, ref, seed, seq=2048, window=512):
    conf = dict(conf, sliding_window=window)
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    picked = (("attn_1", "wg"), ("attn_1", "wq"), ("attn_1", "q_norm"),
              ("attn_2", "wg"), ("attn_2", "k_norm"),
              ("operator_norm_2", "scale"),
              ("post_operator_norm_2", "scale"), ("ffn_norm_2", "scale"),
              ("post_ffn_norm_2", "scale"), ("experts_3", "wg"),
              ("experts_3", "w_gate"), ("experts_3", "ws_down"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_3.w_gate"] = out["experts_3.w_gate"][3]   # one expert
        return out

    compare_gradients(ff, ref, dict(conf), batch_of(conf, seq, seed), seq,
                      pick, "loss")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[5100201])
    ap.add_argument("--load-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "window_gated_moe_ref")
    if not args.skip_kernels:
        kernels(args.seq, conf["sliding_window"])
    if not args.skip_forward:
        ff = forward_checks(conf, ref, args.seq, args.seeds)
        if args.load_seeds:
            load_checks(ff, conf, args.seq, args.load_seeds)
        del ff
        jax.clear_caches()
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0])
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
