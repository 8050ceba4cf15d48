"""On-chip validation of the hybrid convolution/attention decoder with
sparse experts at published widths (run on a real TPU): what the
benchmark's ``reference`` check cannot see, and the readings its
tolerance is set from.

    python3 examples/tpu_validate_hybrid_conv_moe.py [--seeds 1 2 3]
                                                     [--seq 8192]

The model is ``benchmarks/configs/lfm2_24b_a2b.json`` through the normal
path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``), the
reference ``benchmarks/reference/hybrid_conv_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. Checks
(each prints PASS/FAIL, exit code 1 on any failure):

  1. the three flash kernels at (bh 4, s 8192, d 64, bf16, causal: one
     kv head's group of query heads; the golden's s x s scores fit for
     no more), forward and the three gradients, against
     ``mha_reference`` at ``highest`` precision;
  2. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure);
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last;
  4. at 2048 positions (the reference's backward keeps the attention
     layer's s x s probabilities): the loss and its gradient for the
     convolution's taps and ``w_in``, one held expert's weights, a
     router's and the two q/k norm weights, against ``jax.grad`` of the
     reference's loss, each held to twice what the reference itself
     reads with bf16 operands; each expert layer's row budget beside
     what its router sent this share. ``correct`` sees no gradient;
  5. the same with the overflow forced (2 added to the held experts'
     bias, in program and reference alike: every choice of every token
     is theirs, four times the budget's rows): every layer runs the
     further chunks, drops nothing, and the gradients are still the
     reference's.
"""
import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
# the other configuration's validation has the helpers: PASS/FAIL lines,
# the runner's measure, the model through the normal path, its batch
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, compare_gradients,
    named, rel)
from flexflow_tpu.kernels import flash_attention, mha_reference  # noqa: E402

ROUNDED = (("bf16, routers float32", dict(matmul=jnp.bfloat16)),
           ("bf16, routers too", dict(matmul=jnp.bfloat16,
                                      router=jnp.bfloat16)),
           ("float8_e4m3, routers float32",
            dict(matmul=jnp.float8_e4m3fn)))


def kernels(seq):
    ks = jax.random.split(jax.random.key(33), 4)
    q, k, v = (jax.random.normal(ks[i], (1, 4, seq, 64), jnp.bfloat16)
               for i in range(3))
    w = jax.random.normal(ks[3], (1, 4, seq, 64), jnp.float32)

    def gold(q, k, v):
        return mha_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True,
            precision=jax.lax.Precision.HIGHEST)

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    (_, gf) = graded(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    (_, gg) = graded(gold)(q, k, v)
    out = float(rel(flash_attention(q, k, v, causal=True), gold(q, k, v)))
    READINGS["flash_fwd"] = out
    # bf16 operands and a bf16 output against float32: PR 28 read 3e-3
    # to 9e-3 at d 64 over 1024 positions (tolerances 2e-2 / 4e-2)
    check(f"flash 64/64 at {seq} forward", out < 2e-2, f"rel {out:.3e}")
    for name, a, b in zip(("dq", "dk", "dv"), gf, gg):
        e = float(rel(a, b))
        READINGS[f"flash_{name}"] = e
        check(f"flash 64/64 at {seq} {name}", e < 4e-2, f"rel {e:.3e}")


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)

    @jax.jit
    def compare(params, batch):
        outs, _, _, _ = ff.executor._forward(params, ff.state, batch, False,
                                             jnp.int32(0))
        got = jnp.log(jnp.clip(outs[0], 1e-30))
        args = (named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        want = ref.hybrid_conv_moe_decoder(*args)
        out = {"program": rel(got, want)}
        for label, kw in ROUNDED:
            with ref.rounded_operands(**kw):
                low = ref.hybrid_conv_moe_decoder(*args)
            out[label] = rel(low, want)
            if label == ROUNDED[0][0]:
                # the program against the reference at its OWN precision
                out["program, against bf16 reference"] = rel(got, low)
        return out

    tol = conf["reference_rel_tol"]
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        errs = {n: float(v) for n, v in compare(
            ff.params, batch_of(conf, seq, seed)).items()}
        READINGS[f"seed {seed}"] = errs
        print(f"seed {seed}: " + ", ".join(
            f"{n} {v:.3e}" for n, v in errs.items()), flush=True)
        check(f"seed {seed} within the cell's tolerance",
              errs["program"] <= tol, f"{errs['program']:.3e} <= {tol}")
        check(f"seed {seed} as near as bf16 operands allow",
              errs["program"] <= 2 * errs["bf16, routers float32"],
              f"{errs['program']:.3e} against "
              f"{errs['bf16, routers float32']:.3e}")
        check(f"seed {seed} 8-bit operands would be caught",
              errs["float8_e4m3, routers float32"] > tol,
              f"{errs['float8_e4m3, routers float32']:.3e} > {tol}")
    del ff


def gradient_checks(conf, ref, seed, seq=2048):
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    picked = (("conv_2", "taps"), ("conv_2", "w_in"), ("experts_3", "wg"),
              ("experts_3", "w_gate"), ("attn_1", "q_norm"),
              ("attn_1", "k_norm"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_3.w_gate"] = out["experts_3.w_gate"][3]   # one expert
        return out

    compare_gradients(ff, ref, dict(conf), batch_of(conf, seq, seed), seq,
                      pick, "loss")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[3300101])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "hybrid_conv_moe_ref")
    if not args.skip_kernels:
        kernels(args.seq)
    forward_checks(conf, ref, args.seq, args.seeds)
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0])
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
