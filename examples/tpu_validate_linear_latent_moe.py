"""On-chip validation of the hybrid linear-attention / latent-attention
decoder with sparse experts at published widths (run on a real TPU):
what the benchmark's ``reference`` check cannot see, and the readings
its tolerance is set from.

    python3 examples/tpu_validate_linear_latent_moe.py [--seeds 1 2 3]
                                                       [--seq 4096]

The model is ``benchmarks/configs/kimi_linear_48b_a3b.json`` through the
normal path (``FFModel`` -> ``build_latent_moe`` -> ``compile``), the
reference ``benchmarks/reference/linear_latent_moe_ref.py`` (float32,
``highest``, the recurrence token by token), both at the same weights
drawn from each seed. Checks (each prints PASS/FAIL, exit code 1 on any
failure):

  1. the recurrence alone at (1, ``--seq``, 32 x 128), decays drawn as
     the layer's initialisation draws them: ``gated_delta_rule`` with
     bf16 operands (the chunks' terms and the state from chunk to chunk
     both by the kernels of ``kernels/gated_delta_rule.py``, whose
     ``kda.kernel`` instants the check prints), forward and the
     gradients of q, k, v, g and beta,
     against the token-by-token reference, each held to twice what that
     reference itself reads with bf16 operands; and the most negative
     in-chunk running log-decay, which says whether ``exp(-G)`` would
     have overflowed; then the chunks' terms timed alone at that
     shape, the two kernels and the plain code they replace, ms a call;
  2. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure);
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last;
  4. at 2048 positions (the reference's backward keeps the latent
     layer's s x s probabilities): the loss and its gradient for a
     linear-attention layer's ``A_log``, ``dt_bias``, ``wf_b``, a
     convolution's taps and ``wb``, one held expert's weights, a
     router's and the latent layer's ``wkv_b``, against ``jax.grad`` of
     the reference's loss, each held to twice what the reference itself
     reads with bf16 operands; each expert layer's row budget beside
     what its router sent this share. ``correct`` sees no gradient;
  5. the same with the overflow forced (2 added to the held experts'
     bias, in program and reference alike).
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
# the first mixture-of-experts configuration's validation has the
# helpers: PASS/FAIL lines, the runner's measure, the model through the
# normal path, its batch, the gradients' comparison
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, compare_gradients,
    named, rel)
from examples.tpu_validate_hybrid_conv_moe import ROUNDED  # noqa: E402
from flexflow_tpu.ops.recurrent_ops import gated_delta_rule  # noqa: E402


def recurrence(ref, seq, heads=32, d=128):
    rng = np.random.default_rng(35)

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, k = normal(1, seq, heads, d), normal(1, seq, heads, d)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    # g = -A softplus(.), A in (1, 16) a head, softplus log-uniform in
    # (1e-3, 1e-1) a channel and moved by a token's projection
    a = rng.uniform(1, 16, (1, 1, heads, 1))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, 1, heads, d)))
    g = (-a * dt * np.exp(0.5 * normal(1, seq, heads, d))
         ).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, (1, seq, heads)).astype(np.float32)
    args = [jnp.asarray(x) for x in (q, k, normal(1, seq, heads, d), g,
                                     beta)]
    mix = jnp.asarray(normal(1, seq, heads, d))

    def program(*a):
        out, least = gated_delta_rule(
            *(jnp.swapaxes(x, 1, 2) for x in a), mdt=jnp.bfloat16)
        out = jnp.swapaxes(out, 1, 2)
        return jnp.sum(out * mix), (out, least)

    def reference(*a):
        with jax.default_matmul_precision("highest"):
            out = ref.delta_rule_by_token(*a)
        return jnp.sum(out * mix), out

    def rounded(*a):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference(*a)

    def graded(fn):
        return jax.jit(jax.value_and_grad(fn, argnums=range(5),
                                          has_aux=True))

    from flexflow_tpu.kernels.gated_delta_rule import takes_kernel
    from flexflow_tpu.obs import events
    events.enable()
    events.clear()
    try:
        (_, (got, least)), d_got = graded(program)(*args)
        said = [e["attrs"] for e in events.events()
                if e["name"] == "kda.kernel"]
    finally:
        events.clear()
        events.disable()
    for a in said:
        print(f"  kda.kernel {a}", flush=True)
    # the terms' pair and the scan's where the shapes take the kernels
    check("recurrence ran the kernels the shapes say",
          sorted(a["kernel"] for a in said) == (
              ["bwd", "fwd", "scan_bwd", "scan_fwd"]
              if takes_kernel(64, d, d) else []),
          f"kda.kernel instants {[a['kernel'] for a in said]}")
    (_, want), d_want = graded(reference)(*args)
    (_, low), d_low = graded(rounded)(*args)
    least = float(least)
    READINGS["recurrence log_decay_min"] = least
    print(f"recurrence at {seq} x {heads} x {d}: the most negative "
          f"in-chunk running log-decay {least:.1f} (exp(-G) is a float32 "
          f"down to -88.7)", flush=True)
    for name, a, b, c in [("forward", got, want, low)] + [
            (f"d{n}", x, y, z) for n, x, y, z in zip(
                "q k v g beta".split(), d_got, d_want, d_low)]:
        e, eb = float(rel(a, b)), float(rel(c, b))
        READINGS[f"recurrence {name}"] = {
            "program": e, "reference, bf16 operands": eb}
        check(f"recurrence {name} finite and near",
              bool(jnp.all(jnp.isfinite(a))) and e <= 2 * eb + 1e-3,
              f"rel {e:.3e}; the reference with bf16 operands reads "
              f"{eb:.3e}")


def kernels_alone(seq, heads=32, d=128, chunk=64, calls=20):
    """The chunks' terms timed alone at the recurrence's shape, host
    clock around ``calls`` calls that end in ``block_until_ready``: the
    forward and the backward kernel, and the plain ``_chunk_terms`` and
    its autodiff beside them."""
    import time

    from flexflow_tpu.kernels.gated_delta_rule import chunk_terms
    from flexflow_tpu.ops.recurrent_ops import _chunk_terms, _in_chunks
    rng = np.random.default_rng(36)
    shape = (1, heads, seq, d)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           / d ** 0.5) for _ in range(3))
    g = jnp.asarray(-rng.uniform(0.01, 2.0, shape).astype(np.float32))
    beta = jnp.asarray(rng.uniform(0.05, 0.95, shape[:3]
                                   ).astype(np.float32))
    args = (q, k, v, g, beta)

    def kernel(*a):
        return tuple(chunk_terms(*a, chunk, jnp.bfloat16)[:6])

    def plain(*a):
        return tuple(_chunk_terms(*(_in_chunks(x, chunk) for x in a),
                                  jnp.bfloat16)[:6])

    for name, fn in (("kernel", kernel), ("plain", plain)):
        fwd = jax.jit(fn)
        cts = jax.tree.map(jnp.ones_like, fwd(*args))
        # (the backward reads no output of the forward: XLA drops it)
        bwd = jax.jit(lambda a, c, fn=fn: jax.vjp(fn, *a)[1](c))
        for what, call in (("forward", lambda: fwd(*args)),
                           ("backward", lambda: bwd(args, cts))):
            jax.block_until_ready(call())
            t0 = time.perf_counter()
            for _ in range(calls):
                out = call()
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / calls * 1e3
            READINGS[f"chunk terms {name} {what} ms"] = ms
            print(f"chunk terms at {seq} x {heads} x {d}, chunks of "
                  f"{chunk}: {name} {what} {ms:.3f} ms a call",
                  flush=True)


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
        want = ref.linear_latent_moe_decoder(*args)
        out = {"program": rel(got, want)}
        for label, kw in ROUNDED:
            with ref.rounded_operands(**kw):
                low = ref.linear_latent_moe_decoder(*args)
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
    picked = (("kda_2", "A_log"), ("kda_2", "dt_bias"), ("kda_2", "wf_b"),
              ("kda_2", "conv_k"), ("kda_2", "wb"), ("experts_3", "wg"),
              ("experts_3", "w_gate"), ("attn_3", "wkv_b"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_3.w_gate"] = out["experts_3.w_gate"][3]   # one expert
        return out

    compare_gradients(ff, ref, dict(conf), batch_of(conf, seq, seed), seq,
                      pick, "loss")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[3500101])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--skip-recurrence", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "linear_latent_moe_ref")
    if not args.skip_recurrence:
        recurrence(ref, args.seq)
        kernels_alone(args.seq)
    forward_checks(conf, ref, args.seq, args.seeds)
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0])
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
