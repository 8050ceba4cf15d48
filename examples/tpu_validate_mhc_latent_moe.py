"""On-chip validation of the latent-attention decoder with sparse experts
whose residual is four streams under manifold-constrained
hyper-connections and whose rotary embedding is rescaled by YaRN, at
published widths (run on a real TPU): what the benchmark's ``reference``
check cannot see, and the readings its tolerance is set from.

    python3 examples/tpu_validate_mhc_latent_moe.py [--seeds 1 2 3]

The model is ``benchmarks/configs/xing4_29b_a4b.json`` through the
normal path (``FFModel`` -> ``build_latent_moe`` -> ``compile``), the
reference ``benchmarks/reference/mhc_latent_moe_ref.py`` (float32,
``highest``, the 20 Sinkhorn iterations written out), both at the same
weights drawn from each seed. Checks (each prints PASS/FAIL, exit code 1
on any failure):

  1. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), within the cell's tolerance;
  2. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision: the program should be as near as
     that), bf16 in the routers too, and an 8-bit float (e4m3)
     everywhere but the routers, which the tolerance has to catch. The
     maps' product with ``phi`` is float32 in every one of them, as the
     configuration states;
  3. what a wrong model would read: the reference without the
     token-dependent part of the maps (``a_* = 0``), without YaRN, and
     with 5 Sinkhorn iterations for 20. The first two the tolerance has
     to catch; the third is printed (the iterations' last steps move
     ``Hres`` by less than the program's own bf16 products move the
     result: check 6 holds them). And, at the first seed, where the
     bf16 reading comes from: the same rounding of the model without
     YaRN (scores not doubled) and of the one without the
     token-dependent maps, each against ITS OWN float32;
  4. at ``--grad-seq`` positions (the reference's backward keeps every
     layer's s x s probabilities and every literal iteration): the loss
     and its gradient for one ``phi``, a ``b_res``, one held expert's
     weights, a router's and ``wq_b``, against ``jax.grad`` of the
     reference's loss, each held to twice what the reference itself
     reads with bf16 operands; the experts' row budget beside what the
     routers sent this share, nothing dropped. ``correct`` sees no
     gradient;
  5. the same with the experts' overflow forced (2 added to the held
     experts' bias, in program and reference alike);
  6. the operator alone, in float32 at the published width, which is
     what the two above cannot see (their yardstick is bf16 and an
     expert choice that flips): both nodes of one sub-layer around
     ``tanh`` over 512 tokens, once on the embedding copied
     to the streams with the first sub-layer's weights and once on
     random streams with a later one's. ``Hres`` against the 20 literal
     iterations entry by entry, the new streams, and the gradient of a
     fixed cotangent for the streams and each of the five weights
     against ``jax.grad`` of the reference (the scan's transpose; since
     PR 42 the four kernels of ``kernels/hyper_connection.py``, which
     this width takes: the check says which path ran, from the
     ``mhc.maps`` and ``mhc.kernel`` instants), each within 1e-5. The
     same operator with 5 iterations has
     to FAIL the ``Hres`` comparison, and its ``mhc.sum_err`` has to
     leave the band that 20 iterations read in (``SUM_ERR_BAND``).
"""
import argparse
import json
import os
import sys
import types

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
# the first mixture-of-experts configuration's validation has the
# helpers: PASS/FAIL lines, the runner's measure, the model through the
# normal path, its batch, the gradients' comparison
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, compare_gradients, l2,
    named, rel)
from examples.tpu_validate_hybrid_conv_moe import ROUNDED  # noqa: E402

WRONG = (("no token-dependent maps", dict(dynamic_maps=True), {}),
         ("no YaRN", dict(yarn=True), {}),
         ("5 Sinkhorn iterations", {}, {"hc_sinkhorn_iters": 5}))
#: ``mhc.sum_err`` a sub-layer (the worst token's ``|row or column sum
#: - 1|``): the cell's traced runs read 0.0270 to 0.0278 at the 20
#: published iterations and this script's operator 0.011 to 0.017, at 5
#: iterations 0.11 to 0.15 (PERF.md section 6, PR 40)
SUM_ERR_BAND = (0.005, 0.06)
HRES_TOL, STREAMS_TOL, GRAD_TOL = 1e-5, 1e-5, 1e-5


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)

    @jax.jit
    def compare(params, batch):
        outs, _, _, _ = ff.executor._forward(params, ff.state, batch, False,
                                             jnp.int32(0))
        got = jnp.log(jnp.clip(outs[0], 1e-30))

        def reference(**over):
            return ref.mhc_latent_moe_decoder(
                named(ff, params), dict(sizes, **over), batch["input_ids"],
                batch["position_ids"])
        want = reference()
        out = {"program": rel(got, want)}
        for label, kw in ROUNDED:
            with ref.rounded_operands(**kw):
                low = reference()
            out[label] = rel(low, want)
            if label == ROUNDED[0][0]:
                # the program against the reference at its OWN precision
                out["program, against bf16 reference"] = rel(got, low)
        for label, knobs, over in WRONG:
            with ref.without(**knobs):
                out[label] = rel(reference(**over), want)
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
        for label in ("float8_e4m3, routers float32",
                      "no token-dependent maps", "no YaRN"):
            check(f"seed {seed} {label}: would be caught",
                  errs[label] > tol, f"{errs[label]:.3e} > {tol}")

    @jax.jit
    def causes(params, batch):
        """bf16 operands against float32, of the model and of the two
        models that lack one candidate each."""
        def reference():
            return ref.mhc_latent_moe_decoder(
                named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        out = {}
        for label, knobs in (("the model", {}),) + tuple(
                (w[0], w[1]) for w in WRONG[:2]):
            with ref.without(**knobs):
                want = reference()
                with ref.rounded_operands(**ROUNDED[0][1]):
                    out[label] = rel(reference(), want)
        return out

    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seeds[0]))
    errs = {n: float(v) for n, v in causes(
        ff.params, batch_of(conf, seq, seeds[0])).items()}
    READINGS["bf16 operands against float32"] = errs
    print("bf16 operands against float32 of the same model (printed, not "
          "judged): " + ", ".join(f"{n} {v:.3e}" for n, v in errs.items()),
          flush=True)
    operator_checks(conf, ref, ff, batch_of(conf, seq, seeds[0]))
    del ff


def operator_checks(conf, ref, ff, batch, tokens=512):
    """Check 6 of the module's docstring."""
    from flexflow_tpu.ops.hyper_ops import HyperConnectionOp
    op = HyperConnectionOp()
    by_name = {l.name: l for l in ff.executor.program.layers}
    n, c = conf["hc_mult"], conf["hidden_size"]
    tokens = min(tokens, batch["input_ids"].shape[1])
    embedding = next(iter(ff.params["embed_tokens"].values()))
    copied = jnp.repeat(embedding[batch["input_ids"][0, :tokens]]
                        .astype(jnp.float32)[None, :, None, :], n, axis=2)
    drawn = jax.random.normal(jax.random.key(6), (1, tokens, n, c),
                              jnp.float32)
    cot = jax.random.normal(jax.random.key(7), (1, tokens, n, c),
                            jnp.float32)

    def program(x, w, params):
        counts = {}
        ctx = types.SimpleNamespace(
            kv_mode=None, count=lambda k, v: counts.__setitem__(k, v))
        u, maps, xs = op.emit(params, [x], w, ctx, "pre")
        out, = op.emit({"stage": "post"}, [xs, jnp.tanh(u), maps], {}, ctx,
                       "post")
        hres = maps[..., n:].reshape(maps.shape[:2] + (n, n))
        return jnp.sum(out * cot), (out, hres, counts["mhc.sum_err"])

    # which path the shapes take, and that the traced calls say so: at
    # the published width the four kernels, forward and backward
    from flexflow_tpu.kernels.hyper_connection import KERNELS, takes_kernel
    from flexflow_tpu.obs import events
    impl = "kernel" if takes_kernel(n, c, tokens) else "plain"
    events.enable()
    events.clear()
    try:
        jax.eval_shape(                 # traced, not run
            jax.grad(lambda x, w: program(
                x, w, by_name["attn_res_0_pre"].params)[0]), drawn,
            {k: v.astype(jnp.float32)
             for k, v in ff.params["attn_res_0_pre"].items()})
        seen = events.events()
    finally:
        events.disable()
        events.clear()
    said = {e["attrs"]["impl"] for e in seen if e["name"] == "mhc.maps"}
    tiles = {e["attrs"]["kernel"]: e["attrs"]["tile"] for e in seen
             if e["name"] == "mhc.kernel"}
    calls = sorted(tiles)
    READINGS["operator path"] = {"impl": impl, "kernels": calls,
                                 "tiles": tiles}
    check(f"operator: the mixes at {n} x {c} run the {impl} path",
          said == {impl} and calls == (sorted(KERNELS) if impl == "kernel"
                                       else []),
          f"mhc.maps says {sorted(said)}, mhc.kernel {calls} at tiles "
          f"{tiles}")

    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            out = ref.hyper_connected(x, w, conf, jnp.tanh)
            return jnp.sum(out * cot), (out, ref.stream_maps(x, w, conf)[2])

    for label, x, name in (("copied embedding", copied, "attn_res_0_pre"),
                           ("random streams", drawn, "mlp_res_2_pre")):
        params = by_name[name].params
        w = {k: v.astype(jnp.float32) for k, v in ff.params[name].items()}
        (_, (out, hres, err)), (gx, gw) = jax.jit(jax.value_and_grad(
            lambda x, w: program(x, w, params), (0, 1), has_aux=True))(x, w)
        (_, (out_r, hres_r)), (gx_r, gw_r) = jax.jit(jax.value_and_grad(
            reference, (0, 1), has_aux=True))(x, w)
        short = dict(params, iters=5)
        _, (_, hres5, err5) = jax.jit(
            lambda x, w: program(x, w, short))(x, w)
        tag = f"operator, {label} ({name}):"
        far = float(jnp.max(jnp.abs(hres - hres_r)))
        far5 = float(jnp.max(jnp.abs(hres5 - hres_r)))
        err, err5 = float(err), float(err5)
        READINGS[tag] = {
            "Hres": far, "Hres at 5 iterations": far5, "streams":
            float(rel(out, out_r)), "sum_err": err,
            "sum_err at 5 iterations": err5, "grad streams": l2(gx, gx_r),
            **{f"grad {k}": l2(gw[k], gw_r[k]) for k in sorted(gw)}}
        check(f"{tag} Hres is the 20 literal iterations'", far <= HRES_TOL,
              f"largest |difference| {far:.3e} <= {HRES_TOL} over "
              f"{tokens} tokens")
        check(f"{tag} 5 iterations would be caught", far5 > 10 * HRES_TOL,
              f"largest |difference| {far5:.3e} > {10 * HRES_TOL}")
        check(f"{tag} mhc.sum_err in its band",
              SUM_ERR_BAND[0] <= err <= SUM_ERR_BAND[1]
              and not SUM_ERR_BAND[0] <= err5 <= SUM_ERR_BAND[1],
              f"{err:.3e} in {SUM_ERR_BAND}, and at 5 iterations "
              f"{err5:.3e} outside it")
        check(f"{tag} the new streams", READINGS[tag]["streams"]
              <= STREAMS_TOL, f"rel {READINGS[tag]['streams']:.3e} <= "
              f"{STREAMS_TOL}")
        for k, v in READINGS[tag].items():
            if not k.startswith("grad "):
                continue
            if k == "grad b_res" and x is copied:
                # every stream is the same row there, so Hres X is X
                # times Hres's row sums, which the last iteration holds
                # at 1: b_res's gradient is what the iterations' error
                # leaves, a difference of near-equal terms (0.14 of
                # rounding on the chip, 4e-2 on the CPU at a tiny size)
                print(f"{tag} {k} rel {v:.3e} (printed, not judged: the "
                      f"gradient all but vanishes on equal streams)")
                continue
            check(f"{tag} {k}", v <= GRAD_TOL, f"rel {v:.3e} <= {GRAD_TOL}")


def gradient_checks(conf, ref, seed, seq):
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    picked = (("attn_res_3_pre", "phi"), ("mlp_res_2_pre", "b_res"),
              ("mlp_res_2_pre", "alpha"), ("experts_3", "wg"),
              ("experts_3", "w_gate"), ("attn_3", "wq_b"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_3.w_gate"] = out["experts_3.w_gate"][3]   # one expert
        return out

    compare_gradients(ff, ref, dict(conf), batch_of(conf, seq, seed), seq,
                      pick, "loss (with the MTP term)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[4000101])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--grad-seq", type=int, default=1024)
    ap.add_argument("--skip-gradients", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "xing4_29b_a4b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "mhc_latent_moe_ref")
    forward_checks(conf, ref, args.seq, args.seeds)
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0], args.grad_seq)
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
