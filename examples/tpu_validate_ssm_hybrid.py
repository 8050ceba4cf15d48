"""On-chip validation of the state-space / attention hybrid decoder at
published widths (run on a real TPU): what the benchmark's ``reference``
check cannot see, and the readings its tolerance is set from. Run it
after a change to ``ops/recurrent_ops.py::StateSpaceMixerOp`` or
``state_space_scan``, ``MultiHeadAttentionOp``'s ``sm_scale`` or
``build_hybrid_conv_moe``'s ``"mamba"`` / ``"attention"`` kinds and
scalar multipliers.

    python3 examples/tpu_validate_ssm_hybrid.py [--seeds 1 2 3]
        [--seq 4096] [--grad-seq 1024] [--skip-layer] [--skip-forward]
        [--skip-gradients] [--skip-kernels]

The model is ``benchmarks/configs/granite_4_0_h_micro.json`` through the
normal path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``),
the reference ``benchmarks/reference/ssm_hybrid_ref.py`` (float32,
``highest``, the recurrence token by token), both at the same weights
drawn from each seed. Checks (each prints PASS/FAIL, exit code 1 on any
failure):

  1. one state-space mixer ALONE at the published width (2048 -> 64
     heads of 64 x 128, chunks of 256) over ``--seq`` positions with
     bf16 operands, at the first seed's ``mamba_0`` weights: its output
     against the reference's token-by-token layer, held to twice what
     the reference itself reads with bf16 operands (on one chip this is
     the kernels of ``kernels/state_space.py``: the ``ssm.layer``
     instant has to say ``impl="kernel"``); and the
     ``ssm.layer`` instant's sizes; and, printed and not judged, how
     much of that output the carried state is at the seed's weights: the
     reference's layer with every chunk run as a sequence of its own;
  2. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), the eval-mode loss, and the
     counter ``ssm.min_chunk_log_decay`` a layer;
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 (the configuration's stated precision)
     and an 8-bit float (e4m3). The tolerance has to lie over the first
     and under the last. Printed and not judged, what the measure
     does NOT see: the reference at 1 / sqrt(64) for the scores'
     multiplier (one sub-layer of twenty behind a 0.22 and a division by
     8: 1e-7, a thousandth of the tolerance; check 4's gradients hold
     the scale), and the loss with ``logits_scaling`` 1 (which the
     initial-loss band has to exclude);
  4. gradients at ``--grad-seq`` positions of a model cut to three
     layers (mamba, attention, mamba) at the published widths, so that
     the token-by-token reference's backward fits beside it (it keeps a
     2 MB state a position a layer): the loss and its gradient for every
     weight of a mixer, the attention layer's four projections, a
     norm's scale, one SwiGLU, the embedding and the head, against
     ``jax.grad`` of the reference's loss, each held to twice what the
     reference itself reads with bf16 operands. ``correct`` sees no
     gradient;
  5. the recurrence ALONE at the published width over ``--seq``
     positions with bf16 operands, down the kernels and down the plain
     path (``state_space_scan(kernels=False)``): the two outputs against
     each other, and the output and each of the five gradients of both
     against the plain path in float32 (``highest``), the kernels held
     to twice what the plain path itself reads with bf16 operands; one
     ``ssm.kernel`` instant a call.
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
    BENCH, FAILED, READINGS, batch_of, build, check, l2, named,
    program_grads, rel)
from flexflow_tpu import FFConfig  # noqa: E402
from flexflow_tpu.obs import events  # noqa: E402
from flexflow_tpu.kernels import state_space as ssm_kernels  # noqa: E402
from flexflow_tpu.ops.recurrent_ops import (StateSpaceMixerOp,  # noqa: E402
                                            state_space_scan)
from flexflow_tpu.ops.registry import EmitCtx  # noqa: E402
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX  # noqa: E402

ROUNDED = (("bf16", jnp.bfloat16), ("float8_e4m3", jnp.float8_e4m3fn))


def layer_check(conf, ref, ff, seq):
    """Check 1, at the weights ``ff`` holds."""
    w = ff.params["mamba_0"]
    x = jax.random.normal(jax.random.key(55), (1, seq, conf["hidden_size"]),
                          jnp.float32)
    params = next(l.params for l in ff.executor.program.layers
                  if l.name == "mamba_0")

    @jax.jit
    def both(x, w):
        (got,) = StateSpaceMixerOp().emit(
            params, [x], w, EmitCtx(training=True, config=FFConfig()),
            "mamba_0")
        with jax.default_matmul_precision("highest"):
            want = ref.mixer(x, w, conf)
            with ref.rounded_operands(matmul=jnp.bfloat16):
                low = ref.mixer(x, w, conf)
            alone = ref.mixer(x.reshape((-1, params["chunk"])
                                        + x.shape[2:]), w, conf)
        return (rel(got, want), rel(low, want), rel(got, low),
                rel(alone.reshape(want.shape), want))

    events.enable()
    events.clear()
    e, eb, own, cut = (float(v) for v in both(x, w))
    said = [ev["attrs"] for ev in events.events()
            if ev["name"] == "ssm.layer"]
    events.clear()
    events.disable()
    READINGS["mixer alone"] = {"program": e, "reference, bf16 operands": eb,
                               "program against that": own,
                               "reference, each chunk a sequence": cut}
    print(f"  not judged: the reference with each chunk of "
          f"{params['chunk']} a sequence of its own (no carried state) "
          f"reads {cut:.3e}", flush=True)
    check(f"one mixer alone over {seq} positions", e <= 2 * eb + 1e-4,
          f"rel {e:.3e}; the reference with bf16 operands reads {eb:.3e}, "
          f"and the program against THAT {own:.3e}")
    check("the layer says its sizes", len(said) == 1 and all(
        said[0][k] == v for k, v in (
            ("heads", conf["mamba_n_heads"]),
            ("head_dim", conf["mamba_d_head"]),
            ("state", conf["mamba_d_state"]), ("groups", 1),
            ("chunk", conf["mamba_chunk_size"]),
            ("chunks", -(-seq // conf["mamba_chunk_size"])),
            ("impl", "kernel"))), str(said))


def kernel_check(conf, seq):
    """Check 5: the recurrence alone, kernels against the plain path."""
    h, p, n, chunk = (conf["mamba_n_heads"], conf["mamba_d_head"],
                      conf["mamba_d_state"], conf["mamba_chunk_size"])
    keys = jax.random.split(jax.random.key(56), 6)
    x, bm, cm, ct = (jax.random.normal(k, s, jnp.float32) for k, s in zip(
        keys, ((1, seq, h, p), (1, seq, n), (1, seq, n), (1, seq, h, p))))
    # the cell's seeds: steps of 0.01-0.15 a token under A in (-16, -1)
    dt = jax.random.uniform(keys[4], (1, seq, h), jnp.float32, 0.01, 0.15)
    a_log = jnp.log(jax.random.uniform(keys[5], (h,), jnp.float32, 1., 16.))

    def run(mdt, kernels):
        def scan(*v):
            return state_space_scan(v[0], v[1], -jnp.exp(v[2]), v[3], v[4],
                                    chunk, mdt, kernels=kernels)[0]

        def both(*v):
            y, pull = jax.vjp(scan, *v)
            return (y,) + pull(ct)
        with jax.default_matmul_precision("highest"):
            return jax.jit(both)(x, dt, a_log, bm, cm)

    def far(got, want):
        return [float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
                for g, w in zip(got, want)]

    events.enable()
    events.clear()
    by_kernels = run(jnp.bfloat16, True)
    said = [ev["attrs"]["kernel"] for ev in events.events()
            if ev["name"] == "ssm.kernel"]
    events.clear()
    events.disable()
    plain, truth = run(jnp.bfloat16, False), run(jnp.float32, False)
    names = ("y", "d_x", "d_dt", "d_A_log", "d_B", "d_C")
    k_err, p_err = far(by_kernels, truth), far(plain, truth)
    READINGS["recurrence alone"] = {
        "kernels against float32": dict(zip(names, k_err)),
        "plain against float32": dict(zip(names, p_err)),
        "kernels against plain": dict(zip(names, far(by_kernels, plain)))}
    check("the shapes take the kernels, one instant a call",
          ssm_kernels.takes_kernel(chunk, h, p, n)
          and sorted(said) == ["bwd", "fwd"], str(said))
    check(f"the recurrence alone over {seq} positions: the kernels' output "
          f"is the plain path's", far(by_kernels[:1], plain[:1])[0] <= 1e-5,
          f"{far(by_kernels[:1], plain[:1])[0]:.3e} of the largest entry")
    for name, k, q in zip(names, k_err, p_err):
        # (``A_log``'s gradient is a number a head, the sum over every
        # token of terms of both signs: either path's rounding moves it
        # by a few thousandths, seed by seed one more than the other)
        check(f"the recurrence alone: {name} by the kernels",
              k <= 2 * q + (1e-2 if name == "d_A_log" else 1e-4),
              f"{k:.3e} of the largest entry from the float32 plain path; "
              f"the bf16 plain path reads {q:.3e}")


def forward_checks(conf, ref, seq, seeds, layer_alone):
    ff = build(conf, seq, "none")
    plain_scale = dict(conf, attention_multiplier=conf["head_dim"] ** -0.5)
    unscaled = dict(conf, logits_scaling=1.0)

    def parts(params, batch):
        ex = ff.executor
        outs, _, aux, capture = ex._forward(params, ff.state, batch, False,
                                            jnp.int32(0))
        _, bm = ex._loss_and_metrics(outs, capture, batch["label"], aux)
        got = jnp.log(jnp.clip(outs[0], 1e-30))
        args = (named(ff, params), batch["input_ids"],
                batch["position_ids"])
        return got, args, bm

    @jax.jit
    def program(params, batch):
        got, (layers, ids, pos), bm = parts(params, batch)
        want = ref.ssm_hybrid_decoder(layers, conf, ids, pos)
        loss = -jnp.mean(jnp.take_along_axis(got, batch["label"], -1))
        return {"program": rel(got, want), "loss": loss,
                "min chunk log-decay a layer":
                bm[COUNTER_PREFIX + "ssm.min_chunk_log_decay"]
                / bm[COUNTER_PREFIX + "ssm.layers"],
                "scores at 1/sqrt(64) instead": rel(ref.ssm_hybrid_decoder(
                    layers, plain_scale, ids, pos), want),
                "loss with logits_scaling 1": ref.loss(
                    layers, unscaled, ids, pos, batch["label"][..., 0])}

    def rounded(label, dtype):
        @jax.jit
        def f(params, batch):
            got, (layers, ids, pos), _ = parts(params, batch)
            want = ref.ssm_hybrid_decoder(layers, conf, ids, pos)
            with ref.rounded_operands(matmul=dtype):
                low = ref.ssm_hybrid_decoder(layers, conf, ids, pos)
            out = {label: rel(low, want)}
            if label == "bf16":
                # the program against the reference at its OWN precision
                out["program, against bf16 reference"] = rel(got, low)
            return out
        return f

    fns = [program] + [rounded(label, dtype) for label, dtype in ROUNDED]
    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    for i, seed in enumerate(seeds):
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        if i == 0 and layer_alone:
            layer_check(conf, ref, ff, seq)
        batch = batch_of(conf, seq, seed)
        errs = {}
        for fn in fns:
            errs.update({n: float(v) for n, v in fn(ff.params,
                                                    batch).items()})
        READINGS[f"seed {seed}"] = errs
        print(f"seed {seed}: " + ", ".join(
            f"{n} {v:.4e}" for n, v in errs.items()), flush=True)
        check(f"seed {seed} within the cell's tolerance",
              errs["program"] <= tol, f"{errs['program']:.3e} <= {tol}")
        check(f"seed {seed} as near as bf16 operands allow",
              errs["program"] <= 2 * errs["bf16"],
              f"{errs['program']:.3e} against {errs['bf16']:.3e}")
        check(f"seed {seed} 8-bit operands would be caught",
              errs["float8_e4m3"] > tol, f"{errs['float8_e4m3']:.3e} > {tol}")
        check(f"seed {seed} loss inside the band, and outside it with no "
              f"logits_scaling",
              lo <= errs["loss"] <= hi
              and not lo <= errs["loss with logits_scaling 1"] <= hi,
              f"{errs['loss']:.4f} in [{lo}, {hi}], "
              f"{errs['loss with logits_scaling 1']:.4f} not")


def gradient_checks(conf, ref, seed, seq):
    """Check 4: three layers at the published widths."""
    conf = dict(conf, num_hidden_layers=3, num_dense_layers=3,
                layer_types=["mamba", "attention", "mamba"])
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    batch = batch_of(conf, seq, seed)
    picked = [("mamba_0", k) for k in ref.MIXER] \
        + [("mamba_2", "A_log"), ("mamba_2", "in_proj")] \
        + [("attn_1", k) for k in ref.ATTN] \
        + [("operator_norm_1", "scale"), ("ffn_norm_2", "scale"),
           ("gate_proj_0", "kernel"), ("down_proj_0", "kernel"),
           ("embed_tokens", "kernel"), ("lm_head", "kernel")]

    def pick(grads):
        return {f"{n}.{w}": grads[n][w] for n, w in picked}

    def reference_grads(params):
        value, grads = jax.value_and_grad(lambda p: ref.loss(
            named(ff, p), conf, batch["input_ids"], batch["position_ids"],
            batch["label"][..., 0]))(params)
        return value, pick(grads)

    @jax.jit
    def rounded(params):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference_grads(params)

    lp, gp, counters = program_grads(ff, batch, pick, ("ssm.",))(ff.params)
    lr, gr = jax.jit(reference_grads)(ff.params)
    lb, gb = rounded(ff.params)
    print(f"  counters {({k: float(v) for k, v in counters.items()})}",
          flush=True)
    e = abs(float(lp) - float(lr)) / float(lr)
    eb = abs(float(lb) - float(lr)) / float(lr)
    READINGS["loss"] = {"program": float(lp), "reference": float(lr),
                        "reference, bf16 operands": float(lb)}
    check(f"loss at {seq} positions, three layers", e <= 2 * eb + 1e-4,
          f"{float(lp):.6f} against {float(lr):.6f}: rel {e:.3e}; the "
          f"reference with bf16 operands reads {eb:.3e}")
    for name in gp:
        e, eb = l2(gp[name], gr[name]), l2(gb[name], gr[name])
        own = l2(gp[name], gb[name])
        READINGS[f"grad {name}"] = {
            "program": e, "reference, bf16 operands": eb,
            "program against that": own}
        check(f"gradient {name}", e <= 2 * eb + 1e-3,
              f"rel {e:.3e}; the reference with bf16 operands reads "
              f"{eb:.3e}, and the program against THAT {own:.3e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[5500201])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--grad-seq", type=int, default=1024)
    ap.add_argument("--skip-layer", action="store_true")
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--skip-kernels", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs",
                           "granite_4_0_h_micro.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "ssm_hybrid_ref")
    if not args.skip_forward:
        forward_checks(conf, ref, args.seq, args.seeds, not args.skip_layer)
        jax.clear_caches()
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0], args.grad_seq)
        jax.clear_caches()
    if not args.skip_kernels:
        kernel_check(conf, args.seq)
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
