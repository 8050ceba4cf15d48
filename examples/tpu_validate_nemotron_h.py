"""On-chip validation of the one-sub-layer-a-block decoder (grouped
Mamba-2 mixers, NoPE attention, LatentMoE feed-forwards) at published
widths (run on a real TPU): what the benchmark's ``reference`` check
cannot see, and the readings its tolerance is set from. Run it after a
change to ``ops/recurrent_ops.py::StateSpaceMixerOp``'s groups or
``kernels/state_space.py``, ``ops/moe_ops.py::RoutedExpertsOp``'s latent
or ReLU-squared experts, or ``build_hybrid_conv_moe``'s blocks of one
sub-layer.

    python3 examples/tpu_validate_nemotron_h.py [--seeds 1 2 3]
        [--seq 4096] [--grad-seq 1024] [--skip-forward]
        [--skip-gradients] [--skip-kernels]

The model is ``benchmarks/configs/nemotron3_super_120b_a12b.json``
through the normal path (``FFModel`` -> ``build_hybrid_conv_moe`` ->
``compile``), the reference ``benchmarks/reference/nemotron_h_ref.py``
(float32, ``highest``, the recurrence token by token, the experts a
loop), both at the same weights drawn from each seed. Checks (each
prints PASS/FAIL, exit code 1 on any failure):

  1. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), the eval-mode loss, the counter
     ``ssm.min_chunk_log_decay`` a layer and the experts' counters
     against their row budget;
  2. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 (the configuration's stated precision)
     and an 8-bit float (e4m3). The tolerance has to lie over the first
     and under the last. Printed and not judged, what the measure sees
     of two other models at the same weights: every head reading group
     0's B and C (those columns copied over every group's), and the
     routed experts left out (``routed_scaling_factor`` 0);
  3. gradients at ``--grad-seq`` positions of the model cut to its last
     three layers' kinds (``EM*``) at the published widths with
     rematerialised blocks off the table (one [moe, mamba] pair is no
     run), so that the token-by-token reference's backward fits beside
     it: the loss and its gradient for every weight of the mixer, of the
     expert layer and of the attention layer, a norm's scale, the
     embedding and the head, against ``jax.grad`` of the reference's
     loss, each held to twice what the reference itself reads with bf16
     operands; then the same with the experts' overflow forced (2 added
     to the held experts' bias: every assignment is theirs and the
     layer loops over the further chunks of its budget). ``correct``
     sees no gradient;
  4. the grouped recurrence ALONE at the cell's shape (32 heads of 64 in
     2 groups, a state of 128, chunks of 128) over ``--seq`` positions
     with bf16 operands, down the kernels and down the plain path
     (``state_space_scan(kernels=False)``): the two outputs against each
     other, and the output and each of the five gradients of both
     against the plain path in float32 (``highest``), the kernels held
     to twice what the plain path itself reads with bf16 operands; one
     ``ssm.kernel`` instant a call, each saying ``groups`` 2.
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
    BENCH, FAILED, READINGS, batch_of, build, check, check_budget,
    expert_layers, force_overflow, l2, named, program_grads, rel)
from flexflow_tpu.kernels import state_space as ssm_kernels  # noqa: E402
from flexflow_tpu.obs import events  # noqa: E402
from flexflow_tpu.ops.recurrent_ops import state_space_scan  # noqa: E402
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX  # noqa: E402

ROUNDED = (("bf16", jnp.bfloat16), ("float8_e4m3", jnp.float8_e4m3fn))
CONFIG = "nemotron3_super_120b_a12b"


def kernel_check(conf, seq):
    """Check 4."""
    h, p, n, g, c = (conf["mamba_num_heads"], conf["mamba_head_dim"],
                     conf["ssm_state_size"], conf["n_groups"],
                     conf["chunk_size"])
    ks = jax.random.split(jax.random.key(66), 6)
    x = jax.random.normal(ks[0], (1, seq, h, p), jnp.float32)
    dt = jax.random.uniform(ks[1], (1, seq, h), jnp.float32, 1e-3, 0.1)
    a = -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
    bm, cm = (jax.random.normal(k, (1, seq, g, n), jnp.float32)
              for k in ks[3:5])
    w = jax.random.normal(ks[5], x.shape, jnp.float32)

    def run(mdt, kernels):
        def loss(*v):
            y, _ = state_space_scan(*v, c, mdt, layer="alone",
                                    kernels=kernels)
            return jnp.sum(y * w), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(5), has_aux=True))(x, dt, a, bm, cm)
        return (y,) + tuple(grads)

    events.enable()
    events.clear()
    try:
        fast = run(jnp.bfloat16, True)
        said = [e["attrs"] for e in events.events()
                if e["name"] == "ssm.kernel"]
    finally:
        events.disable()
        events.clear()
    plain = run(jnp.bfloat16, False)
    with jax.default_matmul_precision("highest"):
        gold = run(jnp.float32, False)
    check("the grouped scan's kernels announced themselves",
          sorted(s["kernel"] for s in said) == ["bwd", "fwd"]
          and all(s["groups"] == g and s["chunk"] == c for s in said)
          and ssm_kernels.takes_kernel(c, h, p, n, g), f"{said}")
    for name, f, q, z in zip(("y", "dx", "ddt", "dA", "dB", "dC"), fast,
                             plain, gold):
        e, eb = l2(f, z), l2(q, z)
        READINGS[f"scan {name}"] = {"kernels": e, "plain, bf16": eb,
                                    "kernels against plain": l2(f, q)}
        check(f"grouped scan {name}", e <= 2 * eb + 1e-3,
              f"kernels {e:.3e}, the plain path with bf16 operands "
              f"{eb:.3e}, one against the other {l2(f, q):.3e}")


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    unrouted = dict(conf, routed_scaling_factor=0.0)

    def one_group(layers):
        """The weights with group 0's B and C columns copied over every
        group's: in_proj's columns and the convolution's rows."""
        h, p, n, g = (conf["mamba_num_heads"], conf["mamba_head_dim"],
                      conf["ssm_state_size"], conf["n_groups"])
        inner = h * p
        out = []
        for name, w in layers:
            if "in_proj" in w:
                w = dict(w)
                for start in (inner, inner + g * n):     # B, then C
                    rows = slice(start, start + n)
                    for j in range(1, g):
                        to = slice(start + j * n, start + (j + 1) * n)
                        w["in_proj"] = w["in_proj"].at[
                            :, inner + to.start:inner + to.stop].set(
                            w["in_proj"][:, inner + rows.start:
                                         inner + rows.stop])
                        w["conv_w"] = w["conv_w"].at[to].set(
                            w["conv_w"][rows])
                        w["conv_b"] = w["conv_b"].at[to].set(
                            w["conv_b"][rows])
            out.append((name, w))
        return out

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
        want = ref.nemotron_h_decoder(layers, conf, ids, pos)
        loss = -jnp.mean(jnp.take_along_axis(got, batch["label"], -1))
        out = {"program": rel(got, want), "loss": loss,
               "min chunk log-decay a layer":
               bm[COUNTER_PREFIX + "ssm.min_chunk_log_decay"]
               / bm[COUNTER_PREFIX + "ssm.layers"]}
        for key in ("moe.local_assignments", "moe.dropped", "moe.overflow",
                    "moe.load_max"):
            out[key] = bm[COUNTER_PREFIX + key]
        out["every head on group 0's B and C"] = rel(
            ref.nemotron_h_decoder(one_group(layers), conf, ids, pos), want)
        out["routed experts left out"] = rel(ref.nemotron_h_decoder(
            layers, unrouted, ids, pos), want)
        return out

    def rounded(label, dtype):
        @jax.jit
        def f(params, batch):
            got, (layers, ids, pos), _ = parts(params, batch)
            want = ref.nemotron_h_decoder(layers, conf, ids, pos)
            with ref.rounded_operands(matmul=dtype):
                low = ref.nemotron_h_decoder(layers, conf, ids, pos)
            out = {label: rel(low, want)}
            if label == "bf16":
                out["program, against bf16 reference"] = rel(got, low)
            return out
        return f

    fns = [program] + [rounded(label, dtype) for label, dtype in ROUNDED]
    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
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
        check(f"seed {seed} loss inside the band", lo <= errs["loss"] <= hi,
              f"{errs['loss']:.4f} in [{lo}, {hi}]")
        check(f"seed {seed} nothing dropped, no layer past its budget",
              errs["moe.dropped"] == 0 and errs["moe.overflow"] == 0,
              f"{errs['moe.local_assignments']:.0f} assignments in five "
              f"layers, the fullest expert {errs['moe.load_max']:.0f}")


def gradient_checks(conf, ref, seed, seq):
    """Check 3: one layer of each kind at the published widths."""
    conf = dict(conf, num_hidden_layers=3, hybrid_override_pattern="EM*",
                layer_types=["moe", "mamba", "attention"])
    ff = build(conf, seq, "none")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    batch = batch_of(conf, seq, seed)
    picked = [("mamba_1", k) for k in ref.MIXER] \
        + [("experts_0", k) for k in ref.EXPERTS] \
        + [("attn_2", k) for k in ref.ATTN] \
        + [("ffn_norm_0", "scale"), ("operator_norm_2", "scale"),
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

    program = program_grads(ff, batch, pick, ("moe.", "ssm."))
    for forced in (False, True):
        params = force_overflow(ff, ff.params) if forced else ff.params
        label = "overflow forced" if forced else "as routed"
        lp, gp, counters = program(params)
        lr, gr = jax.jit(reference_grads)(params)
        lb, gb = rounded(params)
        moe = {k: float(v) for k, v in counters.items()
               if k.startswith("moe.")}
        if forced:
            # 22 distinct experts a token, 8 of them held: a token has
            # at most ``held`` assignments here, and with the bias forced
            # it has them all (``check_budget`` asks for ``top_k``)
            (layer,) = expert_layers(ff)
            held = seq * min(layer.params["top_k"],
                             layer.params["experts_held"])
            READINGS["counters, overflow forced"] = moe
            check("overflow forced: the layer ran the further chunks and "
                  "dropped nothing",
                  moe["moe.overflow"] == 1 and moe["moe.dropped"] == 0
                  and moe["moe.local_assignments"] == held,
                  f"{moe}, {held} assignments of the held experts")
        else:
            check_budget(ff, seq, moe, forced)
        e = abs(float(lp) - float(lr)) / float(lr)
        eb = abs(float(lb) - float(lr)) / float(lr)
        READINGS[f"loss, {label}"] = {
            "program": float(lp), "reference": float(lr),
            "reference, bf16 operands": float(lb)}
        check(f"loss at {seq} positions, three layers, {label}",
              e <= 2 * eb + 1e-4,
              f"{float(lp):.6f} against {float(lr):.6f}: rel {e:.3e}; the "
              f"reference with bf16 operands reads {eb:.3e}")
        # the balancing rule's: each published expert's assignments over
        # the uniform share, none of it the loss's
        excess = np.asarray(gp.pop("experts_0.bias"))
        gr.pop("experts_0.bias"), gb.pop("experts_0.bias")
        routed = expert_layers(ff)[0].params
        loads = excess + seq * routed["top_k"] / routed["num_experts"]
        check(f"the routers' bias is handed the loads, {label}",
              np.all(loads == np.round(loads)) and loads.min() >= 0
              and 0 <= loads.sum() - seq * routed["top_k"] <= 2,  # a tie
              f"{loads.sum():.0f} assignments over {len(loads)} experts, "
              f"{loads.min():.0f} to {loads.max():.0f} an expert")
        for name in gp:
            e, eb = l2(gp[name], gr[name]), l2(gb[name], gr[name])
            own = l2(gp[name], gb[name])
            READINGS[f"grad {name}, {label}"] = {
                "program": e, "reference, bf16 operands": eb,
                "program against that": own}
            check(f"gradient {name}, {label}", e <= 2 * eb + 1e-3,
                  f"rel {e:.3e}; the reference with bf16 operands reads "
                  f"{eb:.3e}, and the program against THAT {own:.3e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[6600201])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--grad-seq", type=int, default=1024)
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--skip-kernels", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "nemotron_h_ref")
    if not args.skip_kernels:
        kernel_check(conf, args.seq)
        jax.clear_caches()
    if not args.skip_forward:
        forward_checks(conf, ref, args.seq, args.seeds)
        jax.clear_caches()
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0], args.grad_seq)
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
