"""On-chip validation of the block-diffusion training step at published
widths (run on a real TPU): what the benchmark's ``reference`` check
cannot see, and the readings its tolerance and band are set from. Run it
after a change to the flash kernels' ``block_diffusion`` form, the
noising op, the weighted loss or ``build_hybrid_conv_moe``'s
``"block_diffusion_attention"`` kind.

    python3 examples/tpu_validate_block_diffusion.py [--seeds 1 2 3]
        [--seq 4096] [--skip-kernels] [--skip-forward] [--skip-gradients]
        [--gradient-variants default xla float32] [--load-seeds 1 2 ...]
    python3 examples/tpu_validate_block_diffusion.py --time-kernels
        [--sub 128 256 512] [--tree _parent]

``--time-kernels`` does one thing and stops: the three flash kernels
ALONE at the cell's shapes (32 query heads on 4 key/value heads of 128,
bf16, 2 x ``--seq`` positions), sixteen forward + backward pairs in one
jit, ms a call on the device's clock and the share of the square each
grid computes, one JSON line a sub-block side of ``--sub`` (the noised
x noised diagonal tiles' walk, ``kernels/flash_attention.py::BD_SUB``;
default: the tree's own); ``--tree DIR`` times another checkout's
package (a ``git archive`` of the parent: every tile whole). It is the
go / no-go of a change to those kernels and how ``BD_SUB`` was chosen
(1.5 min a tree).

The model is ``benchmarks/configs/sdar_30b_a3b.json`` through the normal
path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``), the
reference ``benchmarks/reference/block_diffusion_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. Checks
(each prints PASS/FAIL, exit code 1 on any failure):

  1. the three flash kernels under the block-diffusion mask at 4 query
     heads on 1 key/value head, 2 L = ``2 x --seq`` positions, d 128,
     bf16, forward and the three gradients, against a plain softmax
     under the explicit (2 L)^2 mask at ``highest`` precision; the
     ``flash.grid`` instants say what the grids visit of the square;
  2. per seed at one sequence of ``--seq`` tokens: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), the eval-mode loss against the
     reference's and ``c (ln V + half the logits' variance)``, and the
     ``attn.block_diffusion`` instants (the flash kernels in every
     layer);
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded: bf16
     everywhere but the routers (the configuration's stated precision),
     bf16 in the routers too, and float8_e4m3 everywhere but the
     routers. The tolerance has to lie over the first and under the
     last;
  4. what the measure does NOT see: the reference with one rule swapped
     for another model's (``perturbed``: a causal mask on the clean half
     only, a noised query allowed its own block's clean keys, positions
     0 .. 2 L - 1, weights all 1), each against the reference as it is,
     by the log-probabilities and by the loss;
  4b. per ``--load-seeds`` seed: the rows the six routers send this
     share against the layers' budgets (``load_checks``);
  5. at 1,024 tokens (2,048 positions) with rematerialised blocks, in
     TRAINING mode (the reference is handed the step's key): the loss
     and its gradient for ``wk`` of the first and the last layer, a q
     and a k norm scale, a router, one held expert's ``w_down`` and the
     embedding's mask row, against ``jax.grad`` of the reference's loss,
     each held to twice what the reference itself reads with bf16
     operands. ``correct`` sees no gradient.
"""
import argparse
import importlib
import json
import math
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
if "--tree" in sys.argv:        # another checkout's package, ahead of ours
    sys.path.insert(0, os.path.abspath(
        sys.argv[sys.argv.index("--tree") + 1]))

from benchmarks.harness import cells  # noqa: E402
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, check_budget, l2,
    named, program_grads, rel)
from flexflow_tpu.kernels import flash_attention  # noqa: E402
from flexflow_tpu.kernels.flash_attention import (  # noqa: E402
    block_diffusion_mask)
from flexflow_tpu.obs import events  # noqa: E402

ROUNDED = (("bf16, routers float32", dict(matmul=jnp.bfloat16)),
           ("bf16, routers too", dict(matmul=jnp.bfloat16,
                                      router=jnp.bfloat16)),
           ("float8_e4m3, routers float32",
            dict(matmul=jnp.float8_e4m3fn)))


def kernels(length, block):
    """4 query heads on 1 key/value head, read in place."""
    ks = jax.random.split(jax.random.key(64), 4)
    q, k, v = (jax.random.normal(ks[i], (1, n, 2 * length, 128),
                                 jnp.bfloat16)
               for i, n in enumerate((4, 1, 1)))
    w = jax.random.normal(ks[3], (1, 4, 2 * length, 128), jnp.float32)
    mask = jnp.asarray(block_diffusion_mask(length, block))

    def gold(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = (jnp.repeat(x, 4, axis=1) for x in (k, v))
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(128)
        a = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", a, v,
                          precision=jax.lax.Precision.HIGHEST)

    def flash(q, k, v):
        return flash_attention(q, k, v, block_diffusion=(length, block))

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    tag = f"flash 128/128 under the block mask, L {length}, B {block}"
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
    square, live = 4 * (2 * length) ** 2, 4 * (length * length
                                               + length * block)
    for kernel, g in sorted(grids.items()):
        print(f"  {kernel}: {g}", flush=True)
        share = g["visited_pairs"] / square
        READINGS[f"{tag} {kernel} visited share"] = share
        check(f"{tag} {kernel} skips the dead quadrant and triangle",
              live <= g["visited_pairs"] < square / 2,
              f"visits {share:.4f} of the square, the mask attends "
              f"{live / square:.4f}")


def time_kernels(length, block, subs, calls=16):
    """The three kernels ALONE at the cell's shapes (32 query heads on 4
    key/value heads of 128, bf16, 2 L positions): ``calls`` forward +
    backward pairs in one jit, each waiting on the one before, and per
    kernel the device's own ms a call (a profiler trace, by op name) and
    the share of the square its grid visits (the ``flash.grid``
    instants); the jit's trace, lowering and compile seconds beside them
    (an unrolled kernel body is paid there, and so in ``setup_s``). One
    JSON line a sub-block side of ``subs`` (None: the tree's own; a tree
    without ``BD_SUB``, the parent of PR 65, scores every tile whole)."""
    from benchmarks.harness import trace_reduce
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    ks = jax.random.split(jax.random.key(65), 4)
    q, k, v, w = (jax.random.normal(ks[i], (1, n, 2 * length, 128),
                                    jnp.bfloat16)
                  for i, n in enumerate((32, 4, 4, 32)))
    square = 32 * (2 * length) ** 2

    def pairs(q, k, v):
        for _ in range(calls):          # the next pair waits on this one
            o, pull = jax.vjp(lambda *x: fa.flash_attention(
                *x, block_diffusion=(length, block)), q, k, v)
            dq, dk, dv = pull(w)
            q, k, v = q + 0 * (dq + o), k + 0 * dk, v + 0 * dv
        return o, dq, dk, dv

    first = None
    for sub in subs or [None]:
        if sub is not None:
            fa.BD_SUB = sub
        jax.clear_caches()
        events.enable()
        events.clear()
        t0 = time.perf_counter()
        traced = jax.jit(pairs).trace(q, k, v)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        fn = lowered.compile()
        t3 = time.perf_counter()
        grids = {e["attrs"]["kernel"]: e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"}
        events.clear()
        events.disable()
        out = jax.block_until_ready(fn(q, k, v))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                jax.block_until_ready(fn(q, k, v))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        ms = {}         # by kernel where the op's name holds one's
        for ops in ev["devices"].values():
            for name, ns in trace_reduce.self_times(ops).items():
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                name = next((n for n in grids if n in name), name)
                ms[name] = ms.get(name, 0.0) + ns / calls / 1e6
        first = first or out
        far = max(float(rel(a, b)) for a, b in zip(out, first))
        line = dict(
            check="time_kernels", tree=os.path.abspath(fa.__file__).rsplit(
                os.sep, 3)[0],
            bd_sub=getattr(fa, "BD_SUB", 0), length=length,
            block=block, calls=calls, device=jax.devices()[0].device_kind,
            ms_a_call={n: round(ms.get(n, 0.0), 4) for n in grids},
            ms_of_the_three=round(sum(ms.get(n, 0.0) for n in grids), 4),
            other_ms_a_pair=round(sum(t for n, t in ms.items()
                                      if n not in grids), 4),
            visited_share={n: g["visited_pairs"] / square
                           for n, g in grids.items()},
            trace_s=round(t1 - t0, 3), lower_s=round(t2 - t1, 3),
            compile_s=round(t3 - t2, 3), against_the_first=far)
        READINGS[f"time_kernels sub {line['bd_sub']}"] = line
        print(json.dumps(line), flush=True)
        check(f"sub-blocks of {line['bd_sub']}: the outputs are the first "
              f"variant's", far < 1e-2, f"rel {far:.3e}")


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)
    log_v = math.log(conf["vocab_size"])

    def parts(params, batch):
        outs, _, aux, capture = ff.executor._forward(
            params, ff.state, batch, False, jnp.int32(0))
        loss, _ = ff.executor._loss_and_metrics(outs, capture,
                                                batch["label"], aux)
        got = jnp.log(jnp.clip(outs[0], 1e-30))
        args = (named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        logits = capture[ff.executor._logits_tensor.guid]
        return got, (loss, logits.astype(jnp.float32)), args

    @jax.jit
    def program(params, batch):
        got, (loss, logits), args = parts(params, batch)
        want = ref.block_diffusion_moe_decoder(*args)
        return {"program": rel(got, want), "loss": loss,
                "reference loss": ref.loss(*args, batch["label"][..., 0]),
                "c": jnp.mean(ref.weights(sizes, batch["input_ids"])),
                "logits variance": jnp.mean(jnp.var(logits, -1))}

    def rounded(label, kw):
        @jax.jit
        def f(params, batch):
            got, _, args = parts(params, batch)
            want = ref.block_diffusion_moe_decoder(*args)
            with ref.rounded_operands(**kw):
                low = ref.block_diffusion_moe_decoder(*args)
            out = {label: rel(low, want)}
            if label == ROUNDED[0][0]:
                out["program, against bf16 reference"] = rel(got, low)
            return out
        return f

    def wrong(rule):
        @jax.jit
        def f(params, batch):
            args = (named(ff, params), sizes, batch["input_ids"],
                    batch["position_ids"])
            want = ref.block_diffusion_moe_decoder(*args)
            with ref.perturbed(rule):
                other = ref.block_diffusion_moe_decoder(*args)
                other_loss = ref.loss(*args, batch["label"][..., 0])
            return {f"{rule}": rel(other, want),
                    f"{rule}, loss": other_loss}
        return f

    fns = [program] + [rounded(label, kw) for label, kw in ROUNDED]
    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    for n, seed in enumerate(seeds):
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        batch = batch_of(conf, seq, seed)
        errs = {}
        events.enable()
        events.clear()
        for fn in fns + ([wrong(r) for r in ref.PERTURBATIONS]
                         if n == 0 else []):
            errs.update({k: float(v) for k, v in fn(ff.params,
                                                    batch).items()})
        noted = [e["attrs"] for e in events.events()
                 if e["name"] == "attn.block_diffusion"]
        noise = [e["attrs"] for e in events.events()
                 if e["name"] == "diffusion.noise"]
        events.clear()
        events.disable()
        READINGS[f"seed {seed}"] = errs
        print(f"seed {seed}: " + ", ".join(
            f"{k} {v:.4e}" for k, v in errs.items()), flush=True)
        if n == 0:
            print(f"  attn.block_diffusion: {noted[:1]}; diffusion.noise: "
                  f"{noise[:1]}", flush=True)
            layers = {a["layer"]: a["impl"] for a in noted}
            check("the flash kernels draw the mask in every layer",
                  len(layers) == conf["num_hidden_layers"]
                  and set(layers.values()) == {"flash"}, f"{layers}")
            check("the eval pass draws from the configuration's seed",
                  noise and all(a["key"] == "eval" for a in noise),
                  f"{noise[:1]}")
        check(f"seed {seed} within the cell's tolerance",
              errs["program"] <= tol, f"{errs['program']:.3e} <= {tol}")
        check(f"seed {seed} as near as bf16 operands allow",
              errs["program"] <= 2 * errs["bf16, routers float32"],
              f"{errs['program']:.3e} against "
              f"{errs['bf16, routers float32']:.3e}")
        check(f"seed {seed} 8-bit operands would be caught",
              errs["float8_e4m3, routers float32"] > tol,
              f"{errs['float8_e4m3, routers float32']:.3e} > {tol}")
        expected = errs["c"] * (log_v + errs["logits variance"] / 2)
        check(f"seed {seed} loss inside the band and the reference's",
              lo <= errs["loss"] <= hi
              and abs(errs["loss"] / errs["reference loss"] - 1) < 1e-3,
              f"{errs['loss']:.4f} in [{lo}, {hi}]; the reference's "
              f"{errs['reference loss']:.4f}; c (ln V + var / 2) = "
              f"{errs['c']:.5f} x ({log_v:.4f} + "
              f"{errs['logits variance'] / 2:.4f}) = {expected:.4f}")
    return ff


def load_checks(ff, conf, seq, seeds):
    """Per ``--load-seeds`` seed: the rows the six routers send this
    share in the eval step against the layers' budgets. About half the
    noised positions hold ONE id (the mask's) and route alike, so under
    a plain draw of the routers the held load, and with it the grouped
    products' time, moved with the seed (44,603 to 53,217 rows over 20
    seeds, PR 64). The configuration's routers are drawn as this share's
    columns repeated for every share (``router_repeats``): the rows are
    then the uniform share exactly, at every seed, and a reading off it
    fails here. No layer may be over its budget."""
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    from flexflow_tpu.runtime.metrics import COUNTER_PREFIX

    @jax.jit
    def counters(params, batch):
        outs, _, aux, capture = ff.executor._forward(
            params, ff.state, batch, False, jnp.int32(0))
        _, bm = ff.executor._loss_and_metrics(outs, capture, batch["label"],
                                              aux)
        return {k[len(COUNTER_PREFIX):]: v for k, v in bm.items()
                if k.startswith(COUNTER_PREFIX + "moe.")}

    layers = [l for l in ff.executor.program.layers
              if l.op_type.name == "OP_ROUTED_EXPERTS"]
    budget = sum(RoutedExpertsOp.rows_multiplied(2 * seq, l.params)
                 for l in layers)
    uniform = len(layers) * 2 * seq * conf["num_experts_per_tok"] \
        * conf["num_experts"] // conf["num_experts_published"]
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        c = {k: float(v) for k, v in counters(
            ff.params, batch_of(conf, seq, seed)).items()}
        READINGS[f"load seed {seed}"] = c
        alike = conf.get("router_repeats", 1) * conf["num_experts"] \
            == conf["num_experts_published"]
        check(f"load seed {seed}: no expert layer over its row budget"
              + (", the uniform share exactly" if alike else ""),
              c.get("moe.overflow") == 0.0 and c.get("moe.dropped") == 0.0
              and (not alike or c.get("moe.local_assignments") == uniform),
              f"{c.get('moe.local_assignments'):.0f} rows sent here ("
              f"{uniform} under uniform routing) against {budget} "
              f"budgeted over {len(layers)} layers; load_max "
              f"{c.get('moe.load_max'):.0f}")


def gradient_checks(conf, ref, seed, seq=1024, variant="default"):
    """``variant``: ``default`` (the cell's own paths and precision),
    ``xla`` (the mask drawn off the kernels, bf16 operands) or
    ``float32`` (the kernels, float32 operands): the last two say whether
    a reading over the yardstick is the kernels' or the precision's."""
    ff = build(conf, seq, "blocks", "xla" if variant == "xla" else None)
    if variant == "float32":        # read when the step is traced, below
        ff.config.use_bf16_compute = False
    tag = "" if variant == "default" else f"[{variant}] "
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    batch = batch_of(conf, seq, seed)
    last = conf["num_hidden_layers"] - 1
    picked = (("attn_0", "wk"), (f"attn_{last}", "wk"),
              ("attn_2", "q_norm"), ("attn_2", "k_norm"),
              ("experts_3", "wg"), ("experts_3", "w_down"),
              ("embed_tokens", "kernel"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_3.w_down"] = out["experts_3.w_down"][3]   # one expert
        out["embed_tokens.kernel"] = \
            out["embed_tokens.kernel"][conf["mask_token_id"]]  # the mask row
        return out

    # the reference draws what the TRAINING step draws at step index 0
    sizes = dict(conf, noise_key=ff.executor._rngs_for_step(
        jnp.int32(0))["noise"])

    def reference_grads(params):
        value, grads = jax.value_and_grad(lambda p: ref.loss(
            named(ff, p), sizes, batch["input_ids"], batch["position_ids"],
            batch["label"][..., 0]))(params)
        return value, pick(grads)

    @jax.jit
    def rounded(params):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference_grads(params)

    lp, gp, counters = program_grads(ff, batch, pick)(ff.params)
    lr, gr = jax.jit(reference_grads)(ff.params)
    lb, gb = rounded(ff.params)
    check_budget(ff, 2 * seq, counters, False)
    e = abs(float(lp) - float(lr)) / float(lr)
    eb = abs(float(lb) - float(lr)) / float(lr)
    READINGS[f"{tag}training loss"] = {"program": float(lp),
                                 "reference": float(lr),
                                 "reference, bf16 operands": float(lb)}
    check(f"{tag}training loss under the step's mask", e <= 2 * eb + 1e-4,
          f"{float(lp):.6f} against {float(lr):.6f}: rel {e:.3e}; the "
          f"reference with bf16 operands reads {eb:.3e}")
    for name in gp:
        e, eb = l2(gp[name], gr[name]), l2(gb[name], gr[name])
        own = l2(gp[name], gb[name])
        READINGS[f"{tag}grad {name}"] = {
            "program": e, "reference, bf16 operands": eb,
            "program against that": own}
        check(f"{tag}gradient {name}", e <= 2 * eb + 1e-3,
              f"rel {e:.3e}; the reference with bf16 operands reads "
              f"{eb:.3e}, and the program against THAT {own:.3e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[6400201])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--load-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--time-kernels", action="store_true",
                    help="the three kernels alone at the cell's shapes, "
                    "then stop")
    ap.add_argument("--sub", type=int, nargs="*", default=[],
                    help="--time-kernels: sub-block sides to time in turn "
                    "(default: the tree's own BD_SUB)")
    ap.add_argument("--tree", default=ROOT,
                    help="time or validate another checkout's package "
                    "(a git archive of the parent in _parent/)")
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--gradient-variants", nargs="+", default=["default"],
                    choices=["default", "xla", "float32"])
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "block_diffusion_moe_ref")
    if args.time_kernels:
        time_kernels(args.seq, conf["block_length"], args.sub)
        print("READINGS " + json.dumps(READINGS), flush=True)
        return 1 if FAILED else 0
    if not args.skip_kernels:
        kernels(args.seq, conf["block_length"])
    if not args.skip_forward:
        ff = forward_checks(conf, ref, args.seq, args.seeds)
        load_checks(ff, conf, args.seq, args.load_seeds)
        del ff
        jax.clear_caches()
    if not args.skip_gradients:
        for variant in args.gradient_variants:
            gradient_checks(conf, ref, args.seeds[0], variant=variant)
            jax.clear_caches()
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
