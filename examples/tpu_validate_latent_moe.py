"""On-chip validation of the DeepSeek-V3-shaped decoder at published
widths (run on a real TPU): what the benchmark's ``reference`` check
cannot see, and the readings its tolerance is set from.

    python3 examples/tpu_validate_latent_moe.py [--seeds 1 2 3] [--seq 4096]

The model is ``benchmarks/configs/joyai_llm_flash.json`` through the
normal path (``FFModel`` -> ``build_latent_moe`` -> ``compile``), the
reference ``benchmarks/reference/latent_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. Checks
(each prints PASS/FAIL, exit code 1 on any failure):

  1. the three flash kernels at (bh 32, s 4096, d 192 / dv 128, bf16,
     causal), forward and the three gradients, against
     ``mha_reference`` at ``highest`` precision;
  2. per seed at one sequence of ``--seq`` positions: the main head's
     and the multi-token-prediction head's log-probabilities against the
     reference (``|sys - ref|_2 / |ref|_2``, the runner's measure), and
     how many of the tokens x layers x 8 expert choices differ between
     the program (bf16 operands, float32 routers) and the reference;
  3. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last. (A bf16 router cannot be
     told from a float32 one by it: on the chip it reads 3 to 6% more
     than the first, because the bf16 products before each router have
     already moved 1.8% of the choices; PERF.md section 6, PR 29);
  4. at 1024 positions (the reference's backward keeps every layer's
     s x s probabilities): the loss and its gradient for one expert's
     weights, a router's and a latent projection's, against ``jax.grad``
     of the reference's loss, each held to twice what the reference
     itself reads with bf16 operands; each expert layer's row budget
     beside what its router sent this share, with nothing dropped and no
     second chunk run;
  5. the same with the overflow forced (2 added to the held experts'
     correction bias, in program and reference alike: every choice of
     every token is theirs, eight times the budget's rows): every layer
     runs the further chunks, drops nothing, and the gradients are still
     the reference's;
  6. one routed-experts layer ALONE at the cell's width (4096 tokens of
     hidden 2048, top 8 of 256, 16 held, the shared expert beside
     them), as routed and with the overflow forced: the way back to the
     tokens through ``kernels/moe_token_sum.py`` (the check prints the
     ``moe.route`` instant's ``token_sum`` and the ``moe.kernel``
     calls, and fails if the shapes did not take the kernel), its
     output and the five gradients (the input, the router, the three
     stacked weights) against ``jax.grad`` of the reference's layer,
     each held to twice what the reference itself reads with bf16
     operands, and against the same layer down the plain path.
"""
import argparse
import dataclasses
import json
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel  # noqa: E402
from flexflow_tpu.kernels import flash_attention, mha_reference  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
FAILED = []
READINGS = {}


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        FAILED.append(name)


def rel(got, want):
    """``|got - want|_2 / |want|_2``, the runner's measure (traceable)."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want ** 2))


def l2(got, want):
    return float(rel(got, want))


def kernels():
    ks = jax.random.split(jax.random.key(29), 4)
    q, k = (jax.random.normal(ks[i], (1, 32, 4096, 192), jnp.bfloat16)
            for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 32, 4096, 128), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, 32, 4096, 128), jnp.float32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    flash = jax.jit(jax.value_and_grad(lambda *a: loss(
        lambda q, k, v: flash_attention(q, k, v, causal=True), *a),
        argnums=(0, 1, 2)))
    gold = jax.jit(jax.value_and_grad(lambda *a: loss(
        lambda q, k, v: mha_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True,
            precision=jax.lax.Precision.HIGHEST), *a), argnums=(0, 1, 2)))
    (lf, gf), (lg, gg) = flash(q, k, v), gold(q, k, v)
    out = l2(flash_attention(q, k, v, causal=True),
             mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True,
                           precision=jax.lax.Precision.HIGHEST))
    # bf16 operands and a bf16 output against float32: 2^-9 a rounding
    # and a few of them; PR 28 read 3e-3 to 9e-3 at d 64 (tolerance 4e-2)
    check("flash 192/128 forward", out < 2e-2, f"rel {out:.3e}")
    for name, a, b in zip(("dq", "dk", "dv"), gf, gg):
        e = l2(a, b)
        READINGS[f"flash_{name}"] = e
        check(f"flash 192/128 {name}", e < 4e-2, f"rel {e:.3e}")
    READINGS["flash_fwd"] = out


def build(conf, seq, remat, impl=None):
    """The model through the normal path; ``impl`` forces its attention
    layers' kernel (None: each layer's own choice from its shapes)."""
    cls = cells.load_attr(conf["config_class"])
    model_cfg = cls(**{f.name: conf[f.name]
                       for f in dataclasses.fields(cls) if f.name in conf})
    cfg = FFConfig()
    cfg.batch_size = 1
    cfg.remat = remat
    if impl:
        cfg.kernel_impls = f"attention:{impl}"
    ff = FFModel(cfg)
    out = cells.load_attr(conf["builder"])(ff, 1, seq, model_cfg)
    ff.compile(AdamOptimizer(1e-5), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    ff.opt_state = None            # room for the reference beside it
    return ff


def batch_of(conf, seq, seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, conf["vocab_size"], (1, seq)).astype(np.int32)
    pos = np.arange(seq, dtype=np.int32)[None]
    return {"input_ids": jnp.asarray(ids), "position_ids": jnp.asarray(pos),
            "label": jnp.asarray(np.roll(ids, -1, 1)[..., None])}


def named(ff, params):
    return [(l.name, params[l.name]) for l in ff.executor.program.layers
            if l.name in params]


def program_forward(ff, params, batch):
    """(main log-probs, MTP log-probs, the experts layers' inputs)."""
    ex = ff.executor
    outs, _, _, capture = ex._forward(params, ff.state, batch, False,
                                      jnp.int32(0))
    by_name = {l.name: l for l in ex.program.layers}
    mtp = capture[by_name["mtp_loss"].inputs[0].guid]
    routed = {n: capture[l.inputs[0].guid] for n, l in by_name.items()
              if n.startswith("experts_")}
    return (jnp.log(jnp.clip(outs[0], 1e-30)),
            jax.nn.log_softmax(mtp.astype(jnp.float32), -1), routed)


def choices(x, w, k):
    """The sets of experts a router picks, as a (tokens, experts) mask."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ w["wg"]) + w["bias"]
    return s >= jax.lax.top_k(s, k)[0][..., -1:]


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)
    k = conf["num_experts_per_tok"]

    @jax.jit
    def compare(params, batch):
        main, mtp, routed = program_forward(ff, params, batch)
        layers = named(ff, params)
        want_main, want_mtp = ref.heads(layers, sizes, batch["input_ids"],
                                        batch["position_ids"])
        out = {"main": rel(main, want_main), "mtp": rel(mtp, want_mtp)}
        for label, kw in (
                ("bf16, routers float32", dict(matmul=jnp.bfloat16)),
                ("bf16, routers too", dict(matmul=jnp.bfloat16,
                                           router=jnp.bfloat16)),
                ("float8_e4m3, routers float32",
                 dict(matmul=jnp.float8_e4m3fn))):
            with ref.rounded_operands(**kw):
                low = ref.heads(layers, sizes, batch["input_ids"],
                                batch["position_ids"])[0]
            out[label] = rel(low, want_main)
            if "router" not in kw and kw["matmul"] == jnp.bfloat16:
                # the program against the reference at its OWN precision
                out["main, against bf16 reference"] = rel(main, low)
        return out, routed

    @jax.jit
    def reference_inputs(params, batch):
        """The reference's own inputs to each experts layer: its hidden
        states differ from the program's by then, so its routers see
        other numbers. Re-walk it, keeping what each router is fed."""
        fed = {}
        real = ref.routed

        def spy(x, w, s):
            fed[len(fed)] = x
            return real(x, w, s)
        ref.routed = spy
        try:
            ref.heads(named(ff, params), sizes, batch["input_ids"],
                      batch["position_ids"])
        finally:
            ref.routed = real
        return [fed[i] for i in range(len(fed))]

    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        batch = batch_of(conf, seq, seed)
        errs, routed = compare(ff.params, batch)
        errs = {n: float(v) for n, v in errs.items()}
        fed = reference_inputs(ff.params, batch)
        names = [n for n, _ in named(ff, ff.params)
                 if n.startswith("experts_")]
        differ = total = 0
        for name, x_ref in zip(names, fed):
            a = choices(routed[name], ff.params[name], k)
            b = choices(x_ref, ff.params[name], k)
            differ += int(jnp.sum(a & ~b))
            total += int(jnp.sum(b))
        READINGS[f"seed {seed}"] = dict(errs, choices_differ=differ,
                                        choices=total)
        print(f"seed {seed}: program main {errs['main']:.3e} mtp "
              f"{errs['mtp']:.3e}; {differ} of {total} expert choices "
              f"differ; reference with rounded operands: "
              + ", ".join(f"{n} {v:.3e}" for n, v in errs.items()
                          if n not in ("main", "mtp")), flush=True)
        own = errs.pop("main, against bf16 reference")
        check(f"seed {seed} nearer the reference at its own precision",
              own < errs["main"], f"{own:.3e} < {errs['main']:.3e}")
        tol = conf["reference_rel_tol"]
        check(f"seed {seed} main head within the cell's tolerance",
              errs["main"] <= tol, f"{errs['main']:.3e} <= {tol}")
        check(f"seed {seed} MTP head within the same",
              errs["mtp"] <= tol, f"{errs['mtp']:.3e} <= {tol}")
        check(f"seed {seed} 8-bit operands would be caught",
              errs["float8_e4m3, routers float32"] > tol,
              f"{errs['float8_e4m3, routers float32']:.3e} > {tol}")
    del ff


def force_overflow(ff, params):
    """2 added to the correction bias of the experts held here, in every
    expert layer: sigmoid scores lie in (0, 1), so every choice of
    every token is one of theirs, whatever the budget."""
    out = dict(params)
    for layer in expert_layers(ff):
        first, held = layer.params["first_held"], layer.params["experts_held"]
        w = params[layer.name]
        out[layer.name] = dict(
            w, bias=w["bias"].at[first:first + held].add(2.0))
    return out


def expert_layers(ff):
    from flexflow_tpu.ffconst import OperatorType
    return [l for l in ff.executor.program.layers
            if l.op_type == OperatorType.OP_ROUTED_EXPERTS]


def check_budget(ff, seq, counters, forced):
    """The step's ``moe.*`` counters (sums over the expert layers)
    against the layers' row budgets: on the budget's path no layer ran
    a second chunk; forced, every layer did. Nothing dropped either
    way."""
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    layers = expert_layers(ff)
    budgets = [RoutedExpertsOp.rows_multiplied(seq, l.params)
               for l in layers]
    rows = [seq * l.params["top_k"] for l in layers]
    c = {k: float(v) for k, v in counters.items()}
    label = "overflow forced" if forced else "as routed"
    READINGS[f"counters, {label}"] = dict(c, rows_budget=budgets)
    print(f"{label}: {len(layers)} expert layers, rows_budget {budgets} "
          f"of {rows} sorted rows; " + ", ".join(
              f"{k} {v:.0f}" for k, v in sorted(c.items())), flush=True)
    check(f"{label}: nothing dropped", c["moe.dropped"] == 0,
          f"moe.dropped {c['moe.dropped']:.0f}")
    if forced:
        check("overflow forced: every layer ran the further chunks",
              c["moe.overflow"] == len(layers)
              and c["moe.local_assignments"] == sum(rows)
              and all(b < r for b, r in zip(budgets, rows)),
              f"moe.overflow {c['moe.overflow']:.0f} of {len(layers)}, "
              f"moe.local_assignments {c['moe.local_assignments']:.0f} "
              f"of {sum(rows)}")
    else:
        check("as routed: every layer inside its budget",
              c["moe.overflow"] == 0
              and c["moe.local_assignments"] <= sum(budgets),
              f"moe.overflow {c['moe.overflow']:.0f}, "
              f"moe.local_assignments {c['moe.local_assignments']:.0f} "
              f"against budgets of {sum(budgets)} in all")


def program_grads(ff, batch, pick, prefixes=("moe.",)):
    """jitted ``params -> (loss, picked gradients, the counters whose
    names start with one of ``prefixes``)`` of the program's training
    loss."""
    from flexflow_tpu.runtime.metrics import COUNTER_PREFIX

    @jax.jit
    def program(params):
        def loss(p):
            ex = ff.executor
            outs, _, aux, capture = ex._forward(
                p, ff.state, batch, True, jnp.int32(0))
            value, bm = ex._loss_and_metrics(outs, capture, batch["label"],
                                             aux)
            return value, {k[len(COUNTER_PREFIX):]: v for k, v in bm.items()
                           if k.startswith(tuple(COUNTER_PREFIX + c
                                                 for c in prefixes))}
        (value, counters), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        return value, pick(grads), counters
    return program


def compare_gradients(ff, ref, sizes, batch, seq, pick, loss_label):
    """The program's training loss and its ``pick``ed gradients against
    ``jax.grad`` of the reference's loss, as routed and then with the
    overflow forced, each gradient held to twice what the reference
    itself reads with bf16 operands; the experts' counters against the
    layers' row budgets both times."""
    program = program_grads(ff, batch, pick)

    def reference_grads(params):
        value, grads = jax.value_and_grad(lambda p: ref.loss(
            named(ff, p), sizes, batch["input_ids"], batch["position_ids"],
            batch["label"][..., 0]))(params)
        return value, pick(grads)

    @jax.jit
    def rounded(params):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference_grads(params)

    plain = jax.jit(reference_grads)
    for forced in (False, True):
        params = force_overflow(ff, ff.params) if forced else ff.params
        tag = "overflow forced: " if forced else ""
        (lp, gp, counters), (lr, gr) = program(params), plain(params)
        lb, gb = rounded(params)
        check_budget(ff, seq, counters, forced)
        e = abs(float(lp) - float(lr)) / float(lr)
        eb = abs(float(lb) - float(lr)) / float(lr)
        READINGS[f"{tag}loss"] = {"program": float(lp),
                                  "reference": float(lr),
                                  "reference, bf16 operands": float(lb)}
        # The yardstick for "as near as its precision allows" is the
        # reference itself with every product's operands rounded to bf16
        # (routers float32): the same mathematics at the precision the
        # configuration states. An expert choice that flips under that
        # rounding moves a token's whole contribution, so these readings
        # are far above a dense model's (on the chip 7e-2 for a latent
        # projection, 2e-1 to 4e-1 for one expert's weights and the
        # router above them, whose gradients are sums over the few
        # tokens routed there). What this catches is what the
        # reference's forward cannot: a backward that is wrong by orders
        # of magnitude. It caught one (rows the grouped products leave
        # unwritten, read 1e5 here; PERF.md section 6, PR 29).
        check(f"{tag}{loss_label}", e <= 2 * eb + 1e-4,
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


def gradient_checks(conf, ref, seed, seq=1024):
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    picked = (("attn_4", "wq_a"), ("experts_4", "wg"),
              ("experts_4", "w_gate"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_4.w_gate"] = out["experts_4.w_gate"][3]   # one expert
        return out

    compare_gradients(ff, ref, dict(conf), batch_of(conf, seq, seed), seq,
                      pick, "loss (with the MTP term)")


def layer_checks(conf, ref, seed, tokens=4096):
    """Check 6: the experts' layer alone, kernel path against plain
    path and against the reference."""
    from flexflow_tpu.kernels import moe_token_sum as mts
    from flexflow_tpu.obs import events
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    from flexflow_tpu.ops.registry import EmitCtx
    e, f = conf["hidden_size"], conf["moe_intermediate_size"]
    n, held = conf["n_routed_experts_published"], conf["n_routed_experts"]
    first = conf.get("first_held_expert", 0)
    params = dict(num_experts=n, top_k=conf["num_experts_per_tok"],
                  expert_dim=f, shared_dim=f, experts_held=held,
                  first_held=first, scale=conf["routed_scaling_factor"])
    sizes = {"num_experts_per_tok": params["top_k"],
             "routed_scaling_factor": params["scale"],
             "first_held_expert": first}
    ks = iter(jax.random.split(jax.random.key(seed), 10))

    def draw(*shape, scale):
        return scale * jax.random.normal(next(ks), shape, jnp.float32)
    w = {"wg": draw(e, n, scale=e ** -0.5),
         "bias": draw(n, scale=conf["router_bias_std"]),
         "w_gate": draw(held, e, f, scale=e ** -0.5),
         "w_up": draw(held, e, f, scale=e ** -0.5),
         "w_down": draw(held, f, e, scale=f ** -0.5),
         "ws_gate": draw(e, f, scale=e ** -0.5),
         "ws_up": draw(e, f, scale=e ** -0.5),
         "ws_down": draw(f, e, scale=f ** -0.5)}
    x, ct = draw(1, tokens, e, scale=1.0), draw(1, tokens, e, scale=1.0)
    wanted = ("wg", "w_gate", "w_up", "w_down")

    def program():
        """Made anew for each path and each routing: ``jax.jit`` keeps
        its traces by function, and the path is chosen at trace time."""
        def loss(x, w):
            ctx = EmitCtx(training=True, config=FFConfig())
            (y,) = RoutedExpertsOp().emit(params, [x], w, ctx, "experts")
            return jnp.sum(y * ct), (y, ctx.counters)

        def run(x, w):
            (_, (y, counters)), (dx, dw) = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(x, w)
            return dict({k: dw[k] for k in wanted}, y=y, x=dx), counters
        return jax.jit(run)

    def reference(x, w):
        def loss(x, w):
            with jax.default_matmul_precision("highest"):
                y = ref.routed(x, w, sizes) + ref.shared(x, w)
            return jnp.sum(y * ct), y
        (_, y), (dx, dw) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, w)
        return dict({k: dw[k] for k in wanted}, y=y, x=dx)

    @jax.jit
    def rounded(x, w):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference(x, w)

    budget = RoutedExpertsOp.rows_multiplied(tokens, params)
    takes = mts.takes_kernel
    for forced in (False, True):
        tag = "layer alone, overflow forced: " if forced \
            else "layer alone: "
        wf = dict(w, bias=w["bias"].at[first:first + held].add(2.0)) \
            if forced else w
        events.enable()
        events.clear()
        try:
            got, counters = program()(x, wf)
            noted = [ev["attrs"] for ev in events.events()
                     if ev["name"] in ("moe.route", "moe.kernel")]
        finally:
            events.disable()
            events.clear()
        mts.takes_kernel = lambda *a: False
        try:
            plain, _ = program()(x, wf)
        finally:
            mts.takes_kernel = takes
        print(f"{tag}" + json.dumps(noted), flush=True)
        c = {k: float(v) for k, v in counters.items()}
        check(f"{tag}the shapes took the kernel",
              noted and noted[0].get("token_sum") == "kernel"
              and [a.get("use") for a in noted[1:]] == ["combine",
                                                        "rows_for_bwd"],
              f"token_sum {noted[0].get('token_sum') if noted else None}")
        check(f"{tag}nothing dropped, the loop ran {'' if forced else 'not'}",
              c["moe.dropped"] == 0 and c["moe.overflow"] == forced
              and (c["moe.local_assignments"] == tokens * params["top_k"]
                   if forced else c["moe.local_assignments"] <= budget),
              f"rows_budget {budget}, " + ", ".join(
                  f"{k} {v:.0f}" for k, v in sorted(c.items())))
        want, low = jax.jit(reference)(x, wf), rounded(x, wf)
        for name in ("y", "x") + wanted:
            err, eb = l2(got[name], want[name]), l2(low[name], want[name])
            own = l2(got[name], plain[name])
            READINGS[f"{tag}{name}"] = {
                "kernel path": err, "plain path": l2(plain[name],
                                                     want[name]),
                "reference, bf16 operands": eb,
                "kernel path against plain path": own}
            what = "output" if name == "y" else f"gradient {name}"
            check(f"{tag}{what}", err <= 2 * eb + 1e-3 and own <= 1e-3,
                  f"rel {err:.3e}; the reference with bf16 operands reads "
                  f"{eb:.3e}; against the plain path {own:.3e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[2900101])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--skip-layer", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "latent_moe_ref")
    if not args.skip_kernels:
        kernels()
    forward_checks(conf, ref, args.seq, args.seeds)
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0])
    if not args.skip_layer:
        layer_checks(conf, ref, args.seeds[0])
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
