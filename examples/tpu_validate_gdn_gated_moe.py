"""On-chip validation of the gated-delta-rule / gated-attention
mixture-of-experts decoder at published widths (run on a real TPU): what
the benchmark's ``reference`` check cannot see, and the readings its
tolerance is set from. Run it after a change to
``ops/recurrent_ops.py::GatedDeltaRuleOp``'s head-decay form
(``_chunk_terms_head``), ``MultiHeadAttentionOp``'s ``rotary_dim`` or
zero-centred q/k norms, the flash kernels at head size 256,
``RoutedExpertsOp``'s ``shared_gate`` or ``build_hybrid_conv_moe``'s
``"linear_attention"`` kind.

    python3 examples/tpu_validate_gdn_gated_moe.py [--seeds 1 2 3]
        [--seq 8192] [--load-seeds 5700101 ...] [--skip-kernels]
        [--skip-recurrence] [--skip-forward] [--skip-gradients]

The model is ``benchmarks/configs/qwen3_next_80b_a3b.json`` through the
normal path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``),
the reference ``benchmarks/reference/gdn_gated_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. Checks
(each prints PASS/FAIL, exit code 1 on any failure):

  1. the three flash kernels at head size 256 (8 query heads on 1
     key/value head, read in place: one kv head's whole group; s 4096,
     bf16: the golden's s x s scores fit for no more), forward and the
     three gradients, against a plain causal softmax at ``highest``
     precision;
  2. the recurrence alone at the published width (16 q/k heads under 32
     value heads of 128, 4,096 positions, chunks of 64, bf16 operands)
     against the token-by-token walk in float32: the output and the five
     gradients, through the kernels (the head form of
     ``kernels/gated_delta_rule.py`` for the chunks' terms and its scan
     kernel pair for the state from chunk to chunk, which these shapes
     take and whose ``gdn.kernel`` instants the check prints) and
     through the plain terms and the plain ``lax.scan``, the kernels'
     readings held to the plain path's own;
  3. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), and the eval-mode loss;
  4. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last. And, printed and not
     judged, what a model of another form would read: the reference
     with the WHOLE head turned (``correct`` cannot see it: the CPU
     tests hold the turn);
  5. at 2,048 positions: the loss and its gradient for a linear layer's
     ``A_log``, ``dt_bias``, ``wa``, ``wb``, ``wz``, ``wq``, ``conv_k``
     and gated norm, the full layer's ``wg``, ``wq`` and q/k norm
     weights, two layer norms, one held expert's weights, the shared
     expert's, its scalar gate's and a router's, against ``jax.grad`` of
     the reference's loss, each held to twice what the reference itself
     reads with bf16 operands; each expert layer's row budget beside
     what its router sent this share. ``correct`` sees no gradient;
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
# the other configurations' validations have the helpers: PASS/FAIL
# lines, the runner's measure, the model through the normal path, its
# batch, the experts' counters against their budgets
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, check_budget, l2,
    named, program_grads, rel)
from examples.tpu_validate_window_gated_moe import (  # noqa: E402
    ROUNDED, banded, load_checks)
from flexflow_tpu.kernels import flash_attention  # noqa: E402
from flexflow_tpu.obs import events  # noqa: E402
from flexflow_tpu.ops import recurrent_ops  # noqa: E402
from flexflow_tpu.ops.recurrent_ops import gated_delta_rule  # noqa: E402


def kernels(conf, seq=4096):
    """One key/value head's group: 8 query heads of 256 on 1 head."""
    d = conf["head_dim"]
    group = conf["num_attention_heads"] // conf["num_key_value_heads"]
    ks = jax.random.split(jax.random.key(57), 4)
    q, k, v = (jax.random.normal(ks[i], (1, n, seq, d), jnp.bfloat16)
               for i, n in enumerate((group, 1, 1)))
    w = jax.random.normal(ks[3], (1, group, seq, d), jnp.float32)

    def graded(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    def gold(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        return banded(q, jnp.repeat(k, group, axis=1),
                      jnp.repeat(v, group, axis=1), 0)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    tag = f"flash {d}/{d}, {group} on 1 heads at {seq}"
    events.enable()
    events.clear()
    (_, gf) = graded(flash)(q, k, v)
    for e in events.events():
        if e["name"] == "flash.grid":
            print(f"  {e['attrs']}", flush=True)
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


def recurrence(conf, ref, seq=4096):
    """``gated_delta_rule`` with a decay a head, bf16 operands, against
    the walk over single tokens in float32."""
    hk, hv, d = (conf["linear_num_key_heads"],
                 conf["linear_num_value_heads"],
                 conf["linear_key_head_dim"])
    ks = jax.random.split(jax.random.key(5702), 6)
    q = ref.unit(jax.random.normal(ks[0], (1, hk, seq, d))) * d ** -0.5
    k = ref.unit(jax.random.normal(ks[1], (1, hk, seq, d)))
    v = jax.random.normal(ks[2], (1, hv, seq, d))
    # a rate a head from 1e-4 to 4.5 a token (the model's A x step size
    # at its seed's weights), jittered a token: the fastest heads' sums
    # over a chunk pass -88.7, where exp(-G) leaves float32
    g = -jnp.exp(jax.random.uniform(ks[3], (1, hv, 1), minval=-9.0,
                                    maxval=1.5)) \
        * jax.random.uniform(ks[3], (1, hv, seq), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, hv, seq)))
    w = jax.random.normal(ks[5], (1, hv, seq, d))

    def program(*a):
        o, least = gated_delta_rule(*a, 64, jnp.bfloat16)
        return jnp.sum(o * w), (o, least)

    def walk(q, k, v, g, beta):
        group = hv // hk
        with jax.default_matmul_precision("highest"):
            o = ref.delta_rule_by_token(*(jnp.moveaxis(x, 1, 2) for x in (
                jnp.repeat(q, group, 1), jnp.repeat(k, group, 1), v, g,
                beta)))
        o = jnp.moveaxis(o, 2, 1)
        return jnp.sum(o * w), o

    args = (q, k, v, g, beta)
    (_, want), gr = jax.jit(jax.value_and_grad(
        walk, (0, 1, 2, 3, 4), has_aux=True))(*args)
    # the path is chosen at trace time from the shapes: the kernels
    # first, then the plain terms with the predicate stubbed and the
    # jitted function built anew
    takes = recurrent_ops.takes_head_kernel
    paths = [recurrent_ops.head_decay_impl(64, hk, hv, d, d)]
    paths += ["plain"] if paths[0] == "kernel" else []
    read = {}
    for path in paths:
        if path == "plain":
            recurrent_ops.takes_head_kernel = lambda *a: False
        jax.clear_caches()
        events.enable()
        events.clear()
        try:
            (_, o), gp = jax.jit(jax.value_and_grad(
                program, (0, 1, 2, 3, 4), has_aux=True))(*args)
            said = [e["attrs"] for e in events.events()
                    if e["name"] == "gdn.kernel"]
        finally:
            recurrent_ops.takes_head_kernel = takes
            events.clear()
            events.disable()
        tag = f"recurrence {hk}/{hv} x {d} at {seq} ({path})"
        for a in said:
            print(f"  gdn.kernel {a}", flush=True)
        # the terms' pair and the scan's, or no kernel at all
        check(f"{tag} ran the kernels it says",
              sorted(a["kernel"] for a in said) == (
                  ["bwd", "fwd", "scan_bwd", "scan_fwd"]
                  if path == "kernel" else []),
              f"gdn.kernel instants {[a['kernel'] for a in said]}")
        e = read[path, "o"] = l2(o[0], want)
        READINGS[f"{tag} o"] = e
        check(f"{tag} a chunk's decays pass float32's exponent",
              float(o[1]) < -88.7,
              f"least in-chunk log-decay {float(o[1]):.1f}")
        check(f"{tag} output", e < 2e-2, f"rel {e:.3e}")
        for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), gp, gr):
            e = read[path, name] = l2(a, b)
            READINGS[f"{tag} {name}"] = e
            check(f"{tag} {name}", e < 5e-2, f"rel {e:.3e}")
    if len(paths) == 2:
        # the kernels round where the plain terms do: each of their
        # readings within a quarter of the plain path's own
        for name in ("o", "dq", "dk", "dv", "dg", "dbeta"):
            a, b = read["kernel", name], read["plain", name]
            check(f"recurrence {name}: the kernels read as the plain "
                  f"terms do", a < 1.25 * b, f"{a:.3e} against {b:.3e}")


def forward_checks(conf, ref, seq, seeds):
    ff = build(conf, seq, "none")
    sizes = dict(conf)

    def parts(params, batch):
        outs, _, _, _ = ff.executor._forward(params, ff.state, batch, False,
                                             jnp.int32(0))
        got = jnp.log(jnp.clip(outs[0], 1e-30))
        args = (named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        return got, args, ref.gdn_gated_moe_decoder(*args)

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
                low = ref.gdn_gated_moe_decoder(*args)
            out = {label: rel(low, want)}
            if label == ROUNDED[0][0]:
                # the program against the reference at its OWN precision
                out["program, against bf16 reference"] = rel(got, low)
            return out
        return f

    @jax.jit
    def whole_turn(params, batch):
        """The reference of a model that turns the whole head."""
        got, args, want = parts(params, batch)
        other = ref.gdn_gated_moe_decoder(
            args[0], dict(sizes, partial_rotary_factor=1.0), *args[2:])
        return {"whole head turned": rel(other, want)}

    fns = [program] + [rounded(label, kw) for label, kw in ROUNDED] \
        + [whole_turn]
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
        # printed, not judged: at untrained weights a query's softmax
        # over thousands of keys is nearly flat and the attention
        # layer's branch is a hundredth of the others', so the head's
        # log-probabilities cannot tell a partial turn from a whole one
        # (1.7e-5 on the chip, PR 57); tests/test_gdn_gated_moe.py holds
        # the turn at 48 positions, where they can
        print(f"seed {seed}: a model that turned the whole head would "
              f"read {errs['whole head turned']:.3e} (tolerance {tol})",
              flush=True)
        check(f"seed {seed} loss inside the band",
              lo <= errs["loss"] <= hi, f"{errs['loss']:.4f} in [{lo}, {hi}]")
    return ff


def gradient_checks(conf, ref, seed, seq=2048):
    ff = build(conf, seq, "blocks")
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    batch = batch_of(conf, seq, seed)
    picked = (("linear_attn_1", "A_log"), ("linear_attn_1", "dt_bias"),
              ("linear_attn_1", "wa"), ("linear_attn_1", "wb"),
              ("linear_attn_1", "wz"), ("linear_attn_1", "wq"),
              ("linear_attn_1", "conv_k"), ("linear_attn_1", "o_norm"),
              ("attn_3", "wg"), ("attn_3", "wq"), ("attn_3", "q_norm"),
              ("attn_3", "k_norm"), ("operator_norm_1", "scale"),
              ("ffn_norm_3", "scale"), ("experts_2", "wg"),
              ("experts_2", "w_gate"), ("experts_2", "ws_down"),
              ("experts_2", "ws_scalar"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_2.w_gate"] = out["experts_2.w_gate"][3]   # one expert
        return out

    program = program_grads(ff, batch, pick, prefixes=("moe.", "gdn."))

    def reference_grads(params):
        value, grads = jax.value_and_grad(lambda p: ref.loss(
            named(ff, p), dict(conf), batch["input_ids"],
            batch["position_ids"], batch["label"][..., 0]))(params)
        return value, pick(grads)

    @jax.jit
    def rounded(params):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference_grads(params)

    (lp, gp, counters) = program(ff.params)
    (lr, gr), (lb, gb) = jax.jit(reference_grads)(ff.params), \
        rounded(ff.params)
    print(f"  gdn counters: " + ", ".join(
        f"{k} {float(v):.1f}" for k, v in sorted(counters.items())
        if k.startswith("gdn.")), flush=True)
    check_budget(ff, seq, {k: v for k, v in counters.items()
                           if k.startswith("moe.")}, False)
    e = abs(float(lp) - float(lr)) / float(lr)
    eb = abs(float(lb) - float(lr)) / float(lr)
    READINGS["loss"] = {"program": float(lp), "reference": float(lr),
                        "reference, bf16 operands": float(lb)}
    check("loss", e <= 2 * eb + 1e-4,
          f"{float(lp):.6f} against {float(lr):.6f}: rel {e:.3e}; the "
          f"reference with bf16 operands reads {eb:.3e}")
    for name in gp:
        # the yardstick is the reference itself at the configuration's
        # stated precision (``compare_gradients`` of the latent
        # configuration's validation says why it is this loose)
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
    ap.add_argument("--seeds", type=int, nargs="+", default=[5700201])
    ap.add_argument("--load-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--skip-kernels", action="store_true")
    ap.add_argument("--skip-recurrence", action="store_true")
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "gdn_gated_moe_ref")
    if not args.skip_kernels:
        kernels(conf)
    if not args.skip_recurrence:
        recurrence(conf, ref)
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
