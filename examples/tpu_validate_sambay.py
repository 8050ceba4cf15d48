"""On-chip validation of the SambaY decoder with differential attention
at published widths (run on a real TPU): what the benchmark's
``reference`` check cannot see, and the readings its tolerance is set
from. Run it after a change to ``ops/recurrent_ops.py::
SelectiveScanMixerOp`` or ``selective_scan``, ``MultiHeadAttentionOp.
_emit_differential`` (``kv_out``, ``kv_projected``), the flash kernels at
64 / 128, ``executor.py::_find_stream_blocks`` / ``_emit_remat``'s handed
tensors or ``build_hybrid_conv_moe``'s six SambaY kinds.

    python3 examples/tpu_validate_sambay.py [--seeds 1 2 3]
        [--seq 8192] [--grad-seq 1024] [--skip-forward] [--skip-gradients]
        [--time-kernels]

The model is ``benchmarks/configs/phi4_mini_flash_reasoning.json``
through the normal path (``FFModel`` -> ``build_hybrid_conv_moe`` ->
``compile``), the reference ``benchmarks/reference/sambay_ref.py``
(float32, ``highest``, the scan token by token, two explicit softmaxes a
pair), both at the same weights drawn from each seed. Checks (each
prints PASS/FAIL, exit code 1 on any failure):

  1. per seed at one sequence of ``--seq`` positions: the head's
     log-probabilities against the reference (``|sys - ref|_2 /
     |ref|_2``, the runner's measure), the eval-mode loss, the counters
     ``ssm1.log_decay_min`` a scan and ``attn.diff_lambda_mean`` a
     layer, and what the ``ssm1.scan`` / ``attn.diff`` instants say each
     layer ran (a window, whose keys, which path);
  2. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 (the configuration's stated precision)
     and an 8-bit float (e4m3). The tolerance has to lie over the first
     and under the last;
  3. printed and not judged, what the measure sees of four OTHER models
     (``sambay_ref.variant``): lambda held at 0, the window off, the
     memory taken from layer 14, the cross layer on keys and values of
     its own input;
  4. gradients at ``--grad-seq`` positions of the six layers at the
     published widths with rematerialised blocks (the path that hands m,
     K and V across block edges): the loss and its gradient for every
     weight of a mixer, ``A_log`` / ``dt_proj`` / ``D`` of the mixer that
     hands on its memory, a lambda vector and the pair norm of each
     attention layer, layer 17's ``wq`` / ``wk`` / ``wv`` / ``bv``
     (through both readers), the gated unit's two matrices, a norm, one
     SwiGLU, the embedding and the head, against ``jax.grad`` of the
     reference's loss, each held to twice what the reference itself
     reads with bf16 operands. ``correct`` sees no gradient.
  5. with ``--time-kernels``, the recurrence ALONE at the published
     shape (1 x ``--seq`` x 5,120 channels, a state of 16, chunks of 64):
     ``selective_scan`` down ``kernels/selective_scan.py`` and down the
     plain path, the forward and the pair under ``jax.grad``, sixteen
     calls in one jit each (a call's ``bm`` waits on the one before),
     the device's clock a call and by op; then ``y`` and the five
     gradients of the two paths against each other. The go / no-go
     reading of ISSUE 62: the kernel's forward under 4 ms, its pair
     under two thirds of the plain pair's.
"""
import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, l2, named,
    program_grads, rel)
from flexflow_tpu.obs import events  # noqa: E402
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX  # noqa: E402

ROUNDED = (("bf16", jnp.bfloat16), ("float8_e4m3", jnp.float8_e4m3fn))
VARIANTS = ("lambda_zero", "no_window", "memory_from_first",
            "cross_own_keys")


def forward_checks(conf, ref, seq, seeds):
    events.enable()
    events.clear()
    ff = build(conf, seq, "none")

    def parts(params, batch):
        ex = ff.executor
        outs, _, aux, capture = ex._forward(params, ff.state, batch, False,
                                            jnp.int32(0))
        _, bm = ex._loss_and_metrics(outs, capture, batch["label"], aux)
        return jnp.log(jnp.clip(outs[0], 1e-30)), bm

    def reference(params, batch):
        return ref.sambay_decoder(named(ff, params), conf,
                                  batch["input_ids"], batch["position_ids"])

    # the reference once a seed; every other reading is against it
    truth = jax.jit(reference)

    @jax.jit
    def program(params, batch, want):
        got, bm = parts(params, batch)
        loss = -jnp.mean(jnp.take_along_axis(got, batch["label"], -1))
        return {"program": rel(got, want), "loss": loss,
                "least dt A a scan": bm[COUNTER_PREFIX + "ssm1.log_decay_min"]
                / bm[COUNTER_PREFIX + "ssm1.scans"],
                "lambda a layer":
                bm[COUNTER_PREFIX + "attn.diff_lambda_mean"]
                / bm[COUNTER_PREFIX + "attn.diff_layers"]}

    def rounded(label, dtype):
        @jax.jit
        def f(params, batch, want):
            with ref.rounded_operands(matmul=dtype):
                low = reference(params, batch)
            out = {label: rel(low, want)}
            if label == "bf16":
                out["program, against bf16 reference"] = rel(
                    parts(params, batch)[0], low)
            return out
        return f

    def departed(which):
        @jax.jit
        def f(params, batch, want):
            with ref.variant(**{which: True}):
                return {which: rel(reference(params, batch), want)}
        return f

    fns = [program] + [rounded(label, dtype) for label, dtype in ROUNDED] \
        + [departed(which) for which in VARIANTS]
    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    for i, seed in enumerate(seeds):
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        batch = batch_of(conf, seq, seed)
        errs, want = {}, truth(ff.params, batch)
        for fn in fns:
            errs.update({n: float(v) for n, v in fn(ff.params, batch,
                                                    want).items()})
        if i == 0:
            said = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
                    if e["name"] in ("ssm1.scan", "attn.diff")}
            events.clear()
            events.disable()
            print(f"  the layers say: {json.dumps(said)}", flush=True)
            window = conf["sliding_window"]
            check("each layer ran what its kind says", {
                n: (a.get("window"), a.get("kv_source"), a.get("impl"),
                    a.get("memory_out")) for n, a in said.items()} == {
                "ssm_0": (None, None, "kernel", False),
                "attn_1": (window, "own", "flash", None),
                "ssm_2": (None, None, "kernel", True),
                "attn_3": (0, "own", "flash", None),
                "attn_5": (0, "attn_3", "flash", None)}, "")
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
        check(f"seed {seed} loss inside the band",
              lo <= errs["loss"] <= hi, f"{errs['loss']:.4f} in [{lo}, {hi}]")
        print("  not judged, what the measure reads of another model: "
              + ", ".join(f"{w} {errs[w]:.3e}" for w in VARIANTS),
              flush=True)


def gradient_checks(conf, ref, seed, seq):
    """Check 4: the six layers with rematerialised blocks."""
    ff = build(conf, seq, "blocks")
    check("six blocks, m, K and V handed across their edges",
          ff.executor._remat is not None and ff.executor._remat[2] == 6,
          str(ff.executor._remat and ff.executor._remat[:3]))
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    batch = batch_of(conf, seq, seed)
    picked = [("ssm_0", k) for k in ref.MIXER] \
        + [("ssm_2", k) for k in ("A_log", "dt_proj", "D", "x_proj")] \
        + [("attn_1", k) for k in ("lambda_q1", "subln", "wq")] \
        + [("attn_3", k) for k in ("wq", "wk", "wv", "bv", "lambda_k2",
                                   "subln")] \
        + [("attn_5", k) for k in ("wq", "wo", "lambda_q2", "subln")] \
        + [("gmu_in_4", "kernel"), ("gmu_out_4", "kernel"),
           ("operator_norm_4", "scale"), ("ffn_norm_5", "bias"),
           ("gate_proj_0", "kernel"), ("down_proj_3", "kernel"),
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

    lp, gp, counters = program_grads(ff, batch, pick, ("ssm1.", "attn."))(
        ff.params)
    lr, gr = jax.jit(reference_grads)(ff.params)
    lb, gb = rounded(ff.params)
    print(f"  counters {({k: float(v) for k, v in counters.items()})}",
          flush=True)
    e = abs(float(lp) - float(lr)) / float(lr)
    eb = abs(float(lb) - float(lr)) / float(lr)
    READINGS["loss"] = {"program": float(lp), "reference": float(lr),
                        "reference, bf16 operands": float(lb)}
    check(f"loss at {seq} positions, six layers", e <= 2 * eb + 1e-4,
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


def time_kernels(seq, calls=16, channels=5120, state=16, chunk=64):
    """Check 5 of the module's docstring."""
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.kernels import selective_scan as kernels
    from flexflow_tpu.ops.recurrent_ops import selective_scan
    f32 = jnp.float32
    on_chip = jax.devices()[0].platform == "tpu"
    rng = np.random.default_rng(62)
    # the cell's seeds: softplus(dt_bias) log-uniform in (1e-3, 1e-1),
    # A = -(1 .. N) in every channel
    x, w = (jnp.asarray(rng.standard_normal((1, seq, channels)), f32)
            for _ in range(2))
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (1, seq, channels))), f32)
    a = -jnp.broadcast_to(jnp.arange(1.0, state + 1, dtype=f32)[:, None],
                          (state, channels))
    bm, cm = (jnp.asarray(rng.standard_normal((1, seq, state)), f32)
              for _ in range(2))
    check("the published shape takes the kernels",
          kernels.takes_kernel(chunk, channels, state),
          f"chunk {chunk}, {channels} channels, a state of {state}")

    def scan(impl):
        return lambda *v: selective_scan(*v, chunk,
                                         kernels=impl == "kernel")[0]

    def forward(impl):
        def f(x, dt, a, bm, cm):
            for _ in range(calls):      # the next call waits on this one
                y = scan(impl)(x, dt, a, bm, cm)
                bm = bm + 0.0 * y[:, :, :state]
            return y
        return f

    def pair(impl):
        def f(x, dt, a, bm, cm):
            for _ in range(calls):
                y, pull = jax.vjp(scan(impl), x, dt, a, bm, cm)
                grads = pull(w)         # (y read too, or XLA drops its work)
                bm = bm + 0.0 * (grads[0] + y)[:, :, :state]
            return (y,) + grads
        return f

    def timed(fn):
        """(result, host ms a call, device ms a call, ms a call by op)."""
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(x, dt, a, bm, cm))      # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, dt, a, bm, cm))
        host = (time.perf_counter() - t0) / calls * 1e3
        if not on_chip:
            return out, host, None, {}
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                jax.block_until_ready(fn(x, dt, a, bm, cm))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        by_name = {}        # self times: a ``while`` holds its body's ops
        for ops in ev["devices"].values():
            for name, ns in trace_reduce.self_times(ops).items():
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                by_name[name] = by_name.get(name, 0.0) + ns / calls / 1e6
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:8])
        return out, host, sum(by_name.values()), {
            k: round(v, 4) for k, v in top.items()}

    got, ms = {}, {}
    for impl in ("plain", "kernel"):
        for what, fn in (("forward", forward), ("pair", pair)):
            jax.clear_caches()
            out, host, device, by_name = timed(fn(impl))
            got[impl, what] = out
            ms[impl, what] = device if device is not None else host
            print(json.dumps(dict(
                check="time_kernels", impl=impl, what=what, tokens=seq,
                channels=channels, state=state, chunk=chunk, calls=calls,
                device=jax.devices()[0].device_kind, host_ms_a_call=host,
                device_ms_a_call=device, by_name=by_name)), flush=True)
    READINGS["time_kernels"] = {f"{i}.{w}": v for (i, w), v in ms.items()}
    far = {}
    for name, u, v in zip(("y", "d_x", "d_dt", "d_a", "d_B", "d_C"),
                          got["plain", "pair"], got["kernel", "pair"]):
        far[name] = l2(v, u)
    READINGS["time_kernels"]["kernel_against_plain"] = far
    check("the kernels against the plain path", max(far.values()) <= 1e-4,
          ", ".join(f"{k} {v:.2e}" for k, v in far.items()))
    check("the kernel's forward alone", ms["kernel", "forward"] < 4.0
          or not on_chip, f"{ms['kernel', 'forward']:.3f} ms a call, the "
          f"plain path's {ms['plain', 'forward']:.3f} (go under 4)")
    check("the kernels' pair alone",
          3 * ms["kernel", "pair"] < 2 * ms["plain", "pair"] or not on_chip,
          f"{ms['kernel', 'pair']:.3f} ms a call, the plain pair's "
          f"{ms['plain', 'pair']:.3f} (go under two thirds)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[6100201])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--grad-seq", type=int, default=1024)
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--time-kernels", action="store_true")
    ap.add_argument("--config", default=os.path.join(
        BENCH, "configs", "phi4_mini_flash_reasoning.json"))
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(args.config) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "sambay_ref")
    if args.time_kernels:
        time_kernels(args.seq)
        jax.clear_caches()
    if not args.skip_forward:
        forward_checks(conf, ref, args.seq, args.seeds)
        jax.clear_caches()
    if not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0], args.grad_seq)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "validate_sambay.json"), "w") as f:
        json.dump({"readings": READINGS, "failed": FAILED}, f, indent=1)
    print("all passed" if not FAILED else f"FAILED: {FAILED}", flush=True)
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
