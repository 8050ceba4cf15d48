"""On-chip validation of the sparse-attention mixture-of-experts decoder
at published widths (run on a real TPU): what the benchmark's
``reference`` check cannot see, and the readings its tolerance is set
from.

    python3 examples/tpu_validate_sparse_index_moe.py [--seeds 1 2 3]
        [--seq 8192 4096] [--load-seeds 4800101 ...] [--skip-forward]
        [--skip-gradients] [--time-kernels]

The model is ``benchmarks/configs/keye_vl2_30b_a3b.json`` through the
normal path (``FFModel`` -> ``build_hybrid_conv_moe`` -> ``compile``),
the reference ``benchmarks/reference/sparse_index_moe_ref.py`` (float32,
``highest``), both at the same weights drawn from each seed. The
attention layers take the KERNEL path by themselves at these lengths
(the flash kernels under the selection as their mask operand, PR 49);
the model is built a second time with ``attention:xla`` forced, the
chunked path, and each check that reads the layers is made for both and
between them. Checks (each prints PASS/FAIL, exit code 1 on any
failure):

  1. per seed at one sequence of each ``--seq`` length, for each path:
     the head's log-probabilities against the reference (``|sys -
     ref|_2 / |ref|_2``, the runner's measure), the eval-mode loss with
     its four ``L_I`` (the band's reading), the share of the causal
     pairs the layers kept (0.4375 at 8192), and the layer-steps the
     kernels ran in over all of them (``dsa.kernel_layers /
     dsa.layers``: 1 by itself, 0 forced); then the kernel path's
     log-probabilities and ``L_I`` against the chunked path's;
  2. what a lower precision would read, by the same measure, from the
     reference itself with its products' operands rounded
     (``rounded_operands``): bf16 everywhere but the routers (the
     configuration's stated precision), bf16 in the routers too, and an
     8-bit float (e4m3) everywhere but the routers. The tolerance has to
     lie over the first and under the last;
  3. the selection, layer by layer from the layer's OWN input as the
     program computed it: of the (query, key) pairs the reference
     selects (float32 index scores, ``jax.lax.top_k``) for the queries
     that have more than ``topk`` causal keys, the share the program's
     threshold search over its bf16-operand scores selects too, beside
     what the reference's own scores read with bf16 and e4m3 operands.
     With random weights the indexer's order says nothing about a key's
     attention weight, so a flip at the threshold trades one key of 2048
     for another of the same expected weight;
  4. at 4096 positions (half the queries select; the reference's layers
     under ``jax.checkpoint`` so that its backward fits): the loss and
     its gradient for attention's ``wq``, ``q_norm``, ``wk`` and
     ``k_norm``, a router and one held expert (the cross-entropy's) and
     for the indexer's three matrices (``L_I``'s), against ``jax.grad``
     of the reference's loss,
     each held to twice what the reference itself reads with bf16
     operands; each expert layer's row budget beside what its router
     sent this share, for the kernel path and the chunked path, and the
     kernel path's gradients against the chunked path's. ``correct``
     sees no gradient;
  5. ``--load-seeds``: at each seed's weights, every expert layer's rows
     bound for the 16 held experts against its budget of 16384 (the
     acceptance's "quiet before it is offered": no layer over at any
     seed);
  6. ``--time-kernels``: each of the four kernels alone at the cell's
     shape (1 x 32 heads on 4 x ``--seq`` x 128, bf16, causal, a mask of the
     2,048 largest of random scores a row), ms a call on the host's
     clock over 10 calls and the FLOP/s over all causal pairs that
     makes, at the derived blocks and at explicit ones around them;
     then the two index-score kernels (``kernels/index_scores.py``, 16
     heads of 64 on one key head, the whole sequence in one causal call
     each) beside the plain products they stand for (a layer's chunks of
     512 queries, each against the keys up to its end, in one jit), the
     FLOP/s over the pairs either visits, and the kernels' results
     against the plain ones.
"""
import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402
# the other configurations' validation has the helpers: PASS/FAIL lines,
# the runner's measure, the model through the normal path, its batch
from examples.tpu_validate_latent_moe import (  # noqa: E402
    BENCH, FAILED, READINGS, batch_of, build, check, check_budget,
    expert_layers, l2, named, program_grads, rel)
from flexflow_tpu.ops import sparse_attention as dsa  # noqa: E402
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp  # noqa: E402
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX  # noqa: E402

ROUNDED = (("bf16, routers float32", dict(matmul=jnp.bfloat16)),
           ("bf16, routers too", dict(matmul=jnp.bfloat16,
                                      router=jnp.bfloat16)),
           ("float8_e4m3, routers float32",
            dict(matmul=jnp.float8_e4m3fn)))


PATHS = (("kernels", None), ("chunked", "xla"))


def kernel_share(bm):
    return bm[COUNTER_PREFIX + "dsa.kernel_layers"] \
        / bm[COUNTER_PREFIX + "dsa.layers"]


def norm_rope_share(counters, prefix=COUNTER_PREFIX):
    """The layers whose q and k went through ``kernels/qk_norm_rope``
    over all of them (the counter exists only where one did)."""
    return counters.get(prefix + "attn.norm_rope_kernel_layers", 0.0) \
        / counters[prefix + "dsa.layers"]


def attention_layers(ff):
    return [l for l in ff.executor.program.layers
            if l.params.get("indexer_heads")]


def selection_agreement(ff, ref, params, inputs, sizes):
    """A layer -> {precision: share}: of the pairs the reference selects
    from the layer's own input (``inputs``, by the layer's name), for
    the rows that select at all, the share selected at each lower
    precision too."""
    sa = sizes["sa_config"]
    topk, q_chunk = sa["topk"], sa["q_chunk_size"]
    out = {}
    for layer in attention_layers(ff):
        x = inputs[layer.name].astype(jnp.float32)
        w = params[layer.name]
        s = x.shape[1]
        rows = jnp.arange(s)

        def reference_set(**kw):
            with jax.default_matmul_precision("highest"), \
                    ref.rounded_operands(**kw):
                qi, ki, wi = ref.indexer(x, w)
                return jnp.concatenate([
                    ref.selected(ref.index_scores(
                        qi[:, lo:lo + ref.QUERY_ROWS], ki,
                        wi[:, lo:lo + ref.QUERY_ROWS]),
                        rows[lo:lo + ref.QUERY_ROWS], topk)
                    for lo in range(0, s, ref.QUERY_ROWS)], 1)

        want = reference_set()
        selecting = (rows >= topk)[None, :, None]
        wanted = jnp.sum(want & selecting)

        def share(got):
            return jnp.sum(got & want & selecting) / jnp.maximum(wanted, 1)

        out[layer.name] = {
            "program": share(dsa.selection(
                *dsa.indexer_inputs(x, w, jnp.bfloat16), topk, q_chunk,
                jnp.bfloat16)),
            "bf16 operands": share(reference_set(matmul=jnp.bfloat16)),
            "float8_e4m3 operands": share(
                reference_set(matmul=jnp.float8_e4m3fn))}
    return out


def forward_checks(conf, ref, seq, seeds):
    models = {path: build(conf, seq, "none", impl) for path, impl in PATHS}
    sizes = dict(conf)

    def program(ff):
        @jax.jit
        def run(params, batch):
            ex = ff.executor
            outs, _, aux, capture = ex._forward(params, ff.state, batch,
                                                False, jnp.int32(0))
            loss, bm = ex._loss_and_metrics(outs, capture, batch["label"],
                                            aux)
            return (jnp.log(jnp.clip(outs[0], 1e-30)),
                    {"loss": loss, "index_kl": sum(aux) / len(aux),
                     "kept_share": bm[COUNTER_PREFIX + "dsa.kept_pairs"]
                     / bm[COUNTER_PREFIX + "dsa.causal_pairs"],
                     "threshold_ties":
                         bm[COUNTER_PREFIX + "dsa.threshold_ties"],
                     "kernel_layers_share": kernel_share(bm),
                     "norm_rope_kernel_share": norm_rope_share(bm)},
                    {l.name: capture[l.inputs[0].guid]
                     for l in attention_layers(ff)})
        return run

    programs = {path: program(ff) for path, ff in models.items()}
    ff = models["kernels"]

    @jax.jit
    def reference(params, batch, got, inputs):
        args = (named(ff, params), sizes, batch["input_ids"],
                batch["position_ids"])
        want = ref.sparse_index_moe_decoder(*args)
        out = {path: rel(g, want) for path, g in got.items()}
        out["kernels against chunked"] = rel(got["kernels"], got["chunked"])
        for label, kw in ROUNDED:
            with ref.rounded_operands(**kw):
                low = ref.sparse_index_moe_decoder(*args)
            out[label] = rel(low, want)
            if label == ROUNDED[0][0]:
                # the program against the reference at its OWN precision
                out["kernels, against bf16 reference"] = rel(got["kernels"],
                                                             low)
        return out, selection_agreement(ff, ref, params, inputs, sizes)

    tol = conf["reference_rel_tol"]
    lo, hi = conf["initial_loss_band"]
    kept = sum(min(t + 1, conf["sa_config"]["topk"]) for t in range(seq)) \
        / (seq * (seq + 1) / 2)
    for seed in seeds:
        ff.params, ff.state = ff.executor.init_params_and_state(
            jax.random.key(seed))
        batch = batch_of(conf, seq, seed)
        got, read = {}, {}
        for path, run in programs.items():
            got[path], read[path], inputs = run(ff.params, batch)
        errs, agree = jax.device_get(reference(ff.params, batch, got,
                                               inputs))
        del got, inputs
        errs = {n: float(v) for n, v in errs.items()}
        read = {path: {n: float(v) for n, v in jax.device_get(r).items()}
                for path, r in read.items()}
        agree = {n: {k: float(v) for k, v in a.items()}
                 for n, a in agree.items()}
        tag = f"seq {seq} seed {seed}"
        READINGS[tag] = dict(errs, selection=agree, **read)
        print(f"{tag}: " + ", ".join(
            f"{n} {v:.4g}" for n, v in errs.items()), flush=True)
        for path, r in read.items():
            print(f"{tag} {path}: " + ", ".join(
                f"{n} {v:.6g}" for n, v in r.items()), flush=True)
        for name, a in agree.items():
            print(f"{tag} {name} selected as the reference: "
                  + ", ".join(f"{k} {v:.5f}" for k, v in a.items()),
                  flush=True)
        for path, r in read.items():
            check(f"{tag} {path} within the cell's tolerance",
                  errs[path] <= tol, f"{errs[path]:.3e} <= {tol}")
            check(f"{tag} {path} as near as bf16 operands allow",
                  errs[path] <= 2 * errs["bf16, routers float32"],
                  f"{errs[path]:.3e} against "
                  f"{errs['bf16, routers float32']:.3e}")
            check(f"{tag} {path} loss inside the cell's band",
                  lo <= r["loss"] <= hi, f"{r['loss']:.4f} in [{lo}, {hi}]")
            check(f"{tag} {path} kept pairs",
                  abs(r["kept_share"] - kept) < 1e-6,
                  f"{r['kept_share']:.6f} against {kept:.6f}")
            check(f"{tag} {path}: the kernels ran in "
                  f"{'every' if path == 'kernels' else 'no'} layer",
                  r["kernel_layers_share"] == float(path == "kernels"),
                  f"dsa.kernel_layers / dsa.layers = "
                  f"{r['kernel_layers_share']}; "
                  f"attn.norm_rope_kernel_layers / dsa.layers = "
                  f"{r['norm_rope_kernel_share']}")
            check(f"{tag} {path}: q and k through the norm-and-rotary "
                  f"kernel in {'every' if path == 'kernels' else 'no'} "
                  f"layer",
                  r["norm_rope_kernel_share"] == float(path == "kernels"),
                  f"{r['norm_rope_kernel_share']}")
        check(f"{tag} the kernel path is the chunked path",
              errs["kernels against chunked"]
              <= errs["bf16, routers float32"]
              and abs(read["kernels"]["index_kl"]
                      - read["chunked"]["index_kl"])
              <= 1e-3 * read["chunked"]["index_kl"]
              and abs(read["kernels"]["threshold_ties"]
                      - read["chunked"]["threshold_ties"])
              <= 0.01 * max(1.0, read["chunked"]["threshold_ties"]),
              f"log-probabilities {errs['kernels against chunked']:.3e} "
              f"apart (the bf16-rounded reference reads "
              f"{errs['bf16, routers float32']:.3e}); L_I "
              f"{read['kernels']['index_kl']:.6f} against "
              f"{read['chunked']['index_kl']:.6f}; rows tied at the "
              f"threshold {read['kernels']['threshold_ties']:.0f} against "
              f"{read['chunked']['threshold_ties']:.0f} (the same code in "
              f"two programs: XLA may order a float32 sum otherwise)")
        check(f"{tag} 8-bit operands would be caught",
              errs["float8_e4m3, routers float32"] > tol,
              f"{errs['float8_e4m3, routers float32']:.3e} > {tol}")
        check(f"{tag} selects as its precision does",
              all(a["program"] >= a["bf16 operands"] - 0.01
                  and a["program"] > a["float8_e4m3 operands"]
                  for a in agree.values()),
              "every layer within 0.01 of the reference's own bf16 reading "
              "and over its e4m3 one")
    del models, programs, ff


def gradient_checks(conf, ref, seed, seq=4096):
    models = {path: build(conf, seq, "blocks", impl) for path, impl in PATHS}
    ff = models["kernels"]
    ff.params, ff.state = ff.executor.init_params_and_state(
        jax.random.key(seed))
    sizes, batch = dict(conf), batch_of(conf, seq, seed)
    picked = (("attn_1", "wq"), ("attn_1", "q_norm"), ("attn_1", "wk"),
              ("attn_1", "k_norm"), ("experts_2", "wg"),
              ("experts_2", "w_gate"), ("attn_1", "wq_idx"),
              ("attn_1", "wk_idx"), ("attn_1", "w_idx"),
              ("attn_3", "w_idx"))

    def pick(grads):
        out = {f"{n}.{w}": grads[n][w] for n, w in picked}
        out["experts_2.w_gate"] = out["experts_2.w_gate"][3]   # one expert
        return out

    # the reference's layers one at a time in its backward: 32 x 4096^2
    # probabilities a layer are 2 GiB, and there are four; and in four
    # blocks of query rows, not sixteen: the host that compiles the
    # three programs below has 40 GiB (sixteen ended a call at that)
    whole, rows = ref.decoder_layer, ref.QUERY_ROWS
    ref.QUERY_ROWS = 1024
    ref.decoder_layer = lambda *a: jax.checkpoint(
        lambda *t: whole(*t, a[-1]))(*a[:-1])

    def reference_grads(params):
        value, grads = jax.value_and_grad(lambda p: ref.loss(
            named(ff, p), sizes, batch["input_ids"], batch["position_ids"],
            batch["label"][..., 0]))(params)
        return value, pick(grads)

    @jax.jit
    def rounded(params):
        with ref.rounded_operands(matmul=jnp.bfloat16):
            return reference_grads(params)

    try:
        of_path = {}
        for path, model in models.items():
            of_path[path] = jax.device_get(
                program_grads(model, batch, pick, ("moe.", "dsa.", "attn."))(
                    ff.params))
            jax.clear_caches()
        lr, gr = jax.device_get(jax.jit(reference_grads)(ff.params))
        jax.clear_caches()
        lb, gb = jax.device_get(rounded(ff.params))
    finally:
        ref.decoder_layer, ref.QUERY_ROWS = whole, rows
    jax.clear_caches()
    eb = abs(float(lb) - float(lr)) / float(lr)
    READINGS["loss"] = {"reference": float(lr),
                        "reference, bf16 operands": float(lb)}
    for path, (lp, gp, counters) in of_path.items():
        check_budget(models[path], seq, counters, False)
        share = float(counters["dsa.kernel_layers"] / counters["dsa.layers"])
        fused = float(norm_rope_share(counters, ""))
        check(f"{path}: the kernels ran in "
              f"{'every' if path == 'kernels' else 'no'} layer-step",
              share == float(path == "kernels") and fused == share,
              f"dsa.kernel_layers / dsa.layers = {share}, "
              f"attn.norm_rope_kernel_layers / dsa.layers = {fused}")
        e = abs(float(lp) - float(lr)) / float(lr)
        READINGS["loss"][path] = float(lp)
        check(f"{path} loss (with the four L_I)", e <= 2 * eb + 1e-4,
              f"{float(lp):.6f} against {float(lr):.6f}: rel {e:.3e}; the "
              f"reference with bf16 operands reads {eb:.3e}")
        for name in gp:
            e, eb_g = l2(gp[name], gr[name]), l2(gb[name], gr[name])
            own = l2(gp[name], gb[name])
            READINGS[f"grad {name}, {path}"] = {
                "program": e, "reference, bf16 operands": eb_g,
                "program against that": own}
            check(f"{path} gradient {name}", e <= 2 * eb_g + 1e-3,
                  f"rel {e:.3e}; the reference with bf16 operands reads "
                  f"{eb_g:.3e}, and the program against THAT {own:.3e}")
    (lk, gk, _), (lc, gc, _) = of_path["kernels"], of_path["chunked"]
    for name in gk:
        e, eb_g = l2(gk[name], gc[name]), l2(gb[name], gr[name])
        READINGS[f"grad {name}, kernels against chunked"] = e
        check(f"gradient {name}: the kernel path's is the chunked path's",
              e <= 2 * eb_g + 1e-3,
              f"rel {e:.3e} (the reference with bf16 operands reads "
              f"{eb_g:.3e} against itself in float32)")
    del models, ff


def time_kernels(seq, heads=32, kv_heads=4, d=128, topk=2048, calls=10):
    """Each of the four kernels alone at the cell's shape, k and v at
    the model's own ``kv_heads`` (read in place since PR 52): ms a call
    and the FLOP/s over ALL causal pairs (what the kernels multiply: the
    selection is scattered over every causal tile) that makes."""
    import importlib
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    ks = jax.random.split(jax.random.key(49), 5)
    q, k, v, do = (jax.random.normal(ks[i], (1, n, seq, d), jnp.bfloat16)
                   for i, n in enumerate((heads, kv_heads, kv_heads, heads)))
    scores = jax.random.normal(ks[4], (1, seq, seq), jnp.float32)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    kth = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                        topk)[0][..., -1:]
    mask = (causal & (scores >= kth)).astype(jnp.int8)
    del scores, kth
    product = 2.0 * heads * d * seq * (seq + 1) / 2      # one q.k or p.v

    def timed(name, products, fn, *args, product=product):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / calls * 1e3
        READINGS[f"kernel {name}, seq {seq}"] = {
            "ms": ms, "tflops": products * product / ms / 1e9}
        print(f"seq {seq} {name}: {ms:.3f} ms a call, {products} products, "
              f"{products * product / ms / 1e9:.1f} TFLOP/s", flush=True)
        return out

    derived = fa.fwd_tiles(seq, seq, d, jnp.bfloat16, False, None, True)
    tiles = {derived, (512, 4096), (1024, 2048), (1024, 1024), (512, 2048)}
    for bq, bk in sorted(t for t in tiles if max(t) <= seq):
        fwd = jax.jit(lambda q, k, v, m, bq=bq, bk=bk:
                      fa.flash_attention_forward(q, k, v, m, causal=True,
                                                 block_q=bq, block_k=bk))
        o, lse = timed(f"fwd {bq}x{bk}"
                       + (" (derived)" if (bq, bk) == derived else ""),
                       2, fwd, q, k, v, mask)
    plain = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    timed("fwd, no mask, derived", 2, plain, q, k, v)
    derived = fa.bwd_tiles(seq, seq, d, jnp.bfloat16, False, None, True)
    for tile in (None, (1024, 1024), (1024, 512), (512, 1024), (512, 512)):
        kw = {} if tile is None else dict(bwd_block_q=tile[0],
                                          bwd_block_k=tile[1])
        for what, argnums, products in (("dq", (0,), 3), ("dkv", (1, 2), 4)):
            bwd = jax.jit(lambda q, k, v, m, o, lse, do, kw=kw, a=argnums:
                          jax.vjp(lambda *x: fa.flash_attention_from_forward(
                              x[0], x[1], x[2], m, o, lse, causal=True,
                              block_q=1024, block_k=1024, **kw),
                              q, k, v)[1](do)[a[0]:a[-1] + 1])
            timed(f"bwd_{what} {tile or derived}"
                  + (" (derived)" if tile is None else ""), products, bwd,
                  q, k, v, mask, o, lse, do)
    mean = jax.jit(lambda q, k, lse, m: fa.flash_attention_head_mean(
        q, k, lse, m, causal=True))
    timed(f"head_mean {fa.head_mean_tiles(seq, seq, d, jnp.bfloat16)}", 1,
          mean, q, k, lse, mask)
    del q, k, v, do, mask, o, lse
    time_index_kernels(seq, timed)


def time_index_kernels(seq, timed, j=16, c=64, chunk=512):
    """The loss's backward as the step runs it: the index scores again
    and their pull-back, the whole sequence in one causal call of each
    kernel; beside them what they stand for, ``jax.vjp`` of
    ``index_scores`` a chunk of queries at a time against the keys up to
    its end, every chunk of a layer in one jit. FLOP/s over the pairs
    either visits (the tiles at or under the diagonal are the chunks'
    pairs)."""
    from flexflow_tpu.kernels import index_scores as isk
    ks = jax.random.split(jax.random.key(54), 4)
    qi = jax.random.normal(ks[0], (1, seq, j, c), jnp.float32)
    ki = jax.random.normal(ks[1], (1, seq, c), jnp.float32)
    wi = jax.random.normal(ks[2], (1, seq, j), jnp.float32)
    d = jnp.tril(jax.random.normal(ks[3], (1, seq, seq), jnp.float32))
    chunks = dsa._chunks(seq, chunk)
    pairs = 2.0 * j * c * sum((hi - lo) * hi for lo, hi in chunks)
    mdt = jnp.bfloat16

    def plain(qi, ki, wi, d):
        out = []
        for lo, hi in chunks:
            args = (qi[:, lo:hi], ki[:, :hi], wi[:, lo:hi], d[:, lo:hi, :hi])
            if out:     # one chunk's 16 heads of scores at a time
                out[-1], args = jax.lax.optimization_barrier((out[-1], args))
            scores, pull = jax.vjp(
                lambda *a: dsa.index_scores(*a, mdt), *args[:3])
            out.append((scores,) + pull(args[3]))
        scores, dqi, dki, dwi = zip(*out)
        dk = jnp.zeros(ki.shape, jnp.float32)
        for (lo, hi), part in zip(chunks, dki):
            dk = dk.at[:, :hi].add(part)
        return (jnp.concatenate([jnp.pad(x, ((0, 0), (0, 0),
                                             (0, seq - x.shape[2])))
                                 for x in scores], 1),
                jnp.concatenate(dqi, 1), dk, jnp.concatenate(dwi, 1))

    tiles = {k: isk.tiles(k, seq, seq, j, c, mdt) for k in ("fwd", "bwd")}
    want = timed("index scores and their vjp, plain, a layer's chunks", 4,
                 jax.jit(plain), qi, ki, wi, d, product=pairs)
    scores = timed(f"index_scores_fwd {tiles['fwd']}", 1, jax.jit(
        lambda qi, ki, wi: isk.index_scores_fwd(qi, ki, wi, mdt,
                                                causal=True)),
        qi, ki, wi, product=pairs)
    pulled = timed(f"index_scores_bwd {tiles['bwd']}", 3, jax.jit(
        lambda *a: isk.index_scores_bwd(*a, mdt, causal=True)),
        qi, ki, wi, d, product=pairs)
    # the forward writes whole tiles and none past the diagonal
    worst = [l2(jnp.tril(scores), jnp.tril(want[0]))] + [
        l2(got, ref) for got, ref in zip(pulled, want[1:])]
    READINGS[f"index kernels against plain, seq {seq}"] = worst
    check(f"seq {seq}: the index kernels are the plain products",
          worst[0] <= 1e-5 and max(worst[1:]) <= 1e-2,
          f"scores {worst[0]:.2e}, dqi {worst[1]:.2e}, dki {worst[2]:.2e}, "
          f"dwi {worst[3]:.2e} (relative)")


def load_checks(conf, seq, seeds):
    """Every expert layer's rows for the held experts, at each seed's
    weights, against its budget: the counters by layer, which the step's
    sums over layers do not give."""
    emit = RoutedExpertsOp.emit

    def by_layer(self, params, inputs, weights, ctx, name):
        before = dict(ctx.counters)
        out = emit(self, params, inputs, weights, ctx, name)
        for key in ("moe.local_assignments", "moe.overflow", "moe.load_max"):
            ctx.count(f"{key}@{name}", ctx.counters[key] - before.get(key, 0))
        return out

    RoutedExpertsOp.emit = by_layer
    try:
        ff = build(conf, seq, "none")

        @jax.jit
        def counted(params, batch):
            ex = ff.executor
            outs, _, aux, capture = ex._forward(params, ff.state, batch,
                                                False, jnp.int32(0))
            _, bm = ex._loss_and_metrics(outs, capture, batch["label"], aux)
            return {k[len(COUNTER_PREFIX):]: v for k, v in bm.items()
                    if "@" in k}

        budgets = {l.name: RoutedExpertsOp.rows_multiplied(seq, l.params)
                   for l in expert_layers(ff)}
        worst = 0.0
        for seed in seeds:
            ff.params, ff.state = ff.executor.init_params_and_state(
                jax.random.key(seed))
            c = {k: float(v) for k, v in jax.device_get(
                counted(ff.params, batch_of(conf, seq, seed))).items()}
            rows = {n: c[f"moe.local_assignments@{n}"] for n in budgets}
            over = [n for n in budgets if c[f"moe.overflow@{n}"]
                    or rows[n] > budgets[n]]
            worst = max(worst, max(rows[n] / budgets[n] for n in budgets))
            READINGS[f"loads, seed {seed}"] = c
            print(f"seed {seed}: rows for the held experts " + ", ".join(
                f"{n} {rows[n]:.0f}/{budgets[n]} (largest expert "
                f"{c[f'moe.load_max@{n}']:.0f})" for n in budgets),
                flush=True)
            check(f"seed {seed}: every expert layer inside its budget",
                  not over, f"over: {over}")
        READINGS["loads, fullest layer over its budget"] = worst
        print(f"fullest layer: {worst:.4f} of its budget", flush=True)
    finally:
        RoutedExpertsOp.emit = emit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="*", default=[4800001])
    ap.add_argument("--seq", type=int, nargs="*", default=[8192, 4096])
    ap.add_argument("--load-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--skip-forward", action="store_true")
    ap.add_argument("--skip-gradients", action="store_true")
    ap.add_argument("--time-kernels", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this validation needs a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.utils.compilation_cache import enable_compilation_cache
    enable_compilation_cache()
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")) as f:
        conf = json.load(f)
    ref = cells.load_module(BENCH, "reference", "sparse_index_moe_ref")
    if args.time_kernels:
        for seq in args.seq:
            time_kernels(seq)
        jax.clear_caches()
    if args.seeds and not args.skip_forward:
        for seq in args.seq:
            forward_checks(conf, ref, seq, args.seeds)
            jax.clear_caches()
    if args.seeds and not args.skip_gradients:
        gradient_checks(conf, ref, args.seeds[0])
    if args.load_seeds:
        load_checks(conf, args.seq[0], args.load_seeds)
    print("READINGS " + json.dumps(READINGS), flush=True)
    print(f"{len(FAILED)} failed: {FAILED}" if FAILED else "all passed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
