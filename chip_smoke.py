#!/usr/bin/env python3
"""chip_smoke.py — does the searched train path still start on the chip?

One process, normal entry points only (``FFConfig`` -> ``FFModel`` ->
``models.nlp.build_*`` -> ``compile`` with the search ON -> ``fit``), on
whatever ``jax.devices()`` returns: one TPU chip or a four-chip host.

  Leg A  trainer, the paper's model: BERT-large at published width,
         Adam, a few steps on one fixed batch drawn from a seed; its
         attention (512 positions, dropout 0.1) resolves to the compiled
         flash kernel by itself, as the `auto` rule says for that shape.
  Leg B  the kernels on the same path: GPT-2 at published width and its
         own context length, so attention resolves to the compiled flash
         kernel by itself; KV-cache generation against the re-forward
         path.
  Leg C  latent attention, routed experts and a multi-token-prediction
         module (``build_latent_moe``): a small model, then one chip's
         share of JoyAI-LLM-Flash at published widths, 1 x 4096 tokens a
         chip with the expert layers rematerialised. Data parallel, no
         search (the expert dimension is not a searched axis yet). The
         ``moe.route`` and ``flash.grid`` instants it checks are printed.
         It looks at no gradient and not at the MTP head's output, and
         neither does the benchmark's ``correct``: after a change to
         either op run ``examples/tpu_validate_latent_moe.py`` as well.
  Leg D  gated short convolutions among grouped-query attention layers
         with q/k norms, 4-of-64 routing with no shared expert
         (``build_hybrid_conv_moe``): a small model, then one chip's
         share of LFM2-24B-A2B at published widths, 1 x 8192 tokens a
         chip, rematerialised as its benchmark cell is. It checks the
         ``conv.short``, ``attn.qk_norm`` and ``moe.route`` instants and
         looks at no gradient: ``examples/tpu_validate_hybrid_conv_moe.
         py`` does.

  Leg E  gated delta-rule linear attention (a chunked scan and its
         backward) among latent-attention layers with no q latent and no
         rotary embedding, 8-of-256 routing with a shared expert
         (``build_latent_moe`` from ``linear_attn_config``): a small
         model, then one chip's share of Kimi-Linear-48B-A3B at
         published widths, 1 x 4096 tokens a chip, rematerialised as its
         benchmark cell is. It checks that the ``kda.scan`` and
         ``attn.latent`` instants are the configuration's two lists,
         that the layers took the path their shapes give (at published
         widths the kernels: the chunks' terms, the scan and q, k and
         v's way from the projections, each forward and backward, six
         kinds of ``kda.kernel`` instant) and
         prints the ``kda.*`` counters; it looks at no gradient:
         ``examples/tpu_validate_linear_latent_moe.py`` does.
  Leg F  four residual streams under manifold-constrained
         hyper-connections around every attention and feed-forward, a
         YaRN-rescaled rotary embedding in latent attention, 4-of-64
         routing with a shared expert and an MTP module
         (``build_latent_moe`` from ``hc_mult`` and ``rope_scaling``): a
         small model, then one chip's share of Xing4.0-29B-A4B at
         published widths, 1 x 4096 tokens a chip, rematerialised as its
         benchmark cell is. It checks that every sub-layer announced its
         maps (``mhc.maps``, with the path the mixes took: ``impl``, the
         ``mhc.kernel`` instants and the kernels' calls in the compiled
         step) and that a rematerialised block is entered by the one
         stream tensor, and prints the ``mhc.*`` counters; it
         looks at no gradient: ``examples/tpu_validate_mhc_latent_moe.
         py`` does.
  Leg G  grouped-query attention over the keys a learned indexer
         selects, trained by its alignment loss, and softmax 8-of-128
         routing (``build_hybrid_conv_moe`` with ``"sparse_attention"``
         layers): a small model, then one chip's share of
         Keye-VL-2.0-30B-A3B at published widths, 1 x 8192 tokens a
         chip, rematerialised as its benchmark cell is (every block
         holds a layer with an auxiliary loss). It checks that every
         layer announced its indexer (``attn.sparse_index``), took the
         path its shapes and the mesh give (the flash kernels under the
         selection as their mask on one chip from 1024 positions, query
         chunks on XLA on a mesh of several) and is kept by its block,
         and that the ``dsa.*`` counters give the share of the causal
         pairs that ``topk`` and the length do and the layer-steps the
         kernels ran in; it looks at no selection and no gradient:
         ``examples/tpu_validate_sparse_index_moe.py`` does.

  Leg M  blocks of ONE sub-layer (``build_hybrid_conv_moe`` with
         ``"moe"``, ``"mamba"`` and ``"attention"`` layers): a small
         model, then one chip's share of Nemotron 3 Super 120B-A12B at
         published widths, 1 x 4096 tokens. It checks the [moe, mamba]
         blocks, that every mixer announced its groups of B and C and
         every expert layer its latent and ReLU-squared activation, the
         experts' counters, and on the chip that the grouped scan and
         the latent-wide token sum go by their kernels; it looks at no
         gradient: ``examples/tpu_validate_nemotron_h.py`` does.

It claims no speed. The times it prints are set-up facts of one run.
It exits non-zero, before building anything, unless JAX reports a TPU;
there is no option that lets it pass without one. Every later PR is
checked with it: ``python3 chip_smoke.py`` from the repository root.

The legs are plain functions, so ``tests/test_chip_smoke.py`` drives them
at tiny widths on the CPU mesh, where the chip-only checks do not apply.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import numpy as np

SEED = 0
SEARCH_BUDGET = 8
TRAIN_STEPS = 5           # after the step that compiles
#: the ``kernel=`` of a delta-rule layer's ``kda.kernel`` / ``gdn.kernel``
#: instants on the kernel path: the chunks' terms and the scan, each
#: forward and backward; and q, k and v's way from the projections
#: (``kernels/delta_mix.py``), which has a predicate of its own
DELTA_RULE_KERNELS = ["bwd", "fwd", "scan_bwd", "scan_fwd"]
DELTA_MIX_KERNELS = ["mix_bwd", "mix_fwd"]
PROMPT_LEN, NEW_TOKENS = 128, 16
# Per-chip batches for f32 weights, gradients and Adam moments, no
# rematerialization. BERT-large: XLA's memory analysis of the compiled
# data-parallel step puts 8 samples at 10.2 GiB of a v5e's 16 (12 would
# be 13.4, 16 does not fit); 8 leaves a searched plan's different
# program its margin (PERF.md, section 5). GPT-2: 4 samples, 4.7 GiB.
BERT_PER_CHIP_BATCH = 8
GPT_PER_CHIP_BATCH = 4
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ----------------------------------------------------------------------
# shared pieces
# ----------------------------------------------------------------------
def _config(batch: int):
    from flexflow_tpu import FFConfig
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.seed = SEED
    cfg.search_budget = SEARCH_BUDGET   # the search runs on one chip too
    cfg.trace = "true"                  # spans and counters are read below
    return cfg


def _compile(ff, out, optimizer, label: str) -> None:
    """``FFModel.compile`` with the search on, then a report of what
    each guarded compile phase did. On an accelerator a phase that is on
    by default there must have run or left a typed reason."""
    import jax

    from flexflow_tpu.obs import events as obs
    obs.clear()
    t0 = time.perf_counter()
    ff.compile(optimizer, "sparse_categorical_crossentropy", [],
               output_tensor=out)
    wall = time.perf_counter() - t0
    spans: dict = {}
    for ev in obs.events():
        if ev["kind"] == "span":
            spans[ev["name"]] = spans.get(ev["name"], 0.0) + ev["dur"]
    ctr = obs.counters()
    cm = getattr(ff, "_search_cost_model", None)
    guard = getattr(ff, "_floor_guard_record", None)
    say(f"{label}: compile {wall:.1f}s = search "
        f"{ff._compile_phases.get('search_s', 0.0):.1f}s (calibrate"
        f"+collective fit {spans.get('search.calibrate', 0.0):.1f}s, "
        f"on-device op measurement "
        f"{spans.get('costmodel.measure', 0.0):.1f}s, unity "
        f"{spans.get('search.unity', 0.0):.1f}s, floor guard incl. its "
        f"two XLA compiles {spans.get('search.floor_guard', 0.0):.1f}s)"
        f" + verify {ff._compile_phases.get('verify_s', 0.0):.2f}s"
        f" + init {ff._compile_phases.get('init_s', 0.0):.1f}s")
    measure = {k.split(".", 1)[1]: int(v) for k, v in ctr.items()
               if k.startswith("costmodel.measure_")}
    say(f"{label}: mesh {dict(ff.dmesh.axis_sizes)}, strategy "
        f"{_strategy_kind(ff)}, floor guard {guard}, op measurement "
        f"{measure}, mxu_eff {getattr(cm, 'mxu_eff', None)}, collective "
        f"fit (bw, lat) {getattr(cm, 'coll_bw', None)}, "
        f"{getattr(cm, 'coll_lat', None)}")
    if cm is not None and cm.measure_failures:
        say(f"{label}: ops priced analytically after a failed "
            f"microbenchmark: {cm.measure_failures}")
    if ff._compile_skips:
        say(f"{label}: skipped compile phases: {ff._compile_skips}")
    check(int(np.prod(list(ff.dmesh.axis_sizes.values())))
          == len(jax.devices()),
          f"{label}: mesh {dict(ff.dmesh.axis_sizes)} does not cover "
          f"{len(jax.devices())} devices")
    check(guard is not None and ("adopted" in guard or guard["skipped"]),
          f"{label}: the floor guard left no record")
    if jax.devices()[0].platform == "cpu":
        return
    check(cm is not None and cm.measure_on_device,
          f"{label}: on-device op measurement was not switched on")
    priced = measure.get("measure_cache_hits", 0) \
        + measure.get("measure_cache_misses", 0) \
        - measure.get("measure_failures", 0) \
        - measure.get("measure_over_budget", 0)
    check(priced > 0,
          f"{label}: no op was priced by an on-device measurement "
          f"({measure}; failures {cm.measure_failures})")
    check(len(jax.devices()) == 1 or cm.coll_bw is not None,
          f"{label}: calibrate_collectives left no fit on "
          f"{len(jax.devices())} devices")


def _strategy_kind(ff) -> str:
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    st = ff.strategy
    rewritten = (f"graph {len(ff.layers)} -> "
                 f"{len(ff.executor.program.layers)} layers")
    if ff.dmesh.num_devices == 1:
        return f"single device ({rewritten})"
    if st.pipeline is not None:
        return "pipeline"
    # (the search may have rewritten the graph: compare on ITS layers)
    dp = ShardingStrategy.data_parallel(ff.executor.program.layers,
                                        ff.graph_inputs, ff.dmesh)

    def axes(spec):       # P(("x0",), None) and P("x0") are one layout
        return [tuple(e) if isinstance(e, (tuple, list)) else (e,)
                for e in (spec or ()) if e is not None]

    off = [n for n, s in st.ops.items()
           if n not in dp.ops
           or [axes(o) for o in s.outputs]
           != [axes(o) for o in dp.ops[n].outputs]
           or any(axes(w) for w in s.weights.values())]
    if not off and not st.banks and not st.place_groups:
        return "data_parallel"
    return (f"searched ({len(off)}/{len(st.ops)} ops off data-parallel, "
            f"{len(st.banks)} banks, {rewritten})")


def _fit(ff, x, y, label: str, dropout: bool = False):
    """``fit`` for 1 + TRAIN_STEPS epochs of the one fixed batch: every
    epoch is one step on the same data. The loss must be finite at every
    step and must have fallen. With dropout the training loss is a noisy
    witness (a new mask every step, a handful of samples), so the fall
    is then judged on the same batch in eval mode, before and after."""
    before = float(ff.eval(x=x, y=y)["loss"]) if dropout else None
    hist = ff.fit(x=x, y=y, epochs=1 + TRAIN_STEPS, verbose=False)
    losses = [float(h["loss"]) for h in hist]
    say(f"{label}: first step (XLA compile unless the floor guard "
        f"already built it) {hist[0]['epoch_time_s']:.1f}s, then "
        f"{TRAIN_STEPS} steps in "
        f"{sum(h['epoch_time_s'] for h in hist[1:]):.2f}s; losses "
        + " ".join(f"{v:.4f}" for v in losses))
    check(all(np.isfinite(losses)), f"{label}: non-finite loss {losses}")
    if dropout:
        after = float(ff.eval(x=x, y=y)["loss"])
        say(f"{label}: eval-mode loss on the fixed batch {before:.4f} -> "
            f"{after:.4f}")
        losses = [before, after]
    check(losses[-1] < losses[0],
          f"{label}: loss on the fixed batch did not fall: {losses}")


def _step_hlo(ff, x, y) -> str:
    """Text of the compiled train step (a compile-cache hit by now)."""
    import jax.numpy as jnp
    loader = ff._combined_loader(x, y, shuffle=False)
    batch = next(iter(loader))
    _check_placement("batch", batch)
    step = ff.executor.make_train_step()
    return step.lower(ff.params, ff.opt_state, ff.state, jnp.int32(0),
                      batch).compile().as_text()


def _check_placement(what: str, tree) -> None:
    """Every leaf must sit on all the devices, sharded or replicated."""
    import jax
    n = len(jax.devices())
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        devs = {s.device for s in leaf.addressable_shards}
        check(len(devs) == n,
              f"{what}{jax.tree_util.keystr(path)} sits on {len(devs)} "
              f"of {n} devices")


def _check_step_program(ff, x, y, label: str,
                        want_custom_call: bool) -> int:
    import jax
    _check_placement("params", ff.params)
    txt = _step_hlo(ff, x, y)
    # (the call target, not the bare word: op metadata repeats the word)
    n_cc = txt.count('custom_call_target="tpu_custom_call"')
    colls = [c for c in COLLECTIVES if c + "(" in txt or c + "-start" in txt]
    say(f"{label}: compiled step has {n_cc} tpu_custom_call(s), "
        f"collectives {colls or 'none'}")
    if want_custom_call:
        check(n_cc > 0, f"{label}: no Mosaic kernel in the compiled step")
    if len(jax.devices()) > 1:
        check(colls, f"{label}: no collective in the compiled step on "
                     f"{len(jax.devices())} devices")
    return n_cc


def _check_flash_grids(label: str, want: bool, rows: int = 0) -> None:
    """The grids the flash kernels were emitted with (``flash.grid``
    instants, one per traced call): tiles, steps, live and fetched, of
    the forward the pieces it walks its k blocks in, and of the two
    backward kernels which way they hold the tile and the bytes of row
    statistics a call is handed: one float32 a row for each of the two,
    8 bytes a (batch, head, position) of this device's share of
    ``rows``, the step's batch x heads x seq (0: not checked)."""
    import jax

    from flexflow_tpu.obs import events
    grids = [dict(g) for g in sorted(
        {tuple(sorted(e["attrs"].items()))
         for e in events.events() if e["name"] == "flash.grid"})]
    for g in grids:
        fwd = "" if "piece_k" not in g else (
            f"; pieces of {g['piece_k']} keys, {g['live_pieces']} live")
        bwd = "" if "tile" not in g else (
            f"; {g['tile']}, {g['stat_bytes']} bytes of row statistics")
        say(f"{label}: {g['kernel']} tiles {g['block_q']}x{g['block_k']}, "
            f"{g['steps']} steps, {g['live_steps']} live, "
            f"{g['fetched_steps']} fetched" + fwd + bwd)
        check(g["fetched_steps"] == g["live_steps"] <= g["steps"],
              f"{label}: {g['kernel']} fetches {g['fetched_steps']} blocks "
              f"for {g['live_steps']} live steps")
        if g["kernel"] != "flash_attention_fwd":
            check(g["tile"] == ("keys_major" if g["kernel"].endswith("dkv")
                                else "queries_major"),
                  f"{label}: {g['kernel']} holds its tile {g['tile']}")
            shards, rest = divmod(8 * rows, g["stat_bytes"])
            check(not rows or (
                rest == 0 and 1 <= shards <= len(jax.devices())),
                f"{label}: {g['kernel']} is handed {g['stat_bytes']} "
                f"bytes of row statistics for {rows} rows: not 8 a row "
                f"of a device's share")
    if want:
        check({g["kernel"] for g in grids} >= {
            "flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"},
            f"{label}: no flash.grid event for one of the three kernels")


def _peak_bytes() -> str:
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return "not reported by this backend"
    worst = max(stats, key=lambda s: s["peak_bytes_in_use"])
    return (f"{worst['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"bytes_limit {worst.get('bytes_limit', 0) / 2**30:.2f} GiB")


# ----------------------------------------------------------------------
# Leg A — trainer, the paper's model
# ----------------------------------------------------------------------
def leg_bert_train(bert_cfg, seq: int, per_chip_batch: int,
                   alpha: float = 1e-6) -> None:
    import jax

    from flexflow_tpu import AdamOptimizer, FFModel
    from flexflow_tpu.models.nlp import build_bert
    batch = per_chip_batch * len(jax.devices())
    ff = FFModel(_config(batch))
    out = build_bert(ff, batch, seq, bert_cfg)
    # (alpha: a post-LN stack from random weights with no warm-up —
    # Adam's first steps move every weight by alpha whatever its
    # gradient, and at 1e-5 the eval loss of BERT-large on the chip
    # rose, 0.79 -> 0.91; PERF.md)
    _compile(ff, out, AdamOptimizer(alpha=alpha), "A/bert")
    rng = np.random.default_rng(SEED)
    x = [rng.integers(0, bert_cfg.vocab_size, (batch, seq)).astype(np.int32),
         np.tile(np.arange(seq, dtype=np.int32), (batch, 1))]
    y = rng.integers(0, bert_cfg.num_labels, (batch, 1)).astype(np.int32)
    _fit(ff, x, y, "A/bert", dropout=bert_cfg.dropout > 0)
    # attention by itself, as in leg B: a layer the search planned no
    # kernel for takes what the `auto` rule gives at this leg's own
    # shape (on the chip at 512 positions with dropout: the flash kernels)
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    head = bert_cfg.hidden_size // bert_cfg.num_heads
    chip = jax.devices()[0].platform != "cpu"
    rule = "flash" if chip and mha.auto_takes_flash(
        seq, seq, head, head, bert_cfg.dropout) else "xla"
    plan = ff.strategy.kernel_impls or {}
    impls = ff.executor.resolved_attention_impls    # of the train step
    want = {n: plan.get(n, plan.get("attention")) or rule for n in impls}
    say(f"A/bert: resolved attention impl {sorted(set(impls.values()))}, "
        f"kernel plan {plan or 'none'}, the rule alone gives {rule}")
    check(impls == want,
          f"A/bert: attention resolved to {impls}, not {want}, at seq "
          f"{seq}, head size {head}, dropout {bert_cfg.dropout}")
    flash = "flash" in want.values()
    _check_step_program(ff, x, y, "A/bert", want_custom_call=flash)
    _check_flash_grids("A/bert", want=flash,
                       rows=batch * bert_cfg.num_heads * seq)
    say(f"A/bert: per-chip batch {per_chip_batch} (global {batch}), "
        f"seq {seq}, {bert_cfg.num_layers} layers x "
        f"{bert_cfg.hidden_size}, peak_bytes_in_use {_peak_bytes()}")


# ----------------------------------------------------------------------
# Leg B — the kernels on the same path
# ----------------------------------------------------------------------
def leg_gpt2_kernels(gpt_cfg, seq: int, per_chip_batch: int) -> int:
    """Nothing forced — the executor resolves attention by itself.
    Returns the number of Mosaic calls in the compiled train step."""
    import jax

    from flexflow_tpu import AdamOptimizer, FFModel
    from flexflow_tpu.models.nlp import build_gpt2
    chip = jax.devices()[0].platform != "cpu"
    batch = per_chip_batch * len(jax.devices())
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, gpt_cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = [ids, np.tile(np.arange(seq, dtype=np.int32), (batch, 1))]
    y = np.roll(ids, -1, axis=1)[..., None]        # next token
    ff = FFModel(_config(batch))
    out = build_gpt2(ff, batch, seq, gpt_cfg)
    _compile(ff, out, AdamOptimizer(alpha=3e-5), "B/gpt2")
    _fit(ff, x, y, "B/gpt2")
    impls = sorted(set(ff.executor.resolved_attention_impls.values()))
    say(f"B/gpt2: resolved attention impl {impls}, kernel plan "
        f"{ff.strategy.kernel_impls or 'none'}")
    if chip:
        check(impls == ["flash"],
              f"B/gpt2: attention resolved to {impls}, not flash, at "
              f"seq {seq}")
    n_flash = _check_step_program(ff, x, y, "B/gpt2",
                                  want_custom_call=chip)
    _check_flash_grids("B/gpt2", want=chip,
                       rows=ids.shape[0] * gpt_cfg.num_heads * seq)
    _check_generate(ff, ids)
    say(f"B/gpt2: per-chip batch {per_chip_batch} (global "
        f"{ids.shape[0]}), seq {seq}, peak_bytes_in_use {_peak_bytes()}")
    return n_flash


def _lm_leg_setup(builder, model_cfg, seq: int, per_chip_batch: int,
                  label: str, alpha: float):
    """One fixed batch of ``seq`` tokens a chip from the seed and the
    model ``builder`` makes of ``model_cfg``, compiled data parallel
    with ``remat = "blocks"`` and no search. Returns ``(ff, x, y)``."""
    import jax

    from flexflow_tpu import AdamOptimizer, FFModel
    from flexflow_tpu.obs import events
    batch = per_chip_batch * len(jax.devices())
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, model_cfg.vocab_size,
                       (batch, seq)).astype(np.int32)
    x = [ids, np.tile(np.arange(seq, dtype=np.int32), (batch, 1))]
    y = np.roll(ids, -1, axis=1)[..., None]
    cfg = _config(batch)
    cfg.search_budget = 0
    cfg.only_data_parallel = True
    cfg.remat = "blocks"
    ff = FFModel(cfg)
    out = builder(ff, batch, seq, model_cfg)
    events.clear()
    t0 = time.perf_counter()
    ff.compile(AdamOptimizer(alpha=alpha),
               "sparse_categorical_crossentropy", [], output_tensor=out)
    n_params = sum(int(np.prod(w.shape)) for l in ff.params.values()
                   for w in l.values())
    say(f"{label}: compile {time.perf_counter() - t0:.1f}s, "
        f"{len(ff.layers)} graph nodes, {n_params:,} parameters, mesh "
        f"{dict(ff.dmesh.axis_sizes)}, rematerialised run "
        f"{ff.executor._remat and ff.executor._remat[:3]}")
    check(ff.executor._remat is not None,
          f"{label}: remat = blocks found no repeated run")
    return ff, x, y


def _check_wraps(label: str, reps: int) -> None:
    """Every wrap of the step by its key, the last trace's (the
    ``remat.wrap`` instants of ``ops/registry.py::checkpointed``): one of
    site ``block`` a rematerialised block, and what the blocks hold for
    their backward."""
    from flexflow_tpu.obs import events
    wraps = {(a["site"], a.get("layer"), a.get("block"), a.get("part")): a
             for a in (e["attrs"] for e in events.events()
                       if e["name"] == "remat.wrap")}
    blocks = [a for a in wraps.values() if a["site"] == "block"]
    say(f"{label}: {len(wraps)} remat.wrap instants a trace at sites "
        f"{sorted({a['site'] for a in wraps.values()})}; the {len(blocks)} "
        f"blocks hold "
        f"{sum(a['entry_bytes'] + a['kept_bytes'] for a in blocks) / 1e6:.1f}"
        f" MB (entries + kept) for their backward")
    check(len(blocks) == reps,
          f"{label}: {len(blocks)} remat.wrap instants of site 'block' "
          f"for {reps} rematerialised blocks")


def _check_kept_outputs(label: str, ff) -> None:
    """What the rematerialised run keeps beside its blocks' entries (the
    ``remat.kept`` instants: one a marked layer a block in each trace of
    the step): the output of every linear-attention layer inside the
    run, the one op that rematerialises itself whole, and nothing else."""
    from flexflow_tpu.obs import events
    from flexflow_tpu.ops.registry import get_op_def
    start, unit, reps = ff.executor._remat[:3]
    want = sorted(l.name for l in ff.layers[start:start + unit * reps]
                  if get_op_def(l.op_type).keeps_output_for_block)
    kept = {(e["attrs"]["block"], e["attrs"]["layer"]): e["attrs"]["bytes"]
            for e in events.events() if e["name"] == "remat.kept"}
    say(f"{label}: rematerialised run {(start, unit, reps)} keeps "
        f"{len(kept)} outputs, {sum(kept.values()) / 1e6:.1f} MB")
    _check_wraps(label, reps)
    check(sorted(layer for _, layer in kept) == want,
          f"{label}: the rematerialised blocks keep {sorted(kept)} where "
          f"the layers that rematerialise themselves whole are {want}")


def _check_experts_counters(label: str) -> None:
    """The ``moe.*`` counters over the leg's steps beside the layers'
    row budgets (the ``moe.route`` instants): nothing dropped, no step
    of any layer past its budget, and so on average (the counters are
    sums over layers and steps) a layer inside it."""
    from flexflow_tpu.obs import events
    ctr = events.counters()
    routes = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
              if e["name"] == "moe.route"}
    budgets = sorted({r["rows_budget"] for r in routes.values()})
    steps = 1 + TRAIN_STEPS
    mean = ctr.get("moe.local_assignments", 0) / max(
        1, len(routes) * steps)
    say(f"{label}: counters " + ", ".join(
        f"{k} {ctr.get(k)}" for k in (
            "moe.local_assignments", "moe.dropped", "moe.overflow",
            "moe.load_max", "moe.load_mean"))
        + f"; rows_budget {budgets} a layer, {mean:.0f} local assignments "
        f"a layer a step over {steps} steps")
    check(ctr.get("moe.dropped") == 0 and ctr.get("moe.overflow") == 0
          and 0 < mean <= min(budgets),
          f"{label}: the experts' counters read {ctr} against row "
          f"budgets {budgets}")


def _compiled_step_size(ff, x, y, label: str, named: str = "") -> int:
    """Print the compiled train step's GiB a device (on a chip it has to
    fit) and return its count of Mosaic calls: all of them, or with
    ``named`` those whose line has that part of a kernel's name."""
    import jax
    import jax.numpy as jnp
    step = ff.executor.make_train_step()
    batch0 = next(iter(ff._combined_loader(x, y, shuffle=False)))
    compiled = step.lower(ff.params, ff.opt_state, ff.state, jnp.int32(0),
                          batch0).compile()
    ma = compiled.memory_analysis()
    gib = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2 ** 30
    calls = [l for l in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    say(f"{label}: compiled step {gib:.2f} GiB a device, {len(calls)} "
        f"tpu_custom_call(s), peak_bytes_in_use {_peak_bytes()}")
    if jax.devices()[0].platform != "cpu":
        check(gib <= 15.0, f"{label}: the step takes {gib:.2f} GiB")
    return sum(named in l for l in calls)


# ----------------------------------------------------------------------
# Leg C — latent attention, routed experts, multi-token prediction
# ----------------------------------------------------------------------
VALIDATION = "examples/tpu_validate_latent_moe.py"


def leg_latent_moe(model_cfg, seq: int, per_chip_batch: int, label: str,
                   alpha: float = 1e-5) -> None:
    """``build_latent_moe`` through compile and fit with ``remat =
    "blocks"``: the loss falls, every attention layer resolved to the
    flash kernel (on a chip), every expert layer announced its routing
    and dropped nothing, and the step fits the chip. A falling loss says
    little about the gradients (Adam divides their scale away: PR 29's
    unwritten rows trained and passed): ``VALIDATION`` holds them, and
    the MTP head, to the reference, and this leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_latent_moe
    from flexflow_tpu.obs import events
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_latent_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    impls = ff.executor.resolved_attention_impls
    n_attn = model_cfg.num_hidden_layers + model_cfg.num_nextn_predict_layers
    say(f"{label}: resolved attention impls "
        f"{sorted(set(impls.values()))} in {len(impls)} layers")
    check(len(impls) == n_attn, f"{label}: {len(impls)} attention layers "
                                f"resolved, the model has {n_attn}")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    routes = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
              if e["name"] == "moe.route"}
    for name, r in sorted(routes.items()):
        say(f"{label}: moe.route {name}: {r['experts_held']} of "
            f"{r['experts_published']} experts held from {r['first_held']}"
            f", top {r['top_k']}, {r['tokens']} tokens, "
            f"{r['rows_budget']} rows of the "
            f"{r['tokens'] * r['top_k']} sorted handed to the products, "
            f"back to the tokens by the {r['token_sum']} path")
        check(r["experts_published"] == (
            model_cfg.n_routed_experts_published
            or model_cfg.n_routed_experts)
            and r["experts_held"] == model_cfg.n_routed_experts
            and r["rows_multiplied"] == r["rows_budget"]
            == RoutedExpertsOp.rows_multiplied(
                r["tokens"], dict(r, num_experts=r["experts_published"])),
            f"{label}: {name} routes as {r}")
    n_expert = n_attn - model_cfg.first_k_dense_replace
    check(len(routes) == n_expert, f"{label}: {len(routes)} expert layers "
                                   f"announced, the model has {n_expert}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip, rows=x[0].shape[0] * seq
                       * model_cfg.num_attention_heads)
    say(f"{label}: not checked here: gradients and the MTP head's "
        f"log-probabilities against the reference: python3 {VALIDATION}")
    n_cc = _compiled_step_size(ff, x, y, label)
    if chip:
        check(n_cc >= 3 * n_attn, f"{label}: {n_cc} Mosaic calls for "
                                  f"{n_attn} attention layers")


# ----------------------------------------------------------------------
# Leg D — short convolutions, grouped-query attention, no shared expert
# ----------------------------------------------------------------------
VALIDATION_HYBRID = "examples/tpu_validate_hybrid_conv_moe.py"


def leg_hybrid_conv_moe(model_cfg, seq: int, per_chip_batch: int,
                        label: str, alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` through compile and fit with ``remat =
    "blocks"``: the loss falls, every layer of ``layer_types`` announced
    itself by its kind, the attention layers resolved to the flash
    kernel (on a chip), nothing was dropped, and the step fits the chip.
    ``VALIDATION_HYBRID`` holds the gradients to the reference, and this
    leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    kinds = list(model_cfg.layer_types)
    seen = {name: sorted({e["attrs"]["layer"] for e in events.events()
                          if e["name"] == name})
            for name in ("conv.short", "attn.qk_norm", "moe.route")}
    say(f"{label}: instants " + "; ".join(
        f"{n} {v}" for n, v in seen.items()))
    check(seen["conv.short"] == [f"conv_{i}" for i, k in enumerate(kinds)
                                 if k == "conv"]
          and seen["attn.qk_norm"] == [f"attn_{i}" for i, k in
                                       enumerate(kinds) if k != "conv"]
          and seen["moe.route"] == [
              f"experts_{i}" for i in range(model_cfg.num_dense_layers,
                                            len(kinds))],
          f"{label}: the layers that announced themselves are not "
          f"layer_types {kinds}: {seen}")
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: resolved attention impls "
        f"{sorted(set(impls.values()))} in {len(impls)} layers")
    check(len(impls) == kinds.count("full_attention"),
          f"{label}: {len(impls)} attention layers resolved")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: gradients against the reference: "
        f"python3 {VALIDATION_HYBRID}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg E — linear attention with a carried state, NoPE latent attention
# ----------------------------------------------------------------------
VALIDATION_LINEAR = "examples/tpu_validate_linear_latent_moe.py"


def _say_delta_rule_kernels(label, name, kernels, of_terms):
    """One line a kind of ``kda.kernel`` / ``gdn.kernel`` instant: the
    grid, and what a step holds (``of_terms`` words the terms' kernels,
    the scan's say how many heads' states ride in VMEM)."""
    for kind, a in sorted(kernels.items()):
        if kind.startswith("mix"):
            say(f"{label}: {name} {kind}: {a['grid_steps']} grid steps of "
                f"{a['tile']} tokens of {a['heads_per_step']} of "
                f"{a['heads']} heads ({a['part']}), "
                f"{a['vmem_bytes'] / 2 ** 20:.1f} MiB of VMEM a step")
            continue
        held = (f"{a['heads_per_step']} heads' states in VMEM"
                if kind.startswith("scan") else of_terms(a))
        say(f"{label}: {name} {kind}: {a['grid_steps']} grid steps of "
            f"{a['chunks_per_step']} chunks of {a['chunk']} ({held}), "
            f"{a['vmem_bytes'] / 2 ** 20:.1f} MiB of VMEM a step")


def leg_linear_latent_moe(model_cfg, seq: int, per_chip_batch: int,
                          label: str, alpha: float = 1e-5) -> None:
    """``build_latent_moe`` with ``linear_attn_config`` through compile
    and fit with ``remat = "blocks"``: the loss falls, the layers that
    announce themselves as linear attention and as latent attention are
    the configuration's two lists (numbered from 1 there), the latent
    layers have no q latent and no rotation and resolved to the flash
    kernel (on a chip), every scan ran, nothing was dropped, and the
    step fits the chip. ``VALIDATION_LINEAR`` holds the recurrence and
    the gradients to the token-by-token reference, and this leg names
    it."""
    import jax

    from flexflow_tpu.kernels.gated_delta_rule import takes_kernel
    from flexflow_tpu.models.nlp import build_latent_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_latent_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    lin = model_cfg.linear_attn_config
    seen = {name: {e["attrs"]["layer"]: e["attrs"]
                   for e in events.events() if e["name"] == name}
            for name in ("kda.scan", "attn.latent", "moe.route")}
    say(f"{label}: instants " + "; ".join(
        f"{n} {sorted(v)}" for n, v in seen.items()))
    check(sorted(seen["kda.scan"]) == [f"kda_{n - 1}"
                                       for n in lin["kda_layers"]]
          and sorted(seen["attn.latent"]) == [
              f"attn_{n - 1}" for n in lin["full_attn_layers"]]
          and sorted(seen["moe.route"]) == [
              f"experts_{i}" for i in range(
                  model_cfg.first_k_dense_replace,
                  model_cfg.num_hidden_layers)],
          f"{label}: the layers that announced themselves are not "
          f"kda_layers {lin['kda_layers']} and full_attn_layers "
          f"{lin['full_attn_layers']}: "
          f"{ {n: sorted(v) for n, v in seen.items()} }")
    scan = next(iter(seen["kda.scan"].values()))
    say(f"{label}: kda.scan {scan['heads']} heads of {scan['head_dim']} "
        f"behind {scan['taps']} taps, {scan['tokens']} tokens in "
        f"{scan['chunks']} chunks of {scan['chunk']}, "
        f"{scan['state_bytes'] / 2 ** 20:.0f} MiB of chunk-boundary "
        f"states a layer")
    kernels = {}
    for e in events.events():
        if e["name"] == "kda.kernel":
            kernels.setdefault(e["attrs"]["kernel"], e["attrs"])
    took = {a["impl"] for a in seen["kda.scan"].values()}
    want = "kernel" if takes_kernel(scan["chunk"], scan["head_dim"],
                                    scan["head_dim"]) else "plain"
    say(f"{label}: the chunks' terms by {sorted(took)} (the shapes say "
        f"{want})")
    _say_delta_rule_kernels(label, "kda.kernel", kernels,
                            lambda a: f"sub-blocks of {a['sub']}")
    mixed = {a["mix"] for a in seen["kda.scan"].values()}
    say(f"{label}: q, k and v from the projections by {sorted(mixed)}")
    check(took == {want} and len(mixed) == 1 and sorted(kernels) == sorted(
        (DELTA_RULE_KERNELS if want == "kernel" else [])
        + (DELTA_MIX_KERNELS if mixed == {"kernel"} else [])),
          f"{label}: the linear-attention layers announced "
          f"{ {n: a['impl'] for n, a in seen['kda.scan'].items()} }, mix "
          f"{sorted(mixed)} and the kernels {sorted(kernels)} where the "
          f"shapes say {want}")
    check(all(a["q_rank"] is None and a["rope"] is False
              for a in seen["attn.latent"].values()),
          f"{label}: latent attention built as {seen['attn.latent']}")
    ctr = events.counters()
    scans = ctr.get("kda.scans", 0)
    steps = 1 + TRAIN_STEPS
    say(f"{label}: counters kda.scans {scans}, kda.log_decay_min "
        f"{ctr.get('kda.log_decay_min')} (a sum over scans: "
        f"{ctr.get('kda.log_decay_min', 0) / max(1, scans):.1f} a scan; "
        f"exp(-G) is a float32 down to -88.7)")
    check(scans == steps * len(lin["kda_layers"]),
          f"{label}: {scans} scans counted in {steps} steps of "
          f"{len(lin['kda_layers'])} linear-attention layers")
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: resolved attention impls "
        f"{sorted(set(impls.values()))} in {len(impls)} layers")
    check(len(impls) == len(lin["full_attn_layers"]),
          f"{label}: {len(impls)} attention layers resolved")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the recurrence and the gradients "
        f"against the token-by-token reference: python3 "
        f"{VALIDATION_LINEAR}")
    n_cc = _compiled_step_size(ff, x, y, label)
    if chip and kernels:
        # at the benchmark's size 131 before the kernels (PERF.md section
        # 6, PR 35): each layer's forward passes and its backward add one
        # call each
        say(f"{label}: {n_cc} Mosaic calls a step; the benchmark's cell "
            f"counted 131 without the linear-attention kernels, "
            f"{n_cc - 131} fewer")


# ----------------------------------------------------------------------
# Leg F — hyper-connected residual streams, YaRN latent attention
# ----------------------------------------------------------------------
VALIDATION_MHC = "examples/tpu_validate_mhc_latent_moe.py"


def leg_mhc_latent_moe(model_cfg, seq: int, per_chip_batch: int,
                       label: str, alpha: float = 1e-5) -> None:
    """``build_latent_moe`` with ``hc_mult`` streams and ``rope_scaling``
    through compile and fit with ``remat = "blocks"``: the loss falls,
    two hyper-connection sub-layers a decoder layer announced their maps
    (the module's layer too), every one ran in every step, no entry of
    ``Hres~`` met the clamp, the blocks that repeat are entered by the
    one stream tensor, attention resolved to the flash kernel (on a
    chip), nothing was dropped, and the step fits the chip.
    ``VALIDATION_MHC`` holds the maps and the gradients to the reference
    of the literal iterations, and this leg names it."""
    import jax

    from flexflow_tpu.kernels.hyper_connection import KERNELS, takes_kernel
    from flexflow_tpu.models.nlp import build_latent_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_latent_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    n_layers = model_cfg.num_hidden_layers \
        + model_cfg.num_nextn_predict_layers
    maps = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
            if e["name"] == "mhc.maps"}
    one = next(iter(maps.values()))
    say(f"{label}: mhc.maps in {len(maps)} sub-layers: {one['streams']} "
        f"streams of {one['channels']}, {one['iters']} iterations, "
        f"{one['tokens']} tokens, {one['stream_bytes'] / 2 ** 20:.0f} MiB "
        f"of streams a pass")
    check(len(maps) == 2 * n_layers
          and all(a["streams"] == model_cfg.hc_mult
                  and a["iters"] == model_cfg.hc_sinkhorn_iters
                  for a in maps.values()),
          f"{label}: {len(maps)} sub-layers announced their maps, the "
          f"model has {2 * n_layers}: {sorted(maps)}")
    kernels = {}
    for e in events.events():
        if e["name"] == "mhc.kernel":
            kernels.setdefault(e["attrs"]["kernel"], e["attrs"])
    took = {a["impl"] for a in maps.values()}
    want = "kernel" if takes_kernel(one["streams"], one["channels"],
                                    one["tokens"]) else "plain"
    say(f"{label}: the streams' mixes by {sorted(took)} (the shapes say "
        f"{want})")
    for kind, a in sorted(kernels.items()):
        say(f"{label}: mhc.kernel {kind}: {a['grid_steps']} grid steps of "
            f"{a['tile']} tokens, {a['vmem_bytes'] / 2 ** 20:.1f} MiB of "
            f"VMEM a step")
    check(took == {want} and sorted(kernels) == (
        sorted(KERNELS) if want == "kernel" else []),
          f"{label}: the hyper-connection nodes announced {sorted(took)} "
          f"and the kernels {sorted(kernels)} where the shapes say {want}")
    latent = {e["attrs"]["layer"] for e in events.events()
              if e["name"] == "attn.latent"}
    check(len(latent) == n_layers,
          f"{label}: {len(latent)} latent-attention layers announced")
    scaled = [l.params.get("rope_scaling") for l in ff.layers
              if l.name in latent]
    check(all(s == model_cfg.rope_scaling for s in scaled),
          f"{label}: latent attention built with rope_scaling {scaled}")
    entry = next(t for l in ff.layers for t in l.outputs
                 if t.guid == ff.executor._remat[3][0])
    say(f"{label}: a rematerialised block is entered by {entry.shape}")
    check(entry.shape == (x[0].shape[0], seq, model_cfg.hc_mult,
                          model_cfg.hidden_size),
          f"{label}: a block is entered by {entry.shape}")
    ctr = events.counters()
    steps = 1 + TRAIN_STEPS
    subs = ctr.get("mhc.sublayers", 0)
    say(f"{label}: counters mhc.sublayers {subs}, mhc.clamped "
        f"{ctr.get('mhc.clamped')}, mhc.sum_err "
        f"{ctr.get('mhc.sum_err', 0) / max(1, subs):.2e} a sub-layer (the "
        f"worst token's |row or column sum - 1| after "
        f"{model_cfg.hc_sinkhorn_iters} iterations)")
    check(subs == steps * 2 * n_layers and ctr.get("mhc.clamped") == 0,
          f"{label}: {subs} sub-layers counted in {steps} steps of "
          f"{2 * n_layers}, {ctr.get('mhc.clamped')} entries clamped")
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: resolved attention impls "
        f"{sorted(set(impls.values()))} in {len(impls)} layers")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the maps and the gradients against "
        f"the reference of the literal iterations: python3 "
        f"{VALIDATION_MHC}")
    calls = _compiled_step_size(ff, x, y, label, named="hyper_connection_")
    say(f"{label}: {calls} of the step's Mosaic calls are the "
        f"hyper-connection kernels'")
    # (compiled for the chip: on the CPU a kernel is interpreted into
    # plain ops and the step has no Mosaic call at all)
    check(calls >= 4 * len(maps) if chip and want == "kernel"
          else calls == 0,
          f"{label}: {calls} hyper-connection kernel calls in the step of "
          f"{len(maps)} sub-layers whose shapes say {want}")


# ----------------------------------------------------------------------
# Leg G — attention over the keys a learned indexer selects
# ----------------------------------------------------------------------
VALIDATION_SPARSE = "examples/tpu_validate_sparse_index_moe.py"


def leg_sparse_index_moe(model_cfg, seq: int, per_chip_batch: int,
                         label: str, alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with ``"sparse_attention"`` layers
    through compile and fit with ``remat = "blocks"``: the loss (the
    cross-entropy plus every layer's alignment loss, which leaves the
    rematerialised blocks as their output) falls, every layer announced
    its indexer and took the path its shapes and the mesh give (the
    kernels on one device that compiles them, from
    ``FLASH_AUTO_MIN_SEQ`` positions; else query chunks on XLA), the
    share of the causal pairs kept is what ``topk`` and ``seq`` give,
    each block keeps its attention layer's output, nothing was dropped,
    and the step fits the chip. ``VALIDATION_SPARSE`` holds the selection and the
    gradients to the reference, and this leg names it."""
    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    layers = [f"attn_{i}" for i in range(model_cfg.num_hidden_layers)]
    seen = {e["attrs"]["layer"]: e["attrs"] for e in events.events()
            if e["name"] == "attn.sparse_index"}
    kept = sorted({e["attrs"]["layer"] for e in events.events()
                   if e["name"] == "remat.kept"})
    impls = ff.executor.resolved_attention_impls
    import jax

    from flexflow_tpu.kernels._interpret import pallas_interpret
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
    want_impl = "flash" if (
        len(jax.devices()) == 1 and not pallas_interpret()
        and seq >= MultiHeadAttentionOp.FLASH_AUTO_MIN_SEQ) else "xla"
    say(f"{label}: attn.sparse_index {seen.get('attn_0')} in "
        f"{sorted(seen)}; resolved {sorted(set(impls.values()))}; the "
        f"rematerialised run {ff.executor._remat[:3]} keeps {kept}")
    _check_wraps(label, ff.executor._remat[2])
    check(sorted(seen) == layers and set(impls.values()) == {want_impl}
          and {a["impl"] for a in seen.values()} == {want_impl}
          and kept == layers,
          f"{label}: {layers} should each announce an indexer, take the "
          f"{want_impl} path and be kept by its block: {sorted(seen)}, "
          f"{impls}, {kept}")
    ctr = events.counters()
    ran = ctr.get("dsa.kernel_layers", 0) / max(1.0, ctr.get("dsa.layers", 0))
    check(ran == float(want_impl == "flash"),
          f"{label}: the kernels ran in {ran} of the layer-steps")
    # q/k norm and rotary embedding in one kernel where the flash path
    # runs and a head is whole lanes (``kernels/qk_norm_rope``)
    fused = ctr.get("attn.norm_rope_kernel_layers", 0) / max(
        1.0, ctr.get("dsa.layers", 0))
    head_dim = model_cfg.head_dim \
        or model_cfg.hidden_size // model_cfg.num_attention_heads
    check(fused == float(want_impl == "flash" and head_dim % 128 == 0),
          f"{label}: q and k went through the norm-and-rotary kernel in "
          f"{fused} of the layer-steps (heads of {head_dim})")
    # and the flash kernels read the key/value heads in place there
    grouped = ctr.get("attn.grouped_kv_layers", 0) / max(
        1.0, ctr.get("dsa.layers", 0))
    check(grouped == float(
        want_impl == "flash"
        and model_cfg.num_key_value_heads < model_cfg.num_attention_heads),
        f"{label}: the kernels read grouped key/value heads in place in "
        f"{grouped} of the layer-steps")
    topk = model_cfg.sa_config["topk"]
    want = sum(min(t + 1, topk) for t in range(seq)) / (seq * (seq + 1) / 2)
    share = ctr.get("dsa.kept_pairs", 0) / max(
        1.0, ctr.get("dsa.causal_pairs", 0))
    index_kl = ctr.get("dsa.index_kl", 0) / max(1.0, ctr.get("dsa.layers", 0))
    say(f"{label}: kept {share:.6f} of the causal pairs (topk {topk} of "
        f"{seq}: {want:.6f}), mean L_I {index_kl:.4f}, rows tied at the "
        f"threshold {ctr.get('dsa.threshold_ties')}")
    check(abs(share - want) < 1e-5 and index_kl > 0,
          f"{label}: kept {share} of the causal pairs, expected {want}; "
          f"mean L_I {index_kl}")
    _check_experts_counters(label)
    say(f"{label}: not checked here: the selection and the gradients "
        f"against the reference: python3 {VALIDATION_SPARSE}")
    _compiled_step_size(ff, x, y, label)


def _check_generate(ff, ids) -> None:
    """KV-cache decode against the re-forward path on one prompt.

    The two paths run different attention programs (flash prefill plus
    cached decode steps against a full flash forward per token), so
    their logits agree to rounding, not to the bit. With random weights
    the best two of 50k logits can be closer than that; a token may
    therefore differ only where the reference's own scores call the two
    candidates a tie."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.obs.metrics_registry import REGISTRY
    L = ids.shape[1]
    prompt = np.array(ids)
    prompt[:, PROMPT_LEN:] = 0
    fallbacks = REGISTRY.counter("ff_kv_fallback_total")
    before = fallbacks.value(model="<unnamed>")
    t0 = time.perf_counter()
    kv = np.asarray(ff.generate(prompt, PROMPT_LEN, NEW_TOKENS,
                                kv_cache=True))
    t1 = time.perf_counter()
    ref = np.asarray(ff.generate(prompt, PROMPT_LEN, NEW_TOKENS,
                                 kv_cache=False))
    t2 = time.perf_counter()
    check(fallbacks.value(model="<unnamed>") == before,
          "B/generate: the KV path fell back to re-forward")
    end = PROMPT_LEN + NEW_TOKENS
    check((kv[:, :PROMPT_LEN] == prompt[:, :PROMPT_LEN]).all()
          and (ref[:, :PROMPT_LEN] == prompt[:, :PROMPT_LEN]).all(),
          "B/generate: the prompt was not preserved")
    rows = np.flatnonzero((kv[:, :end] != ref[:, :end]).any(axis=1))
    ties = 0
    if rows.size:
        pos = jnp.tile(jnp.arange(L, dtype=jnp.int32)[None],
                       (ids.shape[0], 1))
        scores = np.asarray(jax.jit(ff.executor.scored_forward)(
            ff.params, ff.state,
            {"input_ids": jnp.asarray(ref), "position_ids": pos}),
            np.float32)
        for r in rows:
            p = int(np.flatnonzero(kv[r, :end] != ref[r, :end])[0])
            row = scores[r, p - 1]       # both paths share tokens < p
            gap = float(row[ref[r, p]] - row[kv[r, p]])
            check(abs(gap) <= 2e-2 * float(row.std()),
                  f"B/generate: row {r} token {p}: KV path chose "
                  f"{kv[r, p]}, re-forward {ref[r, p]}, and the scores "
                  f"differ by {gap:.4g} (std {row.std():.4g}) — not a "
                  f"tie")
            ties += 1
    say(f"B/generate: {NEW_TOKENS} tokens after a {PROMPT_LEN}-token "
        f"prompt, batch {ids.shape[0]}: KV path {t1 - t0:.1f}s, "
        f"re-forward {t2 - t1:.1f}s (both include their compiles); "
        f"{ids.shape[0] - rows.size}/{ids.shape[0]} rows identical, "
        f"{ties} diverged at a numerical tie")


# ----------------------------------------------------------------------
# ----------------------------------------------------------------------
# Leg H — window and full attention layers mixed, a gated output
# ----------------------------------------------------------------------
VALIDATION_WINDOW = "examples/tpu_validate_window_gated_moe.py"


def leg_window_gated_moe(model_cfg, seq: int, per_chip_batch: int,
                         label: str, alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with ``"sliding_attention"`` layers
    through compile and fit with ``remat = "blocks"``: the loss falls,
    every attention layer announced its q/k norm and every expert layer
    its routing, the window layers' kernels say their window and walk
    fewer live steps than the full layer's (on a chip; from a length
    over the window), the counters give the band's share of the causal
    pairs and a mean gate of a half, nothing was dropped, and the step
    fits the chip. ``VALIDATION_WINDOW`` holds the gradients to the
    reference, and this leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    kinds = list(model_cfg.layer_types)
    seen = {name: sorted({e["attrs"]["layer"] for e in events.events()
                          if e["name"] == name})
            for name in ("attn.qk_norm", "moe.route")}
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: instants " + "; ".join(
        f"{n} {v}" for n, v in seen.items())
        + f"; resolved {sorted(set(impls.values()))} in {len(impls)} layers")
    check(seen["attn.qk_norm"] == [f"attn_{i}" for i in range(len(kinds))]
          and seen["moe.route"] == [
              f"experts_{i}" for i in range(model_cfg.num_dense_layers,
                                            len(kinds))]
          and len(impls) == len(kinds),
          f"{label}: the layers that announced themselves are not "
          f"layer_types {kinds}: {seen}, {impls}")
    ctr = events.counters()
    window, s = model_cfg.sliding_window, seq
    w = min(window, s)
    want = (w * s - w * (w - 1) / 2) / (s * (s + 1) / 2)
    kept = ctr.get("attn.window_pairs", 0) / max(
        1.0, ctr.get("attn.causal_pairs", 0))
    gate = ctr.get("attn.gate_mean", 0) / max(
        1.0, ctr.get("attn.gate_layers", 0))
    say(f"{label}: a window of {window} over {seq} positions keeps "
        f"{kept:.6f} of the causal pairs ({want:.6f} by count); mean gate "
        f"{gate:.4f} over {len(kinds)} layers a step")
    check(abs(kept - want) < 1e-6 and abs(gate - 0.5) < 0.05,
          f"{label}: kept {kept} against {want}, mean gate {gate}")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
        grids = [e["attrs"] for e in events.events()
                 if e["name"] == "flash.grid"]
        banded = {g["kernel"]: g for g in grids if g.get("window")}
        whole = {g["kernel"]: g for g in grids if not g.get("window")}
        check(len(banded) == 3 == len(whole) if window < seq
              else not banded,
              f"{label}: windowed grids {sorted(banded)}, others "
              f"{sorted(whole)}")
        for kernel, g in sorted(banded.items()):
            key = "live_pieces" if "live_pieces" in g else "live_steps"
            say(f"{label}: {kernel} window {g['window']}: {g[key]} {key} "
                f"against the full layer's {whole[kernel][key]}")
            # a tile (a forward piece) lies wholly outside the band only
            # where the sequence holds the window and two tiles more
            side = max(g["block_q"], g.get("piece_k", g["block_k"]))
            skips = g[key] < whole[kernel][key] \
                if seq >= window + 2 * side else g[key] <= whole[kernel][key]
            check(skips and g["window"] == window,
                  f"{label}: {kernel} does not skip the band's outside: "
                  f"{g} against {whole[kernel]}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: gradients against the reference: "
        f"python3 {VALIDATION_WINDOW}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg I — state-space mixers beside an attention layer with its own scale
# ----------------------------------------------------------------------
VALIDATION_SSM = "examples/tpu_validate_ssm_hybrid.py"


def leg_ssm_hybrid(model_cfg, seq: int, per_chip_batch: int, label: str,
                   alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with ``"mamba"`` and ``"attention"``
    layers through compile and fit with ``remat = "blocks"``: the loss
    falls, every layer is one block of the rematerialised run and every
    mixer's output is kept by its block, each mixer announced its sizes
    and the attention layer its scale, the counters give a log-decay
    below zero for every mixer, and the step fits the chip.
    ``VALIDATION_SSM`` holds the recurrence and the gradients to the
    token-by-token reference, and this leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    kinds = list(model_cfg.layer_types)
    check(ff.executor._remat[2] == len(kinds),
          f"{label}: {ff.executor._remat[2]} blocks for {len(kinds)} layers")
    said = {name: sorted({e["attrs"]["layer"]: e["attrs"]
                          for e in events.events()
                          if e["name"] == name}.items())
            for name in ("ssm.layer", "attn.sm_scale")}
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: instants " + "; ".join(
        f"{n} {[k for k, _ in v]}" for n, v in said.items())
        + f"; resolved {sorted(set(impls.values()))} in {len(impls)} layers")
    check([k for k, _ in said["ssm.layer"]] == sorted(
        f"mamba_{i}" for i, k in enumerate(kinds) if k == "mamba")
        and [k for k, _ in said["attn.sm_scale"]] == sorted(
            f"attn_{i}" for i, k in enumerate(kinds) if k == "attention"),
        f"{label}: the layers that announced themselves are not "
        f"layer_types {kinds}: {said}")
    chunks = -(-seq // model_cfg.mamba_chunk_size)
    check(all(a["chunks"] == chunks and a["heads"] == model_cfg.mamba_n_heads
              and a["state"] == model_cfg.mamba_d_state
              for _, a in said["ssm.layer"])
          and all(a["sm_scale"] == model_cfg.attention_multiplier
                  for _, a in said["attn.sm_scale"]),
          f"{label}: sizes {said}")
    ctr = events.counters()
    layers = ctr.get("ssm.layers", 0)
    least = ctr.get("ssm.min_chunk_log_decay", 0) / max(1.0, layers)
    say(f"{label}: {chunks} chunks of {model_cfg.mamba_chunk_size}; a "
        f"layer's most negative whole-chunk log-decay {least:.2f} on "
        f"average over {layers:.0f} layer-steps")
    check(layers > 0 and least < 0.0, f"{label}: ssm counters {ctr}")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the recurrence and the gradients "
        f"against the reference: python3 {VALIDATION_SSM}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg J — delta rules with a decay a head beside a gated attention layer
# ----------------------------------------------------------------------
VALIDATION_GDN = "examples/tpu_validate_gdn_gated_moe.py"


def leg_gdn_gated_moe(model_cfg, seq: int, per_chip_batch: int, label: str,
                      alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with ``"linear_attention"`` layers (a
    decay a head, fewer key heads than value heads) through compile and
    fit with ``remat = "blocks"``: the loss falls, every layer is one
    block of the rematerialised run and every linear layer's output is
    kept by its block, each linear layer announced its heads and the
    attention layer the part of a head it turns and the path it took
    (the plain chain), the chunks' terms ran the path the shapes say
    (``impl``: the head form of the kernels at the published width, the
    plain terms at the tiny one), the experts the shared expert's gate, the
    counters give a log-decay below zero, nothing was dropped, and the
    step fits the chip. ``VALIDATION_GDN`` holds the recurrence and the
    gradients to the token-by-token reference, and this leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    kinds = list(model_cfg.layer_types)
    check(ff.executor._remat[2] == len(kinds),
          f"{label}: {ff.executor._remat[2]} blocks for {len(kinds)} layers")
    said = {name: sorted({e["attrs"]["layer"]: e["attrs"]
                          for e in events.events()
                          if e["name"] == name}.items())
            for name in ("gdn.scan", "attn.qk_norm", "moe.route")}
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: instants " + "; ".join(
        f"{n} {[k for k, _ in v]}" for n, v in said.items())
        + f"; resolved {sorted(set(impls.values()))} in {len(impls)} layers")
    turned = int((model_cfg.head_dim or 0) * model_cfg.partial_rotary_factor)
    check([k for k, _ in said["gdn.scan"]] == sorted(
        f"linear_attn_{i}" for i, k in enumerate(kinds)
        if k == "linear_attention")
        and all(a["key_heads"] == model_cfg.linear_num_key_heads
                and a["value_heads"] == model_cfg.linear_num_value_heads
                and a["chunks"] == -(-seq // a["chunk"])
                for _, a in said["gdn.scan"])
        and all(a.get("rotary_dim") == turned and a["impl"] == "xla"
                for _, a in said["attn.qk_norm"])
        and all(a.get("shared_gate") for _, a in said["moe.route"]),
        f"{label}: what the layers announced is not the "
        f"configuration's: {said}")
    from flexflow_tpu.ops.recurrent_ops import head_decay_impl
    scan = said["gdn.scan"][0][1]
    took = {a["impl"] for _, a in said["gdn.scan"]}
    kernels = {}
    for e in events.events():
        if e["name"] == "gdn.kernel":
            kernels.setdefault(e["attrs"]["kernel"], e["attrs"])
    # (on one device; a mesh's head axis has to hold whole groups too)
    want = head_decay_impl(scan["chunk"], scan["key_heads"],
                           scan["value_heads"], scan["key_head_dim"],
                           scan["head_dim"])
    say(f"{label}: the chunks' terms by {sorted(took)} (the shapes say "
        f"{want})")
    _say_delta_rule_kernels(
        label, "gdn.kernel", kernels,
        lambda a: f"{a['group']} value heads a q/k head")
    mixed = {a["mix"] for _, a in said["gdn.scan"]}
    say(f"{label}: q, k and v from the projections by {sorted(mixed)}")
    check(len(took) == 1 and (took == {want} or took == {"plain"})
          and len(mixed) == 1 and sorted(kernels) == sorted(
              (DELTA_RULE_KERNELS if took == {"kernel"} else [])
              + (DELTA_MIX_KERNELS if mixed == {"kernel"} else [])),
          f"{label}: the linear layers announced "
          f"{ {n: a['impl'] for n, a in said['gdn.scan']} }, mix "
          f"{sorted(mixed)} and the kernels {sorted(kernels)} where the "
          f"shapes say {want}")
    ctr = events.counters()
    scans = ctr.get("gdn.scans", 0)
    least = ctr.get("gdn.log_decay_min", 0) / max(1.0, scans)
    say(f"{label}: a layer's most negative in-chunk log-decay {least:.2f} "
        f"on average over {scans:.0f} layer-steps; the shared experts' "
        f"gates {ctr.get('moe.shared_gate_mean', 0):.2f} summed")
    check(scans > 0 and least < 0.0, f"{label}: gdn counters {ctr}")
    _check_experts_counters(label)
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the recurrence and the gradients "
        f"against the reference: python3 {VALIDATION_GDN}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg K — selective scans, differential attention, tensors handed on
# ----------------------------------------------------------------------
VALIDATION_SAMBAY = "examples/tpu_validate_sambay.py"


def leg_sambay(model_cfg, seq: int, per_chip_batch: int, label: str,
               alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with the six SambaY kinds through
    compile and fit with ``remat = "blocks"``: the loss falls, every
    layer is one block of the rematerialised run although no two are
    built alike, the blocks hand the scan's output and the keys and
    values across their edges and say so, each scan and each
    differential layer announced what it ran (a window, whose keys, two
    calls), the counters give a step's ``dt A`` below zero and a lambda
    a layer, and the step fits the chip. ``VALIDATION_SAMBAY`` holds the
    model and its gradients to the token-by-token reference, and this
    leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    kinds = list(model_cfg.layer_types)
    start, units, reps = ff.executor._remat[:3]
    handed = sorted({(e["attrs"]["block"], e["attrs"]["layer"]):
                     e["attrs"]["bytes"] for e in events.events()
                     if e["name"] == "remat.kept"
                     and e["attrs"].get("handed_on")}.items())
    say(f"{label}: rematerialised run {(start, units, reps)} hands on "
        f"{len(handed)} layers' outputs, "
        f"{sum(b for _, b in handed) / 1e6:.1f} MB a tensor a layer")
    _check_wraps(label, reps)
    check(reps == len(kinds),
          f"{label}: {reps} blocks for {len(kinds)} layers")
    check([k for k, _ in handed] == [
        (i, f"{'ssm' if k == 'mamba1_memory' else 'attn'}_{i}")
        for i, k in enumerate(kinds)
        if k in ("mamba1_memory", "diff_attention_kv")],
        f"{label}: the blocks hand on {handed}")
    said = {name: sorted({e["attrs"]["layer"]: e["attrs"]
                          for e in events.events()
                          if e["name"] == name}.items())
            for name in ("ssm1.scan", "attn.diff")}
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: instants " + "; ".join(
        f"{n} {[k for k, _ in v]}" for n, v in said.items())
        + f"; resolved {sorted(set(impls.values()))} in {len(impls)} layers")
    window = {"diff_sliding_attention": min(model_cfg.sliding_window, seq)}
    check([(k, a["memory_out"]) for k, a in said["ssm1.scan"]] == [
        (f"ssm_{i}", k == "mamba1_memory") for i, k in enumerate(kinds)
        if k.startswith("mamba1")]
        and [(k, min(a["window"] or seq, seq) if a["window"] else 0,
              a["kv_source"], a["calls"]) for k, a in said["attn.diff"]] == [
            (f"attn_{i}", window.get(k, 0),
             "own" if k != "diff_cross_attention" else
             f"attn_{kinds.index('diff_attention_kv')}", 2)
            for i, k in enumerate(kinds) if k.startswith("diff_")],
        f"{label}: what the layers announced is not layer_types {kinds}: "
        f"{said}")
    from flexflow_tpu.kernels.selective_scan import takes_kernel
    scan = said["ssm1.scan"][0][1]
    took = {a["impl"] for _, a in said["ssm1.scan"]}
    kernels = {}
    for e in events.events():
        if e["name"] == "ssm1.kernel":
            kernels.setdefault(e["attrs"]["kernel"], e["attrs"])
    # (on one device: under a mesh of several the plain path)
    want = "kernel" if takes_kernel(scan["chunk"], scan["channels"],
                                    scan["state"]) else "plain"
    say(f"{label}: the scans by {sorted(took)} (the shapes say {want})"
        + "".join(f"; {k}: {a['grid_steps']} grid steps of {a['blocks']} "
                  f"blocks of {a['block_channels']} channels"
                  for k, a in sorted(kernels.items())))
    check((took == {want} or took == {"plain"} and len(jax.devices()) > 1)
          and sorted(kernels) == (["bwd", "fwd"] if took == {"kernel"}
                                  else []),
          f"{label}: the scans announced "
          f"{ {n: a['impl'] for n, a in said['ssm1.scan']} } and the "
          f"kernels {sorted(kernels)} where the shapes say {want}")
    ctr = events.counters()
    scans = ctr.get("ssm1.scans", 0)
    least = ctr.get("ssm1.log_decay_min", 0) / max(1.0, scans)
    lam = ctr.get("attn.diff_lambda_mean", 0) \
        / max(1.0, ctr.get("attn.diff_layers", 0))
    say(f"{label}: a scan's most negative dt A {least:.3f} on average "
        f"over {scans:.0f} layer-steps; lambda {lam:.3f} a layer")
    check(scans > 0 and least < 0.0 and 0.5 < lam < 1.1,
          f"{label}: counters {ctr}")
    if chip:
        check(set(impls.values()) == {"flash"},
              f"{label}: attention resolved to {impls} at seq {seq}")
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the model and its gradients against "
        f"the reference: python3 {VALIDATION_SAMBAY}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg L — a block-diffusion training step
# ----------------------------------------------------------------------
VALIDATION_BLOCK_DIFFUSION = "examples/tpu_validate_block_diffusion.py"


def _bd_walked_share(seq: int) -> float:
    """What the flash kernels' grids compute of the ``(2 seq)^2`` square
    under the block-diffusion mask at tiles of ``min(seq, 1024)`` a
    side, ``h`` of them a half: the ``h (h + 1)`` live tiles of the
    noised x clean and clean x clean triangles whole, the ``h`` diagonal
    tiles of noised x noised as their ``BD_SUB``-wide diagonal
    sub-blocks (one tile a half: 0.5625 at 512 positions, 0.75 where
    the tile is one sub-block; the cell's four: 0.3203)."""
    from flexflow_tpu.kernels.flash_attention import BD_SUB
    side = min(seq, 1024)
    h = seq // side
    return (BD_SUB / side + h + 1) / (4 * h)


def leg_block_diffusion(model_cfg, seq: int, per_chip_batch: int,
                        label: str, alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with ``"block_diffusion_attention"``
    layers through compile and fit with ``remat = "blocks"``: the
    eval-mode loss (one mask, the configuration's) falls, the noising op
    announced a draw from the step's key and masked about half the
    tokens, every attention layer announced the mask and who draws it
    (the flash kernels on a chip, which then skip the dead quadrant and
    the dead triangles' tiles and walk the noised x noised diagonal
    tiles as sub-blocks: :func:`_bd_walked_share`), the loss
    its weights, nothing was dropped, and the step
    fits the chip. ``VALIDATION_BLOCK_DIFFUSION`` holds the mask, the
    draw and the gradients to the reference, and this leg names it."""
    import jax

    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label, dropout=True)     # a new mask every step
    _check_kept_outputs(label, ff)
    layers = len(model_cfg.layer_types)
    noted = {name: [e["attrs"] for e in events.events()
                    if e["name"] == name]
             for name in ("diffusion.noise", "attn.block_diffusion",
                          "loss.weighted")}
    drew = sorted({a["key"] for a in noted["diffusion.noise"]})
    masks = {a["layer"]: a["impl"] for a in noted["attn.block_diffusion"]}
    impls = ff.executor.resolved_attention_impls
    say(f"{label}: the noising op drew from {drew}; the mask in "
        f"{len(masks)} layers by {sorted(set(masks.values()))}; the loss "
        f"weighted over {sorted({a['rows'] for a in noted['loss.weighted']})}"
        f" rows; resolved {sorted(set(impls.values()))}")
    check(drew == ["eval", "step"] and len(masks) == layers == len(impls)
          and noted["loss.weighted"]
          and all(a["block_length"] == model_cfg.block_length
                  for a in noted["attn.block_diffusion"]),
          f"{label}: what announced itself: {noted}")
    ctr = events.counters()
    masked = ctr.get("diffusion.masked_tokens", 0) / max(
        1.0, ctr.get("diffusion.tokens", 0))
    weight = ctr.get("diffusion.weight_sum", 0) / max(
        1.0, ctr.get("diffusion.tokens", 0))
    kept = ctr.get("attn.bd_visited_pairs", 0) / max(
        1.0, ctr.get("attn.bd_pairs", 0))
    live = 0.25 + model_cfg.block_length / (4.0 * seq)
    say(f"{label}: {masked:.4f} of the tokens masked, weights {weight:.4f} "
        f"a token on average; the kernels' grids visit {kept:.4f} of the "
        f"square, the mask attends {live:.4f}")
    check(0.3 < masked < 0.7 and 0.5 < weight < 2.0,
          f"{label}: masked share {masked}, mean weight {weight}")
    if chip:
        # smaller tiles than the widest would visit less, never more
        most = _bd_walked_share(seq)
        check(set(impls.values()) == {"flash"} == set(masks.values())
              and live <= kept <= most + 1e-6,
              f"{label}: attention resolved to {impls}, the mask by "
              f"{masks}, {kept} of the square visited at seq {seq} "
              f"(at most {most:.4f})")
    else:
        check(kept == 1.0, f"{label}: off the kernels every pair is "
                           f"computed, not {kept}")
    _check_experts_counters(label)
    _check_flash_grids(label, want=chip)
    say(f"{label}: not checked here: the mask, the draw and the gradients "
        f"against the reference: python3 {VALIDATION_BLOCK_DIFFUSION}")
    _compiled_step_size(ff, x, y, label)


# ----------------------------------------------------------------------
# Leg M — blocks of one sub-layer: grouped mixers, experts in a latent
# ----------------------------------------------------------------------
VALIDATION_NEMOTRON_H = "examples/tpu_validate_nemotron_h.py"


def leg_latent_experts_hybrid(model_cfg, seq: int, per_chip_batch: int,
                              label: str, alpha: float = 1e-5) -> None:
    """``build_hybrid_conv_moe`` with blocks of ONE sub-layer (``"moe"``,
    ``"mamba"``, ``"attention"``) through compile and fit with ``remat =
    "blocks"``: the loss falls, the rematerialised run is the [moe,
    mamba] pairs (the attention layer after them held whole) and keeps
    every mixer's output, each mixer announced its groups and the heads
    of the whole mixer, each expert layer its latent and its activation,
    nothing is dropped and no layer leaves its row budget, and the step
    fits the chip. On the chip the grouped scan and the way back to the
    tokens over rows as wide as the latent go by their kernels.
    ``VALIDATION_NEMOTRON_H`` holds the gradients to the reference, and
    this leg names it."""
    import jax

    from flexflow_tpu.kernels import state_space
    from flexflow_tpu.models.nlp import build_hybrid_conv_moe
    from flexflow_tpu.obs import events
    chip = jax.devices()[0].platform != "cpu"
    ff, x, y = _lm_leg_setup(build_hybrid_conv_moe, model_cfg, seq,
                             per_chip_batch, label, alpha)
    _fit(ff, x, y, label)
    _check_kept_outputs(label, ff)
    kinds = list(model_cfg.layer_types)
    pairs = sum(a == "moe" and b == "mamba"
                for a, b in zip(kinds[::2], kinds[1::2]))
    check(ff.executor._remat[1:3] == (6, pairs),
          f"{label}: blocks {ff.executor._remat[:3]} for {pairs} pairs of "
          f"{kinds}")
    said = {name: sorted({e["attrs"]["layer"]: e["attrs"]
                          for e in events.events()
                          if e["name"] == name}.items())
            for name in ("ssm.layer", "moe.route")}
    say(f"{label}: instants " + "; ".join(
        f"{n} {[k for k, _ in v]}" for n, v in said.items())
        + "; scan " + str(sorted({a["impl"] for _, a in said["ssm.layer"]}))
        + ", token sum "
        + str(sorted({a["token_sum"] for _, a in said["moe.route"]})))
    check([k for k, _ in said["ssm.layer"]] == sorted(
        f"mamba_{i}" for i, k in enumerate(kinds) if k == "mamba")
        and [k for k, _ in said["moe.route"]] == sorted(
            f"experts_{i}" for i, k in enumerate(kinds) if k == "moe"),
        f"{label}: the layers that announced themselves are not "
        f"layer_types {kinds}: {said}")
    check(all(a["groups"] == model_cfg.n_groups
              and a["heads"] == model_cfg.mamba_num_heads
              and a["chunk"] == model_cfg.chunk_size
              for _, a in said["ssm.layer"])
          and all(a["latent"] == model_cfg.moe_latent_size
                  and a["activation"] == model_cfg.mlp_hidden_act
                  and a["top_k"] == model_cfg.num_experts_per_tok
                  and a["bias_step"] == model_cfg.router_bias_update_rate
                  for _, a in said["moe.route"]),
          f"{label}: sizes {said}")
    if chip and state_space.takes_kernel(
            model_cfg.chunk_size, model_cfg.mamba_num_heads,
            model_cfg.mamba_head_dim, model_cfg.ssm_state_size,
            model_cfg.n_groups):        # the cell's shapes, not tiny()'s
        check({a["impl"] for _, a in said["ssm.layer"]} == {"kernel"}
              and {a["token_sum"] for _, a in said["moe.route"]}
              == {"kernel"},
              f"{label}: off the kernels at seq {seq}: {said}")
    _check_experts_counters(label)
    say(f"{label}: not checked here: the gradients against the "
        f"reference: python3 {VALIDATION_NEMOTRON_H}")
    _compiled_step_size(ff, x, y, label)


def main() -> int:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); refusing to run", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    from flexflow_tpu import MachineSpec, native
    from flexflow_tpu.models.nlp import (BertConfig, GPTConfig,
                                         GraniteHybridRankConfig,
                                         HybridConvMoEConfig,
                                         JoyAIFlashRankConfig,
                                         KeyeRankConfig,
                                         KimiLinearRankConfig,
                                         LatentMoEConfig, LFM2RankConfig,
                                         NemotronHRankConfig,
                                         Phi4FlashRankConfig,
                                         Qwen3NextRankConfig,
                                         SDARRankConfig,
                                         TrinityRankConfig, XingRankConfig)
    from flexflow_tpu.utils.compilation_cache import (
        cache_entries, enable_compilation_cache)
    cache = enable_compilation_cache()
    before = cache_entries(cache)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}, generation "
        f"{MachineSpec.detect().generation}, native runtime "
        f"{native.available()}, jax {jax.__version__}")
    say(f"compile cache {cache}: {len(before)} entries before")
    try:
        leg_bert_train(BertConfig(), 512, BERT_PER_CHIP_BATCH)
        # (B1's model is gone by now: one training state at a time)
        leg_gpt2_kernels(GPTConfig(), 1024, GPT_PER_CHIP_BATCH)
        # a small model at a length where attention resolves to flash,
        # then one chip's share of the published model
        leg_latent_moe(LatentMoEConfig.tiny(), 1024, 1, "C/small",
                       alpha=1e-3)
        leg_latent_moe(JoyAIFlashRankConfig(), 4096, 1, "C/joyai")
        leg_hybrid_conv_moe(HybridConvMoEConfig.tiny(), 1024, 1, "D/small",
                            alpha=1e-3)
        leg_hybrid_conv_moe(LFM2RankConfig(), 8192, 1, "D/lfm2")
        leg_linear_latent_moe(KimiLinearRankConfig.tiny(), 1024, 1,
                              "E/small", alpha=1e-3)
        leg_linear_latent_moe(KimiLinearRankConfig(), 4096, 1, "E/kimi")
        leg_mhc_latent_moe(XingRankConfig.tiny(), 1024, 1, "F/small",
                           alpha=1e-3)
        leg_mhc_latent_moe(XingRankConfig(), 4096, 1, "F/xing")
        # chunks of 128 queries keeping 256 keys: tiny()'s chunks of 16
        # are 64 chunk shapes a layer at 1024 positions, a 6-minute compile
        leg_sparse_index_moe(dataclasses.replace(
            KeyeRankConfig.tiny(), sa_config=dict(
                KeyeRankConfig.tiny().sa_config, q_chunk_size=128,
                kv_chunk_size=128, topk=256)), 1024, 1, "G/small",
            alpha=1e-3)
        leg_sparse_index_moe(KeyeRankConfig(), 8192, 1, "G/keye")
        # a window of 256 under 1024 positions, then the cell's shapes
        leg_window_gated_moe(dataclasses.replace(
            TrinityRankConfig.tiny(), sliding_window=256), 1024, 1,
            "H/small", alpha=1e-3)
        leg_window_gated_moe(TrinityRankConfig(), 8192, 1, "H/trinity")
        # four chunks of 256 at the small size, then the cell's shapes
        leg_ssm_hybrid(dataclasses.replace(
            GraniteHybridRankConfig.tiny(), mamba_chunk_size=256), 1024, 1,
            "I/small", alpha=1e-3)
        leg_ssm_hybrid(GraniteHybridRankConfig(), 4096, 1, "I/granite")
        leg_gdn_gated_moe(Qwen3NextRankConfig.tiny(), 1024, 1, "J/small",
                          alpha=1e-3)
        leg_gdn_gated_moe(Qwen3NextRankConfig(), 8192, 1, "J/qwen3next")
        leg_sambay(dataclasses.replace(
            Phi4FlashRankConfig.tiny(), sliding_window=256), 1024, 1,
            "K/small", alpha=1e-3)
        leg_sambay(Phi4FlashRankConfig(), 8192, 1, "K/phi4flash")
        # 512 tokens as 1,024 positions, then the cell's shapes
        leg_block_diffusion(SDARRankConfig.tiny(), 512, 1, "L/small",
                            alpha=1e-3)
        leg_block_diffusion(SDARRankConfig(), 4096, 1, "L/sdar")
        # a small model (plain scan, plain token sum), then the cell's
        # shapes: the grouped scan at chunks of 128, experts in a latent
        leg_latent_experts_hybrid(NemotronHRankConfig.tiny(), 1024, 1,
                                  "M/small", alpha=1e-3)
        leg_latent_experts_hybrid(NemotronHRankConfig(), 4096, 1,
                                  "M/nemotron")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    added = sorted(cache_entries(cache) - before)
    say(f"compile cache {cache}: {len(before) + len(added)} entries "
        f"after ({len(added)} added"
        + (f": {[n[:48] for n in added]}" if 0 < len(added) <= 8 else "")
        + ")")
    say(f"all legs passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
