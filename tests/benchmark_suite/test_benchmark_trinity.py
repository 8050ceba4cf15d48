"""``trinity_mini.train.1chip`` (PR 51): the files resolve, the
manifest's new entries are there BY NAME (nothing here pins a list's
tail: a later PR appends after them), the configuration's file holds the
catalog row except for the cut, the parameter and operation counts are
the model's, the band's pair count is a loop's, and the nine readers
read a hand-made trace, the recorded test traces and a parent's program
(nothing, without an error).
"""
import json
import math
import os
import types

import pytest

from benchmarks.harness import (cells, peaks, scope_reduce, span_reduce,
                                trace_reduce)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "trinity_mini"
CELL = "trinity_mini.train.1chip"
OLDER_CELLS = {
    "bert_large.train.1chip", "gpt2_124m.train.1chip",
    "joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
    "kimi_linear_48b_a3b.train.1chip", "xing4_29b_a4b.train.1chip",
    "keye_vl2_30b_a3b.train.1chip"}
US = 1000
PR51 = {        # name -> (unit, better, source, layer)
    "trinity_swa_time_share.train": ("%", "lower", "device_trace",
                                     "attention"),
    "trinity_full_attn_time_share.train": ("%", "lower", "device_trace",
                                           "attention"),
    "moe_time_share.train": ("%", "lower", "device_trace",
                                     "experts"),
    "window_flash_fwd_roofline": ("%", "higher", "device_trace",
                                   "kernels"),
    "window_flash_bwd_dq_roofline": ("%", "higher", "device_trace",
                                      "kernels"),
    "window_flash_bwd_dkv_roofline": ("%", "higher", "device_trace",
                                       "kernels"),
    "swa_kept_share": ("ratio", "lower", "program_counter",
                               "attention"),
    "moe_dropped_assignments": ("count", "lower",
                                        "program_counter", "experts"),
    "moe_overflow_layer_steps": ("count", "lower",
                                         "program_counter", "experts"),
}
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"}


OWN = {n for n in PR51 if n.startswith("trinity_")}   # no other cell's


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


def _holding(cell, listed):
    """``listed``, which has to hold ``cell``: an entry lists every cell
    whose run gives its reader a reading, this one among them."""
    assert cell in listed
    return listed


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return cells.resolve_cell(ROOT, CELL)


# ----------------------------------------------------------------------
# the manifest, by name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PR51))
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    unit, better, source, layer = PR51[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "train_tokens_per_s",
        "workloads": _holding(CELL, by_name[name]["workloads"])}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    """After the seven cells and configurations of the parent and the
    sparse-attention cell's metrics (no count is held); what comes
    after this PR's is not this test's to say."""
    order = [m["name"] for m in manifest["per_layer"]]
    assert order.index("keye_dsa_index_kl") \
        < min(order.index(n) for n in OWN)
    assert [n for n in order if n in OWN] \
        == [n for n in PR51 if n in OWN]
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("keye_vl2_30b_a3b") < configs.index(CONFIG)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"]
               if w["name"] in OLDER_CELLS or w["name"] == CELL)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert CELL in perf and "swa_kept_share" in perf


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry["chips"] == 1 and entry["config"] != CONFIG
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert SHARED <= reported and not reported & OWN


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(PR51)
    # no double of the executor, loader, device or set-up readings
    assert not [m["name"] for m in cell.per_layer
                if m["name"].startswith("trinity_")
                and m["name"] not in PR51]
    assert cell.chips == 1
    # cells 4 and 7's traffic as it is: the sequence is not shortened,
    # since at 4096 half a window layer's queries would see every key
    assert cell.traffic_name == "train_b1_s8192"
    assert cell.traffic["seq"] == 8192 == 4 * cell.config["sliding_window"]
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"] == 1


# ----------------------------------------------------------------------
# the configuration's file
# ----------------------------------------------------------------------
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 25024,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention",
                       "sliding_attention"]}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        manifest, cell):
    conf = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] \
        == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/" \
           "config.json"
    differs = [k for k, v in CATALOG.items() if conf[k] != v]
    assert sorted(differs) == sorted(CUT) == sorted(entry["reduced"]) \
        == sorted(conf["reduced"]) == sorted(conf["reduced_why"])
    for key, value in CUT.items():
        assert conf[key] == value
    # the published values of the cut keys are in the file too
    assert conf["num_hidden_layers_published"] == 32
    assert conf["num_dense_layers_published"] == 2
    assert conf["num_experts_published"] == 128
    assert conf["vocab_size_published"] == 200192 == 8 * conf["vocab_size"]
    # the kept layers are published layers 0, 2, 3, 4, 5
    assert [CATALOG["layer_types"][i] for i in (0, 2, 3, 4, 5)] \
        == conf["layer_types"]
    # no width is cut, and none may be
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "sliding_window", "route_scale")
    assert not set(widths) & set(conf["reduced"])
    for form in ("output_gate", "qk_norm", "rope_on_window_layers_only",
                 "four_norms", "embedding_scale", "router_bias",
                 "training_loss", "dropout", "initialisation"):
        assert form in conf["assumed"], form
    assert "modeling_afmoe.py" in conf["assumed"]["source_of_forms"]
    assert "8 chips share each layer" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < math.log(conf["vocab_size"]) + 0.2 < hi
    assert "PLACEHOLDER" not in json.dumps(conf)


def test_the_parameter_count_is_the_built_models(cell):
    """``parameters_here`` against the op's own weight lists at the
    published widths (shapes alone: nothing is allocated)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops.registry import get_op_def
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    mc = cls(**{f.name: conf[f.name] for f in dataclasses.fields(cls)
                if f.name in conf})
    ff = FFModel(FFConfig())
    cells.load_attr(conf["builder"])(ff, 1, 256, mc)
    counts = {}
    for layer in ff.layers:
        specs = get_op_def(layer.op_type).weights(
            layer.params, [t.shape for t in layer.inputs],
            [t.dtype for t in layer.inputs])
        counts[layer.name] = sum(math.prod(s.shape) for s in specs)
    here = conf["parameters_here"]
    assert counts["attn_2"] == here["attention_per_layer"] == 27263232
    assert sum(counts[n] for n in ("operator_norm_2", "post_operator_norm_2",
                                   "ffn_norm_2", "post_ffn_norm_2")) \
        == here["four_norms_per_layer"]
    assert counts["experts_2"] == here["router_per_layer"] \
        + here["shared_expert_per_layer"] + here["experts_held_per_layer"]
    assert counts["attn_2"] + here["four_norms_per_layer"] \
        + counts["experts_2"] == here["expert_layer"] == 134488448
    assert counts["attn_0"] + here["four_norms_per_layer"] + sum(
        counts[n] for n in ("gate_proj_0", "up_proj_0", "down_proj_0")) \
        == here["dense_layer"] == 65020160
    assert counts["embed_tokens"] + counts["lm_head"] \
        == here["embedding_and_head"]
    assert sum(counts.values()) == here["total"] == 705474304 \
        == here["dense_layer"] + 4 * here["expert_layer"] \
        + here["embedding_and_head"] + here["final_norm"]


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", CONFIG)
    h, d, heads, kv, seq = 2048, 128, 32, 4, 8192
    proj = 2 * (3 * h * heads * d + 2 * h * kv * d)
    window_layer = proj + 2 * 2048 * heads * 2 * d
    full_layer = proj + 2 * seq * heads * 2 * d
    dense = 2 * 3 * h * 6144
    experts = 2 * (h * 128 + 3 * h * 1024 * (8 * 16 / 128 + 1))
    want = (window_layer + dense) + 3 * (window_layer + experts) \
        + (full_layer + experts) + 2 * h * 25024
    assert flops.forward_flops_per_token(cell.config, seq) == want
    assert flops.train_flops_per_token(cell.config, seq) == 3 * want
    # a window layer's products are over min(seq, window) keys
    assert flops.forward_flops_per_token(cell.config, 1024) \
        == want - (4 * (2048 - 1024) + (seq - 1024)) * 2 * heads * 2 * d


# ----------------------------------------------------------------------
# the band's pairs
# ----------------------------------------------------------------------
@pytest.mark.parametrize("s,window", [(48, 24), (48, 1), (48, 48), (48, 64),
                                      (48, 0), (1024, 100), (8192, 2048)])
def test_the_bands_pair_count_against_a_loop(s, window):
    cost = cells.load_module(BENCH, "flops", "window_attention")
    by_loop = sum(min(t + 1, window or s) for t in range(s))
    assert cost.band_pairs(s, window) == by_loop
    if (s, window) == (8192, 2048):
        assert by_loop == 14_681_088
        assert by_loop / cost.band_pairs(s, 0) == pytest.approx(0.4375,
                                                               abs=1e-4)


def test_window_attention_changes_neither_products_nor_bytes():
    cost = cells.load_module(BENCH, "flops", "window_attention")
    flash = cells.load_module(BENCH, "flops", "flash_attention")
    assert cost.PRODUCTS == flash.PRODUCTS
    ops = [("s32", (1, 1))] + [("bf16", (32, 8192, 128))] * 3
    res = [("bf16", (32, 8192, 128)), ("f32", (32, 1, 8192))]
    for kernel in flash.PRODUCTS:
        assert cost.bytes_moved(kernel, ops, res) \
            == flash.bytes_moved(kernel, ops, res)
        # no window: the causal count, to the operation
        assert cost.operations(kernel, ops, 0) \
            == flash.operations(kernel, ops, True) \
            == cost.operations(kernel, ops, 8192)
        assert cost.operations(kernel, ops, 2048) \
            == flash.PRODUCTS[kernel] * 2 * 32 * 14_681_088 * 128


# ----------------------------------------------------------------------
# the readers, on a hand-made trace
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/checkpoint/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 40, FWD + "attn_1/attn.proj/ble,ehd->blhd/"
                                 "dot_general"),
    ("flash_attention_fwd.1", 1040, 100,
     FWD + "attn_1/attn.kernels/flash_attention_fwd/pallas_call"),
    ("fusion.2", 1140, 20, FWD + "attn_1/attn.gate/mul"),
    ("fusion.3", 1160, 40, FWD + "attn_2/attn.proj/ble,ehd->blhd/"
                                 "dot_general"),
    ("flash_attention_fwd.2", 1200, 200,
     FWD + "attn_2/attn.kernels/flash_attention_fwd/pallas_call"),
    ("fusion.4", 1400, 50, FWD + "experts_1/gather"),
    ("ragged-dot-none.1", 1450, 50, "ragged-dot-none"),
    ("flash_attention_bwd_dq.1", 1500, 150,
     BWD + "rematted_computation/attn_1/attn.kernels/"
           "flash_attention_bwd_dq/pallas_call"),
    ("fusion.5", 1650, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
GQA = {"num_heads": 32, "num_kv_heads": 4, "causal": True,
       "output_gate": True}
LAYERS = [("attn_1", "OP_MULTIHEAD_ATTENTION",
           dict(GQA, sliding_window=2048, rope=True)),
          ("attn_2", "OP_MULTIHEAD_ATTENTION", GQA),
          ("experts_1", "OP_ROUTED_EXPERTS", {"shared_dim": 1024}),
          ("lm_head", "OP_LINEAR", {})]
QKV = [("s32", (1, 1))] + [("bf16", (32, 8192, 128))] * 3
FWD_RESULTS = [("bf16", (32, 8192, 128)), ("f32", (32, 1, 8192))]
KERNEL_SHAPES = {
    "flash_attention_fwd.1": (QKV, FWD_RESULTS),
    "flash_attention_fwd.2": (QKV, FWD_RESULTS),
    "flash_attention_bwd_dq.1": (
        QKV + [("bf16", (32, 8192, 128)), ("f32", (32, 1, 8192)),
               ("f32", (32, 1, 8192))], [("bf16", (32, 8192, 128))])}
BAND, TRIANGLE = 14_681_088, 8192 * 8193 // 2


def _model(layers):
    return types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, params=p,
                              op_type=types.SimpleNamespace(name=k))
        for n, k, p in layers])


def _hand_ctx(ops=OPS, layers=LAYERS):
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 1000 * US]], "spans": []}
    instr = {n: {"op_name": op, "mosaic": n in KERNEL_SHAPES
                 or n.startswith("ragged"),
                 "operands": KERNEL_SHAPES.get(n, ([], []))[0],
                 "results": KERNEL_SHAPES.get(n, ([], []))[1]}
             for n, _, _, op in ops}
    names = {n for n, _, _ in layers}
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=_model(layers),
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH))


@pytest.mark.parametrize("metric,want", [
    ("trinity_swa_time_share.train", 100.0 * 310 / 700),
    ("trinity_full_attn_time_share.train", 100.0 * 240 / 700),
    ("moe_time_share.train", 100.0 * 100 / 700)])
def test_time_shares_by_hand(metric, want):
    """The window layer's ops (its second forward under the block's
    rematerialisation among them) and the full layer's add up to the
    attention layers' 550 of 700; the grouped product without a name
    goes to the expert layer before it."""
    ctx = _hand_ctx()
    assert ctx.span_reduced["busy_ns"] == 700 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_rooflines_count_each_call_by_its_own_layers_mask():
    ctx = _hand_ctx()
    peak = 197e12
    # forward: the window layer's call over the band, the full layer's
    # over the triangle, summed over the 300 us the two calls took
    fwd = _read("window_flash_fwd_roofline", ctx)
    assert fwd == pytest.approx(
        100.0 * (2 * 2 * 32 * (BAND + TRIANGLE) * 128 / peak) / 300e-6)
    # the backward call sits under the rematerialised block's scope and
    # is the window layer's: the band alone
    dq = _read("window_flash_bwd_dq_roofline", ctx)
    assert dq == pytest.approx(
        100.0 * (3 * 2 * 32 * BAND * 128 / peak) / 150e-6)
    # counted over the causal triangle it would read 2.29 times too high
    assert TRIANGLE / BAND == pytest.approx(2.2858, abs=1e-3)
    assert _read("window_flash_bwd_dkv_roofline", ctx) is None   # no call


def test_rooflines_read_nothing_where_another_layer_shares_the_kernel():
    ops = OPS + [("flash_attention_fwd.3", 1700, 50,
                  FWD + "cross_attn/flash_attention_fwd/pallas_call")]
    KERNEL_SHAPES["flash_attention_fwd.3"] = \
        KERNEL_SHAPES["flash_attention_fwd.1"]
    try:
        ctx = _hand_ctx(ops, LAYERS + [(
            "cross_attn", "OP_MULTIHEAD_ATTENTION", {"num_heads": 12})])
    finally:
        del KERNEL_SHAPES["flash_attention_fwd.3"]
    assert _read("window_flash_fwd_roofline", ctx) is None
    assert _read("window_flash_bwd_dq_roofline", ctx) is not None


def test_the_counters_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"moe.dropped": 0.0, "moe.overflow": 2.0,
                    "attn.window_pairs": 4 * 14681088.0,
                    "attn.causal_pairs": 4 * 33558528.0}
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("moe_overflow_layer_steps", ctx) == 2.0
    assert _read("swa_kept_share", ctx) == pytest.approx(0.43748,
                                                                 abs=1e-5)


@pytest.mark.parametrize("metric", sorted(PR51))
def test_every_new_reader_reads_nothing_from_the_parent(metric):
    """The parent of PR 51 counts no ``attn.window_pairs``, and a run
    without ``--trace 1`` has no trace and no counters: nothing to read,
    and no error. A model of the parent's (cell 4's causal grouped-query
    attention beside convolutions) has no window layer; the readers that
    pick layers the parent has (full causal attention, the experts, the
    flash calls counted causal) read those where a trace is there."""
    lfm2 = [("attn_1", "OP_MULTIHEAD_ATTENTION",
             {"num_heads": 32, "num_kv_heads": 8, "causal": True}),
            ("dense_1", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 100, FWD + "attn_1/mul"),
           ("flash_attention_fwd.1", 1100, 200,
            FWD + "attn_1/flash_attention_fwd/pallas_call")]
    got = _read(metric, _hand_ctx(ops, lfm2))
    if metric in ("trinity_full_attn_time_share.train",
                  "window_flash_fwd_roofline"):
        assert isinstance(got, float)
    else:
        assert got is None
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        model=_model(LAYERS),
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH))
    assert _read(metric, bare) is None


@pytest.mark.parametrize("metric", sorted(PR51))
def test_every_new_reader_reads_the_recorded_testdata_without_error(
        metric):
    """``benchmarks/testdata/``'s recorded traces are of a model with
    none of the layers these readers pick: each returns None or a
    number, and raises nothing."""
    with open(os.path.join(BENCH, "testdata", "trace_events.json")) as f:
        recorded = json.load(f)
    with open(os.path.join(BENCH, "testdata", "span_trace.json")) as f:
        spans = json.load(f)
    assert recorded and spans
    ctx = types.SimpleNamespace(
        trace=trace_reduce.reduce_trace(recorded["events"], [], {}, []),
        span_events=dict(recorded["events"], spans=[]),
        span_instructions={},
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH),
        step_text="", peak=peaks.lookup("TPU v5 lite"), counters={},
        model=_model(LAYERS))
    got = _read(metric, ctx)
    assert got is None or isinstance(got, float)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference",
                           "window_gated_moe_ref.py")) as f:
        text = f.read()
    assert "flexflow_tpu" not in text.replace(
        "``flexflow_tpu``", "") and "import flexflow" not in text
    assert 'default_matmul_precision("highest")' in text
    mod = cells.load_module(BENCH, "reference", "window_gated_moe_ref")
    assert callable(mod.window_gated_moe_decoder) and callable(mod.loss)
