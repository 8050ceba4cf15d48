"""``qwen3_next_80b_a3b.train.1chip`` (PR 57): the files resolve, the
manifest's new entries are there BY NAME (nothing here pins a list's
tail: a later PR appends after them), the configuration's file holds the
catalog row except for the cut, the parameter and operation counts are
the model's, and the ten readers read a hand-made trace, the recorded
test traces and a parent's program (nothing, without an error).
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
CONFIG = "qwen3_next_80b_a3b"
CELL = "qwen3_next_80b_a3b.train.1chip"
OLDER_CELLS = {
    "bert_large.train.1chip", "gpt2_124m.train.1chip",
    "joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
    "kimi_linear_48b_a3b.train.1chip", "xing4_29b_a4b.train.1chip",
    "keye_vl2_30b_a3b.train.1chip", "trinity_mini.train.1chip",
    "granite_4_0_h_micro.train.1chip"}
US = 1000
PR57 = {        # name -> (unit, better, source, layer)
    "qwen3next_gdn_time_share.train": ("%", "lower", "device_trace",
                                       "linear_attention"),
    "qwen3next_gdn_scan_time_share.train": ("%", "lower", "device_trace",
                                            "linear_attention"),
    "qwen3next_attn_time_share.train": ("%", "lower", "device_trace",
                                        "attention"),
    "moe_time_share.train": ("%", "lower", "device_trace",
                                       "experts"),
    "window_flash_fwd_roofline": ("%", "higher", "device_trace",
                                     "kernels"),
    "window_flash_bwd_dq_roofline": ("%", "higher", "device_trace",
                                        "kernels"),
    "window_flash_bwd_dkv_roofline": ("%", "higher", "device_trace",
                                         "kernels"),
    "qwen3next_gdn_min_chunk_log_decay": ("nats", "higher",
                                          "program_counter",
                                          "linear_attention"),
    "moe_dropped_assignments": ("count", "lower",
                                          "program_counter", "experts"),
    "moe_overflow_layer_steps": ("count", "lower",
                                           "program_counter", "experts"),
}
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train", "recompute_time_share.train",
          "recompute_kernel_time_share.train",
          "recompute_again_time_share.train", "remat_held_gib",
          "weights_and_optimizer_gib"}


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
@pytest.mark.parametrize("name", sorted(PR57))
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    unit, better, source, layer = PR57[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "train_tokens_per_s",
        "workloads": _holding(CELL, by_name[name]["workloads"])}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    """After the nine cells and configurations of the parent and
    its metrics (no count is held, and this PR's entries that other
    cells' readers share stand where the first of them stood); what
    comes after this PR's is not this test's to say."""
    order = [m["name"] for m in manifest["per_layer"]]
    own = [n for n in PR57 if n.startswith("qwen3next_")]
    assert order.index("ssm_min_chunk_log_decay") \
        < min(order.index(n) for n in own)
    assert [n for n in order if n in own] == own
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("granite_4_0_h_micro") < configs.index(CONFIG)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"]
               if w["name"] in OLDER_CELLS or w["name"] == CELL)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert CELL in perf and "qwen3next_gdn_min_chunk_log_decay" in perf
    for layer in ("linear_attention", "attention", "experts", "kernels"):
        assert layer in perf


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry["chips"] == 1 and entry["config"] != CONFIG
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert not reported & {n for n in PR57 if n.startswith("qwen3next_")}


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(PR57)
    assert not [m["name"] for m in cell.per_layer
                if m["name"].startswith("qwen3next_")
                and m["name"] not in PR57]
    assert cell.chips == 1
    # the file that was there, as it is: one sequence of 8192 tokens
    assert cell.traffic_name == "train_b1_s8192"
    assert cell.traffic["seq"] == 8192 == 128 * 64        # 128 chunks
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"] == 1


# ----------------------------------------------------------------------
# the configuration's file
# ----------------------------------------------------------------------
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        manifest, cell):
    conf = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] \
        == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/" \
           "main/config.json"
    assert entry["file"] == "benchmarks/configs/qwen3_next_80b_a3b.json"
    differs = [k for k, v in CATALOG.items() if conf[k] != v]
    assert sorted(differs) == sorted(CUT) == sorted(entry["reduced"]) \
        == sorted(conf["reduced"]) == sorted(conf["reduced_why"])
    for key, value in CUT.items():
        assert conf[key] == value
    # the published values of the cut keys are in the file too
    assert conf["num_hidden_layers_published"] == 48
    assert conf["num_experts_published"] == 512 == 16 * conf["num_experts"]
    assert conf["vocab_size_published"] == 151936 == 8 * conf["vocab_size"]
    assert conf["first_held_expert"] == 0
    # one whole period, as full_attention_interval lays it out
    assert conf["layer_types"] == ["linear_attention"] * 3 \
        + ["full_attention"]
    # no width is cut, and none may be
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "head_dim",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_conv_kernel_dim", "num_attention_heads",
              "num_key_value_heads", "num_experts_per_tok",
              "partial_rotary_factor")
    assert not set(widths) & set(conf["reduced"])
    assert set(conf["departures"]) == {"separate_projections",
                                       "query_and_gate", "layout"}
    for form in ("zero_centered_norm", "gated_delta_rule", "attention",
                 "experts", "expert_rows", "A_log", "dt_bias",
                 "initialisation", "training_loss", "dropout", "mtp"):
        assert form in conf["assumed"], form
    assert "NOT built" in conf["assumed"]["mtp"]
    assert "16 chips share each layer" in conf["deployment"]
    assert "rank 0" in conf["deployment"]
    assert "eight slices" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < math.log(conf["vocab_size"]) < hi
    assert "PLACEHOLDER" not in json.dumps(conf)


def test_the_parameter_count_is_the_built_models(cell):
    """``parameters_here`` against the op's own weight lists at the
    published widths (shapes alone: nothing is allocated), and against
    ISSUE 57's table."""
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
    assert counts["linear_attn_0"] == here["linear_mixer"] == 33718464 \
        == 2 * 2048 * 2048 + 3 * 2048 * 4096 + 2 * 2048 * 32 \
        + (2048 + 2048 + 4096) * 4 + 2 * 32 + 128
    assert counts["attn_3"] == here["full_mixer"] == 27263488 \
        == 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 256
    assert counts["experts_0"] == counts["experts_3"] \
        == here["router_shared_expert_and_gate"] \
        + here["experts_held_per_layer"]
    assert here["router_shared_expert_and_gate"] == 4196352 \
        == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert here["experts_held_per_layer"] == 100663296 \
        == 32 * 3 * 2048 * 512
    assert counts["operator_norm_0"] + counts["ffn_norm_0"] \
        == here["two_norms_per_layer"] == 4096
    assert counts["linear_attn_0"] + counts["experts_0"] + 4096 \
        == here["linear_layer"] == 138582208
    assert counts["attn_3"] + counts["experts_3"] + 4096 \
        == here["full_layer"] == 132127232
    assert counts["embed_tokens"] + counts["lm_head"] \
        == here["embedding_and_head"] == 2 * 18992 * 2048
    assert sum(counts.values()) == here["total"] == 625667136 \
        == 3 * here["linear_layer"] + here["full_layer"] \
        + here["embedding_and_head"] + here["final_norm"]
    assert here["four_layers"] == 3 * here["linear_layer"] \
        + here["full_layer"]
    # the experts' row budget: 6 uniform shares, 30,720 of 81,920 rows
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    held = next(l for l in ff.layers if l.name == "experts_3")
    assert held.params["rows_factor"] == conf["expert_rows_factor"] == 6
    assert RoutedExpertsOp.rows_multiplied(8192, held.params) == 30720


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", CONFIG)
    h, seq = 2048, 8192
    linear = 2 * (h * 128 * (2 * 16 + 3 * 32) + 2 * h * 32) \
        + 7 * 32 * 128 * 128
    full = 2 * (3 * h * 16 * 256 + 2 * h * 2 * 256) + 2 * seq * 16 * 2 * 256
    experts = 2 * (h * 512 + 3 * h * 512 * (10 * 32 / 512)
                   + 3 * h * 512 + h)
    want = 3 * linear + full + 4 * experts + 2 * h * 18992
    assert flops.forward_flops_per_token(cell.config, seq) == want
    assert flops.train_flops_per_token(cell.config, seq) == 3 * want
    # ISSUE 57's round number: about 4 TFLOP forward a step
    assert 4.0e12 < want * seq < 4.5e12
    # the recurrence is by its recurrent form: no chunk size in it, and
    # only attention grows with the sequence
    assert flops.forward_flops_per_token(cell.config, 2 * seq) - want \
        == 2 * seq * 16 * 2 * 256


@pytest.mark.parametrize("kernel,products,moved", [
    ("flash_attention_fwd", 2, 2 * 67108864 + 2 * 8388608 + 524288),
    ("flash_attention_bwd_dq", 3, 3 * 67108864 + 2 * 8388608 + 1048576),
    ("flash_attention_bwd_dkv", 4, 2 * 67108864 + 4 * 8388608 + 1048576)])
def test_a_flash_calls_count_is_the_readers(cell, kernel, products, moved):
    """16 query heads on 2 key/value heads of 256 at 8,192 positions:
    the causal triangle's pairs, q-sized arrays of 64 MiB and kv-sized
    ones of 8 MiB in bf16, a float32 a row for each statistic; the
    readers' own function (``flops/window_attention.py`` at no window)
    counts the same from the call's operand shapes."""
    flops = cells.load_module(BENCH, "flops", CONFIG)
    ops, nbytes = flops.flash_call(kernel, cell.config, 8192)
    assert ops == products * 2 * 16 * (8192 * 8193 // 2) * 256
    assert nbytes == moved
    q, kv, stat = ("bf16", (16, 8192, 256)), ("bf16", (2, 8192, 256)), \
        ("f32", (16, 8192, 128))
    seed = ("s32", ())
    operands = [seed, q, kv, kv] if kernel.endswith("fwd") \
        else [seed, q, kv, kv, q, stat, stat]
    results = {"flash_attention_fwd": [q, stat],
               "flash_attention_bwd_dq": [q],
               "flash_attention_bwd_dkv": [kv, kv]}[kernel]
    window = cells.load_module(BENCH, "flops", "window_attention")
    assert window.operations(kernel, operands, 0) == ops
    assert window.bytes_moved(kernel, operands, results) == moved + 4
    # every one of them bound by its operations on a v5e
    peak = peaks.lookup("TPU v5 lite")
    assert window.roofline_s(kernel, operands, results, 0, peak) \
        == (ops / peak["bf16_flops_per_s"], "operations")


# ----------------------------------------------------------------------
# the readers, on a hand-made trace
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/remat.block/checkpoint/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/remat.block/checkpoint/rematted_computation/"
GDN = "linear_attn_0/remat.kda.layer/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 60, FWD + GDN + "remat.kda.branch/checkpoint/"
                                       "bte,ehd->bhtd/dot_general"),
    ("fusion.2", 1060, 100, FWD + GDN + "gdn.scan/remat.gdn.terms/"
                                        "checkpoint/triangular_solve"),
    ("while.1", 1160, 200, FWD + GDN + "gdn.scan/while"),
    ("fusion.3", 1170, 100, FWD + GDN + "gdn.scan/while/body/remat.kda.step/"
                                        "checkpoint/bhcd,bhde->bhce/"
                                        "dot_general"),
    ("fusion.4", 1360, 40, FWD + GDN + "bhtd,hde->bte/dot_general"),
    ("fusion.5", 1400, 100, FWD + "experts_0/ragged_dot"),
    ("flash_attention_fwd.1", 1500, 50,
     FWD + "attn_3/attn.kernels/flash_attention_fwd/pallas_call"),
    ("fusion.6", 1550, 50, FWD + "attn_3/attn.norm_rope/concatenate"),
    ("fusion.7", 1600, 150, BWD + GDN + "rematted_computation/gdn.scan/"
                                        "cumsum"),
    ("fusion.8", 1750, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
LAYERS = [("linear_attn_0", "OP_GATED_DELTA_RULE",
           {"num_heads": 32, "num_key_heads": 16, "head_dim": 128,
            "decay": "head"}),
          ("experts_0", "OP_ROUTED_EXPERTS",
           {"num_experts": 512, "shared_gate": True}),
          ("attn_3", "OP_MULTIHEAD_ATTENTION",
           {"num_heads": 16, "num_kv_heads": 2, "causal": True,
            "rotary_dim": 64}),
          ("lm_head", "OP_LINEAR", {})]


def _model(layers):
    return types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, params=p,
                              op_type=types.SimpleNamespace(name=k))
        for n, k, p in layers])


def _hand_ctx(ops=OPS, layers=LAYERS):
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 1000 * US]], "spans": []}
    q, kv, stat = ("bf16", (16, 8192, 256)), ("bf16", (2, 8192, 256)), \
        ("f32", (16, 8192, 128))
    instr = {n: {"op_name": op, "mosaic": n.startswith("flash"),
                 "operands": [("s32", ()), q, kv, kv]
                 if n.startswith("flash") else [],
                 "results": [q, stat] if n.startswith("flash") else []}
             for n, _, _, op in ops}
    names = {n for n, _, _ in layers}
    by_op = scope_reduce.op_self_ns(events, instr, names)
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=_model(layers),
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        name_by_op=by_op, peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH))


@pytest.mark.parametrize("metric,want", [
    ("qwen3next_gdn_time_share.train", 100.0 * 550 / 800),
    ("qwen3next_gdn_scan_time_share.train", 100.0 * 450 / 800),
    ("qwen3next_attn_time_share.train", 100.0 * 100 / 800),
    ("moe_time_share.train", 100.0 * 100 / 800)])
def test_time_shares_by_hand(metric, want):
    """The linear layer's ops in the forward pass and under the block's
    and the layer's rematerialisation (550 of 800 us busy), of them the
    solve's 100, the loop's own 100, its body's 100 and the recomputed
    running sum's 150 under ``gdn.scan``; the attention layer's kernel
    and its plain rotary chain; the experts' grouped product."""
    ctx = _hand_ctx()
    assert ctx.span_reduced["busy_ns"] == 800 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_forward_kernels_roofline_by_hand():
    """One traced call of 50 us against the least time of its
    operations: 2 products over the causal triangle of 16 heads of 256
    at 8,192 positions, 549.8 GFLOP, 2.79 ms on a v5e; a reader must not
    clip what a hand-made duration makes absurd."""
    ctx = _hand_ctx()
    least = 2 * 2 * 16 * (8192 * 8193 // 2) * 256 / 197e12
    assert _read("window_flash_fwd_roofline", ctx) \
        == pytest.approx(100.0 * least / 50e-6)
    assert _read("window_flash_bwd_dq_roofline", ctx) is None


def test_the_counters_quotient_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"gdn.log_decay_min": -3 * 64 * 120.0,
                    "gdn.scans": 3 * 64.0, "moe.dropped": 0.0,
                    "moe.overflow": 2.0}
    assert _read("qwen3next_gdn_min_chunk_log_decay", ctx) \
        == pytest.approx(-120.0)
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("moe_overflow_layer_steps", ctx) == 2.0
    ctx.counters = {"gdn.log_decay_min": -1.0, "gdn.scans": 0.0}
    assert _read("qwen3next_gdn_min_chunk_log_decay", ctx) is None


@pytest.mark.parametrize("metric", sorted(PR57))
def test_every_new_reader_reads_nothing_from_the_parent(metric):
    """The parent of PR 57 has no delta rule with a decay a head, no
    attention layer that turns part of a head, no gated shared expert
    and no ``gdn.*`` counter, and a run without ``--trace 1`` has no
    trace and no counters: nothing to read, and no error. A model of
    the parent's (cell 5's delta rule beside cell 8's attention and
    experts) is not read as this model's, but for the three rooflines,
    which are the readings of every causal layer's calls."""
    older = [("kda_1", "OP_GATED_DELTA_RULE",
              {"num_heads": 32, "head_dim": 128}),
             ("attn_2", "OP_MULTIHEAD_ATTENTION",
              {"num_heads": 32, "num_kv_heads": 4, "causal": True,
               "output_gate": True}),
             ("experts_2", "OP_ROUTED_EXPERTS", {"num_experts": 128})]
    ops = [("fusion.1", 1000, 100, FWD + "kda_1/kda.scan/mul"),
           ("fusion.2", 1100, 100, FWD + "attn_2/mul"),
           ("flash_attention_fwd.1", 1200, 50,
            FWD + "attn_2/attn.kernels/flash_attention_fwd/pallas_call"),
           ("fusion.3", 1250, 200, FWD + "experts_2/ragged_dot")]
    # the rooflines read any causal layer's calls, and the experts'
    # share any expert layer's ops (200 of the 450 us busy)
    if metric == "moe_time_share.train":
        assert _read(metric, _hand_ctx(ops, older)) == pytest.approx(
            100.0 * 200 / 450)
    elif "roofline" not in metric:
        assert _read(metric, _hand_ctx(ops, older)) is None
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        model=_model(LAYERS),
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH))
    assert _read(metric, bare) is None


@pytest.mark.parametrize("metric", sorted(PR57))
def test_every_new_reader_reads_the_recorded_testdata_without_error(
        metric):
    """``benchmarks/testdata/``'s recorded traces are of a model with
    none of the layers these readers pick: each returns None or a
    number, and raises nothing."""
    with open(os.path.join(BENCH, "testdata", "trace_events.json")) as f:
        recorded = json.load(f)
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
                           "gdn_gated_moe_ref.py")) as f:
        text = f.read()
    assert "flexflow_tpu" not in text and "import flexflow" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "jax.lax.scan(step" in text          # token by token
    assert "partial_rotary_factor" in text and "A_log" in text \
        and "ws_scalar" in text
    mod = cells.load_module(BENCH, "reference", "gdn_gated_moe_ref")
    assert callable(mod.gdn_gated_moe_decoder) and callable(mod.loss) \
        and callable(mod.rounded_operands)
