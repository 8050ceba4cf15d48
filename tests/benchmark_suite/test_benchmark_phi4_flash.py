"""``phi4_mini_flash_reasoning.train.1chip`` (PR 61): the files resolve,
the manifest's new entries are there BY NAME (nothing here pins a list's
tail: a later PR appends after them), the configuration's file holds the
catalog row except for the cut, the parameter and operation counts are
the model's (761,114,752 here, 3,852,562,944 at full depth with the head
tied), and the ten readers read a hand-made trace, the recorded test
traces and a parent's program (nothing, without an error).
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
CONFIG = "phi4_mini_flash_reasoning"
CELL = "phi4_mini_flash_reasoning.train.1chip"
OLDER_CELLS = {
    "bert_large.train.1chip", "gpt2_124m.train.1chip",
    "joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
    "kimi_linear_48b_a3b.train.1chip", "xing4_29b_a4b.train.1chip",
    "keye_vl2_30b_a3b.train.1chip", "trinity_mini.train.1chip",
    "granite_4_0_h_micro.train.1chip", "qwen3_next_80b_a3b.train.1chip"}
US = 1000
PR61 = {        # name -> (unit, better, source, layer)
    "phi4flash_ssm_time_share.train": ("%", "lower", "device_trace",
                                       "state_space"),
    "phi4flash_ssm_scan_time_share.train": ("%", "lower", "device_trace",
                                            "state_space"),
    "phi4flash_diff_attn_time_share.train": ("%", "lower", "device_trace",
                                             "attention"),
    "phi4flash_gmu_time_share.train": ("%", "lower", "device_trace",
                                       "feed_forward"),
    "phi4flash_mlp_time_share.train": ("%", "lower", "device_trace",
                                       "feed_forward"),
    "phi4flash_flash_fwd_roofline": ("%", "higher", "device_trace",
                                     "kernels"),
    "phi4flash_flash_bwd_dq_roofline": ("%", "higher", "device_trace",
                                        "kernels"),
    "phi4flash_flash_bwd_dkv_roofline": ("%", "higher", "device_trace",
                                         "kernels"),
    "swa_kept_share": ("ratio", "lower", "program_counter",
                                 "attention"),
    "phi4flash_ssm_min_step_log_decay": ("nats", "higher",
                                         "program_counter", "state_space"),
}
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train", "recompute_time_share.train",
          "recompute_kernel_time_share.train",
          "recompute_again_time_share.train", "remat_held_gib",
          "weights_and_optimizer_gib"}
KINDS = ["mamba1", "diff_sliding_attention", "mamba1_memory",
         "diff_attention_kv", "gated_memory", "diff_cross_attention"]


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
@pytest.mark.parametrize("name", sorted(PR61))
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    unit, better, source, layer = PR61[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "train_tokens_per_s",
        "workloads": _holding(CELL, by_name[name]["workloads"])}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    """After the ten cells and configurations of the parent and
    its metrics (no count is held, and this PR's entries that other
    cells' readers share stand where the first of them stood); what
    comes after this PR's is not this test's to say."""
    order = [m["name"] for m in manifest["per_layer"]]
    own = [n for n in PR61 if n.startswith("phi4flash_")]
    assert order.index("qwen3next_gdn_min_chunk_log_decay") \
        < min(order.index(n) for n in own)
    assert [n for n in order if n in own] == own
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("qwen3_next_80b_a3b") < configs.index(CONFIG)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"]
               if w["name"] in OLDER_CELLS or w["name"] == CELL)
    assert manifest["run_seconds"] == 20
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert CELL in perf and "phi4flash_ssm_min_step_log_decay" in perf


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry["chips"] == 1 and entry["config"] != CONFIG
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert not reported & {n for n in PR61 if n.startswith("phi4flash_")}


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(PR61)
    assert not [m["name"] for m in cell.per_layer
                if m["name"].startswith("phi4flash_")
                and m["name"] not in PR61]
    assert cell.chips == 1
    # the file that was there, as it is: one sequence of 8192 tokens
    assert cell.traffic_name == "train_b1_s8192"
    assert cell.traffic["seq"] == 8192 \
        == 128 * cell.config["mamba_chunk_size"]
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"] == 1


# ----------------------------------------------------------------------
# the configuration's file
# ----------------------------------------------------------------------
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
CUT = {"num_hidden_layers": 6, "vocab_size": 25008}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        manifest, cell):
    conf = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] \
        == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/" \
           "blob/main/config.json"
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    differs = [k for k, v in CATALOG.items() if conf[k] != v]
    assert sorted(differs) == sorted(CUT) == sorted(entry["reduced"]) \
        == sorted(conf["reduced"]) == sorted(conf["reduced_why"])
    for key, value in CUT.items():
        assert conf[key] == value
    # the published values of the cut keys are in the file too
    assert conf["num_hidden_layers_published"] == 32
    assert conf["vocab_size_published"] == 200064 == 8 * conf["vocab_size"]
    assert conf["layer_types"] == KINDS \
        == conf["layer_types_published"][14:20]
    assert conf["first_layer_index"] == 14
    published = conf["layer_types_published"]
    assert len(published) == 32 \
        and [published.count(k) for k in KINDS] == [8, 8, 1, 1, 7, 7]
    # the ratio it departs from is said
    assert "8 : 1 : 7" in conf["reduced_why"]["num_hidden_layers"] \
        and "1 : 1 : 1" in conf["reduced_why"]["num_hidden_layers"]
    # no width is cut, and none may be
    widths = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "sliding_window",
              "mamba_d_state", "mamba_d_conv", "mamba_expand",
              "mamba_dt_rank")
    assert not set(widths) & set(conf["reduced"])
    assert (conf["mamba_d_state"], conf["mamba_d_conv"],
            conf["mamba_expand"], conf["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert set(conf["departures"]) == {
        "untied_head", "mlp_input_in_two_halves", "qkv_in_three"}
    for form in ("mamba_sizes", "mamba_biases", "attention_biases",
                 "layer_layout", "differential_form", "lambda_vectors",
                 "A_log", "dt_bias", "D", "conv_taps", "matrices",
                 "mamba_chunk_size", "head_dim", "dropout",
                 "training_loss"):
        assert form in conf["assumed"], form
    assert "layers 14 to 19 of 32" in conf["deployment"]
    assert "eight slices" in conf["deployment"]
    # ln 25008 plus half the logits' variance (0.093): a head whose
    # logits are all equal reads ln 25008 and is OUTSIDE the band
    lo, hi = conf["initial_loss_band"]
    assert math.log(conf["vocab_size"]) < lo \
        < math.log(conf["vocab_size"]) + 0.093 < hi
    assert conf["reference_rel_tol"] > 0
    assert "PLACEHOLDER" not in json.dumps(conf) \
        and "PROVISIONAL" not in json.dumps(conf)


def test_the_files_fields_are_the_classs(cell):
    """Every field of the builder's class is in the file at the class's
    own value for this rank: the file IS the configuration that runs."""
    import dataclasses
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    default = cls()
    for f in dataclasses.fields(cls):
        assert f.name in conf, f.name
        assert conf[f.name] == getattr(default, f.name), f.name


def _weight_counts(mc, builder):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops.registry import get_op_def
    ff = FFModel(FFConfig())
    builder(ff, 1, 256, mc)
    counts = {}
    for layer in ff.layers:
        specs = get_op_def(layer.op_type).weights(
            layer.params, [t.shape for t in layer.inputs],
            [t.dtype for t in layer.inputs])
        counts[layer.name] = sum(math.prod(s.shape) for s in specs)
    return counts


def test_the_parameter_count_is_the_built_models(cell):
    """``parameters_here`` against the ops' own weight lists at the
    published widths (shapes alone: nothing is allocated), against ISSUE
    61's table, and the full depth with the head tied against the
    published 3.8B."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    mc = cls(**{f.name: conf[f.name] for f in dataclasses.fields(cls)
                if f.name in conf})
    builder = cells.load_attr(conf["builder"])
    counts = _weight_counts(mc, builder)
    here = conf["parameters_here"]
    assert counts["ssm_0"] == counts["ssm_2"] == here["mamba_mixer"] \
        == 41241600 == 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 \
        + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560
    assert counts["attn_1"] == counts["attn_3"] \
        == here["differential_attention"] == 19668864 \
        == 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 256 + 128
    assert counts["attn_5"] == here["cross_attention"] == 13112704
    assert counts["gmu_in_4"] + counts["gmu_out_4"] \
        == here["gated_memory_unit"] == 26214400
    mlp = sum(counts[n] for n in ("gate_proj_0", "up_proj_0",
                                  "down_proj_0"))
    assert mlp == here["mlp"] == 78643200
    norms = counts["operator_norm_0"] + counts["ffn_norm_0"]
    assert norms == here["two_norms"] == 10240
    assert here["mamba_layer"] == 119895040 == counts["ssm_0"] + mlp + norms
    assert here["attention_layer"] == 98322304 \
        == counts["attn_1"] + mlp + norms
    assert here["gated_memory_layer"] == 104867840 \
        == here["gated_memory_unit"] + mlp + norms
    assert here["cross_layer"] == 91766144 == counts["attn_5"] + mlp + norms
    assert counts["embed_tokens"] == here["embedding"] == here["head"] \
        == counts["lm_head"] == 64020480
    assert here["six_layers"] == 633068672 \
        == 2 * here["mamba_layer"] + 2 * here["attention_layer"] \
        + here["gated_memory_layer"] + here["cross_layer"]
    assert sum(counts.values()) == here["total"] == 761114752 \
        == here["six_layers"] + 2 * here["embedding"] + here["final_norm"]
    # the full depth, the whole vocabulary, the head tied: the published
    # "3.8B", and the check that the assumed Mamba sizes are the model's
    full = _weight_counts(dataclasses.replace(
        mc, first_layer_index=0, num_hidden_layers=32, layer_types=None,
        vocab_size=200064), builder)
    assert sum(full.values()) - full["lm_head"] \
        == here["full_depth_tied"] == 3852562944 \
        == 9 * here["mamba_layer"] + 9 * here["attention_layer"] \
        + 7 * here["gated_memory_layer"] + 7 * here["cross_layer"] \
        + 512163840 + 5120


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", CONFIG)
    h, seq, d, n, r = 2560, 8192, 5120, 16, 160
    mixer = 2 * (h * 2 * d + d * (r + 2 * n) + r * d + d * h) \
        + d * (5 * n + 2)
    def attention(keys, own_kv=True):
        proj = 2 * h * 2560 + (2 * h * 1280 if own_kv else 0)
        return 2 * proj + 20 * 2 * keys * (2 * 64 + 2 * 128)
    gmu = 2 * 2 * h * d
    mlp = 2 * 3 * h * 10240
    want = 2 * mixer + attention(512) + attention(seq) + gmu \
        + attention(seq, False) + 6 * mlp + 2 * h * 25008
    assert flops.forward_flops_per_token(cell.config, seq) == want
    assert flops.train_flops_per_token(cell.config, seq) == 3 * want
    # ISSUE 61's round numbers: 15,360 operations a pair of positions a
    # layer, 0.515 TFLOP a whole layer's causal half, about 12.5 TFLOP
    # of products a forward pass
    assert 20 * 2 * (2 * 64 + 2 * 128) == 15360
    assert 0.51e12 < 15360 * seq * (seq + 1) / 2 < 0.52e12
    assert 12e12 < want * seq < 15e12
    # the recurrence is by its recurrent form: no chunk size in it
    other = dict(cell.config, mamba_chunk_size=256)
    assert flops.forward_flops_per_token(other, seq) == want
    # only whole and cross attention grow with the sequence
    assert flops.forward_flops_per_token(cell.config, 2 * seq) - want \
        == 2 * seq * 15360
    with pytest.raises(ValueError, match="layer kind"):
        flops.forward_flops_per_token(
            dict(cell.config, layer_types=["mamba"] * 6), seq)


@pytest.mark.parametrize("kernel,products", [
    ("flash_attention_fwd", 64 + 128),
    ("flash_attention_bwd_dq", 2 * 64 + 128),
    ("flash_attention_bwd_dkv", 2 * 64 + 2 * 128)])
def test_a_flash_calls_operations_and_bytes_by_hand(kernel, products):
    """One of a layer's two calls at the cell's shapes: 20 query pairs
    reading 10 key pairs in place, q.k over 64 and p.v over 128, whole
    and in the 512 band (12.1% of the causal pairs)."""
    flops = cells.load_module(BENCH, "flops", CONFIG)
    s = 8192
    q, k, v = ("bf16", [20, s, 64]), ("bf16", [10, s, 64]), \
        ("bf16", [10, s, 128])
    operands = [("s32", [1, 1]), q, k, v]
    whole, band = s * (s + 1) // 2, 512 * s - 512 * 511 // 2
    assert band / whole == pytest.approx(0.12109, abs=1e-5)
    assert flops.flash_operations(kernel, operands, 0) \
        == 2 * 20 * whole * products
    assert flops.flash_operations(kernel, operands, 512) \
        == 2 * 20 * band * products
    assert flops.flash_operations(kernel, operands, s) \
        == flops.flash_operations(kernel, operands, 0)
    results = [("bf16", [20, s, 128])]
    assert flops.flash_bytes("flash_attention_fwd", operands, results) \
        == 4 + 2 * s * (20 * 64 + 10 * 64 + 10 * 128 + 20 * 128)
    peak = peaks.lookup("TPU v5 lite")
    seconds, bound = flops.flash_roofline_s(kernel, operands, results, 0,
                                            peak)
    assert bound == "operations" and seconds == pytest.approx(
        2 * 20 * whole * products / peak["bf16_flops_per_s"])


# ----------------------------------------------------------------------
# the readers, on a hand-made trace
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/remat.block/checkpoint/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/remat.block/checkpoint/rematted_computation/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 60, FWD + "ssm_0/bte,ec->btc/dot_general"),
    ("while.1", 1060, 200, FWD + "ssm_0/ssm1.scan/while"),
    ("fusion.2", 1070, 100, FWD + "ssm_0/ssm1.scan/while/body/"
                                  "remat.ssm1.chunk/checkpoint/while/body/"
                                  "mul"),
    ("fusion.3", 1260, 40, FWD + "ssm_0/btc,ce->bte/dot_general"),
    ("fusion.4", 1300, 100, FWD + "gate_proj_0/dot_general"),
    ("fusion.5", 1400, 50, FWD + "op_ew_mul_9/mul"),
    ("fusion.6", 1450, 100, FWD + "down_proj_0/dot_general"),
    ("flash_attention_fwd.1", 1550, 40,
     FWD + "attn_1/attn.kernels/flash_attention_fwd/pallas_call"),
    ("flash_attention_fwd.2", 1590, 60,
     FWD + "attn_3/attn.kernels/flash_attention_fwd/pallas_call"),
    ("fusion.7", 1650, 50, FWD + "attn_3/attn.diff/sub"),
    ("fusion.8", 1700, 30, FWD + "gmu_in_4/dot_general"),
    ("fusion.9", 1730, 20, FWD + "gmu_gate_4/mul"),
    ("fusion.10", 1750, 100, BWD + "ssm_0/ssm1.scan/while/body/"
                                   "remat.ssm1.chunk/checkpoint/"
                                   "rematted_computation/exp"),
    ("fusion.11", 1850, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
DIFF = {"num_heads": 40, "num_kv_heads": 20, "causal": True,
        "differential": True, "lambda_init": 0.79}
LAYERS = [("ssm_0", "OP_SELECTIVE_SCAN_MIXER",
           {"inner": 5120, "state": 16}),
          ("gate_proj_0", "OP_LINEAR", {}), ("up_proj_0", "OP_LINEAR", {}),
          ("op_sigmoid_7", "OP_SIGMOID", {}), ("silu_0", "OP_EW_MUL", {}),
          ("op_ew_mul_9", "OP_EW_MUL", {}),
          ("down_proj_0", "OP_LINEAR", {}),
          ("attn_1", "OP_MULTIHEAD_ATTENTION",
           dict(DIFF, sliding_window=512)),
          ("attn_3", "OP_MULTIHEAD_ATTENTION", dict(DIFF, kv_out=True)),
          ("gmu_in_4", "OP_LINEAR", {}), ("gmu_sigmoid_4", "OP_SIGMOID", {}),
          ("gmu_silu_4", "OP_EW_MUL", {}), ("gmu_gate_4", "OP_EW_MUL", {}),
          ("gmu_out_4", "OP_LINEAR", {}), ("lm_head", "OP_LINEAR", {})]
CALL = [("s32", [1, 1]), ("bf16", [20, 8192, 64]), ("bf16", [10, 8192, 64]),
        ("bf16", [10, 8192, 128])]


def _model(layers):
    return types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, params=p,
                              op_type=types.SimpleNamespace(name=k))
        for n, k, p in layers])


def _hand_ctx(ops=OPS, layers=LAYERS):
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 1000 * US]], "spans": []}
    instr = {n: {"op_name": op, "mosaic": n.startswith("flash"),
                 "operands": CALL if n.startswith("flash") else [],
                 "results": [("bf16", [20, 8192, 128])]
                 if n.startswith("flash") else []}
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
    ("phi4flash_ssm_time_share.train", 100.0 * 400 / 900),
    ("phi4flash_ssm_scan_time_share.train", 100.0 * 300 / 900),
    ("phi4flash_diff_attn_time_share.train", 100.0 * 150 / 900),
    ("phi4flash_gmu_time_share.train", 100.0 * 50 / 900),
    ("phi4flash_mlp_time_share.train", 100.0 * 250 / 900)])
def test_time_shares_by_hand(metric, want):
    """The mixer's ops forward and recomputed (400 of 900 us busy), of
    them the loop's own 100, its body's 100 and the recomputed
    exponential's 100 under ``ssm1.scan``; three attention ops; the gated
    unit's two (not the SwiGLU's); the SwiGLU's two products and the
    unnamed multiply between them (not the gated unit's)."""
    ctx = _hand_ctx()
    assert ctx.span_reduced["busy_ns"] == 900 * US
    assert _read(metric, ctx) == pytest.approx(want)


@pytest.mark.parametrize("kernel", ["fwd", "bwd_dq", "bwd_dkv"])
def test_a_roofline_counts_each_call_by_its_layers_window(kernel):
    """Two calls of one kernel, the window layer's over the band and the
    whole layer's over the triangle, against their summed time."""
    name = f"flash_attention_{kernel}"
    ops = [(n.replace("fwd", kernel), s, d, op.replace("fwd", kernel))
           for n, s, d, op in OPS]
    ctx = _hand_ctx(ops)
    flops = cells.load_module(BENCH, "flops", CONFIG)
    results = [("bf16", [20, 8192, 128])]
    least = sum(flops.flash_roofline_s(name, CALL, results, w, ctx.peak)[0]
                for w in (512, 0))
    got = _read(f"phi4flash_flash_{kernel}_roofline", ctx)
    assert got == pytest.approx(100.0 * least / 100e-6)
    # a call of a layer that is not differential shares the kernel's
    # time: nothing can be said
    plain = [(n, k, {"num_heads": 8, "causal": True} if n == "attn_3" else p)
             for n, k, p in LAYERS]
    assert _read(f"phi4flash_flash_{kernel}_roofline",
                 _hand_ctx(ops, plain)) is None


def test_the_counters_quotients_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"ssm1.log_decay_min": -2 * 64 * 1.5, "ssm1.scans": 128.0,
                    "attn.window_pairs": 64 * 4063488.0,
                    "attn.causal_pairs": 64 * 33558528.0}
    assert _read("phi4flash_ssm_min_step_log_decay", ctx) \
        == pytest.approx(-1.5)
    assert _read("swa_kept_share", ctx) \
        == pytest.approx(0.121087, abs=1e-6)
    ctx.counters = {"ssm1.log_decay_min": -1.0, "ssm1.scans": 0.0}
    assert _read("phi4flash_ssm_min_step_log_decay", ctx) is None
    assert _read("swa_kept_share", ctx) is None


@pytest.mark.parametrize("metric", sorted(PR61))
def test_every_new_reader_reads_nothing_from_the_parent(metric):
    """The parent of PR 61 has no selective-scan mixer, no differential
    layer, no gated unit and no ``ssm1.*`` counter, and a run without
    ``--trace 1`` has no trace and no counters: nothing to read, and no
    error. A model of the parent's (cell 9's mixer and attention beside
    a dense layer) is not read as this model's."""
    granite = [("mamba_0", "OP_STATE_SPACE_MIXER", {"num_heads": 64}),
               ("attn_5", "OP_MULTIHEAD_ATTENTION",
                {"num_heads": 32, "num_kv_heads": 8, "causal": True,
                 "sm_scale": 0.015625}),
               ("gate_proj_0", "OP_LINEAR", {}),
               ("op_ew_mul_3", "OP_EW_MUL", {})]
    ops = [("fusion.1", 1000, 100, FWD + "mamba_0/ssm.scan/mul"),
           ("flash_attention_fwd.1", 1100, 100,
            FWD + "attn_5/attn.kernels/flash_attention_fwd/pallas_call"),
           ("fusion.2", 1200, 200, FWD + "gate_proj_0/dot_general")]
    ctx = _hand_ctx(ops, granite)
    ctx.counters = {"ssm.layers": 9.0, "ssm.min_chunk_log_decay": -9.0}
    assert _read(metric, ctx) is None
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        model=_model(LAYERS),
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH))
    assert _read(metric, bare) is None


@pytest.mark.parametrize("metric", sorted(PR61))
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
    with open(os.path.join(BENCH, "reference", "sambay_ref.py")) as f:
        text = f.read()
    assert "flexflow_tpu" not in text and "import flexflow" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "jax.lax.scan(step" in text          # token by token
    assert "chunk" not in text.split('"""', 2)[2]   # and in no chunks
    assert "tie_word_embeddings" in text and "A_log" in text
    mod = cells.load_module(BENCH, "reference", "sambay_ref")
    assert callable(mod.sambay_decoder) and callable(mod.loss) \
        and callable(mod.loss_and_gradients)
    assert mod.lambda_init(15) == pytest.approx(0.79333, abs=1e-5)
    assert mod.lambda_init(17) == pytest.approx(0.79634, abs=1e-5)
    assert mod.lambda_init(19) == pytest.approx(0.79799, abs=1e-5)
