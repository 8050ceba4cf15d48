"""``granite_4_0_h_micro.train.1chip`` (PR 55): the files resolve, the
manifest's new entries are there BY NAME (nothing here pins a list's
tail: a later PR appends after them), the configuration's file holds the
catalog row except for the cut, the parameter and operation counts are
the model's, and the five readers read a hand-made trace, the recorded
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
CONFIG = "granite_4_0_h_micro"
CELL = "granite_4_0_h_micro.train.1chip"
OLDER_CELLS = {
    "bert_large.train.1chip", "gpt2_124m.train.1chip",
    "joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
    "kimi_linear_48b_a3b.train.1chip", "xing4_29b_a4b.train.1chip",
    "keye_vl2_30b_a3b.train.1chip", "trinity_mini.train.1chip"}
US = 1000
PR55 = {        # name -> (unit, better, source, layer)
    "ssm_time_share.train": ("%", "lower", "device_trace",
                                     "state_space"),
    "ssm_scan_time_share.train": ("%", "lower", "device_trace",
                                          "state_space"),
    "scaled_attn_time_share.train": ("%", "lower", "device_trace",
                                      "attention"),
    "granite_mlp_time_share.train": ("%", "lower", "device_trace",
                                     "feed_forward"),
    "ssm_min_chunk_log_decay": ("nats", "higher",
                                        "program_counter", "state_space"),
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
@pytest.mark.parametrize("name", sorted(PR55))
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    unit, better, source, layer = PR55[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "train_tokens_per_s",
        "workloads": _holding(CELL, by_name[name]["workloads"])}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    """After the eight cells and configurations of the parent and
    its metrics (no count is held, and this PR's entries that other
    cells' readers share stand where the first of them stood); what
    comes after this PR's is not this test's to say."""
    order = [m["name"] for m in manifest["per_layer"]]
    own = [n for n in PR55 if n.startswith("granite_")]
    assert order.index("weights_and_optimizer_gib") \
        < min(order.index(n) for n in own)
    assert [n for n in order if n in own] == own
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("trinity_mini") < configs.index(CONFIG)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"]
               if w["name"] in OLDER_CELLS or w["name"] == CELL)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert CELL in perf and "ssm_min_chunk_log_decay" in perf
    for layer in ("state_space", "feed_forward"):
        assert layer in perf


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry["chips"] == 1 and entry["config"] != CONFIG
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert not reported & {n for n in PR55 if n.startswith("granite_")}


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(PR55)
    assert not [m["name"] for m in cell.per_layer
                if m["name"].startswith("granite_")
                and m["name"] not in PR55]
    assert cell.chips == 1
    # the file that was there, as it is: one sequence of 4096 tokens
    assert cell.traffic_name == "train_b1_s4096"
    assert cell.traffic["seq"] == 4096 == 16 * cell.config["mamba_chunk_size"]
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"] == 1


# ----------------------------------------------------------------------
# the configuration's file
# ----------------------------------------------------------------------
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
CUT = {"num_hidden_layers": 10, "vocab_size": 12544, "layer_types": PERIOD}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        manifest, cell):
    conf = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] \
        == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/" \
           "main/config.json"
    assert entry["file"] == "benchmarks/configs/granite_4_0_h_micro.json"
    differs = [k for k, v in CATALOG.items() if conf[k] != v]
    assert sorted(differs) == sorted(CUT) == sorted(entry["reduced"]) \
        == sorted(conf["reduced"]) == sorted(conf["reduced_why"])
    for key, value in CUT.items():
        assert conf[key] == value
    # the published values of the cut keys are in the file too
    assert conf["num_hidden_layers_published"] == 40
    assert conf["layer_types_published"] == CATALOG["layer_types"]
    assert conf["layer_types_published"][:10] == conf["layer_types"]
    assert [i for i, k in enumerate(conf["layer_types_published"])
            if k == "attention"] == [5, 15, 25, 35]
    assert conf["vocab_size_published"] == 100352 == 8 * conf["vocab_size"]
    # no width is cut, and none may be
    widths = ("hidden_size", "intermediate_size",
              "shared_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_n_groups", "mamba_expand",
              "mamba_d_conv", "mamba_chunk_size", "num_experts_per_tok")
    assert not set(widths) & set(conf["reduced"])
    assert set(conf["departures"]) == {"untied_head",
                                       "mlp_input_in_two_halves"}
    for form in ("A_log", "dt_bias", "D", "conv_taps", "matrices",
                 "gated_norm", "head_dim", "dropout", "training_loss"):
        assert form in conf["assumed"], form
    assert "four-stage pipeline" in conf["deployment"].replace(
        "four-stage pipelined", "four-stage pipeline")
    assert "eight slices" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < math.log(conf["vocab_size"]) < hi
    assert "PLACEHOLDER" not in json.dumps(conf)


def test_the_parameter_count_is_the_built_models(cell):
    """``parameters_here`` against the op's own weight lists at the
    published widths (shapes alone: nothing is allocated), and against
    ISSUE 55's table."""
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
    assert counts["mamba_0"] == here["mamba_mixer"] == 25847232 \
        == 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    mlp = sum(counts[n] for n in ("gate_proj_0", "up_proj_0",
                                  "down_proj_0"))
    assert mlp == here["mlp"] == 50331648
    assert counts["operator_norm_0"] + counts["ffn_norm_0"] \
        == here["two_norms"]
    assert counts["mamba_0"] + mlp + here["two_norms"] \
        == here["mamba_layer"] == 76182976
    assert counts["attn_5"] == here["attention"] == 10485760
    assert counts["attn_5"] + mlp + here["two_norms"] \
        == here["attention_layer"] == 60821504
    assert counts["embed_tokens"] == here["embedding"] == here["head"] \
        == counts["lm_head"]
    assert sum(counts.values()) == here["total"] == 797850560 \
        == 9 * here["mamba_layer"] + here["attention_layer"] \
        + 2 * here["embedding"] + here["final_norm"]
    # no node of the scalars holds a parameter
    assert all(counts[n] == 0 for n in counts if n.endswith(
        ("_multiplier", "_scaling")) or "_scale_" in n)


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", CONFIG)
    h, seq = 2048, 4096
    mamba = 2 * (h * 8512 + 4096 * h) + 64 * (5 * 64 * 128 + 2 * 64)
    attention = 2 * (2 * h * 32 * 64 + 2 * h * 8 * 64) \
        + 2 * seq * 32 * 2 * 64
    mlp = 2 * 3 * h * 8192
    want = 9 * mamba + attention + 10 * mlp + 2 * h * 12544
    assert flops.forward_flops_per_token(cell.config, seq) == want
    assert flops.train_flops_per_token(cell.config, seq) == 3 * want
    # ISSUE 55's round number: about 19 TFLOP a step
    assert 18e12 < 3 * want * seq < 20e12
    # the recurrence is by its recurrent form: no chunk size in it
    other = dict(cell.config, mamba_chunk_size=64)
    assert flops.forward_flops_per_token(other, seq) == want
    # only attention grows with the sequence
    assert flops.forward_flops_per_token(cell.config, 2 * seq) - want \
        == 2 * seq * 32 * 2 * 64


# ----------------------------------------------------------------------
# the readers, on a hand-made trace
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/remat.block/checkpoint/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/remat.block/checkpoint/rematted_computation/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 60, FWD + "mamba_0/remat.ssm.layer/checkpoint/"
                                 "bte,ec->btc/dot_general"),
    ("while.1", 1060, 200, FWD + "mamba_0/remat.ssm.layer/checkpoint/"
                                 "ssm.scan/while"),
    ("fusion.2", 1070, 100, FWD + "mamba_0/remat.ssm.layer/checkpoint/"
                                  "ssm.scan/while/body/remat.ssm.chunk/"
                                  "checkpoint/bhij,bjhp->bihp/dot_general"),
    ("fusion.3", 1260, 40, FWD + "mamba_0/remat.ssm.layer/checkpoint/"
                                 "btc,ce->bte/dot_general"),
    ("fusion.4", 1300, 100, FWD + "gate_proj_0/dot_general"),
    ("fusion.5", 1400, 50, FWD + "op_ew_mul_11/mul"),
    ("fusion.6", 1450, 100, FWD + "down_proj_0/dot_general"),
    ("flash_attention_fwd.1", 1550, 50,
     FWD + "attn_5/attn.kernels/flash_attention_fwd/pallas_call"),
    ("fusion.7", 1600, 150, BWD + "mamba_0/remat.ssm.layer/checkpoint/"
                                  "rematted_computation/ssm.scan/cumsum"),
    ("fusion.8", 1750, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
LAYERS = [("mamba_0", "OP_STATE_SPACE_MIXER",
           {"num_heads": 64, "head_dim": 64, "state": 128}),
          ("gate_proj_0", "OP_LINEAR", {}), ("up_proj_0", "OP_LINEAR", {}),
          ("op_sigmoid_9", "OP_SIGMOID", {}), ("silu_0", "OP_EW_MUL", {}),
          ("op_ew_mul_11", "OP_EW_MUL", {}),
          ("down_proj_0", "OP_LINEAR", {}),
          ("attn_5", "OP_MULTIHEAD_ATTENTION",
           {"num_heads": 32, "num_kv_heads": 8, "causal": True,
            "sm_scale": 0.015625}),
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
    instr = {n: {"op_name": op, "mosaic": n.startswith("flash"),
                 "operands": [], "results": []} for n, _, _, op in ops}
    names = {n for n, _, _ in layers}
    by_op = scope_reduce.op_self_ns(events, instr, names)
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=_model(layers),
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        name_by_op=by_op, peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH))


@pytest.mark.parametrize("metric,want", [
    ("ssm_time_share.train", 100.0 * 450 / 800),
    ("ssm_scan_time_share.train", 100.0 * 350 / 800),
    ("scaled_attn_time_share.train", 100.0 * 50 / 800),
    ("granite_mlp_time_share.train", 100.0 * 250 / 800)])
def test_time_shares_by_hand(metric, want):
    """The mixer's ops in the forward pass and under the block's and the
    layer's rematerialisation (450 of 800 us busy), of them the loop's
    own 100, its body's 100 and the recomputed running sum's 150 under
    ``ssm.scan``; the SwiGLU's two products and the multiply between
    them; the attention layer's kernel."""
    ctx = _hand_ctx()
    assert ctx.span_reduced["busy_ns"] == 800 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_counters_quotient_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"ssm.min_chunk_log_decay": -9 * 64 * 2800.0,
                    "ssm.layers": 9 * 64.0}
    assert _read("ssm_min_chunk_log_decay", ctx) \
        == pytest.approx(-2800.0)
    ctx.counters = {"ssm.min_chunk_log_decay": -1.0, "ssm.layers": 0.0}
    assert _read("ssm_min_chunk_log_decay", ctx) is None


@pytest.mark.parametrize("metric", sorted(PR55))
def test_every_new_reader_reads_nothing_from_the_parent(metric):
    """The parent of PR 55 has no state-space mixer, no attention layer
    with a scale of its own and no ``ssm.*`` counter, and a run without
    ``--trace 1`` has no trace and no counters: nothing to read, and no
    error. A model of the parent's (cell 4's attention beside a dense
    layer) is not read as this model's feed-forward."""
    lfm2 = [("attn_1", "OP_MULTIHEAD_ATTENTION",
             {"num_heads": 32, "num_kv_heads": 8, "causal": True}),
            ("gate_proj_0", "OP_LINEAR", {}),
            ("op_ew_mul_3", "OP_EW_MUL", {})]
    ops = [("fusion.1", 1000, 100, FWD + "attn_1/mul"),
           ("fusion.2", 1100, 200, FWD + "gate_proj_0/dot_general")]
    assert _read(metric, _hand_ctx(ops, lfm2)) is None
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        model=_model(LAYERS),
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH))
    assert _read(metric, bare) is None


@pytest.mark.parametrize("metric", sorted(PR55))
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
    with open(os.path.join(BENCH, "reference", "ssm_hybrid_ref.py")) as f:
        text = f.read()
    assert "flexflow_tpu" not in text and "import flexflow" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "jax.lax.scan(step" in text          # token by token
    assert "tie_word_embeddings" in text and "A_log" in text
    mod = cells.load_module(BENCH, "reference", "ssm_hybrid_ref")
    assert callable(mod.ssm_hybrid_decoder) and callable(mod.loss) \
        and callable(mod.loss_and_gradients)
