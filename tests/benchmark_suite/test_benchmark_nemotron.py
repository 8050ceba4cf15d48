"""``nemotron3_super_120b_a12b.train.1chip`` (PR 66): the files resolve,
the manifest's new entries are there BY NAME (nothing here pins a list's
tail: a later PR appends after them), the configuration's file holds the
catalog row except for the cut, the parameter and operation counts are
the model's (773,582,304 here; 120.67 B at full depth with all 512
experts, every head and the whole vocabulary, 12.77 B a token), and the
readers the cell reports through read a hand-made trace, the recorded
test traces and a parent's program (nothing, without an error).

The driver's contract for ``BENCHMARK.json`` lets ``per_layer`` hold 128
entries and the list held 127: the cell brought ONE metric of its own
(``nemotron_moe_latent_time_share.train``) and edited no older entry. The
state-space, attention, expert and flash readers of cells 7, 8 and 9
read this cell's spans and counters too (held below on a hand-made
trace), and their entries list the cell, as do the two readers of
``moe.route`` and ``moe.shared``.
"""
import dataclasses
import json
import math
import os
import types

import pytest

from benchmarks.harness import cells, peaks, scope_reduce, span_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CONFIG = "nemotron3_super_120b_a12b"
CELL = "nemotron3_super_120b_a12b.train.1chip"
US = 1000
NEW = "nemotron_moe_latent_time_share.train"
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train", "recompute_time_share.train",
          "recompute_kernel_time_share.train",
          "recompute_again_time_share.train", "remat_held_gib",
          "weights_and_optimizer_gib"}
L = 4096


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


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
def test_the_new_metric_lists_the_cell(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    assert by_name[NEW] == {
        "name": NEW, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "experts",
        "moves": "train_tokens_per_s",
        "workloads": [CELL] + by_name[NEW]["workloads"][1:]}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(NEW)).read)
    order = [m["name"] for m in manifest["per_layer"]]
    assert order.index("moe_overflow_layer_steps") < order.index(NEW)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index("sdar_30b_a3b.train.1chip") < names.index(CELL)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("sdar_30b_a3b") < configs.index(CONFIG)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    entry = manifest["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "train_b1_s4096", 1)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert CELL in perf and NEW in perf


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | {NEW}
    assert cell.chips == 1 and cell.traffic_name == "train_b1_s4096"
    assert cell.traffic["seq"] == L
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"] == 1
    assert L % cell.config["chunk_size"] == 0


# ----------------------------------------------------------------------
# the configuration's file
# ----------------------------------------------------------------------
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
    "*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
CUT = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
       "mamba_num_heads": 32, "n_groups": 2, "num_attention_heads": 8,
       "num_key_value_heads": 1, "n_routed_experts": 8,
       "vocab_size": 16384}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        manifest, cell):
    conf = cell.config
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] \
        == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-" \
           "A12B-BF16/blob/main/config.json"
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    differs = [k for k, v in CATALOG.items() if conf[k] != v]
    assert sorted(differs) == sorted(CUT) == sorted(entry["reduced"]) \
        == sorted(conf["reduced"]) == sorted(conf["reduced_why"])
    for key, value in CUT.items():
        assert conf[key] == value
        assert conf[key + "_published"] == CATALOG[key]
    # the period is published layers 26 to 36, the first whole one of 11
    assert CATALOG["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    assert len(CATALOG["hybrid_override_pattern"]) == 88
    assert [CATALOG["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [40, 40, 8]
    # the share is whole groups and the heads that read the held kv head
    assert conf["mamba_num_heads"] * 4 == 128 and conf["n_groups"] * 4 == 8
    assert conf["mamba_num_heads"] // conf["n_groups"] == 128 // 8
    assert conf["num_attention_heads"] * 4 == 32
    assert conf["first_held_expert"] == 0 \
        and conf["num_experts_published"] == 512
    # no width is cut, and none may be
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_latent_size", "moe_shared_expert_intermediate_size",
              "head_dim", "mamba_head_dim", "ssm_state_size", "expand",
              "num_experts_per_tok")
    assert not set(widths) & set(conf["reduced"])
    assert set(conf["departures"]) == {
        "mtp_left_out", "untied_head", "head_share_without_its_all_reduce"}
    for form in ("latent_order", "relu2", "shared_expert", "router_bias",
                 "gates", "attention", "A_log", "dt_bias", "D",
                 "gated_norm", "expert_rows_factor", "dropout"):
        assert form in conf["assumed"], form
    for said in ("8 pipeline stages", "64 chips", "16 tensor-parallel "
                 "groups of 4", "176 tokens an expert"):
        assert said in conf["deployment"], said
    assert conf["reference"] == "nemotron_h_ref:nemotron_h_decoder"
    # ln 16384 and half the untied head's logit variance (0.2): a model
    # that knows nothing sits ABOVE ln(vocabulary)
    lo, hi = conf["initial_loss_band"]
    assert math.log(conf["vocab_size"]) < lo < 9.9 < hi <= 10.0
    assert 0 < conf["reference_rel_tol"] < 0.1
    assert "provisional" not in json.dumps(conf)


def test_the_files_fields_are_the_classs(cell):
    """Every field of the builder's class is in the file at the class's
    own value for this rank: the file IS the configuration that runs."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    default = cls()
    for f in dataclasses.fields(cls):
        assert f.name in conf, f.name
        assert conf[f.name] == getattr(default, f.name), f.name


def test_the_parameter_count_is_the_built_models(cell):
    """``parameters_here`` against the ops' own weight lists at the
    published widths (shapes alone: nothing is allocated), against ISSUE
    66's table, and the whole model by the same equations against the
    name's 120B-A12B."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
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
    assert counts["mamba_1"] == here["mamba_mixer"] == 27408992 \
        == 4096 * 4640 + 2560 * 5 + 3 * 32 + 2048 + 2048 * 4096
    assert counts["attn_10"] == here["attention"] == 9437184
    one = here["routed_expert"]
    assert one == 2 * 1024 * 2688 == 5505024
    assert counts["experts_0"] + 4096 == here["expert_layer"] == 98570752 \
        == here["expert_layer_outside_routed"] + 8 * one
    assert here["mamba_layer"] == counts["mamba_1"] + 4096 == 27413088
    assert here["attention_layer"] == counts["attn_10"] + 4096 == 9441280
    assert counts["embed_tokens"] == counts["lm_head"] == 67108864
    assert sum(counts.values()) == here["total"] == 773582304 \
        == 5 * here["mamba_layer"] + here["attention_layer"] \
        + 5 * here["expert_layer"] + 2 * 67108864 + 4096
    # 11.53 GiB at 16 bytes, 8.65 GiB of it arguments of the step
    assert 16 * here["total"] / 2 ** 30 == pytest.approx(11.528, abs=1e-3)
    assert 12 * here["total"] / 2 ** 30 == pytest.approx(8.646, abs=1e-3)
    # the whole model: "120B", and "A12B" a token
    flops = cells.load_module(BENCH, "flops", CONFIG)
    whole = flops.published_parameters(conf)
    assert whole["mamba_layer"] == 109640064
    assert whole["attention_layer"] == 35655680
    assert whole["expert_layer_outside_routed"] == 54530560
    assert whole["total"] == 120668707840 and whole["a_token"] == 12770237440
    assert "120,668,707,840" in here["total_why"] \
        and "12,770,237,440" in here["total_why"]
    # gated experts in the latent, or two-matrix experts at the stream's
    # width, would not be the name's count
    gated = whole["total"] + 40 * 512 * 1024 * 2688
    wide = whole["total"] + 40 * 512 * 2 * (4096 - 1024) * 2688
    assert round(gated / 1e9) == 177 and round(wide / 1e9) == 459


def test_model_flops_against_a_hand_count(cell):
    conf = cell.config
    flops = cells.load_module(BENCH, "flops", CONFIG)
    mamba = 2 * (4096 * 4640 + 2048 * 4096) + 32 * (5 * 64 * 128 + 2 * 64)
    attention = 2 * (2 * 4096 * 1024 + 2 * 4096 * 128) + 2 * L * 8 * 2 * 128
    experts = 2 * (4096 * 512 + 2 * 4096 * 1024
                   + 2 * 1024 * 2688 * 22 * 8 / 512 + 2 * 4096 * 5376)
    forward = 5 * mamba + attention + 5 * experts + 2 * 4096 * 16384
    assert flops.forward_flops_per_token(conf, L) == pytest.approx(forward)
    assert flops.train_flops_per_token(conf, L) == pytest.approx(3 * forward)
    # by the weights a token multiplies: the expert layers 57%, the
    # mixers 28% (ISSUE 66)
    matrices = (forward - 2 * L * 8 * 2 * 128
                - 5 * 32 * (5 * 64 * 128 + 2 * 64)) / 2
    assert 5 * experts / 2 / matrices == pytest.approx(0.57, abs=0.01)
    assert 5 * (4096 * 4640 + 2048 * 4096) / matrices \
        == pytest.approx(0.28, abs=0.01)


# ----------------------------------------------------------------------
# the readers, on a hand-made trace
# ----------------------------------------------------------------------
TOP = "jit(step_fn)/jit(main)/"
FWD = TOP + "jvp(ff.forward)/remat.block/checkpoint/"
BWD = TOP + "transpose(jvp(ff.forward))/jvp(ff.forward)/remat.block/" \
    "checkpoint/rematted_computation/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 40, FWD + "experts_0/moe.route/dot_general"),
    ("fusion.2", 1040, 60, FWD + "experts_0/moe.latent/dot_general"),
    ("ragged-dot-none.1", 1100, 50, ""),
    ("moe_token_sum.1", 1150, 30,
     BWD + "experts_0/moe.latent/moe_token_sum/pallas_call"),
    ("fusion.3", 1180, 120, FWD + "experts_0/moe.shared/dot_general"),
    ("fusion.4", 1300, 100, FWD + "mamba_1/remat.ssm.layer/checkpoint/"
     "dot_general"),
    ("state_space_fwd.1", 1400, 50,
     FWD + "mamba_1/remat.ssm.layer/checkpoint/ssm.scan/pallas_call"),
    ("state_space_bwd.1", 1450, 100,
     BWD + "mamba_1/remat.ssm.layer/checkpoint/ssm.scan/pallas_call"),
    ("fusion.5", 1550, 50, TOP + "jvp(ff.forward)/attn_10/dot_general"),
    ("flash_attention_fwd.1", 1600, 100,
     TOP + "jvp(ff.forward)/attn_10/attn.kernels/flash_attention_fwd/"
     "pallas_call"),
    ("fusion.6", 1700, 100, TOP + "ff.optimizer/mul"),
]
LATENT = {"num_experts": 512, "top_k": 22, "experts_held": 8,
          "latent": 1024, "activation": "relu2"}
LAYERS = [("embed_tokens", "OP_EMBEDDING", {}),
          ("ffn_norm_0", "OP_RMSNORM", {}),
          ("experts_0", "OP_ROUTED_EXPERTS", LATENT),
          ("ffn_res_0", "OP_EW_ADD", {}),
          ("mamba_1", "OP_STATE_SPACE_MIXER", {"groups": 2}),
          ("attn_10", "OP_MULTIHEAD_ATTENTION",
           {"num_heads": 8, "num_kv_heads": 1, "causal": True,
            "sm_scale": 128 ** -0.5}),
          ("lm_head", "OP_LINEAR", {})]
CALL = [("s32", [1, 1]), ("bf16", [8, L, 128]), ("bf16", [1, L, 128]),
        ("bf16", [1, L, 128])]
RESULTS = [("bf16", [8, L, 128]), ("f32", [8, L, 1])]


def _model(layers):
    return types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, params=p,
                              op_type=types.SimpleNamespace(name=k))
        for n, k, p in layers])


def _hand_ctx(ops=OPS, layers=LAYERS):
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 1000 * US]], "spans": []}
    instr = {n: {"op_name": op, "mosaic": "pallas_call" in op,
                 "operands": CALL if n.startswith("flash") else [],
                 "results": RESULTS if n.startswith("flash") else []}
             for n, _, _, op in ops}
    names = {n for n, _, _ in layers}
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=_model(layers),
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        name_by_op=scope_reduce.op_self_ns(events, instr, names),
        peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH))


@pytest.mark.parametrize("metric,want", [
    (NEW, 100.0 * 140 / 800),
    ("moe_time_share.train", 100.0 * 300 / 800),
    ("moe_route_time_share.train", 100.0 * 40 / 800),
    ("moe_shared_time_share.train", 100.0 * 120 / 800),
    ("ssm_time_share.train", 100.0 * 250 / 800),
    ("ssm_scan_time_share.train", 100.0 * 150 / 800),
    ("scaled_attn_time_share.train", 100.0 * 150 / 800)])
def test_time_shares_by_hand(metric, want):
    """The latent's projection, the unnamed grouped product after it and
    the token sum's backward call (60 + 50 + 30 = 140 of 800 us busy),
    not the router's 40 and the shared expert's 120, which the expert
    layer's own share holds too (300); the mixer with its two scan
    kernels (250, 150 of it under ``ssm.scan``); the attention layer
    with its kernel (150)."""
    ctx = _hand_ctx()
    assert ctx.span_reduced["busy_ns"] == 800 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_scopes_of_an_expert_layer_sum_to_no_more_than_the_layer():
    """``moe.route`` (40), ``moe.latent`` with the unnamed grouped
    product (140) and ``moe.shared`` (120) are parts of the expert
    layers' 300 us; a layer that opens neither scope (every program
    before PR 66) reads nothing for the two, and no error."""
    ctx = _hand_ctx()
    parts = [_read(m, ctx) for m in (
        "moe_route_time_share.train", NEW, "moe_shared_time_share.train")]
    assert sum(parts) == pytest.approx(_read("moe_time_share.train", ctx))
    older = [("experts_0", "OP_ROUTED_EXPERTS",
              {"num_experts": 128, "top_k": 8, "experts_held": 16}),
             ("lm_head", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 200, FWD + "experts_0/dot_general"),
           ("fusion.2", 1200, 100, TOP + "jvp(ff.forward)/lm_head/dot")]
    for metric in ("moe_route_time_share.train",
                   "moe_shared_time_share.train"):
        assert _read(metric, _hand_ctx(ops, older)) is None
        assert _read(metric, _hand_ctx(ops, older[1:])) is None


def test_the_flash_roofline_counts_the_triangle_at_the_kv_heads_own():
    """8 query heads reading ONE key/value head in place: the causal
    triangle's pairs a query head, K and V moved once at their own
    head."""
    ctx = _hand_ctx()
    cost = cells.load_module(BENCH, "flops", "window_attention")
    seconds, bound = cost.roofline_s("flash_attention_fwd", CALL, RESULTS,
                                     0, ctx.peak)
    assert bound == "operations" and seconds == pytest.approx(
        2 * 2 * 8 * (L * (L + 1) // 2) * 128 / ctx.peak["bf16_flops_per_s"])
    assert _read("window_flash_fwd_roofline", ctx) \
        == pytest.approx(100.0 * seconds / 100e-6)


def test_the_counters_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"moe.dropped": 0.0, "moe.overflow": 2.0,
                    "ssm.min_chunk_log_decay": -5.0 * 40 * 30.0,
                    "ssm.layers": 5.0 * 40}
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("moe_overflow_layer_steps", ctx) == 2.0
    assert _read("ssm_min_chunk_log_decay", ctx) \
        == pytest.approx(-30.0)


def test_the_new_reader_reads_nothing_from_the_parent():
    """The parent's expert layers have no latent and no ``moe.latent``
    scope, and a run without ``--trace 1`` has no trace: nothing to
    read, and no error."""
    older = [("embed_tokens", "OP_EMBEDDING", {}),
             ("experts_0", "OP_ROUTED_EXPERTS",
              {"num_experts": 128, "top_k": 8, "experts_held": 16}),
             ("lm_head", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 200, FWD + "experts_0/dot_general"),
           ("fusion.2", 1200, 100, TOP + "jvp(ff.forward)/lm_head/dot")]
    assert _read(NEW, _hand_ctx(ops, older)) is None
    # a latent layer of a program that opens no such scope
    assert _read(NEW, _hand_ctx(ops, [
        (n, k, LATENT if n == "experts_0" else p)
        for n, k, p in older])) is None
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        model=_model(LAYERS),
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH))
    assert _read(NEW, bare) is None


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "nemotron_h_ref.py")) as f:
        text = f.read()
    assert "flexflow_tpu" not in text and "import flexflow" not in text
    assert 'default_matmul_precision("highest")' in text
    code = text.split('"""', 2)[2]
    imports = [l for l in code.splitlines()
               if l.startswith(("import ", "from "))]
    assert sorted(imports) == ["from __future__ import annotations",
                               "import contextlib", "import jax",
                               "import jax.numpy as jnp"]
