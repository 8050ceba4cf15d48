"""What PR 29 adds to the benchmark, on the CPU: the configuration
``joyai_llm_flash`` and its cell's files, the two operation counts
against hand counts, the plain reference's refusals, and each new reader
on a trace or a set of counters small enough to count by hand
(``benchmarks/harness/scope_reduce.py``, eight files of
``benchmarks/layer_metrics/``).

The hand-made trace, in microseconds (one device, one group 1000-2000):

  fusion.1            1000-1100   forward, attn_1
  flash_attention_fwd.1 1100-1300 forward, attn_1
  fusion.2            1300-1350   forward, experts_1   (its gathers)
  ragged-dot-none.1   1350-1450   no op_name of its own: after experts_1
  fusion.3            1450-1500   forward, attn_mtp
  ragged-dot-none.2   1500-1530   after attn_mtp: not an expert layer's
  fusion.4            1530-1600   backward, remat, attn_1 recomputed
  flash_attention_bwd_dq.1 1600-1800  backward, remat, attn_1
  fusion.5            1800-1850   backward, remat, experts_1
  fusion.6            1850-1900   optimizer
  (idle 1900-2000)

busy 900; attn_1 100 + 200 + 70 + 200 = 570; attn_mtp 50 (+ 30 that
follow it); experts_1 50 + 100 + 50 = 200.
"""
import dataclasses
import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells, peaks, scope_reduce  # noqa: E402
from benchmarks.harness import span_reduce, trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
CELL = "joyai_llm_flash.train.1chip"
US = 1000
PR25 = ["fwd_time_share.train", "bwd_time_share.train",
        "opt_time_share.train", "flash_fwd_roofline",
        "flash_bwd_dq_roofline", "flash_bwd_dkv_roofline",
        "idle_attributed_share.train", "dispatch_ms_per_step.train",
        "loader_wait_ms_per_step.train"]
PR29 = ["mla_time_share.train", "moe_time_share.train",
        "mtp_time_share.train", "mla_flash_fwd_roofline",
        "mla_flash_bwd_dq_roofline", "mla_flash_bwd_dkv_roofline",
        "moe_dropped_assignments", "moe_load_max_over_mean"]


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


@pytest.fixture(scope="module")
def cell():
    return cells.resolve_cell(ROOT, CELL)


# ----------------------------------------------------------------------
# the manifest and the configuration's file
# ----------------------------------------------------------------------
def test_the_manifest_holds_pr29s_eight_by_name_and_moves_no_entry():
    """By name: PR 29's eight entries after PR 24's seven (no
    ``workloads`` list) and PR 25's nine, each listing this cell, with
    its reader."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    names = [m["name"] for m in man["per_layer"]]
    by_name = {m["name"]: m for m in man["per_layer"]}
    assert len(by_name) == len(names)
    assert not any("workloads" in m for m in man["per_layer"][:7])
    assert [n for n in names if n in PR25 + PR29] == PR25 + PR29
    for name in PR29:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_tokens_per_s"
        assert callable(cells.load_module(
            BENCH, "layer_metrics", cells.metric_file(name)).read)
    configs = [c["name"] for c in man["configs"]]
    assert configs.index("gpt2_124m") < configs.index("joyai_llm_flash")
    cell_names = [w["name"] for w in man["workloads"]]
    assert cell_names.index("gpt2_124m.train.1chip") \
        < cell_names.index(CELL)
    assert man["workloads"][cell_names.index(CELL)]["chips"] == 1


def test_pr25s_nine_metrics_stand_as_they_were():
    """PR 25's nine entries BY NAME, in the order they came in, before
    this cell's: what ``test_benchmark_span_reduce.py``'s manifest test
    holds, seen from the cell that was added after them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by_name = {m["name"]: m for m in man["per_layer"]}
    names = [m["name"] for m in man["per_layer"]]
    assert [n for n in names if n in PR25] == PR25
    assert max(names.index(n) for n in PR25) \
        < min(names.index(n) for n in PR29)
    both = ["bert_large.train.1chip", "gpt2_124m.train.1chip"]
    for name in PR25:
        m = by_name[name]
        assert m["moves"] == "train_tokens_per_s"
        for cell in both[1:] if "roofline" in name else both:
            assert "workloads" not in m or cell in m["workloads"], name
        assert m["source"] == ("program_span" if name in PR25[6:]
                               else "device_trace")
        assert callable(cells.load_module(
            BENCH, "layer_metrics", cells.metric_file(name)).read)
    assert {by_name[n]["layer"] for n in PR25} == {
        "executor", "kernels", "device", "loader"}


def test_the_cell_reports_the_shared_metrics_and_its_own_by_name(cell):
    """By name, and open to what later PRs list the cell under."""
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    mine = [m["name"] for m in cell.per_layer]
    assert [n for n in mine if n in PR29] == PR29
    assert set(mine) >= {
        "compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
        "mosaic_calls_per_step", "kernel_time_share.train",
        "device_idle_share.train"}
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.traffic["per_chip_batch"] * cell.traffic["seq"] == 4096
    # the runner compares one sequence: the batch has no second
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]


CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(cell):
    conf = cell.config
    differs = {k for k, v in CATALOG.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    # the published value stands beside each held one
    assert conf["n_routed_experts_published"] == 256
    assert conf["num_hidden_layers_published"] == 40
    assert conf["vocab_size_published"] == 129280
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    # the guide's floors
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["vocab_size_published"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "joyai_llm_flash")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    assert {"mtp_module", "mtp_loss_weight", "router_bias",
            "dropout"} <= set(conf["assumed"])
    assert "16 chips share each layer" in conf["deployment"]


def test_the_parameter_count_is_the_built_models(cell):
    """680,441,088, part by part, from the weight specs of the model the
    builder makes at the file's sizes (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    model_cfg = cls(**{f.name: conf[f.name]
                       for f in dataclasses.fields(cls) if f.name in conf})
    ff = FFModel(FFConfig())
    cells.load_attr(conf["builder"])(ff, 1, 4096, model_cfg)

    def count(pick):
        return sum(int(np.prod(w.shape)) for l in ff.layers
                   for w in l.weights if pick(l.name))
    want = conf["parameters_here"]
    per_layer = {"input_norm_1", "attn_1", "post_norm_1"}
    assert count(lambda n: n in per_layer) == \
        want["attention_and_two_norms_per_layer"] == 26351616
    assert count(lambda n: n.endswith("_0")) == want["dense_layer"]
    assert count(lambda n: n.endswith("_3")) == want["expert_layer"]
    assert count(lambda n: "mtp" in n.split("_")) == want["mtp_module"]
    assert count(lambda n: n in ("embed_tokens", "lm_head")) == \
        want["embedding_and_head"]
    assert count(lambda n: True) == want["total"] == 680441088
    assert len(ff.layers) < 100          # one node an expert layer
    held = next(l for l in ff.layers if l.name == "experts_1")
    shapes = {w.name: w.shape for w in held.weights}
    assert shapes["wg"] == (2048, 256) and shapes["bias"] == (256,)
    assert shapes["w_gate"] == shapes["w_up"] == (16, 2048, 768)
    assert shapes["w_down"] == (16, 768, 2048)


# ----------------------------------------------------------------------
# the operation counts, by hand
# ----------------------------------------------------------------------
def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", "joyai_llm_flash")
    # latent attention, matrices a token meets: wq_a 2048 x 1536 =
    # 3,145,728; wq_b 1536 x 32 x 192 = 9,437,184; wkv_a 2048 x 576 =
    # 1,179,648; wkv_b 512 x 32 x 256 = 4,194,304; wo 4096 x 2048 =
    # 8,388,608: 26,345,472. Products at s = 4096: 2 x 4096 x 32 x
    # (192 + 128) = 83,886,080 operations.
    attn = 2 * 26345472 + 83886080
    dense = 2 * 3 * 2048 * 7168
    # router 2048 x 256 = 524,288; shared and routed experts 3 x 2048 x
    # 768 = 4,718,592 each, a token meeting 8 x 16 / 256 = 0.5 routed
    expert = 2 * (524288 + 4718592 * 1.5)
    head = 2 * 2048 * 16160
    w_eh = 2 * 4096 * 2048
    want = 6 * attn + dense + 5 * expert + w_eh + 2 * head
    got = flops.forward_flops_per_token(cell.config, 4096)
    assert got == want == 1132724224.0
    assert flops.train_flops_per_token(cell.config, 4096) == 3 * want
    # uncut, the same functions count the published model: 256 held
    whole = dict(cell.config, n_routed_experts=256, num_hidden_layers=40,
                 vocab_size=129280)
    routed_whole = 2 * 4718592 * 8
    assert flops.forward_flops_per_token(whole, 4096) == (
        41 * attn + dense + 40 * (2 * (524288 + 4718592) + routed_whole)
        + w_eh + 2 * 2 * 2048 * 129280)


LATENT_OPERANDS = [("s32", (1, 1)), ("bf16", (32, 4096, 192)),
                   ("bf16", (32, 4096, 192)), ("bf16", (32, 4096, 128))]
BWD_OPERANDS = LATENT_OPERANDS + [("bf16", (32, 4096, 128)),
                                  ("f32", (32, 4096, 128)),
                                  ("f32", (32, 4096, 128))]


@pytest.mark.parametrize("kernel,operands,results,gflop,mbytes", [
    # pairs 4096 x 4097 / 2 = 8,390,656; x 2 x 32 = 537,001,984
    ("flash_attention_fwd", LATENT_OPERANDS,
     [("bf16", (32, 4096, 128)), ("f32", (32, 4096, 128))],
     537001984 * (192 + 128), 4 + 2 * 50331648 + 2 * 33554432 + 524288),
    ("flash_attention_bwd_dq", BWD_OPERANDS, [("bf16", (32, 4096, 192))],
     537001984 * (2 * 192 + 128),
     4 + 3 * 50331648 + 2 * 33554432 + 2 * 524288),
    ("flash_attention_bwd_dkv", BWD_OPERANDS,
     [("bf16", (32, 4096, 192)), ("bf16", (32, 4096, 128))],
     537001984 * (2 * 192 + 2 * 128),
     4 + 3 * 50331648 + 3 * 33554432 + 2 * 524288)])
def test_unequal_heads_kernel_costs_by_hand(kernel, operands, results,
                                            gflop, mbytes):
    cost = cells.load_module(BENCH, "flops", "mla_attention")
    assert cost.operations(kernel, operands, True) == gflop
    assert cost.bytes_moved(kernel, operands, results) == mbytes
    peak = peaks.lookup("TPU v5 lite")
    seconds, bound = cost.roofline_s(kernel, operands, results, True, peak)
    assert bound == "operations" and seconds == gflop / 197e12
    # the full square without the mask, and equal sizes give the
    # one-size file's count
    assert cost.operations(kernel, operands, False) > 1.99 * gflop
    same = [operands[0]] + [("bf16", (144, 1024, 64))] * 3 + operands[4:]
    one = cells.load_module(BENCH, "flops", "flash_attention")
    assert cost.operations(kernel, same, True) == \
        one.operations(kernel, same, True)


def test_the_reference_refuses_a_graph_it_does_not_know():
    ref = cells.load_module(BENCH, "reference", "latent_moe_ref")
    sizes = {"hidden_size": 4, "vocab_size": 8, "num_hidden_layers": 1,
             "first_k_dense_replace": 1, "rms_norm_eps": 1e-6}
    ids = np.zeros((1, 4), np.int32)
    with pytest.raises(ref.ReferenceMismatch, match="expects"):
        ref.latent_moe_decoder(
            [("embed", {"kernel": np.zeros((8, 4), "f")}),
             ("norm", {"scale": np.ones(4, "f")}),
             ("attn", {"wq": np.zeros((4, 4), "f")})], sizes, ids, ids)
    with pytest.raises(ref.ReferenceMismatch, match=r"\(9, 4\)"):
        ref.latent_moe_decoder(
            [("embed", {"kernel": np.zeros((9, 4), "f")})], sizes, ids, ids)


# ----------------------------------------------------------------------
# the readers, on a trace counted by hand
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 100, FWD + "attn_1/bse,er->bsr/dot_general"),
    ("flash_attention_fwd.1", 1100, 200,
     FWD + "attn_1/flash_attention_fwd/pallas_call"),
    ("fusion.2", 1300, 50, FWD + "experts_1/gather"),
    ("ragged-dot-none.1", 1350, 100, "ragged-dot-none"),
    ("fusion.3", 1450, 50, FWD + "attn_mtp/mul"),
    ("ragged-dot-none.2", 1500, 30, "ragged-dot-none"),
    ("fusion.4", 1530, 70, BWD + "rematted_computation/attn_1/mul"),
    ("flash_attention_bwd_dq.1", 1600, 200,
     BWD + "attn_1/flash_attention_bwd_dq/pallas_call"),
    ("fusion.5", 1800, 50, BWD + "experts_1/dot_general"),
    ("fusion.6", 1850, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
LAYERS = [("attn_1", "OP_LATENT_ATTENTION"),
          ("experts_1", "OP_ROUTED_EXPERTS"),
          ("attn_mtp", "OP_LATENT_ATTENTION"),
          ("mtp_eh_proj", "OP_LINEAR"), ("lm_head", "OP_LINEAR")]
KERNEL_SHAPES = {
    "flash_attention_fwd.1": (LATENT_OPERANDS, [
        ("bf16", (32, 4096, 128)), ("f32", (32, 4096, 128))]),
    "flash_attention_bwd_dq.1": (BWD_OPERANDS, [
        ("bf16", (32, 4096, 192))])}


def _hand_ctx(ops=OPS, layers=LAYERS):
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 1000 * US]], "spans": []}
    instr = {n: {"op_name": op, "mosaic": n in KERNEL_SHAPES
                 or n.startswith("ragged"),
                 "operands": KERNEL_SHAPES.get(n, ([], []))[0],
                 "results": KERNEL_SHAPES.get(n, ([], []))[1]}
             for n, _, _, op in ops}
    model = types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, op_type=types.SimpleNamespace(name=k))
        for n, k in layers])
    names = {n for n, _ in layers}
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=model,
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH))


def test_a_layer_is_found_inside_a_rematerialised_block():
    assert scope_reduce.layer_of(OPS[6][3], {"attn_1"}) == "attn_1"
    assert scope_reduce.layer_of(OPS[7][3], {"attn_1"}) == "attn_1"
    assert scope_reduce.layer_of(OPS[9][3], {"attn_1"}) == ""
    # where span_reduce's finder gives JAX's own scope, by its design
    assert span_reduce.layer_of(OPS[6][3]) == "jvp(ff.forward)"
    ctx = _hand_ctx()
    assert ctx.scope_layer_ns == {
        "attn_1": 570 * US, "experts_1": 200 * US,
        "attn_mtp": 80 * US, "": 50 * US}
    assert ctx.span_reduced["busy_ns"] == 900 * US


@pytest.mark.parametrize("metric,want", [
    ("mla_time_share.train", 100.0 * (570 + 80) / 900),
    ("moe_time_share.train", 100.0 * 200 / 900),
    ("mtp_time_share.train", 100.0 * 80 / 900)])
def test_time_shares_by_hand(metric, want):
    assert _read(metric, _hand_ctx()) == pytest.approx(want)


def test_unequal_heads_rooflines_by_hand():
    ctx = _hand_ctx()
    # forward: 171.84 GFLOP over 197 TFLOP/s = 872.3 us of the 200 the
    # hand-made call took: the reader reports what it is given
    fwd = _read("mla_flash_fwd_roofline", ctx)
    assert fwd == pytest.approx(
        100.0 * (537001984 * 320 / 197e12) / 200e-6)
    dq = _read("mla_flash_bwd_dq_roofline", ctx)
    assert dq == pytest.approx(
        100.0 * (537001984 * 512 / 197e12) / 200e-6)
    assert _read("mla_flash_bwd_dkv_roofline", ctx) is None  # no call


def test_rooflines_read_nothing_where_another_layer_shares_the_kernel():
    """A kernel's time in the trace is summed over all its calls: if a
    layer of another kind called it too, the latent layers' share of
    that time cannot be told, and the reader says nothing."""
    ops = OPS + [("flash_attention_fwd.2", 1900, 50,
                  FWD + "other_attn/flash_attention_fwd/pallas_call")]
    shapes = dict(KERNEL_SHAPES)
    KERNEL_SHAPES["flash_attention_fwd.2"] = shapes["flash_attention_fwd.1"]
    try:
        ctx = _hand_ctx(ops, LAYERS + [("other_attn",
                                        "OP_MULTIHEAD_ATTENTION")])
    finally:
        del KERNEL_SHAPES["flash_attention_fwd.2"]
    assert _read("mla_flash_fwd_roofline", ctx) is None
    assert _read("mla_flash_bwd_dq_roofline", ctx) is not None


def test_counters_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"moe.dropped": 0.0, "moe.load_max": 1500.0,
                    "moe.load_mean": 1000.0, "moe.local_assignments": 16e3}
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("moe_load_max_over_mean", ctx) == 1.5


@pytest.mark.parametrize("metric", PR29)
def test_every_new_reader_reads_nothing_from_the_parent(metric, tmp_path):
    """The parent of PR 29 has no latent or expert layer, no ``moe.*``
    counter; and a run without ``--trace 1`` has no trace: nothing to
    read, and no error."""
    gpt2 = [("attn_1", "OP_MULTIHEAD_ATTENTION"), ("dense_1", "OP_LINEAR")]
    ctx = _hand_ctx(layers=gpt2)
    ctx.scope_layer_ns = scope_reduce.layer_self_ns(
        {"devices": {}, "marks": [], "spans": []}, {}, set()) or None
    assert _read(metric, ctx) is None
    cell = types.SimpleNamespace(root=str(tmp_path), name="x.train",
                                 bench_dir=BENCH)
    bare = types.SimpleNamespace(
        trace=None, cell=cell, step_text="", peak=None, counters={},
        model=types.SimpleNamespace(layers=[
            types.SimpleNamespace(name=n,
                                  op_type=types.SimpleNamespace(name=k))
            for n, k in LAYERS]))
    assert _read(metric, bare) is None


# ----------------------------------------------------------------------
# the cell, rehearsed at a tiny size through the runner
# ----------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=10000.0, intermediate_size=160,
            moe_intermediate_size=32, n_routed_experts=4,
            n_routed_experts_published=16, num_experts_per_tok=4,
            router_bias_std=0.05, initial_loss_band=[5.0, 7.0],
            reference_rel_tol=0.02)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "joyai_llm_flash.json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="joyai_tiny", flops="joyai_llm_flash")
    with open(os.path.join(root, "benchmarks", "configs",
                           "joyai_tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(BENCH, "traffic", "train_b1_s4096.json")) as f:
        traffic = json.load(f)
    traffic.update(seq=32, steps_per_group=3, optimizer={
        "class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": 1e-3}})
    traffic["ffconfig"]["only_data_parallel"] = True
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train_tiny_remat.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "joyai_tiny", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmarks/configs/joyai_tiny.json"})
    man["workloads"].append({"name": "joyai_tiny.train",
                             "config": "joyai_tiny",
                             "traffic": "train_tiny_remat", "chips": 8,
                             "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["joyai_tiny.train"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


@pytest.fixture
def no_profiler(monkeypatch):
    import jax
    with open(os.path.join(BENCH, "testdata", "trace_events.json")) as f:
        recorded = json.load(f)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "extract", lambda *a: dict(
        recorded["events"], spans=[]))


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_through_the_train_runner(tiny_root, no_profiler,
                                                trace):
    """The held share (4 of 16 experts), rematerialised blocks, the
    module's loss in the eval loss, the reference in the runner's own
    comparison: every check but ``device`` passes on the CPU mesh, and
    the traced run's counters reach their readers."""
    said = []
    res = bench_run.run_cell(tiny_root, "joyai_tiny.train", 2 ** 31 + 29,
                             0.3, bool(trace), say=said.append)
    checks = {s.split()[1].rstrip(":"): " ok - " in s
              for s in said if s.startswith("check ")}
    assert checks.pop("device") is False
    assert set(checks) == {"initial_loss", "reference", "finite_losses",
                           "no_compile_in_window", "loss_fell"}
    assert all(checks.values()), said
    assert res["failed"] == 0 and res["attempted"] >= 1
    if trace:
        assert res["metrics"]["moe_dropped_assignments"]["value"] == 0
        assert res["metrics"]["moe_load_max_over_mean"]["value"] >= 1
        assert res["metrics"]["in_window_compiles"]["value"] == 0
    else:
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
