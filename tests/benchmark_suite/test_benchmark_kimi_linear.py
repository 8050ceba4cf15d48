"""What PR 35 adds to the benchmark, on the CPU: the configuration
``kimi_linear_48b_a3b`` and its cell's files, the operation count against
a hand count, and each new reader on a trace small enough to count by
hand (``benchmarks/harness/name_reduce.py``, eight files of
``benchmarks/layer_metrics/``). And, by name, where PR 29's and PR
33's entries stand. Nothing here pins an entry to the tail of a list.

The hand-made trace, in microseconds (one device, one group 1000-2000).
The scan is a ``while`` op on the device whose span covers its body's
ops on the same line:

  fusion.1              1000-1100  forward, kda_2  (a projection)
  while.1               1100-1400  forward, kda_2, under kda.scan
    fusion.2              1110-1200  its body, under kda.scan  (twice:
    fusion.2              1210-1300  two trips of the loop)
  fusion.3              1400-1450  forward, kda_2, kda.scan  (the solve)
  fusion.4              1450-1500  forward, attn_3
  flash_attention_fwd.1 1500-1600  forward, attn_3
  fusion.5              1600-1650  forward, experts_3  (its gathers)
  ragged-dot-none.1     1650-1700  no op_name of its own: after experts_3
  fusion.6              1700-1760  backward, remat, kda_2, kda.scan
  fusion.7              1760-1800  optimizer
  (idle 1800-2000)

busy 800. kda_2: 100 + while's own 300 - 180 = 120, + its body 180, + 50
+ 60 = 510, of which under kda.scan 120 + 180 + 50 + 60 = 410; attn_3
50 + 100 = 150; experts_3 50 + 50 = 100.
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
CELL = "kimi_linear_48b_a3b.train.1chip"
CELL33 = "lfm2_24b_a2b.train.1chip"
CELL29 = "joyai_llm_flash.train.1chip"
US = 1000
PR29 = ["mla_time_share.train", "moe_time_share.train",
        "mtp_time_share.train", "mla_flash_fwd_roofline",
        "mla_flash_bwd_dq_roofline", "mla_flash_bwd_dkv_roofline",
        "moe_dropped_assignments", "moe_load_max_over_mean"]
PR33 = ["short_conv_time_share.train", "gqa_time_share.train",
        "moe_time_share.train", "gqa_flash_fwd_roofline",
        "gqa_flash_bwd_dq_roofline", "gqa_flash_bwd_dkv_roofline",
        "moe_dropped_assignments"]
PR35 = ["kda_time_share.train", "kda_scan_time_share.train",
        "mla_time_share.train", "moe_time_share.train",
        "mla_flash_fwd_roofline", "mla_flash_bwd_dq_roofline",
        "mla_flash_bwd_dkv_roofline", "moe_dropped_assignments"]
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"}


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
@pytest.mark.parametrize("names,its_cell", [
    (PR29, CELL29), (PR33, CELL33), (PR35, CELL)])
def test_each_prs_metrics_list_its_cell_and_have_a_reader(
        manifest, names, its_cell):
    """An entry lists every cell in which its reader finds a reading,
    its PR's own among them."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    for name in names:
        m = by_name[name]
        assert its_cell in m["workloads"]
        assert m["moves"] == "train_tokens_per_s"
        assert callable(cells.load_module(
            BENCH, "layer_metrics", cells.metric_file(name)).read)
    # in the order their PR gave them
    order = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in order if n in names] \
        == sorted(names, key=order.index)


def test_the_older_entries_stand_in_their_prs_order_by_name(manifest):
    """By name and open to later entries: the shared metrics list no
    cells, each PR's OWN entries come after the PR's before it, and the
    cells are what they were."""
    order = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert not any("workloads" in by_name[n] for n in SHARED)
    own33 = [n for n in PR33 if n not in PR29]
    own35 = [n for n in PR35 if n not in PR29 + PR33]
    assert own35 == ["kda_time_share.train", "kda_scan_time_share.train"]
    assert max(order.index(n) for n in SHARED) < order.index(PR29[0])
    assert max(order.index(n) for n in PR29) \
        < min(order.index(n) for n in own33)
    assert max(order.index(n) for n in own33) \
        < min(order.index(n) for n in own35)
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("joyai_llm_flash") < configs.index("lfm2_24b_a2b") \
        < configs.index("kimi_linear_48b_a3b")
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL29) < names.index(CELL33) < names.index(CELL)
    cells_ = {w["name"]: w for w in manifest["workloads"]}
    assert cells_[CELL29]["chips"] == cells_[CELL33]["chips"] \
        == cells_[CELL]["chips"] == 1
    assert cells_[CELL29]["config"] == "joyai_llm_flash"
    assert cells_[CELL29]["traffic"] == "train_b1_s4096"
    assert cells_[CELL33] == dict(
        cells_[CELL33], config="lfm2_24b_a2b", traffic="train_b1_s8192")
    assert cells_[CELL] == dict(
        cells_[CELL], config="kimi_linear_48b_a3b",
        traffic="train_b1_s4096")
    assert [by_name[n]["layer"] for n in PR33] == [
        "short_conv", "attention", "experts", "kernels", "kernels",
        "kernels", "experts"]
    assert [by_name[n]["layer"] for n in PR35] == [
        "linear_attention", "linear_attention", "attention", "experts",
        "kernels", "kernels", "kernels", "experts"]
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert "linear_attention" in f.read()


def test_the_cell_reports_the_shared_metrics_and_its_own_by_name(cell):
    """By name, and open to what later PRs list the cell under."""
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    mine = {m["name"] for m in cell.per_layer}
    assert mine >= SHARED | set(PR35)
    # no convolution, no grouped-query layer, no MTP module here
    assert not {"short_conv_time_share.train", "gqa_time_share.train",
                "mtp_time_share.train"} & mine
    assert cell.traffic["per_chip_batch"] == 1
    # the 14.5 GiB rule of ISSUE 35: 8192 tokens read over it
    assert cell.traffic["seq"] == 4096
    assert cell.traffic["steps_per_group"] == 8
    assert cell.traffic["optimizer"]["args"] == {"alpha": 1e-05}
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    # the runner compares one sequence: the batch has no second
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]
    # and the older cells still report what they reported
    for other, names in ((CELL29, PR29), (CELL33, PR33)):
        theirs = {m["name"] for m in cells.resolve_cell(ROOT,
                                                        other).per_layer}
        assert theirs >= SHARED | set(names)
        assert not {"kda_time_share.train",
                    "kda_scan_time_share.train"} & theirs


FULL = [4, 8, 12, 16, 20, 24, 27]
CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": FULL, "head_dim": 128,
        "kda_layers": [n for n in range(1, 28) if n not in FULL],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        cell, manifest):
    conf = cell.config
    differs = {k for k, v in CATALOG.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"}
    # the published value stands beside each held one
    for key in conf["reduced"]:
        assert conf[key + "_published"] == CATALOG[key]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    # no width among them, nor inside the group that is
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in conf["reduced"])
    lin, pub = conf["linear_attn_config"], CATALOG["linear_attn_config"]
    assert {k for k in pub if lin[k] != pub[k]} == {
        "kda_layers", "full_attn_layers"}
    # the guide's floors: a whole period, four layers after the dense one
    assert (lin["kda_layers"], lin["full_attn_layers"]) == ([1, 2, 3, 5],
                                                            [4])
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["vocab_size_published"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "kimi_linear_48b_a3b")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    assert len(entry["source"]) <= 200
    assert {"router_bias", "projection_bias", "A_log", "dt_bias", "taps",
            "dropout", "initialisation"} <= set(conf["assumed"])
    assert "32 chips share each layer" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < np.log(20480) < hi


def test_the_parameter_count_is_the_built_models(cell):
    """602,434,432, part by part, from the weight specs of the model the
    builder makes at the file's sizes (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    model_cfg = cls(**{f.name: conf[f.name]
                       for f in dataclasses.fields(cls) if f.name in conf})
    assert model_cfg == cls()           # the class's defaults ARE the cut
    ff = FFModel(FFConfig())
    cells.load_attr(conf["builder"])(ff, 1, 4096, model_cfg)

    def count(pick):
        return sum(int(np.prod(w.shape)) for l in ff.layers
                   for w in l.weights if pick(l.name))
    want = conf["parameters_here"]
    gate = 2304 * 128 + 128 * 4096
    assert count(lambda n: n == "kda_2") == want["kda_operator"] \
        == 4 * 2304 * 4096 + 2 * gate + 2304 * 32 + 3 * 4096 * 4 \
        + 32 + 4096 + 128
    assert count(lambda n: n == "attn_3") == want["latent_operator"] \
        == 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 \
        + 32 * 128 * 2304
    assert count(lambda n: n.endswith("_0")) == want["dense_layer"]
    assert count(lambda n: n.endswith("_2")) == want["expert_layer_kda"]
    assert count(lambda n: n.endswith("_3")) == want["expert_layer_latent"]
    assert count(lambda n: n in ("embed_tokens", "lm_head")) == \
        want["embedding_and_head"]
    assert count(lambda n: n == "final_norm") == want["final_norm"]
    assert count(lambda n: True) == want["total"] == 602434432
    held = next(l for l in ff.layers if l.name == "experts_1")
    shapes = {w.name: w.shape for w in held.weights}
    assert shapes["wg"] == (2304, 256) and shapes["bias"] == (256,)
    assert shapes["w_gate"] == shapes["w_up"] == (8, 2304, 1024)
    assert shapes["w_down"] == (8, 1024, 2304)
    assert shapes["ws_gate"] == (2304, 1024)
    assert 3 * 8 * 2304 * 1024 == want["experts_held_per_layer"]
    assert 3 * 2304 * 1024 == want["shared_expert_per_layer"]
    assert 2304 * 256 + 256 == want["router_per_layer"]
    assert held.params["scale"] == 2.446 and held.params["top_k"] == 8
    # 4 uniform shares of rows, not the op's 2: the file says why
    assert held.params["rows_factor"] == conf["expert_rows_factor"] == 4
    assert "expert_rows_factor" in conf["assumed"]


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", "kimi_linear_48b_a3b")
    # wq, wk, wv, wo 2304 x 4096; two gates 2304 x 128 and 128 x 4096;
    # wb 2304 x 32; the recurrence 7 x 32 x 128^2 = 3,670,016 a token
    kda = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
               + 2304 * 32) + 3670016
    # wq 2304 x 6144, wkv_a 2304 x 576, wkv_b 512 x 8192, wo 4096 x
    # 2304; products at s = 4096: 2 x 4096 x 32 x (192 + 128)
    latent = 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304) \
        + 83886080
    dense = 2 * 3 * 2304 * 9216
    # router 2304 x 256; an expert 3 x 2304 x 1024 = 7,077,888, a token
    # meeting the shared one and 8 x 8 / 256 = 0.25 of the routed here
    expert = 2 * (2304 * 256 + 7077888 * 1.25)
    head = 2 * 2304 * 20480
    want = 4 * kda + latent + dense + 4 * expert + head
    got = flops.forward_flops_per_token(cell.config, 4096)
    assert got == want == 769753088.0
    assert flops.train_flops_per_token(cell.config, 4096) == 3 * want
    # uncut, the same functions count the published model
    whole = dict(cell.config, **CATALOG)
    assert flops.forward_flops_per_token(whole, 4096) == (
        20 * kda + 7 * latent + dense
        + 26 * 2 * (2304 * 256 + 7077888 * 9) + 2 * 2304 * 163840)


# ----------------------------------------------------------------------
# the readers, on a trace counted by hand
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 100, FWD + "kda_2/bte,ehd->bhtd/dot_general"),
    ("while.1", 1100, 300, FWD + "kda_2/checkpoint/kda.scan/while"),
    ("fusion.2", 1110, 90,
     FWD + "kda_2/checkpoint/kda.scan/while/body/checkpoint/dot_general"),
    ("fusion.2", 1210, 90,
     FWD + "kda_2/checkpoint/kda.scan/while/body/checkpoint/dot_general"),
    ("fusion.3", 1400, 50,
     FWD + "kda_2/checkpoint/kda.scan/checkpoint/triangular_solve"),
    ("fusion.4", 1450, 50, FWD + "attn_3/mul"),
    ("flash_attention_fwd.1", 1500, 100,
     FWD + "attn_3/flash_attention_fwd/pallas_call"),
    ("fusion.5", 1600, 50, FWD + "experts_3/gather"),
    ("ragged-dot-none.1", 1650, 50, "ragged-dot-none"),
    ("fusion.6", 1700, 60,
     BWD + "rematted_computation/kda_2/checkpoint/kda.scan/mul"),
    ("fusion.7", 1760, 40, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
LAYERS = [("kda_2", "OP_GATED_DELTA_RULE", {"num_heads": 32}),
          ("attn_3", "OP_LATENT_ATTENTION", {"q_rank": None}),
          ("experts_3", "OP_ROUTED_EXPERTS", {"shared_dim": 1024}),
          ("lm_head", "OP_LINEAR", {})]
QKV = [("s32", (1, 1)), ("bf16", (32, 8192, 192)),
       ("bf16", (32, 8192, 192)), ("bf16", (32, 8192, 128))]
KERNEL_SHAPES = {
    "flash_attention_fwd.1": (QKV, [("bf16", (32, 8192, 128)),
                                    ("f32", (32, 8192, 128))])}
PAIRS = 8192 * 8193 // 2


def _model(layers):
    return types.SimpleNamespace(layers=[
        types.SimpleNamespace(name=n, params=p,
                              op_type=types.SimpleNamespace(name=k))
        for n, k, p in layers])


def _hand_ctx(tmp_path, ops=OPS, layers=LAYERS):
    """A context whose trace is the hand-made one: the reductions that
    keep their result on it are given it, and the events beside them."""
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
        cell=types.SimpleNamespace(bench_dir=BENCH, root=str(tmp_path),
                                   name="x.train"))


@pytest.mark.parametrize("metric,want", [
    ("kda_time_share.train", 100.0 * 510 / 800),
    ("kda_scan_time_share.train", 100.0 * 410 / 800),
    ("mla_time_share.train", 100.0 * 150 / 800),
    ("moe_time_share.train", 100.0 * 100 / 800)])
def test_time_shares_by_hand_with_a_loop_over_the_scans_ops(
        tmp_path, metric, want):
    """The ``while`` event counts for what its body's ops leave of it
    (its self time), beside them: the layer is given the loop's 300 us
    once, not 300 + 180."""
    ctx = _hand_ctx(tmp_path)
    assert ctx.span_reduced["busy_ns"] == 800 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_scans_share_is_inside_the_layers(tmp_path):
    ctx = _hand_ctx(tmp_path)
    assert _read("kda_scan_time_share.train", ctx) \
        <= _read("kda_time_share.train", ctx)
    shares = [_read(m, ctx) for m in (
        "kda_time_share.train", "mla_time_share.train",
        "moe_time_share.train")]
    assert sum(shares) == pytest.approx(100.0 * 760 / 800)


def test_the_flash_roofline_counts_the_unmasked_pairs_at_192_over_128(
        tmp_path):
    ctx = _hand_ctx(tmp_path)
    # q.k over 192 and p.v over 128, 32 heads, the unmasked pairs, over
    # 197 TFLOP/s, of the 100 us the hand-made call took
    fwd = _read("mla_flash_fwd_roofline", ctx)
    assert fwd == pytest.approx(
        100.0 * (2 * 32 * PAIRS * (192 + 128) / 197e12) / 100e-6)
    assert _read("mla_flash_bwd_dq_roofline", ctx) is None  # no call
    assert _read("mla_flash_bwd_dkv_roofline", ctx) is None


def test_the_counter_by_hand(tmp_path):
    ctx = _hand_ctx(tmp_path)
    ctx.counters = {"moe.dropped": 0.0, "moe.local_assignments": 16e3,
                    "kda.scans": 4.0}
    assert _read("moe_dropped_assignments", ctx) == 0.0


@pytest.mark.parametrize("metric", PR35)
def test_every_new_reader_reads_nothing_from_the_parent(
        tmp_path, metric):
    """The parent of PR 35 names no linear-attention layer; a model of
    the parent's (GPT-2) has no latent attention, no expert layer, no
    ``moe.*`` counter; and a run without ``--trace 1`` has no trace:
    nothing to read, and no error."""
    gpt2 = [("attn_1", "OP_MULTIHEAD_ATTENTION",
             {"num_heads": 12, "causal": True}),
            ("dense_1", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 100, FWD + "attn_1/mul"),
           ("flash_attention_fwd.1", 1100, 200,
            FWD + "attn_1/flash_attention_fwd/pallas_call")]
    assert _read(metric, _hand_ctx(tmp_path, ops, gpt2)) is None
    cell = types.SimpleNamespace(root=str(tmp_path), name="x.train",
                                 bench_dir=BENCH)
    bare = types.SimpleNamespace(
        trace=None, cell=cell, step_text="", peak=None, counters={},
        model=_model(LAYERS))
    assert _read(metric, bare) is None


def test_a_layer_with_no_op_under_the_scope_reads_nothing(tmp_path,
                                                          monkeypatch):
    """A program whose linear-attention layer opens no ``kda.scan``
    scope: the layer's share reads, the scan's does not."""
    ops = [(n, s, d, op.replace("/kda.scan", "")) for n, s, d, op in OPS]
    ctx = _hand_ctx(tmp_path, ops)
    assert _read("kda_time_share.train", ctx) == pytest.approx(
        100.0 * 510 / 800)
    assert _read("kda_scan_time_share.train", ctx) is None


# ----------------------------------------------------------------------
# the cell, rehearsed at a tiny size through the runner
# ----------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, num_experts=4, num_experts_published=16,
            num_experts_per_token=4, router_bias_std=0.05,
            linear_attn_config={
                "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
            initial_loss_band=[4.0, 5.5], reference_rel_tol=0.05)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="kimi_tiny", flops="kimi_linear_48b_a3b")
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi_tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(BENCH, "traffic", "train_b1_s4096.json")) as f:
        traffic = json.load(f)
    traffic.update(seq=80, steps_per_group=3, optimizer={
        "class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": 1e-3}})
    traffic["ffconfig"]["only_data_parallel"] = True
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train_tiny_kimi.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "kimi_tiny", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmarks/configs/kimi_tiny.json"})
    man["workloads"].append({"name": "kimi_tiny.train",
                             "config": "kimi_tiny",
                             "traffic": "train_tiny_kimi", "chips": 8,
                             "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["kimi_tiny.train"]
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
    """The held share (4 of 16 experts), the layout from the two lists,
    a scan of two chunks (80 tokens), rematerialised blocks, the
    token-by-token reference in the runner's own comparison: every check
    but ``device`` passes on the CPU mesh, and the traced run's counter
    reaches its reader."""
    said = []
    res = bench_run.run_cell(tiny_root, "kimi_tiny.train", 2 ** 31 + 35,
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
        assert res["metrics"]["in_window_compiles"]["value"] == 0
        assert res["metrics"]["step_ms.train"]["value"] > 0
    else:
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
