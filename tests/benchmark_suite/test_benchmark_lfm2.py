"""What PR 33 adds to the benchmark, on the CPU: the configuration
``lfm2_24b_a2b`` and its cell's files, the operation count against a
hand count, and each new reader on a trace small enough to count by hand
(``benchmarks/harness/kind_reduce.py``, seven files of
``benchmarks/layer_metrics/``). And, by name, where PR 29's entries
stand. Nothing here pins an entry to the tail of a list.

The hand-made trace, in microseconds (one device, one group 1000-2000):

  fusion.1              1000-1100  forward, conv_2
  fusion.2              1100-1150  forward, attn_1   (q/k norms, rotary)
  flash_attention_fwd.1 1150-1350  forward, attn_1
  fusion.3              1350-1400  forward, experts_1  (its gathers)
  ragged-dot-none.1     1400-1500  no op_name of its own: after experts_1
  fusion.4              1500-1570  backward, remat, conv_2 recomputed
  flash_attention_bwd_dq.1 1570-1770  backward, under a checkpoint scope
  fusion.5              1770-1820  optimizer
  (idle 1820-2000)

busy 820; conv_2 100 + 70 = 170; attn_1 50 + 200 + 200 = 450;
experts_1 50 + 100 = 150.
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
CELL = "lfm2_24b_a2b.train.1chip"
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
def _reports(entry, workload) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


@pytest.mark.parametrize("names,its_cell", [(PR29, CELL29), (PR33, CELL)])
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


def test_the_older_entries_stand_and_this_prs_are_there_by_name(manifest):
    """By name, and open to later entries: the shared entries list no
    cells and this PR's stand after the PR's before it."""
    order = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert not any("workloads" in by_name[n] for n in SHARED)
    own = [n for n in PR33 if n not in PR29]      # not the shared experts'
    assert max(order.index(n) for n in PR29) \
        < min(order.index(n) for n in own)
    assert [n for n in order if n in own] == own
    configs = [c["name"] for c in manifest["configs"]]
    cells_ = {w["name"]: w for w in manifest["workloads"]}
    assert configs.index("joyai_llm_flash") < configs.index("lfm2_24b_a2b")
    assert cells_[CELL29]["chips"] == cells_[CELL]["chips"] == 1
    assert cells_[CELL29]["config"] == "joyai_llm_flash"
    assert cells_[CELL] == dict(
        cells_[CELL], config="lfm2_24b_a2b", traffic="train_b1_s8192")
    assert [by_name[n]["layer"] for n in PR33] == [
        "short_conv", "attention", "experts", "kernels", "kernels",
        "kernels", "experts"]
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])


def test_the_cell_reports_the_shared_metrics_and_its_own_by_name(cell):
    """By name, and open to what later PRs list the cell under."""
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    mine = [m["name"] for m in cell.per_layer]
    assert set(mine) >= SHARED | set(PR33)
    assert [n for n in mine if n in PR33] == sorted(PR33, key=mine.index)
    assert cell.traffic["per_chip_batch"] == 1
    assert cell.traffic["seq"] == 8192
    assert cell.traffic["steps_per_group"] == 8
    assert cell.traffic["optimizer"]["args"] == {"alpha": 1e-05}
    # the 14.5 GiB rule: rematerialised, and the file gives both readings
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert "14.593" in cell.traffic["why"] and "10.346" in cell.traffic["why"]
    # the runner compares one sequence: the batch has no second
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]
    # and PR 29's cell still reports what it reported
    other = {m["name"] for m in cells.resolve_cell(ROOT, CELL29).per_layer}
    assert other >= SHARED | set(PR29)
    # this cell has no latent attention and no MTP module: not theirs
    assert not {"mla_time_share.train", "mtp_time_share.train",
                "mla_flash_fwd_roofline"} & set(mine)


CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv"]
                    + ["full_attention", "conv", "conv", "conv"] * 10)[:40],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        cell, manifest):
    conf = cell.config
    differs = {k for k, v in CATALOG.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    # the published value stands beside each held one
    for key in conf["reduced"]:
        assert conf[key + "_published"] == CATALOG[key]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    # no width among them
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in conf["reduced"])
    # the guide's floors: a whole period, four layers after the dense one
    assert conf["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert conf["num_hidden_layers"] - conf["num_dense_layers"] >= 4
    assert conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["vocab_size_published"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2_24b_a2b")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    assert len(entry["source"]) <= 200
    assert {"head_dim", "tie_word_embeddings", "router_bias", "dropout",
            "initialisation"} <= set(conf["assumed"])
    assert "8 chips share each layer" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < np.log(8192) < hi


def test_the_parameter_count_is_the_built_models(cell):
    """486,062,464, part by part, from the weight specs of the model the
    builder makes at the file's sizes (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    model_cfg = cls(**{f.name: conf[f.name]
                       for f in dataclasses.fields(cls) if f.name in conf})
    ff = FFModel(FFConfig())
    cells.load_attr(conf["builder"])(ff, 1, 8192, model_cfg)

    def count(pick):
        return sum(int(np.prod(w.shape)) for l in ff.layers
                   for w in l.weights if pick(l.name))
    want = conf["parameters_here"]
    assert count(lambda n: n == "conv_2") == want["conv_operator"] \
        == 2048 * 3 * 2048 + 2048 * 3 + 2048 * 2048
    assert count(lambda n: n == "attn_1") == want["attention_operator"] \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128
    assert count(lambda n: n.endswith("_0")) == want["dense_layer"]
    assert count(lambda n: n.endswith("_3")) == want["expert_layer_conv"]
    assert count(lambda n: n.endswith("_1")) == \
        want["expert_layer_attention"]
    assert count(lambda n: n in ("embed_tokens", "lm_head")) == \
        want["embedding_and_head"]
    assert count(lambda n: True) == want["total"] == 486062464
    held = next(l for l in ff.layers if l.name == "experts_1")
    shapes = {w.name: w.shape for w in held.weights}
    assert shapes["wg"] == (2048, 64) and shapes["bias"] == (64,)
    assert shapes["w_gate"] == shapes["w_up"] == (8, 2048, 1536)
    assert shapes["w_down"] == (8, 1536, 2048)
    assert 3 * 8 * 2048 * 1536 == want["experts_held_per_layer"]
    assert "ws_gate" not in shapes           # no shared expert


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", "lfm2_24b_a2b")
    conv = 2 * (2048 * 6144 + 2048 * 2048)
    # wq, wo 2048 x 2048; wk, wv 2048 x 512; products at s = 8192:
    # 2 x 8192 x 32 x (64 + 64) = 67,108,864
    attn = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 67108864
    dense = 2 * 3 * 2048 * 11776
    # router 2048 x 64; an expert 3 x 2048 x 1536 = 9,437,184, a token
    # meeting 4 x 8 / 64 = 0.5 of them here
    expert = 2 * (2048 * 64 + 9437184 * 0.5)
    head = 2 * 2048 * 8192
    want = 4 * conv + attn + dense + 4 * expert + head
    got = flops.forward_flops_per_token(cell.config, 8192)
    assert got == want == 439353344.0
    assert flops.train_flops_per_token(cell.config, 8192) == 3 * want
    # uncut, the same functions count the published model
    whole = dict(cell.config, **CATALOG)
    assert flops.forward_flops_per_token(whole, 8192) == (
        30 * conv + 10 * attn + 2 * dense
        + 38 * 2 * (2048 * 64 + 9437184 * 4) + 2 * 2048 * 65536)


# ----------------------------------------------------------------------
# the readers, on a trace counted by hand
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 100, FWD + "conv_2/blc,ce->ble/dot_general"),
    ("fusion.2", 1100, 50, FWD + "attn_1/mul"),
    ("flash_attention_fwd.1", 1150, 200,
     FWD + "attn_1/flash_attention_fwd/pallas_call"),
    ("fusion.3", 1350, 50, FWD + "experts_1/gather"),
    ("ragged-dot-none.1", 1400, 100, "ragged-dot-none"),
    ("fusion.4", 1500, 70, BWD + "rematted_computation/conv_2/mul"),
    ("flash_attention_bwd_dq.1", 1570, 200,
     BWD + "attn_1/flash_attention_bwd_dq/pallas_call"),
    ("fusion.5", 1770, 50, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
GQA = {"num_heads": 32, "num_kv_heads": 8, "causal": True}
LAYERS = [("conv_2", "OP_GATED_SHORT_CONV", {"taps": 3}),
          ("attn_1", "OP_MULTIHEAD_ATTENTION", GQA),
          ("experts_1", "OP_ROUTED_EXPERTS", {"shared_dim": 0}),
          ("lm_head", "OP_LINEAR", {})]
QKV = [("s32", (1, 1))] + [("bf16", (32, 8192, 64))] * 3
BWD_OPERANDS = QKV + [("bf16", (32, 8192, 64)), ("f32", (32, 8192, 128)),
                      ("f32", (32, 8192, 128))]
KERNEL_SHAPES = {
    "flash_attention_fwd.1": (QKV, [("bf16", (32, 8192, 64)),
                                    ("f32", (32, 8192, 128))]),
    "flash_attention_bwd_dq.1": (BWD_OPERANDS, [("bf16", (32, 8192, 64))])}
PAIRS = 8192 * 8193 // 2


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
    ("short_conv_time_share.train", 100.0 * 170 / 820),
    ("gqa_time_share.train", 100.0 * 450 / 820),
    ("moe_time_share.train", 100.0 * 150 / 820)])
def test_time_shares_by_hand(metric, want):
    assert _read(metric, _hand_ctx()) == pytest.approx(want)


def test_flash_rooflines_count_the_unmasked_pairs_under_a_checkpoint_too():
    ctx = _hand_ctx()
    # forward: 2 products x 2 x 32 x pairs x 64 over 197 TFLOP/s, of the
    # 200 us the hand-made call took: the reader reports what it is given
    fwd = _read("gqa_flash_fwd_roofline", ctx)
    assert fwd == pytest.approx(
        100.0 * (2 * 2 * 32 * PAIRS * 64 / 197e12) / 200e-6)
    # the backward call sits under jax.checkpoint's scope: still causal
    dq = _read("gqa_flash_bwd_dq_roofline", ctx)
    assert dq == pytest.approx(
        100.0 * (3 * 2 * 32 * PAIRS * 64 / 197e12) / 200e-6)
    # where span_reduce's reader loses the layer and counts the square
    assert span_reduce.kernel_roofline(ctx, "flash_attention_bwd_dq") \
        == pytest.approx(dq * 8192 * 8192 / PAIRS)
    assert _read("gqa_flash_bwd_dkv_roofline", ctx) is None   # no call


def test_rooflines_read_nothing_where_another_layer_shares_the_kernel():
    ops = OPS + [("flash_attention_fwd.2", 1820, 50,
                  FWD + "other_attn/flash_attention_fwd/pallas_call")]
    KERNEL_SHAPES["flash_attention_fwd.2"] = \
        KERNEL_SHAPES["flash_attention_fwd.1"]
    try:
        ctx = _hand_ctx(ops, LAYERS + [(
            "other_attn", "OP_MULTIHEAD_ATTENTION", {"num_heads": 12})])
    finally:
        del KERNEL_SHAPES["flash_attention_fwd.2"]
    assert _read("gqa_flash_fwd_roofline", ctx) is None
    assert _read("gqa_flash_bwd_dq_roofline", ctx) is not None


def test_the_counter_by_hand():
    ctx = _hand_ctx()
    ctx.counters = {"moe.dropped": 0.0, "moe.local_assignments": 16e3}
    assert _read("moe_dropped_assignments", ctx) == 0.0


@pytest.mark.parametrize("metric", PR33)
def test_every_new_reader_reads_nothing_from_the_parent(metric, tmp_path):
    """The parent of PR 33 has no short convolution, no grouped-query
    attention layer (GPT-2's attention has as many key/value heads as
    query heads), no expert layer in that model, no ``moe.*`` counter;
    and a run without ``--trace 1`` has no trace: nothing to read, and
    no error."""
    gpt2 = [("attn_1", "OP_MULTIHEAD_ATTENTION",
             {"num_heads": 12, "causal": True}),
            ("dense_1", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 100, FWD + "attn_1/mul"),
           ("flash_attention_fwd.1", 1100, 200,
            FWD + "attn_1/flash_attention_fwd/pallas_call")]
    assert _read(metric, _hand_ctx(ops, gpt2)) is None
    cell = types.SimpleNamespace(root=str(tmp_path), name="x.train",
                                 bench_dir=BENCH)
    bare = types.SimpleNamespace(
        trace=None, cell=cell, step_text="", peak=None, counters={},
        model=_model(LAYERS))
    assert _read(metric, bare) is None


# ----------------------------------------------------------------------
# the cell, rehearsed at a tiny size through the runner
# ----------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=4,
            num_experts_published=16, num_experts_per_tok=4,
            rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
            router_bias_std=0.05, initial_loss_band=[4.0, 5.5],
            reference_rel_tol=0.05)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="lfm2_tiny", flops="lfm2_24b_a2b")
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2_tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(BENCH, "traffic", "train_b1_s8192.json")) as f:
        traffic = json.load(f)
    traffic.update(seq=32, steps_per_group=3, optimizer={
        "class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": 1e-3}})
    traffic["ffconfig"]["only_data_parallel"] = True
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train_tiny_lfm2.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "lfm2_tiny", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmarks/configs/lfm2_tiny.json"})
    man["workloads"].append({"name": "lfm2_tiny.train",
                             "config": "lfm2_tiny",
                             "traffic": "train_tiny_lfm2", "chips": 8,
                             "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["lfm2_tiny.train"]
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
    """The held share (4 of 16 experts), the layout from ``layer_types``,
    rematerialised blocks, the reference in the runner's own comparison:
    every check but ``device`` passes on the CPU mesh, and the traced
    run's counter reaches its reader."""
    said = []
    res = bench_run.run_cell(tiny_root, "lfm2_tiny.train", 2 ** 31 + 33,
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
