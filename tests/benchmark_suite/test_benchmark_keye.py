"""What PR 48 adds to the benchmark, on the CPU: the configuration
``keye_vl2_30b_a3b`` and its cell's files, the parameter and operation
counts against hand counts, and each new reader on a trace small enough
to count by hand (``benchmarks/layer_metrics/keye_*.py``: eight, of the
cell's own layers; the reductions are ``scope_reduce``, ``name_reduce``
and this PR's ``sparse_reduce``). Every entry is asserted BY NAME, as
``test_benchmark_xing.py`` does. Nothing here pins an entry to the tail
of a list, holds a list to a closed set or a cell to exactly these
metrics: a later cell or metric of any name, after these, leaves every
test here as it is.

The hand-made trace, in microseconds (one device, one group 1000-2000).
The threshold search is a ``while`` op on the device whose span covers
its body's ops on the same line:

  fusion.1           1000-1040  forward, attn_1 (the projections)
  fusion.2           1040-1100  forward, attn_1, dsa.index
  while.1            1100-1200  forward, attn_1, dsa.select
    fusion.3           1110-1140  its body (twice: two of the 32 trips)
    fusion.3           1150-1180
  fusion.4           1200-1400  forward, attn_1, dsa.attend
  fusion.5           1400-1430  forward, attn_1, dsa.loss
  fusion.6           1430-1480  forward, experts_1  (its gathers)
  ragged-dot-none.1  1480-1530  no op_name of its own: after experts_1
  fusion.7           1530-1650  backward, attn_1's own second run,
                                dsa.attend
  fusion.8           1650-1700  backward, attn_1, dsa.index
  fusion.9           1700-1760  optimizer
  (idle 1760-2000)

busy 760. attn_1: 40 + 60 + (while's own 100 - 60 = 40) + 60 + 200 + 30
+ 120 + 50 = 600, of which under the indexer's three scopes 60 + 50
(index) + 100 (select) + 30 (loss) = 240, the selection alone 100, and
outside them 360; experts_1 50 + 50 = 100.
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
CONFIG = "keye_vl2_30b_a3b"
CELL = "keye_vl2_30b_a3b.train.1chip"
OLDER_CELLS = {          # name -> (config, traffic)
    "bert_large.train.1chip": ("bert_large", "train_b8_s512"),
    "gpt2_124m.train.1chip": ("gpt2_124m", "train_b12_s1024"),
    "joyai_llm_flash.train.1chip": ("joyai_llm_flash", "train_b1_s4096"),
    "lfm2_24b_a2b.train.1chip": ("lfm2_24b_a2b", "train_b1_s8192"),
    "kimi_linear_48b_a3b.train.1chip": ("kimi_linear_48b_a3b",
                                        "train_b1_s4096"),
    "xing4_29b_a4b.train.1chip": ("xing4_29b_a4b", "train_b1_s4096")}
US = 1000
PR48 = {     # name -> (unit, source, layer)
    "keye_dsa_time_share.train": ("%", "device_trace", "sparse_attention"),
    "keye_dsa_select_time_share.train": ("%", "device_trace",
                                         "sparse_attention"),
    "keye_attn_time_share.train": ("%", "device_trace", "attention"),
    "moe_time_share.train": ("%", "device_trace", "experts"),
    "keye_dsa_kept_share": ("ratio", "program_counter", "sparse_attention"),
    "keye_dsa_index_kl": ("nats", "program_counter", "sparse_attention"),
    "moe_dropped_assignments": ("count", "program_counter", "experts"),
    "moe_overflow_layer_steps": ("count", "program_counter",
                                      "experts")}
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"}
PARENT_COMMIT = "59797a2853318f7b05b28c9ed00a53dcaa962783"


OWN = {n for n in PR48 if n.startswith("keye_")}     # no other cell's


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
@pytest.mark.parametrize("name", sorted(PR48))
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    unit, source, layer = PR48[name]
    assert by_name[name] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "train_tokens_per_s",
        "workloads": _holding(CELL, by_name[name]["workloads"])}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def test_the_new_entries_come_after_every_entry_the_parent_had(manifest):
    """After the six cells and configurations of the parent and the
    set-up's metrics, whose names ``test_benchmark_xing.py`` holds (PR
    68 folded the copies among the 70 there were, so no count is held);
    what comes after this PR's is not this test's to say."""
    order = [m["name"] for m in manifest["per_layer"]]
    assert order.index("setup_attributed_share") \
        < min(order.index(n) for n in OWN)
    assert [n for n in order if n in OWN] \
        == [n for n in PR48 if n in OWN]
    configs = [c["name"] for c in manifest["configs"]]
    assert all(configs.index(c) < configs.index(CONFIG)
               for c, _ in OLDER_CELLS.values())
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    assert len(set(names)) == len(names) and len(set(configs)) == len(configs)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"]
               if w["name"] in OLDER_CELLS or w["name"] == CELL)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert "sparse_attention" in perf and CELL in perf


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    """Its entry, its configuration's entry, the metrics it reports: what
    they were before this PR, none of this PR's among them."""
    config, traffic = OLDER_CELLS[older]
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry == dict(entry, config=config, traffic=traffic, chips=1)
    assert sum(c["name"] == config for c in manifest["configs"]) == 1
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert SHARED <= reported and not reported & OWN


def test_the_manifest_is_the_parents_plus_this_prs_entries(manifest):
    """Against ``git show <parent>:BENCHMARK.json`` where the checkout
    has its history (the driver's copy of the committed files has not):
    every older configuration and cell in its old place with its old
    files, every older metric still reported under its name or under
    the one ``testdata/folded_names.json`` gives for it, with
    its unit, source, layer and what it moves."""
    import subprocess
    shown = subprocess.run(
        ["git", "show", f"{PARENT_COMMIT}:BENCHMARK.json"], cwd=ROOT,
        capture_output=True, text=True)
    if shown.returncode != 0:
        pytest.skip("no git history here")
    parent = json.loads(shown.stdout)
    with open(os.path.join(BENCH, "testdata", "folded_names.json")) as f:
        renamed = json.load(f)["renamed"]
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert manifest[key] == parent[key]
    for key in ("configs", "workloads"):
        for was, now in zip(parent[key], manifest[key]):
            assert dict(was, why="") == dict(now, why="")
        mine = {"configs": [CONFIG], "workloads": [CELL]}[key]
        added = [e["name"] for e in manifest[key][len(parent[key]):]]
        assert added[:len(mine)] == mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for was in parent["per_layer"]:
        now = by_name[renamed.get(was["name"], was["name"])]
        for field in ("unit", "better", "source", "layer", "moves"):
            assert now[field] == was[field], was["name"]
        for cell_ in was.get("workloads", ()):
            assert "workloads" not in now or cell_ in now["workloads"]


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(PR48)
    assert cell.chips == 1
    # cell 4's traffic as it is: the sequence is not shortened, since at
    # 4096 half the queries would still see every key
    assert cell.traffic_name == "train_b1_s8192"
    assert cell.traffic["per_chip_batch"] == 1
    assert cell.traffic["seq"] == 8192 > 2 * cell.config["sa_config"]["topk"]
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]


CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        cell, manifest):
    conf = cell.config
    differs = {k for k, v in CATALOG.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"}
    for key in conf["reduced"]:     # the published value beside the held
        assert conf[key + "_published"] == CATALOG[key]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    # no width among them: sizes, ranks, head sizes, experts a token
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   and k != "vocab_size" for k in conf["reduced"])
    assert "num_experts_per_tok" not in conf["reduced"]
    assert "sa_config" not in conf["reduced"]
    # the guide's floors: four layers, 8 experts, an eighth of the
    # vocabulary; and the group is 8 chips
    assert conf["num_hidden_layers"] >= 4
    assert conf["num_experts"] == conf["num_local_experts"] >= 8
    assert conf["vocab_size"] * 8 == conf["vocab_size_published"]
    assert conf["num_experts"] * 8 == conf["num_experts_published"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert {"training_loss", "loss_weight", "loss_mean", "index_scale",
            "indexer_form", "qk_norm", "chunk_sizes", "positions",
            "scoring_func", "dropout", "initialisation"} \
        <= set(conf["assumed"])
    assert "8 chips share each layer" in conf["deployment"]
    assert "512 tokens an expert" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    # the cross-entropy of a model that knows nothing, and four L_I of
    # about 0.035 over it: the band lies over the first alone
    assert np.log(18992) < lo < np.log(18992) + 0.25 < hi
    assert 0 < conf["reference_rel_tol"] <= 0.05
    for key in ("compute", "reference_rel_tol_why",
                "initial_loss_band_why"):
        assert len(conf[key]) > 100, key


def test_the_parameter_count_is_the_built_models(cell):
    """465,391,104, part by part, from the weight specs of the model the
    builder makes at the file's sizes (nothing is allocated)."""
    from flexflow_tpu import FFConfig, FFModel
    conf = cell.config
    cls = cells.load_attr(conf["config_class"])
    model_cfg = cls(**{f.name: conf[f.name]
                       for f in dataclasses.fields(cls) if f.name in conf})
    assert model_cfg == cls()           # the class's defaults ARE the cut
    ff = FFModel(FFConfig())
    cells.load_attr(conf["builder"])(ff, 1, 8192, model_cfg)

    def count(pick, weight=lambda w: True):
        return sum(int(np.prod(w.shape)) for l in ff.layers
                   for w in l.weights if pick(l.name) and weight(w.name))
    want = conf["parameters_here"]
    indexer = lambda w: w.endswith("_idx")          # noqa: E731
    assert count(lambda n: n == "attn_2", lambda w: not indexer(w)) \
        == want["attention_per_layer"] \
        == 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 2 * 128 == 18874624
    assert count(lambda n: n == "attn_2", indexer) \
        == want["indexer_per_layer"] \
        == 2048 * 16 * 64 + 2048 * 64 + 2048 * 16 == 2260992
    assert count(lambda n: n == "experts_2",
                 lambda w: w in ("wg", "bias")) \
        == want["router_per_layer"] == 2048 * 128 + 128
    assert count(lambda n: n == "experts_2", lambda w: w.startswith("w_")) \
        == want["experts_held_per_layer"] == 16 * 3 * 2048 * 768
    assert count(lambda n: n in ("operator_norm_2", "ffn_norm_2")) \
        == want["two_norms_per_layer"] == 4096
    assert count(lambda n: n.endswith("_2")) == want["layer"] == 96899456
    assert count(lambda n: n in ("embed_tokens", "lm_head")) == \
        want["embedding_and_head"] == 2 * 18992 * 2048
    assert count(lambda n: n == "final_norm") == want["final_norm"]
    assert count(lambda n: True) == want["total"] == 465391104 \
        == 4 * want["layer"] + want["embedding_and_head"] \
        + want["final_norm"]
    held = next(l for l in ff.layers if l.name == "experts_1")
    assert held.params["scoring"] == "softmax"
    assert held.params["scale"] == 1.0 and held.params["top_k"] == 8
    assert held.params["num_experts"] == 128
    assert held.params["experts_held"] == 16
    assert held.params["bias_std"] == 0.0
    assert "rows_factor" not in held.params       # the default budget
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    assert RoutedExpertsOp.rows_multiplied(8192, held.params) == 16384
    attn = next(l for l in ff.layers if l.name == "attn_1")
    assert (attn.params["num_heads"], attn.params["num_kv_heads"]) == (32, 4)
    assert attn.params["rope_theta"] == 1e7 and attn.params["qk_norm"]
    assert attn.params["qk_norm_eps"] == 1e-6
    assert {k: v for k, v in attn.params.items()
            if k.startswith("indexer_")} == {
        "indexer_heads": 16, "indexer_head_dim": 64, "indexer_topk": 2048,
        "indexer_q_chunk": 512}
    assert len(attn.inputs) == 4                   # x, x, x, positions


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", CONFIG)
    # wq and wo 2048 x 32 x 128, wk and wv 2048 x 4 x 128; q.k and p.v
    # over 128 for 32 heads against the 2048 keys a query attends
    attention = 2 * (2 * 2048 * 4096 + 2 * 2048 * 512) \
        + 2 * 2048 * 32 * 2 * 128
    # the indexer's three matrices, and 16 heads of 64 against every
    # one of the 8192 keys
    indexer = 2 * 2260992 + 2 * 8192 * 16 * 64
    # router 2048 x 128; an expert 3 x 2048 x 768 = 4,718,592, a token
    # meeting 8 x 16 / 128 = 1 of the routed here
    expert = 2 * (2048 * 128 + 4718592 * 1.0)
    head = 2 * 2048 * 18992
    want = 4 * (attention + indexer + expert) + head
    got = flops.forward_flops_per_token(cell.config, 8192)
    assert got == want
    assert flops.train_flops_per_token(cell.config, 8192) == 3 * want
    # no more positions than topk: every causal key, the full square
    short = flops.forward_flops_per_token(cell.config, 1024)
    assert short == 4 * (2 * (2 * 2048 * 4096 + 2 * 2048 * 512)
                         + 2 * 1024 * 32 * 2 * 128
                         + 2 * 2260992 + 2 * 1024 * 16 * 64 + expert) + head
    # uncut, the same functions count the published model
    whole = dict(cell.config, **CATALOG, num_experts_published=None)
    assert flops.forward_flops_per_token(whole, 8192) == (
        48 * (attention + indexer + 2 * (2048 * 128 + 4718592 * 8))
        + 2 * 2048 * 151936)


# ----------------------------------------------------------------------
# the readers, on a trace counted by hand
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/checkpoint/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 40, FWD + "attn_1/ble,ehd->blhd/dot_general"),
    ("fusion.2", 1040, 60, FWD + "attn_1/dsa.index/ble,ejc->bljc/"
                                 "dot_general"),
    ("while.1", 1100, 100, FWD + "attn_1/checkpoint/dsa.select/while"),
    ("fusion.3", 1110, 30,
     FWD + "attn_1/checkpoint/dsa.select/while/body/reduce_sum"),
    ("fusion.3", 1150, 30,
     FWD + "attn_1/checkpoint/dsa.select/while/body/reduce_sum"),
    ("fusion.4", 1200, 200,
     FWD + "attn_1/checkpoint/dsa.attend/bqjgd,bkjd->bjgqk/dot_general"),
    ("fusion.5", 1400, 30, FWD + "attn_1/checkpoint/dsa.loss/log"),
    ("fusion.6", 1430, 50, FWD + "experts_1/gather"),
    ("ragged-dot-none.1", 1480, 50, "ragged-dot-none"),
    ("fusion.7", 1530, 120,
     BWD + "attn_1/checkpoint/rematted_computation/dsa.attend/exp"),
    ("fusion.8", 1650, 50,
     BWD + "attn_1/checkpoint/dsa.index/transpose(jvp(bqjc,bkc->bjqk))/"
           "dot_general"),
    ("fusion.9", 1700, 60, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
INDEXER = {"indexer_heads": 16, "indexer_head_dim": 64,
           "indexer_topk": 2048, "indexer_q_chunk": 512}
LAYERS = [("attn_1", "OP_MULTIHEAD_ATTENTION",
           dict(INDEXER, num_heads=32, num_kv_heads=4, causal=True)),
          ("experts_1", "OP_ROUTED_EXPERTS", {"shared_dim": 0}),
          ("lm_head", "OP_LINEAR", {})]


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
    instr = {n: {"op_name": op, "mosaic": n.startswith("ragged"),
                 "operands": [], "results": []} for n, _, _, op in ops}
    names = {n for n, _, _ in layers}
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, model=_model(layers),
        scope_layer_ns=scope_reduce.layer_self_ns(events, instr, names),
        peak=peaks.lookup("TPU v5 lite"), counters={},
        cell=types.SimpleNamespace(bench_dir=BENCH, root=str(tmp_path),
                                   name="x.train"))


@pytest.mark.parametrize("metric,want", [
    ("keye_dsa_time_share.train", 100.0 * 240 / 760),
    ("keye_dsa_select_time_share.train", 100.0 * 100 / 760),
    ("keye_attn_time_share.train", 100.0 * 360 / 760),
    ("moe_time_share.train", 100.0 * 100 / 760)])
def test_time_shares_by_hand_with_the_threshold_searchs_loop(
        tmp_path, metric, want):
    """The ``while`` event counts for what its body's ops leave of it,
    beside them; a chunk's own second run and the transposes count under
    the scope they carry; the indexer's share and the rest of the layer
    add up to the layer's 600 of 760."""
    ctx = _hand_ctx(tmp_path)
    assert ctx.span_reduced["busy_ns"] == 760 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_counters_by_hand(tmp_path):
    ctx = _hand_ctx(tmp_path)
    ctx.counters = {"moe.dropped": 0.0, "moe.overflow": 3.0,
                    "dsa.kept_pairs": 4 * 14681088.0,
                    "dsa.causal_pairs": 4 * 33558528.0,
                    "dsa.index_kl": 0.5, "dsa.layers": 8.0}
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("moe_overflow_layer_steps", ctx) == 3.0
    assert _read("keye_dsa_kept_share", ctx) == pytest.approx(
        14681088 / 33558528)
    # 6144 queries keep 2048 keys and the first 2048 keep all theirs
    assert 14681088 == 6144 * 2048 + 2048 * 2049 // 2
    assert 33558528 == 8192 * 8193 // 2
    assert _read("keye_dsa_index_kl", ctx) == pytest.approx(0.0625)


@pytest.mark.parametrize("metric", sorted(PR48))
def test_every_new_reader_reads_nothing_from_the_parent(
        tmp_path, metric):
    """The parent of PR 48 names no layer with an indexer, opens no
    ``dsa.*`` scope and counts no ``dsa.*``; a model of the parent's
    (LFM2's grouped-query attention) has no such layer; and a run
    without ``--trace 1`` has no trace and no counters: nothing to read,
    and no error. (``keye_moe_*`` read the experts the parent has, where
    a trace or a counter is there.)"""
    lfm2 = [("attn_1", "OP_MULTIHEAD_ATTENTION",
             {"num_heads": 32, "num_kv_heads": 8, "causal": True}),
            ("dense_1", "OP_LINEAR", {})]
    ops = [("fusion.1", 1000, 100, FWD + "attn_1/mul"),
           ("flash_attention_fwd.1", 1100, 200,
            FWD + "attn_1/flash_attention_fwd/pallas_call")]
    assert _read(metric, _hand_ctx(tmp_path, ops, lfm2)) is None
    cell = types.SimpleNamespace(root=str(tmp_path), name="x.train",
                                 bench_dir=BENCH)
    bare = types.SimpleNamespace(
        trace=None, cell=cell, step_text="", peak=None, counters={},
        model=_model(LAYERS))
    assert _read(metric, bare) is None


@pytest.mark.parametrize("metric", sorted(PR48))
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


def test_a_layer_with_no_op_under_the_scopes_reads_the_layer_alone(
        tmp_path):
    """A program whose attention layers open no ``dsa.*`` scope: the
    experts' share reads, the indexer's and the split do not."""
    ops = [(n, s, d, "/".join(p for p in op.split("/")
                              if not p.startswith("dsa.")))
           for n, s, d, op in OPS]
    ctx = _hand_ctx(tmp_path, ops)
    assert _read("moe_time_share.train", ctx) == pytest.approx(
        100.0 * 100 / 760)
    for metric in ("keye_dsa_time_share.train",
                   "keye_dsa_select_time_share.train",
                   "keye_attn_time_share.train"):
        assert _read(metric, ctx) is None


# ----------------------------------------------------------------------
# the cell, rehearsed at a tiny size through the runner
# ----------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
            rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
            moe_intermediate_size=32, num_experts=4, num_local_experts=4,
            num_experts_published=16, num_experts_per_tok=4,
            sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                       "q_chunk_size": 16, "topk": 24},
            initial_loss_band=[4.0, 8.0], reference_rel_tol=0.05)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="keye_tiny", flops=CONFIG)
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye_tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(BENCH, "traffic", "train_b1_s8192.json")) as f:
        traffic = json.load(f)
    traffic.update(seq=40, steps_per_group=3, optimizer={
        "class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": 1e-3}})
    traffic["ffconfig"]["only_data_parallel"] = True
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train_tiny_keye.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "keye_tiny", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmarks/configs/keye_tiny.json"})
    man["workloads"].append({"name": "keye_tiny.train",
                             "config": "keye_tiny",
                             "traffic": "train_tiny_keye", "chips": 8,
                             "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["keye_tiny.train"]
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
    """The held share (4 of 16 experts), 40 positions with 24 keys a
    query, rematerialised blocks that each hold an auxiliary loss, the
    reference's ``top_k`` in the runner's own comparison: every check
    but ``device`` passes on the CPU mesh, and the traced run's counters
    reach their readers."""
    said = []
    res = bench_run.run_cell(tiny_root, "keye_tiny.train", 2 ** 31 + 48,
                             0.3, bool(trace), say=said.append)
    checks = {s.split()[1].rstrip(":"): " ok - " in s
              for s in said if s.startswith("check ")}
    assert checks.pop("device") is False
    assert set(checks) == {"initial_loss", "reference", "finite_losses",
                           "no_compile_in_window", "loss_fell"}
    assert all(checks.values()), said
    assert res["failed"] == 0 and res["attempted"] >= 1
    if trace:
        metrics = res["metrics"]
        assert metrics["moe_dropped_assignments"]["value"] == 0
        assert metrics["moe_overflow_layer_steps"]["value"] == 0
        kept = sum(min(t + 1, 24) for t in range(40)) / (40 * 41 / 2)
        assert metrics["keye_dsa_kept_share"]["value"] == pytest.approx(
            kept, rel=1e-6)
        assert 0 < metrics["keye_dsa_index_kl"]["value"] < 2
        assert metrics["in_window_compiles"]["value"] == 0
        assert metrics["step_ms.train"]["value"] > 0
    else:
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
