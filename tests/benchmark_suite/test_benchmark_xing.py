"""What PR 40 adds to the benchmark, on the CPU: the configuration
``xing4_29b_a4b`` and its cell's files, the parameter and operation
counts against hand counts, and each new reader on a trace small enough
to count by hand (``benchmarks/layer_metrics/xing_*.py``: eleven of the
cell's own layers, and thirteen that give the cell the executor's and
the entry's accepted readings; the reductions are PR 29's, PR 35's and
PR 37's, ``scope_reduce``, ``name_reduce``, ``span_reduce`` and
``setup_reduce``). Every entry is asserted BY NAME, as
``test_benchmark_kimi_linear.py`` does. Nothing here pins an entry to
the tail of a list, holds a list to a closed set or a cell to exactly
these metrics: a later cell or metric of any name, after these, leaves
every test here as it is.

The hand-made trace, in microseconds (one device, one group 1000-2000).
The Sinkhorn iterations are a ``while`` op on the device whose span
covers its body's ops on the same line:

  fusion.1              1000-1100  forward, attn_res_2_pre, mhc.maps
  while.1               1100-1200  forward, attn_res_2_pre, mhc.sinkhorn
    fusion.2              1110-1140  its body (twice: two trips)
    fusion.2              1150-1180
  fusion.3              1200-1260  forward, attn_res_2_pre, mhc.mix
  fusion.4              1260-1300  forward, attn_2
  flash_attention_fwd.1 1300-1400  forward, attn_2
  fusion.5              1400-1450  forward, attn_res_2, mhc.mix
  fusion.6              1450-1500  forward, experts_2  (its gathers)
  ragged-dot-none.1     1500-1550  no op_name of its own: after experts_2
  fusion.7              1550-1600  forward, mlp_res_mtp_pre, mhc.sinkhorn
  fusion.8              1600-1700  backward, remat, attn_res_2, mhc.mix
  fusion.9              1700-1760  optimizer
  (idle 1760-2000)

busy 760. Hyper-connection nodes: 100 + (while's own 100 - 60 = 40) + 60
+ 60 + 50 + 50 + 100 = 460, of which under mhc.sinkhorn 40 + 60 + 50 =
150; attn_2 40 + 100 = 140; experts_2 50 + 50 = 100; the MTP module's
layers 50.
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
CELL = "xing4_29b_a4b.train.1chip"
OLDER_CELLS = {          # name -> (config, traffic)
    "bert_large.train.1chip": ("bert_large", "train_b8_s512"),
    "gpt2_124m.train.1chip": ("gpt2_124m", "train_b12_s1024"),
    "joyai_llm_flash.train.1chip": ("joyai_llm_flash", "train_b1_s4096"),
    "lfm2_24b_a2b.train.1chip": ("lfm2_24b_a2b", "train_b1_s8192"),
    "kimi_linear_48b_a3b.train.1chip": ("kimi_linear_48b_a3b",
                                        "train_b1_s4096")}
US = 1000
PR40 = ["xing_mhc_time_share.train", "xing_mhc_sinkhorn_time_share.train",
        "mla_time_share.train", "moe_time_share.train",
        "mtp_time_share.train", "mla_flash_fwd_roofline",
        "mla_flash_bwd_dq_roofline", "mla_flash_bwd_dkv_roofline",
        "moe_dropped_assignments", "xing_mhc_sum_err",
        "xing_mhc_clamped"]
LAYER_OF = dict(zip(PR40, [
    "residual", "residual", "attention", "experts", "mtp", "kernels",
    "kernels", "kernels", "experts", "residual", "residual"]))
# the accepted readings of the executor, the loader, the device and the
# entry, which list no cells and so report this one
PR40_SHARED_LAYERS = [
    "fwd_time_share.train", "bwd_time_share.train",
    "opt_time_share.train", "dispatch_ms_per_step.train",
    "loader_wait_ms_per_step.train",
    "idle_attributed_share.train", "retraces_after_warmup",
    "host_init_s", "step_trace_s", "step_backend_compile_s",
    "xla_cache_load_s", "xla_cache_misses",
    "setup_attributed_share"]
MINE = PR40 + PR40_SHARED_LAYERS
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"}
OWN = [n for n in PR40 if n.startswith("xing_")]      # no other cell's
PARENT_COMMIT = "8147231e14972d534d19685abf4dc3380dc528d0"


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


@pytest.mark.parametrize("name", PR40)
def test_each_new_metric_lists_the_cell_and_has_a_reader(manifest, name):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    m = by_name[name]
    assert CELL in m["workloads"]
    assert m["workloads"] == [CELL] or name not in OWN
    assert m["moves"] == "train_tokens_per_s"
    assert m["layer"] == LAYER_OF[name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["unit"] == "%" if "share" in name or "roofline" in name \
        else m["unit"] in ("count", "abs_err")
    assert m["better"] == ("higher" if "roofline" in name else "lower")
    assert m["source"] == ("program_counter" if name in PR40[8:]
                           else "device_trace")
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


@pytest.mark.parametrize("name", PR40_SHARED_LAYERS)
def test_each_shared_layers_metric_is_the_accepted_one_for_this_cell(
        manifest, name):
    """One entry and one file a reader: the accepted entry reports this
    cell, and the copy this cell had is gone with its file."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert _reports(by_name[name], CELL)
    assert "xing_" + name not in by_name
    assert cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file("xing_" + name)) is None
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


def _pr37_tables():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_pr37_tables", os.path.join(os.path.dirname(__file__),
                                     "test_benchmark_setup_spans.py"))
    pr37 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pr37)
    return pr37


def test_the_new_entries_come_after_every_entry_of_the_parents(manifest):
    """This cell's own entries (the streams' four) after the set-up's
    seven, which were the parent's last (PR 37's tables list them); the
    ones it shares with other cells stand where the first of them
    stood. What comes after them is not this test's to say."""
    pr37 = _pr37_tables()
    order = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in order if n in OWN] == OWN
    assert max(order.index(n) for n in list(pr37.SHARED) + list(pr37.NEW)) \
        < min(order.index(n) for n in OWN)
    configs = [c["name"] for c in manifest["configs"]]
    assert all(configs.index(c) < configs.index("xing4_29b_a4b")
               for c, _ in OLDER_CELLS.values())
    names = [w["name"] for w in manifest["workloads"]]
    assert all(names.index(w) < names.index(CELL) for w in OLDER_CELLS)
    assert len(set(names)) == len(names)
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert "residual" in perf and CELL in perf


def test_pr37s_closed_set_test_by_name(manifest):
    """What ``test_benchmark_setup_spans.py``'s closed-set test held,
    from that file's own tables and from below: its seven shared
    entries without a list and its seven of the set-up are all there,
    in their order, the five cells first and in order; more may be."""
    pr37 = _pr37_tables()
    order = [m["name"] for m in manifest["per_layer"]]
    assert len(set(order)) == len(order)
    assert set(order) >= set(pr37.SHARED) | set(pr37.NEW) | set(MINE)
    assert [n for n in order if n in pr37.SHARED] == list(pr37.SHARED)
    assert [n for n in order if n in pr37.NEW] == list(pr37.NEW)
    assert max(order.index(n) for n in pr37.SHARED) \
        < order.index("host_init_s")
    assert [w["name"] for w in manifest["workloads"]][:5] \
        == pr37.CELLS_1_2 + pr37.CELLS_3_5 == list(OLDER_CELLS)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name["compile_s"] == {
        "name": "compile_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "entry", "moves": "setup_s"}
    assert by_name["in_window_compiles"]["source"] == "program_counter"


@pytest.mark.parametrize("older", sorted(OLDER_CELLS))
def test_every_older_cell_is_unmoved(manifest, older):
    """Its entry, its configuration's entry, the metrics it reports: what
    they were before this PR, none of this cell's own among them."""
    config, traffic = OLDER_CELLS[older]
    entry = next(w for w in manifest["workloads"] if w["name"] == older)
    assert entry == dict(entry, config=config, traffic=traffic, chips=1)
    assert sum(c["name"] == config for c in manifest["configs"]) == 1
    reported = {m["name"] for m in cells.resolve_cell(ROOT, older).per_layer}
    assert SHARED <= reported and not reported & set(OWN)
    assert SHARED <= {m["name"] for m in manifest["per_layer"]
                      if "workloads" not in m}


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
        mine = {"configs": ["xing4_29b_a4b"], "workloads": [CELL]}[key]
        added = [e["name"] for e in manifest[key][len(parent[key]):]]
        assert added[:len(mine)] == mine
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for was in parent["per_layer"]:
        now = by_name[renamed.get(was["name"], was["name"])]
        for field in ("unit", "better", "source", "layer", "moves"):
            assert now[field] == was[field], was["name"]
        assert all(_reports(now, c) for c in was.get("workloads", ()))


def test_the_cell_reports_the_shared_metrics_and_its_own(cell):
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= SHARED | set(MINE)
    assert cell.chips == 1
    # ISSUE 40's rule: the step compiled for a described v5e read 14.17
    # GiB at 4096 tokens, under 14.5, so the traffic is cells 3 and 5's
    assert cell.traffic_name == "train_b1_s4096"
    assert cell.traffic["per_chip_batch"] == 1
    assert cell.traffic["seq"] == 4096
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]


CATALOG = {        # the catalog row's ``config``, architectures.jsonl
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


def test_every_published_key_is_in_the_file_and_only_the_cut_differs(
        cell, manifest):
    conf = cell.config
    differs = {k for k, v in CATALOG.items() if conf[k] != v}
    assert differs == set(conf["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size"}
    for key in conf["reduced"]:     # the published value beside the held
        assert conf[key + "_published"] == CATALOG[key]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    # no width among them: sizes, ranks, head sizes, experts a token
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   and k != "vocab_size" for k in conf["reduced"])
    assert "num_experts_per_tok" not in conf["reduced"]
    # the guide's floors: four layers after the dense ones, 8 experts,
    # an eighth of the vocabulary; and the group is 8 chips
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["vocab_size_published"]
    assert conf["num_attention_heads"] * 8 == \
        conf["num_attention_heads_published"]
    assert conf["n_routed_experts"] * 8 == \
        conf["n_routed_experts_published"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "xing4_29b_a4b")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] == conf["source"]
    assert entry["file"] == "benchmarks/configs/xing4_29b_a4b.json"
    assert {"stream_copy_and_sum", "maps_norm", "hc_eps", "mtp_module",
            "mtp_loss_weight", "rope_interleave", "router_bias",
            "maps_draw", "dropout"} <= set(conf["assumed"])
    assert "a_pre = a_post = a_res = 1" in conf["assumed"]["maps_draw"]
    assert "8 chips share each layer" in conf["deployment"]
    assert "same micro-batch" in conf["deployment"]
    assert "256 tokens an expert" in conf["deployment"]
    lo, hi = conf["initial_loss_band"]
    assert lo < 1.3 * np.log(16384) < hi
    assert 0 < conf["reference_rel_tol"] <= 0.05


def test_the_parameter_count_is_the_built_models(cell):
    """789,610,628, part by part, from the weight specs of the model the
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
    attn = 3584 * 768 + 768 + 768 * 4 * 192 + 3584 * 576 + 512 \
        + 512 * 4 * 256 + 4 * 128 * 3584
    assert count(lambda n: n == "attn_2") == attn == 7767296
    assert attn + 2 * 3584 == want["attention_and_two_norms_per_layer"]
    assert count(lambda n: n == "mlp_res_3_pre") \
        == want["maps_per_sublayer"] == 14336 * 24 + 24 + 3
    assert count(lambda n: n in ("gate_proj_0", "up_proj_0",
                                 "down_proj_0")) \
        == want["dense_feed_forward"] == 3 * 3584 * 9216
    assert count(lambda n: n == "experts_1") == want["expert_feed_forward"] \
        == 3584 * 64 + 64 + 3 * 3584 * 1024 * (8 + 1)
    assert count(lambda n: n.endswith(("_0", "_0_pre"))) \
        == want["dense_layer"]
    assert count(lambda n: n.endswith(("_2", "_2_pre"))) \
        == want["expert_layer"]
    assert count(lambda n: "mtp" in n.split("_")) == want["mtp_module"] \
        == want["expert_layer"] + 2 * 3584 * 3584 + 3 * 3584
    assert count(lambda n: n in ("embed_tokens", "lm_head")) == \
        want["embedding_and_head"] == 2 * 16384 * 3584
    assert count(lambda n: n == "final_norm") == want["final_norm"]
    assert count(lambda n: True) == want["total"] == 789610628 \
        == want["dense_layer"] + 4 * want["expert_layer"] \
        + want["mtp_module"] + want["embedding_and_head"] \
        + want["final_norm"]
    held = next(l for l in ff.layers if l.name == "experts_1")
    assert held.params["scale"] == 2.0 and held.params["top_k"] == 4
    assert held.params["num_experts"] == 64
    assert held.params["experts_held"] == 8
    attn = next(l for l in ff.layers if l.name == "attn_1")
    assert attn.params["num_heads"] == 4
    assert attn.params["rope_scaling"] == CATALOG["rope_scaling"]
    pre = next(l for l in ff.layers if l.name == "attn_res_1_pre")
    assert pre.inputs[0].shape == (1, 4096, 4, 3584)
    assert pre.params["iters"] == 20 and pre.params["clamp"] == [-30, 30]
    assert set(pre.params) == {"stage", "iters", "eps", "norm_eps", "clamp"}
    # 12 sub-layers: 10 in the trunk, 2 in the module
    assert sum(l.params.get("stage") == "pre" for l in ff.layers) == 12


def test_model_flops_against_a_hand_count(cell):
    flops = cells.load_module(BENCH, "flops", "xing4_29b_a4b")
    # wq_a 3584 x 768, wq_b 768 x 4 x 192, wkv_a 3584 x 576, wkv_b 512 x
    # 4 x 256, wo 4 x 128 x 3584; products at s = 4096 over 4 heads
    latent = 2 * (3584 * 768 + 768 * 768 + 3584 * 576 + 512 * 1024
                  + 512 * 3584) + 2 * 4096 * 4 * (192 + 128)
    maps = 2 * 14336 * 24
    dense = 2 * 3 * 3584 * 9216
    # router 3584 x 64; an expert 3 x 3584 x 1024 = 11,010,048, a token
    # meeting the shared one and 4 x 8 / 64 = 0.5 of the routed here
    expert = 2 * (3584 * 64 + 11010048 * 1.5)
    head = 2 * 3584 * 16384
    want = 5 * (latent + 2 * maps) + dense + 4 * expert \
        + (2 * 2 * 3584 * 3584 + latent + 2 * maps + expert) + 2 * head
    got = flops.forward_flops_per_token(cell.config, 4096)
    assert got == want
    assert flops.train_flops_per_token(cell.config, 4096) == 3 * want
    # the one product of the new mechanism, and nothing of it besides
    one = dict(cell.config, hc_mult=1)
    assert got - flops.forward_flops_per_token(one, 4096) == 12 * maps
    # uncut, the same functions count the published model
    whole = dict(cell.config, **CATALOG, n_routed_experts_published=None)
    latent32 = 2 * (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                    + 512 * 32 * 256 + 32 * 128 * 3584) \
        + 2 * 4096 * 32 * (192 + 128)
    assert flops.forward_flops_per_token(whole, 4096) == (
        40 * (latent32 + 2 * maps) + 2 * dense
        + 38 * 2 * (3584 * 64 + 11010048 * 5)
        + (2 * 2 * 3584 * 3584 + latent32 + 2 * maps
           + 2 * (3584 * 64 + 11010048 * 5)) + 2 * 2 * 3584 * 131072)


# ----------------------------------------------------------------------
# the readers, on a trace counted by hand
# ----------------------------------------------------------------------
FWD = "jit(step_fn)/jit(main)/jvp(ff.forward)/"
BWD = "jit(step_fn)/jit(main)/transpose(jvp(ff.forward))/jvp(ff.forward)" \
      "/checkpoint/"
PRE = FWD + "attn_res_2_pre/checkpoint/"
OPS = [          # name, start us, duration us, op_name
    ("fusion.1", 1000, 100, PRE + "mhc.maps/bsnc,nck->kbs/dot_general"),
    ("while.1", 1100, 100, PRE + "mhc.sinkhorn/while"),
    ("fusion.2", 1110, 30, PRE + "mhc.sinkhorn/while/body/div"),
    ("fusion.2", 1150, 30, PRE + "mhc.sinkhorn/while/body/div"),
    ("fusion.3", 1200, 60, PRE + "mhc.mix/mul"),
    ("fusion.4", 1260, 40, FWD + "attn_2/mul"),
    ("flash_attention_fwd.1", 1300, 100,
     FWD + "attn_2/flash_attention_fwd/pallas_call"),
    ("fusion.5", 1400, 50, FWD + "attn_res_2/mhc.mix/add"),
    ("fusion.6", 1450, 50, FWD + "experts_2/gather"),
    ("ragged-dot-none.1", 1500, 50, "ragged-dot-none"),
    ("fusion.7", 1550, 50,
     FWD + "mlp_res_mtp_pre/checkpoint/mhc.sinkhorn/exp"),
    ("fusion.8", 1600, 100,
     BWD + "rematted_computation/attn_res_2/mhc.mix/mul"),
    ("fusion.9", 1700, 60, "jit(step_fn)/jit(main)/ff.optimizer/mul"),
]
LAYERS = [("attn_res_2_pre", "OP_HYPER_CONNECTION", {"stage": "pre"}),
          ("attn_res_2", "OP_HYPER_CONNECTION", {"stage": "post"}),
          ("mlp_res_mtp_pre", "OP_HYPER_CONNECTION", {"stage": "pre"}),
          ("attn_2", "OP_LATENT_ATTENTION", {"q_rank": 768}),
          ("experts_2", "OP_ROUTED_EXPERTS", {"shared_dim": 1024}),
          ("lm_head", "OP_LINEAR", {})]
QKV = [("s32", (1, 1)), ("bf16", (4, 4096, 192)),
       ("bf16", (4, 4096, 192)), ("bf16", (4, 4096, 128))]
KERNEL_SHAPES = {
    "flash_attention_fwd.1": (QKV, [("bf16", (4, 4096, 128)),
                                    ("f32", (4, 4096, 128))])}
PAIRS = 4096 * 4097 // 2


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
    ("xing_mhc_time_share.train", 100.0 * 460 / 760),
    ("xing_mhc_sinkhorn_time_share.train", 100.0 * 150 / 760),
    ("mla_time_share.train", 100.0 * 140 / 760),
    ("moe_time_share.train", 100.0 * 100 / 760),
    ("mtp_time_share.train", 100.0 * 50 / 760)])
def test_time_shares_by_hand_with_a_loop_of_iterations(
        tmp_path, metric, want):
    """The ``while`` event counts for what its body's ops leave of it,
    beside them; a ``post`` node's pass and a rematerialised one count
    for the mechanism, the module's nodes for the module too."""
    ctx = _hand_ctx(tmp_path)
    assert ctx.span_reduced["busy_ns"] == 760 * US
    assert _read(metric, ctx) == pytest.approx(want)


def test_the_flash_roofline_at_four_heads(tmp_path):
    ctx = _hand_ctx(tmp_path)
    fwd = _read("mla_flash_fwd_roofline", ctx)
    assert fwd == pytest.approx(
        100.0 * (2 * 4 * PAIRS * (192 + 128) / 197e12) / 100e-6)
    assert _read("mla_flash_bwd_dq_roofline", ctx) is None  # no call
    assert _read("mla_flash_bwd_dkv_roofline", ctx) is None


def test_the_counters_by_hand(tmp_path):
    ctx = _hand_ctx(tmp_path)
    ctx.counters = {"moe.dropped": 0.0, "mhc.sublayers": 96.0,
                    "mhc.sum_err": 0.48, "mhc.clamped": 3.0}
    assert _read("moe_dropped_assignments", ctx) == 0.0
    assert _read("xing_mhc_sum_err", ctx) == pytest.approx(0.005)
    assert _read("xing_mhc_clamped", ctx) == 3.0


@pytest.mark.parametrize("metric", PR40)
def test_every_new_reader_reads_nothing_from_the_parent(
        tmp_path, metric):
    """The parent of PR 40 names no hyper-connection node and counts no
    ``mhc.*``; a model of the parent's (GPT-2) has no latent attention,
    no expert layer, no module; and a run without ``--trace 1`` has no
    trace: nothing to read, and no error."""
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


@pytest.mark.parametrize("metric", PR40)
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


@pytest.fixture
def recorded_ctx(monkeypatch):
    """What a traced run hands the readers: the device's side from
    ``testdata/span_trace.json``, the program's ring PR 37's hand-made
    one (``test_benchmark_setup_spans.py``'s tables)."""
    from flexflow_tpu.obs import events as obs
    pr37 = _pr37_tables()
    with open(os.path.join(BENCH, "testdata", "span_trace.json")) as f:
        hand = json.load(f)
    monkeypatch.setattr(obs, "events", lambda: list(pr37.RING))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    instr = span_reduce.instructions(hand["step_text"])
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(hand["events"], instr),
        span_instructions=instr, counters=dict(pr37.COUNTERS),
        cell=types.SimpleNamespace(traffic={"warmup_groups": 2},
                                   bench_dir=BENCH)), pr37.BY_HAND


@pytest.mark.parametrize("metric", PR40_SHARED_LAYERS)
def test_each_shared_layers_reader_reads_the_recorded_run(recorded_ctx,
                                                          metric):
    """The accepted reader on this cell's recorded run, held to the
    run's hand count where PR 37's tables have one."""
    ctx, by_hand = recorded_ctx
    got = _read(metric, ctx)
    assert got is not None
    if metric in by_hand:
        assert got == pytest.approx(by_hand[metric])
    elif "share" in metric:
        assert 0.0 <= got <= 100.0


@pytest.mark.parametrize("metric,want", [
    ("fwd_time_share.train", 100.0 * 550 / 760),
    ("bwd_time_share.train", 100.0 * 100 / 760),
    ("opt_time_share.train", 100.0 * 60 / 760)])
def test_phase_shares_by_hand(tmp_path, metric, want):
    """Everything before ``fusion.8`` but the expert product with no
    ``op_name`` is the forward pass (550 of 760); the rematerialised mix
    lies inside a ``transpose(`` and counts as backward."""
    assert _read(metric, _hand_ctx(tmp_path)) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", PR40_SHARED_LAYERS)
def test_a_run_with_no_trace_and_no_ring_reads_none_of_them(monkeypatch,
                                                            metric):
    from flexflow_tpu.obs import events as obs
    monkeypatch.setattr(obs, "events", lambda: [])
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    bare = types.SimpleNamespace(
        trace=None, step_text="", peak=None, counters={},
        cell=types.SimpleNamespace(root="/nonexistent", name="x.train",
                                   bench_dir=BENCH,
                                   traffic={"warmup_groups": 2}))
    assert _read(metric, bare) is None


def test_a_node_with_no_op_under_the_scope_reads_nothing(tmp_path,
                                                         monkeypatch):
    """A program whose nodes open no ``mhc.sinkhorn`` scope: the nodes'
    share reads, the iterations' does not."""
    ops = [(n, s, d, op.replace("/mhc.sinkhorn", "")) for n, s, d, op in OPS]
    ctx = _hand_ctx(tmp_path, ops)
    assert _read("xing_mhc_time_share.train", ctx) == pytest.approx(
        100.0 * 460 / 760)
    assert _read("xing_mhc_sinkhorn_time_share.train", ctx) is None


# ----------------------------------------------------------------------
# the cell, rehearsed at a tiny size through the runner
# ----------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
            moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=4, n_routed_experts_published=16,
            router_bias_std=0.05,
            rope_scaling={"beta_fast": 4, "beta_slow": 1, "factor": 4,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 16,
                          "type": "yarn"},
            initial_loss_band=[5.0, 7.0], reference_rel_tol=0.05)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "xing4_29b_a4b.json")) as f:
        conf = json.load(f)
    conf.update(TINY, name="xing_tiny", flops="xing4_29b_a4b")
    with open(os.path.join(root, "benchmarks", "configs",
                           "xing_tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(BENCH, "traffic", "train_b1_s4096.json")) as f:
        traffic = json.load(f)
    traffic.update(seq=40, steps_per_group=3, optimizer={
        "class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": 1e-3}})
    traffic["ffconfig"]["only_data_parallel"] = True
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train_tiny_xing.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({"name": "xing_tiny", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmarks/configs/xing_tiny.json"})
    man["workloads"].append({"name": "xing_tiny.train",
                             "config": "xing_tiny",
                             "traffic": "train_tiny_xing", "chips": 8,
                             "why": "test"})
    for m in man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["xing_tiny.train"]
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
    """The held share (4 of 16 experts), four streams through every
    sub-layer, YaRN, rematerialised blocks, the reference of the literal
    iterations in the runner's own comparison: every check but
    ``device`` passes on the CPU mesh, and the traced run's counters
    reach their readers."""
    said = []
    res = bench_run.run_cell(tiny_root, "xing_tiny.train", 2 ** 31 + 40,
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
        assert metrics["xing_mhc_clamped"]["value"] == 0
        assert 0 < metrics["xing_mhc_sum_err"]["value"] < 0.2
        assert metrics["in_window_compiles"]["value"] == 0
        # the program's own ring reaches the entry's and the executor's
        # readers (the device's side is stubbed here: no phase share)
        assert metrics["retraces_after_warmup"]["value"] == 0
        assert metrics["host_init_s"]["value"] > 0
        assert metrics["step_trace_s"]["value"] > 0
        assert 0 < metrics["setup_attributed_share"]["value"] <= 100
        assert metrics["step_ms.train"]["value"] > 0
    else:
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
