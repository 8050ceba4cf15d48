"""The chip benchmark's harness, rehearsed on the CPU mesh.

``benchmarks/run.py`` refuses to run without a TPU, so these tests import
the functions it calls and drive them at ``BertConfig.tiny()`` /
``GPTConfig.tiny()`` sizes in a temporary checkout laid out like the real
one: a copy of ``benchmarks/`` plus a few *added* files and entries —
which is also how every later PR adds a cell. No timing measured here
means anything; what is checked is plumbing, arithmetic and the contract
of ``BENCHMARK.json``.
"""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells, peaks, trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

TINY = {
    "bert_tiny": ("bert_large", dict(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128, max_position=64,
        initial_loss_band=[0.3, 2.0])),
    "gpt2_tiny": ("gpt2_124m", dict(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        max_position=128, initial_loss_band=[5.5, 7.5],
        # (a 512-word vocabulary: smaller scores, larger relative error)
        reference_rel_tol=0.005)),
}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _tree_hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for name in files:
            p = os.path.join(d, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def add_cell(root, name, base, overrides, traffic, traffic_body, chips=8):
    """Add one configuration, one traffic mix and one cell to the
    checkout at ``root`` by writing NEW files and appending entries."""
    with open(os.path.join(root, "benchmarks", "configs",
                           base + ".json")) as f:
        conf = json.load(f)
    conf.update(overrides, name=name, flops=base)
    _dump(conf, os.path.join(root, "benchmarks", "configs", name + ".json"))
    tpath = os.path.join(root, "benchmarks", "traffic", traffic + ".json")
    if not os.path.exists(tpath):
        _dump(traffic_body, tpath)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"].append({
        "name": name, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmarks/configs/{name}.json"})
    man["workloads"].append({
        "name": f"{name}.train", "config": name, "traffic": traffic,
        "chips": chips, "why": "test"})
    _dump(man, os.path.join(root, "BENCHMARK.json"))


TINY_TRAFFIC = {
    "kind": "train", "per_chip_batch": 1, "seq": 16,
    "optimizer": {"class": "flexflow_tpu:AdamOptimizer",
                  "args": {"alpha": 1e-3}}, "steps_per_group": 3,
    "warmup_groups": 2, "ffconfig": {"only_data_parallel": True},
    "why": "test"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, (base, over) in TINY.items():
        add_cell(root, name, base, over, "train_tiny", TINY_TRAFFIC)
    return root


@pytest.fixture
def stub_profiler(monkeypatch):
    """No profiler on the CPU mesh: the trace step hands back the
    recorded chip trace instead."""
    import jax
    with open(os.path.join(BENCH, "testdata", "trace_events.json")) as f:
        recorded = json.load(f)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "extract", lambda *a: dict(
        recorded["events"], spans=[]))
    return recorded


def _run(root, workload, trace, seed=2 ** 31 + 5):
    said = []
    res = bench_run.run_cell(root, workload, seed, 0.3, trace,
                             say=said.append)
    return res, said


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("config", sorted(TINY))
def test_train_runner_end_to_end(tiny_root, stub_profiler, config, trace):
    res, said = _run(tiny_root, f"{config}.train", bool(trace))
    line = json.dumps(res)                     # what run.py prints last
    assert "\n" not in line
    back = json.loads(line)
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(back) == (want | {"breakdown"} if trace else want)
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(back["device"])
    assert back["device"]["platform"] == "cpu"
    assert back["correct"] is False           # no TPU: never correct here
    assert back["attempted"] >= 1 and back["failed"] == 0
    checks = {s.split()[1].rstrip(":"): " ok - " in s
              for s in said if s.startswith("check ")}
    assert checks.pop("device") is False
    assert checks and all(checks.values()), said
    cell = cells.resolve_cell(tiny_root, f"{config}.train")
    for m in back["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    if trace:
        names = {m["name"] for m in cell.per_layer}
        assert set(back["metrics"]) <= names
        # (mfu needs a chip's peak; the rest of the one-chip set reads)
        assert {"compile_s", "step_ms.train", "in_window_compiles",
                "mosaic_calls_per_step", "kernel_time_share.train",
                "device_idle_share.train"} <= set(back["metrics"])
        assert "mfu.train" not in back["metrics"]
        assert back["metrics"]["in_window_compiles"]["value"] == 0
        assert back["device"]["busy_s"] > 0
        assert back["device"]["window_s"] >= back["device"]["busy_s"]
        assert 1 <= len(back["breakdown"]["device_ops"]) <= 10
        assert len(back["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(back["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert back["metrics"]["train_tokens_per_s"]["value"] > 0
        assert back["metrics"]["step_hbm_gib"]["value"] > 0
        assert back["metrics"]["setup_s"]["value"] > 0


def test_a_later_pr_adds_a_cell_with_files_only(tiny_root, tmp_path):
    """A new configuration, traffic mix and per-layer metric arrive as
    new files and appended entries; no file that was there changes, and
    the harness runs the new cell."""
    root = str(tmp_path / "later")
    shutil.copytree(tiny_root, root)
    before = _tree_hashes(os.path.join(root, "benchmarks"))
    add_cell(root, "gpt2_wider", "gpt2_124m",
             dict(TINY["gpt2_tiny"][1], hidden_size=128, num_layers=1),
             "train_tiny_s32", dict(TINY_TRAFFIC, seq=32, steps_per_group=2))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "loss_drop_train.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.model.config.batch_size * 1.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["per_layer"].append({
        "name": "loss_drop.train", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "executor",
        "moves": "train_tokens_per_s",
        "workloads": ["gpt2_wider.train"]})
    # the new cell opts into a reading whose entry lists other cells:
    # an entry of its own that names the accepted entry's reader
    accepted = next(m for m in man["per_layer"]
                    if m["name"] == "flash_fwd_roofline")
    assert "gpt2_wider.train" not in accepted["workloads"]
    man["per_layer"].append(dict(accepted, name="wider_flash_fwd_roofline",
                                 workloads=["gpt2_wider.train"]))
    _dump({"reader": "flash_fwd_roofline"}, os.path.join(
        root, "benchmarks", "layer_metrics",
        "wider_flash_fwd_roofline.json"))
    _dump(man, os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, "benchmarks")
    assert cells.load_reader(bench, "wider_flash_fwd_roofline").read.__code__ \
        == cells.load_reader(bench, "flash_fwd_roofline").read.__code__
    assert "wider_flash_fwd_roofline" in {
        m["name"] for m in
        cells.resolve_cell(root, "gpt2_wider.train").per_layer}
    res, said = _run(root, "gpt2_wider.train", True)
    assert res["metrics"]["loss_drop.train"] == {"value": 8.0,
                                                 "unit": "count"}
    assert "step_ms.train" in res["metrics"]
    assert any(s.startswith("check reference: ok") for s in said), said
    after = _tree_hashes(os.path.join(root, "benchmarks"))
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/gpt2_wider.json", "traffic/train_tiny_s32.json",
        "layer_metrics/loss_drop_train.py",
        "layer_metrics/wider_flash_fwd_roofline.json"}
    # and the cell that was there does not report the new metric
    assert "loss_drop.train" not in {
        m["name"] for m in
        cells.resolve_cell(root, "gpt2_tiny.train").per_layer}


def test_reference_refuses_parameters_of_another_shape(tiny_root):
    ref = cells.load_module(BENCH, "reference", "transformer_ref")
    import numpy as np
    layers = [("wte", {"kernel": np.zeros((8, 4), np.float32)}),
              ("wpe", {"kernel": np.zeros((8, 4), np.float32)}),
              ("mha", {"wq": np.zeros((4, 1, 4), np.float32)})]
    with pytest.raises(ref.ReferenceMismatch):
        ref.post_ln_encoder_classifier(
            layers, {"num_layers": 1, "hidden_size": 4, "vocab_size": 8,
                     "max_position": 8}, np.zeros((1, 2), np.int32),
            np.zeros((1, 2), np.int32))


def test_reference_refuses_embeddings_in_another_order():
    """A searched plan lists ``position_embeddings`` first; read in that
    order the tables would index silently (JAX clamps) and score
    nonsense."""
    ref = cells.load_module(BENCH, "reference", "transformer_ref")
    import numpy as np
    sizes = {"num_layers": 0, "hidden_size": 4, "vocab_size": 32,
             "max_position": 8}
    layers = [("position_embeddings", {"kernel": np.zeros((8, 4), "f")}),
              ("word_embeddings", {"kernel": np.zeros((32, 4), "f")})]
    ids = np.zeros((1, 2), np.int32)
    with pytest.raises(ref.ReferenceMismatch, match="position_embeddings"):
        ref.pre_ln_causal_decoder(layers + [("lm_head", {
            "kernel": np.zeros((4, 32), "f")})], sizes, ids, ids)
    with pytest.raises(ref.ReferenceMismatch, match="position_embeddings"):
        ref.post_ln_encoder_classifier(layers, sizes, ids, ids)


# ----------------------------------------------------------------------
# BENCHMARK.json against the contract
# ----------------------------------------------------------------------
def test_manifest_has_exactly_the_contracts_keys():
    man = manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= man["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    for p in man["paths"]:
        assert PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
    assert "setup_s" in {m["name"] for m in man["end_to_end"]}
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}


def test_names_units_and_lines_keep_to_the_allowed_characters():
    man = manifest()
    metrics = man["end_to_end"] + man["per_layer"]
    names = [e["name"] for e in metrics] \
        + [w["name"] for w in man["workloads"]] \
        + [c["name"] for c in man["configs"]]
    assert len(set(names)) == len(names)
    for n in names + [w["config"] for w in man["workloads"]] \
            + [w["traffic"] for w in man["workloads"]] \
            + [k for c in man["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in [w["why"] for w in man["workloads"]] \
            + [c["why"] for c in man["configs"]] \
            + [c["source"] for c in man["configs"]] \
            + [m["layer"] for m in man["per_layer"]] + man["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text, text
    for path in man["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    man = manifest()
    all_cells = [w["name"] for w in man["workloads"]]

    def reporting(m):
        return set(m.get("workloads", all_cells))

    e2e = {m["name"]: reporting(m) for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e, m
        assert reporting(m) <= e2e[m["moves"]], m
        assert reporting(m) <= set(all_cells)
    for cell in all_cells:       # setup_s, one more, and a per-layer one
        mine = [n for n, cs in e2e.items() if cell in cs]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in reporting(m) for m in man["per_layer"])
    # one layer, one spelling, and PERF.md's list of layers has it
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in man["per_layer"]}:
        assert layer in perf, layer


@pytest.mark.parametrize("workload",
                         [w["name"] for w in manifest()["workloads"]])
def test_every_workload_resolves_to_files_that_exist(workload):
    cell = cells.resolve_cell(ROOT, workload)
    assert cell.config["name"] == cell.config_name
    kind = cell.traffic["kind"]
    assert os.path.isfile(os.path.join(BENCH, "runners", kind + ".py"))
    flops = cells.load_module(BENCH, "flops",
                              cell.config.get("flops", cell.config_name))
    assert flops.train_flops_per_token(cell.config,
                                       cell.traffic["seq"]) > 0
    mod, _, fn = cell.config["reference"].partition(":")
    assert callable(getattr(cells.load_module(BENCH, "reference", mod), fn))
    for m in cell.per_layer:
        reader = cells.load_reader(BENCH, m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # the configuration is the program's own class at published widths
    cls = cells.load_attr(cell.config["config_class"])
    import dataclasses
    published = cls()
    for f in dataclasses.fields(cls):
        assert cell.config[f.name] == getattr(published, f.name), f.name
    assert callable(cells.load_attr(cell.config["builder"]))
    lo, hi = cell.config["initial_loss_band"]
    assert lo < hi and 0 < cell.config["reference_rel_tol"] <= 0.05


def test_unknown_workload_and_unknown_runner_kind_are_errors(tiny_root,
                                                             tmp_path):
    with pytest.raises(cells.BenchmarkError):
        cells.resolve_cell(ROOT, "no.such.cell")
    root = str(tmp_path / "odd")
    shutil.copytree(tiny_root, root)
    add_cell(root, "odd", "gpt2_124m", TINY["gpt2_tiny"][1], "odd_kind",
             dict(TINY_TRAFFIC, kind="serve"))
    with pytest.raises(cells.BenchmarkError, match="runners/serve.py"):
        bench_run.run_cell(root, "odd.train", 0, 0.1, False)


def test_run_py_exits_non_zero_on_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "bert_large.train.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to run" in proc.stderr


# ----------------------------------------------------------------------
# the yardstick's arithmetic
# ----------------------------------------------------------------------
def test_bert_large_flops_against_a_hand_count():
    cell = cells.resolve_cell(ROOT, "bert_large.train.1chip")
    flops = cells.load_module(BENCH, "flops", "bert_large")
    # per layer: q, k, v, o = 4 x 1024^2 = 4,194,304; FFN = 2 x 1024 x
    # 4096 = 8,388,608; 24 layers = 301,989,888 matmul parameters.
    # pooler + classifier per token = (1024^2 + 2048) / 512 = 2052.
    # attention = 4 x 512 x 1024 x 24 = 50,331,648.
    forward = 2 * (301_989_888 + 2052) + 50_331_648
    assert forward == 654_315_528
    assert flops.forward_flops_per_token(cell.config, 512) == forward
    assert flops.train_flops_per_token(cell.config, 512) == 3 * forward


def test_gpt2_124m_flops_against_a_hand_count():
    cell = cells.resolve_cell(ROOT, "gpt2_124m.train.1chip")
    flops = cells.load_module(BENCH, "flops", "gpt2_124m")
    # per layer 12 x 768^2 = 7,077,888; x 12 = 84,934,656; head 768 x
    # 50257 = 38,597,376; attention 4 x 1024 x 768 x 12 = 37,748,736.
    forward = 2 * (84_934_656 + 38_597_376) + 37_748_736
    assert forward == 284_812_800
    assert flops.forward_flops_per_token(cell.config, 1024) == forward
    assert flops.train_flops_per_token(cell.config, 1024) == 854_438_400


def test_peaks_table_refuses_an_unknown_device_kind():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(peaks.UnknownDevice):
            peaks.lookup(kind)


def test_mfu_reader_is_percent_of_the_tables_peak():
    import types
    reader = cells.load_module(BENCH, "layer_metrics", "mfu_train")
    ctx = types.SimpleNamespace(peak=peaks.lookup("TPU v5 lite"), chips=4,
                                tokens_per_s=4 * 197e12 / 2e9 / 4,
                                train_flops_per_token=2e9)
    assert reader.read(ctx) == pytest.approx(25.0)
    ctx.peak = None
    assert reader.read(ctx) is None
