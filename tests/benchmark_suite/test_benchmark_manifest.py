"""``BENCHMARK.json``'s ``per_layer`` list held to its rule: one entry
and one file a reader, and a cell reports a metric by standing in its
``workloads`` list.

An accepted entry is not edited, so a later cell cannot be added to a
list. It opts in from its own side: the PR that brings the cell appends
an entry of its own name that lists the cell, with a file
``layer_metrics/<name>.json`` holding ``{"reader": "<accepted entry>"}``
(``cells.load_reader``). Nothing here pins a length, a position or a
closed set, and nothing asks a later PR for an edit it may not make.
"""
import ast
import json
import os
import types

import pytest

from benchmarks.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
READERS = os.path.join(BENCH, "layer_metrics")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _code(path: str) -> str:
    """What the file computes: its syntax tree without docstrings."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if isinstance(node, (ast.Module, ast.FunctionDef)) and body \
                and isinstance(body[0], ast.Expr) \
                and isinstance(body[0].value, ast.Constant) \
                and isinstance(body[0].value.value, str):
            node.body = body[1:] or [ast.Pass()]
    return ast.dump(tree)


def _file(name: str, ext: str) -> str:
    return os.path.join(READERS, cells.metric_file(name) + ext)


ALIASES = {n for n in ENTRIES if os.path.isfile(_file(n, ".json"))}


def test_every_name_is_there_once():
    assert len(ENTRIES) == len(MANIFEST["per_layer"])
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_has_its_file_and_lists_cells_that_exist(name):
    entry = ENTRIES[name]
    reader = cells.load_reader(BENCH, name)
    assert reader is not None and callable(reader.read)
    if name not in ALIASES:
        assert reader.__doc__ and f"``{name}``" in reader.__doc__
    assert entry["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    if "workloads" in entry:
        listed = entry["workloads"]
        assert listed and len(set(listed)) == len(listed)
        assert set(listed) <= set(CELLS)
        # in the cells' own order, so that a list reads as the cells do
        assert listed == sorted(listed, key=CELLS.index)


def test_exactly_one_file_an_entry():
    """A reader (``.py``) or the name of one (``.json``), never both."""
    files = sorted(os.path.splitext(f)[0] for f in os.listdir(READERS)
                   if f.endswith((".py", ".json")))
    assert files == sorted(cells.metric_file(n) for n in ENTRIES)
    assert len(set(files)) == len(ENTRIES)   # no two names share a file


def test_no_two_entries_files_compute_the_same_reading():
    seen: dict = {}
    for name in ENTRIES.keys() - ALIASES:
        seen.setdefault(_code(_file(name, ".py")), []).append(name)
    doubles = [names for names in seen.values() if len(names) > 1]
    assert not doubles, (
        f"{doubles}: keep one reader; the other entry's file is "
        f'<name>.json with {{"reader": "<the entry that has it>"}}')


def test_an_entry_that_names_a_reader_reads_as_that_entry_does(tmp_path):
    """An entry whose file names an accepted reader is that metric for
    another cell: same unit, direction, source, layer and end-to-end
    metric, no cell of the accepted entry's, and a reader of its own
    behind the name (no chain)."""
    for name in sorted(ALIASES):
        with open(_file(name, ".json")) as f:
            of = json.load(f)
        assert set(of) == {"reader"} and of["reader"] in ENTRIES, name
        mine, theirs = ENTRIES[name], ENTRIES[of["reader"]]
        assert of["reader"] not in ALIASES, name
        for key in ("unit", "better", "source", "layer", "moves"):
            assert mine[key] == theirs[key], (name, key)
        assert "workloads" in theirs, f"{of['reader']} lists no cells: " \
            f"every cell reports it already"
        assert not set(mine["workloads"]) & set(theirs["workloads"]), name
    # the mechanism itself, on files of this test's own
    readers = tmp_path / "layer_metrics"
    readers.mkdir()
    with open(_file("moe_dropped_assignments", ".py")) as f:
        (readers / "moe_dropped_assignments.py").write_text(f.read())
    (readers / "later_moe_dropped_assignments.json").write_text(
        json.dumps({"reader": "moe_dropped_assignments"}))
    ctx = types.SimpleNamespace(counters={"moe.dropped": 3.0})
    for name in ("moe_dropped_assignments",
                 "later_moe_dropped_assignments"):
        assert cells.load_reader(str(tmp_path), name).read(ctx) == 3.0
    assert cells.load_reader(str(tmp_path), "no_such_metric") is None


def test_a_metric_of_several_configurations_carries_no_ones_prefix():
    config_of = {w["name"]: w["config"] for w in MANIFEST["workloads"]}
    prefixes = {c["name"].split("_")[0] for c in MANIFEST["configs"]} | {
        "xing", "keye", "trinity", "granite", "qwen3next", "phi4flash",
        "sdar", "nemotron", "kimi", "lfm2"}
    for name, entry in ENTRIES.items():
        configs = {config_of[w] for w in entry.get("workloads", CELLS)}
        if len(configs) > 1:
            assert name.split("_")[0] not in prefixes, name


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_reports_the_phase_host_and_set_up_readings(workload):
    """Their readers find the same scopes, spans and events in every
    training cell, so their entries list none."""
    mine = {m["name"] for m in cells.resolve_cell(ROOT, workload).per_layer}
    assert mine >= {
        "fwd_time_share.train", "bwd_time_share.train",
        "opt_time_share.train", "idle_attributed_share.train",
        "dispatch_ms_per_step.train", "loader_wait_ms_per_step.train",
        "host_init_s", "step_trace_s", "step_backend_compile_s",
        "xla_cache_load_s", "xla_cache_misses", "setup_attributed_share",
        "retraces_after_warmup"}
    # and, where the system runs a model, the whole step's share of the
    # chip's peak beside the kernels' rooflines
    assert "mfu.train" in mine


def test_the_folded_names_are_gone_and_their_survivors_stand():
    with open(os.path.join(BENCH, "testdata", "folded_names.json")) as f:
        renamed = json.load(f)["renamed"]
    assert len(renamed) >= 41
    for was, now in renamed.items():
        assert was not in ENTRIES, was
        assert not os.path.exists(os.path.join(
            READERS, cells.metric_file(was) + ".py")), was
        assert now in ENTRIES, now


def test_the_expert_cells_report_the_experts_readings():
    """Every cell with a routed-experts layer (those that report its
    dropless counter) reports the overflow, the load and the router's
    share too, and its expert layers' share under one entry or
    another."""
    expert = set(ENTRIES["moe_dropped_assignments"]["workloads"])
    assert len(expert) >= 9
    for name in ("moe_overflow_layer_steps", "moe_load_max_over_mean",
                 "moe_route_time_share.train"):
        assert set(ENTRIES[name]["workloads"]) >= expert, name
    shares = [m for n, m in ENTRIES.items()
              if n.endswith("moe_time_share.train")]
    assert set().union(*(m["workloads"] for m in shares)) >= expert
    assert set(ENTRIES["moe_shared_time_share.train"]["workloads"]) \
        <= expert
