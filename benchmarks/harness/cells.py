"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a configuration, a traffic mix, a runner kind or a
per-layer metric by name: each is a file of its own, so a later PR adds
a cell by adding files and entries and edits nothing that is there.

  <root>/BENCHMARK.json
  <root>/<paths[0]>/configs/<config>.json        (its ``file`` entry)
  <root>/<paths[0]>/traffic/<traffic>.json       parameters of one mix
  <root>/<paths[0]>/runners/<kind>.py            ``run(cell, ...)``
  <root>/<paths[0]>/flops/<config>.py            operations per token
  <root>/<paths[0]>/reference/<module>.py        the plain reference
  <root>/<paths[0]>/layer_metrics/<metric>.py    ``read(ctx)``, or
  <root>/<paths[0]>/layer_metrics/<metric>.json  ``{"reader": "<metric>"}``

The second form is how a new cell reports a reading whose reader is
there already under an entry that lists other cells (an accepted entry
is not edited): the PR that brings the cell appends an entry of its own
name that lists the cell, and a file that names the accepted entry. It
copies no code.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


class BenchmarkError(Exception):
    """The benchmark's own files do not say what a run needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    root: str             # the checkout
    bench_dir: str        # <root>/<paths[0]>
    config_name: str
    config: dict          # the configuration's file
    traffic_name: str
    traffic: dict         # the traffic mix's file
    end_to_end: list      # the metric entries this cell reports
    per_layer: list


def load_manifest(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _reported_by(metrics: list, cell_name: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or cell_name in m["workloads"]]


def resolve_cell(root: str, workload: str) -> Cell:
    man = load_manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in man['workloads']]})")
    conf = next((c for c in man["configs"] if c["name"] == entry["config"]),
                None)
    if conf is None:
        raise BenchmarkError(f"workload {workload!r} names the unknown "
                             f"configuration {entry['config']!r}")
    bench_dir = os.path.join(root, man["paths"][0])
    return Cell(
        name=workload, chips=int(entry["chips"]), root=root,
        bench_dir=bench_dir, config_name=conf["name"],
        config=_load_json(os.path.join(root, conf["file"])),
        traffic_name=entry["traffic"],
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        entry["traffic"] + ".json")),
        end_to_end=_reported_by(man["end_to_end"], workload),
        per_layer=_reported_by(man["per_layer"], workload))


def load_module(bench_dir: str, sub: str, name: str):
    """The module ``<bench_dir>/<sub>/<name>.py``, loaded from that file
    (not through ``sys.path``), or None if there is no such file."""
    path = os.path.join(bench_dir, sub, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"_bench_{sub}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, metric: str):
    """The per-layer metric's reader: the module of its own file, or of
    the file of the entry that its ``.json`` names (one step, no chain);
    None if it has neither."""
    name = metric_file(metric)
    alias = os.path.join(bench_dir, "layer_metrics", name + ".json")
    if os.path.isfile(alias):
        name = metric_file(_load_json(alias)["reader"])
    return load_module(bench_dir, "layer_metrics", name)


def load_attr(dotted: str):
    """``package.module:attr`` of the program under test."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def metric_file(name: str) -> str:
    """A per-layer metric's reader is named after it, dots as
    underscores: ``step_ms.train`` -> ``step_ms_train``."""
    return name.replace(".", "_").replace("-", "_")
