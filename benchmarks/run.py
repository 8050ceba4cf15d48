#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n>
                              --seconds <s> --trace <0|1>

One process. It exits non-zero, before building anything, unless JAX
reports a TPU and as many chips as the cell asks for; there is no option
that lets it pass without one (the tests import ``run_cell`` instead).
The last line of its standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``. Everything else it has to say
goes on earlier lines.

What a cell is comes from files found by the names in BENCHMARK.json
(``harness/cells.py``); nothing here names a cell, a configuration, a
runner kind or a metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float | None = None, say=say) -> dict:
    """Run the cell and return the result object of the last line."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cells.resolve_cell(root, workload)
    kind = cell.traffic["kind"]
    runner = cells.load_module(cell.bench_dir, "runners", kind)
    if runner is None:
        raise cells.BenchmarkError(
            f"traffic {cell.traffic_name!r} is of kind {kind!r}, and "
            f"there is no runners/{kind}.py")
    res = runner.run(cell, seed, seconds, trace, say, t_start)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            reader = cells.load_reader(cell.bench_dir, m["name"])
            value = None if reader is None else reader.read(res.ctx)
            if value is None:
                say(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = res.end_to_end.get(m["name"])
            if value is None:
                raise cells.BenchmarkError(
                    f"runner {kind!r} gave no end-to-end metric "
                    f"{m['name']!r} for {workload}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(res.correct), "attempted": int(res.attempted),
           "failed": int(res.failed), "metrics": metrics,
           "device": res.device}
    if trace and res.breakdown:
        out["breakdown"] = res.breakdown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.resolve_cell(ROOT, args.workload)
    except cells.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s);"
              f" JAX reports {len(devs)} x {devs[0].device_kind!r} "
              f"(platform {devs[0].platform!r}); refusing to run",
              file=sys.stderr)
        return 2
    say(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {len(devs)} x {devs[0].device_kind}, jax "
        f"{jax.__version__}")
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
