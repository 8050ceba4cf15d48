"""Run every OSDI'22-artifact A/B (searched strategy vs data parallel)
and record the results as JSON — the reference's ``scripts/osdi22ae/``
produce these numbers by hand; here one command captures them all.

Default platform: whatever jax exposes (real TPU under the driver, or
force the 8-device CPU mesh with ``JAX_PLATFORMS=cpu XLA_FLAGS=
--xla_force_host_platform_device_count=8``). Each model runs in its own
subprocess so one failure cannot take down the sweep.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
EXAMPLES = os.path.dirname(HERE)

# (script, extra args) — batch sizes sized for the CPU sim; pass
# --batch-size on the command line to override for a real chip
MODELS = [
    ("mnist_mlp.py", ["-b", "32"]),
    ("alexnet_cifar10.py", ["-b", "8"]),
    ("dlrm.py", ["-b", "32"]),
    ("xdl.py", ["-b", "32"]),
    ("candle_uno.py", ["-b", "16"]),
    ("transformer.py", ["-b", "8"]),
    ("bert.py", ["-b", "4"]),
    ("inception.py", ["-b", "4"]),
    ("resnext50.py", ["-b", "4"]),
]

_LINE = re.compile(r"\[(?P<name>[\w-]+)\] (?P<mode>data-parallel|searched):"
                   r" (?P<sps>[\d.]+) samples/s"
                   r"(?: \(std (?P<std>[\d.]+), n=(?P<n>\d+))?")
_RATIO = re.compile(r"searched vs data-parallel: (?P<ratio>[\d.]+)x")
_PRED = re.compile(r"predicted searched-vs-dp: (?P<ratio>[\d.]+)x")
_GUARD = re.compile(r"floor-guard adopted: (?P<which>\w+)")


if EXAMPLES not in sys.path:
    sys.path.insert(0, EXAMPLES)
# _stats is stdlib-only: the sweep parent must stay importable when the
# framework/jax is broken (failures belong in per-model subprocess rows)
from _stats import spearman as _spearman  # noqa: E402


def main():
    extra = sys.argv[1:]
    results = {}
    for script, args in MODELS:
        # --floor-guard true: the searched leg times itself against the
        # DP program and falls back when it measures slower, so no A/B
        # row can lose to data parallel by more than timing noise.
        # --repeats 3: each leg's steady-state loop is timed three times
        # so every sps row carries a stddev; --min-steps 8 floors the
        # short bert/transformer loops so per-run noise stays bounded
        cmd = [sys.executable, os.path.join(EXAMPLES, script), "--ab",
               "--budget", "8", "--floor-guard", "true",
               "--repeats", "3", "--min-steps", "8"] + args + extra
        t0 = time.time()
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600, cwd=EXAMPLES)
            out = r.stdout
            entry = {"rc": r.returncode,
                     "wall_s": round(time.time() - t0, 1)}
            for m in _LINE.finditer(out):
                key = "dp_sps" if m.group("mode") == "data-parallel" \
                    else "searched_sps"
                entry[key] = float(m.group("sps"))
                if m.group("std") is not None:
                    entry[key + "_std"] = float(m.group("std"))
                    entry[key + "_n"] = int(m.group("n"))
            m = _RATIO.search(out)
            if m:
                entry["searched_vs_dp"] = float(m.group("ratio"))
            # ratio error from per-leg standard errors of the mean
            # (the sps values are means over n runs, so their
            # uncertainty is std/sqrt(n), not the raw run-to-run std)
            if ("searched_vs_dp" in entry and "dp_sps_std" in entry
                    and "searched_sps_std" in entry
                    and entry.get("dp_sps", 0) > 0
                    and entry.get("searched_sps", 0) > 0):
                sem_dp = (entry["dp_sps_std"]
                          / entry.get("dp_sps_n", 1) ** 0.5)
                sem_s = (entry["searched_sps_std"]
                         / entry.get("searched_sps_n", 1) ** 0.5)
                rel = ((sem_dp / entry["dp_sps"]) ** 2
                       + (sem_s / entry["searched_sps"]) ** 2) ** 0.5
                entry["searched_vs_dp_std"] = round(
                    entry["searched_vs_dp"] * rel, 4)
            m = _PRED.search(out)
            if m:
                entry["predicted_searched_vs_dp"] = float(m.group("ratio"))
            m = _GUARD.search(out)
            if m:
                entry["floor_guard_adopted"] = m.group("which")
            if r.returncode != 0:
                entry["error"] = (r.stderr.strip().splitlines()
                                  or ["?"])[-1][:200]
        except subprocess.TimeoutExpired:
            entry = {"rc": -1, "error": "timeout",
                     "wall_s": round(time.time() - t0, 1)}
        results[script] = entry
        print(f"{script}: {entry}", flush=True)
    # platform info WITHOUT initializing a backend in this process: a
    # chip belongs to one process at a time, and the per-model
    # subprocesses are the ones that need it
    doc = {"jax_platforms_env": os.environ.get("JAX_PLATFORMS", "default"),
           "results": results}
    # predicted-vs-measured fidelity across workloads: Spearman rank
    # correlation of the cost model's searched/dp prediction against the
    # measured throughput ratio (the reference's trust in graph_optimize
    # rests on exactly this fidelity, simulator.cc:537)
    # guard-rejected rows measure DP-vs-DP, not the predicted strategy —
    # they carry no fidelity signal and would poison the correlation
    pairs = [(e["predicted_searched_vs_dp"], e["searched_vs_dp"])
             for e in results.values()
             if "predicted_searched_vs_dp" in e and "searched_vs_dp" in e
             and e.get("floor_guard_adopted") != "dp"]
    if len(pairs) >= 3:
        doc["predicted_vs_measured_spearman"] = round(
            _spearman([p for p, _ in pairs], [m for _, m in pairs]), 4)
        doc["n_correlated"] = len(pairs)
    out_path = os.path.join(HERE, "osdi22ae_results.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
