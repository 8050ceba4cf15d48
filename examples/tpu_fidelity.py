"""On-chip simulator-fidelity A/B. Never yet executed on a chip
(ROADMAP S5).

A rank-fidelity number taken on the shared-memory CPU host says little:
no 8-independent-device model can hold there.
This script produces the number that matters: with chip-calibrated
constants (matmul-efficiency microbenchmark + per-op on-device
measurement, the ``simulator.cc:537`` analog), how well do the two
final rankers' predicted step times correlate with MEASURED train-step
times on the real TPU, across a spread of workloads?

Single-chip scope: predictions and
measurements are both for the 1-device data-parallel program, so this
isolates exactly the layer the CPU host could not validate — per-op
compute cost + additive/task-graph composition — with no collective
modelling in the loop. Collective constants are separately fitted by
``calibrate_collectives`` whenever >1 device is visible and recorded.

One subprocess per workload, strictly one at a time: the parent imports
no JAX, because a chip belongs to one process. Each child measures, then
predicts.

Usage:  python examples/tpu_fidelity.py [--steps 10] [--out PATH]
        (CPU smoke: JAX_PLATFORMS=cpu ... --workloads mnist_mlp,dlrm)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from _stats import spearman as _spearman  # noqa: E402

# (builder key, batch) — single-chip-friendly sizes, diverse op mixes:
# embedding-dominated (dlrm/xdl), matmul-dominated (mlp/candle/bert),
# attention (transformer/bert), conv (alexnet)
WORKLOADS = {
    "mnist_mlp": 64,
    "dlrm": 32,
    "xdl": 32,
    "candle_uno": 16,
    "transformer": 8,
    "bert_tiny": 32,
    "bert_base": 8,
    "alexnet_cifar10": 8,
}


def _build(ff, workload: str, batch: int):
    from flexflow_tpu.models import (BertConfig, build_alexnet_cifar10,
                                     build_bert, build_candle_uno,
                                     build_dlrm, build_transformer,
                                     build_xdl)
    if workload == "mnist_mlp":
        import mnist_mlp
        return mnist_mlp.build(ff, ff.config)
    if workload == "dlrm":
        import dlrm
        return build_dlrm(ff, batch, dlrm.CFG)
    if workload == "xdl":
        import xdl
        return build_xdl(ff, batch, xdl.CFG)
    if workload == "candle_uno":
        import candle_uno
        return build_candle_uno(ff, batch, candle_uno.CFG)
    if workload == "transformer":
        import transformer
        return build_transformer(ff, batch, transformer.CFG)
    if workload == "alexnet_cifar10":
        return build_alexnet_cifar10(ff, batch)
    if workload in ("bert_tiny", "bert_base"):
        bcfg = (BertConfig.tiny() if workload == "bert_tiny"
                else BertConfig.base())
        seq = 64 if workload == "bert_tiny" else 128
        bcfg.max_position = seq
        return build_bert(ff, batch, seq, bcfg)
    raise ValueError(workload)


def _child(workload: str, steps: int) -> int:
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    import jax
    import numpy as np
    from bench import timed_mfu

    cfg = FFConfig()
    cfg.batch_size = WORKLOADS[workload]
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    out = _build(ff, workload, cfg.batch_size)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out if out is not None else None)
    from flexflow_tpu.search.optimizer import _synth_batch
    batch = _synth_batch(ff)

    # 1) MEASURE
    sps, mfu, flops, n_chips, dt, sps_std = timed_mfu(ff, batch, steps)
    measured_s = dt / steps

    # 2) PREDICT with chip-calibrated constants
    from flexflow_tpu.search.costmodel import OpCostModel
    from flexflow_tpu.search.tasksim import TaskGraphEvaluator
    from flexflow_tpu.search.unity import (GraphCostEvaluator,
                                           data_parallel_graph)
    cost = OpCostModel(ff.dmesh.spec)
    on_chip = jax.devices()[0].platform != "cpu"
    if on_chip:
        cost.calibrate()
        cost.measure_on_device = True
        cost.measure_budget_s = 90.0
    if ff.dmesh.num_devices > 1:
        cost.calibrate_collectives(ff.dmesh)
    g = data_parallel_graph(
        ff.layers, ff.graph_inputs + getattr(ff, "const_inputs", []),
        [ff._output_tensor], ff.dmesh)
    pred = {}
    for name, ev_cls in (("additive", GraphCostEvaluator),
                         ("tasksim", TaskGraphEvaluator)):
        t0 = time.perf_counter()
        pred[name] = ev_cls(cost, ff.dmesh).graph_cost(g).total
        pred[name + "_eval_s"] = round(time.perf_counter() - t0, 3)
    print("RESULT " + json.dumps({
        "workload": workload, "platform": jax.default_backend(),
        "measured_s": measured_s, "sps_per_chip": round(sps, 2),
        "sps_std": round(sps_std, 2), "mfu": round(mfu, 4),
        "pred_additive_s": pred["additive"],
        "pred_tasksim_s": pred["tasksim"],
        "mxu_eff": round(cost.mxu_eff, 4),
        "coll_bw": cost.coll_bw, "coll_lat": cost.coll_lat,
        "measured_ops": on_chip}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--workloads", default=",".join(WORKLOADS))
    ap.add_argument("--out", default=os.path.join(
        REPO, "bench_results", "r05_ranker_fidelity.json"))
    a = ap.parse_args()
    if a.workload:
        return _child(a.workload, a.steps)
    rows = []
    errors = {}

    def summarize():
        """(Re)write the artifact after every workload, so a stage
        timeout never discards measurements already captured."""
        out = {"rows": rows, "errors": errors,
               "captured": time.strftime("%Y-%m-%d %H:%M:%S"),
               "platform": rows[0]["platform"] if rows else None,
               "scope": ("1-device DP programs: per-op compute cost + "
                         "graph composition fidelity, chip-calibrated "
                         "(simulator.cc:537 analog); collectives not in "
                         "the loop on one device")}
        if len(rows) >= 3:
            meas = [r["measured_s"] for r in rows]
            for k in ("additive", "tasksim"):
                preds = [r[f"pred_{k}_s"] for r in rows]
                out[f"spearman_{k}"] = round(_spearman(preds, meas), 4)
                ratios = [p / m for p, m in zip(preds, meas)]
                out[f"ratio_{k}"] = {
                    r["workload"]: round(p / r["measured_s"], 3)
                    for r, p in zip(rows, preds)}
                gm = 1.0
                for r_ in ratios:
                    gm *= r_
                gm **= 1.0 / len(ratios)
                out[f"geomean_ratio_{k}"] = round(gm, 3)
        tmp = a.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1)
        os.replace(tmp, a.out)
        return out

    for w in a.workloads.split(","):
        w = w.strip()
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--workload", w, "--steps", str(a.steps)],
                capture_output=True, text=True, timeout=600, cwd=HERE)
            got = None
            for line in r.stdout.splitlines():
                if line.startswith("RESULT "):
                    got = json.loads(line[len("RESULT "):])
            if got:
                rows.append(got)
            else:
                errors[w] = (f"rc={r.returncode}: " + (
                    r.stderr.strip().splitlines() or ["?"])[-1][:200])
        except subprocess.TimeoutExpired:
            errors[w] = "timeout"
        summarize()
        print(f"{w}: {rows[-1] if rows and rows[-1]['workload'] == w else errors.get(w)}",
              flush=True)
    out = summarize()
    print(json.dumps({k: v for k, v in out.items()
                      if k.startswith(("spearman", "geomean"))}))
    print(f"wrote {a.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
