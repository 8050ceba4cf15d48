"""A/B: DLRM with banked (device-subset) embedding placement vs
whole-mesh data parallelism, measured with real timed train steps.

Reference analog: the DLRM strategies placing embedding tables on
disjoint GPU subsets (``examples/cpp/DLRM/strategies/``). The banked
side shrinks the dense table-gradient all-reduce and the optimizer
update by the bank degree; this script measures that on the live mesh.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/dlrm_banked_ab.py --rows 200000 --steps 10 \
      --out bench_results/r04_dlrm_banked_ab.json
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np


def _vocabs(rows: int, hetero: bool):
    if not hetero:
        return (rows,) * 4
    # heterogeneous tables averaging `rows` (the padded-bank case: the
    # reference's MachineView places NON-identical tables on subsets)
    return (rows // 2, rows * 3 // 4, rows * 5 // 4, rows * 3 // 2)


def build(banked: bool, rows: int, batch: int, hetero: bool = False):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import DLRMConfig, build_dlrm
    from flexflow_tpu.parallel.banks import (BankSpec, choose_bank_axes,
                                             find_bank_groups,
                                             group_is_padded)
    from flexflow_tpu.parallel.strategy import ShardingStrategy
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    dcfg = DLRMConfig(embedding_size=_vocabs(rows, hetero))
    out = build_dlrm(ff, batch, dcfg)
    if not banked:
        ff.compile(SGDOptimizer(0.05), "sparse_categorical_crossentropy",
                   [], output_tensor=out)
        return ff, None
    ff.compile(SGDOptimizer(0.05), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    st = ShardingStrategy.data_parallel(ff.layers, ff.graph_inputs,
                                        ff.dmesh)
    groups = find_bank_groups(ff.layers)
    assert groups, "no bank group found"
    padded = group_is_padded(groups[0])
    assert padded == hetero
    bank_axes, batch_axes = choose_bank_axes(ff.dmesh, len(groups[0]))
    bk = BankSpec([l.name for l in groups[0]], bank_axes,
                  batch_axes=batch_axes, param_name="__bank0__EMB",
                  padded=padded)
    st.banks = [bk]
    ff.compile(SGDOptimizer(0.05), "sparse_categorical_crossentropy", [],
               strategy=st, output_tensor=out)
    return ff, bk


def timed(ff, batch: int, steps: int, repeats: int):
    rng = np.random.default_rng(0)
    b = {}
    for t in ff.graph_inputs:
        if "sparse" in t.name:
            b[t.name] = rng.integers(0, 1000, size=t.shape).astype(np.int32)
        else:
            b[t.name] = rng.normal(size=t.shape).astype(np.float32)
    b["label"] = rng.integers(0, 2, size=(batch, 1)).astype(np.int32)
    step = ff.executor.make_train_step()
    bm = ff._run_train_step(step, b)
    float(np.asarray(bm["loss"]))     # compile + sync (D2H fetch)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            bm = ff._run_train_step(step, b)
        float(np.asarray(bm["loss"]))
        times.append((time.perf_counter() - t0) / steps)
    return (statistics.median(times),
            statistics.stdev(times) if len(times) > 1 else 0.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200000)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--hetero", action="store_true",
                    help="heterogeneous vocab sizes (padded banks)")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    import jax
    ff_dp, _ = build(False, a.rows, a.batch, a.hetero)
    t_dp, sd_dp = timed(ff_dp, a.batch, a.steps, a.repeats)
    del ff_dp
    ff_bk, bk = build(True, a.rows, a.batch, a.hetero)
    t_bk, sd_bk = timed(ff_bk, a.batch, a.steps, a.repeats)
    rec = {
        "workload": (f"dlrm_4x{a.rows}x64" if not a.hetero else
                     "dlrm_hetero_" + "x".join(
                         str(v) for v in _vocabs(a.rows, True))),
        "padded_banks": a.hetero,
        "platform": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "bank_axes": list(bk.axes),
        "bank_degree": bk.bank_degree(ff_bk.dmesh),
        "whole_mesh_s_per_step": round(t_dp, 6),
        "whole_mesh_stdev": round(sd_dp, 6),
        "banked_s_per_step": round(t_bk, 6),
        "banked_stdev": round(sd_bk, 6),
        "speedup": round(t_dp / t_bk, 4),
        "steps": a.steps, "repeats": a.repeats,
    }
    print(json.dumps(rec))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
