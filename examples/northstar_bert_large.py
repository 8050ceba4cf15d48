"""North-star demonstration (BASELINE.md): Unity-searched BERT-large on a
v5e-32 pod slice vs pure data parallelism — THROUGH THE PRODUCT PATH.

The winner comes from ``FFModel.compile`` with the same flags a user
would pass::

  --budget 8 --enable-pipeline-search --machine-model-version 1 \
  --machine-model-file machine_configs/v5e-32.json

The target machine is described by ``machine_configs/v5e-32.json`` (4x8
ICI torus, 8 hosts) — the analog of the reference's
``--machine-model-file`` (``machine_config_example``) — and strategies
are scored by the native link-level task-graph simulator (machine model
v1, ``search/tasksim.py`` + ``flexflow_tpu/native/src/ffruntime.cc``), the analog of
``Simulator::simulate_runtime`` (``src/runtime/simulator.cc``). No
multi-chip hardware is needed: a 32-virtual-device CPU mesh stands in
for the pod (same mechanism as ``tests/conftest.py``), exactly how the
reference searches for N-GPU strategies from a simulator-equipped
single process (``graph.cc:2046``).

Usage:
  python examples/northstar_bert_large.py [--budget 8] [--batch 64]
      [--seq 512] [--out bench_results/northstar_v5e32_sim.json]
"""
import argparse
import json
import os
import sys
import time

import re as _re

_flags = os.environ.get("XLA_FLAGS", "")
_m = _re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
if _m is None or int(_m.group(1)) < 32:
    # keep a LARGER pre-set count (e.g. 64 for the 2-slice machine)
    want = "--xla_force_host_platform_device_count=32"
    _flags = _flags.replace(_m.group(0), want) if _m \
        else (_flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = _flags
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `python examples/northstar_bert_large.py` puts examples/ (not the
# repo root) on sys.path; make the import work without an installed
# package or PYTHONPATH (same idiom as tpu_memory_validation.py)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer  # noqa: E402
from flexflow_tpu.models import BertConfig, build_bert  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--machine", default=os.path.join(
        REPO, "machine_configs", "v5e-32.json"))
    ap.add_argument("--out", default=os.path.join(
        REPO, "bench_results", "northstar_v5e32_sim.json"))
    a = ap.parse_args()

    # the EXACT product flag spelling (FFConfig.parse_args) — this run
    # is the same code path as any user invocation
    cfg = FFConfig.parse_args([
        "--batch-size", str(a.batch),
        "--budget", str(a.budget),
        "--enable-pipeline-search",
        "--machine-model-version", "1",
        "--machine-model-file", a.machine,
    ])
    from flexflow_tpu.parallel.topology import load_machine_file
    want = load_machine_file(a.machine).num_devices
    assert len(jax.devices()) >= want, \
        (f"need {want} virtual devices for {a.machine}, have "
         f"{len(jax.devices())} — raise "
         f"--xla_force_host_platform_device_count")

    ff = FFModel(cfg)
    bcfg = BertConfig()          # defaults are BERT-large
    bcfg.max_position = a.seq
    out = build_bert(ff, a.batch, a.seq, bcfg)
    n_ops = len(ff.layers)
    print(f"bert-large graph: {n_ops} layers, batch {a.batch}, "
          f"seq {a.seq}", flush=True)

    t0 = time.perf_counter()
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    compile_s = time.perf_counter() - t0
    spec = ff.dmesh.spec
    print(f"machine: {spec.generation} x{spec.num_devices} "
          f"hosts={spec.num_hosts}; compile {compile_s:.1f}s", flush=True)

    pred = getattr(ff, "_search_predicted", None)
    assert pred is not None, "search did not record predicted costs"
    dp_ms = pred["dp_cost_s"] * 1e3
    cand = getattr(ff, "_pipeline_choice", None)
    if ff.executor.pipe is not None and cand is not None:
        kind = (f"pipeline_dp{cand.dp_size}xpp{cand.n_stages}"
                f"_m{cand.n_microbatches}")
        if cand.tp > 1:
            kind += f"_tp{cand.tp}"
        if cand.n_chunks > 1:
            kind += f"_interleaved{cand.n_chunks}"
        searched_ms = cand.cost * 1e3
    else:
        kind = "sharding"
        searched_ms = pred["searched_cost_s"] * 1e3
    speedup = dp_ms / max(searched_ms, 1e-9)
    print(f"data-parallel simulated step: {dp_ms:.3f} ms", flush=True)
    print(f"searched simulated step:      {searched_ms:.3f} ms "
          f"({kind})", flush=True)
    print(f"SEARCHED vs DATA-PARALLEL: {speedup:.2f}x "
          f"(north star: >= 1.5x)", flush=True)

    doc = {
        "_comment": "Simulated (machine-model-v1 link-level task sim) "
                    "searched-vs-DP step time for BERT-large on the "
                    "v5e-32 description, selected by FFModel.compile "
                    "with --enable-pipeline-search (the product path). "
                    "Regenerate: python examples/northstar_bert_large.py",
        "machine": os.path.basename(a.machine),
        "model": "bert-large",
        "batch": a.batch,
        "seq": a.seq,
        "budget": a.budget,
        "n_ops": n_ops,
        "dp_ms": round(dp_ms, 3),
        "searched_ms": round(searched_ms, 3),
        "winner": kind,
        "speedup": round(speedup, 3),
        "via": "FFModel.compile",
        "compile_time_s": round(compile_s, 1),
        # search vs materialization split (ff._compile_phases): on the
        # virtual CPU mesh the replicated-param host copies dominate
        # compile_time_s; on real hardware they are parallel DMA
        "compile_phases": getattr(ff, "_compile_phases", None),
    }
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {a.out}", flush=True)
    return 0 if speedup >= 1.5 else 1   # the north-star gate itself


if __name__ == "__main__":
    sys.exit(main())
