"""On-chip MFU investigation for the flagship BERT-base train step.

Captures (a) a jax.profiler trace of the hot loop (where do the
non-matmul cycles go) and (b) an MFU sweep over three levers: bf16
activations end-to-end, flash attention on/off, and batch size. One JSON
line per config; summary written to ``--out``. One process: it holds the
chip itself and starts no children. Not yet run under this JAX (ROADMAP
S3 folds what survives into the cell benchmark).

Run on the chip:
  python examples/tpu_profile_bert.py [--configs base,bf16act,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _sync(x):
    return float(np.asarray(x))


CONFIGS = {
    # name -> (flash, bf16_activations, batch, seq)
    "tiny":       ("auto",  False, 8, 32),    # CPU smoke of the harness
    "base":       ("auto",  False, 16, 128),
    "bf16act":    ("auto",  True,  16, 128),
    "flash_on":   ("true",  False, 16, 128),
    "flash_off":  ("false", False, 16, 128),
    "b32":        ("auto",  False, 32, 128),
    "b32_bf16":   ("auto",  True,  32, 128),
    "b64_bf16":   ("auto",  True,  64, 128),
    "seq512_flash": ("true", True, 8, 512),
}


def run_config(name, flash, bf16_act, batch, seq, steps, trace_dir=None):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import BertConfig, build_bert
    from flexflow_tpu.parallel.machine import MachineSpec
    from bench import timed_mfu

    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.only_data_parallel = True
    cfg.use_flash_attention = flash
    cfg.bf16_activations = bf16_act
    ff = FFModel(cfg)
    bcfg = BertConfig.tiny() if name == "tiny" else BertConfig.base()
    bcfg.max_position = seq
    bcfg.dropout = 0.1
    out = build_bert(ff, batch, seq, bcfg)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(0, bcfg.vocab_size,
                                   size=(batch, seq)).astype(np.int32),
         "position_ids": np.tile(np.arange(seq, dtype=np.int32),
                                 (batch, 1)),
         "label": rng.integers(0, 2, size=(batch, 1)).astype(np.int32)}
    if trace_dir:
        # warm the compile first so the trace captures steady-state steps
        step = ff.executor.make_train_step()
        for _ in range(2):
            bm = ff._run_train_step(step, b)
        _sync(bm["loss"])
        import jax.profiler
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                bm = ff._run_train_step(step, b)
            _sync(bm["loss"])
    # shared bench harness: per-chip sps + MFU, same conventions as
    # BENCH_r* records
    sps, mfu, flops, n_chips, dt, sps_std = timed_mfu(ff, b, steps)
    spec = MachineSpec.detect()
    rec = {"config": name, "flash": flash, "bf16_act": bf16_act,
           "batch": batch, "seq": seq, "steps": steps, "n_chips": n_chips,
           "sps_per_chip": round(sps, 2),
           "sps_std": round(sps_std, 2),
           "ms_per_step": round(dt / steps * 1e3, 3),
           "mfu": round(mfu, 4), "generation": spec.generation}
    print(json.dumps(rec), flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--trace", default="",
                    help="config name to capture a profiler trace for")
    ap.add_argument("--out", default=os.path.join(
        REPO, "bench_results", "r03_profile.json"))
    a = ap.parse_args()
    import jax
    print(f"platform: {jax.default_backend()} {jax.devices()}", flush=True)
    results = []
    for name in a.configs.split(","):
        flash, bf16_act, batch, seq = CONFIGS[name.strip()]
        trace_dir = None
        if a.trace and a.trace == name:
            trace_dir = os.path.join(REPO, "bench_results",
                                     f"trace_{name}")
        try:
            results.append(run_config(name, flash, bf16_act, batch, seq,
                                      a.steps, trace_dir))
        except Exception as e:  # noqa: BLE001 — continue the sweep
            results.append({"config": name, "error": repr(e)[:300]})
            print(json.dumps(results[-1]), flush=True)
    doc = {"platform": jax.default_backend(),
           "captured": time.strftime("%Y-%m-%d %H:%M:%S"),
           "results": results}
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {a.out}", flush=True)


if __name__ == "__main__":
    main()
