"""Shared runner for the example suite.

The reference's examples double as its integration suite (SURVEY.md §4:
``tests/multi_gpu_tests.sh`` runs every example with accuracy callbacks);
these examples follow the same pattern: build a model from the zoo, train
on synthetic (or downloaded) data, print throughput, and — with ``--ab`` —
run the searched-strategy vs data-parallel A/B the OSDI'22 artifact scripts
perform (``scripts/osdi22ae/*.sh``).
"""
from __future__ import annotations

import os
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

# examples are runnable standalone (cwd=examples/) without pip-installing
# the package: put the repo root on sys.path ahead of the import below
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer


def run_example(name: str, build: Callable[[FFModel, FFConfig], object],
                make_batch: Callable[[FFConfig, np.random.Generator], Dict],
                loss: str = "sparse_categorical_crossentropy",
                metrics=("accuracy",), steps: int = 20,
                argv: Optional[list] = None):
    """Build + train `steps` iterations; honors reference CLI flags.

    With --ab: times data-parallel THEN the searched strategy on the same
    model/batch and reports the ratio (the osdi22ae A/B)."""
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    ab = "--ab" in argv
    if ab:
        argv.remove("--ab")
    def _take_int_flag(flag: str, default: int) -> int:
        """Pop `--flag N` or `--flag=N` from argv; clear error if N is
        missing/non-numeric (FFConfig would reject the leftover flag)."""
        for i, a in enumerate(argv):
            if a == flag or a.startswith(flag + "="):
                if "=" in a:
                    raw, end = a.split("=", 1)[1], i + 1
                else:
                    if i + 1 >= len(argv):
                        raise SystemExit(f"{flag} requires a value")
                    raw, end = argv[i + 1], i + 2
                try:
                    val = int(raw)
                except ValueError:
                    raise SystemExit(f"{flag} expects an int, got {raw!r}")
                del argv[i:end]
                return val
        return default

    repeats = max(1, _take_int_flag("--repeats", 1))
    steps = max(steps, _take_int_flag("--min-steps", 0))
    cfg = FFConfig.parse_args(argv)

    def timed(only_dp: bool) -> float:
        c = FFConfig.parse_args(argv)
        c.only_data_parallel = only_dp or cfg.only_data_parallel
        ff = FFModel(c)
        out = build(ff, c)
        ff.compile(SGDOptimizer(c.learning_rate), loss, list(metrics),
                   output_tensor=out if out is not None else None)
        rng = np.random.default_rng(0)
        b = make_batch(c, rng)
        step = ff.executor.make_train_step()
        bm = ff._run_train_step(step, b)     # compile + warmup
        float(np.asarray(bm["loss"]))
        # --repeats N times the steady-state loop N times on the same
        # compiled step and reports mean +/- stddev, so A/B ratios carry
        # error bars instead of a single noisy sample
        runs = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(steps):
                bm = ff._run_train_step(step, b)
            loss_v = float(np.asarray(bm["loss"]))  # D2H sync
            dt = time.perf_counter() - t0
            runs.append(c.batch_size * steps / dt)
        sps = float(np.mean(runs))
        std = float(np.std(runs, ddof=1)) if len(runs) > 1 else 0.0
        mode = "data-parallel" if c.only_data_parallel else "searched"
        # fixed-point, never scientific: osdi22ae/run_all.py parses this
        print(f"[{name}] {mode}: {sps:.3f} samples/s "
              f"(std {std:.3f}, n={repeats}, loss {loss_v:.4f}, "
              f"{steps} steps in {dt:.2f}s)")
        pred = getattr(ff, "_search_predicted", None)
        if pred and not c.only_data_parallel:
            ratio = pred["dp_cost_s"] / max(pred["searched_cost_s"], 1e-12)
            print(f"[{name}] predicted searched-vs-dp: {ratio:.4f}x")
        guard = getattr(ff, "_floor_guard_record", None)
        if guard and not c.only_data_parallel:
            # "adopted: <which>" is parsed by osdi22ae/run_all.py
            print(f"[{name}] floor-guard adopted: {guard['adopted']}"
                  if "adopted" in guard else
                  f"[{name}] floor-guard skipped: {guard['skipped']}")
        assert np.isfinite(loss_v)
        return sps

    if ab:
        dp = timed(only_dp=True)
        searched = timed(only_dp=False)
        print(f"[{name}] searched vs data-parallel: {searched / dp:.2f}x")
    else:
        timed(only_dp=cfg.only_data_parallel)
