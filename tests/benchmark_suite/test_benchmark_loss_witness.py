"""``check loss_fell`` of the train runner (``benchmarks/runners/
train.py``): the witness that the step trains, judged on a quantity
the seed cannot decide.

The pool's first batch is stepped ``JUDGED_STEPS`` times on its own,
under one dropout mask, from zeroed Adam moments and the weights the
loop left; the check is that its last loss is strictly under its first.
Here: the judgement as a pure function on loss sequences recorded on
the v5e in PR 27's sweep, and the runner itself on the tiny
``seq_cls`` cell of ``test_benchmark_harness.py`` (dropout 0.1, per-chip
batch 1 on the 8-device CPU mesh) with the update switched off, turned
round, and as the cell has it.
"""
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_benchmark_harness as harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import cells  # noqa: E402

RUNNER = cells.load_module(harness.BENCH, "runners", "train")
stub_profiler = harness.stub_profiler      # the recorded chip trace

# The first six losses of four descents as the chip read them (my chip
# runs, PR 27; PERF.md section 6 has the table), each with the verdict it
# must get.
RECORDED = {
    # bert_large.train.1chip, seed 2147510011: the run whose eval-mode
    # pair rose, 0.5924 -> 0.6527, and failed the old witness
    "falls": ([0.7895785570144653, 0.4180005192756653, 0.2293373942375183,
               0.16372674703598022, 0.11103636026382446,
               0.06225723773241043], True),
    # gpt2_124m.train.1chip, seed 2710001, traced: the first update of
    # fresh moments overshoots, the fifth is far under the start
    "first_step_rises": ([9.546159744262695, 9.586326599121094,
                          9.465719223022461, 9.406658172607422,
                          9.31741714477539, 9.223072052001953], True),
    # bert_large at batch 8 x 512 with alpha = 0, seed 2720001
    "flat_to_the_last_bit": ([0.6735786199569702] * 6, False),
    # the same with alpha = -1e-6
    "rises": ([9.905193328857422, 10.259142875671387, 10.691360473632812,
               11.133926391601562, 11.557633399963379,
               11.949792861938477], False),
}


def _adam(alpha):
    return {"class": "flexflow_tpu:AdamOptimizer", "args": {"alpha": alpha}}


MIXES = {
    "own": harness.TINY_TRAFFIC,                       # alpha 1e-3
    "slow": dict(harness.TINY_TRAFFIC, optimizer=_adam(1e-5)),
    "alpha0": dict(harness.TINY_TRAFFIC, optimizer=_adam(0.0)),
    "negated": dict(harness.TINY_TRAFFIC, optimizer=_adam(-1e-3)),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the tiny ``seq_cls`` configuration under four
    mixes that differ in the optimiser's alpha alone."""
    root = str(tmp_path_factory.mktemp("witness"))
    shutil.copytree(harness.BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    base, over = harness.TINY["bert_tiny"]
    for mix, body in MIXES.items():
        harness.add_cell(root, f"bert_{mix}", base, over, f"tiny_{mix}",
                         body)
    return root


def _replace_descent(monkeypatch, make):
    """Every runner module loaded from now on gets ``make(its
    _descent)`` in that function's place."""
    real = cells.load_module

    def load(bench_dir, sub, name):
        mod = real(bench_dir, sub, name)
        if sub == "runners":
            mod._descent = make(mod._descent)
        return mod

    monkeypatch.setattr(cells, "load_module", load)


@pytest.fixture
def spy(monkeypatch):
    """Keep what the runner's ``_descent`` returned, whether the jitted
    step was traced again for it, and how many programs the backend
    compiled while it ran."""
    import jax
    seen = {"compiles": 0, "compiles_in_descent": 0, "inside": False}

    def on_event(name, *a, **k):
        if name == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compiles_in_descent"] += seen["inside"]

    def make(inner):
        def descent(ff, batch, steps):
            step = ff.executor.make_train_step().__wrapped__
            seen["traces"] = [step._cache_size()]
            seen["inside"] = True
            try:
                seen["losses"] = inner(ff, batch, steps)
            finally:
                seen["inside"] = False
            seen["traces"].append(step._cache_size())
            seen["steps"] = steps
            return seen["losses"]
        return descent

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _replace_descent(monkeypatch, make)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on_event)


def _run(root, mix, seed, trace=False):
    said = []
    res = bench_run.run_cell(root, f"bert_{mix}.train", seed, 0.3, trace,
                             say=said.append)
    checks = {s.split()[1].rstrip(":"): s for s in said
              if s.startswith("check ")}
    return res, checks


def _eval_pair(line):
    """The eval-mode pair at the end of the check's line."""
    before, arrow, after = line.split()[-3:]
    assert arrow == "->"
    return float(before), float(after)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_judgement_on_sequences_the_chip_recorded(name):
    losses, want = RECORDED[name]
    assert RUNNER.loss_fell(losses) is want


@pytest.mark.parametrize("losses, want", [
    ([0.7, 0.6999999], True),
    ([0.7, 0.7], False),                # strictly: equal is not fallen
    ([0.7, 0.71, 0.69], True),          # only the ends are judged
    ([0.7, 0.69, 0.71], False),
    ([0.7], False), ([], False),        # nothing to judge
    ([0.7, float("nan")], False), ([float("nan"), 0.7], False),
])
def test_the_judgement_is_strict_and_reads_the_ends_alone(losses, want):
    assert RUNNER.loss_fell(losses) is want


def test_with_alpha_zero_every_judged_loss_is_the_same_to_the_bit(root,
                                                                 spy):
    """One step index, one mask, no update: one loss, k + 1 times. So
    the losses are a function of the weights alone, and the strict
    inequality fails a step that does not train."""
    res, checks = _run(root, "alpha0", 3)
    assert len(spy["losses"]) == RUNNER.JUDGED_STEPS + 1
    assert len(set(spy["losses"])) == 1, spy["losses"]
    assert "FAILED" in checks["loss_fell"]
    others = {k: v for k, v in checks.items()
              if k not in ("loss_fell", "device")}
    assert others and all(" ok - " in v for v in others.values()), checks
    assert res["correct"] is False


def test_with_the_update_negated_the_judged_loss_rises(root, spy):
    _, checks = _run(root, "negated", 3)
    assert spy["losses"][-1] > spy["losses"][0]
    assert "FAILED" in checks["loss_fell"]
    assert " ok - " in checks["finite_losses"]


@pytest.mark.parametrize("mix, seed", [
    ("own", 3), ("own", 2 ** 31 + 11), ("slow", 1), ("slow", 2 ** 31 + 6)])
def test_with_the_cells_own_alpha_the_judged_loss_falls(root, spy, mix,
                                                         seed):
    _, checks = _run(root, mix, seed)
    assert spy["losses"][-1] < spy["losses"][0]
    assert " ok - " in checks["loss_fell"], checks["loss_fell"]
    # the line carries the judged losses and the pair no longer judged
    assert f"{spy['losses'][0]:.6f} -> {spy['losses'][-1]:.6f}" \
        in checks["loss_fell"]
    before, after = _eval_pair(checks["loss_fell"])
    assert before > 0 and after > 0


def test_it_holds_where_the_eval_mode_pair_it_replaced_rose(root, spy):
    """Seed 6 at alpha 1e-5 starts batch 0 under ln 2 (its labels agree
    with the initial gap); training on the pool pushes its eval-mode
    loss UP, which the old witness read as a step that does not train.
    """
    res, checks = _run(root, "slow", 6)
    before, after = _eval_pair(checks["loss_fell"])
    assert before < 0.6931 and after > before, checks["loss_fell"]
    assert " ok - " in checks["loss_fell"]
    bad = [v for k, v in checks.items()
           if k != "device" and " ok - " not in v]
    assert not bad, bad


@pytest.mark.parametrize("trace", [0, 1])
def test_the_judged_steps_compile_nothing_and_move_no_metric(
        root, spy, monkeypatch, request, trace):
    """The same jitted step, the same shapes: no new trace of it, no
    program compiled while the judged steps run. And they run after
    everything a metric reads: a run whose judged steps are taken out
    reports every count-valued number as this one does."""
    if trace:
        request.getfixturevalue("stub_profiler")
    res, checks = _run(root, "own", 5, bool(trace))
    assert spy["traces"] == [1, 1]
    assert spy["compiles"] > 0 and spy["compiles_in_descent"] == 0
    assert spy["steps"] == RUNNER.JUDGED_STEPS

    _replace_descent(monkeypatch,
                     lambda inner: lambda ff, batch, steps: [1.0, 0.5])
    bare, _ = _run(root, "own", 5, bool(trace))
    # what the host's clock gives differs from run to run: the rate,
    # every reading in seconds or milliseconds, and the share of the
    # set-up's seconds under a span (every cell reports the set-up's
    # readings, this test's tiny cell among them)
    clocked = {"train_tokens_per_s", "setup_attributed_share"} | {
        name for name, m in res["metrics"].items()
        if m["unit"] in ("s", "ms")}
    assert set(res["metrics"]) == set(bare["metrics"])
    for name in set(res["metrics"]) - clocked:
        assert res["metrics"][name] == bare["metrics"][name], name
    # (attempted is the count of groups the 0.3 s window held)
    assert (res["correct"], res["failed"]) == (bare["correct"],
                                               bare["failed"])
    assert res.get("breakdown") == bare.get("breakdown")
    with_peak = dict(res["device"], memory_peak_bytes=0)
    assert with_peak == dict(bare["device"], memory_peak_bytes=0)


def test_the_runner_says_what_correct_judges_check_by_check():
    doc = RUNNER.__doc__
    for check in ("device", "initial_loss", "reference", "finite_losses",
                  "no_compile_in_window", "loss_fell"):
        assert f"``{check}``" in doc, check
    assert isinstance(RUNNER.JUDGED_STEPS, int) and RUNNER.JUDGED_STEPS > 1
