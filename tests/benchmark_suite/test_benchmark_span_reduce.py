"""The reduction from the program's names in a profiler trace (phase,
layer and kernel scopes on the device's ops, ``ff:`` host spans) to the
nine per-layer metrics that read them
(``benchmarks/harness/span_reduce.py``, ``benchmarks/flops/
flash_attention.py``, nine files of ``benchmarks/layer_metrics/``).

The witness is ``benchmarks/testdata/span_trace.json``: a trace small
enough to count by hand, with a compiled step's text to go with it
(three of its lines cut from a real compile for a v5e chip). In
microseconds (the file is in nanoseconds):

  marks        |1000 ------------- group 1 ------------|2300 ---- group 2 ----|3400
  host thread  fit.epoch 900 .................................. 2400
                loader_next 1000-1050, 1100-1110, 1150-1160   loader_next 2400-2500, 2560-2570, 2600-2605
                train_step  1050-1100, 1110-1150              train_step  2500-2560, 2570-2600
                flush 1160-2100, callbacks 2150-2250          flush 2610-3350   (epoch 2's own span is lost:
                                                                                 the trace stops inside it)
  device       fusion.1 1080-1180 (fwd, dense_1)              fusion.1 2530-2630
               flash_attention_fwd.1 1180-1380                flash_attention_fwd.1 2630-2830
               while.1 1380-1800 (no scope) holding           fusion.3 2830-2930 (bwd of the loss)
                 fusion.2 1400-1500 (bwd, dense_1)            flash_attention_bwd_dkv.1 2930-3180
                 flash_attention_bwd_dq.1 1500-1700           divide_subtract_fusion.1 3180-3340
               flash_attention_bwd_dkv.1 1800-2050
               divide_subtract_fusion.1 2050-2090 (optimizer)
               copy.1 2090-2100 (no metadata)
"""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, peaks, span_reduce as sr  # noqa: E402
from benchmarks.harness import trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
US = 1000        # the counts below are in microseconds


@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(BENCH, "testdata", "span_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(hand):
    return sr.reduce_spans(hand["events"], sr.instructions(hand["step_text"]))


def _ctx(hand, reduced):
    """What a traced chip run would hand the readers, from the file."""
    layers = [types.SimpleNamespace(name=n, params=p)
              for n, p in hand["layers"].items()]
    return types.SimpleNamespace(
        span_reduced=reduced, peak=peaks.lookup("TPU v5 lite"),
        span_instructions=sr.instructions(hand["step_text"]),
        cell=types.SimpleNamespace(bench_dir=BENCH),
        model=types.SimpleNamespace(layers=layers))


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


NEW_METRICS = [
    "fwd_time_share.train", "bwd_time_share.train", "opt_time_share.train",
    "flash_fwd_roofline", "flash_bwd_dq_roofline", "flash_bwd_dkv_roofline",
    "idle_attributed_share.train", "dispatch_ms_per_step.train",
    "loader_wait_ms_per_step.train"]


def test_busy_and_idle_are_trace_reduces(hand, reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_ns"] == 2400 * US          # 1000 .. 3400
    assert reduced["busy_ns"] == (1020 + 810) * US    # 1080-2100, 2530-3340
    assert reduced["idle_ns"] == (80 + 430 + 60) * US
    kernels = [n for n, e in sr.instructions(hand["step_text"]).items()
               if e["mosaic"]]
    old = trace_reduce.reduce_trace(hand["events"], kernels, {}, [])
    assert old["busy_s"] == pytest.approx(reduced["busy_ns"] / 1e9)
    assert old["idle_share"] == pytest.approx(570 / 2400)
    # the three kernels' device times are kernel_time_share x busy
    assert sum(reduced["kernel_ns"].values()) == pytest.approx(
        old["kernel_time_share"] * reduced["busy_ns"])


def test_phase_shares_against_a_hand_count(hand, reduced):
    assert reduced["phase_ns"] == {
        "forward": (100 + 200) * 2 * US,               # fusion.1, fwd
        "backward": (100 + 200 + 250 + 100 + 250) * US,
        "optimizer": (40 + 160) * US,
        "unscoped": (420 - 300 + 10) * US}             # while's own, copy
    assert sum(reduced["phase_ns"].values()) == reduced["busy_ns"]
    assert reduced["unscoped_ns"] == {"while.1": 120 * US, "copy.1": 10 * US}
    ctx = _ctx(hand, reduced)
    assert _read("fwd_time_share.train", ctx) == pytest.approx(
        100 * 600 / 1830)
    assert _read("bwd_time_share.train", ctx) == pytest.approx(
        100 * 900 / 1830)
    assert _read("opt_time_share.train", ctx) == pytest.approx(
        100 * 200 / 1830)


def test_per_kernel_and_per_layer_time_against_a_hand_count(reduced):
    assert reduced["kernel_ns"] == {
        "flash_attention_fwd": 400 * US, "flash_attention_bwd_dq": 200 * US,
        "flash_attention_bwd_dkv": 500 * US}
    assert reduced["kernel_calls"] == {
        "flash_attention_fwd": {"flash_attention_fwd.1": 2},
        "flash_attention_bwd_dq": {"flash_attention_bwd_dq.1": 1},
        "flash_attention_bwd_dkv": {"flash_attention_bwd_dkv.1": 2}}
    assert reduced["layer_ns"] == {
        "dense_1": 300 * US, "attn_2": (400 + 200 + 500) * US,
        "ff.loss": 100 * US, "ff.optimizer": 200 * US}


def test_idle_by_innermost_host_span_against_a_hand_count(hand, reduced):
    # 1000-1080: loader 50, dispatch 30. 2100-2530: epoch 50, callbacks
    # 100, epoch 150, loader 100, dispatch 30. 3340-3400: flush 10 and
    # 50 under no span. The other thread's span counts for nothing.
    assert reduced["idle_by_span"] == {
        "fit.loader_next": 150 * US, "executor.train_step": 60 * US,
        "fit.epoch": 200 * US, "fit.callbacks": 100 * US,
        "metrics_buffer.flush": 10 * US, sr.NO_SPAN: 50 * US}
    assert sum(reduced["idle_by_span"].values()) == reduced["idle_ns"]
    assert reduced["span_ns"] == {                 # clipped to the window
        "fit.epoch": [1, 1400 * US], "fit.loader_next": [6, 185 * US],
        "executor.train_step": [4, 180 * US],
        "metrics_buffer.flush": [2, (940 + 740) * US],
        "fit.callbacks": [1, 100 * US]}
    ctx = _ctx(hand, reduced)
    assert _read("idle_attributed_share.train", ctx) == pytest.approx(
        100 * 320 / 570)
    assert _read("dispatch_ms_per_step.train", ctx) == pytest.approx(
        0.180 / 4)
    assert _read("loader_wait_ms_per_step.train", ctx) == pytest.approx(
        0.185 / 4)


def test_innermost_segments_of_nested_spans():
    segs = sr.innermost_segments([
        ["a", 0, 100], ["b", 10, 20], ["c", 15, 5], ["d", 50, 50],
        ["e", 120, 10]])
    assert segs == [[0, 10, "a"], [10, 15, "b"], [15, 20, "c"],
                    [20, 30, "b"], [30, 50, "a"], [50, 100, "d"],
                    [120, 130, "e"]]


@pytest.mark.parametrize("op_name,phase,layer", [
    ("jit(step_fn)/jvp(ff.forward)/wte/gather", "forward", "wte"),
    ("jit(step_fn)/ff.forward/wte/gather", "forward", "wte"),
    ("jit(step_fn)/jvp(ff.forward)/add", "forward", "ff.forward"),
    ("jit(step_fn)/jvp(ff.loss)/reduce_sum", "forward", "ff.loss"),
    ("jit(step_fn)/transpose(jvp(ff.forward))/lm_head/dot_general",
     "backward", "lm_head"),
    ("jit(step_fn)/transpose(jvp(ff.loss))/sub", "backward", "ff.loss"),
    ("jit(step_fn)/while/body/transpose(jvp(ff.forward))/fc/dot_general",
     "backward", "fc"),
    ("jit(step_fn)/ff.optimizer/sqrt", "optimizer", "ff.optimizer"),
    ("jit(step_fn)/while", "unscoped", ""),
    ("", "unscoped", "")])
def test_phase_and_layer_of_an_op_name(op_name, phase, layer):
    assert sr.phase_of(op_name) == phase
    assert sr.layer_of(op_name) == layer


def test_instructions_reads_names_scopes_and_a_kernels_shapes(hand):
    instr = sr.instructions(hand["step_text"])
    assert instr["fusion.1"]["op_name"] == \
        "jit(step_fn)/jvp(ff.forward)/dense_1/dot_general"
    assert instr["copy.1"] == {"op_name": "", "mosaic": False}
    assert instr["tuple.9"]["op_name"] == ""              # a ROOT line
    assert [n for n, e in instr.items() if e["mosaic"]] == [
        "flash_attention_fwd.1", "flash_attention_bwd_dq.1",
        "flash_attention_bwd_dkv.1"]
    q, stats = ("bf16", (144, 1024, 64)), ("f32", (144, 1024, 128))
    fwd = instr["flash_attention_fwd.1"]
    assert fwd["operands"] == [("s32", (1, 1)), q, q, q]
    assert fwd["results"] == [q, stats]
    dkv = instr["flash_attention_bwd_dkv.1"]
    assert dkv["operands"] == [("s32", (1, 1)), q, q, q, q, stats, stats]
    assert dkv["results"] == [q, q]
    assert sr.kernel_of("flash_attention_fwd.1", fwd) == \
        "flash_attention_fwd"
    # inside a shard_map the scope is lost; the instruction keeps it
    assert sr.kernel_of("flash_attention_bwd_dq.7",
                        {"op_name": ""}) == "flash_attention_bwd_dq"


def test_flash_operations_and_bytes_against_a_hand_count():
    """b 12, h 12, s 1024, d 64, causal, bf16: 1024 x 1025 / 2 = 524,800
    pairs the mask leaves; one product = 2 x 144 x 524,800 x 64 =
    9,673,113,600. q = 144 x 1024 x 64 x 2 = 18,874,368 bytes; a row
    statistic 144 x 1024 x 4 = 589,824 (not the 128 lanes it comes in);
    the seed 4."""
    cost = cells.load_module(BENCH, "flops", "flash_attention")
    seed, q = ("s32", (1, 1)), ("bf16", (144, 1024, 64))
    stats = ("f32", (144, 1024, 128))
    fwd = ([seed, q, q, q], [q, stats])
    dq = ([seed, q, q, q, q, stats, stats], [q])
    dkv = ([seed, q, q, q, q, stats, stats], [q, q])
    one = 9_673_113_600
    assert cost.operations("flash_attention_fwd", fwd[0], True) == 2 * one
    assert cost.operations("flash_attention_bwd_dq", dq[0], True) == 3 * one
    assert cost.operations("flash_attention_bwd_dkv", dkv[0], True) \
        == 4 * one
    full = 2 * 144 * 1024 * 1024 * 64                    # no mask
    assert cost.operations("flash_attention_fwd", fwd[0], False) == 2 * full
    assert cost.bytes_moved("flash_attention_fwd", *fwd) \
        == 4 + 4 * 18_874_368 + 589_824 == 76_087_300
    assert cost.bytes_moved("flash_attention_bwd_dq", *dq) \
        == 4 + 5 * 18_874_368 + 2 * 589_824
    assert cost.bytes_moved("flash_attention_bwd_dkv", *dkv) \
        == 4 + 6 * 18_874_368 + 2 * 589_824
    v5e = peaks.lookup("TPU v5 lite")
    for kernel, (ops, res), products in (
            ("flash_attention_fwd", fwd, 2),
            ("flash_attention_bwd_dq", dq, 3),
            ("flash_attention_bwd_dkv", dkv, 4)):
        seconds, bound = cost.roofline_s(kernel, ops, res, True, v5e)
        assert bound == "operations"      # at the cell's shapes, all three
        assert seconds == pytest.approx(products * one / 197e12)
    # a short context is bound by its bytes
    small = ("bf16", (144, 128, 64))
    _, bound = cost.roofline_s(
        "flash_attention_fwd", [seed, small, small, small],
        [small, ("f32", (144, 128, 128))], True, v5e)
    assert bound == "bytes"
    with pytest.raises(ValueError):
        cost.operations("flash_attention_fwd",
                        [seed, q, ("bf16", (144, 512, 64))], True)
    with pytest.raises(KeyError):                 # never a default size
        cost.bytes_moved("flash_attention_fwd",
                         [seed, ("q7", (1, 1, 1))] * 2, [])


def test_flash_roofline_shares_against_a_hand_count(hand, reduced):
    ctx = _ctx(hand, reduced)
    one_us = 9_673_113_600 / 197e12 * 1e6            # one product, 49.1 us
    assert _read("flash_fwd_roofline", ctx) == pytest.approx(
        100 * 2 * (2 * one_us) / 400)                # two calls, 400 us
    assert _read("flash_bwd_dq_roofline", ctx) == pytest.approx(
        100 * (3 * one_us) / 200)
    assert _read("flash_bwd_dkv_roofline", ctx) == pytest.approx(
        100 * 2 * (4 * one_us) / 500)
    for m in ("flash_fwd_roofline", "flash_bwd_dq_roofline",
              "flash_bwd_dkv_roofline"):
        assert 0 < _read(m, ctx) < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_returns_none_without_a_trace(metric, tmp_path):
    """No ``--trace 1`` (``ctx.trace`` is None), and a traced run that
    left no events to parse: nothing to read, no error."""
    cell = types.SimpleNamespace(root=str(tmp_path), name="x.train",
                                 bench_dir=BENCH)
    for trace in (None, {"idle_share": 0.1}):
        ctx = types.SimpleNamespace(trace=trace, cell=cell, step_text="",
                                    span_events=None,
                                    span_instructions=None, peak=None)
        assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_returns_none_on_a_program_that_names_nothing(
        metric, hand):
    """The parent of PR 25: numbered kernels, no scope, no ``ff:`` span.
    The driver runs the new readers on it too."""
    step_text = hand["step_text"]
    for scope in ("ff.forward", "ff.loss", "ff.optimizer"):
        step_text = step_text.replace(scope, "x")
    events = dict(hand["events"], spans=[], devices={
        plane: [[n.replace("flash_attention_fwd", "tpu_custom_call")
                 .replace("flash_attention_bwd_dq", "tpu_custom_call")
                 .replace("flash_attention_bwd_dkv", "tpu_custom_call"),
                 s, d] for n, s, d in ops]
        for plane, ops in hand["events"]["devices"].items()})
    r = sr.reduce_spans(events, sr.instructions(step_text))
    assert r["phase_ns"]["unscoped"] == r["busy_ns"] and not r["scoped"]
    ctx = _ctx(hand, r)
    assert _read(metric, ctx) is None


def test_the_manifest_holds_pr25s_nine_metrics_each_with_its_reader():
    """By name: the nine entries in their order, what each moves, where
    it comes from, its layer, its reader, and that cells 1 and 2 report
    them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by_name = {m["name"]: m for m in man["per_layer"]}
    assert len(by_name) == len(man["per_layer"])
    names = [m["name"] for m in man["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == NEW_METRICS
    both = ["bert_large.train.1chip", "gpt2_124m.train.1chip"]
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["moves"] == "train_tokens_per_s"
        for cell in both[1:] if "roofline" in name else both:
            assert "workloads" not in m or cell in m["workloads"], name
        assert m["source"] == ("program_span" if name in NEW_METRICS[6:]
                               else "device_trace")
        assert callable(cells.load_module(
            BENCH, "layer_metrics", cells.metric_file(name)).read)
    assert {by_name[n]["layer"] for n in NEW_METRICS} == {
        "executor", "kernels", "device", "loader"}


# ----------------------------------------------------------------------
# the breakdown under the program's names
# ----------------------------------------------------------------------
@pytest.mark.parametrize("op_name,scope", [
    ("jit(step_fn)/jvp(ff.forward)/dense_1/dot_general", "dense_1"),
    ("jit(step_fn)/transpose(jvp(ff.forward))/jvp(ff.forward)/remat.block/"
     "checkpoint/rematted_computation/experts_0/moe.shared/dot_general",
     "moe.shared"),
    ("jit(step_fn)/jvp(ff.forward)/attn_2/attn.kernels/flash_attention_fwd/"
     "pallas_call", "attn.kernels"),
    # a layer the model does not name is JAX's word for all we know
    ("jit(step_fn)/jvp(ff.forward)/mlp_9/dot_general", "ff.forward"),
    ("jit(step_fn)/transpose(jvp(ff.loss))/sub", "ff.loss"),
    ("jit(step_fn)/ff.optimizer/sub", "ff.optimizer"),
    ("jit(step_fn)/while/body/closed_call/dot_general", "unscoped"),
    ("jit(step_fn)/while", "unscoped"), ("", "unscoped")])
def test_the_innermost_scope_the_program_opened(op_name, scope):
    assert sr.innermost_scope(
        op_name, {"dense_1", "attn_2", "experts_0"}) == scope


def test_the_breakdown_names_ops_by_scope_and_gaps_by_host_span(hand):
    """``device_ops``: each of the nine instructions behind its layer or
    phase scope, ``unscoped/`` where its ``op_name`` has none (the
    ``while`` and the copy). ``idle_gaps``, by the drawing above: the 80
    us before the first op lie under ``loader_next`` (50) and
    ``train_step`` (30); the 430 us after ``copy.1`` under nothing
    (2100-2150 and 2250-2400, across the groups' edge: 200),
    ``fit.callbacks`` (100), ``loader_next`` (100) and ``train_step``
    (30); the 60 us after the last op under the flush (10) and nothing
    (50). ``fit.epoch`` covers everything and names nothing."""
    instr = sr.instructions(hand["step_text"])
    names = sr.scoped_names(instr, set(hand["layers"]))
    host = sr.host_segments(hand["events"])
    assert all(n != sr.EPOCH_SPAN for _, _, n in host)
    events = dict(hand["events"], **{"async": {}})
    r = trace_reduce.reduce_trace(events, [], names, host)
    assert [[n, round(s * 1e6)] for n, s in r["device_ops"]] == [
        ["attn_2/flash_attention_bwd_dkv.1", 500],
        ["attn_2/flash_attention_fwd.1", 400],
        ["dense_1/fusion.1", 200],
        ["attn_2/flash_attention_bwd_dq.1", 200],
        ["ff.optimizer/divide_subtract_fusion.1", 200],
        ["unscoped/while.1", 120],
        ["dense_1/fusion.2", 100],
        ["ff.loss/fusion.3", 100],
        ["unscoped/copy.1", 10]]
    assert {n: round(s * 1e6) for n, s in r["idle_gaps"]} == {
        "between-groups/after:unscoped/copy.1": 200,
        "fit.callbacks/after:unscoped/copy.1": 100,
        "fit.loader_next/after:unscoped/copy.1": 100,
        "fit.loader_next/after:start": 50,
        "between-groups/after:ff.optimizer/divide_subtract_fusion.1": 50,
        "executor.train_step/after:start": 30,
        "executor.train_step/after:unscoped/copy.1": 30,
        "metrics_buffer.flush/after:ff.optimizer/divide_subtract_fusion.1":
        10}
    # the gaps are what ``idle_attributed_share.train`` attributes
    attributed = sum(s for n, s in r["idle_gaps"]
                     if not n.startswith(("between-groups", "inside-group")))
    total = sum(s for _, s in r["idle_gaps"])
    whole = sr.reduce_spans(hand["events"], instr)
    assert total * 1e9 == pytest.approx(whole["idle_ns"])
    assert attributed * 1e9 == pytest.approx(sum(
        ns for n, ns in whole["idle_by_span"].items()
        if n not in (sr.EPOCH_SPAN, sr.NO_SPAN)))


def test_a_printed_name_is_cut_at_eighty_characters(hand):
    long = "experts_0/" + "x" * 100
    events = dict(hand["events"], **{"async": {}})
    r = trace_reduce.reduce_trace(events, [], {"copy.1": long}, [])
    shown = [n for n, _ in r["device_ops"] if n.startswith("experts_0/")]
    assert shown == [long[:trace_reduce.NAME_CUT]]
    assert len(shown[0]) == 80
    assert "between-groups/after:" + long[:80] in dict(r["idle_gaps"])
