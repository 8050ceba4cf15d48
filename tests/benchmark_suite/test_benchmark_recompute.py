"""What PR 53 adds to the benchmark, on the CPU: ``benchmarks/harness/
remat_reduce.py`` and its five readers (``recompute_time_share.train``,
``recompute_kernel_time_share.train``, ``recompute_again_time_share.
train``, ``remat_held_gib``, ``weights_and_optimizer_gib``) on a trace
and a step text small enough to count by hand. And, by name, what
cells 3 to 5 report and where their PRs' entries stand. Nothing here
pins an entry to the tail of a list or a list to a closed set.

The hand-made step (one device, one group 1000-3000 us, one step a
group; F = ``jit(step_fn)/jvp(ff.forward)/``, T = ``jit(step_fn)/
transpose(jvp(ff.forward))/``, B = T + ``remat.block/jvp(ff.forward)/
remat.block/checkpoint/``, R = ``rematted_computation``):

  block only (``experts_1`` in block 0)
    fusion.1   1000 +100  F remat.block/experts_1/dot        first run
    fusion.2   1100 +100  B R/experts_1/dot                  the block's second
    ragged-dot-none.1 1200 +50  (the bare word, Mosaic)       reads as fusion.2
    fusion.3   1250 +100  B experts_1/transpose              backward
  own wrap only, and own inside own (``kda_0``, in no block)
    fusion.4   1350 +50   F kda_0/remat.kda.layer/checkpoint/mul
    fusion.5   1400 +40   T kda_0/remat.kda.layer/checkpoint/R/mul
    fusion.6   1440 +30   T kda_0/remat.kda.layer/checkpoint/R/
                            remat.kda.branch/tanh    the layer's second run
    fusion.7   1470 +30   T kda_0/remat.kda.layer/checkpoint/
                            remat.kda.branch/checkpoint/R/tanh   a THIRD run
  a block around an own wrap, output not kept (``kda_1`` in block 0)
    fusion.8   1500 +60   B R/kda_1/remat.kda.layer/mul      the block's second
    gated_delta_rule_fwd.1 1560 +80  B R/kda_1/remat.kda.layer/kda.scan/
                            gated_delta_rule_fwd/pallas_call  (Mosaic)
    fusion.9   1640 +60   B kda_1/remat.kda.layer/checkpoint/R/mul  a THIRD
  the same with the output kept (``kda_2`` in block 1)
    fusion.10  1700 +70   B kda_2/remat.kda.layer/checkpoint/R/mul  second
    fusion.11  1770 +90   B R/experts_2/dot
  fusion.12    1860 +100  jit(step_fn)/ff.optimizer/mul
  (idle 1960-3000)

busy 960. Recomputed 100 + 50 + 40 + 30 + 30 + 60 + 80 + 60 + 70 + 90 =
610; of which Mosaic 50 + 80 = 130; of which a third run 30 + 60 = 90.
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

from benchmarks.harness import cells, remat_reduce  # noqa: E402
from benchmarks.harness import scope_reduce, span_reduce  # noqa: E402
from benchmarks.harness import trace_reduce  # noqa: E402

BENCH = os.path.join(ROOT, "benchmarks")
US = 1000
NEW = ["recompute_time_share.train", "recompute_kernel_time_share.train",
       "recompute_again_time_share.train", "remat_held_gib",
       "weights_and_optimizer_gib"]
SHARES = NEW[:3]
TRAINING = ["bert_large.train.1chip", "gpt2_124m.train.1chip",
            "joyai_llm_flash.train.1chip", "lfm2_24b_a2b.train.1chip",
            "kimi_linear_48b_a3b.train.1chip", "xing4_29b_a4b.train.1chip",
            "keye_vl2_30b_a3b.train.1chip", "trinity_mini.train.1chip"]

F = "jit(step_fn)/jvp(ff.forward)/"
T = "jit(step_fn)/transpose(jvp(ff.forward))/"
B = T + "remat.block/jvp(ff.forward)/remat.block/checkpoint/"
R = "rematted_computation"
OWN = "remat.kda.layer/checkpoint/"
OPS = [     # name, start, duration (us), op_name
    ("fusion.1", 1000, 100, F + "remat.block/experts_1/dot"),
    ("fusion.2", 1100, 100, B + R + "/experts_1/dot"),
    ("ragged-dot-none.1", 1200, 50, "ragged-dot-none"),
    ("fusion.3", 1250, 100, B + "experts_1/transpose"),
    ("fusion.4", 1350, 50, F + "kda_0/" + OWN + "mul"),
    ("fusion.5", 1400, 40, T + "kda_0/" + OWN + R + "/mul"),
    ("fusion.6", 1440, 30, T + "kda_0/" + OWN + R + "/remat.kda.branch/tanh"),
    ("fusion.7", 1470, 30, T + "kda_0/" + OWN + "remat.kda.branch/checkpoint/"
     + R + "/tanh"),
    ("fusion.8", 1500, 60, B + R + "/kda_1/remat.kda.layer/mul"),
    ("gated_delta_rule_fwd.1", 1560, 80, B + R + "/kda_1/remat.kda.layer/"
     "kda.scan/gated_delta_rule_fwd/pallas_call"),
    ("fusion.9", 1640, 60, B + "kda_1/" + OWN + R + "/mul"),
    ("fusion.10", 1700, 70, B + "kda_2/" + OWN + R + "/mul"),
    ("fusion.11", 1770, 90, B + R + "/experts_2/dot"),
    ("fusion.12", 1860, 100, "jit(step_fn)/ff.optimizer/mul")]
MOSAIC = ("ragged-dot-none.1", "gated_delta_rule_fwd.1")
LAYERS = ["experts_1", "kda_0", "kda_1", "kda_2", "experts_2"]
PLAIN = [   # a step that holds no checkpoint (cells 1 and 2)
    ("fusion.1", 1000, 100, F + "dense_1/dot"),
    ("fusion.2", 1100, 200, T + "dense_1/transpose"),
    ("fusion.3", 1300, 100, "jit(step_fn)/ff.optimizer/mul")]
PARENT = [(n, s, d, op.replace("remat.block/", "").replace(
    "remat.kda.layer/", "").replace("remat.kda.branch/", ""))
    for n, s, d, op in OPS]     # a program that names no wrap


def _step_text(ops) -> str:
    """The compiled step's text with those instructions, as XLA writes
    them: a fusion with its metadata, a Mosaic call with its target."""
    lines = ["HloModule jit_step_fn, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for name, _, _, op in ops:
        meta = f', metadata={{op_name="{op}"}}' if op else ""
        if name in MOSAIC:
            lines.append(
                f"  %{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
                f'custom_call_target="tpu_custom_call", '
                f"operand_layout_constraints={{f32[8]{{0}}}}, "
                f"backend_config={{}}{meta}")
        else:
            lines.append(f"  %{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), "
                         f"kind=kLoop, calls=%fused.{name}{meta}")
    return "\n".join(lines + ["}"])


def _wrap(site, depth, entry, kept=0, weights=0, policy="none", **where):
    return {"site": site, **where, "depth": depth, "policy": policy,
            "entry_bytes": entry, "weights_bytes": weights,
            "kept_bytes": kept}


MB = 2 ** 20
WRAPS = [   # one trace of the hand-made step, as the program records it
    _wrap("kda.branch", 1, 8 * MB, layer="kda_0", part="wq"),
    _wrap("kda.layer", 0, 8 * MB, weights=3 * MB, layer="kda_0"),
    _wrap("kda.layer", 1, 8 * MB, weights=3 * MB, layer="kda_1", block=0),
    _wrap("block", 0, 8 * MB, weights=40 * MB, block=0,
          layers=["kda_1", "experts_1"]),
    _wrap("kda.layer", 1, 8 * MB, weights=3 * MB, layer="kda_2", block=1),
    _wrap("block", 0, 8 * MB, kept=16 * MB, weights=40 * MB,
          policy="keep_marked", block=1, layers=["kda_2", "experts_2"])]
EVAL = [    # the eval step's trace of the same layers: no block
    _wrap("kda.layer", 0, 8 * MB, weights=3 * MB, layer=f"kda_{i}")
    for i in range(3)]


def _instant(attrs, ts=1.0):
    return {"name": "remat.wrap", "kind": "instant", "ts": ts, "dur": 0.0,
            "tid": 1, "attrs": attrs}


def _span(name, ts, dur, **attrs):
    return {"name": name, "kind": "span", "ts": ts, "dur": dur, "tid": 1,
            "attrs": attrs or None}


SETUP = [   # compile()'s draw, the optimizer's state, the seed's draw,
            # two warm-up groups, then a draw inside the window
    _span("executor.init_params", 1.0, 2.0, bytes=100, device_bytes=100),
    _span("compile.opt_state", 3.0, 0.5, device_bytes=5 * 2 ** 30),
    _span("executor.init_params", 4.0, 2.0, bytes=2 ** 31,
          device_bytes=5 * 2 ** 29),
    _span("fit.epoch", 10.0, 5.0), _span("fit.epoch", 15.0, 5.0),
    _span("executor.init_params", 21.0, 1.0, bytes=7, device_bytes=7),
    _span("fit.epoch", 20.0, 5.0)]


def _read(metric, ctx):
    return cells.load_module(BENCH, "layer_metrics",
                             cells.metric_file(metric)).read(ctx)


def _ctx(tmp_path, monkeypatch, ops=OPS, layers=LAYERS, ring=()):
    """A context whose trace and step text are the hand-made ones and
    whose recorder holds ``ring``."""
    events = {"devices": {"/device:TPU:0": [[n, s * US, d * US]
                                            for n, s, d, _ in ops]},
              "marks": [["bench.group", 1000 * US, 2000 * US]], "spans": []}
    text = _step_text(ops)
    instr = span_reduce.instructions(text)
    monkeypatch.setattr(remat_reduce, "_ring", lambda: (list(ring), 0))
    return types.SimpleNamespace(
        span_reduced=span_reduce.reduce_spans(events, instr),
        span_events=events, span_instructions=instr, step_text=text, steps_per_group=1,
        model=types.SimpleNamespace(layers=[
            types.SimpleNamespace(name=n, params={}) for n in layers]),
        cell=types.SimpleNamespace(
            bench_dir=BENCH, root=str(tmp_path), name="x.train",
            traffic={"warmup_groups": 2}))


# ----------------------------------------------------------------------
# the reduction, by hand
# ----------------------------------------------------------------------
def test_the_step_text_reads_as_written():
    instr = span_reduce.instructions(_step_text(OPS))
    assert [n for n, e in instr.items() if e["mosaic"]] == list(MOSAIC)
    assert instr["ragged-dot-none.1"]["op_name"] == "ragged-dot-none"
    assert instr["fusion.9"]["op_name"] == OPS[10][3]


@pytest.mark.parametrize("metric,want", [
    ("recompute_time_share.train", 100.0 * 610 / 960),
    ("recompute_kernel_time_share.train", 100.0 * 130 / 960),
    ("recompute_again_time_share.train", 100.0 * 90 / 960)])
def test_the_three_shares_by_hand(tmp_path, monkeypatch, metric, want):
    ctx = _ctx(tmp_path, monkeypatch)
    assert ctx.span_reduced["busy_ns"] == 960 * US
    assert _read(metric, ctx) == pytest.approx(want)


@pytest.mark.parametrize("layer,unit,owners,again", [
    # block only: two runs
    ("experts_1", "block", {"block": 150}, 0),
    ("experts_2", "block", {"block": 90}, 0),
    # own wrap only: two runs; own inside own: three, the last one again
    ("kda_0", "kda.layer", {"kda.layer": 40}, 0),
    ("kda_0", "kda.branch", {"kda.layer": 30, "kda.branch": 30}, 30),
    # a block around an own wrap, output not kept: three runs
    ("kda_1", "kda.layer", {"block": 140, "kda.layer": 60}, 60),
    # the same with the output kept: two
    ("kda_2", "kda.layer", {"kda.layer": 70}, 0)])
def test_each_piece_of_work_by_owner_and_run(tmp_path, monkeypatch, layer,
                                             unit, owners, again):
    r = remat_reduce.reduced(_ctx(tmp_path, monkeypatch))
    row = r["work"][(layer, unit)]
    assert row["owners"] == {k: v * US for k, v in owners.items()}
    assert row["again_ns"] == again * US
    runs = 1 + len(row["owners"])
    assert runs == {"experts_1": 2, "experts_2": 2, "kda_2": 2,
                    "kda_1": 3}.get(layer, 3 if unit == "kda.branch" else 2)


def test_an_unnamed_call_reads_as_the_op_before_it(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    names = remat_reduce.effective_names(
        ctx.span_events, ctx.span_instructions)
    assert names["ragged-dot-none.1"] == names["fusion.2"] == OPS[1][3]
    row = remat_reduce.reduced(ctx)["work"][("experts_1", "block")]
    assert (row["mosaic_ns"], row["mosaic_events"]) == (50 * US, 1)
    kda = remat_reduce.reduced(ctx)["work"][("kda_1", "kda.layer")]
    assert (kda["mosaic_ns"], kda["mosaic_events"]) == (80 * US, 1)
    # after an op that is NOT recomputed it is not recomputed either
    moved = [o for o in OPS if o[0] != "ragged-dot-none.1"]
    moved.insert(1, ("ragged-dot-none.1", 1050, 50, "ragged-dot-none"))
    moved[0] = ("fusion.1", 1000, 50, OPS[0][3])
    ctx = _ctx(tmp_path, monkeypatch, moved)
    assert _read("recompute_kernel_time_share.train", ctx) \
        == pytest.approx(100.0 * 80 / 910)


@pytest.mark.parametrize("op_name,want", [
    (OPS[0][3], (False, "", [], [], "block")),
    (OPS[1][3], (True, "block", [], [], "block")),
    (OPS[5][3], (True, "kda.layer", [], [], "kda.layer")),
    (OPS[6][3], (True, "kda.layer", [], ["kda.branch"], "kda.branch")),
    (OPS[7][3], (True, "kda.branch", ["kda.layer"], [], "kda.branch")),
    (OPS[8][3], (True, "block", [], ["kda.layer"], "kda.layer")),
    (OPS[10][3], (True, "kda.layer", ["block"], [], "kda.layer")),
    (OPS[13][3], (False, "", [], [], "")),
    # a merged op reads as its first name; a program that names no wrap
    (OPS[5][3] + ";" + OPS[0][3], (True, "kda.layer", [], [], "kda.layer")),
    (T + "checkpoint/" + R + "/attn_2/mul", (True, "", [], [], ""))])
def test_a_name_parses_into_owner_around_and_through(op_name, want):
    assert remat_reduce.parse(op_name) == want


def test_the_table_has_a_line_a_piece_of_work_and_a_total(tmp_path,
                                                          monkeypatch,
                                                          capsys):
    ctx = _ctx(tmp_path, monkeypatch, ring=[_instant(a) for a in WRAPS])
    r = remat_reduce.reduced(ctx)
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l.startswith("[bench]   ")]
    assert len(lines) == len(r["work"]) + 1
    kept = next(l for l in lines if " kda_2 kda.layer:" in l)
    assert "owners kda.layer, runs 2, recomputed 0.070 ms" in kept
    third = next(l for l in lines if " kda_1 kda.layer:" in l)
    assert "owners block+kda.layer, runs 3" in third
    assert "Mosaic 1 calls 0.080 ms" in third
    branch = next(l for l in lines if " kda_0 kda.branch:" in l)
    assert "runs 3" in branch and f"entry {8 * MB} kept 0" in branch
    block = next(l for l in lines if " experts_2 block:" in l)
    assert f"entry {8 * MB} kept {16 * MB}" in block
    assert "total: recomputed 0.610 ms a step" in lines[-1]
    assert "run again 0.090 ms" in lines[-1]
    # printed once, by the first reader that runs
    for metric in SHARES:
        _read(metric, ctx)
    assert "[bench]" not in capsys.readouterr().out


# ----------------------------------------------------------------------
# the controls: no checkpoint, no trace, a program that names nothing
# ----------------------------------------------------------------------
def test_a_step_with_no_checkpoint_reads_zero_and_the_weights(
        tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, PLAIN, ["dense_1"], ring=SETUP)
    for metric in NEW[:4]:
        assert _read(metric, ctx) == 0.0
    # the seed's draw (2.5 GiB), not compile()'s and not the later one,
    # + the optimizer's state (5 GiB)
    assert _read("weights_and_optimizer_gib", ctx) == 7.5


@pytest.mark.parametrize("metric", SHARES)
def test_without_a_trace_there_is_nothing_to_read(tmp_path, metric):
    ctx = types.SimpleNamespace(
        trace=None, step_text=_step_text(OPS), steps_per_group=1,
        model=types.SimpleNamespace(layers=[]),
        cell=types.SimpleNamespace(bench_dir=BENCH, root=str(tmp_path),
                                   name="x.train", traffic={}))
    assert _read(metric, ctx) is None


def test_the_parent_names_no_wrap_and_records_none(tmp_path, monkeypatch):
    """A program from before this PR: ``rematted_computation`` is JAX's
    own part and still reads; who runs what again cannot be told; no
    instant and no attribute."""
    ring = [dict(e, attrs={k: v for k, v in (e["attrs"] or {}).items()
                           if k != "device_bytes"} or None) for e in SETUP]
    ctx = _ctx(tmp_path, monkeypatch, PARENT, ring=ring)
    assert _read("recompute_time_share.train", ctx) \
        == pytest.approx(100.0 * 610 / 960)
    assert _read("recompute_kernel_time_share.train", ctx) \
        == pytest.approx(100.0 * 130 / 960)
    assert _read("recompute_again_time_share.train", ctx) is None
    assert _read("remat_held_gib", ctx) is None
    assert _read("weights_and_optimizer_gib", ctx) is None


def test_a_ring_that_dropped_events_gives_no_bytes(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    monkeypatch.setattr(remat_reduce, "_ring", lambda: (list(SETUP), 3))
    assert _read("remat_held_gib", ctx) is None
    assert _read("weights_and_optimizer_gib", ctx) is None


# ----------------------------------------------------------------------
# the instants
# ----------------------------------------------------------------------
def test_held_is_the_depth_zero_wraps_entries_and_marked_values(
        tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, ring=[_instant(a) for a in WRAPS])
    # kda_0's layer 8, block 0 8, block 1 8 + 16 kept
    assert _read("remat_held_gib", ctx) == 40 / 1024
    assert remat_reduce.held_bytes(WRAPS) == 40 * MB


def test_instants_recorded_twice_count_once(tmp_path, monkeypatch):
    """The runner traces the train step twice and the eval step once
    (its layers' own wraps, outside any block): one trace's worth."""
    ring = [_instant(a, ts) for ts, trace in enumerate((WRAPS, EVAL, WRAPS))
            for a in trace]
    assert len(ring) == 15
    got = remat_reduce.one_trace(ring)
    assert sorted(map(repr, got)) == sorted(map(repr, WRAPS))
    ctx = _ctx(tmp_path, monkeypatch, ring=ring)
    assert _read("remat_held_gib", ctx) == 40 / 1024
    # the later of two is the one kept
    later = [_instant(dict(a, entry_bytes=a["entry_bytes"] + 1), 9.0)
             for a in WRAPS]
    assert remat_reduce.held_bytes(remat_reduce.one_trace(ring + later)) \
        == 40 * MB + 3


def test_the_bytes_are_read_from_the_programs_own_recorder(tmp_path,
                                                           monkeypatch):
    """End to end through ``flexflow_tpu``: the wrap records, the
    reader reads (no stub between them)."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.obs import events
    from flexflow_tpu.ops.registry import checkpointed
    ctx = _ctx(tmp_path, monkeypatch)
    monkeypatch.undo()
    events.enable()
    events.clear()
    try:
        for _ in range(2):      # traced twice
            jax.make_jaxpr(checkpointed(
                lambda x, w: jnp.tanh(x @ w), site="block", block=0,
                weights=(1,), layers=["dense_1"]))(
                jnp.ones((256, 1024)), jnp.ones((1024, 8)))
        assert _read("remat_held_gib", ctx) == 1 / 1024
    finally:
        events.disable()
        events.clear()


@pytest.mark.parametrize("metric,call", [
    ("recompute_time_share.train",
     lambda c: remat_reduce.time_share(c, "recomputed_ns")),
    ("recompute_kernel_time_share.train",
     lambda c: remat_reduce.time_share(c, "mosaic_ns")),
    ("recompute_again_time_share.train",
     lambda c: remat_reduce.time_share(c, "again_ns")),
    ("remat_held_gib", remat_reduce.held_gib),
    ("weights_and_optimizer_gib", remat_reduce.placed_gib)])
def test_each_reader_is_the_harness_function_it_calls(tmp_path, monkeypatch,
                                                      metric, call):
    ring = [_instant(a) for a in WRAPS] + SETUP
    got = _read(metric, _ctx(tmp_path, monkeypatch, ring=ring))
    assert got is not None and got > 0
    assert got == call(_ctx(tmp_path, monkeypatch, ring=ring))


# ----------------------------------------------------------------------
# the manifest, by name
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,unit,source,layer,moves", [
    (NEW[0], "%", "device_trace", "executor", "train_tokens_per_s"),
    (NEW[1], "%", "device_trace", "kernels", "train_tokens_per_s"),
    (NEW[2], "%", "device_trace", "executor", "train_tokens_per_s"),
    (NEW[3], "GiB", "program_span", "executor", "step_hbm_gib"),
    (NEW[4], "GiB", "program_span", "executor", "step_hbm_gib")])
def test_the_five_entries_by_name(manifest, name, unit, source, layer, moves):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    assert by_name[name] == {"name": name, "unit": unit, "better": "lower",
                             "source": source, "layer": layer,
                             "moves": moves}       # and no ``workloads``
    assert moves in {m["name"] for m in manifest["end_to_end"]}
    assert callable(cells.load_module(
        BENCH, "layer_metrics", cells.metric_file(name)).read)


@pytest.mark.parametrize("workload", TRAINING)
def test_every_training_cell_reports_the_five(workload):
    names = [m["name"] for m in cells.resolve_cell(ROOT, workload).per_layer]
    assert [n for n in names if n in NEW] == NEW


# -- cells 3 to 5 and their PRs' entries, by name ------------------------
SHARED = {"compile_s", "step_ms.train", "mfu.train", "in_window_compiles",
          "mosaic_calls_per_step", "kernel_time_share.train",
          "device_idle_share.train"}
PR29 = ["mla_time_share.train", "moe_time_share.train",
        "mtp_time_share.train", "mla_flash_fwd_roofline",
        "mla_flash_bwd_dq_roofline", "mla_flash_bwd_dkv_roofline",
        "moe_dropped_assignments", "moe_load_max_over_mean"]
PR33 = ["short_conv_time_share.train", "gqa_time_share.train",
        "moe_time_share.train", "gqa_flash_fwd_roofline",
        "gqa_flash_bwd_dq_roofline", "gqa_flash_bwd_dkv_roofline",
        "moe_dropped_assignments"]
PR35 = ["kda_time_share.train", "kda_scan_time_share.train",
        "mla_time_share.train", "moe_time_share.train",
        "mla_flash_fwd_roofline", "mla_flash_bwd_dq_roofline",
        "mla_flash_bwd_dkv_roofline", "moe_dropped_assignments"]
CELL29, CELL33, CELL35 = TRAINING[2:5]


@pytest.mark.parametrize("workload,own,tokens", [
    (CELL29, PR29, 4096), (CELL33, PR33, 8192), (CELL35, PR35, 4096)])
def test_cells_3_to_5_report_the_shared_metrics_and_their_own(
        workload, own, tokens):
    cell = cells.resolve_cell(ROOT, workload)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    mine = [m["name"] for m in cell.per_layer]
    assert SHARED <= set(mine)
    # its PR's entries after the shared ones; no other kind of layer's
    # (every expert cell reports the experts' load ratio)
    assert set(own) <= set(mine)
    assert max(mine.index(n) for n in SHARED) \
        < min(mine.index(n) for n in own)
    assert not set(mine) & (set(PR29 + PR33 + PR35) - set(own)
                            - {"moe_load_max_over_mean"})
    assert cell.traffic["ffconfig"] == {"remat": "blocks"}
    assert cell.traffic["per_chip_batch"] == 1
    assert cell.traffic["seq"] == tokens
    assert cell.config["reference_sequences"] == \
        cell.traffic["per_chip_batch"]
    if workload != CELL29:
        assert cell.traffic["steps_per_group"] == 8
        assert cell.traffic["optimizer"]["args"] == {"alpha": 1e-05}
    if workload == CELL33:      # the 14.5 GiB rule: both readings
        assert "14.593" in cell.traffic["why"] \
            and "10.346" in cell.traffic["why"]


def test_the_older_entries_stand_in_their_prs_order_by_name(manifest):
    order = [m["name"] for m in manifest["per_layer"]]
    assert not any("workloads" in m for m in manifest["per_layer"]
                   if m["name"] in SHARED)
    assert max(order.index(n) for n in SHARED) < order.index(PR29[0])
    assert order.index(PR29[-1]) < order.index(PR33[0])
    assert order.index(PR33[-1]) < order.index(PR35[0])
    assert order.index(PR35[-1]) < order.index(NEW[0])
    configs = [c["name"] for c in manifest["configs"]]
    assert configs.index("joyai_llm_flash") < configs.index("lfm2_24b_a2b") \
        < configs.index("kimi_linear_48b_a3b")
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL29) < names.index(CELL33) < names.index(CELL35)
    cells_ = {w["name"]: w for w in manifest["workloads"]}
    assert [(cells_[c]["chips"], cells_[c]["config"], cells_[c]["traffic"])
            for c in (CELL29, CELL33, CELL35)] == [
        (1, "joyai_llm_flash", "train_b1_s4096"),
        (1, "lfm2_24b_a2b", "train_b1_s8192"),
        (1, "kimi_linear_48b_a3b", "train_b1_s4096")]
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert [layers[n] for n in PR33] == [
        "short_conv", "attention", "experts", "kernels", "kernels",
        "kernels", "experts"]
    assert [layers[n] for n in PR35] == [
        "linear_attention", "linear_attention", "attention", "experts",
        "kernels", "kernels", "kernels", "experts"]
    assert all(len(e["why"]) <= 200 for e in
               manifest["configs"] + manifest["workloads"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert "linear_attention" in perf
    for name in NEW + ["remat.wrap", "device_bytes"]:
        assert name in perf, name
