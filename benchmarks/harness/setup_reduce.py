"""From the program's own recorder to the set-up's numbers: where the
seconds before the window went, and whether a step was traced again
inside it.

What the program records (``flexflow_tpu.obs``, recorder on; a program
that records none of it makes every reader here return ``None``):

  - ``executor.jit`` instants: ``name`` (``train`` | ``eval`` |
    ``forward``) and the ``fun_name`` under which XLA's events tell of
    that jitted step;
  - XLA's own events as spans ``xla.trace`` / ``xla.lower`` /
    ``xla.backend_compile`` (attribute ``fun_name``) and
    ``xla.cache_load``, and the counter ``xla.cache_misses``;
  - ``executor.init_params`` around each draw of the weights, and
    ``compile.<phase>`` inside ``model.compile``;
  - the loop's spans: ``fit.epoch``, ``executor.train_step``,
    ``metrics_buffer.*``, ``fit.loader_next``.

The ring is cut twice, by the loop's own ``fit.epoch`` spans: *set-up*
is everything stamped before the end of the ``warmup_groups``-th one
(the runner ends ``setup_s`` in that epoch's callback), and the
*window* runs from there to the end of the last one (a traced run's
two profiled groups included; the witness's steps after ``fit`` come
later and are outside both).

The train and the eval step may share a ``fun_name`` (both are
``step_fn`` to JAX). An event of that name belongs to the train step
unless it lies inside a call span of ANOTHER jit of the same name
(``executor.eval_step``): the eval step compiles inside its first
call, the train step also outside any (the runner's inspection lowers
it through the raw ``.lower``).

``xla.cache_load`` lies inside the ``xla.backend_compile`` of the same
call, and traces nest: nothing here adds spans of different names
except as a union of intervals.
"""
from __future__ import annotations

EPOCH_SPAN = "fit.epoch"
JIT_MARK = "executor.jit"
TRAIN = "train"
#: the spans under which set-up time counts as explained
LEAF_PREFIXES = ("xla.", "compile.", "metrics_buffer.")
LEAF_NAMES = ("executor.init_params", "executor.train_step",
              "fit.loader_next")
METRICS = ("host_init_s", "step_trace_s", "step_backend_compile_s",
           "xla_cache_load_s", "xla_cache_misses", "setup_attributed_share",
           "retraces_after_warmup")


def _end(ev: dict) -> float:
    return ev["ts"] + ev["dur"]


def _fun_name(ev: dict):
    return (ev.get("attrs") or {}).get("fun_name")


def _holds(outer: dict, ev: dict) -> bool:
    """The middle of ``ev`` lies in ``outer`` (an event XLA stamped on
    another clock may stick out of its caller's span by microseconds)."""
    return outer["ts"] <= ev["ts"] + ev["dur"] / 2 <= _end(outer)


def union_s(intervals) -> float:
    """Seconds under the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def reduce_ring(events, counters, dropped: int, warmup_groups: int) -> dict:
    """The seven numbers, each ``None`` where the ring cannot give it:
    an event was dropped, the loop closed fewer than ``warmup_groups``
    epochs, or no ``executor.jit`` names the train step (a program
    from before the recorder heard XLA). Besides ``METRICS``' keys:
    ``setup_s`` (the ring's first stamp to the end of set-up),
    ``by_span`` and ``by_function`` (summed seconds in set-up by span
    name, and by ``fun_name`` then span name) and ``first_compile_s``
    (the train step's first trace + lower + backend compile), for the
    run's earlier lines."""
    out = dict.fromkeys(METRICS)
    spans = [e for e in events if e["kind"] == "span"]
    epochs = sorted((e for e in spans if e["name"] == EPOCH_SPAN), key=_end)
    jits = {}
    for e in events:
        if e["name"] == JIT_MARK and e.get("attrs"):
            jits[e["attrs"]["name"]] = e["attrs"]["fun_name"]
    if dropped or warmup_groups < 1 or len(epochs) < warmup_groups \
            or TRAIN not in jits:
        return out
    setup_end, window_end = _end(epochs[warmup_groups - 1]), _end(epochs[-1])
    start = min(e["ts"] for e in events)
    setup = [e for e in spans if e["ts"] < setup_end]

    others = [e for e in spans for name, fun in jits.items()
              if name != TRAIN and fun == jits[TRAIN]
              and e["name"] == f"executor.{name}_step"]

    def of_train_step(ev: dict) -> bool:
        return _fun_name(ev) == jits[TRAIN] \
            and not any(_holds(o, ev) for o in others)

    def summed(events_, *names) -> float:
        return sum(e["dur"] for e in events_ if e["name"] in names)

    step = [e for e in setup if of_train_step(e)]
    out["host_init_s"] = summed(setup, "executor.init_params")
    out["step_trace_s"] = summed(step, "xla.trace", "xla.lower")
    out["step_backend_compile_s"] = summed(step, "xla.backend_compile")
    out["xla_cache_load_s"] = summed(setup, "xla.cache_load")
    out["xla_cache_misses"] = counters.get("xla.cache_misses", 0)
    leaves = [(e["ts"], min(_end(e), setup_end)) for e in setup
              if e["name"].startswith(LEAF_PREFIXES)
              or e["name"] in LEAF_NAMES]
    out["setup_attributed_share"] = \
        100.0 * union_s(leaves) / (setup_end - start)
    out["retraces_after_warmup"] = sum(
        1 for e in spans if e["name"] == "xla.trace"
        and _fun_name(e) in jits.values()
        and setup_end <= e["ts"] < window_end)

    out["setup_s"] = setup_end - start
    by_span, by_function = {}, {}
    for e in setup:
        by_span[e["name"]] = by_span.get(e["name"], 0.0) + e["dur"]
        if e["name"].startswith("xla.") and _fun_name(e):
            row = by_function.setdefault(_fun_name(e), {})
            row[e["name"]] = row.get(e["name"], 0.0) + e["dur"]
    out["by_span"], out["by_function"] = by_span, by_function
    first = {}
    for e in step:
        first.setdefault(e["name"], e["dur"])
    out["first_compile_s"] = {n: first.get(n, 0.0) for n in (
        "xla.trace", "xla.lower", "xla.backend_compile")}
    return out


def report(r: dict, counters: dict, top: int = 6) -> list:
    """The reduction in words, for the traced run's earlier lines."""
    heavy = sorted(r["by_function"].items(),
                   key=lambda kv: -sum(kv[1].values()))[:top]
    first = r["first_compile_s"]
    return [
        f"set-up by the recorder: {r['setup_s']:.3f}s from its first "
        f"event to the end of warm-up, "
        f"{r['setup_attributed_share']:.2f}% under a leaf span; summed "
        "seconds by span: " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(r["by_span"].items())
            if k.startswith(("compile.", "model.", "executor.init",
                             "executor.eval", "xla."))),
        "XLA's events in set-up by function, seconds: " + "; ".join(
            f"{fun} " + " ".join(f"{k[4:]} {v:.3f}"
                                 for k, v in sorted(row.items()))
            for fun, row in heavy),
        "the train step's first compile: trace "
        f"{first['xla.trace']:.3f} + lower {first['xla.lower']:.3f} + "
        f"backend compile {first['xla.backend_compile']:.3f} = "
        f"{sum(first.values()):.3f}s; cache: "
        + ", ".join(f"{k[4:]} {int(counters.get(k, 0))}" for k in (
            "xla.cache_requests", "xla.cache_hits", "xla.cache_misses")),
    ]


# ----------------------------------------------------------------------
# what the readers in layer_metrics/ call
# ----------------------------------------------------------------------
def reduced(ctx) -> dict:
    """The reduction of the run behind ``ctx``, made once and kept on
    it. The ring is the process's (``flexflow_tpu.obs.events``): the
    runner clears it just before ``compile()`` and nothing clears it
    after."""
    if not hasattr(ctx, "setup_reduced"):
        from flexflow_tpu.obs import events as obs
        r = reduce_ring(obs.events(), ctx.counters, obs.dropped(),
                        int(ctx.cell.traffic.get("warmup_groups", 2)))
        ctx.setup_reduced = r
        if "setup_s" in r:
            for line in report(r, ctx.counters):
                print(f"[bench] {line}", flush=True)
    return ctx.setup_reduced
