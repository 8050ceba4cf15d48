"""What a train step runs AGAIN and what it HOLDS for its backward, from
the names and the instants the program gives its rematerialisation,
beside ``span_reduce``, ``scope_reduce`` and ``name_reduce`` (which stay
as they are).

What the program names (``flexflow_tpu/ops/registry.py::checkpointed``,
the one place where it calls ``jax.checkpoint``):

  - on the device, a ``jax.named_scope("remat.<site>")`` around every
    checkpointed call, so an op's ``op_name`` says whose it is, next to
    JAX's own parts: a recomputed op has the part
    ``rematted_computation``, and the ``remat.<site>`` nearest before
    that part is the wrap that runs it again (its *owner*); the
    ``remat.`` parts before the owner are the wraps around it, those
    after ``rematted_computation`` the wraps whose work the owner's
    recomputation runs through:

      ``.../remat.block/checkpoint/rematted_computation/kda_2/
        remat.kda.layer/...``          the block's second run of kda_2
      ``.../remat.block/checkpoint/kda_2/remat.kda.layer/checkpoint/
        rematted_computation/...``     the layer's own second run

  - on the recorder, one ``remat.wrap`` instant each time a wrap is
    traced (``site``, ``layer`` / ``block`` / ``part``, ``depth``,
    ``entry_bytes``, ``weights_bytes``, ``kept_bytes``, ``policy``; a
    block's also ``layers``), and the attribute ``device_bytes`` on the
    ``executor.init_params`` and ``compile.opt_state`` spans.

What is counted. Self times are ``name_reduce.by_op``'s; an op with no
scope in its ``op_name`` (XLA's ``ragged-dot-none`` Mosaic calls, whose
metadata is the bare word) takes the name of the nearest named op before
it on its device, as
``scope_reduce.op_self_ns`` gives it that op's layer. A recomputed op is
a run *again* (a third or later run of its work in the step) where a
wrap AROUND its owner has device time, in this trace, for a
recomputation that runs through the owner in the same layer: the
owner's recomputation then repeats what that one has run already. A
piece of work is the layer and the innermost wrap an op lies in; it runs
once and once more for every owner that has device time for it.

The step is traced more than once a run (the runner's inspection, the
loop's first call, the eval step through the ops' own wraps), so the
instants are keyed by site, layer, block and part and the last of each
is kept; an op's own wrap recorded outside any block (an eval trace)
for a layer that some block holds is not the train step's and is left
out.

A program that names none of this (the parent of the PR that brought
this file) makes the readers return ``None``, but for a step that holds
no checkpoint at all: that reads 0.0, whoever compiled it.
"""
from __future__ import annotations

from benchmarks.harness import name_reduce, scope_reduce, span_reduce

RECOMPUTED = "rematted_computation"
SCOPE_PREFIX = "remat."
WRAP_MARK = "remat.wrap"
INIT_SPAN = "executor.init_params"
OPT_SPAN = "compile.opt_state"
EPOCH_SPAN = "fit.epoch"
GIB = float(2 ** 30)


# ----------------------------------------------------------------------
# the names
# ----------------------------------------------------------------------
def _sites(parts) -> list:
    """The ``remat.<site>`` parts in order, each once (a transposed
    op's name repeats the path to its outermost wrap)."""
    out: list = []
    for part in parts:
        if part.startswith(SCOPE_PREFIX) and part[len(SCOPE_PREFIX):] \
                not in out:
            out.append(part[len(SCOPE_PREFIX):])
    return out


def parse(op_name: str):
    """``(recomputed, owner, around, through, unit)`` of one
    ``op_name`` (the first of a merged op's): whether it has the part
    ``rematted_computation``; if so the site that runs it again (``""``
    where the program names none), the sites around that one and the
    sites its recomputation runs through; and the innermost site the op
    lies in at all (``""``: in no wrap)."""
    parts = op_name.split(";")[0].split("/")
    every = _sites(parts)
    unit = every[-1] if every else ""
    if RECOMPUTED not in parts:
        return False, "", [], [], unit
    at = parts.index(RECOMPUTED)
    before = _sites(parts[:at])
    through = [s for s in _sites(parts[at:]) if s not in before]
    return (True, before[-1] if before else "", before[:-1], through, unit)


def effective_names(events: dict, instr: dict) -> dict:
    """Instruction -> the ``op_name`` it is read by: its own, or for an
    op of ``scope_reduce.UNNAMED_PREFIXES`` that of the nearest named op
    before it on its device inside the marks."""
    marks = events["marks"]
    out: dict = {}
    if not marks:
        return out
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    for _, ops in sorted(events["devices"].items()):
        last = ""
        for name, s, d in sorted(ops, key=lambda o: o[1]):
            if s >= hi or s + d <= lo:
                continue
            own = instr.get(name, {}).get("op_name", "")
            if "/" not in own \
                    and name.startswith(scope_reduce.UNNAMED_PREFIXES):
                out.setdefault(name, last)      # its own is the bare word
            else:
                out.setdefault(name, own)
                last = own or last
    return out


# ----------------------------------------------------------------------
# the arithmetic
# ----------------------------------------------------------------------
def reduce_remat(by_op: dict, names: dict, instr: dict, calls: dict,
                 busy_ns: int) -> dict:
    """``by_op``: ``(layer, instruction) -> self ns`` (``scope_reduce.
    op_self_ns``); ``names``: :func:`effective_names`; ``calls``:
    instruction -> traced events, for the Mosaic calls
    (``span_reduce.reduce_spans``'s ``kernel_calls``, flattened).
    Returns the three sums in ns (``again_ns`` None where a recomputed
    op has no owner: a program that does not name its wraps) and
    ``work``: ``(layer, unit) -> {"owners": {site: ns},
    "mosaic_ns", "mosaic_events", "again_ns"}`` for the work that has
    recomputed time."""
    rows = []
    ran_through: set = set()        # (layer, owner, site run through)
    for (layer, name), ns in by_op.items():
        recomputed, owner, around, through, unit = parse(
            names.get(name, instr.get(name, {}).get("op_name", "")))
        if recomputed and ns > 0:
            rows.append((layer, name, ns, owner, around, unit))
            ran_through.update((layer, owner, s) for s in through)
    work: dict = {}
    total = mosaic = again = 0
    owned = True
    for layer, name, ns, owner, around, unit in rows:
        total += ns
        owned = owned and bool(owner)
        is_again = any((layer, outer, owner) in ran_through
                       for outer in around)
        row = work.setdefault((layer, unit), {
            "owners": {}, "mosaic_ns": 0, "mosaic_events": 0, "again_ns": 0})
        row["owners"][owner] = row["owners"].get(owner, 0) + ns
        if is_again:
            again += ns
            row["again_ns"] += ns
        if instr.get(name, {}).get("mosaic"):
            mosaic += ns
            row["mosaic_ns"] += ns
            row["mosaic_events"] += calls.get(name, 0)
    return {"busy_ns": busy_ns, "recomputed_ns": total, "mosaic_ns": mosaic,
            "again_ns": again if owned else None, "work": work}


def one_trace(events) -> list:
    """The ``remat.wrap`` instants' attributes, one trace's worth of
    the train step (the module's docstring says which)."""
    last: dict = {}
    for e in events:
        if e["name"] == WRAP_MARK and e.get("attrs"):
            a = e["attrs"]
            last[(a["site"], a.get("layer"), a.get("block"),
                  a.get("part"))] = a
    in_blocks = {l for a in last.values() for l in a.get("layers") or ()}
    return [a for a in last.values()
            if not (a["site"] != "block" and a.get("block") is None
                    and a.get("layer") in in_blocks)]


def held_bytes(wraps) -> int:
    """What the checkpoints hold from the forward to the backward: the
    entries and the marked values of the wraps no other wrap is
    around."""
    return sum(a["entry_bytes"] + a["kept_bytes"] for a in wraps
               if a["depth"] == 0)


def placed_bytes(events, warmup_groups: int):
    """``device_bytes`` of the last ``executor.init_params`` span before
    the window (the runner's draw from ``--seed`` replaces
    ``compile()``'s) + that of ``compile.opt_state``; None where the
    program records neither."""
    spans = [e for e in events if e["kind"] == "span"]
    epochs = sorted(e["ts"] + e["dur"] for e in spans
                    if e["name"] == EPOCH_SPAN)
    window = epochs[warmup_groups - 1] \
        if 0 < warmup_groups <= len(epochs) else float("inf")

    def last_of(name):
        got = [(e.get("attrs") or {}).get("device_bytes") for e in spans
               if e["name"] == name and e["ts"] < window]
        return got[-1] if got else None

    weights, optimizer = last_of(INIT_SPAN), last_of(OPT_SPAN)
    if weights is None or optimizer is None:
        return None
    return weights + optimizer


def report(r: dict, wraps, n_steps: int) -> list:
    """The table a traced run prints: a line for each piece of work
    that has recomputed time, and the total."""
    by_wrap: dict = {}
    for a in wraps:
        keys = [(l, "block") for l in a.get("layers") or ()] \
            if a["site"] == "block" else [(a.get("layer"), a["site"])]
        for key in keys:
            got = by_wrap.setdefault(key, [0, 0])
            got[0] += a["entry_bytes"]
            got[1] += a["kept_bytes"]
    steps = max(n_steps, 1)
    lines = ["recomputed work by layer and innermost wrap: owner site(s), "
             "runs of its forward work a step, recomputed ms a step, of "
             "which Mosaic calls (a step, ms), the wrap's entry and kept "
             "bytes"]
    for (layer, unit), row in sorted(
            r["work"].items(), key=lambda kv: -sum(kv[1]["owners"].values())):
        entry, kept = by_wrap.get((layer, unit), ("-", "-"))
        lines.append(
            f"  {layer or '(no layer)'} {unit or '(no wrap)'}: owners "
            + "+".join(sorted(o or "(unnamed)" for o in row["owners"]))
            + f", runs {1 + len(row['owners'])}, recomputed "
            f"{sum(row['owners'].values()) / steps / 1e6:.3f} ms (again "
            f"{row['again_ns'] / steps / 1e6:.3f}), Mosaic "
            f"{row['mosaic_events'] / steps:g} calls "
            f"{row['mosaic_ns'] / steps / 1e6:.3f} ms, entry {entry} kept "
            f"{kept}")
    busy = r["busy_ns"] or 1
    again = r["again_ns"]
    lines.append(
        f"  total: recomputed {r['recomputed_ns'] / steps / 1e6:.3f} ms a "
        f"step ({100 * r['recomputed_ns'] / busy:.3f}% of busy), Mosaic "
        f"calls {r['mosaic_ns'] / steps / 1e6:.3f} ms, run again "
        + ("not told (the program names no wrap)" if again is None
           else f"{again / steps / 1e6:.3f} ms")
        + f"; held by the wraps at depth 0: {held_bytes(wraps)} bytes in "
        f"{sum(1 for a in wraps if a['depth'] == 0)} of {len(wraps)} wraps")
    return lines


# ----------------------------------------------------------------------
# what the readers in layer_metrics/ call
# ----------------------------------------------------------------------
def _ring():
    from flexflow_tpu.obs import events as obs
    return obs.events(), obs.dropped()


def wraps_of(ctx):
    """:func:`one_trace` of the run behind ``ctx``, kept on it; None
    where the ring dropped an event."""
    if not hasattr(ctx, "remat_wraps"):
        events, dropped = _ring()
        ctx.remat_wraps = None if dropped else one_trace(events)
    return ctx.remat_wraps


def reduced(ctx):
    """:func:`reduce_remat` of the traced run behind ``ctx``, made once
    and kept on it, its table printed; ``None`` where the run left no
    trace."""
    if hasattr(ctx, "remat_reduced"):
        return ctx.remat_reduced
    ctx.remat_reduced = None
    r = span_reduce.reduced(ctx)
    by_op = name_reduce.by_op(ctx) if r else None
    if not by_op:
        return None
    events, instr = ctx.span_events, ctx.span_instructions
    calls = {name: n for table in r["kernel_calls"].values()
             for name, n in table.items()}
    ctx.remat_reduced = reduce_remat(
        by_op, effective_names(events, instr), instr, calls, r["busy_ns"])
    n_steps = len(events["marks"]) * int(ctx.steps_per_group)
    for line in report(ctx.remat_reduced, wraps_of(ctx) or [], n_steps):
        print(f"[bench] {line}", flush=True)
    return ctx.remat_reduced


def time_share(ctx, key: str):
    """Percent of device busy time in ``recomputed_ns`` | ``mosaic_ns``
    | ``again_ns``; ``None`` without a trace (or, for ``again_ns``,
    where the program names no wrap)."""
    r = reduced(ctx)
    if not r or not r["busy_ns"] or r[key] is None:
        return None
    return 100.0 * r[key] / r["busy_ns"]


def held_gib(ctx):
    """``remat_held_gib``: 0.0 where the step holds no checkpoint,
    ``None`` where it does and the program recorded no wrap."""
    wraps = wraps_of(ctx)
    if wraps is None:
        return None
    if not wraps and (RECOMPUTED in ctx.step_text
                      or "/checkpoint/" in ctx.step_text):
        return None
    return held_bytes(wraps) / GIB


def placed_gib(ctx):
    """``weights_and_optimizer_gib``."""
    events, dropped = _ring()
    got = None if dropped else placed_bytes(
        events, int(ctx.cell.traffic.get("warmup_groups", 2)))
    return None if got is None else got / GIB
