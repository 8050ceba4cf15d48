"""From the names the program puts into a profiler trace to numbers:
device time per phase of the train step, per kernel and per layer, and
the device's idle time split over the program's own host spans.

What the program names (``flexflow_tpu``; a program that names nothing
makes every reader here return ``None``):

  - ``jax.named_scope`` s in the train step: ``ff.forward``, ``ff.loss``
    and ``ff.optimizer`` around the phases, and each layer's name around
    its ops. They reach the compiled step as ``op_name`` metadata
    (``jit(step_fn)/transpose(jvp(ff.forward))/<layer>/dot_general``);
    the trace names a device op by its HLO instruction, and
    ``ctx.step_text`` maps instruction to ``op_name``. A fusion has one
    ``op_name``, of the compiler's choosing: the fusions that hold a
    weight's gradient matmul and Adam's update of it carry the matmul's.
  - kernel names: a Pallas call's ``name`` is its instruction's name
    (``flash_attention_fwd.3``) and the second to last part of its
    ``op_name`` (``.../flash_attention_fwd/pallas_call``).
  - host spans: every enabled ``obs.events.span`` is a
    ``TraceAnnotation("ff:<name>")`` on the profiler's clock.

Three steps, as in ``trace_reduce``: ``extract`` reads the
``.xplane.pb`` into plain lists, ``instructions`` reads the compiled
step's text, ``reduce_spans`` does the arithmetic, checked by hand on
``benchmarks/testdata/span_trace.json``.

What is counted. The window, busy time and idle time are
``trace_reduce``'s: first mark's start to last mark's end, the union of
the "XLA Ops" intervals clipped to it, and the rest. A device op's
*self time* is its time not covered by an op nested in it. Its *phase*
is ``optimizer`` if its ``op_name`` is under ``ff.optimizer``,
``backward`` if a ``transpose(`` wraps ``ff.forward`` or ``ff.loss``,
``forward`` if it is under either otherwise, else ``unscoped``; its
*layer* is the part of the ``op_name`` after the phase's (the phase's
own scope for the loss and the optimizer). Each idle interval is split
over the innermost ``ff:`` span that covers it on the thread that holds
the most ``ff:`` spans (``fit``'s).
"""
from __future__ import annotations

import re

from benchmarks.harness import cells, trace_reduce

SPAN_PREFIX = "ff:"
EPOCH_SPAN = "fit.epoch"          # covers everything: attributes nothing
PHASES = ("forward", "backward", "optimizer", "unscoped")
NO_SPAN = "(no span)"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, \w+=")
_BACKWARD = re.compile(r"transpose\([^/]*ff\.(?:forward|loss)")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")          # jvp(..), transpose(..)
_DOTTED = re.compile(r"^[A-Za-z_]\w*(?:\.\w+)+$")  # moe.shared, ff.loss
UNSCOPED = "unscoped"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


# ----------------------------------------------------------------------
# the trace
# ----------------------------------------------------------------------
def extract(xplane_path: str, mark_prefix: str = "bench.") -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "marks": [[name, start_ns, dur_ns], ...],
    "spans": [[name, thread, start_ns, dur_ns], ...]}`` — devices and
    marks as ``trace_reduce.extract`` gives them (the same parse, which
    the runner makes once and keeps as ``ctx.span_events``), spans the
    host events named ``ff:<name>`` (the prefix cut off) with the line
    (thread) they are on."""
    return trace_reduce.extract(xplane_path, mark_prefix, SPAN_PREFIX)


# ----------------------------------------------------------------------
# the compiled step's text
# ----------------------------------------------------------------------
def instructions(step_text: str) -> dict:
    """HLO instruction name -> ``{"op_name": str, "mosaic": bool,
    "operands": [(dtype, dims)], "results": [(dtype, dims)]}``; shapes
    only for Mosaic custom calls (what a kernel's roofline needs)."""
    out = {}
    for line in step_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        entry = {"op_name": found.group(1) if found else "",
                 "mosaic": MOSAIC_TARGET in rest}
        if entry["mosaic"]:
            results, _, call = rest.partition(" custom-call(")
            operands = _OPERANDS.search(call)
            entry["results"] = _shapes(results)
            entry["operands"] = _shapes(operands.group(1)) \
                if operands else []
        out[name] = entry
    return out


def _shapes(text: str) -> list:
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _SHAPE.findall(text)]


def phase_of(op_name: str) -> str:
    if "ff.optimizer" in op_name:
        return "optimizer"
    if _BACKWARD.search(op_name):
        return "backward"
    if "ff.forward" in op_name or "ff.loss" in op_name:
        return "forward"
    return "unscoped"


def layer_of(op_name: str) -> str:
    """The scope right under the phase's: the layer's name. The loss
    and the optimizer have no layers; they are their own."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        for own in ("ff.optimizer", "ff.loss"):
            if own in part:
                return own
        if "ff.forward" in part:
            return parts[i + 1] if i + 2 < len(parts) else "ff.forward"
    return ""


def innermost_scope(op_name: str, layer_names=()) -> str:
    """The innermost part of ``op_name`` that the PROGRAM opened: a
    layer's name, or a dotted scope (``moe.shared``, ``ssm.scan``,
    ``remat.block``, ``ff.optimizer``; the program's scopes carry a dot
    and JAX's own, ``jvp(..)``, ``checkpoint``, ``while``, ``body``,
    ``pallas_call``, do not), read through JAX's wrappers
    (``transpose(jvp(ff.forward))`` is ``ff.forward``). The last part
    is the primitive and is no scope. ``"unscoped"`` where there is
    none."""
    for part in reversed(op_name.split("/")[:-1]):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in layer_names or _DOTTED.match(part):
            return part
    return UNSCOPED


def scoped_names(instr: dict, layer_names=()) -> dict:
    """HLO instruction name -> ``<innermost scope>/<instruction>``: the
    names a traced run's ``breakdown`` prints."""
    return {name: innermost_scope(entry["op_name"], layer_names)
            + "/" + name for name, entry in instr.items()}


def kernel_of(instruction: str, entry: dict) -> str:
    """A Mosaic call's kernel name: the scope its ``pallas_call`` sits
    in, else its instruction's name without the counter."""
    parts = entry["op_name"].split("/")
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        return parts[-2]
    return instruction.rsplit(".", 1)[0]


# ----------------------------------------------------------------------
# the arithmetic
# ----------------------------------------------------------------------
def innermost_segments(spans) -> list:
    """Disjoint ``[start, end, name]`` pieces, each named by the
    innermost of the (properly nested) ``[name, start, dur]`` spans that
    cover it; time no span covers is left out."""
    out: list = []
    stack: list = []                    # [name, end]
    at = None                           # where the open piece starts

    def emit(upto):
        if stack and upto > at:
            out.append([at, upto, stack[-1][0]])

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            at = stack.pop()[1]
        emit(s)
        at = s
        stack.append([name, s + d])
    while stack:
        emit(stack[-1][1])
        at = stack.pop()[1]
    return out


def fit_thread(spans):
    """The line that holds the most ``ff:`` spans."""
    count: dict = {}
    for _, thread, _, _ in spans:
        count[thread] = count.get(thread, 0) + 1
    return max(sorted(count), key=count.get) if count else None


def window_spans(events: dict, lo: int, hi: int) -> list:
    """The ``[name, start, dur]`` spans of ``fit``'s thread, clipped to
    the window ``[lo, hi)``."""
    thread = fit_thread(events["spans"])
    return [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
            for n, t, s, d in events["spans"]
            if t == thread and s < hi and s + d > lo]


def host_segments(events: dict) -> list:
    """``innermost_segments`` of the window's spans on ``fit``'s
    thread, ``fit.epoch`` left out (it covers everything and so names
    nothing): what the host was doing, for ``trace_reduce``'s idle
    gaps. ``[]`` without marks."""
    marks = events["marks"]
    if not marks:
        return []
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    return innermost_segments(
        [sp for sp in window_spans(events, lo, hi) if sp[0] != EPOCH_SPAN])


def reduce_spans(events: dict, instr: dict) -> dict:
    """See the module's docstring. Times in nanoseconds, summed over the
    devices of the trace; ``{}`` without marks or devices."""
    marks = events["marks"]
    if not marks or not events["devices"]:
        return {}
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    mine = window_spans(events, lo, hi)
    segments = innermost_segments(mine)
    busy_ns = idle_ns = 0
    phase_ns = dict.fromkeys(PHASES, 0)
    layer_ns: dict = {}
    kernel_ns: dict = {}
    kernel_calls: dict = {}             # kernel -> {instruction: events}
    unscoped_ns: dict = {}
    idle_by_span: dict = {}
    for _, ops in sorted(events["devices"].items()):
        inside = [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
                  for n, s, d in ops if s < hi and s + d > lo]
        busy = trace_reduce.union((s, s + d) for _, s, d in inside)
        busy_ns += trace_reduce.total(busy)
        for name, ns in trace_reduce.self_times(inside).items():
            entry = instr.get(name, {"op_name": "", "mosaic": False})
            phase = phase_of(entry["op_name"])
            phase_ns[phase] += ns
            if phase == "unscoped":
                unscoped_ns[name] = unscoped_ns.get(name, 0) + ns
            else:
                layer = layer_of(entry["op_name"])
                layer_ns[layer] = layer_ns.get(layer, 0) + ns
            if entry["mosaic"]:
                kernel = kernel_of(name, entry)
                kernel_ns[kernel] = kernel_ns.get(kernel, 0) + ns
        for name, _, _ in inside:
            entry = instr.get(name)
            if entry and entry["mosaic"]:
                calls = kernel_calls.setdefault(kernel_of(name, entry), {})
                calls[name] = calls.get(name, 0) + 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            idle_ns += b - a
            for name, ns in trace_reduce.gap_pieces(a, b, segments):
                name = name or NO_SPAN
                idle_by_span[name] = idle_by_span.get(name, 0) + ns
    span_ns: dict = {}                  # name -> [count, summed ns]
    for name, _, d in mine:
        got = span_ns.setdefault(name, [0, 0])
        got[0] += 1
        got[1] += d
    return {"n_devices": len(events["devices"]), "window_ns": hi - lo,
            "busy_ns": busy_ns, "idle_ns": idle_ns, "phase_ns": phase_ns,
            "scoped": busy_ns > phase_ns["unscoped"],
            "layer_ns": layer_ns, "kernel_ns": kernel_ns,
            "kernel_calls": kernel_calls, "unscoped_ns": unscoped_ns,
            "idle_by_span": idle_by_span, "span_ns": span_ns}


# ----------------------------------------------------------------------
# what the readers in layer_metrics/ call
# ----------------------------------------------------------------------
def reduced(ctx):
    """The reduction of the traced run behind ``ctx``
    (``ctx.span_events``, ``ctx.span_instructions``: the runner's one
    parse of the trace and of the step's text), made once and kept on
    it; ``None`` where the run left no trace."""
    if hasattr(ctx, "span_reduced"):
        return ctx.span_reduced
    ctx.span_reduced = None
    if not ctx.trace or not ctx.span_events:
        return None
    r = reduce_spans(ctx.span_events, ctx.span_instructions)
    ctx.span_reduced = r or None
    if r:
        for line in report(r):
            print(f"[bench] {line}", flush=True)
    return ctx.span_reduced


def _top(table: dict, n: int = 10) -> str:
    return ", ".join(f"{k} {v / 1e6:.3f}" for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:n]) or "none"


def report(r: dict) -> list:
    """The reduction in words, for the traced run's earlier lines."""
    busy = r["busy_ns"] or 1
    return [
        "phases, device ms over the traced groups (share of busy): "
        + ", ".join(f"{p} {r['phase_ns'][p] / 1e6:.3f} "
                    f"({100 * r['phase_ns'][p] / busy:.2f}%)"
                    for p in PHASES),
        f"heaviest layer scopes, ms: {_top(r['layer_ns'])}",
        f"heaviest unscoped ops, ms: {_top(r['unscoped_ns'])}",
        f"kernels, ms (share of busy "
        f"{100 * sum(r['kernel_ns'].values()) / busy:.3f}%): "
        f"{_top(r['kernel_ns'])}",
        f"idle {r['idle_ns'] / 1e6:.3f} ms by host span: "
        f"{_top(r['idle_by_span'])}",
        "host spans in the window, count and ms: " + (", ".join(
            f"{k} {c} x {ns / c / 1e6:.3f}"
            for k, (c, ns) in sorted(r["span_ns"].items())) or "none"),
    ]


def phase_share(ctx, phase: str):
    """Percent of busy time in ``phase``; ``None`` where no device op
    carries a phase scope."""
    r = reduced(ctx)
    if not r or not r["scoped"]:
        return None
    return 100.0 * r["phase_ns"][phase] / r["busy_ns"]


def span_ms(ctx, name: str):
    """``(count, summed ms)`` of the ``ff:<name>`` spans in the window,
    ``None`` where there is none."""
    r = reduced(ctx)
    if not r or name not in r["span_ns"]:
        return None
    count, ns = r["span_ns"][name]
    return count, ns / 1e6


def kernel_roofline(ctx, kernel: str):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` (``flops/flash_attention.py`` over the table of peaks)
    over the device time they took."""
    r = reduced(ctx)
    if not r or ctx.peak is None or not r["kernel_ns"].get(kernel):
        return None
    cost = cells.load_module(ctx.cell.bench_dir, "flops", "flash_attention")
    causal = {l.name: bool(l.params.get("causal", False))
              for l in ctx.model.layers}
    least_s = 0.0
    for name, n_events in r["kernel_calls"][kernel].items():
        entry = ctx.span_instructions[name]
        if len(entry["operands"]) < 3:      # the text gave no shapes
            return None
        seconds, _ = cost.roofline_s(
            kernel, entry["operands"], entry["results"],
            causal.get(layer_of(entry["op_name"]), False), ctx.peak)
        least_s += n_events * seconds
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
