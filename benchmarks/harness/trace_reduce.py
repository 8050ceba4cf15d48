"""From a profiler trace to numbers: device busy and idle time, the
share of Mosaic kernels and of collectives, the heaviest device
operations and the longest idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded
trace (``benchmarks/testdata``) without a chip:

  ``extract(xplane_path, ...)``  reads the ``.xplane.pb`` the JAX profiler
      wrote (``jax.profiler.ProfileData``) into plain lists:
      ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
         "async": {plane: [...]}, "marks": [[name, start_ns, dur_ns], ...]}``
      — the events of each TPU plane's "XLA Ops" line (what the core
      ran) and of its "Async XLA Ops" line (copies and collectives in
      flight beside it), and the host's ``TraceAnnotation`` spans whose
      name starts with ``mark_prefix``.
  ``reduce_trace(events, kernel_names, names, host)``  does the
      arithmetic.

What is counted: *busy* is the union of the intervals in which an XLA
op ran on a device's core, clipped to the window (an async copy or
collective in flight while the core sits idle is not busy time, but it
does count towards the collective share); the *window* runs from the
start of the first mark to the end of the last (the benchmark marks each
group of steps); idle is what is left. A device's numbers are its own;
the reported ones are the mean over the devices in the trace.

The ten heaviest ops and the ten longest gaps are the run's
``breakdown``, the only trace the writer of the next issue sees. The
caller hands in the program's names for both (``span_reduce``): an op
prints as ``<innermost scope>/<instruction>`` and a gap as ``<host
span>/after:<op>``, since XLA's ``multiply_reduce_fusion.22`` names no
layer and ``between-groups`` no cause.
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # copies and collectives in flight
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
NAME_CUT = 80                    # characters of a printed op's name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str, mark_prefix: str = "bench.",
            span_prefix: str = "") -> dict:
    """The one parse of a traced run's file. With a ``span_prefix``,
    also ``"spans"``: the host events so named (the prefix cut off) as
    ``[name, thread, start_ns, dur_ns]``, for ``span_reduce``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices, in_flight, marks, spans = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = devices if line.name == OPS_LINE else in_flight
                    into[plane.name] = [
                        [op_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(mark_prefix):
                        marks.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
                    elif span_prefix and ev.name.startswith(span_prefix):
                        spans.append([ev.name[len(span_prefix):],
                                      line.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    marks.sort(key=lambda m: m[1])
    spans.sort(key=lambda s: s[2])
    return {"devices": devices, "async": in_flight, "marks": marks,
            "spans": spans}


def op_name(event_name: str) -> str:
    """The trace names a device op by its whole HLO instruction,
    ``%fusion.3 = f32[8,128]{1,0} fusion(...)``; keep ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ----------------------------------------------------------------------
# interval arithmetic (integers of nanoseconds)
# ----------------------------------------------------------------------
def union(intervals) -> list:
    """Sorted, disjoint ``[start, end)`` pairs covering the same time."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(spans, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the spans cover."""
    return total(clip(union(spans), lo, hi))


def self_times(ops) -> dict:
    """Time per op name not covered by an op nested inside it (a
    ``while`` or ``call`` holds its body's ops on the same line)."""
    out: dict = {}
    stack: list = []          # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def gap_pieces(a: int, b: int, host) -> list:
    """``[(host span | None, ns)]``: the idle interval ``[a, b)`` split
    over the sorted, disjoint ``[start, end, name]`` pieces of ``host``
    that cover it, and what none covers."""
    out, left = [], b - a
    for s, e, name in host:
        if e <= a:
            continue
        if s >= b:
            break
        ns = min(e, b) - max(s, a)
        out.append((name, ns))
        left -= ns
    if left:
        out.append((None, left))
    return out


def reduce_trace(events: dict, kernel_names, names: dict,
                 host) -> dict:
    """See the module's docstring. ``kernel_names``: the names of the
    compiled step's Mosaic custom calls, as its HLO text gives them.
    ``names``: HLO instruction -> the name to print for it in
    ``device_ops`` and behind a gap's ``after:``
    (``span_reduce.scoped_names``: the program's innermost scope in
    front of XLA's name; an instruction it does not hold prints as XLA
    names it). ``host``: what the host was doing
    (``span_reduce.host_segments``); an idle gap's time goes to the
    host span that covers it, and only what none covers is named by
    where it falls (``between-groups`` | ``inside-group``)."""
    def shown(name: str) -> str:
        return names.get(name, name)[:NAME_CUT]

    marks = events["marks"]
    if not marks or not events["devices"]:
        return {}
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    kernels = {n.lstrip("%") for n in kernel_names}
    bounds = sorted({s for _, s, _ in marks} | {s + d for _, s, d in marks})
    busy_ns, kernel_ns, coll_ns = [], [], []
    op_self: dict = {}
    gaps: dict = {}
    for plane, ops in sorted(events["devices"].items()):
        spans = [(s, s + d) for _, s, d in ops]
        busy = clip(union(spans), lo, hi)
        busy_ns.append(total(busy))
        kernel_ns.append(covered(
            ((s, s + d) for n, s, d in ops if n.lstrip("%") in kernels),
            lo, hi))
        coll_ns.append(covered(
            ((s, s + d)
             for n, s, d in ops + events.get("async", {}).get(plane, [])
             if is_collective(n)), lo, hi))
        inside = [o for o in ops if o[1] < hi and o[1] + o[2] > lo]
        for name, ns in self_times(inside).items():
            op_self[name] = op_self.get(name, 0) + ns
        # idle gaps of this device, named by the host span over them
        # (else by where they fall) and by the op that ran last before
        ends = sorted((s + d, n) for n, s, d in inside)
        end_times = [e for e, _ in ends]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            crosses = any(a <= m <= b for m in bounds[1:-1]) \
                or a == lo or b == hi
            i = bisect.bisect_right(end_times, a)
            after = "/after:" + (shown(ends[i - 1][1]) if i else "start")
            where = "between-groups" if crosses else "inside-group"
            for span, ns in gap_pieces(a, b, host):
                name = (span or where) + after
                gaps[name] = gaps.get(name, 0) + ns
    n = len(busy_ns)
    window = hi - lo
    busy_mean = sum(busy_ns) / n
    return {
        "n_devices": n,
        "window_s": window / 1e9,
        "busy_s": busy_mean / 1e9,
        "idle_share": 1.0 - busy_mean / window,
        "kernel_time_share": (sum(kernel_ns) / sum(busy_ns)
                              if sum(busy_ns) else 0.0),
        "collective_time_share": sum(coll_ns) / n / window,
        "n_marks": len(marks),
        "device_ops": [[shown(k), v / n / 1e9] for k, v in sorted(
            op_self.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:10]],
    }
