"""``xing_idle_attributed_share.train``: of the device's idle time
inside the traced groups, the percent that falls under one of the
program's own host spans (``ff:<name>``) other than ``fit.epoch``.
``idle_attributed_share.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import span_reduce


def read(ctx):
    r = span_reduce.reduced(ctx)
    if not r or not r["span_ns"] or not r["idle_ns"]:
        return None
    named = sum(ns for name, ns in r["idle_by_span"].items()
                if name not in (span_reduce.EPOCH_SPAN, span_reduce.NO_SPAN))
    return 100.0 * named / r["idle_ns"]
