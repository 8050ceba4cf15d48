"""``xing_dispatch_ms_per_step.train``: mean duration of the
``ff:executor.train_step`` spans in the traced groups (the host's
dispatch of one step, not the step's device time), in milliseconds.
``dispatch_ms_per_step.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import span_reduce


def read(ctx):
    got = span_reduce.span_ms(ctx, "executor.train_step")
    return None if got is None else got[1] / got[0]
