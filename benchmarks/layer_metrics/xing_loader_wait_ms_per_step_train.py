"""``xing_loader_wait_ms_per_step.train``: summed ``ff:fit.loader_next``
spans of the traced groups (the fetch that ends an epoch among them)
over the steps dispatched there, in milliseconds.
``loader_wait_ms_per_step.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import span_reduce


def read(ctx):
    steps = span_reduce.span_ms(ctx, "executor.train_step")
    waits = span_reduce.span_ms(ctx, "fit.loader_next")
    if steps is None or waits is None:
        return None
    return waits[1] / steps[0]
