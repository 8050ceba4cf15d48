"""``xing_retraces_after_warmup``: ``xla.trace`` events of a jitted step of the
executor's, stamped after the set-up's end and before the last
``fit.epoch`` closes. Must read 0.
``retraces_after_warmup``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["retraces_after_warmup"]
