"""``retraces_after_warmup``: ``xla.trace`` events of a function that an
``executor.jit`` names, stamped after the set-up's end and before the
last ``fit.epoch`` closes. Must read 0. It sees the retrace that hits
the compile cache and adds no file, which ``in_window_compiles``
cannot."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["retraces_after_warmup"]
