"""``setup_attributed_share``: of the time from the recorder's first
event to the end of the warm-up, the percent under the union of the
leaf spans (``xla.*``, ``executor.init_params``, ``compile.*``, the
warm-up's ``executor.train_step`` / ``metrics_buffer.*`` /
``fit.loader_next``), as ``idle_attributed_share.train`` does for the
device's idle time."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["setup_attributed_share"]
