"""``host_init_s``: summed ``executor.init_params`` spans of the set-up
(each draw of the weights on the host and their placement: the one in
``compile()`` and the runner's from ``--seed``), in seconds."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["host_init_s"]
