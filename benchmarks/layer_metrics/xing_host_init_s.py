"""``xing_host_init_s``: summed ``executor.init_params`` spans of the set-up (each
draw of the weights on the host and their placement), in seconds.
``host_init_s``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["host_init_s"]
