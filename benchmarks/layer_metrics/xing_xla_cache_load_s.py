"""``xing_xla_cache_load_s``: summed ``xla.cache_load`` spans of the set-up,
every function's, in seconds.
``xla_cache_load_s``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["xla_cache_load_s"]
