"""``xing_xla_cache_misses``: the recorder's counter ``xla.cache_misses`` over
the run. 0 says the start was warm, which is what makes two ``setup_s``
comparable.
``xla_cache_misses``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["xla_cache_misses"]
