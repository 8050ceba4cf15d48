"""``xla_cache_misses``: the recorder's counter ``xla.cache_misses``
over the run (JAX counts a miss when it writes the new entry). 0 says
the start was warm, which is what makes two ``setup_s`` comparable."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["xla_cache_misses"]
