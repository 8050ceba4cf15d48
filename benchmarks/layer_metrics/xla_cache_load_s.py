"""``xla_cache_load_s``: summed ``xla.cache_load`` spans of the set-up,
every function's: the seconds the persistent compile cache took to
read and deserialise what it had. They lie inside
``xla.backend_compile`` spans and are never added to them."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["xla_cache_load_s"]
