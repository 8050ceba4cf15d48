"""``step_backend_compile_s``: summed ``xla.backend_compile`` spans of
the train step's ``fun_name`` in the set-up, in seconds: XLA's compile on
a cold start, the persistent cache's read-and-deserialise on a warm
one (``xla_cache_load_s`` is the part of it that was the read)."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["step_backend_compile_s"]
