"""``xing_step_backend_compile_s``: summed ``xla.backend_compile`` spans of
the train step's ``fun_name`` in the set-up, in seconds: XLA's compile
on a cold start, the persistent cache's read on a warm one.
``step_backend_compile_s``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["step_backend_compile_s"]
