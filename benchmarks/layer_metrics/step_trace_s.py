"""``step_trace_s``: summed ``xla.trace`` + ``xla.lower`` spans of the
train step's ``fun_name`` in the set-up (Python tracing to a jaxpr and
lowering to MLIR: the inspection's and ``fit``'s own), in seconds."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["step_trace_s"]
