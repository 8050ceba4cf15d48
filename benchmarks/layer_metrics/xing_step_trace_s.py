"""``xing_step_trace_s``: summed ``xla.trace`` + ``xla.lower`` spans of the train
step's ``fun_name`` in the set-up, in seconds: the twelve sub-layers'
scans and checkpoints are traced here.
``step_trace_s``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["step_trace_s"]
