"""``opt_time_share.train``: device self time of the ops whose
``op_name`` is under ``ff.optimizer`` over device busy time in the
traced groups, in percent. (XLA fuses Adam's update into each weight's
gradient matmul, and that fusion carries the matmul's ``op_name``: it
counts as backward, and only the update of the weights with no matmul
to fuse into lands here.)"""
from benchmarks.harness import span_reduce


def read(ctx):
    return span_reduce.phase_share(ctx, "optimizer")
