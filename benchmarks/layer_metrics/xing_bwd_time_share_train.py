"""``xing_bwd_time_share.train``: device self time of the ops in the
backward phase (``span_reduce.phase_of``: a rematerialised block's second
forward pass lies inside a ``transpose(`` and counts as backward) over
device busy time in the traced groups, in percent.
``bwd_time_share.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import span_reduce


def read(ctx):
    return span_reduce.phase_share(ctx, "backward")
