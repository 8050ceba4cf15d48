"""``recompute_again_time_share.train``: of
``recompute_time_share.train``'s ops, those that are a third or later
run of their work in the step (recomputed by the wrap ``remat.<site>``
that owns their ``rematted_computation`` while a wrap around it has
device time for a recomputation of the same layer through that site),
over device busy time in the traced groups, in percent. 0.0 where
nothing runs a third time."""
from benchmarks.harness import remat_reduce


def read(ctx):
    return remat_reduce.time_share(ctx, "again_ns")
