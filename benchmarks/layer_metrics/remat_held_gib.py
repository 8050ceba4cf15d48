"""``remat_held_gib``: ``entry_bytes`` + ``kept_bytes`` of the
``remat.wrap`` instants at ``depth`` 0, one trace of the train step's
worth, in GiB: what the checkpoints hold from the forward to the
backward. 0.0 where the step holds no checkpoint."""
from benchmarks.harness import remat_reduce


def read(ctx):
    return remat_reduce.held_gib(ctx)
