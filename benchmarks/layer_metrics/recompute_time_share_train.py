"""``recompute_time_share.train``: self time of the device ops inside
the marks whose ``op_name`` has ``rematted_computation`` as a part (the
forward work a ``jax.checkpoint`` runs again for its backward; an op with
no name of its own reads as the nearest named op before it), over device
busy time in the traced groups, in percent. 0.0 where the step holds no
checkpoint; the phase split books all of it as backward."""
from benchmarks.harness import remat_reduce


def read(ctx):
    return remat_reduce.time_share(ctx, "recomputed_ns")
