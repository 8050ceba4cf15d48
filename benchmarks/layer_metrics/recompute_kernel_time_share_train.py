"""``recompute_kernel_time_share.train``: of
``recompute_time_share.train``'s ops, the Mosaic calls (the program's
kernels and XLA's grouped products), over device busy time in the traced
groups, in percent: what a policy that keeps the kernels' outputs would
give back."""
from benchmarks.harness import remat_reduce


def read(ctx):
    return remat_reduce.time_share(ctx, "mosaic_ns")
