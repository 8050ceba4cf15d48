"""``weights_and_optimizer_gib``: ``device_bytes`` of the last
``executor.init_params`` span before the window (parameters and the ops'
state, one device's) + ``device_bytes`` of ``compile.opt_state``, in
GiB: what no rematerialisation policy can give back."""
from benchmarks.harness import remat_reduce


def read(ctx):
    return remat_reduce.placed_gib(ctx)
