"""``trinity_moe_time_share.train``: device self time of the ops of the
routed-experts layers (``OP_ROUTED_EXPERTS``: the sigmoid router, sort,
gathers, the grouped products, the shared expert), forward, backward
and recomputation, over device busy time in the traced groups, in
percent. ``moe_time_share.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_ROUTED_EXPERTS")
