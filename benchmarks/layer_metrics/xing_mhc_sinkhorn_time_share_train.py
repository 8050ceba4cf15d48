"""``xing_mhc_sinkhorn_time_share.train``: of
``xing_mhc_time_share.train``'s ops, those the nodes run under their
name scope ``mhc.sinkhorn`` (the exponential and the loop of
iterations, whose body's ops count once each and the loop's own event
only for what they leave; forward, recomputation and the loop's
transpose), over device busy time in the traced groups, in percent."""
from benchmarks.harness import name_reduce, scope_reduce


def read(ctx):
    return name_reduce.share_of_scope(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_HYPER_CONNECTION",
        "mhc.sinkhorn")
