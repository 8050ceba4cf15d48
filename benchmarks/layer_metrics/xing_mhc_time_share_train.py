"""``xing_mhc_time_share.train``: device self time of the ops of the
hyper-connection nodes (``OP_HYPER_CONNECTION``: the norm of a token's
streams, the product with ``phi``, the gates, the Sinkhorn iterations
and the passes over the streams that read and write them), forward,
backward and recomputation, over device busy time in the traced groups,
in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_HYPER_CONNECTION")
