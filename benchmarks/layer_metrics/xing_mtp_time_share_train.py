"""``xing_mtp_time_share.train``: device self time of the ops of the
multi-token-prediction module's layers (its norms, ``W_eh``, its decoder
layer with that layer's four hyper-connection nodes, its loss; not its
half of the shared head's product, which is one op with the trunk's),
both phases, over device busy time in the traced groups, in percent.
``mtp_time_share.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx, scope_reduce.in_mtp_module)
