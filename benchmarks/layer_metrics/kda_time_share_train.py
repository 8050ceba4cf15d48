"""``kda_time_share.train``: device self time of the ops of the gated
delta-rule linear-attention layers (``OP_GATED_DELTA_RULE``: the
projections, the taps, the gates, the in-chunk matrices, the triangular
solve and the scan over the chunks), forward, backward and
recomputation, over device busy time in the traced groups, in
percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_GATED_DELTA_RULE")
