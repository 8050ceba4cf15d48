"""``short_conv_time_share.train``: device self time of the ops of the
gated short-convolution layers (``OP_GATED_SHORT_CONV``: the two
projections, the gates, the depthwise taps), forward, backward and
recomputation, over device busy time in the traced groups, in
percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_GATED_SHORT_CONV")
