"""``ssm_time_share.train``: device self time of the ops of the
state-space mixers (``OP_STATE_SPACE_MIXER``: the fused input
projection, the convolution, the step sizes and log-decays, the chunked
scan, the skip, the gated norm, the output projection), forward,
backward and the layer's own recomputation, over device busy time in the
traced groups, in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_STATE_SPACE_MIXER")
