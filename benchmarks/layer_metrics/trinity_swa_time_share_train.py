"""``trinity_swa_time_share.train``: device self time of the ops of the
attention layers that have a sliding window (``OP_MULTIHEAD_ATTENTION``
with ``sliding_window``: the projections with the gate's, the q/k norms
and the rotary embedding, the three flash kernels over the band, the
gate, the output projection), forward, backward and recomputation, over
device busy time in the traced groups, in percent."""
from benchmarks.harness import scope_reduce, window_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx,
                                        window_reduce.is_window_attention)
