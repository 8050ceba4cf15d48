"""``phi4flash_diff_attn_time_share.train``: device self time of the ops
of the differential attention layers (``OP_MULTIHEAD_ATTENTION`` with
``differential``: the projections, the two flash calls a layer at 64 /
128 and their backward kernels, lambda, the pair norm, the output
projection; window, whole and cross layers alike), forward, backward
and recomputation, over device busy time in the traced groups, in
percent."""
from benchmarks.harness import diff_reduce, scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx, diff_reduce.is_differential)
