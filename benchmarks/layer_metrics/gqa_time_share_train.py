"""``gqa_time_share.train``: device self time of the ops of the
grouped-query attention layers (``OP_MULTIHEAD_ATTENTION`` with fewer
key/value heads than query heads: the projections, the q/k norms, the
rotary embedding, the repeat of K and V, the flash kernels), forward,
backward and recomputation, over device busy time in the traced groups,
in percent."""
from benchmarks.harness import kind_reduce, scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, kind_reduce.is_grouped_query_attention)
