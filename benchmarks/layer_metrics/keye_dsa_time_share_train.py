"""``keye_dsa_time_share.train``: device self time of what the learned
sparse-attention indexer adds to an attention layer (the name scopes
``dsa.index``, the indexer's three projections and its scores;
``dsa.select``, the threshold search and the mask; ``dsa.loss``, the
alignment loss), forward, backward and recomputation, over device busy
time in the traced groups, in percent."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.share_of_scopes(ctx, sparse_reduce.INDEXER_SCOPES)
