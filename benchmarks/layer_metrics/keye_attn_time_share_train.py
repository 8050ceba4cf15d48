"""``keye_attn_time_share.train``: device self time of the
sparse-attention layers' ops OUTSIDE the indexer's scopes (the
projections, the q/k norms, the rotary embedding, the masked attention
over the selected keys, the output projection), forward, backward and
recomputation, over device busy time in the traced groups, in percent.
With ``keye_dsa_time_share.train`` it adds up to the layers' whole
share."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.share_outside_scopes(
        ctx, sparse_reduce.INDEXER_SCOPES)
