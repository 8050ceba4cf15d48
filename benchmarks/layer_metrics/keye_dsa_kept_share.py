"""``keye_dsa_kept_share``: the (query, key) pairs the sparse-attention
layers attended over the causal pairs there are, from the program's
counters ``dsa.kept_pairs`` and ``dsa.causal_pairs`` summed over layers
and steps: 0.4375 at 8192 positions with 2048 keys a query, 1.0 if the
selection is skipped."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.counter_quotient(ctx, "dsa.kept_pairs",
                                          "dsa.causal_pairs")
