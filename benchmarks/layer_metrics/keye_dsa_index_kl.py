"""``keye_dsa_index_kl``: the indexer's alignment loss ``L_I`` (the
divergence of the indexer's distribution over a query's selected keys
from the attention heads' mean), the mean over the run's layer-steps:
the program's counter ``dsa.index_kl`` over ``dsa.layers``. Training
lowers it."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.counter_quotient(ctx, "dsa.index_kl", "dsa.layers")
