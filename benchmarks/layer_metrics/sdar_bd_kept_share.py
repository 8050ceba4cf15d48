"""``sdar_bd_kept_share``: the (query, key) pairs of the tiles the three
flash kernels' grids compute under the block-diffusion mask over the
pairs of the square, from the program's counters
``attn.bd_visited_pairs`` and ``attn.bd_pairs`` summed over the layers
and steps. The mask itself attends 0.25 + B / 4 L of the square (0.2502
at L 4,096, B 4); the reading says what whole tiles cost beside it, and
1.0 that the mask was drawn off the kernels over every pair."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.counter_quotient(ctx, "attn.bd_visited_pairs",
                                          "attn.bd_pairs")
