"""``swa_kept_share``: the (query, key) pairs the window layers
attend over the causal pairs there are, from the program's counters
``attn.window_pairs`` and ``attn.causal_pairs`` summed over the window
layers and steps: 0.4375 at 8192 positions with a window of 2048, 0.1211
with one of 512, 1.0 where the window is at least the sequence. What
the kernels' grids visit of it is the ``flash.grid`` instants'
``live_steps`` (whole tiles that touch the band: more than the band)."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.counter_quotient(ctx, "attn.window_pairs",
                                          "attn.causal_pairs")
