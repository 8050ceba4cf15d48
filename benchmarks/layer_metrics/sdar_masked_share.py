"""``sdar_masked_share``: the tokens the noising op replaced by the mask
id over the tokens it saw, from the program's counters
``diffusion.masked_tokens`` and ``diffusion.tokens`` summed over the
run: 0.5 in expectation (a block's ``t`` is uniform); 0 says the step
trained on its input as it came, and one value at every step that the
mask never changed."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.counter_quotient(ctx, "diffusion.masked_tokens",
                                          "diffusion.tokens")
