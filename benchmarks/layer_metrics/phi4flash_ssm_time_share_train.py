"""``phi4flash_ssm_time_share.train``: device self time of the ops of the
selective-scan mixers (``OP_SELECTIVE_SCAN_MIXER``: the input projection,
the convolution, the low-rank step size, B and C, the token-by-token scan
in its rematerialised chunks, the skip, the gate, the output projection),
forward, backward and recomputation, over device busy time in the traced
groups, in percent."""
from benchmarks.harness import diff_reduce, scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx, diff_reduce.is_selective_scan)
