"""``phi4flash_gmu_time_share.train``: device self time of the gated
memory units (the builder's ``gmu_in_<i>``, ``gmu_sigmoid_<i>``,
``gmu_silu_<i>``, ``gmu_gate_<i>``, ``gmu_out_<i>``: two products of 2560
x 5120 and the gate of an earlier layer's scan output between them),
forward, backward and recomputation, over device busy time in the traced
groups, in percent."""
from benchmarks.harness import diff_reduce, scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx, diff_reduce.is_gated_memory)
