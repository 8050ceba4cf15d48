"""``sdar_moe_time_share.train``: device self time of the ops of the
routed-experts layers of a model trained by block diffusion
(``OP_ROUTED_EXPERTS``: the softmax router, sort, gathers and the
grouped products over all 2 L positions), forward, backward and
recomputation, over device busy time in the traced groups, in percent.
Nothing where the model has no noising op."""
from benchmarks.harness import bd_reduce, scope_reduce


def read(ctx):
    if not bd_reduce.has_noise(ctx):
        return None
    return scope_reduce.share_of_layers(ctx, bd_reduce.is_routed_experts)
