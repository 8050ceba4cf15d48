"""``xing_mla_time_share.train``: device self time of the ops of the
latent-attention layers (``OP_LATENT_ATTENTION``, here 4 heads held
under a YaRN-rescaled rotary embedding: the projections, the latents'
norms, the rotation, the flash kernels), forward, backward and
recomputation, over device busy time in the traced groups, in percent.
``mla_time_share.train``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_LATENT_ATTENTION")
