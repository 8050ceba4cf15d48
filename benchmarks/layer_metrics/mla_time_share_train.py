"""``mla_time_share.train``: device self time of the ops of the
latent-attention layers (``OP_LATENT_ATTENTION``: the low-rank
projections, the latents' norms, the rotary embedding, the flash
kernels), forward, backward and recomputation, over device busy time in
the traced groups, in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_LATENT_ATTENTION")
