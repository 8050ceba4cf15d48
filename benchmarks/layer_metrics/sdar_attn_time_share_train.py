"""``sdar_attn_time_share.train``: device self time of the ops of the
attention layers under the block-diffusion mask (the projections, q/k
norm and rotary embedding, the three flash kernels over the 2 L
positions, the output projection), forward, backward and recomputation,
over device busy time in the traced groups, in percent."""
from benchmarks.harness import bd_reduce, scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, bd_reduce.is_block_diffusion_attention)
