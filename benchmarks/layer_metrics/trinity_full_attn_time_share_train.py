"""``trinity_full_attn_time_share.train``: device self time of the ops
of the causal attention layers that have NO window (the full layers: no
rotary embedding, the flash kernels over the whole causal triangle),
forward, backward and recomputation, over device busy time in the
traced groups, in percent. With ``trinity_swa_time_share.train`` it
adds up to the attention layers' whole share."""
from benchmarks.harness import scope_reduce, window_reduce


def read(ctx):
    return scope_reduce.share_of_layers(ctx, window_reduce.is_full_attention)
