"""``window_flash_bwd_dkv_roofline``: the least time the chip could take
for the traced calls of the kernel named ``flash_attention_bwd_dkv`` that
the causal attention layers issue (K and V at their own heads where the
kernels read them in place), each call counted by ITS layer's mask (the
band's pairs where the layer has a window under the sequence, the causal
triangle's where it has none: ``flops/window_attention.py`` over the
table of peaks), over the device time they took, in percent."""
from benchmarks.harness import window_reduce


def read(ctx):
    return window_reduce.kernel_roofline(ctx, "flash_attention_bwd_dkv")
