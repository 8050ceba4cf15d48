"""``sdar_flash_fwd_roofline``: the least time the chip could take
for the traced calls of the kernel named ``flash_attention_fwd`` that
the attention layers under the block-diffusion mask issue (128 / 128 on
32-on-4 heads over 2 L positions), each call counted by the mask's LIVE
pairs, L L + L B of the 4 L L (``flops/sdar_30b_a3b.py`` over the
table of peaks), over the device time they took, in percent. Whole tiles
computed for the few pairs of a diagonal block read here as what they
cost."""
from benchmarks.harness import bd_reduce


def read(ctx):
    return bd_reduce.kernel_roofline(ctx, "flash_attention_fwd")
