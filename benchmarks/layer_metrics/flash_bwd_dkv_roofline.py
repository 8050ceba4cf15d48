"""``flash_bwd_dkv_roofline``: the least time the chip could take for the
traced calls of the kernel named ``flash_attention_bwd_dkv``
(``flops/flash_attention.py`` over the table of peaks) over the device
time they took, in percent."""
from benchmarks.harness import span_reduce


def read(ctx):
    return span_reduce.kernel_roofline(ctx, "flash_attention_bwd_dkv")
