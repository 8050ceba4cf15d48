"""``phi4flash_flash_fwd_roofline``: the least time the chip could take
for the traced calls of the kernel named ``flash_attention_fwd`` that
the differential attention layers issue (q.k over 64, p.v over 128, 20
query pairs reading 10 key pairs in place), each counted by ITS layer's
mask, the band's pairs where the layer has a window and the causal
triangle's where it has none, with every operand and result once
(``flops/phi4_mini_flash_reasoning.py::flash_roofline_s`` over the table
of peaks), over the device time they took, in percent."""
from benchmarks.harness import diff_reduce


def read(ctx):
    return diff_reduce.kernel_roofline(ctx, "flash_attention_fwd")
