"""``qwen3next_flash_bwd_dq_roofline``: the least time the chip could
take for the traced calls of the kernel named ``flash_attention_bwd_dq``
that the causal attention layers issue (head size 256, 16 query heads
reading 2 K/V heads in place), each counted over the causal triangle's
pairs with every operand and result once (``flops/window_attention.py``
at no window, over the table of peaks;
``flops/qwen3_next_80b_a3b.py::flash_call`` gives the same count from the
sizes), over the device time they took, in percent.
``trinity_flash_bwd_dq_roofline``'s reading, for a cell that metric's
``workloads`` list does not hold."""
from benchmarks.harness import window_reduce


def read(ctx):
    return window_reduce.kernel_roofline(ctx, "flash_attention_bwd_dq")
