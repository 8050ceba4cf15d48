"""``gqa_flash_fwd_roofline``: the least time the chip could take for
the traced calls of the kernel named ``flash_attention_fwd`` that the
grouped-query attention layers issue, K and V already repeated to the
query heads (``flops/flash_attention.py`` over the table of peaks, the
unmasked pairs), over the device time they took, in percent."""
from benchmarks.harness import kind_reduce


def read(ctx):
    return kind_reduce.kernel_roofline_of(
        ctx, "flash_attention_fwd", kind_reduce.is_grouped_query_attention)
