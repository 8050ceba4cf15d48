"""``mla_flash_fwd_roofline``: the least time the chip could take for
the traced calls of the kernel named ``flash_attention_fwd`` that the
latent-attention layers issue, q.k over one head size and p.v over
another (``flops/mla_attention.py`` over the table of peaks), over
the device time they took, in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.unequal_heads_roofline(ctx, "flash_attention_fwd")
