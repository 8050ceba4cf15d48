"""``xing_mla_flash_bwd_dkv_roofline``: the least time the chip could take
for the traced calls of the kernel named ``flash_attention_bwd_dkv`` that
the latent-attention layers issue (4 heads here), q.k over one head size
and p.v over another, causal wherever the layer stands in the scope
(``flops/mla_attention.py`` over the table of peaks), over the device
time they took, in percent. ``mla_flash_bwd_dkv_roofline``'s reading, for a
cell that metric's ``workloads`` list does not hold."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.unequal_heads_roofline(ctx, "flash_attention_bwd_dkv")
