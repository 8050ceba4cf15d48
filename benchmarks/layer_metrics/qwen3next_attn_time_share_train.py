"""``qwen3next_attn_time_share.train``: device self time of the ops of
the attention layers that turn PART of each head
(``OP_MULTIHEAD_ATTENTION`` with ``rotary_dim``: the four projections,
the zero-centred q/k norms and the partial rotary embedding on the plain
chain, the three flash kernels at head size 256 reading the 2 K/V heads
in place, the sigmoid gate, the output projection), forward, backward
and recomputation, over device busy time in the traced groups, in
percent."""
from benchmarks.harness import scope_reduce


def turns_part_of_a_head(layer) -> bool:
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and (getattr(layer, "params", None) or {}).get("rotary_dim") \
        is not None


def read(ctx):
    return scope_reduce.share_of_layers(ctx, turns_part_of_a_head)
