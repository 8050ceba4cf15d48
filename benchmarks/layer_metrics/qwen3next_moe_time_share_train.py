"""``qwen3next_moe_time_share.train``: device self time of the ops of
the routed-experts layers whose shared expert is gated
(``OP_ROUTED_EXPERTS`` with ``shared_gate``: the softmax router over 512,
the sort, gathers, the grouped products over 32 held experts, the shared
expert and its scalar gate), forward, backward and recomputation, over
device busy time in the traced groups, in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_ROUTED_EXPERTS"
        and bool((getattr(l, "params", None) or {}).get("shared_gate")))
