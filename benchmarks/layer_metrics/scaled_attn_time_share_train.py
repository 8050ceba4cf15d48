"""``scaled_attn_time_share.train``: device self time of the ops of the
attention layers that carry a softmax scale of the model's own
(``OP_MULTIHEAD_ATTENTION`` with ``sm_scale``: the projections, the
three flash kernels reading the K/V heads in place, the output
projection; no rotary embedding and no q/k norm to run), forward,
backward and recomputation, over device busy time in the traced groups,
in percent."""
from benchmarks.harness import scope_reduce


def read(ctx):
    return scope_reduce.share_of_layers(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_MULTIHEAD_ATTENTION"
        and l.params.get("sm_scale") is not None)
