"""``phi4flash_mlp_time_share.train``: device self time of the dense
SwiGLU feed-forwards of a model that has a selective-scan mixer (the
builder's ``gate_proj_<i>``, ``up_proj_<i>``, ``down_proj_<i>`` and the
sigmoid and the two multiplies between them: plain matrix products at
2560 x 10240, the step's compute-bound part; not the gated memory
unit's), forward, backward and recomputation, over device busy time in
the traced groups, in percent. Nothing where the model has no such
mixer."""
from benchmarks.harness import diff_reduce, scope_reduce


def read(ctx):
    if not diff_reduce.has_selective_scan(ctx):
        return None
    return scope_reduce.share_of_layers(ctx, diff_reduce.is_feed_forward)
