"""``granite_mlp_time_share.train``: device self time of the dense
SwiGLU feed-forwards of a model that has a state-space mixer (the
builder's ``gate_proj_<i>``, ``up_proj_<i>``, ``down_proj_<i>`` and the
sigmoid and the two multiplies between them: plain matrix products at
2048 x 8192, the step's compute-bound part), forward, backward and
recomputation, over device busy time in the traced groups, in percent.
Nothing where the model has no such mixer: the other cells' dense layers
are one of five and are not this metric's."""
from benchmarks.harness import scope_reduce

PROJECTIONS = ("gate_proj_", "up_proj_", "down_proj_", "silu_")
BETWEEN = ("OP_SIGMOID", "OP_EW_MUL")


def read(ctx):
    layers = ctx.model.layers
    if not any(scope_reduce.op_kind(l) == "OP_STATE_SPACE_MIXER"
               for l in layers):
        return None
    return scope_reduce.share_of_layers(
        ctx, lambda l: l.name.startswith(PROJECTIONS)
        or scope_reduce.op_kind(l) in BETWEEN)
