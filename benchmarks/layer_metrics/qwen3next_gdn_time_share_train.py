"""``qwen3next_gdn_time_share.train``: device self time of the ops of the
gated delta-rule layers with a decay a HEAD (``OP_GATED_DELTA_RULE``
with ``decay = "head"``: the six projections, the three convolutions,
the unit norms, the decay and step size, the chunked scan, the gated
norm, the output projection), forward, backward and the layer's own
recomputation, over device busy time in the traced groups, in percent.
Nothing where the model has no such layer (``kda_time_share.train``
reads the layers with a decay a channel)."""
from benchmarks.harness import scope_reduce


def is_head_decay(layer) -> bool:
    return scope_reduce.op_kind(layer) == "OP_GATED_DELTA_RULE" \
        and (getattr(layer, "params", None) or {}).get("decay") == "head"


def read(ctx):
    return scope_reduce.share_of_layers(ctx, is_head_decay)
