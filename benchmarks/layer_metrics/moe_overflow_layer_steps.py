"""``moe_overflow_layer_steps``: the program's counter
``moe.overflow`` over the run: how many times an expert layer's router
sent this share more rows than the layer's budget, so that the step ran
the sorted domain's body again over the further chunks (1 a layer a
step in which it happened). Whether a layer overflows is decided by the
seed's weights, and one that does loops in every step of its run: 0
where the cell's speed does not depend on the seed that way."""


def read(ctx):
    return ctx.counters.get("moe.overflow")
