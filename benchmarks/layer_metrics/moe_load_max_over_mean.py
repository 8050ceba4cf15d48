"""``moe_load_max_over_mean``: the program's counters ``moe.load_max``
over ``moe.load_mean``: tokens at the busiest expert held here over the
mean of the held experts, each summed over the expert layers and the
steps of the run. 1 is a perfect balance."""


def read(ctx):
    mean = ctx.counters.get("moe.load_mean")
    if not mean or "moe.load_max" not in ctx.counters:
        return None
    return ctx.counters["moe.load_max"] / mean
