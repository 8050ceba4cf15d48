"""``moe_dropped_assignments``: the program's counter ``moe.dropped``
over the run: assignments of a token to an expert held here that the
grouped products did not reach. The layer is dropless; it must read
0."""


def read(ctx):
    return ctx.counters.get("moe.dropped")
