"""``sdar_moe_dropped_assignments``: the program's counter
``moe.dropped`` over the run: assignments of a position to an expert
held here that the grouped products did not reach. The layer is
dropless; it must read 0. ``moe_dropped_assignments``'s reading, for a
cell that metric's ``workloads`` list does not hold."""


def read(ctx):
    return ctx.counters.get("moe.dropped")
