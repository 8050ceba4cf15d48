"""``moe_route_time_share.train``: of the routed-experts layers' ops
(``OP_ROUTED_EXPERTS``), those the layer runs under its name scope
``moe.route``: the router's product at float32, the scores, the choice
of the experts with its bias, the chosen scores and the held groups' row
counts; not the dispatch and the grouped products (``moe.latent`` where
the layer has a latent, the layer's own scope where it has none) and not
the shared expert (``moe.shared``). Forward, backward and recomputation,
over device busy time in the traced groups, in percent. Nothing where
the model has no such layer or its layers open no such scope."""
from benchmarks.harness import name_reduce, scope_reduce


def read(ctx):
    return name_reduce.share_of_scope(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_ROUTED_EXPERTS",
        "moe.route")
