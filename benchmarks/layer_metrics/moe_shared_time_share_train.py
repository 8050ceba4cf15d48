"""``moe_shared_time_share.train``: of the routed-experts layers' ops
(``OP_ROUTED_EXPERTS``), those the layer runs under its name scope
``moe.shared``: the shared expert's products over every token at the
stream's width, its activation and, where it has one, its gate; not the
router (``moe.route``) and not the routed experts. Forward, backward and
recomputation, over device busy time in the traced groups, in percent.
Not here: a weight's gradient product that XLA sinks into that weight's
Adam update is one fusion under the update's name, as in every layer.
Nothing where the model has no such layer or its layers open no such
scope."""
from benchmarks.harness import name_reduce, scope_reduce


def read(ctx):
    return name_reduce.share_of_scope(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_ROUTED_EXPERTS",
        "moe.shared")
