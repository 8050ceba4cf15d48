"""``nemotron_moe_latent_time_share.train``: of the routed-experts
layers' ops (``OP_ROUTED_EXPERTS`` whose parameters give a ``latent``),
those the layer runs under its name scope ``moe.latent``: the projection
of the stream down to the latent, the dispatch's row gather, the grouped
products of the held ReLU-squared experts over rows as wide as the
latent, the way back to the tokens (``kernels/moe_token_sum.py``) and the
projection up to the stream again; not the router, the sort and the
shared expert, which read the stream (``moe.route``, ``moe.shared``).
Forward, backward and recomputation, over device busy time in the traced
groups, in percent: the backwards of the dispatch are ``custom_vjp``
functions and come out under the scope their forward call was made in
(held in ``tests/test_nemotron_h.py`` and, for the kernel calls of the
step compiled for a v5e, ``tests/test_tpu_aot_compile.py``). Not here: a
weight's gradient product that XLA sinks into that weight's Adam update
is one fusion under the update's name, in this layer as in every other.

XLA:TPU turns ``jax.lax.ragged_dot`` into Mosaic calls of its own with
no ``op_name`` (``scope_reduce.UNNAMED_PREFIXES``), which
``scope_reduce.op_self_ns`` gives the layer of the op before them. In a
routed-experts layer the only grouped products are the routed experts',
so such a call of a latent layer counts here.

A program whose expert layers have no latent (every older cell, and the
parent of the PR that brought this file), or open no such scope, reads
nothing."""
from benchmarks.harness import name_reduce, scope_reduce, span_reduce

SCOPE = "moe.latent"


def read(ctx):
    names = {l.name for l in ctx.model.layers
             if scope_reduce.op_kind(l) == "OP_ROUTED_EXPERTS"
             and (getattr(l, "params", None) or {}).get("latent")}
    table = name_reduce.by_op(ctx) if names else None
    r = span_reduce.reduced(ctx)
    if not table or not r or not r["busy_ns"]:
        return None

    def scoped(name):
        return SCOPE in ctx.span_instructions.get(name, {}).get(
            "op_name", "").split("/")

    mine = {key: ns for key, ns in table.items() if key[0] in names}
    if not any(scoped(name) for _, name in mine):
        return None
    return 100.0 * sum(
        ns for (_, name), ns in mine.items()
        if scoped(name) or name.startswith(scope_reduce.UNNAMED_PREFIXES)
    ) / r["busy_ns"]
