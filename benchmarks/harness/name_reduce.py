"""Device time by a NAME SCOPE inside the program's layers, beside
``span_reduce``, ``scope_reduce`` and ``kind_reduce`` (which stay as they
are): the share of the ops that carry a given scope (a
``jax.named_scope`` the op opens around part of its work) among the ops
of the layers picked.

Such a scope may hold a loop. The device runs a ``while`` as one op
whose span covers its body's ops on the same line; ``scope_reduce.
op_self_ns`` takes self times, so the loop's own event counts only what
no op inside it covers, and each op of the body counts once, under the
layer and the scope its own ``op_name`` carries.

A program that names no layer of the kind asked for (the parent of the
PR that brought this file) makes every function here return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import scope_reduce, span_reduce


def by_op(ctx):
    """``scope_reduce.op_self_ns`` of the traced run behind ``ctx``:
    ``(layer, instruction) -> self time (ns)``, made once and kept on
    it; ``None`` where the run left no trace."""
    if hasattr(ctx, "name_by_op"):
        return ctx.name_by_op
    ctx.name_by_op = None
    if not span_reduce.reduced(ctx):
        return None
    names = {l.name for l in ctx.model.layers}
    ctx.name_by_op = scope_reduce.op_self_ns(
        ctx.span_events, ctx.span_instructions, names) or None
    return ctx.name_by_op


def share_of_scope(ctx, wanted, scope: str):
    """Percent of device busy time spent in the ops of the layers
    ``wanted(layer)`` picks whose ``op_name`` has ``scope`` as one of
    its parts, forward, backward and recomputation alike; ``None`` where
    the model has no such layer, no trace, or no op of those layers
    carries the scope."""
    names = {l.name for l in ctx.model.layers if wanted(l)}
    table = by_op(ctx) if names else None
    r = span_reduce.reduced(ctx)
    if not table or not r or not r["busy_ns"]:
        return None
    inside = [ns for (layer, name), ns in table.items()
              if layer in names and scope in ctx.span_instructions.get(
                  name, {}).get("op_name", "").split("/")]
    if not inside:
        return None
    return 100.0 * sum(inside) / r["busy_ns"]
