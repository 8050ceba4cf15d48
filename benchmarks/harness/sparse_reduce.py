"""Readers for attention layers that carry a learned sparse-attention
indexer (``OP_MULTIHEAD_ATTENTION`` with ``indexer_heads`` in its
parameters), beside the other reducers (which stay as they are): device
time by the name scopes such a layer opens (``dsa.index``: the indexer's
projections and scores; ``dsa.select``: the threshold search and the
mask; ``dsa.loss``: the alignment loss; ``dsa.attend``: the masked
attention), and quotients of the program's ``dsa.*`` counters.

A program that names no such layer, scope or counter (the parent of the
PR that brought this file) makes every function here return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import name_reduce, scope_reduce

INDEXER_SCOPES = ("dsa.index", "dsa.select", "dsa.loss")


def is_sparse_attention(layer) -> bool:
    params = getattr(layer, "params", None) or {}
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and bool(params.get("indexer_heads"))


def share_of_scopes(ctx, scopes):
    """Percent of device busy time in the sparse-attention layers' ops
    under any of ``scopes``; ``None`` where none of them is found."""
    parts = [name_reduce.share_of_scope(ctx, is_sparse_attention, s)
             for s in scopes]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None


def share_outside_scopes(ctx, scopes):
    """Percent of device busy time in the sparse-attention layers' ops
    under none of ``scopes``."""
    whole = scope_reduce.share_of_layers(ctx, is_sparse_attention)
    inside = share_of_scopes(ctx, scopes)
    if whole is None or inside is None:
        return None
    return whole - inside


def counter_quotient(ctx, over: str, under: str):
    num, den = ctx.counters.get(over), ctx.counters.get(under)
    if num is None or not den:
        return None
    return num / den
