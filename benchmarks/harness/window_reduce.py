"""Readers for decoders that mix window and full causal attention
layers (``OP_MULTIHEAD_ATTENTION`` with or without ``sliding_window`` in
its parameters), beside ``kind_reduce`` and the other reducers (which
stay as they are): the flash kernels' roofline with every traced call
counted by ITS layer's mask, and the layers of either kind.

``kind_reduce.kernel_roofline_of`` counts a causal call over the
triangle; a window layer's call computes the band alone, so counted
that way it could read up to 2.3 times too high. Here a call's least
time is ``flops/window_attention.py``'s: the band's pairs where the
call's layer has a window under the sequence, the triangle's where it
has none, summed over the kernel's calls and divided by the kernel's
device time.

A program that names no causal attention layer, or a trace with no such
call (the parent of the PR that brought this file), makes every
function here return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import cells, scope_reduce, span_reduce


def is_causal_attention(layer) -> bool:
    params = getattr(layer, "params", None) or {}
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and bool(params.get("causal", False))


def is_window_attention(layer) -> bool:
    params = getattr(layer, "params", None) or {}
    return is_causal_attention(layer) \
        and bool(params.get("sliding_window", 0))


def is_full_attention(layer) -> bool:
    return is_causal_attention(layer) and not is_window_attention(layer)


def kernel_roofline(ctx, kernel: str):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` that the causal attention layers issue, each counted
    by its own layer's mask, over the device time they took. ``None``
    where there is no such call, or where a call of another kind of
    layer shares the kernel's summed time."""
    layers = {l.name: l for l in ctx.model.layers if is_causal_attention(l)}
    r = span_reduce.reduced(ctx) if layers else None
    if not r or ctx.peak is None or not r["kernel_calls"].get(kernel):
        return None
    cost = cells.load_module(ctx.cell.bench_dir, "flops", "window_attention")
    if cost is None:
        return None
    least_s, calls = 0.0, []
    for name, n_events in r["kernel_calls"][kernel].items():
        entry = ctx.span_instructions[name]
        layer = scope_reduce.layer_of(entry["op_name"], layers)
        if not layer:
            continue
        if len(entry["operands"]) < 3:      # the text gave no shapes
            return None
        calls.append(name)
        least_s += n_events * cost.roofline_s(
            kernel, entry["operands"], entry["results"],
            int(layers[layer].params.get("sliding_window", 0) or 0),
            ctx.peak)[0]
    if not calls or set(calls) != set(r["kernel_calls"][kernel]):
        return None
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
