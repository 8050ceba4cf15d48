"""Readers that pick a model's layers by their KIND (operator type and
parameters) and find them in a device op's ``op_name`` at any depth of
scope, beside ``span_reduce`` and ``scope_reduce`` (which stay as they
are): the flash kernels' roofline for the calls that grouped-query
attention layers issue.

``span_reduce.kernel_roofline`` asks ``span_reduce.layer_of`` whose call
a kernel's was, and under ``jax.checkpoint`` that reads JAX's own scope:
the call would be counted over the full square and a kernel that skips
the masked half could read up to twice too high. Here the layer is found
with ``scope_reduce.layer_of`` wherever in the path it stands, so a
cell that rematerialises its attention layers counts them as causal too.

A program that names no layer of the kind asked for (the parent of the
PR that brought this file) makes every function here return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import cells, scope_reduce, span_reduce


def is_grouped_query_attention(layer) -> bool:
    """An attention layer whose key/value heads are fewer than its
    query heads."""
    params = getattr(layer, "params", None) or {}
    kv = params.get("num_kv_heads", 0)
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and bool(kv) and kv != params.get("num_heads")


def kernel_roofline_of(ctx, kernel: str, wanted):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` issued by the layers ``wanted(layer)`` picks
    (``flops/flash_attention.py`` over the table of peaks, each call
    causal or not as its layer says) over the device time they took.
    ``None`` where there is no such call, or where another layer's calls
    share the kernel's summed time."""
    layers = {l.name: l for l in ctx.model.layers if wanted(l)}
    r = span_reduce.reduced(ctx) if layers else None
    if not r or ctx.peak is None or not r["kernel_calls"].get(kernel):
        return None
    cost = cells.load_module(ctx.cell.bench_dir, "flops", "flash_attention")
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
            bool(layers[layer].params.get("causal", False)), ctx.peak)[0]
    if not calls or set(calls) != set(r["kernel_calls"][kernel]):
        return None
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
