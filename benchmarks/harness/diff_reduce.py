"""Readers for decoders whose attention layers are differential
(``OP_MULTIHEAD_ATTENTION`` with ``differential`` in its parameters: two
flash calls a layer at q.k over ``d`` and p.v over ``2 d``, grouped, in a
band where the layer has a window), beside the other reducers (which stay
as they are): the flash kernels' roofline with every traced call counted
by ITS layer's mask and both head sizes
(``flops/phi4_mini_flash_reasoning.py``), and the layers a model of this
family is made of, by kind and by the builder's names.

A program that names no such layer, or a trace with no such call (the
parent of the PR that brought this file), makes every function here
return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import cells, scope_reduce, span_reduce

FLOPS = "phi4_mini_flash_reasoning"
# the builder's names (``build_hybrid_conv_moe``)
GATED_MEMORY = ("gmu_in_", "gmu_sigmoid_", "gmu_silu_", "gmu_gate_",
                "gmu_out_")
FEED_FORWARD = ("gate_proj_", "up_proj_", "down_proj_", "silu_")
BETWEEN = ("OP_SIGMOID", "OP_EW_MUL")


def is_differential(layer) -> bool:
    params = getattr(layer, "params", None) or {}
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and bool(params.get("differential"))


def is_selective_scan(layer) -> bool:
    return scope_reduce.op_kind(layer) == "OP_SELECTIVE_SCAN_MIXER"


def has_selective_scan(ctx) -> bool:
    return any(is_selective_scan(l) for l in ctx.model.layers)


def is_gated_memory(layer) -> bool:
    return layer.name.startswith(GATED_MEMORY)


def is_feed_forward(layer) -> bool:
    """A dense SwiGLU's three products and what stands between them (the
    sigmoid and the second multiply carry no name of the builder's)."""
    return not is_gated_memory(layer) and (
        layer.name.startswith(FEED_FORWARD)
        or scope_reduce.op_kind(layer) in BETWEEN)


def kernel_roofline(ctx, kernel: str):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` that the differential layers issue, each counted by its
    own layer's window over both head sizes, over the device time they
    took. ``None`` where there is no such call, or where a call of
    another kind of layer shares the kernel's summed time."""
    layers = {l.name: l for l in ctx.model.layers if is_differential(l)}
    r = span_reduce.reduced(ctx) if layers else None
    if not r or ctx.peak is None or not r["kernel_calls"].get(kernel):
        return None
    cost = cells.load_module(ctx.cell.bench_dir, "flops", FLOPS)
    if cost is None:
        return None
    least_s, calls = 0.0, []
    for name, n_events in r["kernel_calls"][kernel].items():
        entry = ctx.span_instructions[name]
        layer = scope_reduce.layer_of(entry["op_name"], layers)
        if not layer:
            continue
        if len(entry["operands"]) < 4:      # the text gave no shapes
            return None
        calls.append(name)
        least_s += n_events * cost.flash_roofline_s(
            kernel, entry["operands"], entry["results"],
            int(layers[layer].params.get("sliding_window", 0) or 0),
            ctx.peak)[0]
    if not calls or set(calls) != set(r["kernel_calls"][kernel]):
        return None
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
