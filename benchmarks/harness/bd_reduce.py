"""Readers for decoders trained by block diffusion (an
``OP_BLOCK_DIFFUSION_NOISE`` op, ``OP_MULTIHEAD_ATTENTION`` layers with
``block_diffusion_block`` in their parameters), beside the other
reducers (which stay as they are): the flash kernels' roofline with
every traced call counted by the mask's LIVE pairs
(``flops/sdar_30b_a3b.py``), the layers of each kind, and the share of
the step outside the decoder's layers (the noising op, the head, the
weighted loss).

``kind_reduce.kernel_roofline_of`` counts a call that is not causal over
the whole square; under this mask a quarter of it is attended, so counted
that way a call could read four times too high.

A program that names no such layer, or a trace with no such call (the
parent of the PR that brought this file), makes every function here
return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import cells, scope_reduce, span_reduce

FLOPS = "sdar_30b_a3b"


def is_block_diffusion_attention(layer) -> bool:
    params = getattr(layer, "params", None) or {}
    return scope_reduce.op_kind(layer) == "OP_MULTIHEAD_ATTENTION" \
        and bool(params.get("block_diffusion_block"))


def is_noise(layer) -> bool:
    return scope_reduce.op_kind(layer) == "OP_BLOCK_DIFFUSION_NOISE"


def has_noise(ctx) -> bool:
    return any(is_noise(l) for l in ctx.model.layers)


def is_routed_experts(layer) -> bool:
    return scope_reduce.op_kind(layer) == "OP_ROUTED_EXPERTS"


def outside_the_decoder(ctx) -> set:
    """The names of the layers before the embedding (the noising op and
    what rolls its weights) and after the last expert layer's residual
    add (what rolls the noised rows, the last norm, the head, the
    softmax)."""
    layers = list(ctx.model.layers)
    kinds = [scope_reduce.op_kind(l) for l in layers]
    if "OP_EMBEDDING" not in kinds or "OP_ROUTED_EXPERTS" not in kinds:
        return set()
    first = kinds.index("OP_EMBEDDING")
    last = len(kinds) - 1 - kinds[::-1].index("OP_ROUTED_EXPERTS")
    return {l.name for i, l in enumerate(layers)
            if i < first or i > last + 1}


def noise_and_loss_share(ctx):
    """Percent of device busy time in the noising op, the head with what
    stands around it, and the loss (the ops under ``ff.loss``, forward
    and backward: the weighted cross-entropy over the vocabulary
    slice)."""
    if not has_noise(ctx):
        return None
    names = outside_the_decoder(ctx)
    layers = scope_reduce.share_of_layers(ctx, lambda l: l.name in names)
    r = span_reduce.reduced(ctx)
    if layers is None or not r or not r["busy_ns"]:
        return None
    return layers + 100.0 * r["layer_ns"].get("ff.loss", 0) / r["busy_ns"]


def kernel_roofline(ctx, kernel: str):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` that the block-diffusion attention layers issue, each
    counted by the mask's live pairs, over the device time they took.
    ``None`` where there is no such call, or where a call of another
    kind of layer shares the kernel's summed time."""
    layers = {l.name: l for l in ctx.model.layers
              if is_block_diffusion_attention(l)}
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
            int(layers[layer].params["block_diffusion_block"]),
            ctx.peak)[0]
    if not calls or set(calls) != set(r["kernel_calls"][kernel]):
        return None
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
