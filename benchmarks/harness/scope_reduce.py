"""Device time by the program's LAYERS, for layers that may sit inside a
rematerialised block, and the roofline of the flash kernels where q.k
and p.v have different head sizes. Beside ``span_reduce`` (which stays
as it is): it finds a layer as the scope right under the phase's, and
under ``jax.checkpoint`` that scope is JAX's own —

  ``.../transpose(jvp(ff.forward))/jvp(ff.forward)/checkpoint/
    rematted_computation/attn_2/...``     (the block's second forward)
  ``.../transpose(jvp(ff.forward))/jvp(ff.forward)/checkpoint/attn_2/...``

Here a device op's layer is the first part of its ``op_name`` that is
the name of one of the model's layers, wherever in the path it stands.

One kind of op has no ``op_name`` to go by: XLA:TPU turns
``jax.lax.ragged_dot`` into Mosaic calls of its own whose metadata is
the bare ``ragged-dot-none``. The device runs its ops in order, so such
an op is given the layer of the nearest op before it on its device that
has one: an expert layer's grouped products run between that layer's
gathers and its activation.

A program that names no layer of the kind asked for (the parent of the
PR that brought this file) makes every function here return ``None``.
"""
from __future__ import annotations

from benchmarks.harness import cells, span_reduce, trace_reduce

UNNAMED_PREFIXES = ("ragged-dot",)


def layer_of(op_name: str, layer_names) -> str:
    for part in op_name.split("/"):
        if part in layer_names:
            return part
    return ""


def op_self_ns(events: dict, instr: dict, layer_names) -> dict:
    """``(layer, instruction) -> self time (ns)`` of the device ops
    inside the marks, summed over the devices of the trace; the layer
    ``""`` holds what no layer claims. ``events`` as
    ``span_reduce.extract`` gives them."""
    marks = events["marks"]
    if not marks or not events["devices"]:
        return {}
    lo = marks[0][1]
    hi = max(s + d for _, s, d in marks)
    out: dict = {}
    for _, ops in sorted(events["devices"].items()):
        inside = sorted(([n, max(s, lo), min(s + d, hi) - max(s, lo)]
                         for n, s, d in ops if s < hi and s + d > lo),
                        key=lambda o: o[1])
        owner: dict = {}
        last = ""
        for name, _, _ in inside:
            if name in owner:
                continue
            layer = layer_of(instr.get(name, {}).get("op_name", ""),
                             layer_names)
            if not layer and name.startswith(UNNAMED_PREFIXES):
                layer = last
            owner[name] = layer
            last = layer or last
        for name, ns in trace_reduce.self_times(inside).items():
            key = (owner[name], name)
            out[key] = out.get(key, 0) + ns
    return out


def _fold_layers(by_op: dict) -> dict:
    out: dict = {}
    for (layer, _), ns in by_op.items():
        out[layer] = out.get(layer, 0) + ns
    return out


def layer_self_ns(events: dict, instr: dict, layer_names) -> dict:
    """Layer -> summed self time (ns) of its device ops."""
    return _fold_layers(op_self_ns(events, instr, layer_names))


def report(by_op: dict, model) -> list:
    """The reduction in words, for the traced run's earlier lines: the
    layers by device time, and the heaviest ops of each kind of layer
    (an instruction's name without its counter)."""
    lines = ["layers at any depth of scope, ms over the traced groups: "
             + ", ".join(f"{k or '(no layer)'} {v / 1e6:.3f}"
                         for k, v in sorted(_fold_layers(by_op).items(),
                                            key=lambda kv: -kv[1])[:16])]
    kinds = {l.name: op_kind(l) for l in model.layers}
    by_kind: dict = {}
    for (layer, name), ns in by_op.items():
        table = by_kind.setdefault(kinds.get(layer, "(no layer)"), {})
        op = name.rsplit(".", 1)[0]
        table[op] = table.get(op, 0) + ns
    for kind, table in sorted(by_kind.items(),
                              key=lambda kv: -sum(kv[1].values()))[:5]:
        lines.append(
            f"{kind} {sum(table.values()) / 1e6:.3f} ms, heaviest ops: "
            + ", ".join(f"{k} {v / 1e6:.3f}" for k, v in sorted(
                table.items(), key=lambda kv: -kv[1])[:8]))
    return lines


def by_layer(ctx):
    """``layer_self_ns`` of the traced run behind ``ctx``, made once and
    kept on it; ``None`` where the run left no trace."""
    if hasattr(ctx, "scope_layer_ns"):
        return ctx.scope_layer_ns
    ctx.scope_layer_ns = None
    if not span_reduce.reduced(ctx):
        return None
    names = {l.name for l in ctx.model.layers}
    by_op = op_self_ns(ctx.span_events, ctx.span_instructions, names)
    ctx.scope_layer_ns = _fold_layers(by_op) or None
    for line in report(by_op, ctx.model) if by_op else ():
        print(f"[bench] {line}", flush=True)
    return ctx.scope_layer_ns


def share_of_layers(ctx, wanted):
    """Percent of device busy time spent in the layers ``wanted(layer)``
    picks (a model layer object), forward, backward and recomputation
    alike; ``None`` where the model has no such layer or no trace."""
    names = {l.name for l in ctx.model.layers if wanted(l)}
    table = by_layer(ctx) if names else None
    r = span_reduce.reduced(ctx)
    if not table or not r or not r["busy_ns"]:
        return None
    return 100.0 * sum(ns for layer, ns in table.items()
                       if layer in names) / r["busy_ns"]


def op_kind(layer) -> str:
    """``OP_LATENT_ATTENTION`` for a layer of that operator type."""
    return getattr(layer.op_type, "name", str(layer.op_type))


def in_mtp_module(layer) -> bool:
    """The multi-token-prediction module's layers carry ``mtp`` in
    their names (``mtp_eh_proj``, ``attn_mtp``, ``experts_mtp``...)."""
    return "mtp" in layer.name.split("_")


def unequal_heads_roofline(ctx, kernel: str):
    """Percent: the least time the chip could take for the traced calls
    of ``kernel`` issued by latent-attention layers (always causal;
    ``flops/mla_attention.py`` over the table of peaks) over the device
    time they took. ``None`` where there is no such call."""
    r = span_reduce.reduced(ctx)
    if not r or ctx.peak is None or not r["kernel_calls"].get(kernel):
        return None
    latent = {l.name for l in ctx.model.layers
              if op_kind(l) == "OP_LATENT_ATTENTION"}
    cost = cells.load_module(ctx.cell.bench_dir, "flops", "mla_attention")
    least_s, calls = 0.0, []
    for name, n_events in r["kernel_calls"][kernel].items():
        entry = ctx.span_instructions[name]
        if layer_of(entry["op_name"], latent):
            if len(entry["operands"]) < 4:      # the text gave no shapes
                return None
            calls.append(name)
            least_s += n_events * cost.roofline_s(
                kernel, entry["operands"], entry["results"], True,
                ctx.peak)[0]
    if not calls:
        return None
    if set(calls) != set(r["kernel_calls"][kernel]):
        # other layers call the kernel too: their time cannot be told
        # from these calls' in the kernel's summed time
        return None
    return 100.0 * least_s / (r["kernel_ns"][kernel] / 1e9)
