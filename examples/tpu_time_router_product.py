"""The routers' float32 products and what follows them under ``moe.route``
timed ALONE on the chip (run on a real TPU), at the shapes of the
benchmark's nine cells with routed experts.

``--part products``: ``logits = x wg``, ``dx = dlogits wg^T`` and ``dwg =
x^T dlogits`` of ``ops/moe_ops.py::RoutedExpertsOp``'s router, in three
forms:

  ``highest``  XLA's own: ``jnp.dot(.., precision=HIGHEST)`` and its
               transposes, what the op runs;
  ``dots``     the three bf16 pieces of each float32 operand made by XLA
               and six plain bf16 ``jnp.dot``s;
  ``kernel``   the same six passes in a Pallas kernel (below; not in the
               package): tiles in VMEM, the float32 tile of ``x`` read
               once and split there, ``wg`` and ``dlogits`` split by XLA.

PR 67 weighed the last two against the first and took neither: XLA's
product runs at 183 to 189 TFLOP/s of bf16 passes alone and inside the
step (0.56 ms at 4,096 x 4,096 x 512), and so does the kernel. Each form
runs ``--calls`` (sixteen) calls on ``x`` of their own in ONE jit, five
ways: the forward alone, each cotangent alone, the three together behind
an RMSNorm of ``x`` inside the same jit (the forward, then both
cotangents from a given ``dlogits``; the norm's own passes are part of
that reading on every form), and the same with ``route()``'s scores,
choice and gates after the product and a bf16 product beside it that
reads the same normed ``x``, as a layer's step has them. One JSON line a
timing with ms a call and the TFLOP/s of bf16 passes (six passes of ``2 t
e n`` a product), then the largest difference of each form from
``highest``. ``--tiles`` times the kernel at other tiles than 512 a side
(``use:tm:tn:tk``).

``--part choice``: the pieces of ``route()`` and of the sort after it,
each alone: the top-k, the chosen experts' own scores as a gather
(``jnp.take_along_axis``) and as the op has them since PR 67
(``moe_ops.own_scores``: a compare and a sum), each with its transpose,
the held groups' sizes as ``jnp.bincount`` and as ``moe_ops.group_sizes``,
and the two sorts.

Times are the device's own clock (a profiler trace of the jit: all
device ops' durations, and the heaviest by op name).

    python3 examples/tpu_time_router_product.py --cell 13 10
"""
import argparse
import functools
import json
import os
import sys
import tempfile
import time

#: cell -> (configuration, tokens, hidden, published experts, top_k,
#: experts held)
SHAPES = {
    "3": ("joyai_llm_flash", 4096, 2048, 256, 8, 16),
    "4": ("lfm2_24b_a2b", 8192, 2048, 64, 4, 8),
    "5": ("kimi_linear_48b_a3b", 4096, 2304, 256, 8, 8),
    "6": ("xing4_29b_a4b", 4096, 3584, 64, 4, 8),
    "7": ("keye_vl2_30b_a3b", 8192, 2048, 128, 8, 16),
    "8": ("trinity_mini", 8192, 2048, 128, 8, 16),
    "10": ("qwen3_next_80b_a3b", 8192, 2048, 512, 10, 32),
    "12": ("sdar_30b_a3b", 8192, 2048, 128, 8, 16),
    "13": ("nemotron3_super_120b_a12b", 4096, 4096, 512, 22, 8),
}
EPS = 1e-6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(SHAPES, key=int), nargs="*",
                    default=sorted(SHAPES, key=int))
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--part", choices=("products", "choice"), nargs="*",
                    default=["products", "choice"])
    ap.add_argument("--forms", nargs="*", default=["highest", "dots",
                                                   "kernel"])
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="use:tm:tn:tk (use: logits, dx or dwg) to time "
                         "besides tiles of 512 a side")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a tiny shape in interpret mode: a rehearsal")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness import trace_reduce
    from flexflow_tpu.ops import moe_ops

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = [SHAPES[c] for c in args.cell] if on_chip else [
        ("rehearsal", 256, 256, 128, 4, 8)]
    calls = args.calls if on_chip else 2
    f32, bf16 = jnp.float32, jnp.bfloat16
    highest = jax.lax.Precision.HIGHEST

    # --- the six passes made by the program ------------------------------
    def split(a):
        """The three bf16 pieces of a float32 array, largest first,
        summing to it exactly: each is the next eight significant bits,
        cut by a mask on the float32's bits. (``a - float32(bf16(a))``
        would do on paper; XLA's TPU compiler, allowed excess precision,
        drops such a round trip and leaves ``a - a``: the pieces after
        the first come out zero, the product is a bf16 one, 2e-3 off,
        and nothing says so.)"""
        def cut(v):
            bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
            return jax.lax.bitcast_convert_type(
                bits & jnp.uint32(0xFFFF0000), f32)
        hi = cut(a)
        mid = cut(a - hi)
        return hi.astype(bf16), mid.astype(bf16), (a - hi - mid).astype(bf16)

    def six(a, b):
        """The six piece products, the small terms first."""
        dot = functools.partial(jnp.dot, preferred_element_type=f32)
        (ah, am, al), (bh, bm, bl) = a, b
        return (dot(al, bh) + dot(am, bm) + dot(ah, bl) + dot(am, bh)
                + dot(ah, bm) + dot(ah, bh))

    def six_dots(a, b):
        return six(split(a), split(b))

    def pieces(a):
        return jnp.stack(split(a))

    def product(a, b, tile, interpret):
        """``A B`` in float32 by the six passes as one Pallas call:
        ``a`` (M, K) float32, split a tile at a time in VMEM, or its
        pieces (3, M, K) bf16; ``b`` (K, N) likewise; the grid ``(M /
        tm, N / tn, K / tk)``, the contraction last, the float32 result
        tile resident across it."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        (m, k), n = a.shape[-2:], b.shape[-1]
        tm, tn, tk = tile

        def kernel(a_ref, b_ref, out_ref):
            def operand(ref):
                if len(ref.shape) == 3:
                    return ref[0], ref[1], ref[2]
                return split(ref[...])

            @pl.when(pl.program_id(2) == 0)
            def _():
                out_ref[...] = jnp.zeros(out_ref.shape, f32)
            out_ref[...] += six(operand(a_ref), operand(b_ref))

        def spec(x, rows, cols, index):
            if x.ndim == 3:
                return pl.BlockSpec(
                    (3, rows, cols), lambda i, j, kk: (0,) + index(i, j, kk))
            return pl.BlockSpec((rows, cols), index)
        return pl.pallas_call(
            kernel, grid=(m // tm, n // tn, k // tk),
            in_specs=[spec(a, tm, tk, lambda i, j, kk: (i, kk)),
                      spec(b, tk, tn, lambda i, j, kk: (kk, j))],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), f32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=12 * m * n * k, transcendentals=0,
                bytes_accessed=(a.size * a.dtype.itemsize * (n // tn)
                                + b.size * b.dtype.itemsize * (m // tm)
                                + m * n * 4)),
            interpret=interpret, name="router_product")(a, b)

    def kernel_tiles(t, e, n):
        """512 a side where it divides (the fastest of the tiles timed,
        under Mosaic's default 16 MiB of scoped VMEM), the experts' axis
        whole; None where the shapes do not tile."""
        def edge(size):
            return next((v for v in (512, 384, 256, 128) if size % v == 0),
                        0)
        te, tt = edge(e), edge(t)
        if n % 128 or n > 512 or not (te and tt):
            return None
        return {"logits": (tt, n, te), "dx": (tt, te, n), "dwg": (n, te, tt)}
    extra = {}
    for s in args.tiles:
        use, *tile = s.split(":")
        extra.setdefault(use, []).append(tuple(int(v) for v in tile))

    def timed(fn, operands, n):
        """(first call's results, host ms, device ms, device ms by op
        name a call) of ONE run of the jit of ``n`` calls."""
        first = jax.block_until_ready(fn(*operands))[0]     # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        host = (time.perf_counter() - t0) * 1e3
        if not on_chip:
            return first, host, None, {}
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                jax.block_until_ready(fn(*operands))
            finally:
                jax.profiler.stop_trace()
            ev = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        by_name = {}
        for ops in ev["devices"].values():
            for name, _, dur in ops:
                name = trace_reduce.op_name(name).rsplit(".", 1)[0]
                by_name[name] = by_name.get(name, 0.0) + dur / 1e6
        top = dict(sorted(by_name.items(), key=lambda kv: -kv[1])[:6])
        return first, host, sum(by_name.values()), {
            k: round(v / n, 4) for k, v in top.items()}

    # --- the three forms: (logits(x, wg), dx(dl, wg), dwg(x, dl)) -------
    def kernel_form(tiles):
        interpret = not on_chip
        return (
            lambda x, wg: product(x, pieces(wg), tiles["logits"],
                                  interpret),
            lambda dl, wg: product(pieces(dl), pieces(wg.T), tiles["dx"],
                                   interpret),
            lambda x, dl: product(pieces(dl.T), x, tiles["dwg"],
                                  interpret).T)

    def forms(t, e, n):
        out = {}
        if "highest" in args.forms:
            out["highest"] = (
                lambda x, wg: jnp.dot(x, wg, precision=highest),
                lambda dl, wg: jnp.dot(dl, wg.T, precision=highest),
                lambda x, dl: jnp.dot(x.T, dl, precision=highest))
        if "dots" in args.forms:
            out["dots"] = (six_dots,
                           lambda dl, wg: six_dots(dl, wg.T),
                           lambda x, dl: six_dots(x.T, dl))
        derived = kernel_tiles(t, e, n)
        if "kernel" in args.forms and derived:
            out["kernel"] = kernel_form(derived) + (derived,)
            for use, tiles in extra.items():
                for tile in tiles:
                    out["kernel@%s:%s" % (use, ":".join(map(str, tile)))] \
                        = kernel_form({**derived, use: tile}) \
                        + ({use: tile},)
        return out

    # --- the choice: what follows the product under ``moe.route`` -------
    def choice(cell, t, n, k, held):
        """The pieces of ``route()`` and of the sort after it, each
        alone (sixteen calls on logits of their own in one jit): the
        top-k, the chosen experts' own scores as a gather
        (``jnp.take_along_axis``) and as the op has them (one compare
        with the experts' numbers and a sum), each with its transpose,
        the held groups' sizes as ``jnp.bincount`` (a scatter-add) and
        as a compare and a count, and the two sorts."""
        keys = jax.random.split(jax.random.PRNGKey(68), calls)
        ls = [jax.jit(lambda key: jax.random.normal(key, (t, n), f32))(key)
              for key in keys]
        idxs = [jax.lax.top_k(v, k)[1] for v in ls]
        groups = [jnp.where(i.reshape(-1) < held, i.reshape(-1), held)
                  for i in idxs]
        cts = [v[:, :k] for v in ls]

        def with_transpose(fn):
            def both(v, i, ct):
                out, pull = jax.vjp(lambda v: fn(v, i), v)
                return out, pull(ct)[0]
            return both
        gather = lambda v, i: jnp.take_along_axis(v, i, axis=-1)
        pieces = (
            ("top_k", lambda v, i, g, ct: jax.lax.top_k(v, k)),
            ("chosen.gather", lambda v, i, g, ct: gather(v, i)),
            ("chosen.compare", lambda v, i, g, ct: moe_ops.own_scores(v, i)),
            ("chosen.gather+transpose", lambda v, i, g, ct:
             with_transpose(gather)(v, i, ct)),
            ("chosen.compare+transpose", lambda v, i, g, ct:
             with_transpose(moe_ops.own_scores)(v, i, ct)),
            ("sizes.bincount", lambda v, i, g, ct:
             jnp.bincount(g, length=held + 1)[:held]),
            ("sizes.compare", lambda v, i, g, ct:
             moe_ops.group_sizes(g, held)),
            ("two_sorts", lambda v, i, g, ct: jnp.argsort(jnp.argsort(
                g, stable=True).astype(jnp.int32))))
        for what, fn in pieces:
            def all_calls(ls, idxs, groups, cts, _fn=fn):
                return [(_fn(*a),) for a in zip(ls, idxs, groups, cts)]
            _, host, device, by_name = timed(
                jax.jit(all_calls), (ls, idxs, groups, cts), calls)
            print(json.dumps(dict(
                cell=cell, part="choice", what=what, tokens=t, experts=n,
                top_k=k, held=held, calls=calls, device=dev.device_kind,
                host_ms_a_call=host / calls,
                device_ms_a_call=device / calls if device else None,
                by_name_ms_a_call=by_name)), flush=True)

    for cell, t, e, n, k, held in shapes:
        if "choice" in args.part:
            choice(cell, t, n, k, held)
        if "products" not in args.part:
            continue
        keys = jax.random.split(jax.random.PRNGKey(67), calls + 3)
        xs = [jax.jit(lambda key: jax.random.normal(key, (t, e), f32))(key)
              for key in keys[3:]]
        wg = jax.random.normal(keys[0], (e, n), f32) * e ** -0.5
        dl = jax.random.normal(keys[1], (t, n), f32)
        # a cotangent of its own a call of ``dx``, or XLA runs one
        dls = [dl + i for i in range(calls)]
        gamma = 1 + 0.1 * jax.random.normal(keys[2], (e,), f32)
        bias = 0.02 * jax.random.normal(keys[2], (n,), f32)
        v = (jax.random.normal(keys[1], (e, 128), f32)
             * e ** -0.5).astype(jnp.bfloat16)
        got = {}
        for form, (logits, dx, dwg, *tiles) in forms(t, e, n).items():
            only = form.split("@")[1].split(":")[0] if "@" in form else None

            def behind_vjp():
                @jax.custom_vjp
                def f(x, wg):
                    return logits(x, wg)
                f.defvjp(lambda x, wg: (logits(x, wg), (x, wg)),
                         lambda kept, g: (dx(g, kept[1]), dwg(kept[0], g)))
                return f

            def together(wg, dl, gamma, xs, dls):
                """Norm, forward and both cotangents, as a step's
                layer: the product behind a ``custom_vjp`` of this
                form."""
                f = behind_vjp()

                def layer(x, gamma, wg):
                    normed = x * jax.lax.rsqrt(jnp.mean(
                        x * x, -1, keepdims=True) + EPS) * gamma
                    return f(normed, wg)
                out = []
                for x in xs:
                    y, pull = jax.vjp(layer, x, gamma, wg)
                    out.append((y,) + pull(dl))
                return out

            def as_routed(wg, dl, gamma, xs, dls):
                """As ``together``, and what a layer has about them:
                the scores, the choice and the gates after the product
                (``route()``: ``dlogits`` is their backward's), and a
                bf16 product that reads the same normed ``x`` (the
                shared expert's stand-in)."""
                f = behind_vjp()

                def loss(x, gamma, wg):
                    normed = x * jax.lax.rsqrt(jnp.mean(
                        x * x, -1, keepdims=True) + EPS) * gamma
                    _, gates = moe_ops.route(f(normed, wg), bias, 8, 2.5)
                    y = jnp.dot(normed.astype(jnp.bfloat16), v,
                                preferred_element_type=f32)
                    return jnp.sum(gates * y[:, :8])
                return [jax.grad(loss, argnums=(0, 1, 2))(x, gamma, wg)
                        for x in xs]
            whats = (
                ("logits", lambda wg, dl, gamma, xs, dls:
                 [(logits(x, wg),) for x in xs], 1),
                ("dx", lambda wg, dl, gamma, xs, dls:
                 [(dx(d, wg),) for d in dls], 1),
                ("dwg", lambda wg, dl, gamma, xs, dls:
                 [(dwg(x, dl),) for x in xs], 1),
                ("norm+three", together, 3),
                ("norm+route+three", as_routed, 3))
            for what, fn, products in whats:
                if only and what != only:
                    continue
                try:
                    first, host, device, by_name = timed(
                        jax.jit(fn), (wg, dl, gamma, xs, dls), calls)
                except Exception as ex:     # a tile Mosaic refuses
                    print(json.dumps(dict(cell=cell, form=form, what=what,
                                          refused=str(ex)[-300:])),
                          flush=True)
                    continue
                ms = device / calls if device is not None else None
                print(json.dumps(dict(
                    cell=cell, form=form, what=what, tokens=t, hidden=e,
                    experts=n, calls=calls, device=dev.device_kind,
                    tiles=tiles[0] if tiles else None,
                    host_ms_a_call=host / calls, device_ms_a_call=ms,
                    peak_ms_a_call=products * 12 * t * e * n / 197e12 * 1e3,
                    tflops_of_bf16_passes=(
                        products * 12 * t * e * n / ms / 1e9
                        if ms else None),
                    by_name_ms_a_call=by_name)), flush=True)
                if not only:
                    got.setdefault(what, {})[form] = first
        for what, by_form in got.items():
            if "highest" not in by_form:
                continue
            ref = [np.asarray(v, np.float64) for v in by_form["highest"]]
            for form, vals in by_form.items():
                if form == "highest":
                    continue
                print(json.dumps(dict(
                    cell=cell, what=what, form=form,
                    against_highest_relative=[
                        float(np.max(np.abs(np.asarray(v, np.float64) - r))
                              / max(np.max(np.abs(r)), 1e-30))
                        for v, r in zip(vals, ref)])), flush=True)
        del xs, dls, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
