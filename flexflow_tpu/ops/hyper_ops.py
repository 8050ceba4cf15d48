"""A residual of several streams: manifold-constrained hyper-connections
(mHC, arXiv:2512.24880, on hyper-connections, arXiv:2409.19606).

A token's residual is ``X``, ``n`` streams of ``C`` channels. Around a
sub-layer ``F`` (an attention or a feed-forward), with ``x`` the ``n C``
entries of ``X`` as one vector under an RMSNorm with no learned weight:

    Hpre~  = a_pre  (x phi_pre)       + b_pre          (n)
    Hpost~ = a_post (x phi_post)      + b_post         (n)
    Hres~  = a_res  mat(x phi_res)    + b_res          (n x n, row-major)
    Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    Hres = SinkhornKnopp(clip(Hres~, lo, hi))
    u  = Hpre X                        what F reads (C)
    X <- Hres X + Hpost^T F(u)         what F's output is written into

``SinkhornKnopp``: ``M = exp(.)``, then ``iters`` times ``M <- M /
(colsum(M) + eps)``, ``M <- M / (rowsum(M) + eps)``: ``Hres`` is doubly
stochastic to the iteration's accuracy, so a product of them keeps the
streams' mean. Everything here is float32, whatever the compute dtype.

One operator kind, two nodes a sub-layer: ``stage: "pre"`` holds the
weights (``phi`` as ONE ``n C x n (n + 2)`` matrix: the three products
are one) and yields ``u``, the finished ``[Hpost ; Hres]`` a token and
``X`` again; ``stage: "post"`` takes that ``X``, ``F``'s output and
those maps. Between
sub-layers only ``X`` is live, so a rematerialised block is entered by
the one stream tensor.

What decides the cost on the chip, and what is done about each:
  * an array whose last axis is ``n`` = 4 is padded to 128 lanes: the
    maps and the Sinkhorn state are held tokens-last, ``(n, n, b, s)``;
    only the 4 + 16 finished values a token cross to the ``post`` node
    token-major (2 MB padded at 4096 tokens, against 235 MB of ``X``);
  * the iterations are ONE ``lax.scan`` in the step's text a pass
    (forward, recomputation, backward), not ``iters`` unrolled copies;
  * the passes over ``X``. Written as broadcasts and sums (the plain
    functions here: every shape's path before PR 42, now that of the
    shapes the kernels do not take and the tests' oracle), XLA does NOT
    make ``Hres X + Hpost^T y`` one pass nor ``Hpre X`` one read: at
    4 x 3584 a sub-layer's forward and backward moved the stream tensor
    31.6 times (9.06 ms at 819 GB/s), the product with ``phi`` at
    ``HIGHEST`` and its transpose as vector-unit fusions of 3.2-3.5
    passes each, every output stream's sum a fusion of its own in the
    backward, the tensor turned between two tiled layouts seven times a
    pair of sub-layers (PERF.md section 6, PR 40). Where the channels
    are whole lanes (:func:`flexflow_tpu.kernels.hyper_connection.
    takes_kernel`) four Pallas kernels do them instead, on the streams
    seen stream-major, ``(n, b s, C)`` (a view XLA makes free by laying
    the four-axis array so): a tile of tokens' whole ``n C`` entries in
    VMEM, ``pre`` one read of ``X`` forward (norm, product, ``Hpre X``)
    and two reads and one write backward (the WHOLE ``dX``: ``post``
    takes its streams from the ``pre`` node's third output, which is its
    input, so what ``post`` left for ``X`` is an operand of that kernel
    and not an add of two stream tensors after it), ``post`` one read
    and one write forward, two reads and one write backward. The
    residuals are ``X`` and 25 floats a token (the raw products and the
    norm's reciprocal); the gates of ``Hpost`` and ``Hres`` and the
    Sinkhorn scan stay in XLA, tokens-last, rematerialised (they are
    1% of the step and their intermediates 2.5 KB a token).

Name scopes inside the layer's own: ``mhc.maps`` (norm, product, affine,
gates), ``mhc.sinkhorn`` (the iterations), ``mhc.mix`` (the passes over
``X``: the kernels' calls, or the plain functions). Instants at trace
time: ``mhc.maps`` a ``pre`` node (``impl`` = ``kernel`` | ``plain``),
``mhc.kernel`` a kernel call. Counters: ``mhc.sublayers`` (one a ``pre``
node), ``mhc.sum_err`` (that node's largest ``|rowsum - 1|`` or
``|colsum - 1|`` over its tokens; counters add, so divide by
``mhc.sublayers``), ``mhc.clamped`` (entries of ``Hres~`` at or beyond
the clamp).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import WeightSpec
from ..ffconst import DataType, InitializerType, OperatorType
from ..kernels import hyper_connection as hck
from ..obs import events
from .registry import OpDef, checkpointed, register, wrap_specs

F32 = jnp.float32
#: the draw of a sub-layer's maps, a trained model's being in no config
#: (``assumed.maps_draw`` of the configuration that has such streams):
#: the three scalars, the biases' spread, what ``b_res``'s diagonal gains
MAPS_DRAW = {"alpha": 1.0, "bias_std": 0.5, "res_diagonal": 2.0}


def sinkhorn(logits, iters: int, eps: float):
    """``logits``: (n, n, ...) float32, rows first. Columns are
    normalised first and rows last (the paper's ``T_r(T_c(M))``)."""
    def step(m, _):
        m = m / (jnp.sum(m, 0, keepdims=True) + eps)
        return m / (jnp.sum(m, 1, keepdims=True) + eps), None

    return jax.lax.scan(step, jnp.exp(logits), None, length=iters)[0]


def stream_products(x, phi, norm_eps):
    """``x phi`` under the norm, tokens last: ``x`` (b, s, n, C) float32,
    ``phi`` (n C, K) -> (K, b, s). The plain path's: two reads of ``x``."""
    n, c = x.shape[-2:]
    phi = phi.astype(F32).reshape(n, c, -1)
    # x phi = (X phi) / rms(X): the norm has no weight of its own
    # (the product token-major and its 24 columns turned after: asked
    # for tokens-last, XLA turns the streams themselves, 235 MB)
    raw = jnp.einsum("bsnc,nck->bsk", x, phi,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(raw, -1, 0) * jax.lax.rsqrt(
        jnp.mean(x * x, axis=(-2, -1)) + norm_eps)


def _affine(scalar, rows, bias):
    bias = bias.astype(F32)
    return scalar * rows.reshape(bias.shape + rows.shape[1:]) \
        + bias[..., None, None]


def write_maps(t, w, params):
    """``Hpost`` (n, b, s) and ``Hres`` (n, n, b, s) from the products
    ``t`` (K, b, s) tokens-last, and how many entries of ``Hres~`` met
    the clamp."""
    n = w["b_post"].shape[0]
    lo, hi = params["clamp"]
    a = w["alpha"].astype(F32)
    with jax.named_scope("mhc.maps"):
        post = 2.0 * jax.nn.sigmoid(_affine(a[1], t[n:2 * n], w["b_post"]))
        res = _affine(a[2], t[2 * n:], w["b_res"])
        clamped = jnp.sum(((res <= lo) | (res >= hi)).astype(F32))
    with jax.named_scope("mhc.sinkhorn"):
        res = sinkhorn(jnp.clip(res, lo, hi), params["iters"],
                       params["eps"])
    return post, res, clamped


def stream_maps(x, w, params):
    """The three maps of every token of ``x`` (b, s, n, C) float32,
    tokens last: ``Hpre`` (n, b, s), ``Hpost`` (n, b, s), ``Hres``
    (n, n, b, s), and how many entries of ``Hres~`` met the clamp."""
    n = x.shape[2]
    with jax.named_scope("mhc.maps"):
        t = stream_products(x, w["phi"], params["norm_eps"])
        pre = jax.nn.sigmoid(_affine(w["alpha"].astype(F32)[0], t[:n],
                                     w["b_pre"]))
    return (pre,) + write_maps(t, w, params)


def read_streams(x, hpre):
    """``Hpre X``: ``x`` (b, s, n, C), ``hpre`` (b, s, n) -> (b, s, C)."""
    return jnp.sum(hpre[..., None] * x, axis=-2)


def write_streams(x, y, hpost, hres):
    """``Hres X + Hpost^T y`` as one pass of broadcasts: ``y`` (b, s, C),
    ``hpost`` (b, s, n), ``hres`` (b, s, n, n) -> (b, s, n, C). No
    stream is sliced out of ``x``: a slice's transpose is a pad, and the
    backward pass would write ``n`` padded copies of the streams and add
    them."""
    return hpost[..., None] * y[..., None, :] \
        + jnp.sum(hres[..., None] * x[..., None, :, :], axis=-2)


def _kernel_shard_spec(ctx, batch: int, seq: int):
    """``(mesh, spec)`` for the kernels inside the executor's
    multi-device jit: the batch and sequence entries of the node's
    adopted output sharding (the only axes it may be sharded by), where
    they divide the axis. ``(None, None)``: call them directly, as
    ``MultiHeadAttentionOp._kernel_shard_spec`` says (its rule, over
    other axes; ``nn_ops.py`` is left as it is so that the other
    configurations' steps keep their compile-cache keys)."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None or mesh.size == 1 or getattr(ctx, "local_shape", False):
        return None, None
    from jax.sharding import PartitionSpec as P
    sh = getattr(ctx, "op_sharding", None)
    out = tuple(sh.outputs[0]) if sh is not None and sh.outputs else ()

    def entry(i, dim):
        e = out[i] if len(out) > i else None
        axes = e if isinstance(e, tuple) else (e,)
        deg = int(np.prod([mesh.shape[a] for a in axes if a is not None]))
        return e if dim % deg == 0 else None

    return mesh, P(entry(0, batch), entry(1, seq))


@register
class HyperConnectionOp(OpDef):
    """One of the two nodes of a hyper-connected sub-layer (the module's
    docstring). ``stage: "pre"``: input ``X`` (b, s, n, C); outputs
    ``u`` (b, s, C), the maps (b, s, n + n n), ``[Hpost ; Hres]``
    row-major, and ``X`` itself, for the ``post`` node to take (so that
    the one consumer of a sub-layer's incoming streams is this node,
    and their whole cotangent is written once, by its backward);
    weights ``phi``, ``b_pre``, ``b_post``, ``b_res`` and ``alpha`` =
    ``(a_pre, a_post, a_res)``. ``stage: "post"``: inputs ``X``, ``F``'s
    output (b, s, C) and the maps; output the new ``X``; no weights. Per
    token: batch and sequence may be sharded, the stream axis never, the
    channels only with the norm's and ``phi``'s partial sums reduced,
    which is not built. Training and evaluation only."""
    op_type = OperatorType.OP_HYPER_CONNECTION

    def infer(self, params, in_shapes, in_dtypes):
        b, s, n, c = in_shapes[0]
        if params["stage"] == "post":
            if tuple(in_shapes[1]) != (b, s, c) \
                    or tuple(in_shapes[2]) != (b, s, n + n * n):
                raise ValueError(
                    f"streams {in_shapes[0]} take an output of "
                    f"{(b, s, c)} and maps of {(b, s, n + n * n)}, not "
                    f"{in_shapes[1]} and {in_shapes[2]}")
            return [(tuple(in_shapes[0]), in_dtypes[0])]
        return [((b, s, c), in_dtypes[0]),
                ((b, s, n + n * n), DataType.DT_FLOAT),
                (tuple(in_shapes[0]), in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        if params["stage"] == "post":
            return []
        n, c = in_shapes[0][-2:]
        dt = in_dtypes[0]
        normal = InitializerType.NORMAL
        spread = {"mean": 0.0, "stddev": MAPS_DRAW["bias_std"]}
        return [
            WeightSpec("phi", (n * c, n * (n + 2)), dt),
            WeightSpec("b_pre", (n,), dt, normal, spread),
            WeightSpec("b_post", (n,), dt, normal, spread),
            # towards the identity: a stream mostly keeps itself
            WeightSpec("b_res", (n, n), dt, normal,
                       dict(spread, diagonal=MAPS_DRAW["res_diagonal"])),
            WeightSpec("alpha", (3,), dt, InitializerType.CONSTANT,
                       {"value": MAPS_DRAW["alpha"]})]

    def emit(self, params, inputs, weights, ctx, name):
        if getattr(ctx, "kv_mode", None) is not None:
            raise NotImplementedError(
                f"{name}: the residual streams have no decode path")
        x = inputs[0]
        b, s, n, c = x.shape
        kernel = hck.takes_kernel(n, c, b * s)
        # a compiled kernel inside a multi-device jit runs on each
        # device's tokens, as the attention kernels on their heads
        mesh, spec = _kernel_shard_spec(ctx, b, s) if kernel else (None, None)
        if params["stage"] == "post":
            _, y, maps = inputs
            xf, y, maps = x.astype(F32), y.astype(F32), maps.astype(F32)
            with jax.named_scope("mhc.mix"):
                if kernel:
                    out = hck.write_streams(xf, y, maps, layer=name,
                                            mesh=mesh, spec=spec)
                else:
                    out = write_streams(xf, y, maps[..., :n],
                                        maps[..., n:].reshape(b, s, n, n))
            return [out.astype(x.dtype)]
        if events.enabled():
            events.instant("mhc.maps", layer=name, streams=n, channels=c,
                           iters=params["iters"], tokens=b * s,
                           stream_bytes=4 * b * s * n * c,
                           impl="kernel" if kernel else "plain")

        def finish(hpost, hres, clamped):
            err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(hres, 0) - 1.0)),
                              jnp.max(jnp.abs(jnp.sum(hres, 1) - 1.0)))
            maps = jnp.concatenate([hpost, hres.reshape(n * n, b, s)], 0)
            return jnp.moveaxis(maps, 0, -1), err, clamped

        # rematerialised: the backward pass keeps X (and, of the
        # kernels, 25 floats a token), and computes the gates and the
        # iterations (the plain path: the norm and the product too) again
        xf = x.astype(F32)
        (x_spec, w_specs), wrap_mesh = wrap_specs(ctx)
        if kernel:
            with jax.named_scope("mhc.mix"):
                u, stats, xf = hck.read_streams(
                    xf, *hck.pre_operands(
                        weights["phi"], weights["alpha"][0],
                        weights["b_pre"]), params["norm_eps"], layer=name,
                    mesh=mesh, spec=spec)

            def maps_of(stats, w):
                k = n * (n + 2)
                t = jnp.moveaxis(stats[..., :k] * stats[..., k:k + 1], -1, 0)
                return finish(*write_maps(t, w, params))

            maps, err, clamped = checkpointed(
                maps_of, site="mhc.maps", layer=name, weights=(1,),
                specs=(spec, w_specs), mesh=mesh)(stats, weights)
        else:
            def plain(x, w):
                hpre, *rest = stream_maps(x, w, params)
                with jax.named_scope("mhc.mix"):
                    u = read_streams(x, jnp.moveaxis(hpre, 0, -1))
                return (u,) + finish(*rest)

            u, maps, err, clamped = checkpointed(
                plain, site="mhc.plain", layer=name, weights=(1,),
                specs=(x_spec, w_specs), mesh=wrap_mesh)(xf, weights)
        ctx.count("mhc.sublayers", jnp.float32(1.0))
        ctx.count("mhc.sum_err", err)
        ctx.count("mhc.clamped", clamped)
        return [u.astype(x.dtype), maps, xf.astype(x.dtype)]

    def flops(self, params, in_shapes, out_shapes):
        b, s, n, c = in_shapes[0]
        if params["stage"] == "post":
            return float(b * s) * (2.0 * n * n * c + 2.0 * n * c)
        return float(b * s) * (2.0 * n * c * n * (n + 2) + 5.0 * n * c
                               + params["iters"] * 4.0 * n * n)

    def bytes_moved(self, params, in_shapes, out_shapes):
        """A memory-bound op, by what the path taken reads: ``post`` the
        streams once; ``pre`` once where the kernel runs, three times
        otherwise (the norm, the product, ``Hpre X``). ``pre``'s third
        output is its input and moves nothing."""
        b, s, n, c = in_shapes[0]
        post = params["stage"] == "post"
        reads = 1 if post or hck.takes_kernel(n, c, b * s) else 3
        outs = out_shapes if post else out_shapes[:2]
        return 4.0 * (reads * float(np.prod(in_shapes[0]))
                      + sum(float(np.prod(sh)) for sh in in_shapes[1:])
                      + sum(float(np.prod(sh)) for sh in outs))

    def backward_flops_factor(self):
        return 2.0
