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
are one) and yields ``u`` and the finished ``[Hpost ; Hres]`` a token;
``stage: "post"`` takes ``X``, ``F``'s output and those maps. Between
sub-layers only ``X`` is live, so a rematerialised block is entered by
the one stream tensor.

What decides the cost on the chip, and what is done about each:
  * an array whose last axis is ``n`` = 4 is padded to 128 lanes: the
    maps and the Sinkhorn state are held tokens-last, ``(n, n, b, s)``;
    only the 4 + 16 finished values a token cross to the ``post`` node
    token-major (2 MB padded at 4096 tokens, against 235 MB of ``X``);
  * the iterations are ONE ``lax.scan`` in the step's text a pass
    (forward, recomputation, backward), not ``iters`` unrolled copies;
  * ``Hres X + Hpost^T y`` is written as broadcasts and sums, one fused
    pass over ``X``; ``Hpre X`` is one more read. The ``pre`` node is
    rematerialised: the backward pass keeps ``X`` and not the norm's,
    the product's or the iterations' intermediates.

Name scopes inside the layer's own: ``mhc.maps`` (norm, product, affine,
gates), ``mhc.sinkhorn`` (the iterations), ``mhc.mix`` (the passes over
``X``). Counters: ``mhc.sublayers`` (one a ``pre`` node), ``mhc.sum_err``
(that node's largest ``|rowsum - 1|`` or ``|colsum - 1|`` over its
tokens; counters add, so divide by ``mhc.sublayers``), ``mhc.clamped``
(entries of ``Hres~`` at or beyond the clamp).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import WeightSpec
from ..ffconst import DataType, InitializerType, OperatorType
from ..obs import events
from .registry import OpDef, register

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


def stream_maps(x, w, params):
    """The three maps of every token of ``x`` (b, s, n, C) float32,
    tokens last: ``Hpre`` (n, b, s), ``Hpost`` (n, b, s), ``Hres``
    (n, n, b, s), and how many entries of ``Hres~`` met the clamp."""
    b, s, n, c = x.shape
    lo, hi = params["clamp"]
    with jax.named_scope("mhc.maps"):
        phi = w["phi"].astype(F32).reshape(n, c, -1)
        # x phi = (X phi) / rms(X): the norm has no weight of its own
        # (the product token-major and its 24 columns turned after: asked
        # for tokens-last, XLA turns the streams themselves, 235 MB)
        raw = jnp.einsum("bsnc,nck->bsk", x, phi,
                         precision=jax.lax.Precision.HIGHEST)
        t = jnp.moveaxis(raw, -1, 0) * jax.lax.rsqrt(
            jnp.mean(x * x, axis=(-2, -1)) + params["norm_eps"])
        a = w["alpha"].astype(F32)

        def affine(scalar, rows, bias):
            bias = bias.astype(F32)
            return scalar * rows.reshape(bias.shape + (b, s)) \
                + bias[..., None, None]

        pre = jax.nn.sigmoid(affine(a[0], t[:n], w["b_pre"]))
        post = 2.0 * jax.nn.sigmoid(affine(a[1], t[n:2 * n], w["b_post"]))
        res = affine(a[2], t[2 * n:], w["b_res"])
        clamped = jnp.sum(((res <= lo) | (res >= hi)).astype(F32))
    with jax.named_scope("mhc.sinkhorn"):
        res = sinkhorn(jnp.clip(res, lo, hi), params["iters"],
                       params["eps"])
    return pre, post, res, clamped


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


@register
class HyperConnectionOp(OpDef):
    """One of the two nodes of a hyper-connected sub-layer (the module's
    docstring). ``stage: "pre"``: input ``X`` (b, s, n, C); outputs
    ``u`` (b, s, C) and the maps (b, s, n + n n), ``[Hpost ; Hres]``
    row-major; weights ``phi``, ``b_pre``, ``b_post``, ``b_res`` and
    ``alpha`` = ``(a_pre, a_post, a_res)``. ``stage: "post"``: inputs
    ``X``, ``F``'s output (b, s, C) and the maps; output the new ``X``;
    no weights. Per token: batch and sequence may be sharded, the stream
    axis never, the channels only with the norm's and ``phi``'s partial
    sums reduced, which is not built. Training and evaluation only."""
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
                ((b, s, n + n * n), DataType.DT_FLOAT)]

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
        if params["stage"] == "post":
            _, y, maps = inputs
            maps = maps.astype(F32)
            with jax.named_scope("mhc.mix"):
                out = write_streams(
                    x.astype(F32), y.astype(F32), maps[..., :n],
                    maps[..., n:].reshape(b, s, n, n))
            return [out.astype(x.dtype)]
        if events.enabled():
            events.instant("mhc.maps", layer=name, streams=n, channels=c,
                           iters=params["iters"], tokens=b * s,
                           stream_bytes=4 * b * s * n * c)

        # rematerialised whole: the backward pass keeps X, and computes
        # the norm, the product and the iterations again
        @jax.checkpoint
        def pre(x, w):
            hpre, hpost, hres, clamped = stream_maps(x, w, params)
            with jax.named_scope("mhc.mix"):
                u = read_streams(x, jnp.moveaxis(hpre, 0, -1))
            err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(hres, 0) - 1.0)),
                              jnp.max(jnp.abs(jnp.sum(hres, 1) - 1.0)))
            maps = jnp.concatenate([hpost, hres.reshape(n * n, b, s)], 0)
            return u, jnp.moveaxis(maps, 0, -1), err, clamped

        u, maps, err, clamped = pre(x.astype(F32), weights)
        ctx.count("mhc.sublayers", jnp.float32(1.0))
        ctx.count("mhc.sum_err", err)
        ctx.count("mhc.clamped", clamped)
        return [u.astype(x.dtype), maps]

    def flops(self, params, in_shapes, out_shapes):
        b, s, n, c = in_shapes[0]
        if params["stage"] == "post":
            return float(b * s) * (2.0 * n * n * c + 2.0 * n * c)
        return float(b * s) * (2.0 * n * c * n * (n + 2) + 5.0 * n * c
                               + params["iters"] * 4.0 * n * n)

    def bytes_moved(self, params, in_shapes, out_shapes):
        """A memory-bound op that reads ``X`` more than once: ``pre`` for
        the norm, for the product and for ``Hpre X``."""
        reads = 1 if params["stage"] == "post" else 3
        return 4.0 * (reads * float(np.prod(in_shapes[0]))
                      + sum(float(np.prod(sh)) for sh in in_shapes[1:])
                      + sum(float(np.prod(sh)) for sh in out_shapes))

    def backward_flops_factor(self):
        return 2.0
