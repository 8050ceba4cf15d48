"""Neural-net operators: dense, conv, pool, norms, attention, embedding, ...

Reference parity: ``src/ops/{linear,conv_2d,pool_2d,batch_norm,layer_norm,
softmax,dropout,embedding,attention,batch_matmul,flat}.cc`` — rebuilt as JAX
emission (XLA handles kernel selection/fusion; bf16 matmuls target the MXU).
Shape conventions follow the reference's Python API: images are NCHW,
sequences are (batch, seq, hidden).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ffconst import (ActiMode, AggrMode, DataType, InitializerType,
                       OperatorType, PoolType)
from ..core.tensor import WeightSpec
from ..dtypes import to_jnp
from ..obs import events
from .registry import (EmitCtx, OpDef, bf16_enabled, compute_dtype,
                       kept_by_block, matmul, register)


def apply_activation(x, acti: ActiMode):
    acti = ActiMode(acti)
    if acti == ActiMode.AC_MODE_NONE:
        return x
    if acti == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if acti == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if acti == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if acti == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x)
    raise ValueError(acti)


# ---------------------------------------------------------------------------
@register
class LinearOp(OpDef):
    """Dense / fully-connected (reference ``src/ops/linear.cc``).

    y = act(x @ kernel + bias); kernel (in_dim, out_dim). The reference's
    cuBLAS GEMM + activation epilogue becomes one bf16 MXU matmul that XLA
    fuses with the epilogue.
    """
    op_type = OperatorType.OP_LINEAR

    def infer(self, params, in_shapes, in_dtypes):
        (ish,) = in_shapes
        out_dim = params["out_dim"]
        out_dtype = params.get("dtype", in_dtypes[0])
        return [(tuple(ish[:-1]) + (out_dim,), out_dtype)]

    def weights(self, params, in_shapes, in_dtypes):
        in_dim = in_shapes[0][-1]
        out_dim = params["out_dim"]
        dt = params.get("dtype", in_dtypes[0])
        ws = [WeightSpec("kernel", (in_dim, out_dim), dt,
                         params.get("kernel_initializer",
                                    InitializerType.GLOROT_UNIFORM))]
        if params.get("use_bias", True):
            ws.append(WeightSpec("bias", (out_dim,), dt, InitializerType.ZERO))
        return ws

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        y = matmul(x, weights["kernel"], ctx=ctx)
        if "bias" in weights:
            y = y + weights["bias"]
        y = apply_activation(y, params.get("activation",
                                           ActiMode.AC_MODE_NONE))
        if "dtype" in params:
            y = y.astype(to_jnp(params["dtype"]))
        return [y]

    def flops(self, params, in_shapes, out_shapes):
        batch = int(np.prod(in_shapes[0][:-1]))
        return 2.0 * batch * in_shapes[0][-1] * params["out_dim"]

    def backward_flops_factor(self):
        return 2.0


# ---------------------------------------------------------------------------
@register
class Conv2DOp(OpDef):
    """2-D convolution, NCHW (reference ``src/ops/conv_2d.cc``)."""
    op_type = OperatorType.OP_CONV2D

    def infer(self, params, in_shapes, in_dtypes):
        n, c, h, w = in_shapes[0]
        kh, kw = params["kernel_h"], params["kernel_w"]
        sh, sw = params["stride_h"], params["stride_w"]
        ph, pw = params["padding_h"], params["padding_w"]
        oh = (h + 2 * ph - kh) // sh + 1
        ow = (w + 2 * pw - kw) // sw + 1
        return [((n, params["out_channels"], oh, ow), in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        c = in_shapes[0][1]
        groups = params.get("groups", 1)
        dt = in_dtypes[0]
        ws = [WeightSpec("kernel",
                         (params["out_channels"], c // groups,
                          params["kernel_h"], params["kernel_w"]), dt,
                         params.get("kernel_initializer",
                                    InitializerType.GLOROT_UNIFORM))]
        if params.get("use_bias", True):
            ws.append(WeightSpec("bias", (params["out_channels"],), dt,
                                 InitializerType.ZERO))
        return ws

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        k = weights["kernel"]
        cdt = x.dtype
        if cdt == jnp.float32 and bf16_enabled(ctx):
            x16, k16 = x.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
        else:
            x16, k16 = x, k
        # No preferred_element_type here: its conv VJP emits a transposed
        # conv with mismatched (f32 cotangent, bf16 kernel) dtypes. bf16
        # in/out is fine — the MXU accumulates in f32 internally.
        y = jax.lax.conv_general_dilated(
            x16, k16,
            window_strides=(params["stride_h"], params["stride_w"]),
            padding=[(params["padding_h"], params["padding_h"]),
                     (params["padding_w"], params["padding_w"])],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=params.get("groups", 1))
        y = y.astype(cdt)
        if "bias" in weights:
            y = y + weights["bias"][None, :, None, None]
        return [apply_activation(y, params.get("activation",
                                               ActiMode.AC_MODE_NONE))]

    def flops(self, params, in_shapes, out_shapes):
        n, co, oh, ow = out_shapes[0]
        ci = in_shapes[0][1] // params.get("groups", 1)
        return 2.0 * n * co * oh * ow * ci * params["kernel_h"] * params["kernel_w"]

    def backward_flops_factor(self):
        return 2.0


# ---------------------------------------------------------------------------
@register
class Pool2DOp(OpDef):
    """Max/avg pooling, NCHW (reference ``src/ops/pool_2d.cc``)."""
    op_type = OperatorType.OP_POOL2D

    def infer(self, params, in_shapes, in_dtypes):
        n, c, h, w = in_shapes[0]
        kh, kw = params["kernel_h"], params["kernel_w"]
        sh, sw = params["stride_h"], params["stride_w"]
        ph, pw = params["padding_h"], params["padding_w"]
        oh = (h + 2 * ph - kh) // sh + 1
        ow = (w + 2 * pw - kw) // sw + 1
        return [((n, c, oh, ow), in_dtypes[0])]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        kh, kw = params["kernel_h"], params["kernel_w"]
        sh, sw = params["stride_h"], params["stride_w"]
        ph, pw = params["padding_h"], params["padding_w"]
        dims = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
        if PoolType(params.get("pool_type", PoolType.POOL_MAX)) == PoolType.POOL_MAX:
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            y = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, pads)
        else:
            s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads)
            # count_include_pad=True matches cuDNN's default used by the reference
            y = s / float(kh * kw)
        return [apply_activation(y, params.get("activation",
                                               ActiMode.AC_MODE_NONE))]


# ---------------------------------------------------------------------------
@register
class FlatOp(OpDef):
    """NCHW → (N, C*H*W) (reference ``src/ops/flat.cc``)."""
    op_type = OperatorType.OP_FLAT

    def infer(self, params, in_shapes, in_dtypes):
        s = in_shapes[0]
        return [((s[0], int(np.prod(s[1:]))), in_dtypes[0])]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        return [x.reshape(x.shape[0], -1)]


# ---------------------------------------------------------------------------
@register
class SoftmaxOp(OpDef):
    op_type = OperatorType.OP_SOFTMAX

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        return [jax.nn.softmax(x, axis=params.get("axis", -1))]


# ---------------------------------------------------------------------------
@register
class DropoutOp(OpDef):
    op_type = OperatorType.OP_DROPOUT

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        rate = params.get("rate", 0.5)
        if not ctx.training or rate <= 0.0:
            return [x]
        rng = ctx.rng_for(name)
        if rng is None:
            raise RuntimeError(f"dropout layer {name} needs an rng")
        keep = 1.0 - rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, jnp.zeros_like(x))]


# ---------------------------------------------------------------------------
@register
class BatchNormOp(OpDef):
    """Batch norm over NCHW, with running stats in the state collection
    (reference ``src/ops/batch_norm.cc``; cuDNN BN → jnp + state threading)."""
    op_type = OperatorType.OP_BATCHNORM

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        c = in_shapes[0][1]
        dt = in_dtypes[0]
        return [WeightSpec("scale", (c,), dt, InitializerType.ONE),
                WeightSpec("bias", (c,), dt, InitializerType.ZERO)]

    def state_spec(self, params, in_shapes, in_dtypes):
        c = in_shapes[0][1]
        return {"mean": ((c,), DataType.DT_FLOAT),
                "var": ((c,), DataType.DT_FLOAT)}

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        eps = params.get("eps", 1e-5)
        momentum = params.get("momentum", 0.1)
        axes = (0, 2, 3) if x.ndim == 4 else (0,)
        bshape = (1, -1) + (1,) * (x.ndim - 2)
        st = ctx.state.get(name, {})
        if ctx.training or not st:
            mean = jnp.mean(x.astype(jnp.float32), axis=axes)
            var = jnp.var(x.astype(jnp.float32), axis=axes)
            if st:
                ctx.new_state[name] = {
                    "mean": (1 - momentum) * st["mean"] + momentum * mean,
                    "var": (1 - momentum) * st["var"] + momentum * var,
                }
        else:
            mean, var = st["mean"], st["var"]
        inv = jax.lax.rsqrt(var + eps) * weights["scale"].astype(jnp.float32)
        y = (x.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape) \
            + weights["bias"].astype(jnp.float32).reshape(bshape)
        y = y.astype(x.dtype)
        if params.get("relu", True):
            y = jax.nn.relu(y)
        return [y]


# ---------------------------------------------------------------------------
@register
class LayerNormOp(OpDef):
    """Layer norm (reference ``src/ops/layer_norm.cc`` — Welford kernels →
    jnp mean/var which XLA fuses into one pass)."""
    op_type = OperatorType.OP_LAYERNORM

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        if not params.get("elementwise_affine", True):
            return []
        axes = params.get("axes", [len(in_shapes[0]) - 1])
        shape = tuple(in_shapes[0][a] for a in axes)
        dt = in_dtypes[0]
        return [WeightSpec("scale", shape, dt, InitializerType.ONE),
                WeightSpec("bias", shape, dt, InitializerType.ZERO)]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        ndim = x.ndim
        axes = tuple(a % ndim for a in params.get("axes", [ndim - 1]))
        eps = params.get("eps", 1e-5)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        if "scale" in weights:
            bshape = [x.shape[a] if a in axes else 1 for a in range(ndim)]
            y = y * weights["scale"].astype(jnp.float32).reshape(bshape) \
                + weights["bias"].astype(jnp.float32).reshape(bshape)
        return [y.astype(x.dtype)]


# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    """RMSNorm of the last axis in float32 (float32 result)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


@register
class RMSNormOp(OpDef):
    """RMSNorm — TPU-native addition (used by T5/LLaMA-style models; the
    reference fuses T5LayerNorm patterns in its fx frontend).
    ``zero_centered`` in the parameters: ``x / rms(x) * (1 + scale)``
    with ``scale`` drawn at 0 (the Qwen3-Next family's norm)."""
    op_type = OperatorType.OP_RMSNORM

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        return [WeightSpec("scale", (in_shapes[0][-1],), in_dtypes[0],
                           InitializerType.ZERO
                           if params.get("zero_centered")
                           else InitializerType.ONE)]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        scale = weights["scale"]
        if params.get("zero_centered"):
            scale = 1.0 + scale
        y = _rms(x, scale, params.get("eps", 1e-6))
        return [y.astype(x.dtype)]


# ---------------------------------------------------------------------------
@register
class EmbeddingOp(OpDef):
    """Embedding lookup with none/sum/avg aggregation
    (reference ``src/ops/embedding.cc``: gather/scatter-add kernels →
    jnp.take, which XLA lowers to TPU gather)."""
    op_type = OperatorType.OP_EMBEDDING

    def infer(self, params, in_shapes, in_dtypes):
        ish = in_shapes[0]
        out_dim = params["out_dim"]
        dt = params.get("dtype", DataType.DT_FLOAT)
        aggr = AggrMode(params.get("aggr", AggrMode.AGGR_MODE_NONE))
        if aggr == AggrMode.AGGR_MODE_NONE:
            return [(tuple(ish) + (out_dim,), dt)]
        # sum/avg aggregate over the trailing (bag) dim
        return [(tuple(ish[:-1]) + (out_dim,), dt)]

    def weights(self, params, in_shapes, in_dtypes):
        dt = params.get("dtype", DataType.DT_FLOAT)
        return [WeightSpec("kernel", (params["num_entries"], params["out_dim"]),
                           dt, params.get("kernel_initializer",
                                          InitializerType.GLOROT_UNIFORM))]

    def emit(self, params, inputs, weights, ctx, name):
        (ids,) = inputs
        table = weights["kernel"]
        aggr = AggrMode(params.get("aggr", AggrMode.AGGR_MODE_NONE))
        out = jnp.take(table, ids.astype(jnp.int32), axis=0)
        if aggr == AggrMode.AGGR_MODE_SUM:
            out = jnp.sum(out, axis=-2)
        elif aggr == AggrMode.AGGR_MODE_AVG:
            out = jnp.mean(out, axis=-2)
        return [out]


# ---------------------------------------------------------------------------
def short_conv(z, taps):
    """Causal depthwise convolution along the sequence:
    ``c[:, t] = sum_j taps[:, j] * z[:, t - (K - 1) + j]`` with zeros left
    of position 0. ``z``: (B, L, C); ``taps``: (C, K). K shifted
    multiply-adds that XLA fuses into one pass over ``z``."""
    k, length = taps.shape[1], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(zp[:, j:j + length] * taps[:, j] for j in range(k))


@register
class GatedShortConvOp(OpDef):
    """The gated short convolution of hybrid convolution/attention
    decoders: the operator that mixes positions without attention and
    without a recurrence.

      [B ; C ; x] = u w_in          (E -> 3 x E)
      c = short_conv(B * x, taps)   depthwise over E, causal, K taps
      y = (C * c) w_out             (E -> E)

    Both projections are matrix products at the compute dtype with
    float32 accumulation; the gates and the taps run in float32 on the
    vector unit. No bias anywhere. Its backward is autodiff's."""
    op_type = OperatorType.OP_GATED_SHORT_CONV

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, k, dt = in_shapes[0][-1], params["taps"], in_dtypes[0]
        return [WeightSpec("w_in", (e, 3, e), dt,
                           init_args={"fans": (e, 3 * e)}),
                # one filter a channel: K taps in, K positions reached
                WeightSpec("taps", (e, k), dt, init_args={"fans": (k, k)}),
                WeightSpec("w_out", (e, e), dt)]

    def emit(self, params, inputs, weights, ctx, name):
        (u,) = inputs
        mdt = compute_dtype(ctx, u.dtype)
        if events.enabled():
            events.instant("conv.short", layer=name,
                           channels=weights["taps"].shape[0],
                           taps=weights["taps"].shape[1],
                           tokens=u.shape[0] * u.shape[1])
        bcx = jnp.einsum("ble,egc->blgc", u.astype(mdt),
                         weights["w_in"].astype(mdt),
                         preferred_element_type=jnp.float32)
        gate_b, gate_c, x = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
        c = short_conv(gate_b * x, weights["taps"].astype(jnp.float32))
        y = jnp.einsum("blc,ce->ble", (gate_c * c).astype(mdt),
                       weights["w_out"].astype(mdt),
                       preferred_element_type=jnp.float32)
        return [y.astype(u.dtype)]

    def flops(self, params, in_shapes, out_shapes):
        tokens = float(np.prod(in_shapes[0][:-1]))
        e, k = in_shapes[0][-1], params["taps"]
        return tokens * (2.0 * e * 3 * e + 2.0 * e * e + (2 * k + 2) * e)

    def backward_flops_factor(self):
        return 2.0


# ---------------------------------------------------------------------------
def _apply_rope(x, pos, theta: float, rotary_dim: int | None = None):
    """Rotary position embedding, LLaMA half-split-rotate convention.
    ``x``: (B, L, h, d) with d even; ``pos``: (L,) absolute indices
    shared by the batch, or (B, L) per-row (ragged-prompt decode).
    ``rotary_dim`` (None: the head): only the first ``rotary_dim``
    entries of a head turn, in half-split pairs among themselves with
    frequencies ``theta ** (-2 i / rotary_dim)``; the rest pass."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [_apply_rope(x[..., :rotary_dim], pos, theta),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pf = pos.astype(jnp.float32)
    if pf.ndim == 1:
        pf = pf[None, :]                                # (1, L)
    freqs = pf[:, :, None] * inv[None, None, :]         # (B|1, L, d/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)      # (B|1, L, d)
    cos = jnp.cos(emb)[:, :, None, :]
    sin = jnp.sin(emb)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    xf = x.astype(jnp.float32)
    return (xf * cos + rot.astype(jnp.float32) * sin).astype(x.dtype)


@register
class MultiHeadAttentionOp(OpDef):
    """Multi-head attention (reference ``src/ops/attention.cc`` wraps cuDNN
    MHA; here: einsum attention, bf16 on the MXU, fp32 softmax).

    Inputs: query (B, Lq, E), key (B, Lk, Ek), value (B, Lv, Ev).
    Output: (B, Lq, E) after the output projection — matching
    ``FFModel::multihead_attention`` (reference ``model.h``).
    """
    op_type = OperatorType.OP_MULTIHEAD_ATTENTION

    def hands_on(self, params):
        return (1, 2) if params.get("kv_out") else ()

    def infer(self, params, in_shapes, in_dtypes):
        q = in_shapes[0]
        outs = [((q[0], q[1], params["embed_dim"]), in_dtypes[0])]
        if params.get("kv_out"):
            # the projected keys and values, in heads, after the bias
            h = params["num_heads"]
            kvh = params.get("num_kv_heads", 0) or h
            e = params["embed_dim"]
            for size in (params.get("kdim", 0) or e,
                         params.get("vdim", 0) or e):
                outs.append(((in_shapes[1][0], in_shapes[1][1], kvh,
                              size // h), in_dtypes[0]))
        return outs

    def weights(self, params, in_shapes, in_dtypes):
        e = params["embed_dim"]
        h = params["num_heads"]
        kvh = params.get("num_kv_heads", 0) or h   # GQA: kv-head groups
        kdim = params.get("kdim", 0) or e
        vdim = params.get("vdim", 0) or e
        # qProjSize == kProjSize == kdim (reference attention.cc:182)
        dt = in_dtypes[0]
        qe, ke, ve = in_shapes[0][-1], in_shapes[1][-1], in_shapes[2][-1]
        ws = [WeightSpec("wq", (qe, h, kdim // h), dt),
              WeightSpec("wk", (ke, kvh, kdim // h), dt),
              WeightSpec("wv", (ve, kvh, vdim // h), dt),
              WeightSpec("wo", (h, vdim // h, e), dt)]
        if params.get("bias", True):
            ws += [WeightSpec("bq", (h, kdim // h), dt, InitializerType.ZERO),
                   WeightSpec("bk", (kvh, kdim // h), dt,
                              InitializerType.ZERO),
                   WeightSpec("bv", (kvh, vdim // h), dt,
                              InitializerType.ZERO),
                   WeightSpec("bo", (e,), dt, InitializerType.ZERO)]
        if params.get("kv_projected"):
            # keys and values come in heads from the layer that
            # projected them: this one has no wk, wv of its own
            ws = [w for w in ws if w.name not in ("wk", "wv", "bk", "bv")]
        if params.get("differential"):
            # the four vectors of the learned scalar lambda and the
            # scale of the norm over a pair's 2 d values
            ws += [WeightSpec(f"lambda_{k}", (kdim // h,), dt,
                              InitializerType.NORMAL, {"stddev": 0.1})
                   for k in ("q1", "k1", "q2", "k2")]
            ws.append(WeightSpec("subln", (2 * vdim // h,), dt,
                                 InitializerType.ONE))
        if params.get("qk_norm", False):
            # one learned scale a projection, shared by its heads; a
            # zero-centred norm multiplies by 1 + w with w drawn at 0
            at = InitializerType.ZERO \
                if params.get("qk_norm_zero_centered") \
                else InitializerType.ONE
            ws += [WeightSpec("q_norm", (kdim // h,), dt, at),
                   WeightSpec("k_norm", (kdim // h,), dt, at)]
        if params.get("output_gate", False):
            # an elementwise sigmoid gate on the attention's output, read
            # from the layer's input: a projection of its own, as wq is
            ws.append(WeightSpec("wg", (qe, h, vdim // h), dt))
        if params.get("indexer_heads"):
            # the sparse-attention indexer: its queries, its one key
            # head and a weight a query head (``ops/sparse_attention``)
            j, c = params["indexer_heads"], params["indexer_head_dim"]
            ws += [WeightSpec("wq_idx", (qe, j, c), dt),
                   WeightSpec("wk_idx", (qe, c), dt),
                   WeightSpec("w_idx", (qe, j), dt)]
        return ws

    @staticmethod
    def _impl_for(ctx, name: str):
        """This op's kernel impl from the adopted plan (the executor
        threads ``strategy.kernel_impls`` through EmitCtx): the
        layer-name key wins over the "attention" kind key, which a
        forced choice sets for every attention op; None = no plan, the
        ``auto`` rule decides."""
        plan = getattr(ctx, "kernel_impls", None)
        if not plan:
            return None
        return plan.get(name, plan.get("attention"))

    # "auto" without a kernel plan: the compiled kernel from the length
    # at which the chip showed it faster than XLA's materialised s²
    # attention in every measured column (PERF.md section 6, PR 30: one
    # layer's forward + backward, the 54 rows of
    # ``examples/tpu_attention_choice.py`` on a v5e: 128 to 1024
    # positions, 4k-16k tokens a batch, head sizes 64 and 128, dropout 0
    # and 0.1, causal and not). With no dropout the kernel wins every
    # column from 1024 (0.60-0.65 of XLA's time) and loses or ties below
    # (768: 0.97-1.07; 512: 0.83-0.92 at 64 but 1.19 at 128; 384: 1.12-
    # 1.28). With dropout XLA's path also draws, writes and re-reads an
    # s²-sized mask, and the kernel wins every column from 256 (0.67-0.70;
    # 384: 0.47-0.49; 512: 0.38-0.42; 1024: 0.28-0.31); at 128 it loses
    # at head size 64 (1.23).
    FLASH_AUTO_MIN_SEQ = 1024
    FLASH_AUTO_MIN_SEQ_DROPOUT = 256

    @classmethod
    def auto_takes_flash(cls, q_len: int, kv_len: int, head_dim: int,
                         v_dim: int, dropout: float) -> bool:
        """The ``auto`` rule, from the shapes and the dropout rate alone
        (causal or not never changed a column's winner). Under
        ``FLASH_AUTO_MIN_SEQ`` only what the table covers moves to the
        kernel: self-attention with dropout over a multiple of 128
        positions at head sizes 64 to 128; a length it does not cover
        (197 was timed at one head size only) stays on XLA."""
        s = max(q_len, kv_len)
        if s >= cls.FLASH_AUTO_MIN_SEQ:
            return True
        measured = (dropout > 0.0 and q_len == kv_len and s % 128 == 0
                    and 64 <= min(head_dim, v_dim)
                    and max(head_dim, v_dim) <= 128)
        return measured and s >= cls.FLASH_AUTO_MIN_SEQ_DROPOUT

    @classmethod
    def _flash_enabled(cls, impl, q_len: int, kv_len: int, head_dim: int,
                       v_dim: int, dropout: float = 0.0, *,
                       causal: bool = False, window: int = 0) -> bool:
        """Whether this attention call takes the Pallas flash kernel.
        ``impl`` is the adopted plan's for this layer: "flash" and "xla"
        decide, anything else (no plan) asks :meth:`auto_takes_flash` on
        a backend that compiles the kernel. A ``window`` is the
        kernels' own band arithmetic on the full training or eval
        forward (``q_len == kv_len``); the kernels have no causal mask
        for ``q_len != kv_len`` (prefill against a cache, decode), with
        a window or without: those stay on XLA whoever asks."""
        if causal and q_len != kv_len:
            return False
        if impl in ("flash", "xla"):
            return impl == "flash"
        from ..kernels._interpret import pallas_interpret
        return not pallas_interpret() and cls.auto_takes_flash(
            q_len, kv_len, head_dim, v_dim, dropout)

    @staticmethod
    def _bd_kernels_tile(params, seq: int) -> bool:
        """No block-diffusion mask, or one the flash kernels draw: their
        tiles are multiples of 128 that divide a half of the sequence."""
        return not params.get("block_diffusion_block") or seq % 256 == 0

    @staticmethod
    def _note_impl(ctx, name: str, impl: str) -> None:
        """Record which implementation this trace emitted for the full
        (non-KV) forward: ``Executor.resolved_attention_impls`` is what
        the step really runs, whatever the plan or the switches say. A
        training trace's record stands: an eval trace, which draws no
        dropout mask and may resolve otherwise, only fills a gap."""
        rec = getattr(ctx, "resolved_impls", None)
        if rec is not None and ctx.kv_mode is None \
                and (ctx.training or name not in rec):
            rec[name] = impl

    @staticmethod
    def _kernel_shard_spec(ctx, batch: int, heads: int):
        """``(mesh, spec)`` for a compiled kernel on (b, h, s, d)
        operands inside the executor's multi-device jit, read from the
        op's adopted sharding: the batch axes of output 0 and the head
        axes of ``wq``. ``(None, None)`` means call the kernel directly
        — one device, or emission already inside a manual region
        (pipeline stages, the quantized-sync shard_map)."""
        mesh = getattr(ctx, "mesh", None)
        if mesh is None or mesh.size == 1 \
                or getattr(ctx, "local_shape", False):
            return None, None
        from jax.sharding import PartitionSpec as P
        sh = getattr(ctx, "op_sharding", None)
        out = sh.outputs[0] if sh is not None and sh.outputs else None
        wq = sh.weights.get("wq") if sh is not None else None

        def entry(spec, i, dim):
            e = spec[i] if spec is not None and len(spec) > i else None
            if e is None:
                return None
            deg = 1
            for a in (e if isinstance(e, tuple) else (e,)):
                deg *= mesh.shape[a]
            return e if dim % deg == 0 else None

        return mesh, P(entry(out, 0, batch), entry(wq, 1, heads))

    def _takes_norm_rope_kernel(self, params, ctx, name, qh, kh, vh, rate,
                                mdt) -> bool:
        """Whether q and k go from the projections to the flash kernels
        through ``kernels/qk_norm_rope`` (norm, rotary embedding, cast
        and the turn to heads-first in one pass), from what the op can
        observe: both q/k norm and rotary embedding, heads in whole
        lanes, the full training or eval forward (no key/value cache),
        one device, and a path that hands q and k to the flash kernels
        heads-first (this method's last line is the test
        :meth:`emit` and :meth:`_emit_sparse` make). Every other layer
        keeps ``_rms``, ``_apply_rope`` and its own turn; so does a
        layer that turns PART of a head (``rotary_dim``): the kernel's
        pairs are the whole head's halves."""
        if not (params.get("qk_norm", False) and params.get("rope", False)) \
                or params.get("rotary_dim") is not None \
                or getattr(ctx, "kv_mode", None) is not None \
                or not (params.get("causal", False)
                        or params.get("block_diffusion_block")) \
                or qh.shape[1] != kh.shape[1] or qh.shape[2] % kh.shape[2]:
            return False
        mesh = getattr(ctx, "mesh", None)
        if (mesh is not None and mesh.size > 1) \
                or getattr(ctx, "local_shape", False):
            return False
        from ..kernels import qk_norm_rope as nrk
        _, s, h, d = qh.shape
        kv = kh.shape[2]
        impl = self._impl_for(ctx, name)
        return (impl != "ring" and nrk.takes_kernel(s, h, d, 1, mdt)
                and nrk.takes_kernel(s, kv, d, 1, mdt)
                and self._flash_enabled(
                    impl, s, s, d, vh.shape[-1], rate, causal=True,
                    window=params.get("sliding_window", 0))
                and self._bd_kernels_tile(params, s))

    def emit(self, params, inputs, weights, ctx, name):
        # an optional fourth input: (B, L) int32 positions that the
        # rotary embedding turns by (default: 0 .. L - 1)
        q, k, v, *positions = inputs
        cdt = q.dtype
        h = params["num_heads"]

        mdt = compute_dtype(ctx, cdt)

        def proj(x, w, b):
            y = jnp.einsum("ble,ehd->blhd", x.astype(mdt),
                           w.astype(mdt),
                           preferred_element_type=jnp.float32)
            if b is not None:
                y = y + b.astype(jnp.float32)
            return y

        # keys and values that another layer projected come in heads
        own_kv = not params.get("kv_projected")
        with jax.named_scope("attn.proj"):
            qh = proj(q, weights["wq"], weights.get("bq"))
            kh = proj(k, weights["wk"], weights.get("bk")) if own_kv \
                else k.astype(jnp.float32)
            vh = proj(v, weights["wv"], weights.get("bv")) if own_kv \
                else v.astype(jnp.float32)
            # the output gate's pre-activation, (B, L, h, dv) float32,
            # from the input the query projection reads
            gate = proj(q, weights["wg"], None) \
                if params.get("output_gate", False) else None
        window = params.get("sliding_window", 0)
        bd_block = params.get("block_diffusion_block", 0)
        if bd_block:
            unbuilt = [k for k in ("causal", "sliding_window",
                                   "indexer_heads", "differential",
                                   "output_gate", "dropout", "kv_out",
                                   "kv_projected") if params.get(k)]
            if unbuilt or self._impl_for(ctx, name) == "ring" \
                    or getattr(ctx, "kv_mode", None) is not None \
                    or qh.shape[1] != kh.shape[1] or qh.shape[1] % 2:
                raise ValueError(
                    f"{name}: the block-diffusion mask is self-attention "
                    f"over 2 L positions on the flash and XLA paths of the "
                    f"training and eval forward; not built beside "
                    f"{unbuilt or 'the ring path or a key/value cache'}")
        if params.get("differential"):
            return self._emit_differential(params, weights, ctx, name, qh,
                                           kh, vh, mdt, cdt)
        if not own_kv or params.get("kv_out"):
            raise ValueError(f"{name}: keys and values handed from layer "
                             f"to layer are built for differential "
                             f"attention only")
        if gate is not None and (params.get("indexer_heads")
                                 or self._impl_for(ctx, name) == "ring"):
            raise ValueError(f"{name}: an output gate is built on the "
                             f"flash, XLA and decode paths only")
        # the scores' multiplier where the model publishes its own
        # (absent: 1 / sqrt(head size), and nothing below changes)
        sm_scale = params.get("sm_scale")
        if sm_scale is not None:
            if params.get("indexer_heads") \
                    or self._impl_for(ctx, name) == "ring":
                raise ValueError(f"{name}: a softmax scale of the "
                                 f"model's own is built on the flash, XLA "
                                 f"and decode paths only")
            if events.enabled():
                events.instant("attn.sm_scale", layer=name, heads=h,
                               kv_heads=kh.shape[2], head_dim=qh.shape[-1],
                               sm_scale=sm_scale,
                               default=1.0 / math.sqrt(qh.shape[-1]))
        # qh.shape[2], not params["num_heads"]: under the tp attn role
        # this code runs inside shard_map with LOCAL head counts
        heads = qh.shape[2]
        rate = params.get("dropout", 0.0) if ctx.training else 0.0
        # q and k from the projections to the flash kernels' operands in
        # one kernel (norm, rotary embedding, cast, heads-first): from
        # here on they are (B, h, L, d) in ``mdt`` where ``fused``
        fused = self._takes_norm_rope_kernel(params, ctx, name, qh, kh, vh,
                                             rate, mdt)
        with jax.named_scope("attn.norm_rope"):
            if params.get("qk_norm", False):
                # RMSNorm over each head's own entries, before the rotary
                # embedding; ahead of the decode branch, so the cache holds
                # normed (and rotated) keys
                eps = params.get("qk_norm_eps", 1e-6)
                q_scale, k_scale = weights["q_norm"], weights["k_norm"]
                if params.get("qk_norm_zero_centered"):
                    q_scale, k_scale = 1.0 + q_scale, 1.0 + k_scale
                if not fused:
                    qh = _rms(qh, q_scale, eps)
                    kh = _rms(kh, k_scale, eps)
                if events.enabled():
                    # ``rotary_dim``: the entries of a head that turn
                    # (absent from a layer that gives none: the head)
                    turned = {} if params.get("rotary_dim") is None else {
                        "rotary_dim": params["rotary_dim"]}
                    events.instant("attn.qk_norm", layer=name, heads=h,
                                   kv_heads=kh.shape[2],
                                   kv_group=heads // kh.shape[2],
                                   head_dim=qh.shape[-1],
                                   tokens=qh.shape[0] * qh.shape[1],
                                   impl="kernel" if fused else "xla",
                                   **turned)

            causal = params.get("causal", False)
            kv_mode = getattr(ctx, "kv_mode", None)
            if params.get("rope", False):
                # rotary embeddings applied in-op (LLaMA convention,
                # half-split rotate) — positions are absolute indices, so
                # the single decode token rotates at kv_index and the cache
                # stores already-rotated keys
                if not (causal or bd_block):
                    raise ValueError(
                        "rope is only supported for causal attention")
                if qh.shape[1] != kh.shape[1]:
                    raise ValueError(
                        "rope=True requires self-attention (Lq == Lk); "
                        "cross-attention has no single absolute position "
                        "stream")
                theta = float(params.get("rope_theta", 10000.0))
                if kv_mode == "decode":
                    kvi = jnp.asarray(ctx.kv_index)
                    # scalar index -> (1,); per-row (ragged prompts) -> (B,1)
                    pos = kvi[:, None] if kvi.ndim else kvi[None]
                elif positions:
                    pos = positions[0]
                    if bd_block and pos.shape[1] * 2 == qh.shape[1]:
                        # the noised half and the clean one turn alike
                        pos = jnp.concatenate([pos, pos], axis=1)
                else:
                    pos = jnp.arange(qh.shape[1], dtype=jnp.int32)
                if fused:
                    from ..kernels import qk_norm_rope as nrk
                    tables = nrk.rope_tables(pos, qh.shape[-1], theta)
                    qh = nrk.qk_norm_rope(qh, q_scale, tables,
                                          eps=eps, dtype=mdt)
                    # k at its own heads: the flash kernels that follow
                    # read a group's k/v head in place
                    kh = nrk.qk_norm_rope(kh, k_scale, tables,
                                          eps=eps, dtype=mdt)
                    ctx.count("attn.norm_rope_kernel_layers", jnp.float32(1.0))
                else:
                    part = params.get("rotary_dim")
                    if part is not None and (
                            params.get("indexer_heads")
                            or self._impl_for(ctx, name) == "ring"):
                        raise ValueError(
                            f"{name}: a rotary embedding over part of a "
                            f"head (rotary_dim {part}) is built on the "
                            f"flash, XLA and decode paths only")
                    qh = _apply_rope(qh, pos, theta, part)
                    kh = _apply_rope(kh, pos, theta, part)
        if params.get("indexer_heads") and (
                kv_mode is not None or not causal or rate > 0.0
                or params.get("sliding_window", 0)):
            raise ValueError(
                f"{name}: an attention layer with an indexer is causal "
                f"self-attention on the training path, with no dropout, "
                f"window or key/value cache")
        if kv_mode == "prefill":
            # record per-position K/V for incremental decode; padded
            # positions hold garbage but every one is rewritten by the
            # decode step that first unmasks it. GQA caches the kv-head
            # count (the cache-size win is the point of GQA).
            W = params.get("sliding_window", 0)
            plen = getattr(ctx, "kv_prefill_len", None)
            if W and plen is not None and W < kh.shape[1]:
                # sliding window: ring-buffer cache of W slots (position
                # p lives at slot p % W) + a position track for masking —
                # O(window) HBM instead of O(max_seq). Slot s seeds with
                # the largest prompt position ≡ s (mod W); slots no
                # prompt position reached carry pos -inf (masked).
                L = kh.shape[1]
                s_idx = jnp.arange(W)
                pstar = plen - 1 - jnp.mod(plen - 1 - s_idx, W)
                valid = pstar >= 0
                gather = jnp.clip(pstar, 0, L - 1)
                pos = jnp.where(valid, pstar, -(10 ** 9))
                ctx.new_kv[name] = {
                    "k": jnp.take(kh, gather, axis=1),
                    "v": jnp.take(vh, gather, axis=1),
                    "pos": jnp.broadcast_to(pos[None, :],
                                            (kh.shape[0], W)),
                }
            else:
                ctx.new_kv[name] = {"k": kh, "v": vh}
        elif kv_mode == "decode":
            return self._emit_decode(params, weights, ctx, name, qh, kh,
                                     vh, mdt, cdt, gate)
        if params.get("indexer_heads"):
            return self._emit_sparse(params, q, weights, ctx, name, qh, kh,
                                     vh, mdt, cdt, fused)
        impl = self._impl_for(ctx, name)
        ring = impl == "ring" and kv_mode is None
        # a windowed prefill keeps its XLA path beside the ring-buffer
        # cache it fills: the kernels' window is the full forward's
        flash = not ring and (fused or (
            (not window or kv_mode is None) and self._flash_enabled(
                impl, qh.shape[1], kh.shape[1], qh.shape[-1], vh.shape[-1],
                rate, causal=causal, window=window)
            and self._bd_kernels_tile(params, qh.shape[1])))
        mesh, spec = self._kernel_shard_spec(ctx, qh.shape[0], heads) \
            if flash else (None, None)
        # GQA: the flash kernels read the kv heads in place (a query
        # head names its group's row; ``bwd_dkv`` sums a group's heads in
        # its accumulator). The XLA and ring contractions, and a mesh
        # whose head axis does not divide the kv heads (the shard_map
        # wrap shards q, k and v by the one spec: the kv heads have to
        # get the query heads' entry), get kv-head groups repeated to
        # the query head count (cache/weights stay at kvh heads)
        if not (flash and self._kernel_shard_spec(
                ctx, qh.shape[0], vh.shape[2])[1] == spec):
            kh = self._expand_kv(kh, heads)
            vh = self._expand_kv(vh, heads)
        if ring:
            if rate > 0.0:
                raise ValueError(
                    f"{name}: kernel impl 'ring' has no in-kernel "
                    f"dropout (the registry predicate rejects it; a "
                    f"forced plan must not bypass the verifier)")
            self._note_impl(ctx, name, "ring")
            return self._emit_ring(weights, ctx, name, qh, kh, vh, mdt,
                                   cdt, causal)
        if window and kv_mode is None:
            # the band's pairs beside the causal ones, whoever masks them
            b_, s_ = q.shape[0], q.shape[1]
            causal_pairs = b_ * s_ * (s_ + 1) / 2
            w_ = min(window, s_)
            ctx.count("attn.window_pairs",
                      jnp.float32(b_ * (w_ * s_ - w_ * (w_ - 1) / 2)))
            ctx.count("attn.causal_pairs", jnp.float32(causal_pairs))
        if bd_block:
            self._note_block_diffusion(ctx, name, q.shape[0],
                                       q.shape[1] // 2, bd_block, heads,
                                       vh.shape[2], qh.shape[-1], flash, mdt)
        if flash:
            # Pallas flash kernel ((b,h,s,d) layout); dropout on the
            # probabilities is counter-based and in-kernel, compiled on
            # TPU and in interpret mode alike, seeded from this layer's
            # key of the step
            from ..kernels import flash_attention
            seed = None
            if rate > 0.0:
                seed = jax.random.randint(ctx.rng_for(name), (),
                                          0, 2 ** 31 - 1, jnp.int32)
            self._note_impl(ctx, name, "flash")
            if vh.shape[2] != heads:
                ctx.count("attn.grouped_kv_layers", jnp.float32(1.0))
            with jax.named_scope("attn.kernels"):
                # a window is the kernels' own band arithmetic, never a
                # mask operand; under a mesh the shard_map wrap passes
                # it through (heads and batch are what is sharded)
                o = flash_attention(
                    *((qh, kh) if fused else
                      (jnp.swapaxes(qh, 1, 2).astype(mdt),
                       jnp.swapaxes(kh, 1, 2).astype(mdt))),
                    jnp.swapaxes(vh, 1, 2).astype(mdt),
                    causal=causal,
                    dropout_rate=rate, dropout_seed=seed,
                    mesh=mesh, spec=spec,
                    **({"window": window} if window else {}),
                    **({"block_diffusion": (q.shape[1] // 2, bd_block)}
                       if bd_block else {}),
                    **({} if sm_scale is None else {"sm_scale": sm_scale}))
            ctxv = jnp.swapaxes(o, 1, 2).astype(jnp.float32)
            return self._project_out(ctxv, gate, weights, ctx, mdt, cdt)

        self._note_impl(ctx, name, "xla")
        scale = 1.0 / math.sqrt(qh.shape[-1]) if sm_scale is None \
            else sm_scale
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh.astype(mdt),
                            kh.astype(mdt),
                            preferred_element_type=jnp.float32) * scale
        if params.get("causal", False):
            lq, lk = logits.shape[-2], logits.shape[-1]
            qpos = jnp.arange(lq)[:, None] + (lk - lq)
            kpos = jnp.arange(lk)[None, :]
            mask = kpos <= qpos
            window = params.get("sliding_window", 0)
            if window:
                # Mistral-family sliding window: each query attends the
                # last `window` positions only
                mask = jnp.logical_and(mask, kpos > qpos - window)
            logits = jnp.where(mask, logits, jnp.float32(-1e9))
        if bd_block:
            from ..kernels.flash_attention import block_diffusion_mask
            logits = jnp.where(
                block_diffusion_mask(logits.shape[-1] // 2, bd_block),
                logits, jnp.float32(-1e9))
        probs = jax.nn.softmax(logits, axis=-1)
        rate = params.get("dropout", 0.0)
        if ctx.training and rate > 0.0:
            rng = ctx.rng_for(name)
            keep = 1.0 - rate
            probs = jnp.where(jax.random.bernoulli(rng, keep, probs.shape),
                              probs / keep, 0.0)
        ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(mdt),
                          vh.astype(mdt),
                          preferred_element_type=jnp.float32)
        return self._project_out(ctxv, gate, weights, ctx, mdt, cdt)

    @staticmethod
    def _note_block_diffusion(ctx, name, batch, length, block, heads,
                              kv_heads, head_dim, flash, mdt) -> None:
        """What a layer under the block-diffusion mask announces: the
        instant ``attn.block_diffusion`` (the mask's sizes and live
        pairs, which path draws it and, on the flash path, the tiles'
        pairs each kernel's grid visits) and the counters
        ``attn.bd_pairs`` (the 4 L L pairs a kernel, three kernels a
        layer) and ``attn.bd_visited_pairs`` (those the three grids
        compute, a noised x noised diagonal tile's sub-blocks where it
        is walked in them; off the kernels every pair is), whose
        quotient is the
        share of the square the layer pays for: 0.25 + B / 4 L is the
        mask's own."""
        from ..kernels.flash_attention import block_diffusion_visited
        pairs = 4 * length * length
        visited = block_diffusion_visited(
            batch * heads, length, block, head_dim, mdt,
            heads // kv_heads) if flash else {
            k: batch * heads * pairs for k in ("fwd", "bwd_dq", "bwd_dkv")}
        ctx.count("attn.bd_pairs", jnp.float32(3 * batch * heads * pairs))
        ctx.count("attn.bd_visited_pairs",
                  jnp.float32(sum(visited.values())))
        if events.enabled():
            events.instant(
                "attn.block_diffusion", layer=name, tokens=length,
                block_length=block, heads=heads, kv_heads=kv_heads,
                live_pairs=length * length + length * block, pairs=pairs,
                impl="flash" if flash else "xla",
                flash_calls=3 if flash else 0,
                **{f"visited_pairs_{k}": v // (batch * heads)
                   for k, v in visited.items()})

    def _emit_differential(self, params, weights, ctx, name, qh, kh, vh,
                           mdt, cdt):
        """Differential attention (arXiv:2410.05258) from the projected
        heads, float32 ``qh`` (B, L, h, d), ``kh``, ``vh`` (B, L, kvh,
        d): adjacent heads are a pair. Query pair ``j`` is heads ``2j,
        2j + 1`` (``q1``, ``q2``), key pair ``g`` heads ``2g, 2g + 1`` of
        k with ``V_g = [v_2g | v_2g+1]`` (2 d wide), and query pair ``j``
        reads key pair ``j // group``:

            o_j = (1 - lambda_init) RMSNorm(P1 V - lambda P2 V; subln)
            P1 = softmax(q1 k1^T / sqrt(d) + mask),  P2 likewise
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init

        ``attend(q1, k1, V)`` is ONE grouped call at ``d`` / ``2 d`` on
        ``h / 2`` heads reading ``kvh / 2``, so a layer is two calls,
        down the flash kernels (a window is their own band) or XLA's
        masked softmax as the plan or the ``auto`` rule says. The
        softmaxes, lambda and the norm are float32. Causal
        self-attention with no dropout, rotary embedding, q/k norm,
        scale, gate or indexer; not on the ring path and not beside a
        key/value cache."""
        b, l, h, d = qh.shape
        kvh = kh.shape[2]
        impl = self._impl_for(ctx, name)
        unbuilt = [k for k in ("indexer_heads", "output_gate", "rope",
                               "qk_norm", "sm_scale", "dropout")
                   if params.get(k)]
        if unbuilt or impl == "ring" or not params.get("causal", False) \
                or getattr(ctx, "kv_mode", None) is not None:
            raise ValueError(
                f"{name}: differential attention is causal, on the flash "
                f"and XLA paths of the training and eval forward; not "
                f"built beside {unbuilt or 'the ring path or a KV cache'}")
        if h % 2 or kvh % 2 or (h // 2) % (kvh // 2) \
                or kh.shape[1] != l or vh.shape[-1] != d:
            raise ValueError(f"{name}: {h} query heads on {kvh} key/value "
                             f"heads do not pair (adjacent heads, a query "
                             f"pair on key pair j // group)")
        f32 = jnp.float32
        window = params.get("sliding_window", 0)
        lam_init = float(params["lambda_init"])
        flash = self._flash_enabled(impl, l, l, d, 2 * d, 0.0, causal=True,
                                    window=window)
        self._note_impl(ctx, name, "flash" if flash else "xla")
        mesh, spec = self._kernel_shard_spec(ctx, b, h // 2) \
            if flash else (None, None)
        if events.enabled():
            events.instant(
                "attn.diff", layer=name, pairs=h // 2, key_pairs=kvh // 2,
                head_dim=d, value_dim=2 * d, window=window,
                lambda_init=lam_init,
                kv_source=params.get("kv_source") or "own",
                kv_out=bool(params.get("kv_out")),
                impl="flash" if flash else "xla", calls=2)
        if window:
            # the band's pairs beside the causal ones, a layer
            w_ = min(window, l)
            ctx.count("attn.window_pairs",
                      jnp.float32(b * (w_ * l - w_ * (w_ - 1) / 2)))
            ctx.count("attn.causal_pairs", jnp.float32(b * l * (l + 1) / 2))
        values = vh.reshape(b, l, kvh // 2, 2 * d)

        def attend(q_, k_):
            if flash:
                from ..kernels import flash_attention
                with jax.named_scope("attn.kernels"):
                    o = flash_attention(
                        *(jnp.swapaxes(x, 1, 2).astype(mdt)
                          for x in (q_, k_, values)), causal=True,
                        mesh=mesh, spec=spec,
                        **({"window": window} if window else {}))
                return jnp.swapaxes(o, 1, 2).astype(f32)
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q_.astype(mdt),
                self._expand_kv(k_, h // 2).astype(mdt),
                preferred_element_type=f32) / math.sqrt(d)
            qpos, kpos = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
            mask = kpos <= qpos
            if window:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            probs = jax.nn.softmax(jnp.where(mask, logits, f32(-1e9)), -1)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", probs.astype(mdt),
                self._expand_kv(values, h // 2).astype(mdt),
                preferred_element_type=f32)

        first = attend(qh[:, :, 0::2], kh[:, :, 0::2])
        second = attend(qh[:, :, 1::2], kh[:, :, 1::2])
        with jax.named_scope("attn.diff"):
            lq1, lk1, lq2, lk2 = (weights[f"lambda_{k}"].astype(f32)
                                  for k in ("q1", "k1", "q2", "k2"))
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam_init
            pair = _rms(first - lam * second, weights["subln"],
                        params.get("subln_eps", 1e-5)) * (1.0 - lam_init)
            # a layer's lambda: lambda_init at untrained vectors, and a
            # step that ran plain attention reads no ``attn.diff_layers``
            ctx.count("attn.diff_lambda_mean", lam)
            ctx.count("attn.diff_layers", f32(1.0))
        out = self._project_out(pair.reshape(b, l, h, d), None, weights,
                                ctx, mdt, cdt)
        return out + ([kh.astype(cdt), vh.astype(cdt)]
                      if params.get("kv_out") else [])

    @staticmethod
    def _project_out(ctxv, gate, weights, ctx, mdt, cdt):
        """The heads' outputs ``ctxv`` (B, L, h, dv) float32, times the
        sigmoid of the output gate's pre-activation where the layer has
        one (elementwise, in float32, one pass over the array: plain
        XLA, bound by bytes), through the output projection."""
        if gate is not None:
            with jax.named_scope("attn.gate"):
                g = jax.nn.sigmoid(gate)
                ctxv = ctxv * g
                # the layers' mean gate: 0.5 at untrained weights, and a
                # step that lost its gate reads no ``attn.gate_layers``
                ctx.count("attn.gate_mean", jnp.mean(g))
                ctx.count("attn.gate_layers", jnp.float32(1.0))
        with jax.named_scope("attn.out"):
            out = jnp.einsum("bqhd,hde->bqe", ctxv.astype(mdt),
                             weights["wo"].astype(mdt),
                             preferred_element_type=jnp.float32)
            if "bo" in weights:
                out = out + weights["bo"].astype(jnp.float32)
        return [out.astype(cdt)]

    def keeps_for_block(self, params):
        return bool(params.get("indexer_heads"))

    def _emit_sparse(self, params, x, weights, ctx, name, qh, kh, vh, mdt,
                     cdt, fused=False):
        """The layer with an indexer (``indexer_heads`` in its
        parameters): attention over the ``indexer_topk`` keys a query's
        index scores select (``ops/sparse_attention``). Two paths of the
        same equations, chosen as a layer without an indexer chooses
        (:meth:`_flash_enabled`: the forced implementation, else the
        shapes on a backend that compiles the kernels): the flash
        kernels with the selection as their mask operand and a fourth
        kernel for the heads' mean probability, or query chunks on XLA
        (short sequences; a mesh of more than one device, where the
        masked kernels have no ``shard_map`` wrap). The indexer reads
        the layer's input detached and its alignment loss joins the
        step's through ``ctx.aux_losses`` with weight 1; where the
        sequence is no longer than ``indexer_topk`` every causal key is
        selected and the output is the plain causal path's. ``fused``:
        ``qh`` and ``kh`` come heads-first in ``mdt`` from
        ``kernels/qk_norm_rope`` (only ever on the kernel path). The
        kernels read ``kh`` and ``vh`` at their own head count."""
        from . import sparse_attention as dsa
        topk, q_chunk = params["indexer_topk"], params["indexer_q_chunk"]
        with jax.named_scope("dsa.index"):
            qi, ki, wi = dsa.indexer_inputs(x, weights, mdt)
        s = x.shape[1]
        impl = self._impl_for(ctx, name)
        if impl == "ring":
            raise ValueError(f"{name}: kernel impl 'ring' takes no mask "
                             f"of selected keys")
        kernels = fused or (
            self._kernel_shard_spec(ctx, qh.shape[0], qh.shape[2])[0] is None
            and self._flash_enabled(impl, s, s, qh.shape[-1], vh.shape[-1],
                                    causal=True))
        path = "flash" if kernels else "xla"
        self._note_impl(ctx, name, path)
        if events.enabled():
            events.instant("attn.sparse_index", layer=name,
                           heads=params["indexer_heads"],
                           head_dim=params["indexer_head_dim"], topk=topk,
                           q_chunk=q_chunk, chunks=-(-s // q_chunk),
                           selecting=s > topk, positions=s, impl=path)
        if kernels:
            if vh.shape[2] != params["num_heads"]:
                ctx.count("attn.grouped_kv_layers", jnp.float32(1.0))
            o, loss, kept, ties = dsa.sparse_index_attention_flash(
                qh, kh, vh, qi, ki, wi, topk, q_chunk, mdt,
                qk_heads_first=fused, layer=name)
        else:
            o, loss, kept, ties = dsa.sparse_index_attention(
                qh, kh, vh, qi, ki, wi, topk, q_chunk, mdt, layer=name)
        # either path rematerialises itself: a rematerialised block
        # around the layer keeps the attention's output (the output
        # projection's backward reads it; the kernel path marks its own,
        # with the log-sum-exp and the mask) and does not run the chunks
        # or the forward kernel again (``keeps_for_block``); outside
        # such a block the identity
        if not kernels:
            o = kept_by_block(o.astype(mdt))
        ctx.aux_losses.append(loss)
        for key, v in (("dsa.kept_pairs", kept),
                       ("dsa.causal_pairs", qh.shape[0] * s * (s + 1) / 2),
                       ("dsa.index_kl", loss), ("dsa.layers", 1.0),
                       ("dsa.kernel_layers", float(kernels)),
                       ("dsa.threshold_ties", ties)):
            ctx.count(key, jnp.asarray(v, jnp.float32))
        with jax.named_scope("dsa.attend"):
            out = jnp.einsum("bqhd,hde->bqe", o, weights["wo"].astype(mdt),
                             preferred_element_type=jnp.float32)
        if "bo" in weights:
            out = out + weights["bo"].astype(jnp.float32)
        return [out.astype(cdt)]

    @staticmethod
    def _expand_kv(x, h):
        """GQA: repeat kv-head groups up to ``h`` query heads
        ((B, L, kvh, d) -> (B, L, h, d)); identity when kvh == h."""
        kvh = x.shape[2]
        if kvh == h:
            return x
        return jnp.repeat(x, h // kvh, axis=2)

    def _emit_ring(self, weights, ctx, name, qh, kh, vh, mdt, cdt,
                   causal):
        """Ring-attention lowering: ONE shard_map over the mesh's
        dedicated ``seq`` axis. Each device holds a (B, L/deg, H, D)
        context chunk; the K/V blocks rotate ring-wise with explicit
        ``ppermute`` hops (kernels/ring_attention.py) while block
        compute hides the next block's KV transfer. The (seq, seq)
        score matrix never materializes and per-device activation
        residency drops by the seq degree — the 1/deg envelope the
        plan verifier accounts (docs/kernels.md)."""
        from ..kernels import ring_attention
        from jax import shard_map
        mesh = getattr(ctx, "mesh", None)
        ax = getattr(ctx, "seq_axis", None)
        if mesh is None or ax is None:
            raise ValueError(
                f"{name}: kernel impl 'ring' requires a mesh sequence "
                f"axis (--seq-parallel N >= 2); this compile has none")
        deg = dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
        if qh.shape[1] % deg != 0:
            raise ValueError(
                f"{name}: sequence length {qh.shape[1]} is not "
                f"divisible by the seq-axis degree {deg}")

        from jax.sharding import PartitionSpec as P

        def _ring(qc, kc, vc):
            o = ring_attention(
                jnp.swapaxes(qc, 1, 2).astype(mdt),
                jnp.swapaxes(kc, 1, 2).astype(mdt),
                jnp.swapaxes(vc, 1, 2).astype(mdt),
                ax, causal=causal)
            return jnp.swapaxes(o, 1, 2)

        spec = P(None, ax, None, None)
        o = shard_map(_ring, mesh=mesh, in_specs=(spec,) * 3,
                      out_specs=spec, check_vma=False)(qh, kh, vh)
        ctxv = o.astype(jnp.float32)
        out = jnp.einsum("bqhd,hde->bqe", ctxv.astype(mdt),
                         weights["wo"].astype(mdt),
                         preferred_element_type=jnp.float32)
        if "bo" in weights:
            out = out + weights["bo"].astype(jnp.float32)
        return [out.astype(cdt)]

    def _emit_decode(self, params, weights, ctx, name, qh, kh, vh, mdt,
                     cdt, gate=None):
        """Single-token decode against the KV cache: write this
        position's K/V into the cache, attend the length-1 query over
        positions <= kv_index. Exactly matches the full re-forward's row
        at kv_index (same mask, same softmax domain) — the re-forward
        path is the numerics oracle in tests/test_generate_kv.py."""
        if not params.get("causal", False):
            raise ValueError(
                "KV-cache decode requires causal self-attention")
        cache = ctx.kv_cache[name]
        idx = jnp.asarray(ctx.kv_index)
        ragged = idx.ndim == 1            # per-row positions (B,)
        ring = "pos" in cache
        if ring and ragged:
            raise ValueError(
                "ragged prompts use the full cache (generate passes "
                "prefill_len=None for vector prompt lengths)")
        if ring:
            # sliding-window ring buffer: write slot idx % W, track the
            # stored position for the validity mask
            W = cache["k"].shape[1]
            slot = jnp.mod(idx, W)
            b_ = kh.shape[0]
            pos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], jnp.full((b_, 1), idx, cache["pos"].dtype),
                slot, axis=1)
        else:
            slot = idx
        if ragged:
            # one-hot write at each row's own position
            sel = (jnp.arange(cache["k"].shape[1])[None, :]
                   == idx[:, None])[:, :, None, None]
            k_full = jnp.where(sel, kh.astype(cache["k"].dtype),
                               cache["k"])
            v_full = jnp.where(sel, vh.astype(cache["v"].dtype),
                               cache["v"])
        else:
            k_full = jax.lax.dynamic_update_slice_in_dim(cache["k"], kh,
                                                         slot, axis=1)
            v_full = jax.lax.dynamic_update_slice_in_dim(cache["v"], vh,
                                                         slot, axis=1)
        ctx.new_kv[name] = {"k": k_full, "v": v_full}
        if ring:
            ctx.new_kv[name]["pos"] = pos
        # GQA: contract the length-1 query against the cache AT kvh
        # heads (grouped einsum) — materializing an expanded copy of
        # the whole cache every step would undo GQA's decode-bandwidth
        # win. g == 1 reduces to plain MHA.
        b_, lq_, hq, d_ = qh.shape
        kvh = k_full.shape[2]
        g = hq // kvh
        qg = qh.reshape(b_, lq_, kvh, g, d_)
        scale = params.get("sm_scale") or 1.0 / math.sqrt(d_)
        logits = jnp.einsum("bqkgd,bmkd->bkgqm", qg.astype(mdt),
                            k_full.astype(mdt),
                            preferred_element_type=jnp.float32) * scale
        window = params.get("sliding_window", 0)
        if ring:
            # slot positions carry the mask (invalid slots hold -1e9)
            p = pos[:, None, None, None, :]
            mask = jnp.logical_and(p <= idx, p > idx - window)
        else:
            lk = k_full.shape[1]
            kpos = jnp.arange(lk)[None, None, None, None, :]
            # scalar idx broadcasts; ragged (B,) idx masks per row
            iq = idx[:, None, None, None, None] if ragged else idx
            mask = kpos <= iq
            if window:
                mask = jnp.logical_and(mask, kpos > iq - window)
        logits = jnp.where(mask, logits, jnp.float32(-1e9))
        probs = jax.nn.softmax(logits, axis=-1)
        ctxv = jnp.einsum("bkgqm,bmkd->bqkgd", probs.astype(mdt),
                          v_full.astype(mdt),
                          preferred_element_type=jnp.float32)
        ctxv = ctxv.reshape(b_, lq_, hq, d_)
        return self._project_out(ctxv, gate, weights, ctx, mdt, cdt)

    def flops(self, params, in_shapes, out_shapes):
        b, lq, _ = in_shapes[0]
        lk = in_shapes[1][1]
        e = params["embed_dim"]
        h = params["num_heads"]
        kv_frac = (params.get("num_kv_heads", 0) or h) / h
        if params.get("kv_projected"):
            kv_frac = 0.0                     # another layer's products
        proj = (2.0 * b * lq * e * e                      # q proj
                + 2.0 * b * 2 * lk * e * e * kv_frac     # k+v (GQA)
                + 2.0 * b * lq * e * e)                  # out proj
        if params.get("output_gate", False):
            proj += 2.0 * b * lq * e * e                 # the gate's
        # a window's cost is the band's: at most ``window`` keys a query
        keys = min(lk, params.get("sliding_window", 0) or lk)
        attn = 2.0 * b * lq * keys * e * 2
        return proj + attn

    def backward_flops_factor(self):
        return 2.0


# ---------------------------------------------------------------------------
def yarn_correction_range(rope_dim: int, theta: float, scaling: dict):
    """``(low, high)``: the rotary pairs below ``low`` turn more than
    ``beta_fast`` times over the original context and keep their
    frequency, those from ``high`` on turn less than ``beta_slow`` times
    and are divided by ``factor`` (YaRN, arXiv:2309.00071, as DeepSeek-V3
    computes it)."""
    def pair(turns):
        return rope_dim * math.log(
            scaling["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = math.floor(pair(scaling["beta_fast"]))
    high = math.ceil(pair(scaling["beta_slow"]))
    return max(low, 0), min(high, rope_dim - 1)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 mscale ln(factor) + 1``: what YaRN multiplies by to keep
    the scores' spread over the stretched context."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(rope_dim: int, theta: float, scaling=None):
    """A pair's angle a position, ``theta ^ (-2i / d)`` for pair ``i``
    (float32, (d / 2,)). ``scaling`` (a ``type: "yarn"`` group): pair
    ``i`` keeps that, ``f_i``, below the correction range, takes ``f_i /
    factor`` above it and the blend ``f_i (1 - r_i) + (f_i / factor)
    r_i`` on the ramp ``r_i = (i - low) / (high - low)`` between."""
    if not scaling:
        return 1.0 / theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                               / rope_dim)
    i = np.arange(rope_dim // 2)
    f = theta ** (-2.0 * i / rope_dim)
    low, high = yarn_correction_range(rope_dim, theta, scaling)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(f * (1 - ramp) + f / scaling["factor"] * ramp,
                       jnp.float32)


def _rope_interleaved(x, pos, inv):
    """Rotary embedding over interleaved pairs ``(2i, 2i+1)`` of the
    last axis (DeepSeek's ``rope_interleave``), in float32. ``x``:
    (b, s, ..., d); ``pos``: (b, s) absolute positions; ``inv``: (d / 2,)
    the pairs' frequencies (:func:`rope_frequencies`)."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[..., None] * inv           # (b, s, d/2)
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - 3) + (d // 2,))
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(xf.shape)


@register
class LatentAttentionOp(OpDef):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA) as one op:
    causal self-attention whose queries and keys/values come through
    low-rank latents with an RMSNorm on each.

      c_q = RMSNorm(x wq_a);  q_h = c_q wq_b      -> [q_nope ; q_rope]
      [c_kv ; k_rope] = x wkv_a;  c_kv <- RMSNorm(c_kv)
      c_kv wkv_b                                  -> [k_nope ; v] a head
      k_h = [k_nope_h ; rope(k_rope)]   (one rotary key for all heads)
      o_h = softmax(q_h k_h / sqrt(d_nope + d_rope), causal) v_h
      out = [o_1 .. o_H] wo

    Inputs: the hidden states (b, s, e) and their positions (b, s).
    The rotary embedding turns interleaved pairs of the ``rope_dim``
    last dimensions of q and of the shared key. q and k have ``nope_dim + rope_dim`` per head, v and
    o ``v_dim``: the flash kernels take the two sizes as they are
    (``kernels/flash_attention.py``), chosen by shape and switches
    exactly as in :class:`MultiHeadAttentionOp`.

    ``rope_scaling`` (a published ``type: "yarn"`` group; absent: none)
    rescales the rotary embedding for a context stretched ``factor``
    times: the slow pairs' frequencies divided by it, the fast ones
    kept, a ramp between (:func:`rope_frequencies`), and the scores
    times ``yarn_mscale(factor, mscale_all_dim) ^ 2``.

    Two parameters switch parts of it off. ``q_rank=None``: no q latent,
    ``q_h = x wq`` with one ``wq`` (e, h, d_nope + d_rope) and no
    ``q_norm``. ``rope=False``: the ``rope_dim`` entries of q and the
    shared key are used as they are (NoPE); the positions are then not
    read."""
    op_type = OperatorType.OP_LATENT_ATTENTION

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        h, qr, kvr = (params["num_heads"], params["q_rank"],
                      params["kv_rank"])
        dn, dr, dv = params["nope_dim"], params["rope_dim"], params["v_dim"]
        one = InitializerType.ONE

        def fans(i, o):          # per-head projections: fans as a matrix
            return {"fans": (i, o)}
        q_proj = [WeightSpec("wq", (e, h, dn + dr), dt,
                             init_args=fans(e, h * (dn + dr)))] \
            if qr is None else [
                WeightSpec("wq_a", (e, qr), dt),
                WeightSpec("q_norm", (qr,), dt, one),
                WeightSpec("wq_b", (qr, h, dn + dr), dt,
                           init_args=fans(qr, h * (dn + dr)))]
        return q_proj + [
                WeightSpec("wkv_a", (e, kvr + dr), dt),
                WeightSpec("kv_norm", (kvr,), dt, one),
                WeightSpec("wkv_b", (kvr, h, dn + dv), dt,
                           init_args=fans(kvr, h * (dn + dv))),
                WeightSpec("wo", (h, dv, e), dt,
                           init_args=fans(h * dv, e))]

    def emit(self, params, inputs, weights, ctx, name):
        x, pos = inputs
        if getattr(ctx, "kv_mode", None) is not None:
            raise NotImplementedError(
                f"{name}: latent attention has no KV-cache decode path")
        cdt, mdt = x.dtype, compute_dtype(ctx, x.dtype)
        eps = params.get("eps", 1e-6)
        dn, dr = params["nope_dim"], params["rope_dim"]
        kvr = params["kv_rank"]
        b, s, _ = x.shape

        def mm(a, w, pattern):
            return jnp.einsum(pattern, a.astype(mdt), w.astype(mdt),
                              preferred_element_type=jnp.float32)

        scaling = params.get("rope_scaling")
        # None without rope_scaling: the kernels' and XLA's 1 / sqrt(d)
        sm_scale = None
        if scaling:
            # cos and sin times m(mscale) / m(mscale_all_dim), the
            # scores times m(mscale_all_dim)^2
            m_all = yarn_mscale(scaling["factor"],
                                scaling.get("mscale_all_dim", 0.0))
            rot_scale = yarn_mscale(scaling["factor"],
                                    scaling.get("mscale", 1.0)) / m_all
            if rot_scale != 1.0:
                raise NotImplementedError(
                    f"{name}: mscale != mscale_all_dim scales the rotary "
                    f"embedding itself by {rot_scale}, which is not built")
            sm_scale = m_all * m_all / math.sqrt(dn + dr)

        def inv_freq():
            return rope_frequencies(dr, float(params["rope_theta"]),
                                    scaling)

        if params["q_rank"] is None:
            q = mm(x, weights["wq"], "bse,ehd->bshd")
        else:
            c_q = _rms(mm(x, weights["wq_a"], "bse,er->bsr"),
                       weights["q_norm"], eps)
            q = mm(c_q, weights["wq_b"], "bsr,rhd->bshd")
        kv_a = mm(x, weights["wkv_a"], "bse,er->bsr")
        c_kv = _rms(kv_a[..., :kvr], weights["kv_norm"], eps)
        kv = mm(c_kv, weights["wkv_b"], "bsr,rhd->bshd")
        if params.get("rope", True):
            # the frequencies are made anew for each use: the step's
            # text stays what it was when each call made its own
            q_rope = _rope_interleaved(q[..., dn:], pos, inv_freq())
            k_rope = _rope_interleaved(kv_a[..., kvr:], pos, inv_freq())
        else:
            q_rope, k_rope = q[..., dn:], kv_a[..., kvr:]
        h = q.shape[2]
        if events.enabled():
            events.instant("attn.latent", layer=name, heads=h,
                           q_rank=params["q_rank"], kv_rank=kvr,
                           rope=bool(params.get("rope", True)),
                           tokens=b * s)
        qh = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        kh = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
            axis=-1)
        vh = kv[..., dn:]

        mha = MultiHeadAttentionOp
        if mha._flash_enabled(mha._impl_for(ctx, name), s, s, dn + dr,
                              vh.shape[-1], causal=True):
            from ..kernels import flash_attention
            mha._note_impl(ctx, name, "flash")
            mesh, spec = mha._kernel_shard_spec(ctx, b, h)
            o = flash_attention(
                jnp.swapaxes(qh, 1, 2).astype(mdt),
                jnp.swapaxes(kh, 1, 2).astype(mdt),
                jnp.swapaxes(vh, 1, 2).astype(mdt),
                causal=True, sm_scale=sm_scale, mesh=mesh, spec=spec)
            o = jnp.swapaxes(o, 1, 2)
        else:
            mha._note_impl(ctx, name, "xla")
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", qh.astype(mdt), kh.astype(mdt),
                preferred_element_type=jnp.float32)
            logits = logits / math.sqrt(dn + dr) if sm_scale is None \
                else logits * sm_scale
            mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            probs = jax.nn.softmax(
                jnp.where(mask, logits, jnp.float32(-1e9)), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(mdt),
                           vh.astype(mdt),
                           preferred_element_type=jnp.float32)
        out = mm(o, weights["wo"], "bqhd,hde->bqe")
        return [out.astype(cdt)]

    def flops(self, params, in_shapes, out_shapes):
        b, s, e = in_shapes[0]
        h, qr, kvr = (params["num_heads"], params["q_rank"],
                      params["kv_rank"])
        dn, dr, dv = params["nope_dim"], params["rope_dim"], params["v_dim"]
        q_proj = e * h * (dn + dr) if qr is None \
            else e * qr + qr * h * (dn + dr)
        proj = (q_proj + e * (kvr + dr) + kvr * h * (dn + dv)
                + h * dv * e)
        return 2.0 * b * s * (proj + s * h * (dn + dr + dv))

    def backward_flops_factor(self):
        return 2.0


@register
class NextTokenLossOp(OpDef):
    """The loss of a second prediction head: the mean cross-entropy of
    ``logits[:, t]`` against ``ids[:, t + offset]`` over the positions
    that have such a target (the last ``offset`` of a sequence have none
    and are masked), times ``weight``, added to the step's loss through
    ``ctx.aux_losses``. The output is the unweighted mean, shape (1,)."""
    op_type = OperatorType.OP_NEXT_TOKEN_LOSS

    def infer(self, params, in_shapes, in_dtypes):
        return [((1,), DataType.DT_FLOAT)]

    @staticmethod
    def mean_nll(logits, ids, offset: int):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = ids[:, offset:].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp[:, :-offset], tgt[..., None],
                                   axis=-1)
        return jnp.mean(nll)

    def emit(self, params, inputs, weights, ctx, name):
        logits, ids = inputs
        loss = self.mean_nll(logits, ids, int(params["offset"]))
        ctx.aux_losses.append(params["weight"] * loss)
        return [loss.reshape(1)]


@register
class BlockDiffusionNoiseOp(OpDef):
    """The noising of a block-diffusion training step (SDAR,
    arXiv:2510.06303; BD3-LMs' vectorised form, arXiv:2503.09573): from
    ids ``x`` (batch, L) in blocks of ``block_length``,

        u_b ~ U[0, 1) a block,   t_b = t_min + (1 - t_min) u_b
        m_i = [v_i < t_b(i)],    v_i ~ U[0, 1) a token
        z = [where(m, mask_token_id, x) ; x]        (batch, 2 L) ids
        w_i = m_i / t_b(i)                          (batch, L) float32

    ``u`` and ``v`` are ``jax.random.uniform`` on the two halves of
    ``jax.random.split(key)``, shapes (batch, L / block_length) and
    (batch, L). The key is the step's in training (``ctx.rng_for``: a
    new mask every step index, one mask at one index) and
    ``jax.random.key(eval_noise_seed)`` otherwise, so that an eval pass
    and a reference see one mask. Whether a token is masked is ``m``,
    never a comparison of ids. No gradient flows in or out.

    It announces itself: the instant ``diffusion.noise`` and the
    counters ``diffusion.tokens``, ``diffusion.masked_tokens`` and
    ``diffusion.weight_sum`` (over tokens: near 1, and far from it when
    a block drew a tiny ``t``)."""
    op_type = OperatorType.OP_BLOCK_DIFFUSION_NOISE

    def infer(self, params, in_shapes, in_dtypes):
        (b, l), = in_shapes
        if l % params["block_length"]:
            raise ValueError(f"{l} tokens are not whole blocks of "
                             f"{params['block_length']}")
        return [((b, 2 * l), in_dtypes[0]), ((b, l), DataType.DT_FLOAT)]

    @staticmethod
    def draw(key, batch: int, length: int, block: int, t_min: float):
        """``(masked, t)``, (batch, L) bool and float32, from ``key``."""
        k_t, k_v = jax.random.split(key)
        u = jax.random.uniform(k_t, (batch, length // block), jnp.float32)
        v = jax.random.uniform(k_v, (batch, length), jnp.float32)
        t = jnp.repeat(t_min + (1.0 - t_min) * u, block, axis=1)
        return v < t, t

    def emit(self, params, inputs, weights, ctx, name):
        (ids,) = inputs
        b, l = ids.shape
        key = ctx.rng_for(name) if ctx.training else None
        source = "eval" if key is None else "step"
        if key is None:
            key = jax.random.key(params["eval_noise_seed"])
        masked, t = self.draw(key, b, l, params["block_length"],
                              params["t_min"])
        noised = jnp.where(masked, jnp.asarray(params["mask_token_id"],
                                               ids.dtype), ids)
        hit = masked.astype(jnp.float32)
        w = hit / t
        ctx.count("diffusion.tokens", jnp.float32(b * l))
        ctx.count("diffusion.masked_tokens", jnp.sum(hit))
        ctx.count("diffusion.weight_sum", jnp.sum(w))
        if events.enabled():
            events.instant(
                "diffusion.noise", layer=name, tokens=b * l,
                block_length=params["block_length"],
                blocks=b * l // params["block_length"],
                mask_token_id=params["mask_token_id"],
                t_min=params["t_min"], key=source)
        return [jnp.concatenate([noised, ids], axis=1),
                jax.lax.stop_gradient(w)]


# ---------------------------------------------------------------------------
@register
class BatchMatmulOp(OpDef):
    """Batched matmul with optional seq-length masking
    (reference ``src/ops/batch_matmul.cc``)."""
    op_type = OperatorType.OP_BATCHMATMUL

    def infer(self, params, in_shapes, in_dtypes):
        a, b = in_shapes
        if a[:-2] != b[:-2]:
            raise ValueError(
                f"batch_matmul batch dims differ: {a} vs {b}")
        if a[-1] != b[-2]:
            raise ValueError(
                f"batch_matmul contraction dims differ: {a} vs {b}")
        return [(tuple(a[:-1]) + (b[-1],), in_dtypes[0])]

    def emit(self, params, inputs, weights, ctx, name):
        a, b = inputs
        return [matmul(a, b, ctx=ctx)]

    def flops(self, params, in_shapes, out_shapes):
        a, b = in_shapes
        return 2.0 * float(np.prod(a)) * b[-1]

    def backward_flops_factor(self):
        return 2.0


@register
class MatmulOp(BatchMatmulOp):
    op_type = OperatorType.OP_MATMUL
