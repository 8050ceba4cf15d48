"""Mixture-of-Experts operator family: TopK routing, Group_by dispatch,
Aggregate combine, Cache.

Reference parity: ``src/ops/{group_by,aggregate,aggregate_spec,cache}.cc``
(custom expert-routing CUDA kernels, alpha capacity factor, lambda_bal
load balancing). TPU-native design: GShard-style dense dispatch/combine
einsums over a static capacity — one-hot matmuls ride the MXU, shapes stay
static for XLA, and the expert dimension shards cleanly over a mesh axis
(expert parallelism).

Shapes (numpy order):
  group_by:  input (B, D), assign (B, K) int  ->  n tensors (C, D),
             C = ceil(alpha * K * B / n)
  aggregate: [gate_preds (B,K), gate_assign (B,K), true_assign (B,K),
              full_gate_preds (B,n), exp_pred_0 (C,Do), ... exp_pred_{n-1}]
             -> (B, Do)
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import WeightSpec
from ..ffconst import DataType, InitializerType, OperatorType
from ..obs import events
from .registry import EmitCtx, OpDef, compute_dtype, matmul, register


def _capacity(params, batch: int, k: int) -> int:
    n = params["n"]
    alpha = params.get("alpha", 1.0)
    return int(math.ceil(alpha * k * batch / n))


def _dispatch_mask(assign, n: int, capacity: int):
    """(B, K) int assignments -> (T=B*K, n, C) one-hot dispatch tensor.

    Position of each (token, choice) within its expert's buffer is its
    running count in flattened token order; overflow tokens are dropped —
    matching the reference kernels' first-come capacity policy
    (``group_by.cu`` expert_rows bound).
    """
    b, k = assign.shape
    flat = assign.reshape(-1).astype(jnp.int32)          # (T,)
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.int32)    # (T, n)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1        # (T, n): slot per tok
    in_cap = (pos < capacity) & (pos >= 0)
    poscap = jnp.where(in_cap, pos, 0)
    poshot = jax.nn.one_hot(poscap.sum(-1), capacity, dtype=jnp.float32)
    mask = (onehot.astype(jnp.float32) * in_cap.astype(jnp.float32))
    return mask[:, :, None] * poshot[:, None, :]          # (T, n, C)


@register
class GroupByOp(OpDef):
    op_type = OperatorType.OP_GROUP_BY

    def infer(self, params, in_shapes, in_dtypes):
        (b, d), (b2, k) = in_shapes[0], in_shapes[1]
        if b != b2:
            raise ValueError(
                f"group_by input/assign batch dims differ: {in_shapes}")
        c = _capacity(params, b, k)
        return [((c, d), in_dtypes[0])] * params["n"]

    def emit(self, params, inputs, weights, ctx, name):
        x, assign = inputs
        b, k = assign.shape
        n = params["n"]
        c = _capacity(params, b, k)
        disp = _dispatch_mask(assign, n, c)               # (T, n, C)
        xr = jnp.repeat(x, k, axis=0)                     # (T, D) token per slot
        mdt = compute_dtype(ctx, x.dtype)
        buf = jnp.einsum("tec,td->ecd", disp.astype(mdt),
                         xr.astype(mdt),
                         preferred_element_type=jnp.float32)
        buf = buf.astype(x.dtype)
        return [buf[e] for e in range(n)]


@register
class AggregateOp(OpDef):
    """Combine expert outputs weighted by gate probabilities; adds the
    lambda_bal load-balancing auxiliary loss (the reference injects an
    equivalent term directly into gate gradients in ``aggregate.cu``)."""
    op_type = OperatorType.OP_AGGREGATE

    def infer(self, params, in_shapes, in_dtypes):
        b = in_shapes[0][0]
        out_dim = in_shapes[4][-1]
        return [((b, out_dim), in_dtypes[4])]

    def emit(self, params, inputs, weights, ctx, name):
        gate_preds, gate_assign = inputs[0], inputs[1]
        full_gate_preds = inputs[3]
        exp_preds = inputs[4:]
        n = params["n"]
        b, k = gate_assign.shape
        c = exp_preds[0].shape[0]
        disp = _dispatch_mask(gate_assign, n, c)          # (T, n, C)
        w = gate_preds.reshape(-1)                        # (T,)
        combine = disp * w[:, None, None]
        stacked = jnp.stack(exp_preds, axis=0)            # (n, C, Do)
        mdt = compute_dtype(ctx, exp_preds[0].dtype)
        out = jnp.einsum("tec,ecd->td", combine.astype(mdt),
                         stacked.astype(mdt),
                         preferred_element_type=jnp.float32)
        out = out.reshape(b, k, -1).sum(axis=1).astype(exp_preds[0].dtype)
        # GShard-style load-balance aux loss: n * sum_e(frac_tokens_e * mean_gate_e)
        lam = params.get("lambda_bal", 0.0)
        if lam > 0.0 and full_gate_preds is not None:
            frac = jnp.mean(
                jax.nn.one_hot(gate_assign[:, 0], n, dtype=jnp.float32), axis=0)
            mean_gate = jnp.mean(jax.nn.softmax(full_gate_preds, -1), axis=0)
            ctx.aux_losses.append(lam * n * jnp.sum(frac * mean_gate))
        return [out]


@register
class AggregateSpecOp(AggregateOp):
    """Aggregate variant that ignores gate weighting for the expert pass-
    through (reference ``aggregate_spec.cc`` — used with Cache for MoE
    speculation). Same output shape as Aggregate."""
    op_type = OperatorType.OP_AGG_SPEC

    def emit(self, params, inputs, weights, ctx, name):
        inputs = list(inputs)
        inputs[0] = jnp.ones_like(inputs[0]) / inputs[0].shape[-1]
        return super().emit(params, inputs, weights, ctx, name)


@register
class CacheOp(OpDef):
    """Rolling tensor cache (reference ``src/ops/cache.cc``): stores the
    input in the state collection; with a score trigger the runtime's
    recompile hook can switch to serving the cached value."""
    op_type = OperatorType.OP_CACHE

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def state_spec(self, params, in_shapes, in_dtypes):
        return {"cached": (in_shapes[0], in_dtypes[0])}

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        st = ctx.state.get(name)
        use_cached = params.get("use_cached", False)
        if st is not None:
            ctx.new_state[name] = {"cached": x}
            if use_cached and not ctx.training:
                return [st["cached"]]
        return [x]


# ---------------------------------------------------------------------------
# sparse, dropless routed experts (one op a layer)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _rows_for(x, order, inverse):
    """Row ``order[r] // k`` of ``x`` for every sorted assignment ``r``
    (``k = len(order) // len(x)`` assignments a token). Its transpose
    gathers too: the ``k`` rows of a token sit at ``inverse`` and are
    summed, so no scatter is emitted in either direction."""
    return x[order // (order.shape[0] // x.shape[0])]


def _rows_for_fwd(x, order, inverse):
    return _rows_for(x, order, inverse), (inverse, x.shape[0])


def _rows_for_bwd(res, g):
    inverse, t = res
    summed = g[inverse].astype(jnp.float32).reshape(
        t, -1, g.shape[-1]).sum(axis=1)
    return summed.astype(g.dtype), None, None


_rows_for.defvjp(_rows_for_fwd, _rows_for_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation whose inverse is known: the
    transpose is ``g[inverse]``, a gather, where autodiff would scatter."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                lambda inverse, g: (g[inverse], None, None))


def route(scores, bias, top_k: int, scale: float):
    """Bias-corrected top-k (DeepSeek-V3's ``noaux_tc`` with one group):
    the choice is over ``scores + bias``, the gates are the chosen
    experts' own scores, normalised over all ``top_k`` chosen and scaled.
    ``scores``: (tokens, experts) float32. Returns ``(idx, gates)``, both
    (tokens, top_k)."""
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, gates


@register
class RoutedExpertsOp(OpDef):
    """One mixture-of-experts feed-forward layer, sparse and dropless,
    for a device that holds ``experts_held`` of the model's
    ``num_experts`` routed experts (``first_held`` onwards) and a shared
    expert beside them.

      s = sigmoid(x wg)                      float32, over ALL experts
      S = top-k of (s + bias);  g_i = scale * s_i / sum_{j in S} s_j
      y = sum_{i in S, i held} g_i E_i(x)  +  E_shared(x)

    Every expert is a SwiGLU, ``w_down(silu(w_gate x) * w_up x)``. The
    router, the choice and the gates' normalisation run over the
    published expert count whatever is held: what the absent experts
    would have added is left out, as on one rank of an expert-parallel
    layer before its exchange. ``bias`` corrects the choice only and
    gets no gradient (its balancing rule is a training recipe's, not
    this op's).

    Dispatch: the ``tokens x top_k`` assignments are sorted by expert,
    those bound for absent experts in a trailing group that no product
    touches; one grouped matrix product (``jax.lax.ragged_dot``) a
    projection runs over the stacked weights ``(held, in, out)`` with
    the held experts' counts as group sizes. Shapes are static and
    nothing is dropped: the sorted buffer has a row for every
    assignment, so any imbalance fits. The counters ``moe.*`` of the
    step's metrics go through ``ctx.count``."""
    op_type = OperatorType.OP_ROUTED_EXPERTS

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        n, held = params["num_experts"], params["experts_held"]
        f, fs = params["expert_dim"], params["shared_dim"]
        up, down = {"fans": (e, f)}, {"fans": (f, e)}   # fans per expert
        ws = [WeightSpec("wg", (e, n), dt),
              # drawn once; corrects the choice, never trained
              WeightSpec("bias", (n,), dt, InitializerType.NORMAL,
                         {"stddev": params.get("bias_std", 0.0)},
                         create_grad=False),
              WeightSpec("w_gate", (held, e, f), dt, init_args=up),
              WeightSpec("w_up", (held, e, f), dt, init_args=up),
              WeightSpec("w_down", (held, f, e), dt, init_args=down)]
        if fs:
            ws += [WeightSpec("ws_gate", (e, fs), dt),
                   WeightSpec("ws_up", (e, fs), dt),
                   WeightSpec("ws_down", (fs, e), dt)]
        return ws

    @staticmethod
    def rows_multiplied(tokens: int, params) -> int:
        """Rows of the sorted buffer the grouped products are handed: one
        for every assignment, so none can be dropped."""
        return tokens * params["top_k"]

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        cdt, mdt = x.dtype, compute_dtype(ctx, x.dtype)
        n, held, first = (params["num_experts"], params["experts_held"],
                          params.get("first_held", 0))
        k = params["top_k"]
        xt = x.reshape(-1, x.shape[-1])
        t = xt.shape[0]
        rows = self.rows_multiplied(t, params)
        if events.enabled():
            events.instant("moe.route", layer=name, experts_published=n,
                           experts_held=held, first_held=first, top_k=k,
                           tokens=t, rows_multiplied=rows)

        # the router in float32, as published: a bf16 pass moves scores
        # by 1e-2 and with them the choice of experts
        scores = jax.nn.sigmoid(jnp.dot(
            xt.astype(jnp.float32), weights["wg"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        idx, gates = route(scores, weights["bias"].astype(jnp.float32), k,
                           float(params.get("scale", 1.0)))

        # sort the assignments by held expert; absent ones trail
        local = idx.reshape(-1) - first
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(
            jnp.int32)

        # Rows past the held groups are never multiplied, and on the
        # TPU a grouped product leaves them UNWRITTEN, in its output and
        # in the cotangent its transpose hands back (the CPU's writes
        # zeros; found on the chip, PERF.md section 6, PR 29). Both
        # sides of every product are therefore masked: autodiff carries
        # the two selects into the backward, where they zero what the
        # transposed products leave in those rows.
        multiplied = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

        def grouped(a, w):
            out = jax.lax.ragged_dot(
                jnp.where(multiplied, a, 0).astype(mdt), w.astype(mdt),
                sizes, preferred_element_type=jnp.float32)
            return jnp.where(multiplied, out, 0.0)

        # gathered at the products' operand width: half the bytes of
        # the op's largest buffer, in both directions
        xs = _rows_for(xt.astype(mdt), order, inverse)      # (rows, e)
        act = jax.nn.silu(grouped(xs, weights["w_gate"])) \
            * grouped(xs, weights["w_up"])
        ys = grouped(act, weights["w_down"])
        y = jnp.einsum("tk,tke->te", gates,
                       _permute(ys, inverse, order).reshape(t, k, -1))
        if "ws_gate" in weights:
            g = matmul(xt, weights["ws_gate"], ctx=ctx)
            u = matmul(xt, weights["ws_up"], ctx=ctx)
            y = y + matmul(jax.nn.silu(g) * u, weights["ws_down"], ctx=ctx)

        # what the router bound for this share, read from its choices,
        # against what the grouped products reached: an assignment is
        # reached when the sort put it on a row inside the span that
        # ``sizes`` gives its chosen expert, since that is whose weights
        # the row meets. Dropless by construction, so 0 unless sort,
        # sizes and mask disagree. One compare over (rows, held).
        bound = jnp.sum((idx >= first) & (idx < first + held))
        ends = jnp.cumsum(sizes)
        at = inverse[:, None]
        reached = jnp.sum((group[:, None] == jnp.arange(held))
                          & (at >= ends - sizes) & (at < ends))
        load = sizes.astype(jnp.float32)
        for key, v in (("moe.local_assignments", bound),
                       ("moe.dropped", bound - reached),
                       ("moe.load_max", jnp.max(load)),
                       ("moe.load_mean", jnp.mean(load))):
            ctx.count(key, v.astype(jnp.float32))
        return [y.reshape(x.shape).astype(cdt)]

    def flops(self, params, in_shapes, out_shapes):
        tokens = float(np.prod(in_shapes[0][:-1]))
        e = in_shapes[0][-1]
        share = params["experts_held"] / params["num_experts"]
        routed = 3 * e * params["expert_dim"] * params["top_k"] * share
        return 2.0 * tokens * (e * params["num_experts"] + routed
                               + 3 * e * params["shared_dim"])

    def backward_flops_factor(self):
        return 2.0
