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

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import WeightSpec
from ..ffconst import DataType, InitializerType, OperatorType
from ..kernels import moe_token_sum as mts
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
def _from_rows(src, at):
    """``src[at]`` for a ``src`` that holds ``len(src)`` rows of the
    sort and assignments' places ``at`` among them: an assignment whose
    row is not one of them reads zeros."""
    b = src.shape[0]
    there = ((at >= 0) & (at < b)).reshape((-1,) + (1,) * (src.ndim - 1))
    return jnp.where(there, src[jnp.clip(at, 0, b - 1)], 0)


def _of_each_choice(src, at, k: int):
    """``_from_rows`` for each of a token's ``k`` assignments in turn,
    ``k`` arrays of a row a token: the plain way back to the tokens,
    which WRITES ``k`` arrays of ``tokens`` rows, zeros wherever a
    choice has no row here, for the sum that follows to read back
    (where ``kernels/moe_token_sum.py`` takes the shapes it walks the
    rows instead and writes the sum alone). One gather of ``tokens x
    k`` rows reshaped to ``(tokens, k, width)`` is a relayout on the TPU
    wherever ``k`` does not fill the tile's 8 sublanes: 8 of cell 4's
    55 ms (PERF.md section 6, PR 34)."""
    at = at.reshape(-1, k)
    return [_from_rows(src, at[:, j]) for j in range(k)]


@jax.custom_vjp
def _rows_for(x, order, at, inside):
    """Row ``order[r] // k`` of ``x`` for each sorted assignment in
    ``order`` (``k = len(at) // len(x)`` assignments a token). Its
    transpose gathers too: the ``k`` rows of a token sit ``at`` their
    places and are summed, so no scatter is emitted in either
    direction. With ``inside`` (the held groups' rows, each group's
    count) that sum is ``moe_token_sum``'s walk over those rows (each
    read once, the sum written once); with None,
    ``_of_each_choice``'s ``k`` arrays and their sum."""
    return x[order // (at.shape[0] // x.shape[0])]


def _rows_for_fwd(x, order, at, inside):
    return (_rows_for(x, order, at, inside),
            (at, None if inside is None else (order, inside), x.shape[0]))


def _rows_for_bwd(res, g):
    at, sort, t = res
    k = at.shape[0] // t
    if sort is not None:
        summed = mts.token_sum(g, *sort, t, k)
    else:
        summed = sum(rows.astype(jnp.float32)
                     for rows in _of_each_choice(g, at, k))
    return summed.astype(g.dtype), None, None, None


_rows_for.defvjp(_rows_for_fwd, _rows_for_bwd)


@jax.custom_vjp
def _combine(ys, gates, order, at, inside):
    """``sum_k gates[t, k] * ys[row of assignment (t, k)]``: the sorted
    rows ``ys`` back at their tokens, weighted, in float32. With
    ``inside`` by ``moe_token_sum`` (each of the held groups' rows of
    ``ys``, ``inside`` of them each, read once, times its gate, onto
    its token's row; ``tokens`` rows written); with None,
    ``_of_each_choice`` writes ``top_k`` arrays of ``tokens`` rows and
    the sum reads them back.
    The transpose stays in the sorted domain either way: a row's
    cotangent is its token's times its gate, a gate's the product of
    its row with its token's cotangent, so the backward gathers
    ``len(ys)`` rows, keeps ``ys`` and neither writes nor keeps
    ``tokens x top_k`` of them."""
    k = gates.shape[1]
    if inside is not None:
        return mts.token_sum(ys, order, inside, gates.shape[0], k, gates)
    return sum(gates[:, j:j + 1] * rows
               for j, rows in enumerate(_of_each_choice(ys, at, k)))


def _combine_fwd(ys, gates, order, at, inside):
    return _combine(ys, gates, order, at, inside), (ys, gates, order, at)


def _combine_bwd(res, g):
    ys, gates, order, at = res
    gs = g[order // gates.shape[1]]        # each row's token's cotangent
    d_ys = gates.reshape(-1)[order][:, None] * gs
    d_gates = _from_rows(jnp.sum(ys * gs, axis=-1), at)
    return (d_ys.astype(ys.dtype),
            d_gates.reshape(gates.shape).astype(gates.dtype), None, None,
            None)


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _chunk(plan, mdt, c, floats, ints):
    """The held experts' part of the output from rows ``c * budget``
    onwards of the sort, ``budget`` of them: the row gather, the
    grouped products (three of a SwiGLU, ``floats`` ending in ``w_gate,
    w_up, w_down``; two of a ReLU-squared expert, which has no gate
    matrix: ``w_up, w_down``) and the activation over those rows, then
    each token's sum of what they hold for it. ``plan`` is ``(budget,
    kernel)``: with ``kernel`` that sum and the row gather's transpose
    are ``kernels/moe_token_sum.py``'s. Jitted and inlined for the
    trace cache alone: a model's layers and a layer's loops trace and
    differentiate this body once a shape, not once a use (a step's trace
    is set-up time, twice in a benchmark run)."""
    budget, kernel = plan
    xm, gates, *ws = floats
    order, inverse, sizes = ints
    lo, ends = c * budget, jnp.cumsum(sizes)
    # each group's rows among these
    inside = (jnp.clip(ends, lo, lo + budget)
              - jnp.clip(ends - sizes, lo, lo + budget))
    # Rows past the held groups are never multiplied, and on the TPU a
    # grouped product leaves them UNWRITTEN, in its output and in the
    # cotangent its transpose hands back (the CPU's writes zeros; found
    # on the chip, PERF.md section 6, PR 29). Both sides of every
    # product are therefore masked: autodiff carries the two selects
    # into the backward, where they zero what the transposed products
    # leave in those rows.
    multiplied = (jnp.arange(budget) < jnp.sum(inside))[:, None]

    def grouped(a, w):
        out = jax.lax.ragged_dot(
            jnp.where(multiplied, a, 0).astype(mdt), w, inside,
            preferred_element_type=jnp.float32)
        return jnp.where(multiplied, out, 0.0)

    mine = jax.lax.dynamic_slice(order, (lo,), (budget,))
    at = inverse - lo
    # the kernel walks the held groups' rows alone (the others are
    # zeros on both sides, and nobody's)
    held = inside if kernel else None
    # gathered at the products' operand width: half the bytes of the
    # op's largest buffer, in both directions
    xs = _rows_for(xm, mine, at, held)                      # (budget, e)
    if len(ws) == 3:
        act = jax.nn.silu(grouped(xs, ws[0])) * grouped(xs, ws[1])
    else:
        act = jnp.square(jax.nn.relu(grouped(xs, ws[0])))
    return _combine(grouped(act, ws[-1]), gates, mine, at, held)


def _further_chunks(plan, sizes, body, start):
    """``body(c, state)`` for every chunk of ``budget`` rows past the
    first that holds a live row: none in the common step, and the
    device decides by itself."""
    budget = plan[0]
    return jax.lax.fori_loop(1, (jnp.sum(sizes) + budget - 1) // budget,
                             body, start)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_domain(plan, mdt, floats, ints):
    """``(y, chunks)``: the held experts' part of the output over the
    live rows of the sort, ``budget`` rows at a time, and how many such
    chunks ran. The first holds every live row unless the router sent
    this share more than the budget; the step that overflows runs the
    same body again over the next rows, in a loop whose trip count the
    device reads from the group sizes, so nothing is dropped and no
    buffer is ever larger than the budget's. Differentiated, the first
    chunk keeps what autodiff keeps of it; the further ones keep
    nothing and are computed again in the backward."""
    return _further_outputs(
        plan, mdt, floats, ints,
        _chunk(plan, mdt, jnp.int32(0), floats, ints))


def _sorted_domain_fwd(plan, mdt, floats, ints):
    y, back = jax.vjp(lambda *f: _chunk(plan, mdt, jnp.int32(0), f, ints),
                      *floats)
    return _further_outputs(plan, mdt, floats, ints, y), (back, floats,
                                                          ints)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _further_outputs(plan, mdt, floats, ints, y):
    return _further_chunks(
        plan, ints[2],
        lambda c, s: (s[0] + _chunk(plan, mdt, c, floats, ints), s[1] + 1),
        (y, jnp.int32(1)))


def _sorted_domain_bwd(plan, mdt, res, g):
    back, floats, ints = res
    return _further_cotangents(plan, mdt, floats, ints, g[0],
                               back(g[0])), None


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _further_cotangents(plan, mdt, floats, ints, g, first):
    """The first chunk's cotangents ``first``, each the start of a loop
    that adds the further chunks' in place. Two loops, one for the
    activations' (input rows and gates, wanted by the layer before) and
    one for the experts' weights', which only the optimizer wants: XLA
    sinks the weights' products to their update at the end of the step,
    and with them a loop of their own; through one loop with the
    activations' they are written here and lie about until then, 150
    MB a layer (PERF.md section 6, PR 34)."""
    def further(part):
        def more(c, sofar):
            def chunk(*mine):
                full = list(floats)
                full[part] = mine
                return _chunk(plan, mdt, c, tuple(full), ints)
            return jax.tree.map(jnp.add, sofar,
                                jax.vjp(chunk, *floats[part])[1](g))
        return _further_chunks(plan, ints[2], more, first[part])

    return further(slice(0, 2)) + further(slice(2, len(floats)))


_sorted_domain.defvjp(_sorted_domain_fwd, _sorted_domain_bwd)


def _named(idx, experts: int):
    """(.., top_k, experts) bool: which expert each choice names."""
    return idx[..., None] == jnp.arange(experts, dtype=idx.dtype)


@jax.custom_vjp
def own_scores(scores, idx):
    """``scores[t, idx[t, j]]``, (tokens, top_k): each chosen expert's
    own score, by ONE compare of the choices with the experts' numbers
    and a sum along the experts (the one entry that is the choice's and
    zeros: exact), where ``jnp.take_along_axis`` is a gather of ``tokens
    x top_k`` single numbers, which this chip walks one at a time: 0.94
    ms for 4,096 x 22 of 512 against 0.08, and 1.75 against 0.09 with
    the transposes (``examples/tpu_time_router_product.py --part
    choice``, PERF.md section 6, PR 67). The transpose likewise: each
    expert's cotangent is the sum over the choices that named it, no
    scatter; it keeps ``idx`` alone."""
    return jnp.sum(jnp.where(_named(idx, scores.shape[-1]),
                             scores[..., None, :], 0), axis=-1)


def _own_scores_fwd(scores, idx):
    # (the experts' count rides on an empty array: a residual is arrays)
    return own_scores(scores, idx), (idx, jnp.zeros((0, scores.shape[-1]),
                                                    scores.dtype))


def _own_scores_bwd(kept, g):
    idx, like = kept
    return (jnp.sum(jnp.where(_named(idx, like.shape[-1]),
                              g[..., None].astype(like.dtype), 0),
                    axis=-2), None)


own_scores.defvjp(_own_scores_fwd, _own_scores_bwd)


def group_sizes(group, held: int):
    """How many of ``group``'s entries name each of the ``held`` groups,
    (held,) int32: a compare with the groups' numbers and a count
    (``jnp.bincount`` is a scatter-add of every assignment, which this
    chip walks one at a time: 0.79 ms for 90,112 of them against
    0.001)."""
    return jnp.sum(group[:, None] == jnp.arange(held, dtype=group.dtype),
                   axis=0, dtype=jnp.int32)


def route(logits, bias, top_k: int, scale: float, scoring: str = "sigmoid",
          loads: bool = False):
    """``(idx, gates)``, both (tokens, top_k), from the router's
    ``logits`` (tokens, experts) float32, by one of two score functions:

    ``"sigmoid"``  bias-corrected top-k (DeepSeek-V3's ``noaux_tc`` with
                   one group): ``s = sigmoid(logits)``, the choice is
                   over ``s + bias``, the gates are the chosen experts'
                   own scores.
    ``"softmax"``  ``s = softmax(logits)`` over all experts (the
                   Qwen3-MoE family): the choice is over ``s`` alone and
                   ``bias`` is not read.

    Either way the gates are normalised over all ``top_k`` chosen and
    scaled. With ``loads`` a third result, (experts,): the tokens that
    chose each expert, counted as those whose biased score reaches
    their token's ``top_k``-th (one compare over (tokens, experts); two
    experts level at the cut both count)."""
    if scoring == "softmax":
        biased = scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(bias)
    least, idx = jax.lax.top_k(biased, top_k)
    chosen = own_scores(scores, idx)
    gates = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    if loads:
        return idx, gates, jnp.sum(biased >= least[:, -1:], axis=0)
    return idx, gates


@register
class RoutedExpertsOp(OpDef):
    """One mixture-of-experts feed-forward layer, sparse and dropless,
    for a device that holds ``experts_held`` of the model's
    ``num_experts`` routed experts (``first_held`` onwards) and a shared
    expert beside them.

      s = sigmoid(x wg)  or  softmax(x wg)   float32, over ALL experts
      S = top-k of (s + bias);  g_i = scale * s_i / sum_{j in S} s_j
      y = sum_{i in S, i held} g_i E_i(x)  +  E_shared(x)

    With ``shared_gate`` in the parameters the shared expert's output is
    multiplied by ``sigmoid(x . w_s)``, one scalar a token from a
    weight of its own (``ws_scalar``, hidden x 1; the Qwen2-MoE / Qwen3-
    Next family's). With ``choice_bias`` false (softmax scores, whose
    choice reads none) the weight ``bias`` is not in the op's list.

    ``scoring`` in the parameters says which score function
    (:func:`route`): ``"sigmoid"`` where it is absent, the DeepSeek-V3
    family's, whose choice a bias corrects; ``"softmax"``, the Qwen3-MoE
    family's, has no choice bias: the weight ``bias`` is still in the
    op's list, all zeros (``bias_std`` 0) and never read.

    Every expert is a SwiGLU, ``w_down(silu(w_gate x) * w_up x)``, or,
    with ``activation`` ``"relu2"`` in the parameters, ``w_down relu(w_up
    x)^2`` (two matrices: no ``w_gate`` and no ``ws_gate`` in the op's
    list). With ``latent`` l in the parameters the routed experts work
    in a latent: they read ``u = x w_latent_in`` (l wide, their matrices
    l x f and f x l) and their weighted sum goes back through
    ``w_latent_out`` (l x hidden),

      y = (sum_{i in S, i held} g_i E_i(x w_latent_in)) w_latent_out
          +  E_shared(x)

    while the router's scores and the shared expert read ``x`` itself;
    the dispatch, its budget and ``kernels/moe_token_sum.py`` then move
    rows l wide. The
    router, the choice and the gates' normalisation run over the
    published expert count whatever is held: what the absent experts
    would have added is left out, as on one rank of an expert-parallel
    layer before its exchange. ``bias`` corrects the choice only and
    the loss gives it no gradient. With ``bias_step`` u > 0 in the
    parameters it follows the family's balancing rule (Wang et al.,
    "Auxiliary-loss-free load balancing", DeepSeek-V3's): after each
    training step ``bias_i -= u * sign(c_i - mean(c))``, ``c_i`` the
    assignments the step's choice gave expert i of ALL the published
    ones. The op hands ``c - mean(c)`` over as the bias's gradient, by a
    term of the loss whose value is zero, and the weight's
    ``sign_step`` has the train step move it by the sign
    (``Executor._apply_update``): so the rule crosses rematerialised
    blocks and sums over micro-batches and data-parallel shards as a
    gradient does.

    Dispatch: the ``tokens x top_k`` assignments are sorted by expert,
    those bound for absent experts in a trailing group that no product
    touches; one grouped matrix product (``jax.lax.ragged_dot``) a
    projection runs over the stacked weights ``(held, in, out)`` with
    the held experts' counts as group sizes. Shapes are static: the
    sorted domain (the row gather, the products with their masks, the
    activation, and the transposes of all of them) runs over
    ``rows_multiplied`` rows of the sort from its start, a budget read
    from the op's shapes, with the live rows leading and the group
    sizes beside them: what an expert-parallel exchange delivers.
    Nothing is dropped: the step whose router sends this share more
    than the budget runs the same body again over the next rows
    (``_sorted_domain``: a loop whose trip count the device reads from
    the group sizes, rematerialised in the backward), and where the
    budget is every row there is no loop. The counters ``moe.*`` of the
    step's metrics go through ``ctx.count``."""
    op_type = OperatorType.OP_ROUTED_EXPERTS

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        n, held = params["num_experts"], params["experts_held"]
        f, fs = params["expert_dim"], params["shared_dim"]
        gated = params.get("activation", "swiglu") == "swiglu"
        latent = params.get("latent", 0)
        e_in = latent or e                  # what a routed expert reads
        up, down = {"fans": (e_in, f)}, {"fans": (f, e_in)}     # an expert's
        # ``router_repeats`` r: the router's first n / r columns are drawn
        # and repeated r times, so a token's scores are alike in every
        # share of n / r experts
        r = params.get("router_repeats", 1)
        ws = [WeightSpec("wg", (e, n), dt,
                         init_args={"column_repeats": r} if r > 1 else {})]
        if params.get("choice_bias", True):
            # drawn once; corrects the choice; moved by the balancing
            # rule where the layer has a ``bias_step``, else never
            ws.append(WeightSpec("bias", (n,), dt, InitializerType.NORMAL,
                                 {"stddev": params.get("bias_std", 0.0)},
                                 create_grad=False,
                                 sign_step=params.get("bias_step", 0.0)))
        if latent:
            ws += [WeightSpec("w_latent_in", (e, latent), dt),
                   WeightSpec("w_latent_out", (latent, e), dt)]
        if gated:
            ws.append(WeightSpec("w_gate", (held, e_in, f), dt,
                                 init_args=up))
        ws += [WeightSpec("w_up", (held, e_in, f), dt, init_args=up),
               WeightSpec("w_down", (held, f, e_in), dt, init_args=down)]
        if fs:
            if gated:
                ws.append(WeightSpec("ws_gate", (e, fs), dt))
            ws += [WeightSpec("ws_up", (e, fs), dt),
                   WeightSpec("ws_down", (fs, e), dt)]
            if params.get("shared_gate"):
                ws.append(WeightSpec("ws_scalar", (e, 1), dt))
        return ws

    @staticmethod
    def rows_multiplied(tokens: int, params) -> int:
        """The row budget: how many rows of the sort, from its start,
        the grouped products are handed. Twice the share of the ``tokens
        x top_k`` assignments that a uniform router sends the held
        experts, rounded up to 512 rows, and at most all of them (with
        half the experts or more held it IS all of them). ``rows_factor``
        (default 2) is that "twice": the smaller the share held, the
        more its load swings about the uniform one (8 of 256 experts
        were sent 3.1 to 3.4 times theirs at two seeds of five), and a
        layer that overflows at its seed's weights loops in every step."""
        rows = tokens * params["top_k"]
        share = -(-params.get("rows_factor", 2) * rows
                  * params["experts_held"] // params["num_experts"])
        return min(rows, -(-share // 512) * 512)

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        cdt, mdt = x.dtype, compute_dtype(ctx, x.dtype)
        n, held, first = (params["num_experts"], params["experts_held"],
                          params.get("first_held", 0))
        k = params["top_k"]
        xt = x.reshape(-1, x.shape[-1])
        t = xt.shape[0]
        rows, budget = t * k, self.rows_multiplied(t, params)
        latent = params.get("latent", 0)
        width = latent or x.shape[-1]       # of the rows the dispatch moves
        expert_weights = [w for w in ("w_gate", "w_up", "w_down")
                          if w in weights]
        # the way back to the tokens: the kernel where the shapes take
        # it, on one device (under a mesh the sort is over the global
        # batch, which no kernel call of a shard's own could walk)
        mesh = getattr(ctx, "mesh", None)
        kernel = ((mesh is None or mesh.size == 1) and all(
            mts.takes_kernel(t, width, k, budget, held, dt)
            for dt in (jnp.float32, mdt)))
        if events.enabled():
            events.instant("moe.route", layer=name, experts_published=n,
                           experts_held=held, first_held=first, top_k=k,
                           tokens=t, rows_budget=budget,
                           rows_multiplied=budget,
                           token_sum="kernel" if kernel else "plain",
                           latent=latent,
                           activation=params.get("activation", "swiglu"),
                           **({"shared_gate": True}
                              if "ws_scalar" in weights else {}),
                           **{key: params[key]
                              for key in ("router_repeats", "bias_step")
                              if key in params})
            if kernel:
                # ``_chunk`` is traced once a shape, so its calls are
                # noted here, where the layer has a name: the forward's
                # sum and the row gather's transpose
                for use, dt in (("combine", jnp.float32),
                                ("rows_for_bwd", mdt)):
                    tile = mts.tile_tokens(t, width, dt)
                    events.instant(
                        "moe.kernel", use=use, layer=name, tile=tile,
                        rows=budget, vmem_bytes=mts.vmem_bytes(
                            tile, width, dt))

        # the router in float32, as published: a bf16 pass moves scores
        # by 1e-2 and with them the choice of experts
        with jax.named_scope("moe.route"):
            logits = jnp.dot(
                xt.astype(jnp.float32), weights["wg"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            bias = weights["bias"].astype(jnp.float32) \
                if "bias" in weights else None  # softmax scores read none
            balanced = bool(params.get("bias_step")) and ctx.training
            idx, gates, *loads = route(logits, bias, k,
                                       float(params.get("scale", 1.0)),
                                       params.get("scoring", "sigmoid"),
                                       loads=balanced)
            if balanced:
                # the balancing rule's gradient: every published
                # expert's assignments over the uniform share, on a term
                # that is zero whatever the bias
                ctx.aux_losses.append(jnp.sum(
                    (bias - jax.lax.stop_gradient(bias))
                    * (loads[0] - rows / n)))

            # sort the assignments by held expert; absent ones trail
            local = idx.reshape(-1) - first
            group = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            sizes = group_sizes(group, held)

        def routed(xr):
            """The held experts' weighted sum over rows as wide as
            ``xr``'s, and how many chunks of the budget ran."""
            floats = (xr.astype(mdt), gates) + tuple(
                weights[w].astype(mdt) for w in expert_weights)
            if budget == rows:
                return _chunk((rows, kernel), mdt, jnp.int32(0), floats,
                              (order, inverse, sizes)), None
            # whole chunks to slice: the padding sorts last, is never
            # live and reads token 0
            return _sorted_domain(
                (budget, kernel), mdt, floats,
                (jnp.pad(order, (0, -rows % budget)), inverse, sizes))

        if latent:
            # down to the latent, the experts there, and back: the
            # router above and the shared expert below read x itself
            with jax.named_scope("moe.latent"):
                y, chunks = routed(matmul(xt, weights["w_latent_in"],
                                          ctx=ctx))
                y = matmul(y, weights["w_latent_out"], ctx=ctx)
        else:
            y, chunks = routed(xt)
        if "ws_up" in weights:
            with jax.named_scope("moe.shared"):
                if "ws_gate" in weights:
                    g = matmul(xt, weights["ws_gate"], ctx=ctx)
                    u = matmul(xt, weights["ws_up"], ctx=ctx)
                    act = jax.nn.silu(g) * u
                else:
                    act = jnp.square(jax.nn.relu(
                        matmul(xt, weights["ws_up"], ctx=ctx)))
                shared = matmul(act, weights["ws_down"], ctx=ctx)
                if "ws_scalar" in weights:
                    # one scalar a token, float32 as the router's scores
                    opened = jax.nn.sigmoid(jnp.dot(
                        xt.astype(jnp.float32),
                        weights["ws_scalar"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST))
                    shared = shared * opened
                    ctx.count("moe.shared_gate_mean", jnp.mean(opened))
                y = y + shared

        # what the router bound for this share, read from its choices,
        # against what the grouped products reached: an assignment is
        # reached when the sort put it on a row inside the span that
        # ``sizes`` gives its chosen expert, since that is whose weights
        # the row meets, and the row lies in a chunk that ran. Dropless
        # by construction, so 0 unless sort, sizes, mask and the loop
        # over chunks disagree. One compare over (rows, held).
        bound = jnp.sum((idx >= first) & (idx < first + held))
        ends = jnp.cumsum(sizes)
        at = inverse[:, None]
        met = ((group[:, None] == jnp.arange(held))
               & (at >= ends - sizes) & (at < ends))
        if chunks is not None:
            met &= at < chunks * budget
        load = sizes.astype(jnp.float32)
        for key, v in (("moe.local_assignments", bound),
                       ("moe.dropped", bound - jnp.sum(met)),
                       ("moe.overflow", 0 if chunks is None else chunks > 1),
                       ("moe.load_max", jnp.max(load)),
                       ("moe.load_mean", jnp.mean(load))):
            ctx.count(key, jnp.asarray(v, jnp.float32))
        return [y.reshape(x.shape).astype(cdt)]

    def flops(self, params, in_shapes, out_shapes):
        tokens = float(np.prod(in_shapes[0][:-1]))
        e = in_shapes[0][-1]
        share = params["experts_held"] / params["num_experts"]
        latent = params.get("latent", 0)
        # matrices an expert: a SwiGLU's three, a ReLU-squared one's two
        mats = 3 if params.get("activation", "swiglu") == "swiglu" else 2
        routed = mats * (latent or e) * params["expert_dim"] \
            * params["top_k"] * share
        return 2.0 * tokens * (e * params["num_experts"] + routed
                               + 2 * e * latent
                               + mats * e * params["shared_dim"])

    def backward_flops_factor(self):
        return 2.0
