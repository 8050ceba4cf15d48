"""Attention over the keys a learned indexer selects (DeepSeek-V3.2's
sparse attention, arXiv:2512.02556 section 2.1).
:class:`~.nn_ops.MultiHeadAttentionOp` comes here whenever its
parameters name an indexer, and chooses between two paths of the same
equations by its shapes, as it chooses for a layer without one:
:func:`sparse_index_attention`, plain XLA a chunk of queries at a time
(short sequences, and what the other is tested against), and
:func:`sparse_index_attention_flash`, which hands the selection to the
flash kernels as their mask operand.

  I[t, s] = scale * sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t
  S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s];
            equal scores: the lower s first (``jax.lax.top_k``'s order)
  a[t, i, s] = softmax over S_t of q[t, i] . k[s, i // g] / sqrt(d)
  o[t, i] = sum_{S_t} a[t, i, s] v[s, i // g]
  p[t, s] = stop_gradient(mean_i a[t, i, s])
  L_I     = mean_t sum_{S_t} p (log p - log softmax_{S_t} I[t, :])

The selection passes no gradient: ``L_I`` alone moves what ``I`` is made
of, and nothing else reaches it.

On both paths the index scores and the selection are made by the same
code, a chunk of ``q_chunk`` queries at a time (:func:`_index_chunk`).

The XLA path: a chunk of ``q_chunk`` queries ending at position ``e`` reads keys
``0 .. e`` only, so the causal half of the square is not computed, and
runs under ``jax.checkpoint``: a chunk's scores (heads x q_chunk x keys
float32) live while it runs, forward or backward, and the layer's never
do. The k-th largest index score of a row is found WITHOUT sorting the
row: 32 compare-and-count passes over the chunk's scores, one a bit of
the threshold (:func:`kth_largest`); a chunk whose keys are ``topk`` or
fewer selects every causal key and skips that.

The kernel path: the chunks' selections make one (b, s, s) int8 mask,
the three flash kernels attend under it (``kernels/flash_attention``:
scores and probabilities stay in VMEM), a fourth kernel writes ``p``,
and ``L_I`` and its gradient into the indexer come from ``I``, the mask
and ``p``: arrays over (queries, keys) that the heads share may live in
HBM, arrays with a head axis over them are never written. In the loss's
backward that holds for the indexer's own 16 heads too wherever the
shapes take ``kernels/index_scores`` (heads in whole lanes, tiles that
divide the sequence): two kernels make ``I`` again and pull its
cotangent back with a tile's heads of scores in VMEM, the whole sequence
in one causal call each; every other shape pulls it back through
``jax.vjp`` of :func:`index_scores` a chunk at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import index_scores as isk
from ..kernels.flash_attention import (flash_attention_forward,
                                       flash_attention_from_forward,
                                       flash_attention_head_mean)
from ..obs import events
from .registry import checkpointed, kept_by_block

MASKED = -1e9             # what ``MultiHeadAttentionOp``'s plain path uses


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' (-0.0 as
    +0.0, so that equal scores are equal bits)."""
    x = jnp.where(x == 0, jnp.float32(0), x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    i = jnp.where(i < 0, i ^ jnp.int32(0x7fffffff), i)
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def kth_largest(bits, k):
    """The ``k``-th largest entry of each row of ``bits`` (uint32, rows
    on the last axis; ``k``: int32, one a row with a trailing axis of
    1): the largest ``T`` with ``count(bits >= T) >= k``, built from its
    top bit down, one compare-and-count pass over ``bits`` a bit."""
    def one_bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = jnp.sum(bits >= cand, -1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)
    return jax.lax.fori_loop(
        0, 32, one_bit, jnp.zeros(bits.shape[:-1] + (1,), jnp.uint32))


def select(scores, causal, k):
    """``(chosen, ties)``: for each row the ``k`` entries of largest
    score among those ``causal`` allows, equal scores to the lower
    index, as ``jax.lax.top_k`` orders them; ``ties`` counts the rows
    whose threshold value occurs more than once among the allowed.
    ``scores`` (..., rows, keys) float32, ``causal`` (rows, keys) bool,
    ``k`` (rows, 1) int32, at most the row's allowed entries."""
    bits = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))
    t = kth_largest(bits, k)
    above = bits > t
    equal = (bits == t) & causal
    # of the equal ones, the first few by a running count
    wanted = k - jnp.sum(above, -1, keepdims=True, dtype=jnp.int32)
    first = jnp.cumsum(equal.astype(jnp.int32), -1) <= wanted
    ties = jnp.sum(jnp.sum(equal, -1, dtype=jnp.int32) > 1)
    return above | (equal & first), ties


def indexer_inputs(x, weights, mdt):
    """``(qi, ki, wi)``: the indexer's queries (b, s, j, c), its one key
    head (b, s, c) and its head weights (b, s, j), from the layer's
    input DETACHED: nothing the indexer computes reaches the input."""
    x = jax.lax.stop_gradient(x).astype(mdt)

    def proj(pattern, w):
        return jnp.einsum(pattern, x, weights[w].astype(mdt),
                          preferred_element_type=jnp.float32)
    return (proj("ble,ejc->bljc", "wq_idx"), proj("ble,ec->blc", "wk_idx"),
            proj("ble,ej->blj", "w_idx"))


def index_scores(qi, ki, wi, mdt):
    """``I`` for a chunk: ``qi`` (b, q, j, c), ``ki`` (b, k, c), ``wi``
    (b, q, j) -> (b, q, k) float32; the products' operands in ``mdt``."""
    j, c = qi.shape[2], qi.shape[3]
    raw = jnp.einsum("bqjc,bkc->bjqk", qi.astype(mdt), ki.astype(mdt),
                     preferred_element_type=jnp.float32)
    w = jnp.swapaxes(wi.astype(jnp.float32), 1, 2)[..., None]
    return jnp.sum(w * jax.nn.relu(raw), 1) * (j * c) ** -0.5


def _choose(start: int, topk: int, scores):
    """``(chosen, ties)`` for a chunk's index scores (b, rows, keys), its
    rows at positions ``start ..``: :func:`select` among the causal
    keys, or all of them where the chunk sees ``topk`` keys or fewer."""
    rows, keys = scores.shape[-2:]
    at = start + jnp.arange(rows, dtype=jnp.int32)[:, None]
    causal = jnp.arange(keys, dtype=jnp.int32)[None, :] <= at
    if keys <= topk:
        return jnp.broadcast_to(causal, scores.shape), jnp.int32(0)
    with jax.named_scope("dsa.select"):
        return select(jax.lax.stop_gradient(scores), causal,
                      jnp.minimum(at + 1, topk))


def _index_chunk(start: int, topk: int, mdt, qi, ki, wi):
    """``(scores, chosen, ties)`` of one chunk of queries, positions
    ``start ..``, against keys ``0 .. start + rows``: both paths'
    index scores and selection. ``qi`` (b, rows, j, c); ``ki`` (b, keys,
    c); ``wi`` (b, rows, j)."""
    with jax.named_scope("dsa.index"):
        scores = index_scores(qi, ki, wi, mdt)
    return (scores,) + _choose(start, topk, scores)


def _divergence(scores, chosen, p):
    """``sum_t KL(p[t] || softmax over the chosen of scores[t])``."""
    log_i = jax.nn.log_softmax(
        jnp.where(chosen, scores, jnp.float32(MASKED)), axis=-1)
    live = chosen & (p > 0)                           # 0 log 0 = 0
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_i), 0.0))


def _chunk(start: int, topk: int, mdt, q, k, v, qi, ki, wi):
    """One chunk of queries of the XLA path: ``(o, kl, kept, ties)``,
    the chunk's attention output (b, rows, kv, g, d), the sum of its
    rows' divergences, how many (query, key) pairs it kept and how many
    rows tied at the threshold. ``q`` (b, rows, kv, g, d); ``k``, ``v``
    (b, keys, kv, d); the indexer's as :func:`_index_chunk`'s."""
    scores, chosen, ties = _index_chunk(start, topk, mdt, qi, ki, wi)
    with jax.named_scope("dsa.attend"):
        logits = jnp.einsum("bqjgd,bkjd->bjgqk", q.astype(mdt),
                            k.astype(mdt),
                            preferred_element_type=jnp.float32) \
            * (1.0 / math.sqrt(q.shape[-1]))
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None, None], logits, jnp.float32(MASKED)),
            axis=-1)
        o = jnp.einsum("bjgqk,bkjd->bqjgd", probs.astype(mdt),
                       v.astype(mdt), preferred_element_type=jnp.float32)
    with jax.named_scope("dsa.loss"):
        kl = _divergence(scores, chosen,
                         jax.lax.stop_gradient(jnp.mean(probs, (1, 2))))
    return o, kl, jnp.sum(chosen, dtype=jnp.int32), ties


def _chunks(s: int, q_chunk: int):
    return [(lo, min(lo + q_chunk, s)) for lo in range(0, s, q_chunk)]


def selection(qi, ki, wi, topk: int, q_chunk: int, mdt):
    """The (b, s, s) bool mask of the pairs :func:`sparse_index_attention`
    attends, built chunk by chunk as it builds them: for tests and the
    chip validation, which compare it with the reference's."""
    s = qi.shape[1]
    return jnp.concatenate([
        jnp.pad(_choose(lo, topk, index_scores(
            qi[:, lo:hi], ki[:, :hi], wi[:, lo:hi], mdt))[0],
            ((0, 0), (0, 0), (0, s - hi)))
        for lo, hi in _chunks(s, q_chunk)], 1)


def sparse_index_attention(q, k, v, qi, ki, wi, topk: int, q_chunk: int,
                           mdt, *, layer=None):
    """``(o, loss, kept, ties)`` of the module's equations for whole
    sequences: ``q`` (b, s, h, d) and ``k``, ``v`` (b, s, kv, d) after
    norms and rotary embedding, the indexer's ``qi`` (b, s, j, c), ``ki``
    (b, s, c), ``wi`` (b, s, j). ``o`` (b, s, h, d) float32; ``loss`` is
    ``L_I``, the mean over sequences and positions; ``kept`` and
    ``ties`` are float32 counts over the batch.

    The chunks run one after another, forward and backward: each
    chunk's operands pass an optimization barrier together with the
    chunk before's output, and the barrier's transpose holds a chunk's
    backward (which ``jax.checkpoint`` starts from its cotangents) until
    the next chunk's is done. Left to itself XLA's scheduler ran the
    sixteen independent chunks' recomputations side by side, and a
    step's temporaries read 10.4 GiB."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, h // kv, d)
    outs, kl, kept, ties = [], 0.0, 0, 0
    for lo, hi in _chunks(s, q_chunk):
        args = (q[:, lo:hi], k[:, :hi], v[:, :hi], qi[:, lo:hi], ki[:, :hi],
                wi[:, lo:hi])
        if outs:
            outs[-1], args = jax.lax.optimization_barrier((outs[-1], args))
        o, kl_c, kept_c, ties_c = checkpointed(
            lambda *a, _lo=lo: _chunk(_lo, topk, mdt, *a), site="dsa.chunk",
            layer=layer, part=lo)(*args)
        outs.append(o)
        kl, kept, ties = kl + kl_c, kept + kept_c, ties + ties_c
    o = jnp.concatenate(outs, 1).reshape(b, s, h, v.shape[-1])
    return (o, kl / (b * s), jnp.asarray(kept, jnp.float32),
            jnp.asarray(ties, jnp.float32))


# ----------------------------------------------------------------------
# the kernel path
# ----------------------------------------------------------------------
def _scores_and_mask(qi, ki, wi, topk: int, q_chunk: int, mdt):
    """``(I, mask, kept, ties)`` for whole sequences, with no gradient:
    the index scores (b, s, s) float32 and the selection (b, s, s) int8,
    chunk by chunk as the XLA path makes them (zeros past a chunk's
    end), each chunk written into the two arrays in place and one chunk
    after another (an optimization barrier between them: a chunk's 16
    heads of scores are 268 MB at 8,192 keys)."""
    qi, ki, wi = map(jax.lax.stop_gradient, (qi, ki, wi))
    b, s = qi.shape[:2]
    out = (jnp.zeros((b, s, s), jnp.float32), jnp.zeros((b, s, s), jnp.int8))
    kept, ties = 0, 0
    for lo, hi in _chunks(s, q_chunk):
        args = (qi[:, lo:hi], ki[:, :hi], wi[:, lo:hi])
        if lo:
            out, args = jax.lax.optimization_barrier((out, args))
        scores, chosen, ties_c = _index_chunk(lo, topk, mdt, *args)
        out = tuple(whole.at[:, lo:hi, :hi].set(part.astype(whole.dtype))
                    for whole, part in zip(out, (scores, chosen)))
        kept, ties = kept + jnp.sum(chosen, dtype=jnp.int32), ties + ties_c
    return out + (kept, ties)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _index_loss(qi, ki, wi, scores, mask, q, k, lse, q_chunk, mdt):
    """``sum_t KL(p[t] || softmax_{S_t} I[t])`` on the kernel path, as a
    function of the indexer's ``qi``, ``ki``, ``wi`` alone. Its value
    comes from the ``scores`` the selection was made from (no product is
    run for it) and ``p``, the heads' mean probability, which the fourth
    flash kernel writes from ``q``, ``k`` (b, h, s, d), the forward's
    ``lse`` and the mask. Its backward keeps neither ``scores`` nor
    ``p``: it runs that kernel again, then the index products once more
    and their transposes: through ``kernels/index_scores`` over the
    whole sequence where the shapes take them (:func:`_index_kernels`),
    else a chunk of queries at a time, as the XLA path's chunks do
    under their ``jax.checkpoint``."""
    with jax.named_scope("dsa.loss"):
        p = flash_attention_head_mean(q, k, lse, mask, causal=True)
        return _divergence(scores, mask != 0, p)


def _index_loss_fwd(qi, ki, wi, scores, mask, q, k, lse, q_chunk, mdt):
    return (_index_loss(qi, ki, wi, scores, mask, q, k, lse, q_chunk, mdt),
            (qi, ki, wi, mask, q, k, lse))


def _index_kernels(qi, mdt) -> bool:
    """Whether the loss's backward makes the index scores and pulls
    their cotangent back through ``kernels/index_scores``, the whole
    sequence in one causal call each: told by the shapes."""
    _, s, j, c = qi.shape
    return isk.takes_kernel(s, s, j, c, mdt)


def _index_loss_bwd(q_chunk, mdt, res, g):
    # nothing below starts before the cotangent is there: what it reads
    # are residuals, which XLA's scheduler would otherwise be free to
    # turn into every layer's ``p`` (268 MB each at 8,192 positions) at
    # the start of the backward pass
    g, res = jax.lax.optimization_barrier((g, res))
    qi, ki, wi, mask, q, k, lse = res
    s = qi.shape[1]
    with jax.named_scope("dsa.loss"):
        p = flash_attention_head_mean(q, k, lse, mask, causal=True)
    rest = (jnp.zeros(mask.shape, jnp.float32),
            np.zeros(mask.shape, jax.dtypes.float0), jnp.zeros_like(q),
            jnp.zeros_like(k), jnp.zeros_like(lse))
    if _index_kernels(qi, mdt):
        # the kernels keep a tile's heads of scores in VMEM, so the
        # whole sequence's ``I``, (s, s) float32 like ``p``, is all that
        # a layer holds, and each kernel is called once; the loss's
        # passes between them still go chunk by chunk, over the keys up
        # to the chunk's end (half the square), and write the scores'
        # cotangent where the chunk's scores were (past the diagonal the
        # backward kernel reads nothing)
        with jax.named_scope("dsa.index"):
            d_scores = isk.index_scores_fwd(qi, ki, wi, mdt, causal=True)
        with jax.named_scope("dsa.loss"):
            for lo, hi in _chunks(s, q_chunk):
                d_scores = d_scores.at[:, lo:hi, :hi].set(
                    g * jax.grad(_divergence)(
                        d_scores[:, lo:hi, :hi], mask[:, lo:hi, :hi] != 0,
                        p[:, lo:hi, :hi]))
        with jax.named_scope("dsa.index"):
            return isk.index_scores_bwd(qi, ki, wi, d_scores, mdt,
                                        causal=True) + rest
    dqi, dwi, dki = [], [], jnp.zeros(ki.shape, jnp.float32)
    for lo, hi in _chunks(s, q_chunk):
        args = (qi[:, lo:hi], ki[:, :hi], wi[:, lo:hi], mask[:, lo:hi, :hi],
                p[:, lo:hi, :hi])
        if dqi:      # one chunk's scores at a time, as the forward's
            dki, args = jax.lax.optimization_barrier((dki, args))
        *idx, chosen, p_c = args
        with jax.named_scope("dsa.index"):
            scores, pull = jax.vjp(
                lambda *a: index_scores(*a, mdt), *idx)
        with jax.named_scope("dsa.loss"):
            d_scores = g * jax.grad(_divergence)(scores, chosen != 0, p_c)
        with jax.named_scope("dsa.index"):
            dqi_c, dki_c, dwi_c = pull(d_scores)
        dqi.append(dqi_c)
        dwi.append(dwi_c)
        dki = dki.at[:, :hi].add(dki_c.astype(jnp.float32))
    return (jnp.concatenate(dqi, 1), dki.astype(ki.dtype),
            jnp.concatenate(dwi, 1)) + rest


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def sparse_index_attention_flash(q, k, v, qi, ki, wi, topk: int,
                                 q_chunk: int, mdt, *,
                                 qk_heads_first: bool = False, layer=None):
    """:func:`sparse_index_attention` through the flash kernels: the
    same arguments, the same ``(o, loss, kept, ties)``. ``o`` is in
    ``mdt``, the kernels' output type. ``qk_heads_first``: ``q`` and
    ``k`` come as the kernels take them, (b, h, s, d) and (b, kvh, s, d)
    in ``mdt`` (``kernels/qk_norm_rope``), and only ``v`` is turned
    here. Nothing is repeated: the kernels read k's and v's ``kvh``
    heads in place, a group of ``h / kvh`` query heads each.

    What a rematerialised block around the layer keeps is named here
    (``KEPT_BY_BLOCK``): the selection's mask (int8), the forward
    kernel's output and its log-sum-exp. The block's second run then
    makes q, k, v and the indexer's inputs again and nothing else: no
    index product, no selection and no forward kernel; the backward runs
    the dq and dkv kernels, the head-mean kernel and the index products
    (the two index-score kernels, or each chunk's on XLA). ``layer``
    names the layer on the ``dsa.index_kernel`` instant of a traced
    run. Outside such a block the names do nothing."""
    b, s = q.shape[0], q.shape[2 if qk_heads_first else 1]
    if events.enabled():
        kernels = _index_kernels(qi, mdt)
        events.instant(
            "dsa.index_kernel", layer=layer, impl="kernel" if kernels
            else "plain", heads=qi.shape[2], head_dim=qi.shape[3],
            q_chunk=q_chunk, chunks=-(-s // q_chunk),
            **({f"{k}_tile": isk.tiles(k, s, s, *qi.shape[2:], mdt)
                for k in ("fwd", "bwd")} if kernels else {}))
    scores, mask, kept, ties = _scores_and_mask(qi, ki, wi, topk, q_chunk,
                                                mdt)
    mask = kept_by_block(mask)
    with jax.named_scope("dsa.attend"):
        def heads_first(x):      # (b, s, heads, d) -> (b, heads, s, d)
            return jnp.swapaxes(x, 1, 2).astype(mdt)
        qh, kh = (q, k) if qk_heads_first \
            else (heads_first(q), heads_first(k))
        vh = heads_first(v)
        o, lse = flash_attention_forward(qh, kh, vh, mask,
                                               causal=True)
        o = kept_by_block(o)
        lse = kept_by_block(lse)
        o = flash_attention_from_forward(qh, kh, vh, mask, o, lse,
                                               causal=True)
        o = jnp.swapaxes(o, 1, 2)
    kl = _index_loss(qi, ki, wi, scores, mask,
                     *map(jax.lax.stop_gradient, (qh, kh, lse)), q_chunk,
                     mdt)
    # the loss is there when the output is, and ``I`` and ``p`` are
    # dead by then (nothing else asks for it before the step's end);
    # the barrier's transpose hands the loss's backward its cotangent
    # with the output's: each layer's in its turn, not all four at once
    o, kl = jax.lax.optimization_barrier((o, kl))
    return (o, kl / (b * s), jnp.asarray(kept, jnp.float32),
            jnp.asarray(ties, jnp.float32))
