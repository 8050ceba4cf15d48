"""Plain reference for decoders whose every layer is grouped-query
attention over the keys a learned indexer selects, then softmax-routed
experts (``model_type: KeyeVL2``'s language model; the indexer is
DeepSeek-V3.2's, arXiv:2512.02556 section 2.1, its loss section 2.1.1):
the forward pass and the loss in straightforward float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``. No kernel, no
threshold search, no grouped product, no bf16 operand: the selection is
``jax.lax.top_k`` over a query's index scores, attention the explicit
s x s softmax over the selected keys in blocks of query rows (one after
another through ``jax.lax.map``) so that 8192 positions fit beside a
training step's state, and the experts a
Python loop over the experts held, each applied to every token under its
gate. ISSUE 48 states the equations (``sg`` is stop-gradient, one
sequence of S positions):

  x   = RMSNorm(h)
  q   = rope(RMSNorm_d(x Wq) g_q)  k = rope(RMSNorm_d(x Wk) g_k)  v = x Wv
        (half-split pairs over all of the head; heads / kv_heads query
        heads to a key/value head)
  qI  = sg(x) WqI (S, J, c)   kI = sg(x) WkI (S, c)   w = sg(x) Ww (S, J)
  I[t, s] = (J c)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t
  S_t = the min(t + 1, topk) keys s <= t of largest I[t, s]; equal
        scores: the lower s first
  a[t, i, s] = softmax over S_t of q[t, i] . k[s, i // g] / sqrt(d)
  h  <- h + concat_i(sum_{S_t} a[t, i, s] v[s, i // g]) Wo
  p[t, s] = sg(mean_i a[t, i, s])
  L_I = (1 / S) sum_t sum_{S_t} p (log p - log softmax_{S_t}(I[t, :]))
  x   = RMSNorm(h);  r = softmax(x Wg) over all experts
  T   = top-k of r;  g_e = r_e / sum_{T} r_e'
  h  <- h + sum_{e in T, e held} g_e Wdown_e(silu(Wgate_e x) * Wup_e x)

The step's loss is the head's mean cross-entropy plus every layer's
``L_I`` with weight 1. No rotary embedding and no norm inside the
indexer.

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``;
``sizes`` carries the config.json keys plus ``num_experts_published``
and ``first_held_expert``.

Departures from the published model, each also a line where it happens:
  * the share of an 8-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the top-k and the gates'
    normalisation run over all of them and what the absent ones would
    have added is left out; the vocabulary is the slice ``vocab_size``
    says;
  * the projections come in the program's layout: attention's as
    (hidden, heads, d) and (heads, d, hidden), the indexer's as (hidden,
    J, c), (hidden, c), (hidden, J);
  * the routers carry a ``bias`` in their weight list, which softmax
    routing does not read;
  * text only: one position stream.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the s x s scores held at a time

# The one knob, for the question "would a lower precision be caught":
# ``rounded_operands`` rounds both operands of every matrix product to a
# narrower type first (the sums stay float32), as an MXU fed that type
# would. Left alone, nothing is rounded: that is the reference.
_ROUND = {"matmul": None, "router": None}


@contextlib.contextmanager
def rounded_operands(matmul=None, router=None):
    """Inside: every product's operands rounded to ``matmul`` (a dtype;
    None: not rounded), the routers' to ``router``."""
    before = dict(_ROUND)
    _ROUND.update(matmul=matmul, router=router)
    try:
        yield
    finally:
        _ROUND.update(before)


def _dot(pattern, a, b, kind="matmul"):
    to = _ROUND[kind]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(pattern, a, b)


class ReferenceMismatch(Exception):
    """The program's parameters do not have the architecture's shape."""


ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "wq_idx", "wk_idx", "w_idx")
EXPERTS = ("wg", "bias", "w_gate", "w_up", "w_down")


class _Walk:
    def __init__(self, layers):
        self.layers = list(layers)
        self.i = 0

    def take(self, *keys):
        if self.i >= len(self.layers):
            raise ReferenceMismatch(
                f"the program has {len(self.layers)} parameter layers; "
                f"the architecture needs more (next: {keys})")
        name, w = self.layers[self.i]
        self.i += 1
        if set(keys) != set(w):
            raise ReferenceMismatch(
                f"layer {self.i - 1} ({name}) holds {sorted(w)}, the "
                f"architecture expects {sorted(keys)} there")
        return w

    def scale(self):
        return self.take("scale")["scale"]

    def matrix(self, rows: int, cols: int):
        kernel = self.take("kernel")["kernel"]
        if kernel.shape != (rows, cols):
            raise ReferenceMismatch(
                f"layer {self.i - 1} ({self.layers[self.i - 1][0]}) is "
                f"{kernel.shape}, the architecture expects {(rows, cols)}")
        return kernel

    def done(self):
        if self.i != len(self.layers):
            raise ReferenceMismatch(
                f"{len(self.layers) - self.i} parameter layers left over "
                f"(first: {self.layers[self.i][0]})")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """Half-split rotary embedding over all of the last axis: the pair
    ``(i, i + d/2)`` turns by ``pos * theta ** (-2i / d)``. x: (b, s,
    heads, d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, :, None, None] * freq   # (b,s,1,d/2)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def indexer(u, w):
    """``(qI, kI, w)`` from the layer's input DETACHED."""
    u = jax.lax.stop_gradient(u)
    return (_dot("bse,ejc->bsjc", u, w["wq_idx"]),
            _dot("bse,ec->bsc", u, w["wk_idx"]),
            _dot("bse,ej->bsj", u, w["w_idx"]))


def index_scores(qi, ki, wi):
    """``I`` for the queries given, (b, rows, s); entries with s > t are
    computed and never read."""
    j, c = qi.shape[2], qi.shape[3]
    dots = jax.nn.relu(_dot("bqjc,bkc->bqjk", qi, ki))
    return jnp.einsum("bqj,bqjk->bqk", wi, dots) * (j * c) ** -0.5


def selected(scores, rows, topk: int):
    """(b, rows, s) bool: ``S_t`` for the query rows ``rows`` (their
    positions, (rows,)) given their index scores (b, rows, s): the
    causal keys among ``jax.lax.top_k``'s choice, which orders equal
    scores by the lower index; a row with ``topk`` causal keys or fewer
    keeps them all."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, s))
    taken = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None],
        jnp.arange(scores.shape[1])[None, :, None], idx].set(True)
    return taken & causal


def sparse_attention(u, pos, w, sizes):
    """``(y, L_I, S)``: the layer's attention output (b, s, hidden), its
    alignment loss (the mean over sequences and positions) and the
    selection (b, s, s) bool."""
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    topk = sizes["sa_config"]["topk"]
    # the norms over each head's entries come BEFORE the rotation
    q = rope(rms_norm(_dot("bse,ehd->bshd", u, w["wq"]), w["q_norm"], eps),
             pos, theta)
    k = rope(rms_norm(_dot("bse,ehd->bshd", u, w["wk"]), w["k_norm"], eps),
             pos, theta)
    v = _dot("bse,ehd->bshd", u, w["wv"])
    b, s, heads, d = q.shape
    kv = k.shape[2]
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    qi, ki, wi = indexer(u, w)
    # blocks of query rows, one after another (``jax.lax.map``: one
    # block's program, not s / QUERY_ROWS copies of it); a length that
    # is no whole number of blocks is one block
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1

    def blocks(x):
        return jnp.moveaxis(x.reshape((b, n, s // n) + x.shape[2:]), 1, 0)

    def block(args):
        q_rows, qi_rows, wi_rows, rows = args
        score = index_scores(qi_rows, ki, wi_rows)
        keep = selected(jax.lax.stop_gradient(score), rows, topk)
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -jnp.inf), -1)
        p = jax.lax.stop_gradient(a.mean((1, 2)))
        log_i = jax.nn.log_softmax(jnp.where(keep, score, -jnp.inf), -1)
        live = keep & (p > 0)                    # 0 log 0 = 0
        kl = jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0))
                       - jnp.where(live, log_i, 0.0)), 0.0))
        return _dot("bjgqk,bkjd->bqjgd", a, v), kl, keep

    outs, kls, sets = jax.lax.map(block, (
        blocks(q), blocks(qi), blocks(wi), jnp.arange(s).reshape(n, s // n)))

    def whole(x):
        return jnp.moveaxis(x, 0, 1).reshape((b, s) + x.shape[3:])

    ctx = whole(outs).reshape(b, s, heads, d)
    return (_dot("bqhd,hde->bqe", ctx, w["wo"]), jnp.sum(kls) / (b * s),
            whole(sets))


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def gates(x, w, sizes):
    """(tokens, published experts): ``g_e`` where expert ``e`` is one of
    the token's top-k by ``r = softmax(x Wg)``, 0 elsewhere. ``w["bias"]``
    is not read: softmax routing has no choice bias."""
    k = sizes["num_experts_per_tok"]
    r = jax.nn.softmax(_dot("...e,en->...n", x, w["wg"], "router"), -1)
    _, idx = jax.lax.top_k(r, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, r.shape[-1], dtype=r.dtype), -2) > 0
    picked = jnp.where(chosen, r, 0.0)
    return picked / picked.sum(-1, keepdims=True)        # norm_topk_prob


def routed(x, w, sizes):
    """What the experts HELD here add: departure, the share (the sum in
    the gates' denominator still runs over all the chosen)."""
    g = gates(x, w, sizes)
    first = sizes.get("first_held_expert", 0)
    y = jnp.zeros_like(x)
    for j in range(w["w_gate"].shape[0]):        # a loop and a gate
        y = y + g[..., first + j, None] * swiglu(
            x, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    return y


def decoder_layer(x, pos, norm1, attn, norm2, experts, sizes):
    """One layer: ``(h, L_I, S)``."""
    eps = sizes["rms_norm_eps"]
    y, kl, keep = sparse_attention(rms_norm(x, norm1, eps), pos, attn, sizes)
    x = x + y
    return x + routed(rms_norm(x, norm2, eps), experts, sizes), kl, keep


def _forward(layers, sizes, ids, pos):
    """``(log-probabilities, [L_I a layer], [S a layer])``."""
    walk = _Walk(layers)
    hid = sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    kls, sets = [], []
    for _ in range(sizes["num_hidden_layers"]):
        x, kl, keep = decoder_layer(
            x, pos, walk.scale(), walk.take(*ATTN), walk.scale(),
            walk.take(*EXPERTS), sizes)
        kls.append(kl)
        sets.append(keep)
    x = rms_norm(x, walk.scale(), sizes["rms_norm_eps"])
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1), kls, sets


def sparse_index_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _forward(layers, sizes, ids, pos)[0]


def selections(layers, sizes, ids, pos):
    """Every layer's ``S``: a list of (n, seq, seq) bool."""
    with jax.default_matmul_precision("highest"):
        return _forward(layers, sizes, ids, pos)[2]


def losses(layers, sizes, ids, pos, labels):
    """``(cross-entropy, sum of the layers' L_I)``: the two terms of the
    step's loss, ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp, kls, _ = _forward(layers, sizes, ids, pos)
        return (-jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1)),
                sum(kls))


def loss(layers, sizes, ids, pos, labels):
    """The step's loss: the mean cross-entropy of the head against
    ``labels`` plus every layer's alignment loss with weight 1."""
    ce, kl = losses(layers, sizes, ids, pos, labels)
    return ce + kl
