"""Plain reference for decoders that mix window and full grouped-query
attention layers, gate every attention layer's output, norm each
sub-layer on both sides and route sigmoid-scored experts beside a shared
one (``model_type: afmoe``, Arcee's Trinity family): the forward pass and
the loss in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped product, no bf16 operand: attention is the explicit softmax over
the allowed keys in blocks of query rows (one after another through
``jax.lax.map``; a window layer's block reads only the keys its band
reaches) so that 8192 positions fit beside a training step's state, and
the experts a Python loop over the experts held, each applied to every
token under its gate. ISSUE 51 states the equations (``h`` the one residual stream, one sequence of S positions,
no bias in any projection):

  h   = Embed[ids] * sqrt(hidden)                          mup_enabled
  x   = RMSNorm_in(h)
  q   = x Wq (S,H,d)   k = x Wk (S,G,d)   v = x Wv (S,G,d)   g = x Wg (S,H,d)
  q   = RMSNorm_d(q) s_q     k = RMSNorm_d(k) s_k
  sliding layer:  q, k = rope(q), rope(k)   half-split pairs, all of d
                  allowed(t, s) = (s <= t) and (s > t - window)
  full layer:     no rotary embedding;  allowed(t, s) = (s <= t)
  a[t,i,s] = softmax over allowed s of q[t,i] . k[s, i // (H/G)] / sqrt(d)
  o[t,i]   = (sum_s a[t,i,s] v[s, i // (H/G)]) * sigmoid(g[t,i])
  h  <- h + RMSNorm_post_attn(concat_i(o[t,i]) Wo)
  x   = RMSNorm_pre_mlp(h)
  dense layer:    y = Wdown(silu(Wgate x) * Wup x)
  expert layer:   r = sigmoid(x Wr) over all experts
                  T = top-k of (r + b)                     b: no gradient
                  w_e = route_scale * r_e / (sum_{e' in T} r_e' + 1e-20)
                  y = sum_{e in T, e held} w_e E_e(x)  +  E_shared(x)
  h  <- h + RMSNorm_post_mlp(y)
  logits = RMSNorm(h) Wlm;  loss = mean next-token cross-entropy

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file (the config.json keys plus
``num_experts_published`` and ``first_held_expert``).

Departures from the published model, each also a line where it happens:
  * the share of an 8-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the top-k and the gates'
    normalisation run over all of them and what the absent ones would
    have added is left out; the shared expert is whole; the vocabulary
    is the slice ``vocab_size`` says;
  * the projections come in the program's layout: (hidden, heads, d)
    and (heads, d, hidden);
  * the routers' bias is whatever the weights hold: it corrects the
    choice only and no gradient reaches it.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time

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


ATTN = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm")
EXPERTS = ("wg", "bias", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
           "ws_down")
KINDS = ("sliding_attention", "full_attention")


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
    ``(i, i + d/2)`` turns by ``pos * theta ** (-2i / d)`` (no scaling).
    x: (b, s, heads, d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, :, None, None] * freq   # (b,s,1,d/2)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def band_pairs(s: int, window: int) -> int:
    """The (query, key) pairs ``s <= t and s > t - window`` of ``s``
    positions; ``window`` 0 or at least ``s``: the causal triangle."""
    w = min(window, s) if window else s
    return w * s - w * (w - 1) // 2


def attention(u, pos, w, sizes, kind):
    """One attention layer's branch before its post-norm: ``kind`` says
    whether the layer has a window and a rotary embedding (sliding) or
    neither (full)."""
    eps = sizes["rms_norm_eps"]
    sliding = kind == "sliding_attention"
    window = sizes["sliding_window"] if sliding else 0
    # the norms over each head's entries come BEFORE the rotation
    q = rms_norm(_dot("bse,ehd->bshd", u, w["wq"]), w["q_norm"], eps)
    k = rms_norm(_dot("bse,ehd->bshd", u, w["wk"]), w["k_norm"], eps)
    if sliding:          # the full layers have no rotary embedding
        q = rope(q, pos, sizes["rope_theta"])
        k = rope(k, pos, sizes["rope_theta"])
    v = _dot("bse,ehd->bshd", u, w["wv"])
    gate = _dot("bse,ehd->bshd", u, w["wg"])    # from the same normed u
    b, s, heads, d = q.shape
    kv = k.shape[2]
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    # blocks of query rows, one after another (``jax.lax.map``: one
    # block's program, not s / QUERY_ROWS copies of it); a length that
    # is no whole number of blocks is one block. A block reads the
    # ``span`` keys that end with its last query's own: all a window
    # layer's band reaches, every key of a full layer
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    rows_n = s // n
    span = min(s, window + rows_n - 1) if window else s

    def block(args):
        q_rows, rows = args                      # (b, rows_n, ...), (rows_n,)
        first = jnp.maximum(rows[-1] + 1 - span, 0)
        keys = first + jnp.arange(span)
        k_span = jax.lax.dynamic_slice_in_dim(k, first, span, 1)
        v_span = jax.lax.dynamic_slice_in_dim(v, first, span, 1)
        allowed = keys[None, :] <= rows[:, None]
        if window:
            allowed = allowed & (keys[None, :] > rows[:, None] - window)
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k_span) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
        return _dot("bjgqk,bkjd->bqjgd", a, v_span)

    outs = jax.lax.map(block, (
        jnp.moveaxis(q.reshape((b, n, rows_n) + q.shape[2:]), 1, 0),
        jnp.arange(s).reshape(n, rows_n)))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads, d)
    ctx = ctx * jax.nn.sigmoid(gate)            # elementwise, before Wo
    return _dot("bqhd,hde->bqe", ctx, w["wo"])


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def gates(x, w, sizes):
    """(tokens, published experts): ``w_e`` where expert ``e`` is one of
    the token's top-k by ``r + bias``, 0 elsewhere."""
    k = sizes["num_experts_per_tok"]
    r = jax.nn.sigmoid(_dot("...e,en->...n", x, w["wg"], "router"))
    # the bias corrects the choice only
    corrected = r + jax.lax.stop_gradient(w["bias"])
    chosen = corrected >= jax.lax.top_k(corrected, k)[0][..., -1:]
    picked = jnp.where(chosen, r, 0.0)
    # route_norm, then route_scale
    return sizes["route_scale"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)


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


def shared(x, w):
    return swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def attention_sublayer(x, pos, norm_in, attn, norm_post, sizes, kind):
    """``h + RMSNorm_post(Attn(RMSNorm_in(h)))``: the second norm is
    INSIDE the residual branch."""
    eps = sizes["rms_norm_eps"]
    return x + rms_norm(attention(rms_norm(x, norm_in, eps), pos, attn,
                                  sizes, kind), norm_post, eps)


def _forward(layers, sizes, ids, pos):
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    if sizes.get("mup_enabled", False):
        x = x * math.sqrt(hid)
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"]:
        raise ReferenceMismatch(
            f"{len(kinds)} layer_types for {sizes['num_hidden_layers']} "
            f"layers")
    for i, kind in enumerate(kinds):
        if kind not in KINDS:
            raise ReferenceMismatch(f"layer {i} is of kind {kind!r}")
        x = attention_sublayer(x, pos, walk.scale(), walk.take(*ATTN),
                               walk.scale(), sizes, kind)
        u = rms_norm(x, walk.scale(), eps)
        if i < sizes["num_dense_layers"]:
            ffn = sizes["intermediate_size"]
            y = swiglu(u, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                       walk.matrix(ffn, hid))
        else:
            w = walk.take(*EXPERTS)
            y = routed(u, w, sizes) + shared(u, w)
        x = x + rms_norm(y, walk.scale(), eps)
    x = rms_norm(x, walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def window_gated_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _forward(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """The mean cross-entropy of the head against ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp = _forward(layers, sizes, ids, pos)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
