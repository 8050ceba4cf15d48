"""Plain reference for the hybrid convolution/attention decoders with
sparse experts (``model_type: lfm2_moe``): the forward pass and the loss
in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped product, no bf16 operand: the convolution is a loop over its
taps, attention the explicit s x s softmax in blocks of query rows so
that 8192 positions fit beside a training step's state, and the experts
a Python loop over the experts held, each applied to every token under
its gate.

Written from the published description, LFM2-24B-A2B's ``config.json``
and its layer equations (ISSUE 33 states them):

  block      h <- h + Op(RMSNorm(h)); h <- h + FF(RMSNorm(h)); a final
             RMSNorm before the head; no bias anywhere
  conv       [B ; C ; x] = u W_in; z = B * x;
             c_t = sum_j w_j * z_{t - (K - 1) + j} (depthwise, causal,
             zeros left of position 0); y = (C * c) W_out
  attention  q, k each through an RMSNorm over the head's entries with a
             learned weight a projection, THEN the rotary embedding
             (half-split, all of the head), causal softmax of
             q k / sqrt(d) with each kv head serving heads / kv_heads
             query heads, output projection
  FF         SwiGLU in the first ``num_dense_layers``; after them
             s = sigmoid(u Wg), the choice the top-k of s + bias, the
             gates the chosen s_i over their sum, times the scale; each
             expert a SwiGLU; no shared expert

``layers``, ``sizes``, ``ids``, ``pos`` as in ``transformer_ref.py``;
``sizes`` carries the config.json keys plus ``num_experts_published``
and ``first_held_expert``.

Departures from the published model, each also a line where it happens:
  * the share of an 8-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the top-k and the gates'
    normalisation run over all of them and what the absent ones would
    have added is left out; the vocabulary is the slice ``vocab_size``
    says;
  * the head is a matrix of its own, not the embedding's transpose (the
    program's graph has no weight read by two layers);
  * the projections come in the program's layout: ``w_in`` as
    (hidden, 3, channels) for [B ; C ; x], attention's as (hidden,
    heads, d) and (heads, d, hidden);
  * the routers' bias is whatever the weights hold: it corrects the
    choice only and no gradient reaches it.
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


CONV = ("w_in", "taps", "w_out")
ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
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
    ``(i, i + d/2)`` turns by ``pos * theta ** (-2i / d)``
    (``rope_type: default``, no scaling). x: (b, s, heads, d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, :, None, None] * freq   # (b,s,1,d/2)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def short_conv(z, taps):
    """``c_t = sum_j taps[:, j] * z_{t - (K - 1) + j}``, zeros before
    position 0: a loop over the taps, each a shift of the sequence.
    z: (b, s, channels); taps: (channels, K)."""
    k = taps.shape[1]
    c = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j                       # positions looked back
        moved = z if back == 0 else jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :z.shape[1] - back]], 1)
        c = c + taps[:, j] * moved
    return c


def gated_short_conv(u, w):
    bcx = _dot("bse,egc->bsgc", u, w["w_in"])   # departure: the layout
    gate_b, gate_c, x = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    return _dot("bsc,ce->bse", gate_c * short_conv(gate_b * x, w["taps"]),
                w["w_out"])


def attention(u, pos, w, sizes):
    eps = sizes["norm_eps"]
    theta = sizes["rope_parameters"]["rope_theta"]
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
    outs = []
    for lo in range(0, s, QUERY_ROWS):          # blocks of query rows
        rows = jnp.arange(lo, min(lo + QUERY_ROWS, s))
        sc = _dot("bqjgd,bkjd->bjgqk", q[:, lo:lo + QUERY_ROWS], k) \
            / math.sqrt(d)
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc,
                       -jnp.inf)
        outs.append(_dot("bjgqk,bkjd->bqjgd", jax.nn.softmax(sc, axis=-1),
                         v))
    ctx = jnp.concatenate(outs, 1).reshape(b, s, heads, d)
    return _dot("bqhd,hde->bqe", ctx, w["wo"])


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def gates(x, w, sizes):
    """(tokens, published experts): ``g_i`` where expert ``i`` is one of
    the token's top-k by ``s + bias``, 0 elsewhere."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_dot("...e,en->...n", x, w["wg"], "router"))
    # use_expert_bias: the bias corrects the choice only
    corrected = s + jax.lax.stop_gradient(w["bias"])
    chosen = corrected >= jax.lax.top_k(corrected, k)[0][..., -1:]
    picked = jnp.where(chosen, s, 0.0)
    # norm_topk_prob, then routed_scaling_factor
    return sizes["routed_scaling_factor"] * picked / (
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


def _log_probs(layers, sizes, ids, pos):
    walk = _Walk(layers)
    eps, hid = sizes["norm_eps"], sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"]:
        raise ReferenceMismatch(
            f"{len(kinds)} layer_types for {sizes['num_hidden_layers']} "
            f"layers")
    for i, kind in enumerate(kinds):
        u = rms_norm(x, walk.scale(), eps)
        if kind == "conv":
            x = x + gated_short_conv(u, walk.take(*CONV))
        elif kind == "full_attention":
            x = x + attention(u, pos, walk.take(*ATTN), sizes)
        else:
            raise ReferenceMismatch(f"layer {i} is of kind {kind!r}")
        u = rms_norm(x, walk.scale(), eps)
        if i < sizes["num_dense_layers"]:
            ffn = sizes["intermediate_size"]
            x = x + swiglu(u, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                           walk.matrix(ffn, hid))
        else:
            x = x + routed(u, walk.take(*EXPERTS), sizes)
    x = rms_norm(x, walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])   # departure: untied
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def hybrid_conv_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _log_probs(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """The mean cross-entropy of the head against ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp = _log_probs(layers, sizes, ids, pos)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
