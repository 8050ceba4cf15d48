"""Plain reference for decoders that mix gated delta-rule linear
attention with a decay a HEAD (Gated DeltaNet) and gated grouped-query
attention with a rotary embedding over part of each head, every layer
followed by softmax-routed experts beside a sigmoid-gated shared one
(``model_type: qwen3_next``): the forward pass and the loss in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no
sort, no grouped product, no bf16 operand: the recurrence runs TOKEN BY
TOKEN (a ``lax.scan`` over the positions that carries each value head's
d x d state; nothing of the program's chunked algebra), the convolution
is a loop over its taps, attention the explicit softmax over the causal
keys in blocks of query rows (one after another through ``jax.lax.map``)
so that 8192 positions fit beside a training step's state, and the
experts a Python loop over the experts held, each applied to every token
under its gate. ISSUE 57 states the equations (``h`` the one residual
stream, no bias in any projection):

  norm       RMSNorm(x; w) = x / rms(x) * (1 + w), eps 1e-6   zero-centred
  block      h <- h + Mixer(RMSNorm(h));  h <- h + MoE(RMSNorm(h));
             layer i is full attention where (i + 1) % 4 == 0
  linear     q = x Wq (S,Hk,d)  k = x Wk (S,Hk,d)  v = x Wv (S,Hv,d)
             z = x Wz (S,Hv,d)  b = x Wb (S,Hv)    a = x Wa (S,Hv)
             q, k, v <- silu(conv(.)), depthwise, causal, 4 taps, zeros
             left of position 0
             q <- q / |q| * d^-1/2;  k <- k / |k|         (1e-6 under the root)
             value head j reads q/k head j // (Hv / Hk)
             beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
             state S (d x d, keys by values) a value head, from zeros:
               S <- exp(g_t) S
               S <- S + beta_t k_t (v_t - S^T k_t)^T
               o_t = S^T q_t
             y_t = [RMSNorm_d(o_t) * w_o * silu(z_t)] Wo   a PLAIN scale
  full       q = x Wq (S,H,d)  gate = x Wg (S,H,d)  k = x Wk  v = x Wv (S,G,d)
             q = RMSNorm_d(q; w_q)   k = RMSNorm_d(k; w_k)    zero-centred
             rope over the FIRST ``partial_rotary_factor x d`` entries,
             half-split pairs among themselves, the rest untouched
             a = causal softmax of q . k[i // (H/G)] / sqrt(d)
             y = [o * sigmoid(gate)] Wo
  experts    p = softmax(x Wr) over all experts;  T = the top-k of p
             w_e = p_e / sum_{e' in T} p_e'
             y = sum_{e in T, e held} w_e E_e(x)
                 + sigmoid(x . w_s) E_shared(x)
  head       logits = RMSNorm(h) Wlm;  loss = mean next-token
             cross-entropy

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file (the config.json keys plus
``num_experts_published`` and ``first_held_expert``).

Departures from the published model, each also a line where it happens:
  * the share of a 16-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the softmax, the top-k and
    the gates' normalisation run over all of them and what the absent
    ones would have added is left out; the shared expert is whole; the
    vocabulary is the slice ``vocab_size`` says;
  * the projections come in the program's layout: (hidden, heads, d)
    and (heads, d, hidden); the published ``in_proj_qkvz`` /
    ``in_proj_ba`` are ``Wq, Wk, Wv, Wz`` / ``Wb, Wa`` side by side and
    its one convolution over [q ; k ; v] three over q, k and v (column
    permutations of the same maps); the published ``q_proj`` of twice
    the width is ``Wq`` and ``Wg``;
  * the multi-token-prediction module the family's card describes has
    no key in ``config.json`` and is not built.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time
SEGMENT = 128             # tokens between the states a backward pass keeps
UNIT_EPS = 1e-6           # under the root of q's and k's lengths

# The one knob, for the question "would a lower precision be caught":
# ``rounded_operands`` rounds both operands of every matrix product to a
# narrower type first (the sums stay float32), as an MXU fed that type
# would. Left alone, nothing is rounded: that is the reference.
_ROUND = {"matmul": None, "router": None}


@contextlib.contextmanager
def rounded_operands(matmul=None, router=None):
    """Inside: every product's operands rounded to ``matmul`` (a dtype;
    None: not rounded), the routers' and the shared expert's gate's to
    ``router``."""
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


LINEAR = ("wq", "conv_q", "wk", "conv_k", "wv", "conv_v", "wa", "A_log",
          "dt_bias", "wb", "wz", "o_norm", "wo")
ATTN = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm")
EXPERTS = ("wg", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
           "ws_scalar")
KINDS = ("linear_attention", "full_attention")


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
    """``x / rms(x) * scale`` over the last axis: a zero-centred norm
    hands ``1 + w`` for ``scale``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + UNIT_EPS)


def short_conv(z, taps):
    """``z``: (b, s, heads, d); ``taps``: (heads, d, K).
    ``out[t] = sum_j taps[.., j] * z[t - (K - 1) + j]``, zeros left of
    position 0."""
    k, s = taps.shape[-1], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
    out = jnp.zeros_like(z)
    for j in range(k):                          # a loop over the taps
        out = out + padded[:, j:j + s] * taps[..., j]
    return out


def rope_part(x, pos, theta, turned: int):
    """Half-split rotary embedding over the FIRST ``turned`` entries of
    the last axis: among them the pair ``(i, i + turned/2)`` turns by
    ``pos * theta ** (-2i / turned)`` (no scaling); the entries from
    ``turned`` on pass as they are. x: (b, s, heads, d)."""
    freq = theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32) / turned)
    ang = pos.astype(jnp.float32)[:, :, None, None] * freq
    lo, hi = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang),
                            x[..., turned:]], -1)


def delta_rule_by_token(q, k, v, g, beta):
    """The recurrence, one token at a time, at the value heads. ``q``,
    ``k``: (b, s, heads, d); ``v``: (b, s, heads, dv); ``g``, ``beta``:
    (b, s, heads), ``g`` the log of a head-token's ONE decay. Returns
    ``o`` (b, s, heads, dv).

    The walk is cut into segments of ``SEGMENT`` tokens only for what a
    backward pass stores: the state at each segment's start instead of
    at every token; the steps and their order are the same."""
    def step(state, x):                         # state: (b, heads, d, dv)
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        read = _dot("bhde,bhd->bhe", state, k_t)
        state = state + _dot("bhd,bhe->bhde", k_t,
                             beta_t[..., None] * (v_t - read))
        return state, _dot("bhde,bhd->bhe", state, q_t)

    b, s, heads, d = k.shape
    segment = SEGMENT if s % SEGMENT == 0 else s
    start = jnp.zeros((b, heads, d, v.shape[-1]), jnp.float32)
    xs = [jnp.moveaxis(x, 1, 0).reshape((-1, segment) + x.shape[:1]
                                        + x.shape[2:])
          for x in (q, k, v, g, beta)]
    _, out = jax.lax.scan(
        jax.checkpoint(lambda state, seg: jax.lax.scan(step, state, seg)),
        start, xs)
    return jnp.moveaxis(out.reshape((s,) + out.shape[2:]), 0, 1)


def linear_inputs(x, w):
    """``q, k, v, g, beta`` as the recurrence takes them, q and k at the
    VALUE heads."""
    def mixed(name):
        return jax.nn.silu(short_conv(
            _dot("bse,ehd->bshd", x, w["w" + name]), w["conv_" + name]))

    d = w["wq"].shape[-1]
    group = w["wv"].shape[1] // w["wk"].shape[1]
    # value head j reads q/k head j // group
    q = jnp.repeat(unit(mixed("q")) * d ** -0.5, group, axis=2)
    k = jnp.repeat(unit(mixed("k")), group, axis=2)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        _dot("bse,eh->bsh", x, w["wa"]) + w["dt_bias"])
    beta = jax.nn.sigmoid(_dot("bse,eh->bsh", x, w["wb"]))
    return q, k, mixed("v"), g, beta


def linear_attention(x, w, sizes):
    o = delta_rule_by_token(*linear_inputs(x, w))
    z = _dot("bse,ehd->bshd", x, w["wz"])
    # the norm first, a PLAIN scale (drawn at 1), then the gate
    y = rms_norm(o, w["o_norm"], sizes["rms_norm_eps"]) * jax.nn.silu(z)
    return _dot("bshd,hde->bse", y, w["wo"])


def attention(u, pos, w, sizes):
    eps, d = sizes["rms_norm_eps"], sizes["head_dim"]
    turned = int(d * sizes["partial_rotary_factor"])
    # the norms over each head's entries come BEFORE the rotation
    q = rms_norm(_dot("bse,ehd->bshd", u, w["wq"]), 1.0 + w["q_norm"], eps)
    k = rms_norm(_dot("bse,ehd->bshd", u, w["wk"]), 1.0 + w["k_norm"], eps)
    q = rope_part(q, pos, sizes["rope_theta"], turned)
    k = rope_part(k, pos, sizes["rope_theta"], turned)
    v = _dot("bse,ehd->bshd", u, w["wv"])
    gate = _dot("bse,ehd->bshd", u, w["wg"])    # from the same normed u
    b, s, heads, _ = q.shape
    kv = k.shape[2]
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    rows_n = s // n

    def block(args):
        q_rows, rows = args                      # (b, rows_n, ...), (rows_n,)
        allowed = jnp.arange(s)[None, :] <= rows[:, None]
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
        return _dot("bjgqk,bkjd->bqjgd", a, v)

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
    the token's top-k by ``p``, 0 elsewhere; no choice bias, scale 1."""
    k = sizes["num_experts_per_tok"]
    p = jax.nn.softmax(_dot("...e,en->...n", x, w["wg"], "router"), -1)
    chosen = p >= jax.lax.top_k(p, k)[0][..., -1:]
    picked = jnp.where(chosen, p, 0.0)
    return picked / (picked.sum(-1, keepdims=True) + 1e-20)


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
    """The shared expert times one scalar a token."""
    opened = jax.nn.sigmoid(_dot("...e,eo->...o", x, w["ws_scalar"],
                                 "router"))
    return opened * swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def _forward(layers, sizes, ids, pos):
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    every = sizes["full_attention_interval"]
    for i in range(sizes["num_hidden_layers"]):
        u = rms_norm(x, 1.0 + walk.scale(), eps)
        if (i + 1) % every == 0:
            x = x + attention(u, pos, walk.take(*ATTN), sizes)
        else:
            x = x + linear_attention(u, walk.take(*LINEAR), sizes)
        u = rms_norm(x, 1.0 + walk.scale(), eps)
        w = walk.take(*EXPERTS)
        x = x + routed(u, w, sizes) + shared(u, w)
    x = rms_norm(x, 1.0 + walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def gdn_gated_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _forward(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """The mean cross-entropy of the head against ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp = _forward(layers, sizes, ids, pos)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
