"""Plain reference for decoders that mix state-space (Mamba-2) layers
with a few grouped-query attention layers that have no positional
embedding, a dense SwiGLU in every layer and four scalar multipliers
(``model_type: granitemoehybrid``, IBM's Granite 4.0-H family): the
forward pass and the loss in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no bf16
operand: the state-space recurrence runs TOKEN BY TOKEN (one
``jax.lax.scan`` over the positions, the state a ``(heads, head_dim,
state)`` float32 array), and attention is the explicit masked softmax in
blocks of query rows (one after another through ``jax.lax.map``) so that
4096 positions fit. ISSUE 55 states the equations (``h`` the residual
stream, one sequence of T positions, ``config.json``'s keys in
backticks, ``RMS(x; w) = w x / sqrt(mean(x^2) + rms_norm_eps)``):

  h   = `embedding_multiplier` * Embed[ids]                          (12)
  layer i:
    h <- h + `residual_multiplier` * Op_i(RMS(h; w_in_i))          (0.22)
    [g, u] = W_in_i RMS(h; w_post_i)            2048 -> 2 x 8192, no bias
    h <- h + `residual_multiplier` * W_out_i (silu(g) * u)
  logits = (W_head RMS(h; w_final)) / `logits_scaling`                (8)
  loss   = mean next-token cross-entropy

  Op_i, `layer_types[i]` == "attention" (`position_embedding_type` nope):
    q = Wq x (H heads of d)   k = Wk x   v = Wv x (G heads of d), no bias,
    no rotary embedding, no q/k norm
    a[t,i,s] = softmax over s <= t of `attention_multiplier` q[t,i] . k[s, i // (H/G)]
    out = Wo concat_i(sum_s a[t,i,s] v[s, i // (H/G)])
    (`attention_multiplier` 1/64 stands where 1/sqrt(d) = 1/8 would)

  Op_i, `layer_types[i]` == "mamba" (`mamba_n_heads` = Hm heads of
  `mamba_d_head` = P, `mamba_d_state` = N, `mamba_n_groups` 1,
  `mamba_d_conv` = K taps, `mamba_conv_bias` true, `mamba_proj_bias` false):
    [z | xBC | dt] = W_in x                    Hm P | Hm P + 2 N | Hm
    xBC[t] = silu(sum_j w[:, j] xBC[t - (K - 1) + j] + b)   zeros left of 0
    [x | B | C] = xBC                          x: (T, Hm, P); B, C: (T, N)
    dt_t = softplus(dt_t + dt_bias)            a head; no clamp
    a_t  = exp(dt_t A),  A = -exp(A_log)       a scalar a head-token
    S_t  = a_t S_{t-1} + dt_t x_t B_t^T        S: (P, N) a head, S_0 = 0
    y_t  = S_t C_t + D x_t                     D a scalar a head
    y    = RMS(y * silu(z); w_norm)            the gate BEFORE the norm,
                                               the mean over all Hm P
    out  = W_out y

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file. ``pos`` is taken and not read: nothing in this
model turns by a position.

Departures from the published model, each also a line where it happens:
  * `tie_word_embeddings` is true in the published model; the head here
    is a matrix of its own, given beside the embedding (the program's
    graph has no weight read by two layers);
  * ``config.json`` gives no initialisation for ``A_log``, ``dt_bias``
    and ``D``: whatever the weights hold is used (the configuration's
    file lists what the program draws, under ``assumed``);
  * the share of a deployment: the layers given are the first period of
    ``layer_types`` and the vocabulary is the slice ``vocab_size`` says;
  * the weights come in the program's layout: the attention projections
    (hidden, heads, d) and (heads, d, hidden), the MLP's input
    projection as its two halves (gate, up), each (hidden, 8192).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time

# The one knob, for the question "would a lower precision be caught":
# ``rounded_operands`` rounds both operands of every matrix product to a
# narrower type first (the sums stay float32), as an MXU fed that type
# would. Left alone, nothing is rounded: that is the reference.
_ROUND = {"matmul": None}


@contextlib.contextmanager
def rounded_operands(matmul=None):
    """Inside: every product's operands rounded to ``matmul`` (a dtype;
    None: not rounded)."""
    before = dict(_ROUND)
    _ROUND.update(matmul=matmul)
    try:
        yield
    finally:
        _ROUND.update(before)


def _dot(pattern, a, b):
    to = _ROUND["matmul"]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(pattern, a, b)


class ReferenceMismatch(Exception):
    """The program's parameters do not have the architecture's shape."""


ATTN = ("wq", "wk", "wv", "wo")
MIXER = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
         "out_proj")
KINDS = ("mamba", "attention")


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


def attention(u, w, sizes):
    """The attention layer's branch: causal, grouped, no positions, the
    scores times ``attention_multiplier``."""
    q = _dot("bse,ehd->bshd", u, w["wq"])
    k = _dot("bse,ehd->bshd", u, w["wk"])
    v = _dot("bse,ehd->bshd", u, w["wv"])
    b, s, heads, d = q.shape
    kv = k.shape[2]
    if (heads, kv) != (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"]):
        raise ReferenceMismatch(f"{heads} query heads on {kv}")
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    rows_n = s // n

    def block(args):
        q_rows, rows = args                      # (b, rows_n, ...), (rows_n,)
        allowed = jnp.arange(s)[None, :] <= rows[:, None]
        # attention_multiplier, NOT 1 / sqrt(d)
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k) \
            * sizes["attention_multiplier"]
        a = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
        return _dot("bjgqk,bkjd->bqjgd", a, v)

    outs = jax.lax.map(block, (
        jnp.moveaxis(q.reshape((b, n, rows_n) + q.shape[2:]), 1, 0),
        jnp.arange(s).reshape(n, rows_n)))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads, d)
    return _dot("bqhd,hde->bqe", ctx, w["wo"])


def causal_conv(x, taps, bias):
    """``out[t] = sum_j taps[:, j] x[t - (K - 1) + j] + bias``, zeros to
    the left of position 0. x: (b, T, channels); taps: (channels, K)."""
    k, t = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for j in range(k):
        out = out + padded[:, j:j + t] * taps[:, j]
    return out


def recurrence(x, dt, a_log, bm, cm, d_skip):
    """The state-space recurrence token by token. x: (b, T, Hm, P); dt:
    (b, T, Hm), already through the softplus; bm, cm: (b, T, N).
    Returns y (b, T, Hm, P)."""
    big_a = -jnp.exp(a_log)                                # (Hm,)
    b, _, heads, p = x.shape

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        decay = jnp.exp(dt_t * big_a)                      # (b, Hm)
        state = decay[..., None, None] * state + _dot(
            "bhp,bn->bhpn", dt_t[..., None] * x_t, b_t)
        return state, _dot("bhpn,bn->bhp", state, c_t)

    state = jnp.zeros((b, heads, p, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x


def mixer(u, w, sizes):
    """The state-space layer's branch."""
    heads, p, n = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                   sizes["mamba_d_state"])
    if sizes["mamba_n_groups"] != 1:
        raise ReferenceMismatch("one group of B and C")
    inner = heads * p
    if w["in_proj"].shape[1] != 2 * inner + 2 * n + heads \
            or w["conv_w"].shape != (inner + 2 * n, sizes["mamba_d_conv"]):
        raise ReferenceMismatch(
            f"in_proj {w['in_proj'].shape}, taps {w['conv_w'].shape}")
    zxbcdt = _dot("bte,ec->btc", u, w["in_proj"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * n]
    dt = zxbcdt[..., 2 * inner + 2 * n:]
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
    x = xbc[..., :inner].reshape(xbc.shape[:2] + (heads, p))
    bm, cm = xbc[..., inner:inner + n], xbc[..., inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                # no clamp
    y = recurrence(x, dt, w["A_log"], bm, cm, w["D"])
    # the gate BEFORE the norm; the mean over all the channels
    y = rms_norm(y.reshape(z.shape) * jax.nn.silu(z), w["norm"],
                 sizes["rms_norm_eps"])
    return _dot("btc,ce->bte", y, w["out_proj"])


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def _forward(layers, sizes, ids, pos):
    del pos                          # no layer turns by a position
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    ffn, res = sizes["shared_intermediate_size"], \
        sizes["residual_multiplier"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids] \
        * sizes["embedding_multiplier"]
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"]:
        raise ReferenceMismatch(
            f"{len(kinds)} layer_types for {sizes['num_hidden_layers']} "
            f"layers")
    for i, kind in enumerate(kinds):
        if kind not in KINDS:
            raise ReferenceMismatch(f"layer {i} is of kind {kind!r}")
        u = rms_norm(x, walk.scale(), eps)
        x = x + res * (mixer(u, walk.take(*MIXER), sizes) if kind == "mamba"
                       else attention(u, walk.take(*ATTN), sizes))
        u = rms_norm(x, walk.scale(), eps)
        x = x + res * swiglu(u, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                             walk.matrix(ffn, hid))
    x = rms_norm(x, walk.scale(), eps)
    # departure: a head of its own, where the published model reads the
    # embedding's matrix again
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(
        _dot("bse,ev->bsv", x, head) / sizes["logits_scaling"], -1)


def ssm_hybrid_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _forward(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """The mean cross-entropy of the head against ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp = _forward(layers, sizes, ids, pos)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def loss_and_gradients(layers, sizes, ids, pos, labels):
    """``(loss, [weights' gradients, a dict a layer, in the layers'
    order])``."""
    names = [name for name, _ in layers]
    return jax.value_and_grad(
        lambda ws: loss(list(zip(names, ws)), sizes, ids, pos, labels))(
        [w for _, w in layers])
