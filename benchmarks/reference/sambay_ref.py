"""Plain reference for the SambaY decoder-hybrid-decoder with
differential attention (``model_type: phi4flash``, Microsoft's
Phi-4-mini-flash-reasoning; arXiv:2507.06607, with Mamba, arXiv:2312.00752,
and the Differential Transformer, arXiv:2410.05258): the forward pass and
the loss in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no bf16
operand: the selective scan runs TOKEN BY TOKEN (one ``jax.lax.scan`` over
the positions, the state an ``(N, channels)`` float32 array a sequence),
and differential attention is two explicit masked softmaxes a pair of
heads, their difference taken as written on each of the pair's two value
heads, in blocks of query rows (one after another through
``jax.lax.map``) so that 8192 positions fit. ISSUE 61 states the
equations (``h`` the residual stream, one sequence of T positions,
``config.json``'s keys in backticks, ``LN`` a LayerNorm with scale and
bias and `layer_norm_eps`, ``d`` = `hidden_size` / `num_attention_heads`):

  h = Embed[ids]                         not scaled, NO positional embedding
  layer i (PUBLISHED index, ``first_layer_index`` + its place here):
    h <- h + Mixer_i(LN(h))
    [g | u] = LN(h) W_1;  h <- h + (silu(g) * u) W_2       no bias
  logits = LN(h) W_head

  Mixer_i, ``"mamba1"`` / ``"mamba1_memory"`` (i even, i <= 16):
    [x | z] = u W_in                              D | D, D = 2 hidden
    x  = silu(conv_4(x) + b_c)                    causal, depthwise
    [dl | B | C] = x W_x                          R | N | N
    dt = softplus(dl W_dt + b_dt)                 R -> D
    A  = -exp(A_log)                              (N, D)
    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T         from s = 0
    m_t = C_t s_t + D * x_t
    out = (m * silu(z)) W_out;  layer 16 also hands on m (BEFORE the gate)

  Mixer_i, differential attention (i odd): H query heads on G key/value
  heads of d, adjacent heads a pair; query pair j is heads 2j, 2j + 1
  (q1_j, q2_j), key pair g heads 2g, 2g + 1 of k (k1_g, k2_g) and of v
  (va_g, vb_g); query pair j reads key pair j // (H / G):
    P1 = softmax(q1 k1^T / sqrt(d) + mask)   P2 = softmax(q2 k2^T / sqrt(d) + mask)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
    lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)
    o_j = (1 - lambda_init) RMS([P1 va - lambda P2 va | P1 vb - lambda P2 vb]; w)
    out = concat_j(o_j) W_o + b_o
    ``"diff_sliding_attention"`` (i < 16): mask causal and key > query -
    `sliding_window`; ``"diff_attention_kv"`` (i = 17): causal, and k, v
    (after the bias) are handed on; ``"diff_cross_attention"`` (i > 17):
    q = u W_q + b alone, k and v layer 17's, causal

  Mixer_i, ``"gated_memory"`` (i even, i > 16):
    out = (m * silu(u W_1)) W_2              m layer 16's, no bias

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file. ``pos`` is taken and not read.

Departures from the published model, each also a line where it happens:
  * `tie_word_embeddings` is true in the published model; the head here
    is a matrix of its own, given beside the embedding;
  * ``config.json`` names no Mamba size, no initialisation and not where
    the one full layer stands: the file lists them under ``assumed``,
    and whatever the weights hold is used;
  * the share of a deployment: the layers given are published layers
    ``first_layer_index`` onwards and the vocabulary is the slice
    ``vocab_size`` says;
  * the weights come in the program's layout: the attention projections
    (hidden, heads, d) and (heads, d, hidden), ``A_log`` (N, D), the
    MLP's input projection as its two halves (gate, up).
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time

# The knobs, for the questions "would a lower precision be caught" and
# "what does the comparison not see": ``rounded_operands`` rounds both
# operands of every matrix product to a narrower type first (the sums
# stay float32), as an MXU fed that type would; ``variant`` computes
# ANOTHER model (lambda held at 0, no window, the memory taken from the
# first mixer here, the cross layer on keys and values of its own input).
# Left alone: the reference.
_ROUND = {"matmul": None}
_VARIANT = {"lambda_zero": False, "no_window": False,
            "memory_from_first": False, "cross_own_keys": False}


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


@contextlib.contextmanager
def variant(**which):
    """Inside: the model with the named departures (``_VARIANT``'s
    keys), for reading what the comparison sees of each."""
    unknown = set(which) - set(_VARIANT)
    if unknown:
        raise ValueError(f"no variant {sorted(unknown)}")
    before = dict(_VARIANT)
    _VARIANT.update(which)
    try:
        yield
    finally:
        _VARIANT.update(before)


def _dot(pattern, a, b):
    to = _ROUND["matmul"]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(pattern, a, b)


class ReferenceMismatch(Exception):
    """The program's parameters do not have the architecture's shape."""


LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "subln") + LAMBDAS
CROSS = tuple(k for k in ATTN if k not in ("wk", "wv", "bk", "bv"))
MIXER = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
         "A_log", "D", "out_proj")
KINDS = ("mamba1", "mamba1_memory", "diff_sliding_attention",
         "diff_attention_kv", "gated_memory", "diff_cross_attention")


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


def layer_norm(x, w, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def lambda_init(depth: int) -> float:
    """The learned scalar's start, by the PUBLISHED layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def keys_and_values(u, w):
    """A layer's projected keys and values in heads, after the bias."""
    return (_dot("bse,ehd->bshd", u, w["wk"]) + w["bk"],
            _dot("bse,ehd->bshd", u, w["wv"]) + w["bv"])


def differential_attention(u, w, k, v, sizes, depth: int, window: int):
    """Differential attention of ``u``'s queries over ``k``, ``v`` (b, s,
    G, d): causal, and with ``window`` > 0 also ``key > query -
    window``."""
    q = _dot("bse,ehd->bshd", u, w["wq"]) + w["bq"]
    b, s, heads, d = q.shape
    kv = k.shape[2]
    if (heads, kv) != (sizes["num_attention_heads"],
                       sizes["num_key_value_heads"]) \
            or heads % 2 or kv % 2 or (heads // 2) % (kv // 2):
        raise ReferenceMismatch(f"{heads} query heads on {kv}")
    pairs, key_pairs = heads // 2, kv // 2
    # adjacent heads are a pair: (.., pair, which of the two, d); query
    # pair j reads key pair j // group
    q = q.reshape(b, s, key_pairs, pairs // key_pairs, 2, d)
    k = k.reshape(b, s, key_pairs, 2, d)
    v = v.reshape(b, s, key_pairs, 2, d)
    lam0 = lambda_init(depth)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0
    if _VARIANT["lambda_zero"]:
        lam = 0.0
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    rows_n = s // n

    def block(args):
        q_rows, rows = args          # (b, rows_n, G/2, group, 2, d), (rows_n,)
        keys = jnp.arange(s)[None, :]
        allowed = keys <= rows[:, None]
        if window and not _VARIANT["no_window"]:
            allowed = allowed & (keys > rows[:, None] - window)

        def probabilities(which):    # softmax(q_which k_which^T / sqrt(d))
            sc = _dot("bqgjd,bkgd->bgjqk", q_rows[..., which, :],
                      k[..., which, :]) / math.sqrt(d)
            return jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), -1)

        p1, p2 = probabilities(0), probabilities(1)
        # the difference as written, on each of the pair's value heads
        halves = [_dot("bgjqk,bkgd->bqgjd", p1, v[..., half, :])
                  - lam * _dot("bgjqk,bkgd->bqgjd", p2, v[..., half, :])
                  for half in (0, 1)]
        return jnp.concatenate(halves, -1)           # (b, rows, G/2, group, 2 d)

    outs = jax.lax.map(block, (
        jnp.moveaxis(q.reshape((b, n, rows_n) + q.shape[2:]), 1, 0),
        jnp.arange(s).reshape(n, rows_n)))
    o = jnp.moveaxis(outs, 0, 1).reshape(b, s, pairs, 2 * d)
    o = rms_norm(o, w["subln"], sizes["layer_norm_eps"]) * (1.0 - lam0)
    return _dot("bqhd,hde->bqe", o.reshape(b, s, heads, d), w["wo"]) \
        + w["bo"]


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
    """The selective scan token by token. x, dt: (b, T, D), dt already
    through the softplus; a_log: (N, D); bm, cm: (b, T, N). Returns m
    (b, T, D), the skip included."""
    big_a = -jnp.exp(a_log)                                # (N, D)

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        decay = jnp.exp(dt_t[:, None, :] * big_a)          # (b, N, D)
        state = decay * state + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return state, jnp.sum(c_t[:, :, None] * state, 1)

    state = jnp.zeros((x.shape[0],) + a_log.shape, jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1) + d_skip * x


def mixer(u, w, sizes):
    """The selective-scan layer's branch: ``(output, m)``."""
    inner = sizes["mamba_expand"] * sizes["hidden_size"]
    n, r = sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    if w["in_proj"].shape[1] != 2 * inner \
            or w["conv_w"].shape != (inner, sizes["mamba_d_conv"]) \
            or w["x_proj"].shape != (inner, r + 2 * n) \
            or w["A_log"].shape != (n, inner):
        raise ReferenceMismatch(
            f"in_proj {w['in_proj'].shape}, taps {w['conv_w'].shape}, "
            f"x_proj {w['x_proj'].shape}, A_log {w['A_log'].shape}")
    xz = _dot("bte,ec->btc", u, w["in_proj"])
    x, z = xz[..., :inner], xz[..., inner:]
    x = jax.nn.silu(causal_conv(x, w["conv_w"], w["conv_b"]))
    dbc = _dot("btc,cr->btr", x, w["x_proj"])
    low, bm, cm = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    dt = jax.nn.softplus(_dot("btr,rc->btc", low, w["dt_proj"])
                         + w["dt_bias"])
    m = recurrence(x, dt, w["A_log"], bm, cm, w["D"])
    # the gate, no norm; m is what a gated memory unit reads
    return _dot("btc,ce->bte", m * jax.nn.silu(z), w["out_proj"]), m


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def _forward(layers, sizes, ids, pos):
    del pos                          # no layer turns by a position
    walk = _Walk(layers)
    eps, hid = sizes["layer_norm_eps"], sizes["hidden_size"]
    ffn, inner = sizes["intermediate_size"], \
        sizes["mamba_expand"] * sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]           # not scaled
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"]:
        raise ReferenceMismatch(
            f"{len(kinds)} layer_types for {sizes['num_hidden_layers']} "
            f"layers")
    memory = first_memory = handed = handed_w = None
    for i, kind in enumerate(kinds):
        depth = sizes["first_layer_index"] + i
        if kind not in KINDS:
            raise ReferenceMismatch(f"layer {i} is of kind {kind!r}")
        u = layer_norm(x, walk.take("scale", "bias"), eps)
        if kind in ("mamba1", "mamba1_memory"):
            op, m = mixer(u, walk.take(*MIXER), sizes)
            first_memory = m if first_memory is None else first_memory
            if kind == "mamba1_memory":
                memory = first_memory if _VARIANT["memory_from_first"] else m
        elif kind == "gated_memory":
            if memory is None:
                raise ReferenceMismatch(f"layer {i}: no memory to gate")
            op = _dot("btc,ce->bte", memory * jax.nn.silu(
                _dot("bte,ec->btc", u, walk.matrix(hid, inner))),
                walk.matrix(inner, hid))
        elif kind == "diff_cross_attention":
            if handed is None:
                raise ReferenceMismatch(f"layer {i}: no keys and values "
                                        f"to attend over")
            k, v = keys_and_values(u, handed_w) \
                if _VARIANT["cross_own_keys"] else handed
            op = differential_attention(u, walk.take(*CROSS), k, v, sizes,
                                        depth, 0)
        else:
            w = walk.take(*ATTN)
            k, v = keys_and_values(u, w)
            if kind == "diff_attention_kv":
                handed, handed_w = (k, v), w
            op = differential_attention(
                u, w, k, v, sizes, depth, sizes["sliding_window"]
                if kind == "diff_sliding_attention" else 0)
        x = x + op
        u = layer_norm(x, walk.take("scale", "bias"), eps)
        x = x + swiglu(u, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                       walk.matrix(ffn, hid))
    x = layer_norm(x, walk.take("scale", "bias"), eps)
    # departure: a head of its own, where the published model reads the
    # embedding's matrix again
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def sambay_decoder(layers, sizes, ids, pos):
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
