"""Plain reference for decoders whose block is ONE sub-layer: a Mamba-2
mixer with several groups of B and C, grouped-query attention with no
positional embedding, or a LatentMoE feed-forward whose routed experts
work in a latent between two projections (``model_type: nemotron_h``,
NVIDIA's Nemotron-H / Nemotron 3 family): the forward pass and the loss
in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no
sort, no bf16 operand: the state-space recurrence runs TOKEN BY TOKEN
(one ``jax.lax.scan`` over the positions, the state a ``(heads,
head_dim, state)`` float32 array), attention is the explicit masked
softmax in blocks of query rows (one after another through
``jax.lax.map``) so that 4096 positions fit, and the experts are a loop
over the held ones, every token through each, times its gate or 0.
ISSUE 66 states the equations (``h`` the residual stream, one sequence
of T positions, ``config.json``'s keys in backticks, ``RMS(x; w) = w x /
sqrt(mean(x^2) + norm_eps)``; no bias anywhere but the convolution's,
no rotary embedding, no residual or logit multiplier):

  h = Embed[ids]
  layer i, its kind letter i of `hybrid_override_pattern`:
    h <- h + Mix_kind(RMS(h; w_i))                 ONE sub-layer a layer
  logits = W_head RMS(h; w_final)                  the head untied
  loss   = mean next-token cross-entropy

  ``M`` (`mamba_num_heads` = Hm heads of `mamba_head_dim` = P,
  `ssm_state_size` = N, `n_groups` = G, `conv_kernel` = K taps with a
  bias, `chunk_size` read by nothing here):
    [z | xBC | dt] = W_in x                  Hm P | Hm P + 2 G N | Hm
    xBC[t] = silu(sum_j w[:, j] xBC[t - (K - 1) + j] + b)  zeros left of 0
    [x | B | C] = xBC              x: (T, Hm, P); B, C: (T, G, N)
    g(h) = h // (Hm / G)                     the group head h reads
    dt_t = softplus(dt_t + dt_bias)          a head; no clamp
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_{g,t}^T     A = -exp(A_log)
    y_t  = S_t C_{g,t} + D x_t
    y    = RMS_group(y * silu(z); w_norm)    the gate BEFORE the norm,
                                             the mean square over each
                                             group's Hm P / G channels
    out  = W_out y

  ``*`` (`num_attention_heads` = H query heads on `num_key_value_heads`
  = Hk of `head_dim` = d): q = Wq x, k = Wk x, v = Wv x, no positions,
  no q/k norm;
    a[t,i,s] = softmax over s <= t of q[t,i] . k[s, i // (H/Hk)] / sqrt(d)
    out = Wo concat_i(sum_s a[t,i,s] v[s, i // (H/Hk)])

  ``E`` (`n_routed_experts` published, `num_experts_per_tok` = k,
  `moe_latent_size` = l, `moe_intermediate_size` = f,
  `moe_shared_expert_intermediate_size` = fs, `routed_scaling_factor`):
    s   = sigmoid(W_r x)                     over ALL published experts
    S   = the top k of s + bias              `n_group` 1: no group limit
    g_e = `routed_scaling_factor` s_e / sum_{j in S} s_j       (e in S)
    u   = W_a x                              hidden -> l
    r   = sum_{e in S, e held} g_e W2_e relu(W1_e u)^2     l -> f -> l
    out = W_b r + Ws2 relu(Ws1 x)^2          the shared expert reads x,
                                             no gate on its output

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file. ``pos`` is taken and not read: nothing in this
model turns by a position.

Departures from the published model, each also a line where it happens:
  * the share of a deployment: the layers given are one period of the
    pattern, the vocabulary is the slice ``vocab_size`` says; the mixers
    hold ``mamba_num_heads`` of the published heads in ``n_groups`` whole
    groups and the attention layer ``num_attention_heads`` of the query
    heads on ``num_key_value_heads`` key/value heads, and what goes on is
    those heads' part of each output projection (the tensor-parallel
    group's all-reduce is not run); the expert layers hold experts
    ``first_held_expert`` onwards, ``n_routed_experts`` of them, and
    what the absent ones would add is left out, while the router, the
    choice and the gates' sum run over ``num_experts_published``;
  * the multi-token-prediction module is not here;
  * ``config.json`` gives no initialisation for ``A_log``, ``dt_bias``,
    ``D`` and the routers' bias: whatever the weights hold is used;
  * the weights come in the program's layout: the attention projections
    (hidden, heads, d) and (heads, d, hidden), the experts' matrices
    stacked (held, in, out).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time

# The one knob, for the question "would a lower precision be caught":
# ``rounded_operands`` rounds both operands of every matrix product to a
# narrower type first (the sums stay float32), as an MXU fed that type
# would; the router's product has a type of its own (the program runs it
# in float32 whatever the rest runs in). Left alone, nothing is rounded:
# that is the reference.
_ROUND = {"matmul": None, "router": None}


@contextlib.contextmanager
def rounded_operands(matmul=None, router=None):
    """Inside: every product's operands rounded to ``matmul``, the
    router's to ``router`` (dtypes; None: not rounded)."""
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


ATTN = ("wq", "wk", "wv", "wo")
MIXER = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
         "out_proj")
EXPERTS = ("wg", "bias", "w_latent_in", "w_latent_out", "w_up", "w_down",
           "ws_up", "ws_down")
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


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
    scores over ``sqrt(head_dim)``."""
    q = _dot("bse,ehd->bshd", u, w["wq"])
    k = _dot("bse,ehd->bshd", u, w["wk"])
    v = _dot("bse,ehd->bshd", u, w["wv"])
    b, s, heads, d = q.shape
    kv = k.shape[2]
    if (heads, kv, d) != (sizes["num_attention_heads"],
                          sizes["num_key_value_heads"], sizes["head_dim"]):
        raise ReferenceMismatch(f"{heads} query heads on {kv} of {d}")
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    rows_n = s // n

    def block(args):
        q_rows, rows = args                      # (b, rows_n, ...), (rows_n,)
        allowed = jnp.arange(s)[None, :] <= rows[:, None]
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k) / jnp.sqrt(
            jnp.float32(d))
        a = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
        return _dot("bjgqk,bkjd->bqjgd", a, v)

    outs = jax.lax.map(block, (
        jnp.moveaxis(q.reshape((b, n, rows_n) + q.shape[2:]), 1, 0),
        jnp.arange(s).reshape(n, rows_n)))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads, d)
    # departure: these heads' part of the output projection goes on
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
    """The state-space recurrence token by token. x: (b, T, G, Hg, P),
    a group's heads together; dt: (b, T, G, Hg), already through the
    softplus; bm, cm: (b, T, G, N). Returns y like x."""
    big_a = -jnp.exp(a_log)                                # (G, Hg)
    b, _, groups, heads, p = x.shape

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        decay = jnp.exp(dt_t * big_a)                      # (b, G, Hg)
        state = decay[..., None, None] * state + _dot(
            "bghp,bgn->bghpn", dt_t[..., None] * x_t, b_t)
        return state, _dot("bghpn,bgn->bghp", state, c_t)

    state = jnp.zeros((b, groups, heads, p, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1) + d_skip[..., None] * x


def mixer(u, w, sizes):
    """The state-space layer's branch."""
    heads, p, n, groups = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                           sizes["ssm_state_size"], sizes["n_groups"])
    inner, bc = heads * p, groups * n
    if w["in_proj"].shape[1] != 2 * inner + 2 * bc + heads \
            or w["conv_w"].shape != (inner + 2 * bc, sizes["conv_kernel"]) \
            or heads % groups:
        raise ReferenceMismatch(
            f"in_proj {w['in_proj'].shape}, taps {w['conv_w'].shape}, "
            f"{heads} heads in {groups} groups")
    zxbcdt = _dot("bte,ec->btc", u, w["in_proj"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * bc]
    dt = zxbcdt[..., 2 * inner + 2 * bc:]
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
    lead = xbc.shape[:2]
    # head h reads the B and C of group h // (heads / groups)
    x = xbc[..., :inner].reshape(lead + (groups, heads // groups, p))
    bm = xbc[..., inner:inner + bc].reshape(lead + (groups, n))
    cm = xbc[..., inner + bc:].reshape(lead + (groups, n))
    dt = jax.nn.softplus(dt + w["dt_bias"])                # no clamp
    by_group = (groups, heads // groups)
    y = recurrence(x, dt.reshape(lead + by_group),
                   w["A_log"].reshape(by_group), bm, cm,
                   w["D"].reshape(by_group))
    # the gate BEFORE the norm; the mean square over a GROUP's channels
    y = rms_norm((y.reshape(z.shape) * jax.nn.silu(z)).reshape(
        lead + (groups, inner // groups)),
        w["norm"].reshape(groups, inner // groups), sizes["norm_eps"])
    # departure: these heads' part of the output projection goes on
    return _dot("btc,ce->bte", y.reshape(z.shape), w["out_proj"])


def relu2(x, up, down):
    """``down relu(up x)^2``: two matrices, no gate."""
    return _dot("...f,fe->...e",
                jnp.square(jax.nn.relu(_dot("...e,ef->...f", x, up))), down)


def gates(x, w, sizes):
    """(tokens, published experts): ``g_e`` where expert ``e`` is one of
    the token's top-k by ``s + bias``, 0 elsewhere."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_dot("...e,en->...n", x, w["wg"], "router"))
    if s.shape[-1] != (sizes.get("num_experts_published")
                       or sizes["n_routed_experts"]):
        raise ReferenceMismatch(f"a router over {s.shape[-1]} experts")
    biased = s + w["bias"]
    chosen = biased >= jax.lax.top_k(biased, k)[0][..., -1:]
    picked = jnp.where(chosen, s, 0.0)
    return sizes["routed_scaling_factor"] * picked \
        / (picked.sum(-1, keepdims=True) + 1e-20)


def bias_after_step(x, w, sizes, rate):
    """The choice's bias after a training step on the tokens ``x`` under
    the balancing rule (Wang et al., "Auxiliary-loss-free load
    balancing"): ``b_e - rate * sign(c_e - mean(c))``, ``c_e`` the
    tokens whose top-k hold expert ``e``, over all published experts."""
    c = (gates(x, w, sizes) > 0).reshape(-1, w["bias"].shape[0]).sum(0)
    return w["bias"] - rate * jnp.sign(c - c.mean())


def latent_moe(x, w, sizes):
    """The expert layer's branch: the router and the shared expert on
    the stream, the routed experts in the latent."""
    g = gates(x, w, sizes)
    first = sizes.get("first_held_expert", 0)
    u = _dot("...e,el->...l", x, w["w_latent_in"])
    if u.shape[-1] != sizes["moe_latent_size"] \
            or w["w_up"].shape[1:] != (sizes["moe_latent_size"],
                                       sizes["moe_intermediate_size"]):
        raise ReferenceMismatch(f"experts of {w['w_up'].shape[1:]} in a "
                                f"latent of {u.shape[-1]}")
    r = jnp.zeros_like(u)
    # departure: the held experts alone (a loop and a gate)
    for j in range(w["w_up"].shape[0]):
        r = r + g[..., first + j, None] * relu2(u, w["w_up"][j],
                                                w["w_down"][j])
    return _dot("...l,le->...e", r, w["w_latent_out"]) \
        + relu2(x, w["ws_up"], w["ws_down"])


def _forward(layers, sizes, ids, pos):
    del pos                          # no layer turns by a position
    walk = _Walk(layers)
    eps, hid = sizes["norm_eps"], sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    pattern = sizes["hybrid_override_pattern"]
    if len(pattern) != sizes["num_hidden_layers"] \
            or set(pattern) - set(KINDS):
        raise ReferenceMismatch(
            f"hybrid_override_pattern {pattern!r} for "
            f"{sizes['num_hidden_layers']} layers")
    for letter in pattern:
        u = rms_norm(x, walk.scale(), eps)
        if letter == "M":
            x = x + mixer(u, walk.take(*MIXER), sizes)
        elif letter == "*":
            x = x + attention(u, walk.take(*ATTN), sizes)
        else:
            x = x + latent_moe(u, walk.take(*EXPERTS), sizes)
    x = rms_norm(x, walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])    # untied, as published
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def nemotron_h_decoder(layers, sizes, ids, pos):
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
