"""Plain reference for the hybrid linear-attention / latent-attention
decoders with sparse experts (``model_type: kimi_linear``): the forward
pass and the loss in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no
sort, no grouped product, no bf16 operand: the gated delta rule runs
TOKEN BY TOKEN (a ``lax.scan`` over the sequence that carries each
head's d x d state; nothing of the program's chunked algebra), the
convolution is a loop over its taps, latent attention the explicit s x s
softmax in blocks of query rows so that 8192 positions fit beside a
training step's state, and the experts a Python loop over the experts
held, each applied to every token under its gate.

Written from the published description, Kimi-Linear-48B-A3B's
``config.json`` and its layer equations (Kimi Linear, arXiv:2510.26692;
ISSUE 35 states them):

  block      h <- h + Op(RMSNorm(h)); h <- h + FF(RMSNorm(h)); a final
             RMSNorm before the head; no bias in any projection
  KDA        q, k, v = silu(conv(x W)), conv depthwise, causal, K taps,
             zeros left of position 0; a head at a time
             q <- q / |q| * d^-1/2, k <- k / |k|;
             g_t = -exp(A_log) * softplus(Wfb (Wfa x_t) + dt_bias), a
             channel; beta_t = sigmoid(x_t Wb), a head; state S (d x d,
             keys by values) from zeros:
               S <- Diag(exp(g_t)) S
               S <- S + beta_t k_t (v_t - S^T k_t)^T
               o_t = S^T q_t
             y_t = Wo [RMSNorm_d(o_t; w) * sigmoid(Wgb (Wga x_t))]
  latent     q = x Wq (no q latent); [c ; k_r] = x Wkva; c <- RMSNorm(c);
             [k_nope ; v] = c Wkvb; k_h = [k_nope_h ; k_r], the k_r
             shared by every head and NOT rotated, nor q's last entries
             (``mla_use_nope``); causal softmax of q k / sqrt(d_qk)
  FF         SwiGLU in the first ``first_k_dense_replace`` layers; after
             them s = sigmoid(u Wg), the choice the top-k of s + bias,
             the gates the chosen s_i over their sum
             (``moe_renormalize``) times ``routed_scaling_factor``; each
             expert a SwiGLU; plus one shared expert on every token

``layers``, ``sizes``, ``ids``, ``pos`` as in ``transformer_ref.py``;
``sizes`` carries the config.json keys plus ``num_experts_published``
and ``first_held_expert``. Layers are numbered from 1 in
``linear_attn_config``.

Departures from the published model, each also a line where it happens:
  * the share of a 32-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the top-k and the gates'
    normalisation run over all of them and what the absent ones would
    have added is left out; the vocabulary is the slice ``vocab_size``
    says;
  * ``|q|`` and ``|k|`` are the root of the squares' sum plus 1e-6, as
    the family's implementation takes them;
  * the projections come in the program's layout: (hidden, heads, d),
    (rank, heads, d) and (heads, d, hidden); a convolution's taps as
    (heads, d, K);
  * the routers' bias is whatever the weights hold: it corrects the
    choice only and no gradient reaches it;
  * ``pos`` is taken and not read: no layer has a positional encoding.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the s x s scores held at a time
SEGMENT = 64              # tokens between the states a backward pass keeps

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


KDA = ("wq", "conv_q", "wk", "conv_k", "wv", "conv_v", "wf_a", "wf_b",
       "A_log", "dt_bias", "wb", "wg_a", "wg_b", "o_norm", "wo")
LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
EXPERTS = ("wg", "bias", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
           "ws_down")


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


def unit(x):
    """x / |x|_2 over the last axis (departure: 1e-6 under the root)."""
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule_by_token(q, k, v, g, beta):
    """The recurrence, one token at a time. ``q``, ``k``, ``g``:
    (b, s, heads, d); ``v``: (b, s, heads, dv); ``beta``: (b, s, heads).
    Returns ``o`` (b, s, heads, dv).

    The walk is cut into segments of ``SEGMENT`` tokens only for what a
    backward pass stores: the state at each segment's start instead of
    at every token (17 GB a layer at 8192 tokens); the steps and their
    order are the same."""
    def step(state, x):                         # state: (b, heads, d, dv)
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
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


def kda_inputs(x, w):
    """``q, k, v, g, beta`` as the recurrence takes them."""
    def mixed(name):
        proj = w["w" + name]
        z = _dot("bse,ehd->bshd", x, proj)
        taps = w["conv_" + name]               # departure: the layout
        z = short_conv(z.reshape(z.shape[:2] + (-1,)),
                       taps.reshape(-1, taps.shape[-1]))
        return jax.nn.silu(z).reshape(z.shape[:2] + proj.shape[1:])

    d = w["wq"].shape[-1]
    f = _dot("bsr,rhd->bshd", _dot("bse,er->bsr", x, w["wf_a"]), w["wf_b"])
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"])
    beta = jax.nn.sigmoid(_dot("bse,eh->bsh", x, w["wb"]))
    return (unit(mixed("q")) * d ** -0.5, unit(mixed("k")), mixed("v"), g,
            beta)


def kda(x, w, sizes):
    o = delta_rule_by_token(*kda_inputs(x, w))
    gate = jax.nn.sigmoid(
        _dot("bsr,rhd->bshd", _dot("bse,er->bsr", x, w["wg_a"]), w["wg_b"]))
    return _dot("bshd,hde->bse",
                rms_norm(o, w["o_norm"], sizes["rms_norm_eps"]) * gate,
                w["wo"])


def latent_attention(x, w, sizes):
    """No q latent (``q_lora_rank: null``) and no rotation
    (``mla_use_nope``)."""
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank = sizes["kv_lora_rank"]
    q = _dot("bse,ehd->bshd", x, w["wq"])
    kv_a = _dot("bse,er->bsr", x, w["wkv_a"])
    c_kv = rms_norm(kv_a[..., :rank], w["kv_norm"], sizes["rms_norm_eps"])
    kv = _dot("bsr,rhd->bshd", c_kv, w["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    # one further key of d_r entries, shared by every head, as it is
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kv_a[:, :, None, rank:],
                                  k_nope.shape[:-1] + (dr,))], -1)
    s = x.shape[1]
    outs = []
    for lo in range(0, s, QUERY_ROWS):          # blocks of query rows
        rows = jnp.arange(lo, min(lo + QUERY_ROWS, s))
        sc = _dot("bqhd,bkhd->bhqk", q[:, lo:lo + QUERY_ROWS], k) \
            / math.sqrt(dn + dr)
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc,
                       -jnp.inf)
        outs.append(_dot("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1),
                         v))
    return _dot("bqhd,hde->bqe", jnp.concatenate(outs, 1), w["wo"])


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def gates(x, w, sizes):
    """(tokens, published experts): ``g_i`` where expert ``i`` is one of
    the token's top-k by ``s + bias``, 0 elsewhere."""
    k = sizes["num_experts_per_token"]
    s = jax.nn.sigmoid(_dot("...e,en->...n", x, w["wg"], "router"))
    # the bias corrects the choice only; num_expert_group = topk_group
    # = 1, so the grouped top-k is a plain one
    corrected = s + jax.lax.stop_gradient(w["bias"])
    chosen = corrected >= jax.lax.top_k(corrected, k)[0][..., -1:]
    picked = jnp.where(chosen, s, 0.0)
    # moe_renormalize, then routed_scaling_factor
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


def shared(x, w):
    return swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def layer_kinds(sizes) -> list:
    """``"kda"`` or ``"latent"`` for each of the layers, first to last."""
    lin = sizes["linear_attn_config"]
    kinds = []
    for n in range(1, sizes["num_hidden_layers"] + 1):   # from 1
        if (n in lin["kda_layers"]) == (n in lin["full_attn_layers"]):
            raise ReferenceMismatch(
                f"layer {n} is in both or in neither of kda_layers and "
                f"full_attn_layers")
        kinds.append("kda" if n in lin["kda_layers"] else "latent")
    return kinds


def _log_probs(layers, sizes, ids, pos):
    del pos                                     # departure: never read
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    x = walk.matrix(sizes["vocab_size"], hid)[ids]
    for i, kind in enumerate(layer_kinds(sizes)):
        u = rms_norm(x, walk.scale(), eps)
        if kind == "kda":
            x = x + kda(u, walk.take(*KDA), sizes)
        else:
            x = x + latent_attention(u, walk.take(*LATENT), sizes)
        u = rms_norm(x, walk.scale(), eps)
        if i < sizes["first_k_dense_replace"]:
            ffn = sizes["intermediate_size"]
            x = x + swiglu(u, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                           walk.matrix(ffn, hid))
        else:
            w = walk.take(*EXPERTS)
            x = x + routed(u, w, sizes) + shared(u, w)
    x = rms_norm(x, walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def linear_latent_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _log_probs(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """The mean cross-entropy of the head against ``labels`` (n, seq)."""
    with jax.default_matmul_precision("highest"):
        lp = _log_probs(layers, sizes, ids, pos)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))
