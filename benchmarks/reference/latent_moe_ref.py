"""Plain reference for the DeepSeek-V3-shaped configurations (latent
attention, sigmoid-routed experts with a shared one, a multi-token-
prediction module): the forward pass and the loss in straightforward
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
No kernel, no sort, no grouped product, no bf16 operand: attention is the
explicit s x s softmax, computed in blocks of query rows so that 4096
positions fit beside a training step's state, and the experts are a
Python loop over the experts held, each applied to every token under a
mask.

Written from the published description: JoyAI-LLM-Flash's ``config.json``
(its keys are DeepSeek-V3's) and, for what the config only counts,
DeepSeek-V3's report (arXiv:2412.19437). ``layers``, ``sizes``, ``ids``,
``pos`` as in ``transformer_ref.py``; ``sizes`` carries the config.json
keys plus ``n_routed_experts_published``, ``first_held_expert`` and
``mtp_loss_weight``.

Departures from the published model, each also a line where it happens:
  * the share of a 16-chip deployment: ``n_routed_experts`` experts are
    held (``first_held_expert`` onwards) of ``n_routed_experts_
    published``; the router, the top-k and the gates' normalisation run
    over all of them and what the absent ones would have added is left
    out; the vocabulary is the slice ``vocab_size`` says;
  * the multi-token-prediction module's form is DeepSeek-V3's (the
    config names only its count), its loss weight ``mtp_loss_weight``;
  * the routers' correction bias is whatever the weights hold: it
    corrects the choice only and no gradient reaches it.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 512          # rows of the s x s scores held at a time

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


ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
EXPERTS = ("wg", "bias", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
           "ws_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """Rotate the interleaved pairs ``(2i, 2i + 1)`` of the last axis
    (``rope_interleave: true``) by ``pos * theta ** (-2i / d)``;
    ``rope_scaling`` is null, so nothing else. x: (b, s, ..., d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * freq          # (b, s, d/2)
    while ang.ndim < x.ndim:
        ang = ang[:, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def latent_attention(x, pos, w, sizes):
    eps = sizes["rms_norm_eps"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank = sizes["kv_lora_rank"]
    c_q = rms_norm(_dot("bse,er->bsr", x, w["wq_a"]), w["q_norm"], eps)
    q = _dot("bsr,rhd->bshd", c_q, w["wq_b"])
    kv_a = _dot("bse,er->bsr", x, w["wkv_a"])
    c_kv = rms_norm(kv_a[..., :rank], w["kv_norm"], eps)
    kv = _dot("bsr,rhd->bshd", c_kv, w["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn],
                         rope(q[..., dn:], pos, sizes["rope_theta"])], -1)
    # one rotary key, shared by every head
    k_rope = rope(kv_a[..., rank:], pos, sizes["rope_theta"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
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
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_dot("...e,en->...n", x, w["wg"], "router"))
    # the bias corrects the choice only (noaux_tc; n_group = topk_group
    # = 1: no group limit)
    corrected = s + jax.lax.stop_gradient(w["bias"])
    chosen = corrected >= jax.lax.top_k(corrected, k)[0][..., -1:]
    picked = jnp.where(chosen, s, 0.0)
    return sizes["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)


def routed(x, w, sizes):
    """What the experts HELD here add: departure, the share (the sum in
    the gates' denominator still runs over all the chosen)."""
    g = gates(x, w, sizes)
    first = sizes.get("first_held_expert", 0)
    y = jnp.zeros_like(x)
    for j in range(w["w_gate"].shape[0]):        # a loop and a mask
        y = y + g[..., first + j, None] * swiglu(
            x, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    return y


def shared(x, w):
    return swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])


def _decoder_layer(x, pos, walk, sizes, experts: bool):
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    x = x + latent_attention(
        rms_norm(x, walk.take("scale")["scale"], eps), pos,
        walk.take(*ATTN), sizes)
    h = rms_norm(x, walk.take("scale")["scale"], eps)
    if experts:
        w = walk.take(*EXPERTS)
        return x + routed(h, w, sizes) + shared(h, w)
    ffn = sizes["intermediate_size"]
    return x + swiglu(h, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                      walk.matrix(ffn, hid))


def _both_heads(layers, sizes, ids, pos):
    """Log-probabilities of the main head and of the multi-token-
    prediction head (None without the module)."""
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    table = walk.matrix(sizes["vocab_size"], hid)
    x = emb = table[ids]
    for i in range(sizes["num_hidden_layers"]):
        x = _decoder_layer(x, pos, walk, sizes,
                           i >= sizes["first_k_dense_replace"])
    main = rms_norm(x, walk.take("scale")["scale"], eps)
    mtp = None
    if sizes.get("num_nextn_predict_layers", 0):
        # departure: DeepSeek-V3's module, section 2.2. The embedding of
        # token t + 1 (none after the last: zeros, and no target there)
        nxt = jnp.concatenate([emb[:, 1:], jnp.zeros_like(emb[:, :1])], 1)
        joined = jnp.concatenate(
            [rms_norm(nxt, walk.take("scale")["scale"], eps),
             rms_norm(x, walk.take("scale")["scale"], eps)], -1)
        h = _dot("bse,eh->bsh", joined, walk.matrix(2 * hid, hid))
        h = _decoder_layer(h, pos, walk, sizes, True)
        mtp = rms_norm(h, walk.take("scale")["scale"], eps)
    head = walk.matrix(hid, sizes["vocab_size"])     # shared by both
    walk.done()
    return (jax.nn.log_softmax(_dot("bse,ev->bsv", main, head), -1),
            None if mtp is None
            else jax.nn.log_softmax(_dot("bse,ev->bsv", mtp, head), -1))


def latent_moe_decoder(layers, sizes, ids, pos):
    """The main head's log-probabilities, (n, seq, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _both_heads(layers, sizes, ids, pos)[0]


def heads(layers, sizes, ids, pos):
    """Both heads' log-probabilities, for the tests."""
    with jax.default_matmul_precision("highest"):
        return _both_heads(layers, sizes, ids, pos)


def loss(layers, sizes, ids, pos, labels):
    """``CE_main + mtp_loss_weight * CE_mtp``, each a mean over its
    positions: the main head against ``labels`` (n, seq) at every
    position, the module's against token ``t + 2``, which the last two
    positions of a sequence do not have."""
    with jax.default_matmul_precision("highest"):
        main, mtp = _both_heads(layers, sizes, ids, pos)
        total = -jnp.mean(jnp.take_along_axis(main, labels[..., None], -1))
        if mtp is not None:
            ce = -jnp.mean(jnp.take_along_axis(
                mtp[:, :-2], ids[:, 2:, None], -1))
            total = total + sizes["mtp_loss_weight"] * ce
        return total
