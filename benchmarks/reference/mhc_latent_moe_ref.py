"""Plain reference for a DeepSeek-V3-shaped decoder whose residual is
``n`` streams under manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on hyper-connections, arXiv:2409.19606) and whose
rotary embedding is rescaled by YaRN (arXiv:2309.00071): the forward
pass and the loss in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no sort, no
grouped product, no scan, no bf16 operand: the Sinkhorn-Knopp projection
is its ``hc_sinkhorn_iters`` iterations written out one after another on
(tokens, n, n) matrices, attention the explicit s x s softmax in blocks
of query rows, the experts a Python loop over the experts held, each
applied to every token under a mask. It imports nothing of the program.

Written from the published description: Xing4.0-29B-A4B's
``config.json`` (``model_type: xing4_0``; every key but five is
DeepSeek-V3's, arXiv:2412.19437) and, for the five (``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min``,
``mhc_h_res_clamp_max``), the mHC paper. ``layers``: ``[(name,
{weight: array}), ...]`` in the order the model was built; ``sizes``: the
config.json keys plus ``n_routed_experts_published``,
``first_held_expert`` and ``mtp_loss_weight``; ``ids``, ``pos``: (n,
seq) int32.

Around a sub-layer ``F``, a token's streams ``X`` (n x C), ``x`` their
``n C`` entries as one vector under an RMSNorm:

    Hpre  = sigmoid(a_pre (x phi_pre) + b_pre)                   (n)
    Hpost = 2 sigmoid(a_post (x phi_post) + b_post)              (n)
    Hres  = SinkhornKnopp(clip(a_res mat(x phi_res) + b_res))    (n x n)
    X <- Hres X + Hpost^T F(Hpre X)

Departures from the published model, each also a line where it happens:
  * the share of an 8-chip group: ``num_attention_heads`` heads are held
    of the published 32 (the program's ``wq_b``, ``wkv_b`` and ``wo``
    have that many; their part of the output projection is what goes
    on), ``n_routed_experts`` experts of ``n_routed_experts_published``
    (the router, the top-k and the gates' normalisation run over all of
    them), the vocabulary is the slice ``vocab_size`` says;
  * assumed, the config naming no more than counts and constants: the
    embedding copied to the ``n`` streams and the streams summed before
    the final norm (hyper-connections' own); no learned weight in the
    maps' norm (it folds into ``phi``); ``hc_eps`` added to each
    Sinkhorn denominator; columns normalised first, rows last;
    ``rope_interleave`` true;
  * the multi-token-prediction module's form is DeepSeek-V3's; it reads
    the SUMMED trunk state, and its one layer runs on streams of its own
    (``h'`` copied, summed after); its loss weight ``mtp_loss_weight``;
  * the routers' correction bias is whatever the weights hold: it
    corrects the choice only and no gradient reaches it.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 512          # rows of the s x s scores held at a time

# The knobs, for the question "would this be caught": ``rounded_operands``
# rounds both operands of every matrix product to a narrower type first
# (the sums stay float32), as an MXU fed that type would; ``without``
# leaves part of the mathematics out. Left alone, nothing is rounded and
# nothing left out: that is the reference.
_ROUND = {"matmul": None, "router": None}
_OFF = {"dynamic_maps": False, "yarn": False}


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


@contextlib.contextmanager
def without(dynamic_maps=False, yarn=False):
    """Inside, a WRONG model: ``dynamic_maps`` drops the token-dependent
    part of every map (``a_* = 0``), ``yarn`` the rescaling of the
    rotary embedding and of the scores."""
    before = dict(_OFF)
    _OFF.update(dynamic_maps=dynamic_maps, yarn=yarn)
    try:
        yield
    finally:
        _OFF.update(before)


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


MAPS = ("phi", "b_pre", "b_post", "b_res", "alpha")
ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
EXPERTS = ("wg", "bias", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
           "ws_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


# ----------------------------------------------------------------------
# the residual streams
# ----------------------------------------------------------------------
def sinkhorn_knopp(logits, iters: int, eps: float):
    """(..., n, n) -> doubly stochastic to the iterations' accuracy:
    ``exp``, then ``iters`` times the columns and then the rows divided
    by their sums (the paper's ``T_r(T_c(M))``). Departure (assumed):
    ``eps`` is added to each sum."""
    m = jnp.exp(logits)
    for _ in range(iters):                                  # literally
        m = m / (m.sum(-2, keepdims=True) + eps)            # columns
        m = m / (m.sum(-1, keepdims=True) + eps)            # rows
    return m


def stream_maps(streams, w, sizes):
    """``Hpre`` (.., n), ``Hpost`` (.., n), ``Hres`` (.., n, n) of every
    token of ``streams`` (b, s, n, C)."""
    n = streams.shape[-2]
    flat = streams.reshape(streams.shape[:2] + (-1,))
    # departure (assumed): the norm has no learned weight
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + sizes["rms_norm_eps"])
    # one product with phi = [phi_pre | phi_post | phi_res]; float32
    # always, whatever a matrix unit would be fed
    t = jnp.einsum("bse,ek->bsk", x, w["phi"])
    a_pre, a_post, a_res = (0.0, 0.0, 0.0) if _OFF["dynamic_maps"] \
        else w["alpha"]
    pre = jax.nn.sigmoid(a_pre * t[..., :n] + w["b_pre"])
    post = 2.0 * jax.nn.sigmoid(a_post * t[..., n:2 * n] + w["b_post"])
    res = a_res * t[..., 2 * n:].reshape(t.shape[:2] + (n, n)) + w["b_res"]
    res = jnp.clip(res, sizes["mhc_h_res_clamp_min"],
                   sizes["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn_knopp(res, sizes["hc_sinkhorn_iters"],
                                     sizes["hc_eps"])


def hyper_connected(streams, w, sizes, sublayer):
    """``Hres X + Hpost^T F(Hpre X)``."""
    pre, post, res = stream_maps(streams, w, sizes)
    u = jnp.einsum("bsn,bsnc->bsc", pre, streams)
    return jnp.einsum("bsij,bsjc->bsic", res, streams) \
        + post[..., :, None] * sublayer(u)[..., None, :]


# ----------------------------------------------------------------------
# latent attention under YaRN
# ----------------------------------------------------------------------
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d, theta, scaling):
    """``theta ^ (-2i / d)`` for pair ``i``; under ``rope_scaling`` of
    type yarn, that below pair ``low`` (more than ``beta_fast`` turns
    over the original context), divided by ``factor`` from pair ``high``
    on (fewer than ``beta_slow`` turns), a linear blend between."""
    i = jnp.arange(0, d, 2, dtype=jnp.float32) / 2
    freq = theta ** (-2.0 * i / d)
    if not scaling or _OFF["yarn"]:
        return freq

    def pair(turns):
        return d * math.log(scaling["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / scaling["factor"] * ramp


def rope(x, pos, freq):
    """Rotate the interleaved pairs ``(2i, 2i + 1)`` of the last axis
    (departure, assumed: ``rope_interleave`` true) by ``pos * freq_i``.
    x: (b, s, ..., d)."""
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
    scaling = sizes.get("rope_scaling")
    freq = rope_frequencies(dr, sizes["rope_theta"], scaling)
    scale = 1.0 / math.sqrt(dn + dr)
    if scaling and not _OFF["yarn"]:
        # cos and sin times m(mscale) / m(mscale_all_dim), which is 1
        # where the two are equal, as published; the scores times
        # m(mscale_all_dim) squared
        assert scaling["mscale"] == scaling["mscale_all_dim"]
        scale *= yarn_mscale(scaling["factor"],
                             scaling["mscale_all_dim"]) ** 2
    c_q = rms_norm(_dot("bse,er->bsr", x, w["wq_a"]), w["q_norm"], eps)
    # departure: the heads held here, of the published 32
    q = _dot("bsr,rhd->bshd", c_q, w["wq_b"])
    kv_a = _dot("bse,er->bsr", x, w["wkv_a"])
    c_kv = rms_norm(kv_a[..., :rank], w["kv_norm"], eps)
    kv = _dot("bsr,rhd->bshd", c_kv, w["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, freq)], -1)
    # one rotary key, shared by every head
    k_rope = rope(kv_a[..., rank:], pos, freq)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:-1] + (dr,))], -1)
    s = x.shape[1]
    outs = []
    for lo in range(0, s, QUERY_ROWS):          # blocks of query rows
        rows = jnp.arange(lo, min(lo + QUERY_ROWS, s))
        sc = _dot("bqhd,bkhd->bhqk", q[:, lo:lo + QUERY_ROWS], k) * scale
        sc = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], sc,
                       -jnp.inf)
        outs.append(_dot("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1),
                         v))
    # departure: these heads' part of the output projection
    return _dot("bqhd,hde->bqe", jnp.concatenate(outs, 1), w["wo"])


# ----------------------------------------------------------------------
# feed-forwards
# ----------------------------------------------------------------------
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


def _decoder_layer(streams, pos, walk, sizes, experts: bool):
    """Two hyper-connected sub-layers, each with maps of its own; a
    sub-layer's input meets the sub-layer's own RMSNorm."""
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]

    def attention(u):
        return latent_attention(
            rms_norm(u, walk.take("scale")["scale"], eps), pos,
            walk.take(*ATTN), sizes)

    def feed_forward(u):
        h = rms_norm(u, walk.take("scale")["scale"], eps)
        if experts:
            w = walk.take(*EXPERTS)
            return routed(h, w, sizes) + shared(h, w)
        ffn = sizes["intermediate_size"]
        return swiglu(h, walk.matrix(hid, ffn), walk.matrix(hid, ffn),
                      walk.matrix(ffn, hid))

    streams = hyper_connected(streams, walk.take(*MAPS), sizes, attention)
    return hyper_connected(streams, walk.take(*MAPS), sizes, feed_forward)


def _copied(x, n):
    """Departure (assumed): every stream starts as a copy."""
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (n, x.shape[-1]))


def _both_heads(layers, sizes, ids, pos):
    """Log-probabilities of the main head and of the multi-token-
    prediction head (None without the module)."""
    walk = _Walk(layers)
    eps, hid, n = sizes["rms_norm_eps"], sizes["hidden_size"], \
        sizes["hc_mult"]
    table = walk.matrix(sizes["vocab_size"], hid)
    emb = table[ids]
    streams = _copied(emb, n)
    for i in range(sizes["num_hidden_layers"]):
        streams = _decoder_layer(streams, pos, walk, sizes,
                                 i >= sizes["first_k_dense_replace"])
    x = streams.sum(-2)        # departure (assumed): the streams summed
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
        # departure (assumed): streams of the module's own
        h = _decoder_layer(_copied(h, n), pos, walk, sizes, True).sum(-2)
        mtp = rms_norm(h, walk.take("scale")["scale"], eps)
    head = walk.matrix(hid, sizes["vocab_size"])     # shared by both
    walk.done()
    return (jax.nn.log_softmax(_dot("bse,ev->bsv", main, head), -1),
            None if mtp is None
            else jax.nn.log_softmax(_dot("bse,ev->bsv", mtp, head), -1))


def mhc_latent_moe_decoder(layers, sizes, ids, pos):
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
