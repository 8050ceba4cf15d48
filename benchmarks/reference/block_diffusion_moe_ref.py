"""Plain reference for a block-diffusion training step over a
grouped-query, softmax-routed mixture-of-experts decoder (``model_type:
sdar_moe``; SDAR, arXiv:2510.06303, whose training mask is BD3-LMs'
vectorised one, arXiv:2503.09573): the forward pass and the loss in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. No kernel, no tile, no
sort, no grouped product, no bf16 operand: the mask is an explicit
boolean array written from each position's half and block, the softmax
explicit over the masked scores in blocks of query rows (one after
another through ``jax.lax.map``, so that 8192 positions fit beside a
training step's state), and the experts a Python loop over the experts
held, each applied to every position under its gate. ISSUE 64 states
the equations (one sequence of L ids ``x`` in blocks of B; no bias in
any projection):

  u_b ~ U[0,1) a block,  t_b = t_min + (1 - t_min) u_b
  m_i = [v_i < t_b(i)],  v_i ~ U[0,1)                 b(i) = i // B
  z   = [where(m, MASK, x) ; x]   2L ids at positions [0..L-1, 0..L-1]
  w_i = m_i / t_b(i)
  h   = Embed[z]                                      (not scaled)
  u   = RMSNorm(h)
  q   = u Wq (2L,H,d)   k = u Wk (2L,G,d)   v = u Wv (2L,G,d)
  q   = rope(RMSNorm_d(q) s_q, pos)    k = rope(RMSNorm_d(k) s_k, pos)
  allowed(i, j), half(i) = i >= L, b(i) = (i mod L) // B:
      noised i, noised j:  b(j) == b(i)      noised i, clean j:  b(j) < b(i)
      clean i,  clean j:   b(j) <= b(i)      clean i,  noised j: never
  a[i,n,j] = softmax over allowed j of q[i,n] . k[j, n // (H/G)] / sqrt(d)
  h  <- h + concat_n(sum_j a[i,n,j] v[j, n // (H/G)]) Wo
  u   = RMSNorm(h);   s = softmax(u Wr) over all experts;  T = top-k of s
  g_e = s_e / sum_{e' in T} s_e'
  h  <- h + sum_{e in T, e held} g_e Wd,e (silu(Wg,e u) * Wu,e u)
  logits = RMSNorm(h)[:L] Wlm
  loss   = (1 / L) sum_i w_i (-log softmax(logits_i)[x_i])

The draw is the program's in EVAL mode: ``jax.random.uniform`` on the two
halves of ``jax.random.split(jax.random.key(eval_noise_seed))``, shapes
(n, L / B) and (n, L).

``layers``, ``sizes``, ``ids``, ``pos`` as in ``hybrid_conv_moe_ref.py``:
the program's parameter layers in the order they were built, and the
configuration's file (the config.json keys plus
``num_experts_published``, ``first_held_expert`` and the recipe's
``block_length``, ``mask_token_id``, ``t_min``, ``eval_noise_seed``).

Departures from the published model, each also a line where it happens:
  * the share of an 8-chip deployment: the experts whose weights are
    given are held (``first_held_expert`` onwards) of
    ``num_experts_published``; the router, the top-k and the gates'
    normalisation run over all of them and what the absent ones would
    have added is left out; the vocabulary is the slice ``vocab_size``
    says;
  * the projections come in the program's layout: (hidden, heads, d)
    and (heads, d, hidden);
  * the log-probabilities are returned with their L rows ROLLED by one,
    ``out[i] = P[(i + 1) mod L]``, the order in which the program hands
    them to a runner whose labels are ``roll(ids, -1)``: an order, not a
    value.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 256          # rows of the scores held at a time

# The knobs, for the questions "would a lower precision be caught" and
# "what does the comparison not see". ``rounded_operands`` rounds both
# operands of every matrix product to a narrower type first (the sums
# stay float32), as an MXU fed that type would. ``perturbed`` swaps one
# of the step's rules for a plausible wrong one. Left alone, nothing is
# rounded or swapped: that is the reference.
_ROUND = {"matmul": None, "router": None}
_WRONG = {"rule": None}
PERTURBATIONS = ("causal_clean_only", "own_block_clean_keys",
                 "consecutive_positions", "unit_weights")


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
def perturbed(rule):
    """Inside, one rule is another model's: ``causal_clean_only`` (the
    noised half sees what the clean half sees: the clean keys of its own
    and earlier blocks, and no noised key), ``own_block_clean_keys`` (a
    noised query also sees its own block's clean keys: the answer
    leaks), ``consecutive_positions`` (positions 0 .. 2L - 1 in place of
    0 .. L - 1 twice), ``unit_weights`` (every row of the loss weighs
    1)."""
    if rule not in PERTURBATIONS:
        raise ValueError(f"{rule!r} is none of {PERTURBATIONS}")
    before = dict(_WRONG)
    _WRONG["rule"] = rule
    try:
        yield
    finally:
        _WRONG.update(before)


def _dot(pattern, a, b, kind="matmul"):
    to = _ROUND[kind]
    if to is not None:
        a = a.astype(to).astype(jnp.float32)
        b = b.astype(to).astype(jnp.float32)
    return jnp.einsum(pattern, a, b)


class ReferenceMismatch(Exception):
    """The program's parameters do not have the architecture's shape."""


ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
EXPERTS = ("wg", "w_gate", "w_up", "w_down")
KIND = "block_diffusion_attention"


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


def noise(sizes, ids):
    """The eval-mode draw: ``(masked, t)``, (n, L) bool and float32.
    ``sizes["noise_key"]``, where a caller gives one, is drawn from in
    place of ``eval_noise_seed``'s key: a training step's own key, for a
    comparison of that step's gradients."""
    n, length = ids.shape
    block, t_min = sizes["block_length"], sizes["t_min"]
    key = sizes.get("noise_key")
    if key is None:
        key = jax.random.key(sizes["eval_noise_seed"])
    k_t, k_v = jax.random.split(key)
    u = jax.random.uniform(k_t, (n, length // block), jnp.float32)
    v = jax.random.uniform(k_v, (n, length), jnp.float32)
    t = jnp.repeat(t_min + (1.0 - t_min) * u, block, axis=1)
    return v < t, t


def weights(sizes, ids):
    """``w_i = m_i / t_b(i)``, (n, L)."""
    masked, t = noise(sizes, ids)
    if _WRONG["rule"] == "unit_weights":
        return jnp.ones_like(t)
    return masked.astype(jnp.float32) / t


def allowed(rows, keys, length: int, block: int):
    """The mask's entries for query positions ``rows`` and key positions
    ``keys`` of the 2 L, written from the halves and the blocks."""
    q_clean, k_clean = rows[:, None] >= length, keys[None, :] >= length
    qb = (rows[:, None] % length) // block
    kb = (keys[None, :] % length) // block
    noised_q = jnp.where(k_clean, kb < qb, kb == qb)
    if _WRONG["rule"] == "causal_clean_only":
        noised_q = k_clean & (kb <= qb)
    if _WRONG["rule"] == "own_block_clean_keys":
        noised_q = jnp.where(k_clean, kb <= qb, kb == qb)
    return jnp.where(q_clean, k_clean & (kb <= qb), noised_q)


def attention(u, pos, w, sizes):
    """One attention layer's branch over the 2 L positions."""
    eps, block = sizes["rms_norm_eps"], sizes["block_length"]
    # the norms over each head's entries come BEFORE the rotation
    q = rms_norm(_dot("bse,ehd->bshd", u, w["wq"]), w["q_norm"], eps)
    k = rms_norm(_dot("bse,ehd->bshd", u, w["wk"]), w["k_norm"], eps)
    q = rope(q, pos, sizes["rope_theta"])
    k = rope(k, pos, sizes["rope_theta"])
    v = _dot("bse,ehd->bshd", u, w["wv"])
    b, s, heads, d = q.shape
    kv = k.shape[2]
    # kv head j serves query heads j * heads / kv .. (j + 1) * heads / kv
    q = q.reshape(b, s, kv, heads // kv, d)
    n = s // QUERY_ROWS if s % QUERY_ROWS == 0 else 1
    keys = jnp.arange(s)

    def rows_block(args):
        q_rows, rows = args
        sc = _dot("bqjgd,bkjd->bjgqk", q_rows, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(allowed(rows, keys, s // 2, block),
                                     sc, -jnp.inf), axis=-1)
        return _dot("bjgqk,bkjd->bqjgd", a, v)

    outs = jax.lax.map(rows_block, (
        jnp.moveaxis(q.reshape((b, n, s // n) + q.shape[2:]), 1, 0),
        keys.reshape(n, s // n)))
    ctx = jnp.moveaxis(outs, 0, 1).reshape(b, s, heads, d)
    return _dot("bqhd,hde->bqe", ctx, w["wo"])


def swiglu(x, gate, up, down):
    return _dot("...f,fe->...e", jax.nn.silu(_dot("...e,ef->...f", x, gate))
                * _dot("...e,ef->...f", x, up), down)


def gates(x, w, sizes):
    """(positions, published experts): ``g_e`` where expert ``e`` is one
    of the position's top-k by its softmax score, 0 elsewhere."""
    s = jax.nn.softmax(_dot("...e,en->...n", x, w["wg"], "router"), -1)
    chosen = s >= jax.lax.top_k(s, sizes["num_experts_per_tok"])[0][..., -1:]
    picked = jnp.where(chosen, s, 0.0)
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


def _forward(layers, sizes, ids, pos):
    """log softmax of the head over the noised half's L rows."""
    walk = _Walk(layers)
    eps, hid = sizes["rms_norm_eps"], sizes["hidden_size"]
    length = ids.shape[1]
    if length % sizes["block_length"]:
        raise ReferenceMismatch(f"{length} tokens are not whole blocks of "
                                f"{sizes['block_length']}")
    kinds = sizes["layer_types"]
    if len(kinds) != sizes["num_hidden_layers"] or set(kinds) != {KIND}:
        raise ReferenceMismatch(
            f"{len(kinds)} layer_types {sorted(set(kinds))} for "
            f"{sizes['num_hidden_layers']} layers of kind {KIND!r}")
    masked, _ = noise(sizes, ids)
    # whether a token is masked is the draw's, never a comparison of ids
    z = jnp.concatenate([jnp.where(masked, sizes["mask_token_id"], ids),
                         ids], axis=1)
    z_pos = jnp.concatenate([pos, pos], axis=1)
    if _WRONG["rule"] == "consecutive_positions":
        z_pos = jnp.concatenate([pos, pos + length], axis=1)
    x = walk.matrix(sizes["vocab_size"], hid)[z]
    for _ in kinds:
        x = x + attention(rms_norm(x, walk.scale(), eps), z_pos,
                          walk.take(*ATTN), sizes)
        x = x + routed(rms_norm(x, walk.scale(), eps), walk.take(*EXPERTS),
                       sizes)
    x = rms_norm(x[:, :length], walk.scale(), eps)
    head = walk.matrix(hid, sizes["vocab_size"])
    walk.done()
    return jax.nn.log_softmax(_dot("bse,ev->bsv", x, head), -1)


def block_diffusion_moe_decoder(layers, sizes, ids, pos):
    """The head's log-probabilities for the noised half, (n, L, vocab),
    rolled by one along L: ``out[:, i] = P[:, (i + 1) mod L]``."""
    with jax.default_matmul_precision("highest"):
        return jnp.roll(_forward(layers, sizes, ids, pos), -1, axis=1)


def loss(layers, sizes, ids, pos, labels):
    """``(1 / L) sum_i w_i nll(P[i], x_i)`` a sequence, the mean over
    sequences, from the ROLLED rows and ``labels = roll(ids, -1)`` (n,
    L), as a runner hands them: the same sum in another order."""
    with jax.default_matmul_precision("highest"):
        lp = jnp.roll(_forward(layers, sizes, ids, pos), -1, axis=1)
        w = jnp.roll(weights(sizes, ids), -1, axis=1)
        nll = -jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
        return jnp.sum(w * nll) / nll.size
