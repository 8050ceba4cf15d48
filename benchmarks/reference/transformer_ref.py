"""Plain references for the transformer configurations: the forward pass
in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no kernels, no bf16
operands, dropout off — written from the published descriptions and fed
the program's own initial parameters.

Each function takes

  ``layers``  the program's parameter-holding layers in graph order, as
              ``[(name, {weight_name: array}), ...]``; the reference
              walks them in the order the architecture fixes and raises
              ``ReferenceMismatch`` if a shape is not what that order
              demands (a rewritten graph is then a reported failure,
              not a silent pass),
  ``sizes``   the configuration file,
  ``ids``, ``pos``  ``(n, seq)`` int32 token and position ids,

and returns log-probabilities: ``(n, classes)`` for the classifier,
``(n, seq, vocab)`` for the language model.

Departures from the published models, all taken from what the program
under test builds (``flexflow_tpu/models/nlp.py``) so that the same
function is computed: GELU in its tanh form (BERT's paper code uses erf),
layer-norm epsilon 1e-5 (BERT: 1e-12), no token-type embedding, GPT-2's
output head untied from ``wte``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


class ReferenceMismatch(Exception):
    """The program's parameters do not have the architecture's shape."""


class _Walk:
    def __init__(self, layers):
        self.layers = list(layers)
        self.i = 0

    def table(self, rows: int, width: int):
        """An embedding table of exactly this shape."""
        kernel = self.take("kernel")["kernel"]
        if kernel.shape != (rows, width):
            raise ReferenceMismatch(
                f"layer {self.i - 1} ({self.layers[self.i - 1][0]}) is "
                f"{kernel.shape}, the architecture expects an embedding "
                f"of {(rows, width)} there")
        return kernel

    def take(self, *keys):
        if self.i >= len(self.layers):
            raise ReferenceMismatch(
                f"the program has {len(self.layers)} parameter layers; "
                f"the architecture needs more (next: {keys})")
        name, w = self.layers[self.i]
        self.i += 1
        if not set(keys) <= set(w):
            raise ReferenceMismatch(
                f"layer {self.i - 1} ({name}) holds {sorted(w)}, the "
                f"architecture expects {keys} there")
        return w

    def done(self):
        if self.i != len(self.layers):
            raise ReferenceMismatch(
                f"{len(self.layers) - self.i} parameter layers left over "
                f"(first: {self.layers[self.i][0]})")


def _ln(x, w):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * w["scale"] + w["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _dense(x, w):
    y = x @ w["kernel"]
    return y + w["bias"] if "bias" in w else y


def _attention(x, w, causal: bool):
    """Multi-head self-attention; weights ``wq/wk/wv`` (hidden, heads,
    head_dim), ``wo`` (heads, head_dim, hidden) and their biases."""
    q = jnp.einsum("ble,ehd->blhd", x, w["wq"]) + w["bq"]
    k = jnp.einsum("ble,ehd->blhd", x, w["wk"]) + w["bk"]
    v = jnp.einsum("ble,ehd->blhd", x, w["wv"]) + w["bv"]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.einsum("bqhd,hde->bqe", o, w["wo"]) + w["bo"]


def post_ln_encoder_classifier(layers, sizes, ids, pos):
    """BERT (Devlin et al. 2018): embeddings, LN; per layer
    ``x = LN(x + Attn(x)); x = LN(x + FFN(x))``; tanh pooler on the
    first token; classifier."""
    with jax.default_matmul_precision("highest"):
        w = _Walk(layers)
        h = sizes["hidden_size"]
        x = w.table(sizes["vocab_size"], h)[ids] \
            + w.table(sizes["max_position"], h)[pos]
        x = _ln(x, w.take("scale", "bias"))
        for _ in range(sizes["num_layers"]):
            x = _ln(x + _attention(x, w.take("wq", "wo"), False),
                    w.take("scale", "bias"))
            h = _gelu(_dense(x, w.take("kernel", "bias")))
            x = _ln(x + _dense(h, w.take("kernel", "bias")),
                    w.take("scale", "bias"))
        pooled = jnp.tanh(_dense(x[:, 0], w.take("kernel", "bias")))
        logits = _dense(pooled, w.take("kernel", "bias"))
        w.done()
        return jax.nn.log_softmax(logits, axis=-1)


def pre_ln_causal_decoder(layers, sizes, ids, pos):
    """GPT-2 (Radford et al. 2019): embeddings; per layer
    ``x = x + Attn(LN(x)); x = x + MLP(LN(x))`` with a causal mask;
    final LN; output head."""
    with jax.default_matmul_precision("highest"):
        w = _Walk(layers)
        # the program names its layers, and a dict of parameters sorts
        # by name: the head may come first. Find it by its shape.
        head = [i for i, (_, p) in enumerate(w.layers)
                if set(p) == {"kernel"}
                and p["kernel"].shape == (sizes["hidden_size"],
                                          sizes["vocab_size"])]
        if len(head) != 1:
            raise ReferenceMismatch(f"{len(head)} candidates for the "
                                    f"output head")
        lm_head = w.layers.pop(head[0])[1]
        h = sizes["hidden_size"]
        x = w.table(sizes["vocab_size"], h)[ids] \
            + w.table(sizes["max_position"], h)[pos]
        for _ in range(sizes["num_layers"]):
            x = x + _attention(_ln(x, w.take("scale", "bias")),
                               w.take("wq", "wo"), True)
            h = _ln(x, w.take("scale", "bias"))
            h = _gelu(_dense(h, w.take("kernel", "bias")))
            x = x + _dense(h, w.take("kernel", "bias"))
        x = _ln(x, w.take("scale", "bias"))
        w.done()
        return jax.nn.log_softmax(_dense(x, lm_head), axis=-1)
