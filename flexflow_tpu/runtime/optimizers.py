"""Optimizers: SGD (+momentum/nesterov) and Adam, matching the reference's
update semantics (``src/runtime/optimizer.cc:158,449`` /
``optimizer_kernel.cu:77-196``).

Gradient sync: the reference launches per-view ncclAllReduce before the
update. Here weights are replicated (or sharded) via NamedSharding in the
jitted step, so XLA inserts the all-reduce/reduce-scatter automatically —
ParameterSyncType.NCCL and PS both map to this path.

Implemented as pure (init_state, update) pairs over pytrees — optax-style,
hand-rolled so the update math exactly mirrors the reference kernels.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer:
    def init_state(self, params):
        raise NotImplementedError

    def update(self, params, grads, state, step):
        """Returns (new_params, new_state). `step` is 1-based."""
        raise NotImplementedError

    def next(self):  # reference Optimizer::next() parity (per-step hook)
        pass


class SGDOptimizer(Optimizer):
    """Reference ``SGDOptimizer`` (``optimizer_kernel.cu:77-100``):
    grad += wd*w;  v = momentum*v + grad;  (nesterov: grad += momentum*v)
    w -= lr * (grad or v)."""

    def __init__(self, lr: float = 0.01, momentum: float = 0.0,
                 nesterov: bool = False, weight_decay: float = 0.0):
        self.lr = lr
        self.momentum = momentum
        self.nesterov = nesterov
        self.weight_decay = weight_decay

    def init_state(self, params):
        if self.momentum == 0.0:
            return {}
        return {"v": jax.tree.map(jnp.zeros_like, params)}

    def update(self, params, grads, state, step):
        lr = jnp.asarray(self.lr, jnp.float32)
        wd = self.weight_decay

        if self.momentum == 0.0:
            new_params = jax.tree.map(
                lambda w, g: w - (lr * (g + wd * w)).astype(w.dtype),
                params, grads)
            return new_params, state

        def upd(w, g, v):
            g = g + wd * w
            v = self.momentum * v + g
            step_dir = g + self.momentum * v if self.nesterov else v
            return w - (lr * step_dir).astype(w.dtype), v

        flat = jax.tree.map(upd, params, grads, state["v"],
                            is_leaf=lambda x: isinstance(x, jnp.ndarray))
        new_params = jax.tree.map(lambda t: t[0], flat,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[1], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"v": new_v}


class AdamOptimizer(Optimizer):
    """Reference ``AdamOptimizer`` (``optimizer.cc:449``,
    ``optimizer_kernel.cu:196``): bias-corrected alpha_t, decoupled-from-
    nothing weight decay folded into the gradient (L2 style, as the
    reference does)."""

    def __init__(self, alpha: float = 0.001, beta1: float = 0.9,
                 beta2: float = 0.999, weight_decay: float = 0.0,
                 epsilon: float = 1e-8):
        self.alpha = alpha
        self.beta1 = beta1
        self.beta2 = beta2
        self.weight_decay = weight_decay
        self.epsilon = epsilon

    @property
    def lr(self):
        return self.alpha

    def init_state(self, params):
        return {"m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params)}

    def update(self, params, grads, state, step):
        t = step.astype(jnp.float32)
        alpha_t = self.alpha * jnp.sqrt(1.0 - self.beta2 ** t) \
            / (1.0 - self.beta1 ** t)

        def upd(w, g, m, v):
            g = (g + self.weight_decay * w).astype(jnp.float32)
            m = self.beta1 * m + (1 - self.beta1) * g
            v = self.beta2 * v + (1 - self.beta2) * g * g
            w = w - (alpha_t * m / (jnp.sqrt(v) + self.epsilon)).astype(w.dtype)
            return w, m, v

        flat = jax.tree.map(upd, params, grads, state["m"], state["v"])
        is_t = lambda x: isinstance(x, tuple)
        return (jax.tree.map(lambda t3: t3[0], flat, is_leaf=is_t),
                {"m": jax.tree.map(lambda t3: t3[1], flat, is_leaf=is_t),
                 "v": jax.tree.map(lambda t3: t3[2], flat, is_leaf=is_t)})
