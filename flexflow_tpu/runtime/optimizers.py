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

import functools
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


def fused_adam_tree_update(opt: AdamOptimizer, params, grads, state, step,
                           *, mesh=None, param_specs=None,
                           state_specs=None, interpret=None):
    """Adam update through the one-HBM-pass Pallas kernel
    (kernels/opt_update.py fused_adam_update), selected by the searched
    kernel tier (``opt_update: fused``). Same update math as
    ``AdamOptimizer.update`` — w/g/m/v stream through VMEM once instead
    of XLA's per-term HBM round trips.

    Inside a multi-device ``jit`` GSPMD cannot partition a Mosaic
    kernel, so with a ``mesh`` of more than one device each leaf runs
    under ``shard_map``. The update is elementwise, hence every operand
    takes one spec: the moments' (``state_specs``, the ZeRO placement)
    where given, else the parameter's own (``param_specs``); both are
    PartitionSpec pytrees congruent with ``params``. A ZeRO leaf's new
    weight comes back on the moments' spec and is constrained to the
    parameter's — the all-gather of the sharded update."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..kernels.opt_update import fused_adam_update

    t = step.astype(jnp.float32)
    alpha_t = opt.alpha * jnp.sqrt(1.0 - opt.beta2 ** t) \
        / (1.0 - opt.beta1 ** t)
    kern = functools.partial(
        fused_adam_update, beta1=opt.beta1, beta2=opt.beta2,
        eps=opt.epsilon, wd=opt.weight_decay, interpret=interpret)

    def upd(w, g, m, v, pspec, sspec):
        spec = sspec if sspec is not None else pspec
        # check_vma off: pallas_call outputs carry no varying-axes info
        nw, nm, nv = jax.shard_map(
            kern, mesh=mesh, in_specs=(spec,) * 4 + (P(),),
            out_specs=(spec,) * 3, check_vma=False)(w, g, m, v, alpha_t)
        if spec != pspec:
            nw = jax.lax.with_sharding_constraint(
                nw, NamedSharding(mesh, pspec))
        return nw, nm, nv

    if mesh is None or mesh.size == 1:
        flat = jax.tree.map(lambda *wgmv: kern(*wgmv, alpha_t), params,
                            grads, state["m"], state["v"])
    else:
        # spec trees are matched up to the structure of ``params``, so
        # a PartitionSpec (a tuple) or None arrives at ``upd`` whole
        flat = jax.tree.map(
            upd, params, grads, state["m"], state["v"], param_specs,
            state_specs if state_specs is not None
            else jax.tree.map(lambda _: None, params))
    is_t = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda t3: t3[0], flat, is_leaf=is_t),
            {"m": jax.tree.map(lambda t3: t3[1], flat, is_leaf=is_t),
             "v": jax.tree.map(lambda t3: t3[2], flat, is_leaf=is_t)})
