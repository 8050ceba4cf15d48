"""Fused Pallas optimizer update — the ``opt_update:fused`` kernel tier.

The reference runs its Adam update as one CUDA kernel per parameter view
(``optimizer_kernel.cu:196``). XLA usually fuses the tree-mapped jnp
update well, but on the ZeRO-sharded path the per-shard update is small
and bandwidth-bound: this kernel does the whole Adam step — weight-decay
fold, both moment updates, bias-corrected step — in ONE HBM pass over
(w, g, m, v), writing (w', m', v') without intermediate materialization.

The leaf is viewed as a 2-D array and streamed through VMEM block by
block over a grid, so a leaf of any size compiles (BERT's 30522x1024
word embeddings included) inside the default scoped-VMEM budget.

Semantics exactly mirror ``runtime.optimizers.AdamOptimizer.update`` (the
parity oracle in tests/test_kernel_tier.py): the registry predicate gates
it to TPU + Adam; interpret mode exists for CPU numerics tests only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import pallas_interpret

_LANES = 128
_SUBLANES = 16  # min tile height of the narrowest weight dtype (bf16)
_MAX_BLOCK_COLS = 1024
# Elements per block. Four inputs and three outputs, each double
# buffered by the Pallas pipeline, at 4 B: 14 x 512 KiB = 7 MiB, which
# leaves the kernel's own temporaries room under the default 16 MiB
# scoped-VMEM limit of a v5e.
_BLOCK_ELEMS = 128 * 1024


def _adam_kernel(beta1, beta2, eps, wd, scal_ref, w_ref, g_ref, m_ref,
                 v_ref, ow_ref, om_ref, ov_ref):
    alpha_t = scal_ref[0, 0]
    w32 = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) + wd * w32
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    step = alpha_t * m / (jnp.sqrt(v) + eps)
    ow_ref[:] = (w32 - step.astype(ow_ref.dtype)
                 .astype(jnp.float32)).astype(ow_ref.dtype)
    om_ref[:] = m
    ov_ref[:] = v


def _view_2d(shape):
    """(rows, cols) of the 2-D view the kernel streams. A lane-aligned
    trailing dim is kept (collapsing leading dims moves no data under
    the TPU's tiled layout); anything else is flattened onto 128 lanes
    and padded up to whole tiles."""
    n = 1
    for s in shape:
        n *= int(s)
    if len(shape) >= 2 and shape[-1] % _LANES == 0:
        return n // shape[-1], int(shape[-1])
    rows = pl.cdiv(n, _LANES)
    return pl.cdiv(rows, _SUBLANES) * _SUBLANES, _LANES


def _to_2d(x, rows, cols):
    flat = x.reshape(-1)
    pad = rows * cols - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols)


def fused_adam_update(w, g, m, v, alpha_t, *, beta1: float = 0.9,
                      beta2: float = 0.999, eps: float = 1e-8,
                      wd: float = 0.0, interpret=None):
    """One-pass Adam update for a single parameter leaf.

    ``alpha_t`` is the bias-corrected step size (traced — it depends on
    the step counter), fed through SMEM. Returns ``(w', m', v')`` with
    the exact update math of ``AdamOptimizer.update``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    n, shape = w.size, w.shape
    rows, cols = _view_2d(shape)
    w2, g2 = _to_2d(w, rows, cols), _to_2d(g, rows, cols)
    m2 = _to_2d(m.astype(jnp.float32), rows, cols)
    v2 = _to_2d(v.astype(jnp.float32), rows, cols)
    scal = jnp.asarray(alpha_t, jnp.float32).reshape(1, 1)
    # a block dim is a tile multiple or the whole array dim; the last
    # block along each grid axis may be partial (Pallas masks it)
    bc = min(cols, _MAX_BLOCK_COLS)
    br = min(rows, _BLOCK_ELEMS // bc // _SUBLANES * _SUBLANES)
    blk = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    kern = functools.partial(_adam_kernel, float(beta1), float(beta2),
                             float(eps), float(wd))
    ow, om, ov = pl.pallas_call(
        kern,
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        out_shape=(
            jax.ShapeDtypeStruct((rows, cols), w.dtype),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            blk, blk, blk, blk,
        ],
        out_specs=(blk, blk, blk),
        name="fused_adam_update",
        interpret=bool(interpret),
    )(scal, w2, g2, m2, v2)
    unflat = lambda a: a.reshape(-1)[:n].reshape(shape)  # noqa: E731
    return unflat(ow), unflat(om), unflat(ov)
