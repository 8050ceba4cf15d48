"""From a q or k projection's float32 output to the flash kernels'
operand in one pass: the heads' RMSNorm, the rotary embedding, the cast
to the compute type and the turn to heads-first, as two Pallas TPU
kernels (forward and backward).

``ops/nn_ops.py::MultiHeadAttentionOp`` with ``qk_norm`` and ``rope``
runs, between the projection and the attention, ``_rms``, ``_apply_rope``
and a ``swapaxes`` with a cast. XLA's version of the chain (the path of
every other shape and the tests' oracle) writes the normed array, the
rotated array and the turned bf16 copy to HBM one after another, with the
rotate-half as two lane slices at ``d / 2`` and a concatenate, and in the
backward the cotangent cast back to float32, turned, un-rotated and
un-normed as arrays of their own: six to twelve passes over the largest
activation of the layer for one multiply-add an element and one
reduction a head (PERF.md section 6, PR 50).

Here a grid step takes ``block_s`` positions of a few heads:

  x     (b, s, heads * d) float32   the projection's output, its own bytes
  n     = x * rsqrt(mean(x^2) + eps) * scale            a head's d entries
  y     = n * cos + rot(n) * sin     rot(n) = (-n[d/2:], n[:d/2])
  out   (b, heads, s, d) in the compute type            what flash takes

The grid is ``(b, s / block_s, heads / heads_per_step)`` with the heads
INNERMOST: the tables' block ``(block_s, d)`` does not depend on the
head, so Pallas fetches it once a row of the grid and every head of
those positions reads it from VMEM. A head's entries are whole lanes of
``x`` (``d`` a multiple of 128), so the turn to heads-first is the
output's block index and nothing is transposed in the kernel. ``rot`` is
a lane rotation by ``d / 2`` (``pltpu.roll``) and a sign by lane, which
:func:`rope_tables` folds into the sine table. At ``d`` 64 two heads
share a vreg's lanes and the rotation is inside half a vreg: another
kernel body, not built (``takes_kernel`` says no).

The backward kernel reads the cotangent heads-first in the compute type,
as the flash backward kernels write it, and ``x`` again (nothing is kept
but the operands), and writes ``dx`` in ``x``'s shape:

  dn     = dy * cos + rot^T(dy * sin)        rot^T = -rot
  dscale = sum over rows of dn * xh          xh = x * r
  dx     = r * (dn * scale - xh * mean(dn * scale * xh))

all float32. Where the output was repeated to ``repeat`` times the heads
(one broadcast of the bf16 heads-first array, made here after the
kernel), the kernel reads a group's ``repeat`` cotangents and sums them
in float32 first; the attention op no longer asks for that (the flash
kernels read grouped-query attention's k at its own heads, PR 52).
``dscale``
is accumulated over the heads of a grid row in a resident ``(8, d)``
block, one a (batch row, tile of positions), and the partials are summed
outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import pallas_interpret

LANES = 128
SUBLANES = 8
F32 = jnp.float32
#: what a grid step's blocks and values may count of Mosaic's default
#: scoped VMEM (16 MiB: the calls ask for no more, so XLA keeps the rest
#: for what it prefetches around them), the tiles of positions tried and
#: the heads a grid step may take
VMEM_BUDGET = 12 * 1024 * 1024
BLOCKS = (1024, 512, 256, 128, 64, 32, 16)
HEADS_A_STEP = (4, 2, 1)


def vmem_bytes(kernel: str, block_s: int, heads: int, d: int, repeat: int,
               dtype) -> int:
    """Working set of a grid step of ``kernel`` ("fwd" / "bwd") over
    ``heads`` heads: both buffers of its blocks and the float32 values
    of one head it holds at once."""
    one, size = block_s * d * 4, jnp.dtype(dtype).itemsize
    narrow = block_s * d * size
    if kernel == "fwd":
        return 2 * (heads * one + heads * narrow + 2 * one) + 4 * one
    return (2 * (heads * repeat * narrow + 2 * heads * one + 2 * one
                 + SUBLANES * d * 4) + 6 * one)


def tiles(kernel: str, s: int, heads: int, d: int, repeat: int, dtype):
    """``(block_s, heads_per_step)`` of ``kernel``: the most heads of
    ``HEADS_A_STEP`` that divide ``heads`` (longer rows for the DMA and
    fewer grid steps), then the largest of ``BLOCKS`` no longer than the
    sequence whose working set is inside ``VMEM_BUDGET``. ``(0, 0)``:
    nothing fits (fewer than 16 positions, or a head too wide)."""
    for hs in HEADS_A_STEP:
        if heads % hs:
            continue
        for block in BLOCKS:
            if block <= s and vmem_bytes(kernel, block, hs, d, repeat,
                                         dtype) <= VMEM_BUDGET:
                return block, hs
    return 0, 0


def takes_kernel(s: int, heads: int, d: int, repeat: int, dtype) -> bool:
    """Whether these shapes run the kernels: a head in whole lanes, the
    compute type float32 or bf16, and a tile of 16 positions of one head
    inside the VMEM budget, forward and backward."""
    return (d > 0 and d % LANES == 0 and heads > 0 and repeat > 0
            and jnp.dtype(dtype) in (jnp.dtype(F32), jnp.dtype(jnp.bfloat16))
            and all(tiles(k, s, heads, d, repeat, dtype)[0]
                    for k in ("fwd", "bwd")))


def rope_tables(pos, d: int, theta: float):
    """``(cos, sin)`` float32 ``(1 | b, s, d)`` for positions ``pos``
    (``(s,)`` shared by the batch or ``(b, s)``), the frequencies as
    ``nn_ops._apply_rope`` makes them. The sine comes SIGNED, negative in
    the first ``d / 2`` lanes: ``rot(n) * sin`` is then
    ``roll(n, d / 2) * sin`` here, and ``rot^T(dy * sin)`` is
    ``-roll(dy, d / 2) * sin`` (both halves of a table are equal, so the
    rotation passes the sine)."""
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    pf = pos.astype(F32)
    if pf.ndim == 1:
        pf = pf[None, :]
    freqs = pf[:, :, None] * inv[None, None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0).astype(F32)
    return jnp.cos(emb), jnp.sin(emb) * sign


def _normed(x, eps):
    """``(xh, r)`` of one head's block: ``x * r`` and the norm's
    reciprocal, float32."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(x_ref, scale_ref, cos_ref, sin_ref, o_ref, *, d, heads, eps):
    """``heads`` heads of one tile of positions: x (1, block_s, heads *
    d), the tables (1, block_s, d), out (1, heads, block_s, d)."""
    scale, cos, sin = scale_ref[...], cos_ref[0], sin_ref[0]
    for j in range(heads):
        n = _normed(x_ref[0, :, j * d:(j + 1) * d], eps)[0] * scale
        o_ref[0, j] = (n * cos + pltpu.roll(n, d // 2, 1) * sin
                       ).astype(o_ref.dtype)


def _bwd_kernel(dy_ref, x_ref, scale_ref, cos_ref, sin_ref, dx_ref, ds_ref,
                *, d, heads, repeat, eps, block_s, tail):
    """The same tile's cotangents: dy (1, heads * repeat, block_s, d),
    dx as x, and the tile's (1, 1, 8, d) sums for ``dscale``, resident
    over the grid's head axis. ``tail``: the rows the last tile has
    (0: it is whole); the rows past them are nobody's and are kept out
    of the sums."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, F32)

    scale, cos, sin = scale_ref[...], cos_ref[0], sin_ref[0]
    if tail:
        rows = jnp.where(pl.program_id(1) == pl.num_programs(1) - 1, tail,
                         block_s)
        live = jax.lax.broadcasted_iota(jnp.int32, (block_s, d), 0) < rows
    acc = jnp.zeros((SUBLANES, d), F32)
    for j in range(heads):
        dy = dy_ref[0, j * repeat].astype(F32)
        for g in range(1, repeat):
            dy = dy + dy_ref[0, j * repeat + g].astype(F32)
        xh, r = _normed(x_ref[0, :, j * d:(j + 1) * d], eps)
        dn = dy * cos - pltpu.roll(dy, d // 2, 1) * sin
        part = dn * xh
        if tail:
            part = jnp.where(live, part, 0.0)
        acc = acc + jnp.sum(part.reshape(-1, SUBLANES, d), axis=0)
        t = dn * scale
        dx_ref[0, :, j * d:(j + 1) * d] = r * (
            t - xh * jnp.mean(t * xh, axis=-1, keepdims=True))
    ds_ref[0, 0] += acc


def _table_spec(table, block_s, d):
    """The tables' block: a tile of positions whatever the head; row 0
    where the positions are the batch's."""
    if table.shape[0] == 1:
        return pl.BlockSpec((1, block_s, d), lambda bi, si, hi: (0, si, 0))
    return pl.BlockSpec((1, block_s, d), lambda bi, si, hi: (bi, si, 0))


# jitted with ``inline=True`` as the flash kernels' calls are
# (``flash_attention.py``): a step's layers of one shape trace each body
# once and the traced step is what it was without the jit.
@functools.partial(jax.jit, static_argnames=(
    "d", "eps", "dtype", "block_s", "hs", "interpret"), inline=True)
def _fwd_call(x, scale, cos, sin, d, eps, dtype, block_s, hs, interpret):
    b, s, width = x.shape
    heads = width // d
    size = jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, heads=hs, eps=eps),
        grid=(b, -(-s // block_s), heads // hs),
        in_specs=[
            pl.BlockSpec((1, block_s, hs * d), lambda bi, si, hi: (bi, si, hi)),
            pl.BlockSpec((1, d), lambda bi, si, hi: (0, 0)),
            _table_spec(cos, block_s, d), _table_spec(sin, block_s, d)],
        out_specs=pl.BlockSpec((1, hs, block_s, d),
                               lambda bi, si, hi: (bi, hi, si, 0)),
        out_shape=jax.ShapeDtypeStruct((b, heads, s, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # what XLA's scheduler may count on around the call (it takes a
        # call without one for no time at all): every entry read once,
        # written once, a dozen operations each
        cost_estimate=pl.CostEstimate(
            flops=12 * x.size, transcendentals=b * s * heads,
            bytes_accessed=x.size * (4 + size) + 4 * (cos.size + sin.size)),
        interpret=interpret, name="qk_norm_rope_fwd",
    )(x, scale.astype(F32).reshape(1, d), cos, sin)


@functools.partial(jax.jit, static_argnames=(
    "d", "repeat", "eps", "block_s", "hs", "interpret"), inline=True)
def _bwd_call(dy, x, scale, cos, sin, d, repeat, eps, block_s, hs,
              interpret):
    b, s, width = x.shape
    heads = width // d
    tiles_s = -(-s // block_s)
    wide = pl.BlockSpec((1, block_s, hs * d), lambda bi, si, hi: (bi, si, hi))
    dx, parts = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, heads=hs, repeat=repeat, eps=eps,
                          block_s=block_s, tail=s % block_s),
        grid=(b, tiles_s, heads // hs),
        in_specs=[
            pl.BlockSpec((1, hs * repeat, block_s, d),
                         lambda bi, si, hi: (bi, hi, si, 0)),
            wide, pl.BlockSpec((1, d), lambda bi, si, hi: (0, 0)),
            _table_spec(cos, block_s, d), _table_spec(sin, block_s, d)],
        out_specs=[wide, pl.BlockSpec((1, 1, SUBLANES, d),
                                      lambda bi, si, hi: (bi, si, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32),
                   jax.ShapeDtypeStruct((b, tiles_s, SUBLANES, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(20 + repeat) * x.size, transcendentals=b * s * heads,
            bytes_accessed=(dy.size * dy.dtype.itemsize + 8 * x.size
                            + 4 * (cos.size + sin.size))),
        interpret=interpret, name="qk_norm_rope_bwd",
    )(dy, x, scale.astype(F32).reshape(1, d), cos, sin)
    return dx, jnp.sum(parts, axis=(0, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _norm_rope(x, scale, cos, sin, d, repeat, eps, dtype, blocks, interpret):
    y = _fwd_call(x, scale, cos, sin, d, eps, dtype, *blocks[0], interpret)
    # grouped-query attention's k: every group's heads read one head
    return jnp.repeat(y, repeat, axis=1) if repeat > 1 else y


def _norm_rope_fwd(x, scale, cos, sin, d, repeat, eps, dtype, blocks,
                   interpret):
    return (_norm_rope(x, scale, cos, sin, d, repeat, eps, dtype, blocks,
                       interpret), (x, scale, cos, sin))


def _norm_rope_bwd(d, repeat, eps, dtype, blocks, interpret, res, dy):
    x, scale, cos, sin = res
    dx, dscale = _bwd_call(dy, x, scale, cos, sin, d, repeat, eps,
                           *blocks[1], interpret)
    # the tables come from the positions, integers: nothing reads theirs
    return (dx, dscale.astype(scale.dtype), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def qk_norm_rope(x, scale, tables, *, eps: float, dtype, repeat: int = 1,
                 block_s=None, heads_per_step=None, interpret=None):
    """``x`` (b, s, heads, d) float32, a q or k projection's output, ->
    (b, heads * repeat, s, d) in ``dtype``: each head's RMSNorm under
    ``scale`` (d,), the rotary embedding of :func:`rope_tables`'
    ``tables``, rounded once and heads-first, every head ``repeat``
    times in a row (``jnp.repeat`` on the heads' axis; the flash path
    leaves it at 1 since its kernels read a group's k head in place).
    Differentiable in ``x`` and ``scale``. ``block_s`` and ``heads_per_step`` override
    both kernels' tiles (the tests' and the timing script's)."""
    b, s, heads, d = x.shape
    if interpret is None:
        interpret = pallas_interpret()
    blocks = tuple(
        (block_s or derived[0], heads_per_step or derived[1])
        for derived in (tiles(k, s, heads, d, repeat, dtype)
                        for k in ("fwd", "bwd")))
    cos, sin = map(jax.lax.stop_gradient, tables)
    return _norm_rope(x.astype(F32).reshape(b, s, heads * d), scale, cos, sin,
                      d, repeat, float(eps), jnp.dtype(dtype), blocks,
                      bool(interpret))
