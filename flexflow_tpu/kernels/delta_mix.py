"""From a q, k or v projection's float32 output to the operand the delta
rule's kernels read, in one pass: the causal depthwise taps, the SiLU,
the unit-length norm with its scale and the turn to heads-first, as two
Pallas TPU kernels (forward and backward).

``ops/recurrent_ops.py::GatedDeltaRuleOp.projections`` makes q, k and v
as ``silu(short_conv(x w))``, q and k then through ``_unit`` and q times
``d ** -0.5``. XLA's version of the chain (the path of every other shape
and the tests' oracle) writes the padded copy, the tapped sum, the SiLU,
the sum of squares and the scaled array as fusions of their own over a
(tokens, H d) float32 array, and in the backward un-norms, un-SiLUs,
un-taps and reduces the taps' gradient over the tokens as more: tens of
passes a layer-step over 64 to 128 MiB for a dozen operations an element
(PERF.md section 6, PR 63).

Here a grid step takes ``block_t`` tokens of a few heads:

  p     (b, t, heads * d) float32    the projection's output, its own rows
  c[t]  = sum_j taps[h, :, j] * p[t - (K - 1) + j]     zeros left of 0
  z     = c * sigmoid(c)
  out   = z * rsqrt(sum_d(z z) + eps) * scale          ``unit``; else z
  out   (b, heads, t, d) float32     what the chunks' terms take

The grid is ``(b, heads / heads_per_step, t / block_t)``, the tokens
INNERMOST. A head's entries are whole lanes of ``p`` (``d`` a multiple
of 128), so the turn to heads-first is the output's block index and
nothing is transposed in the kernel. The taps are K multiply-adds of the
tile shifted along the sublanes (``pltpu.roll`` of the tile under its
halo: the ``HALO`` rows before it, a second block of the same operand,
zeros at the first tile); no loop over tokens.

The backward kernel reads ``dout`` heads-first, ``p`` again and the taps
(nothing is kept but the operands), forms ``c``, ``z`` and the norm
again in VMEM for the tile and the ``HALO`` rows AFTER it (whose ``dc``
reaches back into the tile through the taps: ``p``'s and ``dout``'s next
blocks are two more operands, nothing past the last token), and writes
``dp`` in ``p``'s shape:

  dz    = r * scale * (dout - z * r^2 * sum_d(dout z))     r = rsqrt(..)
  dc    = dz * sigmoid(c) * (1 + c * (1 - sigmoid(c)))
  dp[s] = sum_j taps[:, j] * dc[s + (K - 1) - j]
  dtaps[:, j] = sum_t dc[t] * p[t - (K - 1) + j]

``dtaps`` is accumulated over the tiles of tokens in a resident ``(K,
8, d)`` block a head, one a batch row, and the partials are summed
outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import events
from ._interpret import pallas_interpret

LANES = 128
SUBLANES = 8
#: rows of the blocks before and after a tile: what K - 1 may reach
HALO = SUBLANES
F32 = jnp.float32
#: what a grid step's blocks and values may count of Mosaic's default
#: scoped VMEM (16 MiB: the calls ask for no more, so XLA keeps the rest
#: for what it prefetches around them), the tiles of tokens tried and
#: the heads a grid step may take
VMEM_BUDGET = 12 * 1024 * 1024
BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8)
HEADS_A_STEP = (4, 2, 1)


def vmem_bytes(kernel: str, block_t: int, heads: int, d: int,
               taps: int) -> int:
    """Working set of a grid step of ``kernel`` ("fwd" / "bwd") over
    ``heads`` heads: both buffers of its blocks and the float32 values
    of one head it holds at once (the tile under its halo, a shifted
    copy, ``c``, ``z`` and, backward, their cotangents)."""
    one, halo = block_t * d * 4, HALO * d * 4
    small = 2 * heads * (taps + taps * SUBLANES) * d * 4
    if kernel == "fwd":
        return 2 * heads * (2 * one + halo) + small + 6 * (one + halo)
    return 2 * heads * (3 * one + 3 * halo) + small + 12 * (one + 2 * halo)


def tiles(kernel: str, tokens: int, heads: int, d: int, taps: int):
    """``(block_t, heads_per_step)`` of ``kernel``: the most heads of
    ``HEADS_A_STEP`` that divide ``heads`` (longer rows for the DMA and
    fewer grid steps), then the largest of ``BLOCKS`` no longer than the
    sequence whose working set is inside ``VMEM_BUDGET``. ``(0, 0)``:
    nothing fits (fewer than 8 tokens, or a head too wide)."""
    for hs in HEADS_A_STEP:
        if heads % hs:
            continue
        for block in BLOCKS:
            if block <= tokens and vmem_bytes(kernel, block, hs, d,
                                              taps) <= VMEM_BUDGET:
                return block, hs
    return 0, 0


def takes_kernel(d: int, taps: int, tokens: int, dtype) -> bool:
    """Whether these shapes run the kernels: a head in whole lanes,
    float32, taps that reach no further than the halo, and a tile of 8
    tokens of one head inside the VMEM budget, forward and backward (a
    token count no tile divides is padded by the grid)."""
    return (d > 0 and d % LANES == 0 and 2 <= taps <= HALO + 1
            and jnp.dtype(dtype) == jnp.dtype(F32)
            and all(tiles(k, tokens, 1, d, taps)[0]
                    for k in ("fwd", "bwd")))


def _rows(shape, start):
    """Each row's position in the sequence: ``start`` + its index."""
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _tapped(ext, w, taps):
    """``sum_j w[j] * ext[r - (K - 1) + j]`` at every row ``r`` of
    ``ext`` (the first K - 1 rows wrap and are nobody's) and the K
    shifted copies; ``w``: (K, d). Added in ``short_conv``'s order."""
    shifted = [pltpu.roll(ext, taps - 1 - j, 0) if j < taps - 1 else ext
               for j in range(taps)]
    c = shifted[0] * w[0:1]
    for j in range(1, taps):
        c = c + shifted[j] * w[j:j + 1]
    return c, shifted


def _fwd_kernel(prev_ref, p_ref, w_ref, o_ref, *, d, heads, taps, unit,
                scale, eps):
    """``heads`` heads of one tile of tokens: p (1, block_t, heads * d)
    under the (1, HALO, heads * d) block before it, the taps (K, heads *
    d), out (1, heads, block_t, d)."""
    first = pl.program_id(2) == 0
    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        prev = jnp.where(first, 0.0, prev_ref[0, :, lanes])
        ext = jnp.concatenate([prev, p_ref[0, :, lanes]], axis=0)
        c = _tapped(ext, w_ref[:, lanes], taps)[0][HALO:]
        z = c * jax.nn.sigmoid(c)
        if unit:
            z = z * jax.lax.rsqrt(
                jnp.sum(z * z, axis=-1, keepdims=True) + eps) * scale
        o_ref[0, j] = z


def _bwd_kernel(prev_ref, p_ref, next_ref, do_ref, do_next_ref, w_ref,
                dp_ref, dw_ref, *, d, heads, taps, unit, scale, eps,
                block_t, tokens):
    """The same tile's cotangents: dout (1, heads, block_t, d) and the
    (1, heads, HALO, d) block after it, p with the blocks before and
    after, dp as p, and the batch row's (1, heads, K, 8, d) sums for
    ``dtaps``, resident over the grid's token axis. Where no tile
    divides ``tokens`` the rows past the last token are nobody's: they
    are kept out of ``p`` and of ``dc`` by their positions."""
    ti = pl.program_id(2)
    first, last = ti == 0, ti == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    rows = block_t + HALO               # the tile and the rows after it
    ragged = tokens % block_t != 0
    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        w = w_ref[:, lanes]
        ext = jnp.concatenate(
            [jnp.where(first, 0.0, prev_ref[0, :, lanes]),
             p_ref[0, :, lanes], next_ref[0, :, lanes]], axis=0)
        do = jnp.concatenate(
            [do_ref[0, j], jnp.where(last, 0.0, do_next_ref[0, j])], axis=0)
        if ragged:
            at = _rows(ext.shape, ti * block_t - HALO)
            ext = jnp.where(at < tokens, ext, 0.0)
        c, shifted = _tapped(ext, w, taps)
        c = c[HALO:]
        sig = jax.nn.sigmoid(c)
        z = c * sig
        if unit:
            r = jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + eps)
            dz = r * scale * (do - z * (r * r * jnp.sum(
                do * z, axis=-1, keepdims=True)))
        else:
            dz = do
        dc = dz * (sig * (1.0 + c * (1.0 - sig)))
        if ragged:
            dc = jnp.where(_rows(dc.shape, ti * block_t) < tokens, dc, 0.0)
        # dp[s] = sum_j w[j] dc[s + (K - 1) - j]: the rows AFTER s
        dp = dc * w[taps - 1:taps]
        for i in range(taps - 1):
            dp = dp + pltpu.roll(dc, rows - (taps - 1 - i), 0) * w[i:i + 1]
        dp_ref[0, :, lanes] = dp[:block_t]
        own = dc[:block_t]
        for i in range(taps):
            part = own * shifted[i][HALO:HALO + block_t]
            dw_ref[0, j, i] += jnp.sum(
                part.reshape(-1, SUBLANES, d), axis=0)


def _layout(p, taps, d, block_t, hs):
    """The grid and the blocks both kernels share: p's tile and the
    HALO rows before it, the taps' lanes, the heads-first tile; and the
    halo blocks a tile."""
    b, t, width = p.shape
    per = block_t // HALO
    grid = (b, width // d // hs, -(-t // block_t))
    tile = pl.BlockSpec((1, block_t, hs * d), lambda bi, hi, ti: (bi, ti, hi))
    prev = pl.BlockSpec(
        (1, HALO, hs * d),
        lambda bi, hi, ti: (bi, jnp.maximum(ti * per - 1, 0), hi))
    lanes = pl.BlockSpec((taps, hs * d), lambda bi, hi, ti: (0, hi))
    first = pl.BlockSpec((1, hs, block_t, d),
                         lambda bi, hi, ti: (bi, hi, ti, 0))
    return grid, per, tile, prev, lanes, first


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# jitted with ``inline=True`` as the flash kernels' calls are
# (``flash_attention.py``): a step's layers of one shape trace each body
# once and the traced step is what it was without the jit.
@functools.partial(jax.jit, static_argnames=(
    "d", "unit", "scale", "eps", "block_t", "hs", "interpret"), inline=True)
def _fwd_call(p, w, d, unit, scale, eps, block_t, hs, interpret):
    b, t, width = p.shape
    taps = w.shape[0]
    grid, _, tile, prev, lanes, first = _layout(p, taps, d, block_t, hs)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, heads=hs, taps=taps, unit=unit,
                          scale=scale, eps=eps),
        grid=grid,
        in_specs=[prev, tile, lanes], out_specs=first,
        out_shape=jax.ShapeDtypeStruct((b, width // d, t, d), F32),
        compiler_params=_PARAMS,
        # what XLA's scheduler may count on around the call (it takes a
        # call without one for no time at all): every entry read once,
        # written once, a dozen operations each
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 8) * p.size, transcendentals=p.size,
            bytes_accessed=8 * p.size + 4 * w.size),
        interpret=interpret, name="delta_mix_fwd",
    )(p, p, w)


@functools.partial(jax.jit, static_argnames=(
    "d", "unit", "scale", "eps", "block_t", "hs", "interpret"), inline=True)
def _bwd_call(do, p, w, d, unit, scale, eps, block_t, hs, interpret):
    b, t, width = p.shape
    heads, taps = width // d, w.shape[0]
    grid, per, tile, prev, lanes, first = _layout(p, taps, d, block_t, hs)
    ends = -(-t // HALO) - 1            # the last halo block there is
    dp, parts = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, heads=hs, taps=taps, unit=unit,
                          scale=scale, eps=eps, block_t=block_t, tokens=t),
        grid=grid,
        in_specs=[
            prev, tile,
            pl.BlockSpec(
                (1, HALO, hs * d),
                lambda bi, hi, ti: (bi, jnp.minimum((ti + 1) * per, ends),
                                    hi)),
            first,
            pl.BlockSpec(
                (1, hs, HALO, d),
                lambda bi, hi, ti: (bi, hi,
                                    jnp.minimum((ti + 1) * per, ends), 0)),
            lanes],
        out_specs=[tile, pl.BlockSpec((1, hs, taps, SUBLANES, d),
                                      lambda bi, hi, ti: (bi, hi, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(p.shape, F32),
                   jax.ShapeDtypeStruct((b, heads, taps, SUBLANES, d), F32)],
        compiler_params=_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 24) * p.size, transcendentals=p.size,
            bytes_accessed=12 * p.size + 4 * w.size),
        interpret=interpret, name="delta_mix_bwd",
    )(p, p, p, do, do, w)
    # a head's (K, d) sums back to the taps' (K, heads * d)
    return dp, jnp.moveaxis(jnp.sum(parts, axis=(0, 3)), 1, 0).reshape(
        w.shape)


def _note(kernel, scope, layer, part, p, d, taps, block_t, hs):
    """One ``kda.kernel`` / ``gdn.kernel`` instant per emitted call, at
    trace time."""
    if events.enabled():
        b, t, width = p.shape
        events.instant(
            scope + ".kernel", kernel="mix_" + kernel, layer=layer,
            part=part, heads=width // d, tokens=b * t, tile=block_t,
            heads_per_step=hs,
            grid_steps=b * (width // d // hs) * -(-t // block_t),
            vmem_bytes=vmem_bytes(kernel, block_t, hs, d, taps))


def _noted_fwd_call(p, w, d, unit, scale, eps, blocks, names, interpret):
    _note("fwd", *names, p, d, w.shape[0], *blocks[0])
    return _fwd_call(p, w, d, unit, scale, eps, *blocks[0], interpret)


_mix = jax.custom_vjp(_noted_fwd_call,
                      nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))


def _mix_fwd(p, w, *static):
    return _noted_fwd_call(p, w, *static), (p, w)


def _mix_bwd(d, unit, scale, eps, blocks, names, interpret, res, do):
    p, w = res
    _note("bwd", *names, p, d, w.shape[0], *blocks[1])
    return _bwd_call(do, p, w, d, unit, scale, eps, *blocks[1], interpret)


_mix.defvjp(_mix_fwd, _mix_bwd)


def delta_mix(p, taps, *, unit: bool, scale: float = 1.0, eps: float,
              scope: str = "kda", layer=None, part=None, block_t=None,
              heads_per_step=None, interpret=None, mesh=None, spec=None):
    """``p`` (b, t, heads, d) float32, a q, k or v projection's output
    tokens-first, and its ``taps`` (heads, d, K) -> (b, heads, t, d)
    float32: the causal taps, the SiLU and, with ``unit``, each head's
    unit length under ``eps`` times ``scale``, heads-first. Differentiable
    in ``p`` and ``taps``. ``scope`` (``"kda"`` / ``"gdn"``), ``layer``
    and ``part`` name the caller in the ``<scope>.kernel`` instants;
    ``block_t`` and ``heads_per_step`` override both kernels' tiles (the
    tests' and the timing script's).

    ``mesh`` / ``spec`` as :func:`flash_attention` takes them: under a
    mesh of more than one device the call runs under ``shard_map`` over
    the batch and head entries of ``spec``; a device holds whole heads
    and their taps."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bi, hi = (tuple(spec or ()) + (None, None))[:2]
        local = functools.partial(
            delta_mix, unit=unit, scale=scale, eps=eps, scope=scope,
            layer=layer, part=part, block_t=block_t,
            heads_per_step=heads_per_step, interpret=interpret)
        # check_vma off: pallas_call outputs carry no varying-axes info
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(bi, None, hi, None), P(hi, None, None)),
            out_specs=P(bi, hi, None, None), check_vma=False)(p, taps)
    b, t, heads, d = p.shape
    k = taps.shape[-1]
    blocks = tuple(
        (block_t or derived[0], heads_per_step or derived[1])
        for derived in (tiles(kernel, t, heads, d, k)
                        for kernel in ("fwd", "bwd")))
    # the taps with a head's channels along the lanes, as p has them
    w = jnp.moveaxis(taps.astype(F32), -1, 0).reshape(k, heads * d)
    return _mix(p.astype(F32).reshape(b, t, heads * d), w, d, bool(unit),
                float(scale), float(eps), blocks, (scope, layer, part),
                bool(interpret))
