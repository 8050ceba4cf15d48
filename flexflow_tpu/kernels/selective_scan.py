"""The selective (Mamba-1) scan as two Pallas TPU kernels (forward +
backward).

``ops/recurrent_ops.py::selective_scan`` runs, a channel ``c`` and a
state entry ``n`` at a time (``D`` channels, ``N`` entries, ``a`` < 0,
``dt`` > 0),

    h_t[n, c] = exp(dt_t[c] a[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] b_t[n]
    y_t[c]    = sum_n c_t[n] h_t[n, c]

The decay differs by channel AND state entry, so no chunk of it is a
matrix product: it is a walk over the tokens on the vector unit. XLA's
version (the plain path of ``selective_scan``, the fallback and the
tests' oracle) forms ``exp(dt a)`` and ``dt x b`` for a chunk as ``(C, N,
D)`` arrays in HBM, stacks every ``h_t`` there and reads the stack back
through ``c``: a recurrence whose live state is ``N x D`` floats moves 2.7
GB a pass at the published width. Here the state stays on the chip: what
a call reads from HBM is ``x`` and ``dt``, what it writes is ``y`` (and,
under differentiation, the state each chunk starts from).

**Layout.** A block is 1,024 channels, one vector register: eight groups
of 128 lanes down the eight sublanes. A block's state is ``N`` registers,
one a state entry, so ``b_t[n]`` and ``c_t[n]`` are SCALARS (read from
SMEM) times whole registers, the sum over ``n`` is ``N - 1`` register
adds, and no sublane or lane is ever reduced, spread or masked in the
walk. ``x``, ``dt`` and ``y`` ``(B, T, D)`` reach the kernels as ``(B,
T / 8, D / 16, 128)``: row ``8 g + r`` of token group ``q`` holds lanes
``128 g ..`` of token ``8 q + r``. That is the order in which the TPU's
``(8, 128)`` tiling already holds a ``(T, D)`` float32 array, so the
reshape-transpose-reshape that says it (:func:`_tiled`) compiles to a
bitcast, and a token's register of a block is ONE strided load (eight
rows, stride 8).

The grid is (batch, chunks), the chunks in order (``arbitrary``); a grid
step takes a chunk of every channel and walks the blocks one after the
other, the tokens inside; the whole state ``(D / 1024, N, 8, 128)`` (320
KiB at 5,120 channels) rides in VMEM scratch from chunk to chunk.

**Backward.** The residuals of the ``custom_vjp`` are the five inputs and
the state each chunk starts from. The backward kernel walks the chunks
last to first carrying the state's cotangent in scratch; a block of a
chunk first walks its tokens forward again, keeping each ``h_{t-1}`` and
``exp(dt_t a)`` in VMEM (``C x N`` registers each, 4 MiB at a chunk of
64), then back: with ``g_t`` the cotangent of ``y_t``,

    G_t = c_t g_t + da_{t+1} G_{t+1}                    (that of h_t)
    E_t = G_t da_t h_{t-1}                              (that of dt_t a)
    d x_t  = dt_t sum_n G_t b_t,   d dt_t = sum_n E_t a + x_t sum_n G_t b_t
    d a   += E_t dt_t  (a block's own, summed over the chunks in its
                        output block, which stays put a batch row)
    d b_t[n] = sum_c G_t dt_t x_t,   d c_t[n] = sum_c g_t h_t

The last two sum over every channel: each (token, n) pair's register of
partial products is added up over the blocks in VMEM, and once a chunk
the ``C N`` registers are summed to ``C N`` numbers (down the sublanes,
then along the lanes after a turn of each 128 x 128 piece), written 128
to a row in the order ``(t, n)``, and set back to zero for the next
chunk's.

Arithmetic as the plain code's: float32 throughout; every exponent taken
is ``dt a <= 0``; padded positions have ``dt`` 0 and change nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import events
from ._interpret import pallas_interpret

LANES, SUBLANES = 128, 8
#: channels of a block: one vector register, eight lane groups deep
BLOCK = SUBLANES * LANES
#: state entries: registers a block's state takes (the walk is unrolled
#: over them, and a token of the backward holds three sets of them)
MAX_STATE = 32
#: what the backward call may take of VMEM (tiles in and out twice over,
#: a chunk's states and decays, the two accumulators); v5e has 128 MiB
VMEM_LIMIT = 48 * 2 ** 20
#: tokens a step of the walk's loop takes (it has to divide the chunk).
#: The walk is traced and lowered again in every trace of a step, six
#: calls of it in cell 11's, so what is unrolled here is paid in
#: ``setup_s``: the cell's step compiled-or-loaded in 7.3 s on the plain
#: path, 9.0 at 1 token, 11.7-14.2 at 2 and 30 at 8 in the first form;
#: alone, 8 tokens are a quarter faster than 1 (1.34 against 1.97 ms
#: forward, 5.68 against 6.88 backward, before the block's rates were
#: loaded once), which is 0.5% of the cell's step (PERF.md section 6,
#: PR 62)
UNROLL = 1
F32 = jnp.float32


def _bwd_vmem_bytes(chunk: int, channels: int, state: int) -> int:
    """VMEM a backward grid step holds: five ``(chunk, channels)`` tiles
    and three copies of the state's size, each twice (the pipeline's two
    buffers), the cotangent carried, and a block's ``chunk + 1`` states,
    ``chunk`` decays and the two ``chunk x state`` accumulators."""
    whole = channels * state * 4
    return 2 * (5 * chunk * channels * 4 + 3 * whole) + whole \
        + (4 * chunk + 1) * state * BLOCK * 4


def takes_kernel(chunk: int, channels: int, state: int) -> bool:
    """Whether these shapes run the kernels: the channels in whole
    blocks of 1,024 (eight lane groups: a register), a state of whole
    eights (no more than ``MAX_STATE`` registers a block), a chunk of
    whole sublane tiles whose ``chunk x state`` sums fill whole rows of
    128 lanes, and the backward's step within ``VMEM_LIMIT`` less 4 MiB
    for what Mosaic keeps of its own."""
    return (channels > 0 and channels % BLOCK == 0
            and 0 < state <= MAX_STATE and state % SUBLANES == 0
            and chunk > 0 and chunk % SUBLANES == 0
            and (chunk * state) % LANES == 0
            and _bwd_vmem_bytes(chunk, channels, state)
            <= VMEM_LIMIT - 4 * 2 ** 20)


# ---------------------------------------------------------------------------
# the operands as the kernels read them
# ---------------------------------------------------------------------------
def _tiled(v):
    """(B, T, D) -> (B, T / 8, D / 16, 128): row ``8 g + r`` of group
    ``q`` is lanes ``128 g ..`` of token ``8 q + r``. The bytes of the
    ``(8, 128)``-tiled ``(T, D)`` array as they lie: a bitcast on the
    chip."""
    b, t, d = v.shape
    v = v.reshape(b, t // SUBLANES, SUBLANES, d // LANES, LANES)
    return jnp.swapaxes(v, 2, 3).reshape(b, t // SUBLANES, d // 16, LANES)


def _untiled(v):
    """:func:`_tiled` back: (B, T / 8, D / 16, 128) -> (B, T, D)."""
    b, q, rows, _ = v.shape
    v = v.reshape(b, q, rows // SUBLANES, SUBLANES, LANES)
    return jnp.swapaxes(v, 2, 3).reshape(b, q * SUBLANES, rows * 16)


def _blocks_of(a):
    """(N, D) -> (D / 1024, N, 8, 128): a block's registers, one a state
    entry."""
    n, d = a.shape
    return jnp.swapaxes(a.reshape(n, d // BLOCK, SUBLANES, LANES), 0, 1)


def _token(base, t):
    """Where token ``t`` of a chunk's tile lies for the block whose rows
    start at ``base``: its token group, and the eight rows of the group
    that are its register."""
    return jax.lax.shift_right_logical(t, np.int32(3)), pl.ds(
        _add(base, jax.lax.bitwise_and(t, np.int32(SUBLANES - 1))),
        SUBLANES, stride=SUBLANES)


# The walk is written in ``lax`` primitives, its index arithmetic too: it
# is traced again in every trace of a step, a few thousand operations of
# it, and each ``jnp`` operator on a tracer costs a nested ``jit``'s
# dispatch, ten times a primitive's bind (``setup_s``).
_mul, _add, _exp = jax.lax.mul, jax.lax.add, jax.lax.exp


def _at(t, n, i=0):
    """``t n + i``: where pair ``(t, i)`` stands among a chunk's."""
    return _add(_mul(t, np.int32(n)), np.int32(i))


def _by(v, scalar):
    """A register times a number out of SMEM."""
    return _mul(v, jax.lax.broadcast(scalar, v.shape))


def _tree_sum(terms):
    terms = list(terms)
    while len(terms) > 1:
        terms = [_add(terms[j], terms[j + 1]) if j + 1 < len(terms)
                 else terms[j] for j in range(0, len(terms), 2)]
    return terms[0]


def _walk(c, token, carry, reverse=False):
    """``token(t, carry) -> carry`` over a chunk's ``c`` tokens, first
    to last or last to first, ``UNROLL`` of them a loop step."""
    def step(j, carry):
        for u in range(UNROLL):
            t = _at(j, UNROLL, u)
            carry = token(jax.lax.sub(np.int32(c - 1), t) if reverse else t,
                          carry)
        return carry
    return jax.lax.fori_loop(0, c // UNROLL, step, carry)


# ---------------------------------------------------------------------------
# forward kernel: grid (batch, chunks); the state of every block rides in
# scratch from a chunk to the next
# ---------------------------------------------------------------------------
def _fwd_kernel(bm_ref, cm_ref, x_ref, dt_ref, a_ref, y_ref, *rest, n, c):
    *starts_ref, h_ref = rest

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    if starts_ref:                      # under differentiation only
        starts_ref[0][...] = h_ref[...]

    def block(k, _):
        base = _at(k, SUBLANES * SUBLANES)
        a = [a_ref[k, i] for i in range(n)]

        def token(t, h):
            q, rows = _token(base, t)
            at = [_at(t, n, i) for i in range(n)]
            dt = dt_ref[q, rows, :]
            u = _mul(dt, x_ref[q, rows, :])
            h = tuple(_add(_mul(_exp(_mul(dt, a[i])), h[i]),
                           _by(u, bm_ref[0, at[i]])) for i in range(n))
            y_ref[q, rows, :] = _tree_sum(
                _by(h[i], cm_ref[0, at[i]]) for i in range(n))
            return h

        h = _walk(c, token, tuple(h_ref[k, i] for i in range(n)))
        for i in range(n):
            h_ref[k, i] = h[i]
        return 0

    jax.lax.fori_loop(0, h_ref.shape[0], block, 0)


# ---------------------------------------------------------------------------
# backward kernel: same grid, the chunks last to first; the cotangent of
# every block's state rides in scratch from a chunk to the one before
# ---------------------------------------------------------------------------
def _bwd_kernel(bm_ref, cm_ref, x_ref, dt_ref, dy_ref, a_ref, starts_ref,
                dx_ref, ddt_ref, da_ref, dbm_ref, dcm_ref,
                g_ref, hs_ref, das_ref, dbs_ref, dcs_ref, *, n, c):
    @pl.when(pl.program_id(1) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dbs_ref[...] = jnp.zeros_like(dbs_ref)
        dcs_ref[...] = jnp.zeros_like(dcs_ref)

    def block(k, _):
        base = _at(k, SUBLANES * SUBLANES)
        a = [a_ref[k, i] for i in range(n)]

        # the chunk's tokens forward again: h_{t-1} and da_t of each
        def again(t, h):
            q, rows = _token(base, t)
            dt = dt_ref[q, rows, :]
            u = _mul(dt, x_ref[q, rows, :])
            new = []
            for i in range(n):
                da = _exp(_mul(dt, a[i]))
                hs_ref[t, i] = h[i]
                das_ref[t, i] = da
                new.append(_add(_mul(da, h[i]),
                                _by(u, bm_ref[0, _at(t, n, i)])))
            return tuple(new)

        h = _walk(c, again, tuple(starts_ref[k, i] for i in range(n)))
        for i in range(n):
            hs_ref[c, i] = h[i]

        # and back: ``later`` is da_{t+1} G_{t+1}, ``d_a`` the block's
        # share of this chunk
        def back(t, carry):
            later, d_a = carry
            q, rows = _token(base, t)
            dt, x, gy = dt_ref[q, rows, :], x_ref[q, rows, :], \
                dy_ref[q, rows, :]
            u, after = _mul(dt, x), _add(t, np.int32(1))
            new, to_u, to_dt, to_a = [], [], [], []
            for i in range(n):
                at = _at(t, n, i)
                g = _add(_by(gy, cm_ref[0, at]), later[i])
                dcs_ref[at] = _add(dcs_ref[at], _mul(gy, hs_ref[after, i]))
                dbs_ref[at] = _add(dbs_ref[at], _mul(g, u))
                to_u.append(_by(g, bm_ref[0, at]))
                g = _mul(das_ref[t, i], g)
                e = _mul(g, hs_ref[t, i])
                to_dt.append(_mul(e, a[i]))
                to_a.append(_add(d_a[i], _mul(e, dt)))
                new.append(g)
            du = _tree_sum(to_u)
            dx_ref[q, rows, :] = _mul(du, dt)
            ddt_ref[q, rows, :] = _add(_tree_sum(to_dt), _mul(du, x))
            return tuple(new), tuple(to_a)

        later, d_a = _walk(
            c, back, (tuple(g_ref[k, i] for i in range(n)),
                      tuple(da_ref[k, i] for i in range(n))), reverse=True)
        for i in range(n):
            g_ref[k, i] = later[i]
            da_ref[k, i] = d_a[i]
        return 0

    jax.lax.fori_loop(0, g_ref.shape[0], block, 0)

    # a (token, n) pair's register of partial sums -> its number, 128
    # pairs to a row; the registers start the next chunk's sums from zero
    def total(p, _):
        rows = pl.ds(_at(p, LANES), LANES)
        for acc, out in ((dbs_ref, dbm_ref), (dcs_ref, dcm_ref)):
            piece = jnp.sum(acc[rows], axis=1)
            out[pl.ds(p, 1), :] = jnp.sum(piece.T, axis=0, keepdims=True)
            acc[rows] = jnp.zeros((LANES, SUBLANES, LANES), F32)
        return 0

    jax.lax.fori_loop(0, c * n // LANES, total, 0)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------
def _specs(x, n, c, backward):
    """Block specs by kind for ``x`` (B, T, D) in chunks of ``c``; the
    backward walks the chunks last to first."""
    b, t, d = x.shape
    m, blocks = t // c, d // BLOCK

    def chunk(j):
        return m - 1 - j if backward else j

    tile = pl.BlockSpec((None, c // SUBLANES, d // 16, LANES),
                        lambda i, j: (i, chunk(j), 0, 0))
    # a chunk's b_t[n] or c_t[n], (t, n) in order, as scalars
    small = pl.BlockSpec((None, 1, c * n),
                         lambda i, j: (i * m + chunk(j), 0, 0),
                         memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((blocks, n, SUBLANES, LANES),
                         lambda i, j: (0, 0, 0, 0))
    starts = pl.BlockSpec((None, None, blocks, n, SUBLANES, LANES),
                          lambda i, j: (i, chunk(j), 0, 0, 0, 0))
    return tile, small, whole, starts


def _small(v, c):
    """(B, T, N) -> (B M, 1, C N): a chunk's scalars a row."""
    b, t, n = v.shape
    return v.reshape(b * (t // c), 1, c * n)


def _cost(kernel, x, n):
    """What a call computes and moves, for XLA's scheduler: a dozen
    operations a (token, channel, state entry) forward and three dozen
    back, one exponential each, ``x``, ``dt``, ``y`` in and out (and
    their cotangents)."""
    b, t, d = x.shape
    back = kernel == "bwd"
    return pl.CostEstimate(
        flops=(36 if back else 12) * b * t * d * n,
        transcendentals=b * t * d * n,
        bytes_accessed=4 * b * t * ((5 if back else 3) * d + 2 * n))


@functools.partial(jax.jit, static_argnames=("c", "keep", "interpret"),
                   inline=True)
def _fwd_call(x, dt, a, bm, cm, c, keep, interpret):
    b, t, d = x.shape
    n = a.shape[0]
    m, blocks = t // c, d // BLOCK
    tile, small, whole, starts = _specs(x, n, c, False)
    state = (blocks, n, SUBLANES, LANES)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, c=c),
        grid=(b, m),
        in_specs=[small, small, tile, tile, whole],
        out_specs=[tile] + [starts] * keep,
        out_shape=[jax.ShapeDtypeStruct((b, t // SUBLANES, d // 16, LANES),
                                        F32)]
        + [jax.ShapeDtypeStruct((b, m) + state, F32)] * keep,
        scratch_shapes=[pltpu.VMEM(state, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, cost_estimate=_cost("fwd", x, n),
        name="selective_scan_fwd",
    )(_small(bm, c), _small(cm, c), _tiled(x), _tiled(dt), _blocks_of(a))
    return (_untiled(out[0]),) + tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("c", "interpret"), inline=True)
def _bwd_call(x, dt, a, bm, cm, starts, dy, c, interpret):
    b, t, d = x.shape
    n = a.shape[0]
    m, blocks = t // c, d // BLOCK
    tile, small, whole, of_chunk = _specs(x, n, c, True)
    state = (blocks, n, SUBLANES, LANES)
    sums = pl.BlockSpec((None, c * n // LANES, LANES),
                        lambda i, j: (i * m + m - 1 - j, 0, 0))
    slab = jax.ShapeDtypeStruct((b, t // SUBLANES, d // 16, LANES), F32)
    summed = jax.ShapeDtypeStruct((b * m, c * n // LANES, LANES), F32)
    registers = pltpu.VMEM((c * n, SUBLANES, LANES), F32)
    dx, ddt, da, dbm, dcm = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, c=c),
        grid=(b, m),
        in_specs=[small, small, tile, tile, tile, whole, of_chunk],
        out_specs=[tile, tile,
                   pl.BlockSpec((None,) + state,
                                lambda i, j: (i, 0, 0, 0, 0)),
                   sums, sums],
        out_shape=[slab, slab, jax.ShapeDtypeStruct((b,) + state, F32),
                   summed, summed],
        scratch_shapes=[pltpu.VMEM(state, F32),
                        pltpu.VMEM((c + 1, n, SUBLANES, LANES), F32),
                        pltpu.VMEM((c, n, SUBLANES, LANES), F32),
                        registers, registers],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, cost_estimate=_cost("bwd", x, n),
        name="selective_scan_bwd",
    )(_small(bm, c), _small(cm, c), _tiled(x), _tiled(dt), _tiled(dy),
      _blocks_of(a), starts)
    # a batch row's share of d a: summed here
    da = jnp.swapaxes(jnp.sum(da, 0), 0, 1).reshape(a.shape)
    return (_untiled(dx), _untiled(ddt), da, dbm.reshape(bm.shape),
            dcm.reshape(cm.shape))


def _note(kernel, layer, x, n, c):
    """One ``ssm1.kernel`` instant per emitted call, at trace time."""
    if events.enabled():
        b, t, d = x.shape
        events.instant("ssm1.kernel", kernel=kernel, layer=layer, chunk=c,
                       chunks=b * t // c, grid_steps=b * t // c,
                       blocks=d // BLOCK, block_channels=BLOCK, state=n,
                       state_bytes=b * n * d * 4)


def _scan_primal(x, dt, a, bm, cm, c, layer, interpret):
    _note("fwd", layer, x, a.shape[0], c)
    return _fwd_call(x, dt, a, bm, cm, c, False, interpret)[0]


_scan = jax.custom_vjp(_scan_primal, nondiff_argnums=(5, 6, 7))


def _scan_fwd(x, dt, a, bm, cm, c, layer, interpret):
    _note("fwd", layer, x, a.shape[0], c)
    y, starts = _fwd_call(x, dt, a, bm, cm, c, True, interpret)
    return y, (x, dt, a, bm, cm, starts)


def _scan_bwd(c, layer, interpret, res, dy):
    _note("bwd", layer, res[0], res[2].shape[0], c)
    # a custom_vjp's backward is traced outside the forward's scopes: the
    # recurrence's share of a step has to hold this call too
    with jax.named_scope("ssm1.scan"):
        return _bwd_call(*res, dy, c, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_chunks(x, dt, a, bm, cm, chunk, *, layer=None, interpret=None):
    """The recurrence over whole chunks from a zero state, by the
    kernels: ``x``, ``dt`` (B, T, D), ``a`` (N, D), ``bm``, ``cm`` (B, T,
    N); float32, ``T`` whole chunks, the shapes as :func:`takes_kernel`
    wants them. Returns ``y`` (B, T, D) float32. ``layer`` names the
    caller in the ``ssm1.kernel`` instants."""
    if interpret is None:
        interpret = pallas_interpret()
    return _scan(x, dt, a, bm, cm, int(chunk), layer, bool(interpret))
