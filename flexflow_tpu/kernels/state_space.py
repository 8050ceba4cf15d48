"""The state-space (Mamba-2) recurrence in chunks as two Pallas TPU
kernels (forward + backward).

``ops/recurrent_ops.py::state_space_scan`` runs the recurrence in chunks
of ``C`` tokens; with ``G`` the running log-decay inside a chunk, ``S``
the state a chunk starts from (``H`` heads of ``P`` channels, a state of
``N``, ``B`` / ``C`` shared by the heads):

    Y  = (L * C B^T)(dt x) + exp(G) * (C S^T),  L_ij = exp(G_i - G_j)
    S' = exp(G_C) S + ((dt x) * exp(G_C - G))^T B            (j <= i)

``L`` is a ``C x C`` float32 matrix a head a chunk. XLA's version of the
same algebra (the plain path of ``state_space_scan``: ``_ssm_chunks``,
the fallback and the tests' oracle, a ``lax.scan`` over the chunk states
and one product that reads them) makes ``L`` for all chunks at once,
stacks the states in HBM and passes five times over arrays the size of
``x``. Here a 128 x 128 tile of ``L`` is a row of ``G`` minus a column
of ``G`` in VMEM, multiplied by the tile of ``C B^T`` there, rounded and
handed to the matrix unit (the tiles under the diagonal are never
formed), and the state of a block of heads rides in VMEM scratch from a
chunk to the next: ``x`` comes in once and ``y`` leaves once.

Channels lie down the rows and a chunk's tokens along the lanes: the
layer's input projection writes ``x`` tokens last on the chip (XLA's
choice, for the convolution along them), so the kernels read it as it
lies; a ``(T, H P)`` operand cost a 67 MB copy each way in and out, and
a ``(T, H, P)`` view of it another (PERF.md section 6, PR 56). A head is
then ``P`` whole rows, whatever ``P`` (a multiple of 16, bf16's sublane
tile), every per-head factor (``dt``, ``exp(G)``, ``exp(G_C - G)``) is a
row of tokens spread down the head's channels, and the sums a head's
``dG`` and ``d dt`` take are sums down its rows. A grid step is
``(BLOCK_ROWS, C)`` of ``x``: eight heads of 64; the grid is (batch,
blocks of heads, chunks) with the chunks innermost and in order (the
backward walks them last to first, carrying the state's cotangent).
``C B^T`` is one product a chunk, made again by each block of heads
(0.27 GFLOP a layer eight times over: the matrix unit has the room);
the backward sums ``d(C B^T)`` over a block's heads in scratch and hands
out the block's share of ``dB`` and ``dC``, summed over the blocks
outside. ``G`` comes in twice, along the lanes and down the rows (it is
1 MB), so ``G_i - G_j`` is a row minus a column.

With several groups of ``B`` and ``C`` (head ``h`` reads group ``h // (H
/ groups)``) a block of heads lies inside ONE group (``takes_kernel``
asks that a group is whole blocks of eight heads), so the only thing
that changes is which ``N`` columns a grid step reads: ``B`` and ``C``
come in as ``(B, T, groups N)``, the groups side by side, and the block
specs' index maps name the step's group (``block // blocks a group``);
the backward's shares of ``dB`` and ``dC`` are summed over each group's
blocks and laid side by side again. One group is the case of one: every
block's group is 0 and the sum runs over all blocks.

Every exponent taken is a difference of running log-decays that is <= 0
(masked to 0 under the diagonal before the exponential, to nothing after
it) or ``G`` itself, as in the plain code and for its reason.

The backward kernel takes ``dY`` and returns ``dx, d dt, dG, dB, dC`` in
float32; the residuals of the ``custom_vjp`` are the five inputs and the
states the chunks start from (what the plain path's scan stacks: ``P x
N`` a head a chunk). It makes ``L * C B^T`` again a tile at a time and
never forms ``dY X^T`` beyond the tile that ``d(C B^T)`` takes: with ``M
= L * C B^T`` a head and ``X = dt x``,

    dG_i = sum_j (dM * M)_ij - sum_j (dM * M)_ji
         = sum_p dY_ip (M X)_ip - sum_p X_ip (M^T dY)_ip

so the sums over a tile's rows and columns are sums over a head's own
channels of arrays the size of ``x`` (with ``dY`` and ``X`` as the
products read them, rounded: what a row takes a column gives, to the
bit, or ``d A_log``, which sums ``dG`` over every token, drifts).

Products round their operands to ``mdt`` where the plain code does (the
three products of a chunk, and their transposes in the backward);
running sums, exponentials, ``L``, ``L * C B^T`` before it is rounded,
the states and the gradients are float32; at ``mdt`` float32 the
products are exact (``Precision.HIGHEST``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import events
from ._interpret import pallas_interpret

LANES = 128             # lanes of a vector; rows and columns of a tile
#: channels of ``x`` a grid step takes, whole heads: 512 are eight heads
#: of 64, 128 grid steps a layer at 4,096 tokens where a step of two
#: heads would make 512 of them at 0.35 us each
BLOCK_ROWS = 512
#: a backward step holds three (BLOCK_ROWS, chunk) float32 slabs twice
#: over, ``C B^T`` and its cotangent: at 512 Mosaic counts 17.4 MiB of
#: the 16 it may take
MAX_CHUNK = 256
F32 = jnp.float32
_HIGHEST, _DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT


def takes_kernel(chunk: int, heads: int, head_dim: int, state: int,
                 groups: int = 1) -> bool:
    """Whether these shapes run the kernels: a chunk in whole tiles (one
    or two: ``MAX_CHUNK``), the state in whole lanes, a head's channels
    whole sublane tiles of bf16 (16 rows), and the heads in blocks of
    eight (``G``'s rows a step: a sublane tile) of no more than
    ``BLOCK_ROWS`` channels, or all in one such block. With several
    ``groups`` of B and C a block lies inside ONE group (whole eights of
    a group's heads: the step reads that group's B and C)."""
    return (chunk % LANES == 0 and 0 < chunk <= MAX_CHUNK
            and state % LANES == 0 and state > 0
            and head_dim % 16 == 0 and head_dim > 0
            and groups > 0 and heads % groups == 0
            and 0 < heads_per_block(heads, head_dim, groups) * head_dim
            <= BLOCK_ROWS)


def heads_per_block(heads: int, head_dim: int, groups: int = 1) -> int:
    """Heads a grid step takes: the most eights of them that divide a
    group's ``heads / groups`` within ``BLOCK_ROWS`` channels, or, with
    one group, all of them (with several: 0, no block there is)."""
    n = BLOCK_ROWS // head_dim // 8 * 8
    while n and heads // groups % n:
        n -= 8
    return n or (heads if groups == 1 else 0)


# ---------------------------------------------------------------------------
# pieces of a grid step: channels down the rows, a chunk's tokens along
# the lanes
# ---------------------------------------------------------------------------
def _dot(a, b, mdt, dims=((1,), (0,))):
    """``a @ b`` (``dims``: the contracted axes, for a transposed
    operand), float32 sums; exact at ``mdt`` float32, and one pass of
    the matrix unit otherwise whatever ``jax_default_matmul_precision``
    says around the call (Mosaic takes no bf16 operand at ``highest``:
    ``Bad lhs type``)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=_HIGHEST if jnp.dtype(mdt) == F32 else _DEFAULT,
        preferred_element_type=F32)


_TN = ((0,), (0,))      # a^T b
_NT = ((1,), (1,))      # a b^T


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _tile(i):
    return slice(i * LANES, (i + 1) * LANES)


def _head(k, p):
    return slice(k * p, (k + 1) * p)


def _rows_of(v, p):
    """(heads, C) a number a head and token -> (heads p, C): a head's
    row down its own channels."""
    return jnp.concatenate([jnp.broadcast_to(v[k:k + 1, :], (p, v.shape[1]))
                            for k in range(v.shape[0])], axis=0)


def _decay_tile(gc, gr, k, j, i):
    """Rows ``j``, columns ``i`` (tiles, ``j <= i``) of head ``k``'s
    ``L^T``: ``exp(G_i - G_j)`` for ``j <= i``, 0 under the diagonal."""
    d = gr[k:k + 1, _tile(i)] - gc[_tile(j), k:k + 1]
    if j < i:
        return jnp.exp(d)
    up = _iota((LANES, LANES), 0) <= _iota((LANES, LANES), 1)
    return jnp.where(up, jnp.exp(jnp.where(up, d, 0.0)), 0.0)


def _common(gr_ref, dt_ref, p):
    """What both kernels spread down a block's channels: the step sizes,
    ``exp(G)``, ``exp(G_C - G)``, and ``exp(G_C)`` a channel."""
    gr = gr_ref[...]
    c = gr.shape[1]
    rise = _rows_of(jnp.exp(gr), p)
    fall = _rows_of(jnp.exp(gr[:, c - 1:c] - gr), p)
    return gr, _rows_of(dt_ref[...], p), rise, fall, rise[:, c - 1:c]


# ---------------------------------------------------------------------------
# forward kernel: grid (batch, blocks of heads, chunks); a block of
# heads' state rides in scratch from a chunk to the next
# ---------------------------------------------------------------------------
def _fwd_kernel(x_ref, dt_ref, gr_ref, gc_ref, bm_ref, cmt_ref, y_ref,
                starts_ref, cbt_ref, s_ref, *, mdt, p):
    c = x_ref.shape[1]
    tiles = c // LANES

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    bm, cmt = bm_ref[...].astype(mdt), cmt_ref[...].astype(mdt)
    cbt_ref[...] = _dot(bm, cmt, mdt)               # (C B^T)^T
    gr, dts, rise, fall, keep = _common(gr_ref, dt_ref, p)
    gc = gc_ref[...]
    dtx = x_ref[...] * dts
    state = s_ref[...]
    starts_ref[...] = state
    before = rise * _dot(state.astype(mdt), cmt, mdt)
    s_ref[...] = keep * state + _dot((dtx * fall).astype(mdt), bm, mdt)
    xm = dtx.astype(mdt)
    for k in range(gr.shape[0]):
        xs = xm[_head(k, p), :]
        for i in range(tiles):
            out = before[_head(k, p), _tile(i)]
            for j in range(i + 1):
                m = (_decay_tile(gc, gr, k, j, i)
                     * cbt_ref[_tile(j), _tile(i)]).astype(mdt)
                out = out + _dot(xs[:, _tile(j)], m, mdt)
            y_ref[_head(k, p), _tile(i)] = out


# ---------------------------------------------------------------------------
# backward kernel: same grid, the chunks last to first; the cotangent of
# a block of heads' state rides in scratch from a chunk to the one before
# ---------------------------------------------------------------------------
def _bwd_kernel(x_ref, dt_ref, gr_ref, gc_ref, bm_ref, bmt_ref, cm_ref,
                cmt_ref, starts_ref, dy_ref, dx_ref, ddt_ref, dg_ref,
                dbmt_ref, dcmt_ref, cbt_ref, dcbt_ref, ds_ref, yin_ref,
                dxin_ref, *, mdt, p):
    c = x_ref.shape[1]
    tiles = c // LANES

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    bm, bmt = bm_ref[...].astype(mdt), bmt_ref[...].astype(mdt)
    cm, cmt = cm_ref[...].astype(mdt), cmt_ref[...].astype(mdt)
    cbt_ref[...] = _dot(bm, cmt, mdt)
    dcbt_ref[...] = jnp.zeros_like(dcbt_ref)
    gr, dts, rise, fall, keep = _common(gr_ref, dt_ref, p)
    gc = gc_ref[...]
    x, dy = x_ref[...], dy_ref[...]
    dtx = x * dts
    state, ds = starts_ref[...], ds_ref[...]    # S a chunk starts from, dS'
    # before = exp(G) (S C^T)
    sm, dye = state.astype(mdt), (dy * rise).astype(mdt)
    before = rise * _dot(sm, cmt, mdt)
    dcmt = _dot(sm, dye, mdt, _TN)
    # S' = exp(G_C) S + (dtx f) B, f = exp(G_C - G)
    ds_ref[...] = keep * ds + _dot(dye, cm, mdt)
    dsm, xf = ds.astype(mdt), dtx * fall
    dxf = _dot(dsm, bmt, mdt)
    dbmt = _dot(dsm, xf.astype(mdt), mdt, _TN)
    through = dxf * xf                      # what d f took, times f
    held = ds * state * keep                # what d exp(G_C) took, times it
    # inside = dtx M^T a head, M = L * C B^T
    xm, dym = dtx.astype(mdt), dy.astype(mdt)
    for k in range(gr.shape[0]):
        xs, dys = xm[_head(k, p), :], dym[_head(k, p), :]
        dx_in = [None] * tiles
        for i in range(tiles):
            y_in = None
            for j in range(i + 1):
                decay = _decay_tile(gc, gr, k, j, i)
                m = (decay * cbt_ref[_tile(j), _tile(i)]).astype(mdt)
                t = _dot(xs[:, _tile(j)], m, mdt)
                y_in = t if y_in is None else y_in + t
                t = _dot(dys[:, _tile(i)], m, mdt, _NT)
                dx_in[j] = t if dx_in[j] is None else dx_in[j] + t
                dcbt_ref[_tile(j), _tile(i)] += decay * _dot(
                    xs[:, _tile(j)], dys[:, _tile(i)], mdt, _TN)
            yin_ref[_head(k, p), _tile(i)] = y_in
        for j in range(tiles):
            dxin_ref[_head(k, p), _tile(j)] = dx_in[j]
    y_in, dx_in = yin_ref[...], dxin_ref[...]
    ddtx = dx_in + dxf * fall
    dx_ref[...] = ddtx * dts
    # G_i takes its row of dM * M, gives its column, and takes what
    # exp(G_i) and exp(G_C - G_i) took; G_C takes what every token of
    # the chunk sent through exp(G_C - G) and what exp(G_C) took. (The
    # row and the column read the same rounded dY and dt x the products
    # did: what a row takes, a column gives, to the bit.)
    mine = (dym.astype(F32) * y_in - xm.astype(F32) * dx_in
            + dy * before - through)
    to_dt = ddtx * x
    last = _iota((1, c), 1) == c - 1
    dg, ddt = [], []
    for k in range(gr.shape[0]):
        rows = _head(k, p)
        sent = jnp.sum(jnp.sum(through[rows], 0, keepdims=True), 1,
                       keepdims=True) \
            + jnp.sum(jnp.sum(held[rows], 0, keepdims=True), 1,
                      keepdims=True)
        dg.append(jnp.sum(mine[rows], 0, keepdims=True)
                  + jnp.where(last, sent, 0.0))
        ddt.append(jnp.sum(to_dt[rows], 0, keepdims=True))
    dg_ref[...] = jnp.concatenate(dg, axis=0)
    ddt_ref[...] = jnp.concatenate(ddt, axis=0)
    dcbt = dcbt_ref[...].astype(mdt)
    dcmt_ref[...] = dcmt + _dot(bmt, dcbt, mdt)
    dbmt_ref[...] = dbmt + _dot(cmt, dcbt, mdt, _NT)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------
def _layout(x, c, p, backward, groups=1):
    """Grid, heads a step and the block specs by kind, for ``x`` (B, H
    P, T) in chunks of ``c``; the backward walks the chunks last to
    first. With several ``groups`` the B and C operands hold the groups'
    ``N`` columns (rows, transposed) side by side, and a block of heads
    reads its own group's by the block index."""
    b, hp, t = x.shape
    m = t // c
    n_heads = heads_per_block(hp // p, p, groups)
    per_group = hp // p // groups // n_heads    # blocks of heads a group

    def chunk(j):
        return m - 1 - j if backward else j

    def group(k):
        return k // per_group

    def rows_by_tokens(rows):       # (B, blocks x rows, T): x, dt, G
        return pl.BlockSpec((None, rows, c), lambda i, k, j: (i, k, chunk(j)))

    def tokens_by(cols):            # (B, T, cols): B, C
        return pl.BlockSpec((None, c, cols),
                            lambda i, k, j: (i, chunk(j), group(k)))

    def by_tokens(rows):            # (B, rows, T): B^T, C^T
        return pl.BlockSpec((None, rows, c),
                            lambda i, k, j: (i, group(k), chunk(j)))

    def of_block(rows, cols):       # (B, M, blocks, rows, cols)
        return pl.BlockSpec((None, None, None, rows, cols),
                            lambda i, k, j: (i, chunk(j), k, 0, 0))

    return ((b, hp // (n_heads * p), m), n_heads, rows_by_tokens, tokens_by,
            by_tokens, of_block)


def _cost(kernel, x, c, n, p, groups=1):
    """What a call computes and moves, for XLA's scheduler: the products
    of the upper tiles and of the states, one exponential an entry of
    ``L``, the slabs in and out."""
    b, hp, t = x.shape
    tiles = c // LANES
    blocks = hp // (heads_per_block(hp // p, p, groups) * p)
    entries = b * (t // c) * (hp // p) * (tiles * (tiles + 1) // 2) \
        * LANES * LANES
    runs = 1 if kernel == "fwd" else 3      # x M^T | and dY M, x^T dY
    slabs = 2 if kernel == "fwd" else 3     # x, y | x, dy, dx
    return pl.CostEstimate(
        flops=2 * runs * (entries * p + b * t * n * (blocks * c + 2 * hp)),
        transcendentals=entries + 2 * b * t * (hp // p),
        bytes_accessed=4 * b * t * (slabs * hp + 2 * runs * blocks * n)
        + 4 * b * (t // c) * n * hp)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_STATIC = ("c", "p", "mdt", "interpret", "groups")


def _down_rows(gr, c, n_heads):
    """``G`` (B, H, T) a block of heads and a chunk at a time, tokens
    down the rows: (B, M, blocks, C, heads)."""
    b, h, t = gr.shape
    v = gr.reshape(b, h // n_heads, n_heads, t // c, c)
    return jnp.transpose(v, (0, 3, 1, 4, 2))


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _fwd_call(x, dt, gr, bm, cm, c, p, mdt, interpret, groups=1):
    b, hp, t = x.shape
    n = bm.shape[-1] // groups
    grid, n_heads, rows_by_tokens, tokens_by, by_tokens, of_block = _layout(
        x, c, p, False, groups)
    rows = n_heads * p
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mdt=mdt, p=p),
        grid=grid,
        in_specs=[rows_by_tokens(rows), rows_by_tokens(n_heads),
                  rows_by_tokens(n_heads), of_block(c, n_heads),
                  tokens_by(n), by_tokens(n)],
        out_specs=[rows_by_tokens(rows), of_block(rows, n)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32),
                   jax.ShapeDtypeStruct((b, t // c, grid[1], rows, n), F32)],
        scratch_shapes=[pltpu.VMEM((c, c), F32), pltpu.VMEM((rows, n), F32)],
        compiler_params=_PARAMS, interpret=interpret,
        cost_estimate=_cost("fwd", x, c, n, p, groups),
        name="state_space_fwd",
    )(x, dt, gr, _down_rows(gr, c, n_heads), bm, jnp.swapaxes(cm, 1, 2))


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_call(x, dt, gr, bm, cm, starts, dy, c, p, mdt, interpret,
              groups=1):
    b, hp, t = x.shape
    n = bm.shape[-1] // groups
    grid, n_heads, rows_by_tokens, tokens_by, by_tokens, of_block = _layout(
        x, c, p, True, groups)
    rows, m = n_heads * p, t // c
    a_part = pl.BlockSpec((None, None, n, c),
                          lambda i, k, j: (k, i, 0, m - 1 - j))
    small = jax.ShapeDtypeStruct(gr.shape, F32)
    part = jax.ShapeDtypeStruct((grid[1], b, n, t), F32)
    dx, ddt, dg, dbmt, dcmt = pl.pallas_call(
        functools.partial(_bwd_kernel, mdt=mdt, p=p),
        grid=grid,
        in_specs=[rows_by_tokens(rows), rows_by_tokens(n_heads),
                  rows_by_tokens(n_heads), of_block(c, n_heads),
                  tokens_by(n), by_tokens(n), tokens_by(n), by_tokens(n),
                  of_block(rows, n), rows_by_tokens(rows)],
        out_specs=[rows_by_tokens(rows), rows_by_tokens(n_heads),
                   rows_by_tokens(n_heads), a_part, a_part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32), small, small, part,
                   part],
        scratch_shapes=[pltpu.VMEM((c, c), F32), pltpu.VMEM((c, c), F32),
                        pltpu.VMEM((rows, n), F32),
                        pltpu.VMEM((rows, c), F32),
                        pltpu.VMEM((rows, c), F32)],
        compiler_params=_PARAMS, interpret=interpret,
        cost_estimate=_cost("bwd", x, c, n, p, groups),
        name="state_space_bwd",
    )(x, dt, gr, _down_rows(gr, c, n_heads), bm, jnp.swapaxes(bm, 1, 2), cm,
      jnp.swapaxes(cm, 1, 2), starts, dy)
    # a block of heads' share of dB and dC a chunk: summed here, over
    # each group's own blocks
    def by_group(part):         # (blocks, B, N, T) -> (B, T, groups N)
        part = jnp.sum(part.reshape((groups, -1) + part.shape[1:]), 1)
        return jnp.transpose(part, (1, 3, 0, 2)).reshape(b, t, groups * n)

    return dx, ddt, dg, by_group(dbmt), by_group(dcmt)


def _note(kernel, layer, x, c, p, groups):
    """One ``ssm.kernel`` instant per emitted call, at trace time."""
    if events.enabled():
        b, hp, t = x.shape
        n_heads = heads_per_block(hp // p, p, groups)
        events.instant("ssm.kernel", kernel=kernel, layer=layer, chunk=c,
                       chunks=b * t // c, heads_per_step=n_heads,
                       groups=groups,
                       grid_steps=b * (t // c) * (hp // p) // n_heads)


def _noted_fwd_call(x, dt, gr, bm, cm, c, p, mdt, layer, interpret,
                    groups):
    _note("fwd", layer, x, c, p, groups)
    return tuple(_fwd_call(x, dt, gr, bm, cm, c, p, mdt, interpret, groups))


_scan = jax.custom_vjp(_noted_fwd_call,
                       nondiff_argnums=(5, 6, 7, 8, 9, 10))


def _scan_fwd(x, dt, gr, bm, cm, *static):
    y, starts = _noted_fwd_call(x, dt, gr, bm, cm, *static)
    return (y, starts), (x, dt, gr, bm, cm, starts)


def _scan_bwd(c, p, mdt, layer, interpret, groups, res, cts):
    _note("bwd", layer, res[0], c, p, groups)
    # a custom_vjp's backward is traced outside the forward's scopes:
    # the recurrence's share of a step has to hold this call too. (The
    # states are handed out for the tests to read, not to be pulled
    # back through.)
    with jax.named_scope("ssm.scan"):
        return _bwd_call(*res, cts[0], c, p, mdt, interpret, groups)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_chunks(x, dt, big_g, bm, cm, chunk, mdt, *, layer=None,
                interpret=None, groups=1):
    """The recurrence over whole chunks from a zero state, by the
    kernels, channels first: ``x`` (B, H, P, T), ``dt`` the step sizes
    and ``big_g`` the running log-decay inside each chunk (B, H, T),
    ``bm``, ``cm`` (B, T, groups N), a group's ``N`` columns after the
    one before (head ``h`` reads group ``h // (H / groups)``); float32,
    ``T`` whole chunks. Returns ``y``
    (B, H, P, T) float32 and the state each chunk starts from, (B, M, H,
    P, N) (what the backward keeps; no cotangent is taken for it).
    ``layer`` names the caller in the ``ssm.kernel`` instants."""
    if interpret is None:
        interpret = pallas_interpret()
    b, h, p, t = x.shape
    y, starts = _scan(x.reshape(b, h * p, t), dt, big_g, bm, cm, chunk, p,
                      jnp.dtype(mdt), layer, bool(interpret), int(groups))
    return y.reshape(x.shape), starts.reshape(b, t // chunk, h, p, -1)
