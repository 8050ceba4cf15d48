"""Flash attention as a Pallas TPU kernel (forward + backward).

Replaces the reference's cuDNN multi-head attention kernels
(``src/ops/attention.cu:35,105,128``) with a TPU-native tiled kernel:
online-softmax accumulation in VMEM scratch so the (seq_q, seq_k) score
matrix never hits HBM, bf16/f32 matmuls on the MXU with f32 accumulation,
and a custom VJP whose dq and dk/dv passes are separate Pallas kernels
(the standard split so each pass has a sequential accumulation grid).

Attention-probability dropout (the reference's cuDNN attnDropout) runs
in-kernel and counter-based: keep[i, j] is a pure hash of (seed, bh,
absolute q/k positions), so the differently-blocked backward kernels
regenerate the identical keep mask without storing it, and the same
hash lowers in interpret mode for CPU CI.

Row statistics (the forward's log-sum-exp, the backward's ``delta``)
travel one float32 a row, (bh, 1, sq) with the rows along the lanes,
from the forward kernel through the residual to the backward kernels,
and inside a kernel meet a score tile as whole lane-replicated vregs or
as a sublane broadcast, never as a (rows, 1) column (docs/kernels.md).

A sliding window (``window=W`` under ``causal``: a query sees the ``W``
keys that end with its own) is index arithmetic like the causal edge:
the mask from the tile's positions, the grid steps and forward pieces on
either side of the band skipped, their index maps naming the band's
nearest block so that nothing is copied for them. It is never an operand;
with ``window=0`` every kernel is the text it was (docs/kernels.md).

A block-diffusion mask (``bd=(L, B)``, not causal, over ``2 L`` positions
``[noised | clean]`` in blocks of ``B``: a noised query sees the noised
keys of its own block and the clean keys of earlier blocks, a clean
query the clean keys of its own and earlier blocks) is a third static
form of the same kind: drawn from the tile's positions, its dead tiles
skipped and named as their live neighbours, and a live noised x noised
tile, where the mask is block-diagonal, scored as the sub-blocks of its
diagonal alone (:func:`_bd_sub`). With ``bd=()`` every kernel is the
text it was (docs/kernels.md).

Grouped-query attention (k and v at fewer heads than q) is index
arithmetic too: the kernels read the key/value heads in place, a query
head naming its group's row, and ``bwd_dkv`` walks the key/value heads
and adds a group's query heads into one accumulator. Nothing is repeated
before the calls and no gradient is summed after them; with equal head
counts every kernel is the text it was (docs/kernels.md).

Layout: (batch, heads, seq, head_dim), batch*heads collapsed into one grid
axis. Sequence/head dims are padded to block/lane multiples; the padded-key
mask is baked in statically (shapes are static under jit). TPU grids
execute sequentially over the last grid axis, which is what makes the VMEM
scratch accumulators correct; interpret mode preserves that, so the same
kernel is unit-testable on CPU.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import events
from ._interpret import pallas_interpret

NEG_INF = -1e30


def _pad_to(x, mult, axis):
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def _tile_positions(iq, ik, block_q, block_k, keys_major):
    """Absolute (query, key) positions of the elements of one (q block,
    k block) tile, (block_q, block_k) with the queries down the sublanes
    or, ``keys_major``, its transpose (block_k, block_q). The masks are
    functions of these positions alone, so a kernel that holds the tile
    transposed draws the same mask, bit for bit."""
    shape, q_axis = (((block_k, block_q), 1) if keys_major
                     else ((block_q, block_k), 0))
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                    1 - q_axis)
    return q_pos, k_pos


def _key_mask(iq, ik, block_q, block_k, kv_len, causal, keys_major=False,
              window=0, bd=(), part=None):
    """Validity mask for one (q block, k block) tile; kv_len, window and
    bd are static. ``window``: a query sees the ``window`` keys that end
    with its own, ``k_pos > q_pos - window`` beside the causal edge.
    ``bd``: the block-diffusion mask alone (:func:`_bd_mask`; such a call
    has no padded key), and under it ``part = (q_off, k_off, side)``: the
    mask of the ``side x side`` sub-block at those places of the tile
    alone (:func:`_bd_diagonal`)."""
    if bd:
        if part is None:
            return _bd_mask(iq * block_q, ik * block_k, block_q, block_k,
                            keys_major, bd)
        q_off, k_off, side = part
        return _bd_mask(iq * block_q + q_off, ik * block_k + k_off, side,
                        side, keys_major, bd)
    q_pos, k_pos = _tile_positions(iq, ik, block_q, block_k, keys_major)
    mask = k_pos < kv_len
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window:
        mask = jnp.logical_and(mask, k_pos > q_pos - window)
    return mask


def _bd_mask(q0, k0, block_q, block_k, keys_major, bd):
    """The block-diffusion mask of the ``block_q x block_k`` pairs from
    query ``q0`` and key ``k0`` on (a tile, or a sub-block of one), ``bd
    = (L, B)``: positions
    ``[0, L)`` are the noised copy, ``[L, 2 L)`` the clean one, and
    ``b(i) = (i mod L) // B``. A tile lies in one quadrant (``L`` is a
    multiple of every block), which two scalars say; with ``e`` = the
    key's place in its half less the first place of the query's block,

      noised q, noised k   ``b(k) == b(q)``   ``0 <= e <= B - 1``
      noised q, clean k    ``b(k) <  b(q)``   ``e <= -1``
      clean q, clean k     ``b(k) <= b(q)``   ``e <= B - 1``
      clean q, noised k    never

    so the mask is two comparisons of ``e`` with the quadrant's scalars."""
    length, block = bd
    shape, q_axis = (((block_k, block_q), 1) if keys_major
                     else ((block_q, block_k), 0))
    q_rel = q0 % length + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_rel = k0 % length + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                   1 - q_axis)
    q_start = (q_rel & -block) if block & (block - 1) == 0 \
        else q_rel - jax.lax.rem(q_rel, jnp.int32(block))
    e = k_rel - q_start
    q_clean, k_clean = q0 >= length, k0 >= length
    lo = jnp.where(k_clean, -length, 0)
    hi = jnp.where(k_clean, jnp.where(q_clean, block - 1, -1),
                   jnp.where(q_clean, -1, block - 1))
    return jnp.logical_and(e >= lo, e <= hi)


def _bd_live(iq, ik, block_q, block_k, bd):
    """Whether any pair of q block ``iq`` and k block ``ik`` is attended
    under the block-diffusion mask ``bd`` (ints, or traced program ids):
    noised x noised where the blocks' ranges of the rows and the columns
    meet, noised x clean where the tile's first key lies before the last
    query's block, clean x clean as the causal edge moved to the block's
    end, clean x noised never."""
    length, block = bd
    q0, k0 = iq * block_q, ik * block_k
    qb0 = (q0 % length) // block
    qb1 = (q0 % length + block_q - 1) // block
    kb0 = (k0 % length) // block
    kb1 = (k0 % length + block_k - 1) // block
    q_noised, q_clean = q0 < length, q0 >= length
    k_noised, k_clean = k0 < length, k0 >= length
    return ((q_noised & k_noised & (kb1 >= qb0) & (kb0 <= qb1))
            | (q_noised & k_clean & (kb0 < qb1))
            | (q_clean & k_clean & (kb0 <= qb1)))


#: side of the sub-blocks a live noised x noised tile is walked in
#: (:func:`_bd_diagonal`). Timed alone on a v5e at cell 12's shapes (bh
#: 32 on 4 k/v heads, 8,192 positions, d 128, bf16; ms a call, forward /
#: ``bwd_dq`` / ``bwd_dkv``): the tiles whole 2.754 / 4.019 / 4.910; in
#: sub-blocks of 512 2.622 / 3.758 / 4.570, of 256 2.617 / 3.644 / 4.417,
#: of 128 2.580 / 3.609 / 4.334. The matrix unit still fills on 128 x 128
#: x 128 products, but a sub-block is a chain of two or five dependent
#: ones with the row reductions between them, so 7/8 of a tile's pairs
#: gone is 6-12% of a call and not 14.6 (PERF.md section 6, PR 65).
BD_SUB = 128


def _bd_sub(block_q, block_k, bd):
    """Side of the diagonal sub-blocks that a live noised x noised tile
    (the forward: piece) of ``block_q x block_k`` pairs is walked in
    under ``bd = (L, B)``, or 0: the tile is scored whole. In that
    quadrant the mask is block-diagonal in blocks of ``B``, so where
    ``B`` divides ``BD_SUB`` no pair off the tile's ``BD_SUB``-wide
    diagonal blocks is attended. The walk wants ``BD_SUB`` to divide
    both sides and the narrower side the wider (aligned tiles then nest,
    so that a live tile holds its narrower side's whole diagonal), and a
    tile wider than one sub-block."""
    if not bd:
        return 0
    small, large = sorted((block_q, block_k))
    walked = (BD_SUB % bd[1] == 0 and small % BD_SUB == 0
              and large % small == 0 and BD_SUB < large)
    return BD_SUB if walked else 0


def _bd_noised(iq, ik, block_q, block_k, bd):
    """Whether tile (``iq``, ``ik``) lies in the noised x noised
    quadrant (ints, or traced program ids)."""
    return (iq * block_q < bd[0]) & (ik * block_k < bd[0])


def _bd_diagonal(iq, ik, block_q, block_k, bd, sub, body):
    """``body((q_off, k_off, sub))`` for each ``sub x sub`` block on the
    diagonal of the live noised x noised tile (``iq``, ``ik``), by its
    first row's and first key's place in the tile. Along the narrower
    side those are the multiples of ``sub``; the wider side holds the
    narrower one (aligned tiles nest) from the remainder of its first
    position by the wider side on: a static place where ``iq`` / ``ik``
    are ints, which the forward, whose k block is whole q blocks, gets by
    handing its piece's place in the k block for ``ik``. Unrolled, so
    that the sub-blocks' slices of the scratch are static and Mosaic may
    overlap one sub-block's chain of products with the next's: as a
    ``fori_loop`` over ``pl.multiple_of`` offsets the three calls of
    cell 12 took 11.12 ms where unrolled they take 10.55 (PERF.md
    section 6, PR 65)."""
    q_in = (ik * block_k) % block_q if block_q > block_k else 0
    k_in = (iq * block_q) % block_k if block_k > block_q else 0

    def place(start, r):
        at = start + r * sub
        return at if isinstance(at, int) else pl.multiple_of(at, sub)

    for r in range(min(block_q, block_k) // sub):
        body((place(q_in, r), place(k_in, r), sub))


def _part_slices(part):
    """``(rows, keys)``: what a tile's body reads of its q-side and its
    k-side blocks and scratch: all of them, or the sub-block ``part =
    (q_off, k_off, side)``'s."""
    if part is None:
        return slice(None), slice(None)
    q_off, k_off, side = part
    return pl.ds(q_off, side), pl.ds(k_off, side)


def _bd_walked(live, iq, ik, block_q, block_k, bd, compute, place=None):
    """Tile (``iq``, ``ik``) of a kernel (the forward: a piece) where it
    is ``live``: ``compute()``, the tile whole, but where the
    block-diffusion mask's shapes allow (:func:`_bd_sub`) a tile of the
    noised x noised quadrant as ``compute(part)`` over its diagonal's
    sub-blocks. ``place``: what :func:`_bd_diagonal` is handed for
    ``ik`` (the forward's piece in its k block), ``ik`` itself if None."""
    sub = _bd_sub(block_q, block_k, bd)
    if sub:
        noised = _bd_noised(iq, ik, block_q, block_k, bd)
        pl.when(live & noised)(functools.partial(
            _bd_diagonal, iq, ik if place is None else place, block_q,
            block_k, bd, sub, compute))
        live = live & ~noised
    pl.when(live)(compute)


def _tile_keep_mask(seed_ref, b, iq, ik, block_q, block_k, rate,
                    keys_major=False):
    """Counter-based dropout keep-mask (rate is static).

    keep[i, j] is a pure hash of (seed, batch-head, ABSOLUTE query
    position, ABSOLUTE key position) — independent of the tiling — so
    the forward (its own blocks, :func:`fwd_tiles`, walked in pieces)
    and the backward kernels (their own tiles, :func:`bwd_tiles`)
    regenerate bit-identical masks. Found
    compiling on a real v5e: a pltpu-PRNG mask seeded per (b, iq, ik)
    tile cannot be reproduced by a differently-blocked backward pass,
    which silently corrupted dq
    (and Mosaic's prng_set_seed_32 takes at most two seed words anyway).
    A position hash also lowers in interpret mode, so CPU CI now covers
    the dropout path. Mix: odd-constant multiplies folded by xor, then
    the murmur3 fmix32 finalizer in uint32."""
    q_pos, k_pos = _tile_positions(iq, ik, block_q, block_k, keys_major)
    return _position_keep(seed_ref[0, 0], jnp.asarray(b, jnp.int32),
                          q_pos, k_pos, rate)


def _position_keep(seed, bh, q_pos, k_pos, rate):
    """keep = hash(seed, bh, q_pos, k_pos) >= rate-threshold, in ops that
    lower identically inside Pallas and in plain XLA — the single source
    of truth for the dropout mask shared by the kernels (via
    :func:`_tile_keep_mask`) and the explicit-mask golden (via
    :func:`dropout_keep_mask`)."""
    h = (seed * jnp.int32(-1640531527)                 # 0x9E3779B1
         ^ bh * jnp.int32(840146601)                   # 0x3243F6A9
         ^ q_pos * jnp.int32(-2048144789)              # 0x85EBCA6B
         ^ k_pos * jnp.int32(-1028477387))             # 0xC2B2AE35
    u = jax.lax.bitcast_convert_type(h, jnp.uint32)
    u = u ^ (u >> jnp.uint32(16))
    u = u * jnp.uint32(0x85EBCA6B)
    u = u ^ (u >> jnp.uint32(13))
    u = u * jnp.uint32(0xC2B2AE35)
    u = u ^ (u >> jnp.uint32(16))
    thresh = min(int(rate * 4294967296.0), 4294967295)
    return u >= jnp.uint32(thresh)


# ---------------------------------------------------------------------------
# forward kernel: grid (bh, nq, nk), accumulate over the nk axis in scratch
# ---------------------------------------------------------------------------
LANES = 128
#: keys one piece of a forward grid step scores at once. A step streams a
#: k/v block as wide as fits and walks it in pieces, so the (block_q,
#: piece) float32 intermediates stay small while a step's fixed cost is
#: paid once a block. 512 was the fastest piece or within 2% of it at
#: every shape timed on a v5e where it divides the block (1,024 is 2-10%
#: behind at 2,048 keys and more, 256 is 15-35% behind); 768 keys are
#: faster whole than in pieces of 384 (PERF.md section 6, PR 32).
FWD_PIECE = 512


def _fwd_piece(block_k):
    """Width of the pieces a k block of ``block_k`` keys is walked in:
    ``FWD_PIECE`` where it divides the block, else the whole block."""
    return FWD_PIECE if block_k % FWD_PIECE == 0 else block_k


def _piece_live(iq, piece, block_q, piece_k, window=0, bd=()):
    """Causal: whether any pair of q block ``iq`` with the keys
    ``[piece * piece_k, (piece + 1) * piece_k)`` lies on or under the
    diagonal and, with a ``window``, inside the band: the piece's last
    key within the window of the block's first query (ints, or traced
    program ids). ``bd``: under that mask instead (:func:`_bd_live`). A
    dead piece is skipped."""
    if bd:
        return _bd_live(iq, piece, block_q, piece_k, bd)
    live = piece * piece_k <= (iq + 1) * block_q - 1
    if window:
        live = live & ((piece + 1) * piece_k - 1 > iq * block_q - window)
    return live


def _lanes(x, width):
    """A lane-replicated (rows, 128) value at ``width`` lanes."""
    if width <= LANES:
        return x[:, :width]
    return jnp.tile(x, (1, -(-width // LANES)))[:, :width]


def _split_mask(refs, masked):
    """``(mask_ref or None, the other refs)``: a masked call's mask tile
    comes last of its operands, ahead of the outputs and the scratch."""
    return (refs[0], refs[1:]) if masked else (None, refs)


def _attended(mask_ref, keys=slice(None)):
    """The mask tile's ``keys`` columns as booleans. The tile is int8 (a
    quarter of the bytes of the narrowest type Mosaic compares in place)
    and is widened to int32 here, in registers, for the comparison."""
    return mask_ref[0, :, keys].astype(jnp.int32) != 0


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *refs, sm_scale, causal,
                kv_len, block_q, block_k, dropout_rate, masked=False,
                window=0, bd=()):
    """Online softmax over the k blocks of one q block. ``bd``: the
    block-diffusion mask in place of the causal one, its dead pieces
    skipped as the causal ones are (every row has a live key, and a
    piece that holds none of ITS keys adds nothing that stays, as under
    a window). ``window``: the
    band's other edge, index arithmetic like the causal one (a row's
    first live pieces may lie wholly left of ITS window: what they add
    at the stand-in maximum is scaled to 0 by the first real score).
    ``masked``: a
    (1, block_q, block_k) int8 tile of the call's mask follows v, and a
    pair is attended where it is not 0 AND :func:`_key_mask` holds (the
    tile is shared by the heads of a batch row; no piece is skipped for
    it beyond the causal ones). The running
    maximum lives lane-replicated in ``m_sc`` (rows, 128), so the
    rescaling factors are whole vregs and never a (rows, 1) column; the
    running sum lives LANE-WISE in ``l_sc``: each of its 128 lanes sums
    the keys congruent to it, rescaled by the row's factor (uniform over
    a row's lanes), and the lanes meet once, in ``_finish``. One
    cross-lane reduction a piece is left, the row maximum. Every live
    piece is masked, as every live tile was: leaving the select off the
    pieces that need none was timed and was worth nothing (PERF.md
    section 6, PR 32)."""
    mask_ref, (o_ref, lse_ref, acc_sc, m_sc, l_sc) = _split_mask(refs,
                                                                 masked)
    b = pl.program_id(0)     # read out here: interpret mode has no
    iq = pl.program_id(1)    # program_id inside pl.when's cond
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    piece_k = _fwd_piece(block_k)
    pieces = block_k // piece_k

    @pl.when(ik == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def _piece(c, part=None):
        """Piece ``c`` of the k block, whole, or its sub-block ``part``
        (:func:`_bd_diagonal`): the same update on those rows' running
        statistics and accumulator alone."""
        piece = ik * pieces + c              # among all pieces of the row
        keys = slice(None) if pieces == 1 else pl.ds(
            pl.multiple_of(c * piece_k, piece_k), piece_k)
        rows, width = slice(None), piece_k
        if part is not None:
            q_off, k_off, width = part
            rows, keys = pl.ds(q_off, width), pl.ds(c * piece_k + k_off,
                                                    width)
        q = q_ref[0, rows, :]                # (block_q, d)
        k = k_ref[0, keys, :]                # (piece_k, d)
        v = v_ref[0, keys, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        valid = _key_mask(iq, piece, block_q, piece_k, kv_len, causal,
                          window=window, bd=bd, part=part)
        if masked:
            valid = jnp.logical_and(valid, _attended(mask_ref, keys))
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_sc[rows, :]                           # (block_q, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, width))            # (block_q, piece_k)
        # softmax denominator uses UNdropped p; dropout only scales the
        # numerator (matches dropout-on-probs semantics)
        if width % LANES == 0:
            l_part = p[:, :LANES]
            for g in range(1, width // LANES):
                l_part = l_part + p[:, g * LANES:(g + 1) * LANES]
        else:     # an explicit block Mosaic would not take: sum in lane 0
            lane = jax.lax.broadcasted_iota(jnp.int32, l_sc.shape, 1)
            l_part = jnp.where(lane == 0,
                               jnp.sum(p, axis=1, keepdims=True), 0.0)
        l_sc[rows, :] = l_sc[rows, :] * alpha + l_part
        if dropout_rate > 0.0:
            keep = _tile_keep_mask(seed_ref, b, iq, piece,
                                   block_q, piece_k, dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_sc[rows, :] = acc_sc[rows, :] * _lanes(alpha,
                                                   acc_sc.shape[1]) + pv
        m_sc[rows, :] = m_new

    # One piece's intermediates at a time: each piece runs in a scope of
    # its own. Unrolled inline, Mosaic kept several pieces' float32 and
    # hash tiles live at once and refused float32 operands with dropout
    # from 2,048 keys on, for a described v5e (2-13 MiB over its 16 MiB
    # of scoped VMEM). Under ``causal`` the scope is the branch that
    # skips a dead piece, and the pieces are unrolled so that k and v are
    # sliced at static offsets (a loop was 20% slower at cell 2's shape,
    # the same at cell 3's); without it, where a condition that always
    # holds would be folded away, the scope is a loop's body (0-9% slower
    # than inline where inline compiled; PERF.md section 6, PR 32).
    # A live piece of the noised x noised quadrant is walked in the
    # sub-blocks of its diagonal, where the shapes allow (:func:`_bd_sub`);
    # under a k block of whole q blocks the piece's place in it says
    # where in the q block its keys' rows lie, statically.
    if causal or bd:
        for c in range(pieces):
            piece = ik * pieces + c
            _bd_walked(_piece_live(iq, piece, block_q, piece_k, window, bd),
                       iq, piece, block_q, piece_k, bd,
                       functools.partial(_piece, c),
                       place=piece if block_k % block_q else c)
    elif pieces == 1:
        _piece(0)
    else:
        jax.lax.fori_loop(0, pieces, lambda c, _: _piece(c), None)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.sum(l_sc[:], axis=1, keepdims=True)      # (block_q, 1)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        # one float32 a row, the rows along the lanes of a (1, block_q)
        # block: the lane-replicated tile, transposed, is 128 such rows
        lse_ref[0] = (m_sc[:] + jnp.log(l_safe)).T[:1]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
def _scores(queries, keys, keys_major):
    """``queries . keys^T`` over the head size in float32, (rows of
    ``queries``, rows of ``keys``) or, ``keys_major``, its transpose:
    either way a product over the last dimension of both operands."""
    lhs, rhs = (keys, queries) if keys_major else (queries, keys)
    return jax.lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dropped(x, keep, rate):
    """``x`` as the forward's dropout left it; ``keep`` None: as it is."""
    return x if keep is None else jnp.where(keep, x / (1.0 - rate), 0.0)


def _bwd_p(q, k, lse, mask, sm_scale, keys_major):
    """The tile's softmax probabilities from the row statistic ``lse``,
    which meets the tile as whole vregs or as a sublane broadcast."""
    s = jnp.where(mask, _scores(q, k, keys_major) * sm_scale, NEG_INF)
    return jnp.exp(s - lse)


def _bwd_ds(p, do, v, delta, keep, rate, sm_scale, keys_major):
    """The cotangent of the tile's scaled scores."""
    dp = _dropped(_scores(do, v, keys_major), keep, rate)
    return p * (dp - delta) * sm_scale


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, sm_scale, causal, kv_len, block_q, block_k,
                   dropout_rate, masked=False, window=0, bd=()):
    """A q block stays while k blocks stream, so the tile is held
    queries-major, (block_q, block_k), and the q block's two statistics
    are made lane-replicated (block_q, 128) tiles ONCE, into scratch
    (the (1, block_q) row over 128 sublanes, transposed), and used as
    whole vregs (:func:`_lanes`). Never a (rows, 1) column: in a Mosaic
    kernel that is as dear as 128 lanes to hold and dearer to use
    (PERF.md section 6, PR 32 and PR 39). Timed alone on a v5e against
    the keys-major form of ``bwd_dkv`` (here one transposed-left product
    more): within 2% either way at cells 2 to 5's shapes, 9% faster
    with dropout at cell 1's. ``masked``: the forward's (1, block_q,
    block_k) mask tile follows ``delta``."""
    mask_ref, (dq_ref, dq_sc, lse_sc, delta_sc) = _split_mask(refs, masked)
    b = pl.program_id(0)     # read out here: interpret mode has no
    iq = pl.program_id(1)    # program_id inside pl.when's cond
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)
        for row_ref, sc in ((lse_ref, lse_sc), (delta_ref, delta_sc)):
            sc[:] = jnp.broadcast_to(row_ref[0], (LANES, block_q)).T

    live = _piece_live(iq, ik, block_q, block_k, window, bd) \
        if causal or bd else True

    def _compute(part=None):
        """The tile, whole, or its sub-block ``part``
        (:func:`_bd_diagonal`): those rows of ``dq`` from those keys."""
        rows, keys = _part_slices(part)
        width = block_k if part is None else part[2]
        k = k_ref[0, keys, :]
        keep = _tile_keep_mask(seed_ref, b, iq, ik, block_q, block_k,
                               dropout_rate) if dropout_rate > 0.0 else None
        q, lse = q_ref[0, rows, :], _lanes(lse_sc[rows, :], width)
        valid = _key_mask(iq, ik, block_q, block_k, kv_len, causal,
                          window=window, bd=bd, part=part)
        if masked:
            valid = jnp.logical_and(valid, _attended(mask_ref))
        p = _bwd_p(q, k, lse, valid, sm_scale, keys_major=False)
        ds = _bwd_ds(p, do_ref[0, rows, :], v_ref[0, keys, :],
                     _lanes(delta_sc[rows, :], width),
                     keep, dropout_rate, sm_scale, keys_major=False)
        dq_sc[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _bd_walked(live, iq, ik, block_q, block_k, bd, _compute)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, *refs, sm_scale, causal, kv_len, block_q,
                    block_k, dropout_rate, masked=False, window=0, group=1,
                    q_blocks=0, bd=()):
    """A k block stays while q blocks stream, and the tile is held
    KEYS-MAJOR, (block_k, block_q): ``s^T = k q^T`` and ``dp^T = v do^T``
    contract the last dimension of both operands as ``s`` always did,
    ``dv += p^T do`` and ``dk += ds^T q`` are then plain products with
    no transposed tile (queries-major they contracted dimension 0 of
    both operands, two (block_q, block_k) transposes a step), and a
    streamed q block's statistics, (1, block_q) rows, meet the tile as a
    sublane broadcast. The masks are functions of absolute positions, so
    the transposed tile draws the forward's mask from swapped iotas.
    ``dv`` is accumulated before ``dp`` is formed, so that ``p_eff`` is
    dead by then: with both products after both tiles the call was 3%
    slower at cell 2's shape (PERF.md section 6, PR 39). ``masked``: a
    (1, block_k, block_q) tile of the call's mask TRANSPOSED (keys by
    queries, as this tile is held) follows ``delta``. ``group`` > 1
    (grouped-query attention): grid axis 0 walks the KEY/VALUE heads and
    axis 2 the ``group`` query heads that read this one, ``q_blocks``
    steps each, so the k block stays for all of them and their ``dk``
    and ``dv`` meet in the float32 scratch, rounded once; the dropout's
    counter is the QUERY head's, as in the forward."""
    mask_ref, (dk_ref, dv_ref, dk_sc, dv_sc) = _split_mask(refs, masked)
    b = pl.program_id(0)
    ik = pl.program_id(1)
    step = iq = pl.program_id(2)
    steps = pl.num_programs(2)
    if group > 1:       # member ``step // q_blocks`` of k/v head ``b``
        b, iq = b * group + step // q_blocks, step % q_blocks

    @pl.when(step == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    # causal: the q block is live iff its last query can see the first key
    live = ((iq + 1) * block_q - 1 >= ik * block_k) if causal else True
    if window:      # and its first query still sees the block's last key
        live = live & (iq * block_q < (ik + 1) * block_k - 1 + window)
    if bd:
        live = _bd_live(iq, ik, block_q, block_k, bd)

    def _compute(part=None):
        """The tile, whole, or its sub-block ``part``
        (:func:`_bd_diagonal`): those keys' ``dk`` and ``dv`` from those
        rows."""
        rows, keys = _part_slices(part)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        # the forward's (seed, b, absolute positions) -> the forward's mask
        keep = _tile_keep_mask(
            seed_ref, b, iq, ik, block_q, block_k, dropout_rate,
            keys_major=True) if dropout_rate > 0.0 else None
        k, lse = k_ref[0, keys, :], lse_ref[0, :, rows]
        valid = _key_mask(iq, ik, block_q, block_k, kv_len, causal,
                          keys_major=True, window=window, bd=bd, part=part)
        if masked:
            valid = jnp.logical_and(valid, _attended(mask_ref))
        p = _bwd_p(q, k, lse, valid, sm_scale, keys_major=True)
        over_q = (((1,), (0,)), ((), ()))
        dv_sc[keys, :] += jax.lax.dot_general(
            _dropped(p, keep, dropout_rate).astype(do.dtype), do, over_q,
            preferred_element_type=jnp.float32)
        ds = _bwd_ds(p, do, v_ref[0, keys, :], delta_ref[0, :, rows], keep,
                     dropout_rate, sm_scale, keys_major=True)
        dk_sc[keys, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, over_q,
            preferred_element_type=jnp.float32)

    _bd_walked(live, iq, ik, block_q, block_k, bd, _compute)

    @pl.when(step == steps - 1)
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# flat (BH, S, D) custom-vjp core
# ---------------------------------------------------------------------------
_SEED_SPEC = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                          memory_space=pltpu.SMEM)


def _last_live_k(iq, block_q, block_k):
    """Last k block the causal ``live`` test of q block ``iq`` passes."""
    return ((iq + 1) * block_q - 1) // block_k


def _first_live_q(ik, block_q, block_k):
    """First q block the causal ``live`` test of k block ``ik`` passes."""
    return (ik * block_k) // block_q


def _first_live_k(iq, block_q, block_k, window):
    """First k block with a key inside the window of q block ``iq``'s
    first query; below 0 where the band reaches the first key."""
    return (iq * block_q - window + 1) // block_k


def _last_live_q(ik, block_q, block_k, window):
    """Last q block whose first query still sees k block ``ik``'s last
    key through the window (it may lie past the grid's end)."""
    return ((ik + 1) * block_k - 2 + window) // block_q


def _q_spec(block_q, d):
    return pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))


def _where(cond, a, b):
    """``jnp.where`` that stays Python's on Python values: the index
    maps below are also walked with ints (:func:`grid_steps`, which an
    op may call inside a trace, where a ``jnp`` function of ints is a
    tracer)."""
    return (a if cond else b) if isinstance(cond, bool) \
        else jnp.where(cond, a, b)


def _clamp(x, lo, hi):
    """``min(max(x, lo), hi)``, Python's on ints."""
    if all(isinstance(n, int) for n in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _bd_live_k(block_q, block_k, bd):
    """:func:`_live_k` under the block-diffusion mask ``bd = (L, B)``:
    a noised q block's live k blocks are the noised ones its blocks of
    ``B`` meet, ``[n0, n1]``, and the clean ones up to ``c1``, the last
    with a key before its last query's block; a clean q block's the
    clean ones up to its last query's block. A dead step names the
    nearest live block of its half of the keys (a clean q block's
    noised steps the first clean block), so nothing is copied for it."""
    length, block = bd
    nqh, nkh = length // block_q, length // block_k

    def ik(i, j):
        q0 = (i % nqh) * block_q
        qb0, qb1 = q0 // block, (q0 + block_q - 1) // block
        noised = i < nqh
        n0 = _where(noised, qb0 * block // block_k, nkh)
        n1 = _where(noised, (qb1 * block + block - 1) // block_k, nkh)
        c1 = nkh + (qb1 * block + _where(noised, -1, block - 1)) // block_k
        return _where(j < nkh, _clamp(j, n0, n1), _clamp(j, 0, c1))
    return ik


def _bd_live_q(block_q, block_k, bd):
    """:func:`_dkv_live_q` under the block-diffusion mask: a noised k
    block is read by the noised q blocks its blocks of ``B`` meet; a
    clean one by the noised q blocks from the first whose last query's
    block lies after its first key's, and by the clean ones from the
    block that holds its first key's block."""
    length, block = bd
    nqh, nkh = length // block_q, length // block_k

    def iq(j, i):
        k0 = (j % nkh) * block_k
        kb0, kb1 = k0 // block, (k0 + block_k - 1) // block
        a0 = kb0 * block // block_q
        a1 = (kb1 * block + block - 1) // block_q
        last = 2 * nqh - 1
        clean = _where(
            i < nqh, _clamp(i, (kb0 + 1) * block // block_q, last),
            _clamp(i, nqh + kb0 * block // block_q, last))
        return _where(j < nkh, _clamp(i, a0, a1), clean)
    return iq


def _live_k(block_q, block_k, causal, window, bd=()):
    """``ik(i, j)``: the k block step ``j`` of q block ``i``'s row of the
    (bh, nq, nk) grids names. A step above the causal diagonal, whose
    arithmetic ``pl.when(live)`` skips, names the row's last live block
    again, and a step left of the window's band its first, so the
    pipeline issues no copy for either."""
    if bd:
        return _bd_live_k(block_q, block_k, bd)
    if not causal:
        return lambda i, j: j
    if not window:
        return lambda i, j: jnp.minimum(j, _last_live_k(i, block_q, block_k))
    return lambda i, j: jnp.minimum(
        jnp.maximum(j, _first_live_k(i, block_q, block_k, window)),
        _last_live_k(i, block_q, block_k))


def _kv_row(group):
    """``row(b)``: the row of the flat (batch * kv heads, sk, d) k and v
    that query head ``b`` of the flat (batch * heads, ...) arrays reads:
    its own where the head counts are equal, else its group's."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _k_spec(block_q, block_k, d, causal, window=0, group=1, bd=()):
    """k/v blocks of the (bh, nq, nk) grids (see :func:`_live_k`);
    ``group`` query heads read one k/v head (:func:`_kv_row`)."""
    ik, row = _live_k(block_q, block_k, causal, window, bd), _kv_row(group)
    return pl.BlockSpec((1, block_k, d),
                        lambda b, i, j: (row(b), ik(i, j), 0))


def _stat_spec(block_q):
    """A q block's row statistics (log-sum-exp, delta) in the (bh, nq,
    nk) grids: one float32 a row, the rows along the lanes of a (bh, 1,
    sq) array."""
    return pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))


def _mask_spec(block_q, block_k, causal, heads):
    """A masked call's (1, block_q, block_k) int8 mask tile in the (bh,
    nq, nk) grids: the mask is (batch, sq, sk), one for the ``heads``
    heads of a batch row, and a dead causal step names the tile of the
    row's last live k block, as :func:`_k_spec` does."""
    live_k = _live_k(block_q, block_k, causal, 0)
    return pl.BlockSpec((1, block_q, block_k),
                        lambda b, i, j: (b // heads, i, live_k(i, j)))


def _dkv_live_q(block_q, block_k, causal, window=0, bd=()):
    """``iq(j, i)``: the q block step ``i`` of k block ``j``'s row of the
    (bh, nk, nq) dkv grid names: a dead causal step names the column's
    first live q block, a step under the window's band its last (see
    _live_k)."""
    if bd:
        return _bd_live_q(block_q, block_k, bd)
    if causal and window:
        return lambda j, i: jnp.minimum(
            jnp.maximum(i, _first_live_q(j, block_q, block_k)),
            _last_live_q(j, block_q, block_k, window))
    if causal:
        return lambda j, i: jnp.maximum(i, _first_live_q(j, block_q,
                                                         block_k))
    return lambda j, i: i


def _dkv_member(group, nq):
    """``(head(b, t), block(t))`` of step ``t`` of the dkv grid's last
    axis under k/v head ``b``: the query head whose blocks stream and
    which of its ``nq`` q blocks. With equal head counts the axis is the
    q blocks of head ``b``; with ``group`` query heads a k/v head it is
    the group's members one after another, ``nq`` steps each."""
    if group == 1:
        return (lambda b, t: b), (lambda t: t)
    return (lambda b, t: b * group + t // nq), (lambda t: t % nq)


def _dkv_mask_spec(block_q, block_k, causal, heads, group=1, nq=0):
    """The (1, block_k, block_q) tile of the TRANSPOSED mask, (batch, sk,
    sq), in the dkv grid, whose axis 0 walks the ``heads // group`` k/v
    heads of a batch row."""
    iq = _dkv_live_q(block_q, block_k, causal)
    _, block = _dkv_member(group, nq)
    kv_heads = heads // group
    return pl.BlockSpec((1, block_k, block_q),
                        lambda b, j, i: (b // kv_heads, j, iq(j, block(i))))


def _dkv_specs(block_q, block_k, d, causal, window=0, group=1, nq=0,
               bd=()):
    """(q/do, k/v, row statistics) specs of the (b * kv heads, nk,
    group * nq) dkv grid: the index maps swap the roles of grid axes 1
    and 2, and a dead step names its OWN member's nearest live block
    (:func:`_dkv_member`, :func:`_dkv_live_q`)."""
    iq = _dkv_live_q(block_q, block_k, causal, window, bd)
    head, block = _dkv_member(group, nq)
    return (pl.BlockSpec((1, block_q, d),
                         lambda b, j, i: (head(b, i), iq(j, block(i)), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, j, i: (head(b, i), 0, iq(j, block(i)))))


def grid_steps(kernel, bh, sq, sk, block_q, block_k, causal, window=0,
               kv_group=1, bd=()):
    """What the grid of one call costs (``window``: under the causal
    band of that many keys a query, both of whose edges are skipped;
    ``kv_group``: the query heads that read one k/v head, which changes
    none of the counts: ``bwd_dkv`` walks the same steps a k/v head at a
    time).
    ``steps``: all of them, each paid for; ``live_steps``: those whose
    arithmetic runs; ``fetched_steps``:
    those that name another streamed block (k/v for ``fwd`` and
    ``bwd_dq``, q/do/row statistics for ``bwd_dkv``) than the step before
    in their row of the grid, so that the pipeline copies one. A row's
    first step counts as a fetch (Mosaic skips that one too where the
    row before ended on the same block). The forward walks a step's k
    block in pieces of ``piece_k`` keys (:func:`_fwd_piece`) and skips
    the dead ones: ``live_pieces`` are those it computes. ``bd``: under
    the block-diffusion mask, whose live steps :func:`_bd_live` says
    and whose index maps are walked as they stand; ``visited_pairs`` are
    then the pairs the kernel's body is entered with: a live tile's (the
    forward: a live piece's), but of one whose diagonal is walked
    (``bd_sub``, :func:`_bd_sub`) its sub-blocks', of which the mask
    attends ``bh (L L + L B)``."""
    nq, nk = sq // block_q, sk // block_k
    live = fetched = 0
    if bd:
        dkv = kernel == "bwd_dkv"
        name = (_bd_live_q if dkv else _bd_live_k)(block_q, block_k, bd)
        for row in range(nk if dkv else nq):
            along = range(nq if dkv else nk)
            live += sum(bool(_bd_live(*((t, row) if dkv else (row, t)),
                                      block_q, block_k, bd)) for t in along)
            fetched += len({name(row, t) for t in along})
        # the forward scores a piece at a time, the others a tile
        wide = _fwd_piece(block_k) if kernel == "fwd" else block_k
        sub = _bd_sub(block_q, wide, bd)
        visited = sum(
            min(block_q, wide) * sub
            if sub and _bd_noised(i, j, block_q, wide, bd)
            else block_q * wide
            for i in range(nq) for j in range(sk // wide)
            if _bd_live(i, j, block_q, wide, bd))
    elif kernel == "bwd_dkv":            # rows are k blocks, q blocks stream
        for j in range(nk):
            first = _first_live_q(j, block_q, block_k) if causal else 0
            last = min(nq - 1, _last_live_q(j, block_q, block_k, window)) \
                if window else nq - 1
            live += max(0, last + 1 - first)
            fetched += len({min(max(i, first), last) for i in range(nq)})
    else:                              # rows are q blocks, k blocks stream
        for i in range(nq):
            last = _last_live_k(i, block_q, block_k) if causal else nk - 1
            first = max(0, _first_live_k(i, block_q, block_k, window)) \
                if window else 0
            live += max(0, min(nk, last + 1) - first)
            fetched += len({min(max(j, first), last) for j in range(nk)})
    out = {"kernel": "flash_attention_" + kernel, "block_q": block_q,
           "block_k": block_k, "steps": bh * nq * nk,
           "live_steps": bh * live, "fetched_steps": bh * fetched}
    if window:
        out["window"] = window
    if kv_group > 1:
        out["kv_group"] = kv_group
    if bd:
        out.update(block_diffusion="%dx%d" % bd, bd_sub=sub,
                   visited_pairs=bh * visited)
    if kernel == "fwd":
        piece_k = _fwd_piece(block_k)
        out.update(piece_k=piece_k, live_pieces=bh * sum(
            not (causal or bd)
            or bool(_piece_live(i, p, block_q, piece_k, window, bd))
            for i in range(nq) for p in range(sk // piece_k)))
    else:       # the row statistics the call is handed, and its tile's form
        out.update(stat_bytes=2 * bh * sq * 4,
                   tile="keys_major" if kernel == "bwd_dkv"
                   else "queries_major")
    return out


def _note_grid(kernel, q, k, block_q, block_k, causal, masked=False,
               window=0, bd=()):
    """One ``flash.grid`` instant per emitted call (flat operands ``q``
    and ``k``), at trace time; a masked call's says so, a windowed
    call's says ``window=``, a grouped one's ``kv_group=``, one under
    the block-diffusion mask ``block_diffusion=`` and ``bd_sub=``, the
    side of the sub-blocks its noised x noised tiles are walked in (0:
    they are scored whole)."""
    if events.enabled():
        events.instant("flash.grid", **grid_steps(
            kernel, q.shape[0], q.shape[1], k.shape[1], block_q, block_k,
            causal, window, q.shape[0] // k.shape[0], bd),
            **({"masked": True} if masked else {}))


# The three calls are jitted with ``inline=True``: a step with many attention
# layers of one shape then traces each kernel's body once, not once a
# layer, and every layer's ``pallas_call`` holds the same jaxpr, which
# JAX lowers to Mosaic once. The traced step is what it was without the
# jit, equation for equation (a jit that is not inlined also saves the
# time but hands XLA another module: BERT-large's step ran 1.9% slower
# and GPT-2's 2.7% faster). Traced and lowered a layer at a time the
# three kernels cost 85 ms a layer on the chip's host, 6 s of set-up for
# BERT-large's 24 layers (PERF.md section 6, PR 30).
_STATIC = ("kv_len", "sm_scale", "causal", "block_q", "block_k",
           "dropout_rate", "interpret", "heads", "window", "bd")


def _masked(mask, spec, window=0, bd=()):
    """What a mask, a window or the block-diffusion form adds to a call:
    ``(kernel options, in_specs, operands)``, nothing where ``mask`` is
    None, the window 0 and ``bd`` empty. A window and ``bd`` are options
    alone, never operands."""
    opts = {"window": window} if window else {}
    if bd:
        opts["bd"] = bd
    if mask is None:
        return opts, [], ()
    return dict(opts, masked=True), [spec()], (mask,)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _fwd_call(q, k, v, seed, kv_len, sm_scale, causal, block_q, block_k,
              dropout_rate, interpret, mask=None, heads=1, window=0, bd=()):
    """q (bh, sq, d); k and v (bh // group, sk, .): a query head reads
    its group's row (:func:`_kv_row`). ``mask``: None or (bh // heads,
    sq, sk) int8."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]      # q.k over d, p.v over dv
    group = bh // k.shape[0]
    opts, mask_specs, mask_args = _masked(mask, functools.partial(
        _mask_spec, block_q, block_k, causal, heads), window, bd)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        kv_len=kv_len, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate, **opts)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[_SEED_SPEC, _q_spec(block_q, d),
                  _k_spec(block_q, block_k, d, causal, window, group, bd),
                  _k_spec(block_q, block_k, dv, causal, window, group, bd)]
        + mask_specs,
        out_specs=[_q_spec(block_q, dv), _stat_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(seed, q, k, v, *mask_args)
    return o, lse[:, 0, :]


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_dq_call(seed, q, k, v, do, lse, delta, kv_len, sm_scale,
                 causal, block_q, block_k, dropout_rate, interpret,
                 mask=None, heads=1, window=0, bd=()):
    """``lse`` and ``delta`` are (bh, 1, sq) float32: one value a row;
    k and v (bh // group, sk, .) as the forward's; ``mask``: None or
    (bh // heads, sq, sk) int8."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    opts, mask_specs, mask_args = _masked(mask, functools.partial(
        _mask_spec, block_q, block_k, causal, heads), window, bd)
    stat = _stat_spec(block_q)
    qs = _q_spec(block_q, d)
    ks = _k_spec(block_q, block_k, d, causal, window, group, bd)
    dos = _q_spec(block_q, dv)
    vs = _k_spec(block_q, block_k, dv, causal, window, group, bd)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          kv_len=kv_len, block_q=block_q, block_k=block_k,
                          dropout_rate=dropout_rate, **opts),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[_SEED_SPEC, qs, ks, vs, dos, stat, stat] + mask_specs,
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[     # dq, and the q block's lse and delta by lanes
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(seed, q, k, v, do, lse, delta, *mask_args)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_dkv_call(seed, q, k, v, do, lse, delta, kv_len, sm_scale,
                  causal, block_q, block_k, dropout_rate, interpret,
                  mask=None, heads=1, window=0, bd=()):
    """``lse`` and ``delta`` are (bh, 1, sq) float32: one value a row;
    k and v (bh // group, sk, .), and ``dk``, ``dv`` in their shapes:
    the grid walks the k/v heads, and a k block stays while its group's
    query heads' q blocks stream (:func:`_dkv_member`). ``mask``: None
    or the mask TRANSPOSED, (bh // heads, sk, sq) int8."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    group, nq = bh // k.shape[0], sq // block_q
    opts, mask_specs, mask_args = _masked(mask, functools.partial(
        _dkv_mask_spec, block_q, block_k, causal, heads, group, nq), window,
        bd)
    if group > 1:
        opts = dict(opts, group=group, q_blocks=nq)
    qs2, ks2, stat2 = _dkv_specs(block_q, block_k, d, causal, window, group,
                                 nq, bd)
    dos2, vs2, _ = _dkv_specs(block_q, block_k, dv, causal, window, group,
                              nq, bd)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          kv_len=kv_len, block_q=block_q, block_k=block_k,
                          dropout_rate=dropout_rate, **opts),
        grid=(bh // group, sk // block_k, group * nq),
        in_specs=[_SEED_SPEC, qs2, ks2, vs2, dos2, stat2, stat2]
        + mask_specs,
        out_specs=[ks2, vs2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(seed, q, k, v, do, lse, delta, *mask_args)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, seed, kv_len, sm_scale, causal, block_q, block_k,
           dq_blocks, dkv_blocks, dropout_rate, interpret, window, bd):
    o, _ = _noted_fwd_call(q, k, v, seed, kv_len, sm_scale, causal, block_q,
                           block_k, dropout_rate, interpret, window=window,
                           bd=bd)
    return o


def _noted_fwd_call(q, k, v, seed, kv_len, sm_scale, causal, block_q,
                    block_k, dropout_rate, interpret, mask=None, heads=1,
                    window=0, bd=()):
    _note_grid("fwd", q, k, block_q, block_k, causal, mask is not None,
               window, bd)
    return _fwd_call(q, k, v, seed, kv_len, sm_scale, causal, block_q,
                     block_k, dropout_rate, interpret, mask=mask,
                     heads=heads, window=window, bd=bd)


def _flash_fwd_rule(q, k, v, seed, kv_len, sm_scale, causal, block_q,
                    block_k, dq_blocks, dkv_blocks, dropout_rate,
                    interpret, window, bd):
    o, lse = _noted_fwd_call(q, k, v, seed, kv_len, sm_scale, causal,
                             block_q, block_k, dropout_rate, interpret,
                             window=window, bd=bd)
    return o, (q, k, v, seed, o, lse)


def _backward_calls(kv_len, sm_scale, causal, dq_blocks, dkv_blocks,
                    dropout_rate, interpret, q, k, v, seed, o, lse, do,
                    mask=None, heads=1, window=0, bd=()):
    """``(dq, dk, dv)`` from the forward's operands, output and
    log-sum-exp; ``mask``: the forward's, which the dkv call is handed
    transposed (an (sk, sq) int8 copy a batch row, made here)."""
    masked = mask is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # one float32 a row, the rows along the lanes: nothing is replicated
    operands = (seed, q, k, v, do, lse[:, None, :], delta[:, None, :],
                kv_len, sm_scale, causal)
    _note_grid("bwd_dq", q, k, *dq_blocks, causal, masked, window, bd)
    dq = _bwd_dq_call(*operands, *dq_blocks, dropout_rate, interpret,
                      mask=mask, heads=heads, window=window, bd=bd)
    _note_grid("bwd_dkv", q, k, *dkv_blocks, causal, masked, window, bd)
    dk, dv = _bwd_dkv_call(
        *operands, *dkv_blocks, dropout_rate, interpret,
        mask=jnp.swapaxes(mask, 1, 2) if masked else None, heads=heads,
        window=window, bd=bd)
    return dq, dk, dv


def _no_cotangent(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _flash_bwd_rule(kv_len, sm_scale, causal, fwd_block_q, fwd_block_k,
                    dq_blocks, dkv_blocks, dropout_rate, interpret, window,
                    bd, res, do):
    q, k, v, seed, o, lse = res
    return _backward_calls(kv_len, sm_scale, causal, dq_blocks, dkv_blocks,
                           dropout_rate, interpret, q, k, v, seed, o, lse,
                           do, window=window, bd=bd) + (_no_cotangent(seed),)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# A masked call is two steps, so that a caller may stand between them:
# the forward kernel alone, with no gradient, and ``_from_forward``,
# which IS the forward's output to whoever reads it and whose backward
# runs the dq and dkv kernels from the operands, the mask and the
# forward's output and log-sum-exp. Those two are then ARGUMENTS of the
# differentiated function, not residuals made inside it: a caller that
# names them (``jax.ad_checkpoint.checkpoint_name``) for an enclosing
# ``jax.checkpoint``'s policy keeps them, and the forward kernel does
# not run again for the backward (``ops/sparse_attention.py``).
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _from_forward(q, k, v, seed, mask, o, lse, kv_len, sm_scale, causal,
                  heads, dq_blocks, dkv_blocks, dropout_rate, interpret):
    return o


def _from_forward_fwd_rule(q, k, v, seed, mask, o, lse, *static):
    return o, (q, k, v, seed, mask, o, lse)


def _from_forward_bwd_rule(kv_len, sm_scale, causal, heads, dq_blocks,
                           dkv_blocks, dropout_rate, interpret, res, do):
    q, k, v, seed, mask, o, lse = res
    grads = _backward_calls(kv_len, sm_scale, causal, dq_blocks, dkv_blocks,
                            dropout_rate, interpret, q, k, v, seed, o, lse,
                            do, mask=mask, heads=heads)
    return grads + (_no_cotangent(seed), _no_cotangent(mask),
                    jnp.zeros_like(o), jnp.zeros_like(lse))


_from_forward.defvjp(_from_forward_fwd_rule, _from_forward_bwd_rule)


# ---------------------------------------------------------------------------
# backward tiles
# ---------------------------------------------------------------------------
#: What the working set of one backward grid step may take, as
#: :func:`_bwd_vmem_bytes` counts it: Mosaic compiles a v5e kernel under a
#: scoped-VMEM limit of 16 MiB, and of 480 compiles for a described v5e
#: (both kernels, bf16 / f32, d 64 / 128 / 256, dropout, causal, ten tiles
#: of 256 to 2048 a side) every one it refused counts 16.0 MiB or more.
BWD_VMEM_BUDGET = 15 * 1024 * 1024
#: the widest tile timed on the chip (PERF.md section 6, PR 28)
MAX_BWD_TILE = 1024


def _mask_vmem_bytes(rows, cols):
    """What a masked call's (rows, cols) int8 mask tile adds to a grid
    step's working set: the tile, double-buffered. What it is widened to
    for the comparison streams through registers with the other masks
    (of 150 masked compiles for a described v5e, bf16 / f32, d 64 / 128
    / 256, dropout, the three kernels at six tiles each, every one
    Mosaic refused counts 18 MiB or more this way; PERF.md section 6,
    PR 49)."""
    return 2 * rows * cols


def _bwd_vmem_bytes(kernel, block_q, block_k, d, itemsize, dropout,
                    dv=None, masked=False):
    """Working set of one grid step of ``bwd_dq`` / ``bwd_dkv``: the
    double-buffered operand and output blocks (the two row statistics
    are (1, block_q) float32 rows, which fill 8 sublanes), the f32
    scratch accumulators and, in ``bwd_dq``, the q block's two
    lane-replicated (block_q, 128) statistics, a staging copy of the
    q-side and k-side blocks at twice their width around the matmuls,
    and the (block_q, block_k) intermediates: of ``s``, ``p``, ``dp``,
    ``ds``, the masks and the casts Mosaic streams through registers and
    keeps about one f32 tile, one more for the dropout hash and keep
    mask. ``d`` is the head size of q and k (and dq, dk), ``dv`` that of
    v and do (and dv); None means the same. ``masked``: the call's mask
    tile beside them (:func:`_mask_vmem_bytes`)."""
    qk = -(-d // 128) * 128                  # a (rows, 64) block fills 128
    both = qk + -(-(d if dv is None else dv) // 128) * 128
    q_side = block_q * both * itemsize + 2 * 8 * block_q * 4     # q, do
    k_side = block_k * both * itemsize                           # k, v
    if kernel == "bwd_dq":
        out = block_q * qk * itemsize
        scratch = block_q * (qk + 2 * LANES) * 4
    else:
        out, scratch = block_k * both * itemsize, block_k * both * 4
    stage = (block_q + block_k) * both * itemsize
    tiles = (2 if dropout else 1) * block_q * block_k * 4
    if masked:
        tiles += _mask_vmem_bytes(block_q, block_k)
    return 2 * (q_side + k_side + out) + scratch + stage + tiles


def _tile_sizes(padded, most=MAX_BWD_TILE):
    """Multiples of 128, at most ``most``, that divide ``padded``; a
    sequence with none (shorter than 128, or not a multiple of it) is
    one tile."""
    sizes = [t for t in range(128, min(padded, most) + 1, 128)
             if padded % t == 0]
    return sizes or [padded]


def bwd_tiles(sq, sk, d, dtype, dropout, dv=None, masked=False):
    """``((block_q, block_k) of bwd_dq, (block_q, block_k) of bwd_dkv)``
    for padded sequence lengths ``sq``, ``sk`` and padded head dims ``d``
    (q, k) and ``dv`` (v, the output; None: the same as ``d``):
    per kernel the tile of the most pairs whose working set fits
    ``BWD_VMEM_BUDGET``, and of two such the one with the wider resident
    side (dq holds a q block while k blocks stream, dkv a k block). On a
    v5e a grid step costs 0.3-0.4 us whatever it does and a live tile 4-6
    ns per 1,024 pairs, so fewer and larger steps won at every shape
    timed, causal or not: one 1,024-wide causal tile computes twice the
    pairs it needs and still beats three 512-wide ones (PERF.md section
    6, PR 28)."""
    itemsize = jnp.dtype(dtype).itemsize
    out = []
    for kernel, resident in (("bwd_dq", 0), ("bwd_dkv", 1)):
        tiles = list(itertools.product(_tile_sizes(sq), _tile_sizes(sk)))
        fits = [t for t in tiles
                if _bwd_vmem_bytes(kernel, *t, d, itemsize, dropout, dv,
                                   masked)
                <= BWD_VMEM_BUDGET] or tiles[:1]      # the smallest there is
        out.append(max(fits, key=lambda t: (t[0] * t[1], t[resident])))
    return tuple(out)


def _explicit_block(bwd_block, fwd_block):
    """A caller's backward block, made to tile the sequence the forward's
    block padded: no larger than that block, and a divisor of it."""
    bwd_block = min(bwd_block, fwd_block)
    return fwd_block if fwd_block % bwd_block else bwd_block


# ---------------------------------------------------------------------------
# forward tiles
# ---------------------------------------------------------------------------
#: the tallest q block and the widest k block timed on the chip (PERF.md
#: section 6, PR 32)
MAX_FWD_BLOCK_Q = 1024
MAX_FWD_BLOCK_K = 4096


def _fwd_vmem_bytes(block_q, block_k, d, itemsize, dropout, dv=None,
                    masked=False):
    """Working set of one forward grid step: the double-buffered q, k, v,
    o and log-sum-exp blocks, the three float32 scratch buffers, the
    (block_q, piece) intermediates of one piece of the k block (each
    piece runs in a scope of its own: of ``s``, ``p``, the mask and the
    cast Mosaic keeps about one float32 tile, one more for the dropout
    hash and keep mask), and the float32 quotient and the lane-replicated
    log-sum-exp with its transpose that ``_finish`` forms before it
    writes the output blocks (with one k block a row they share the step
    with the pieces; the log-sum-exp block itself is a (1, block_q) row,
    which fills 8 sublanes). Held to
    ``BWD_VMEM_BUDGET`` and checked against Mosaic for a described v5e
    (PERF.md section 6, PR 32): every block :func:`fwd_tiles` derives
    over a grid of 488 (dtype, head sizes, dropout, causal, lengths of
    256 to 8,192, sq != sk) compiles, and of explicit blocks around the
    limit every one Mosaic refused counts over the budget."""
    qk = -(-d // 128) * 128                  # a (rows, 64) block fills 128
    pv = -(-(d if dv is None else dv) // 128) * 128
    blocks = (block_q * (qk + pv) * itemsize + 8 * block_q * 4     # q, o, lse
              + block_k * (qk + pv) * itemsize)                    # k, v
    scratch = block_q * (pv + 2 * 128) * 4
    tiles = (2 if dropout else 1) * block_q * _fwd_piece(block_k) * 4
    if masked:      # the whole (block_q, block_k) tile, read by pieces
        tiles += _mask_vmem_bytes(block_q, block_k)
    finish = block_q * (pv + 2 * 128) * 4
    return 2 * blocks + scratch + tiles + finish


def fwd_tiles(sq, sk, d, dtype, dropout, dv=None, masked=False):
    """``(block_q, block_k)`` of the forward kernel for padded sequence
    lengths ``sq``, ``sk`` and padded head dims ``d`` (q, k) and ``dv``
    (v, the output; None: the same as ``d``). The q block is resident
    while k/v blocks stream, and a step walks its k block in pieces
    (:func:`_fwd_piece`), so a wider k block buys fewer steps and not
    larger intermediates: the widest k block whose working set fits
    ``BWD_VMEM_BUDGET`` won at every shape timed on a v5e (all 4,096 keys
    of the benchmark's cell 3: 1.52-1.60 ms a call, 1.60 at 2,048,
    1.70-1.74 on the best tiles without pieces, 3.25 on the 512 x 512 it
    had). The q block is the tallest that fits beside it, up to 1,024
    rows (the whole of cell 2's sequence, one step a (batch, head): 0.49
    ms a call against 0.52 in two of 512). Where the keys do not fit one
    block they are streamed again for every q block, and the taller
    block halves that traffic (8,192 positions: 0.96 against 1.04 ms).
    PERF.md section 6, PR 32."""
    itemsize = jnp.dtype(dtype).itemsize
    tiles = itertools.product(_tile_sizes(sq, MAX_FWD_BLOCK_Q),
                              _tile_sizes(sk, MAX_FWD_BLOCK_K))
    fits = [t for t in tiles
            if _fwd_vmem_bytes(*t, d, itemsize, dropout, dv, masked)
            <= BWD_VMEM_BUDGET]
    if not fits:                       # the smallest there is
        return _tile_sizes(sq)[0], _tile_sizes(sk)[0]
    block_k = max(bk for _, bk in fits)
    if masked and block_k < sk:
        # the mask's tile grows with both sides, and the widest k block
        # would leave a q block of half the rows: where the keys are
        # streamed again for every q block anyway, the tallest q block
        # first (8,192 keys at head size 128 on a v5e, ms a call: 4.97 at
        # 1024 x 2048, 5.69 at 512 x 4096; at 4,096 keys, all in one
        # block, 512 x 4096 wins, 1.33 against 1.53; PERF.md section 6,
        # PR 49)
        block_q = max(bq for bq, _ in fits)
        return block_q, max(bk for bq, bk in fits if bq == block_q)
    return max(bq for bq, bk in fits if bk == block_k), block_k


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    mesh=None, spec=None, mask=None, window: int = 0,
                    block_diffusion: tuple = ()):
    """Tiled flash attention. q: (b, h, sq, d); k: (b, kvh, sk, d); v:
    (b, kvh, sk, dv), and the output (b, h, sq, dv). ``dv`` may differ
    from ``d`` (latent attention: q.k over 192, p.v over 128); the score
    scale defaults to ``1 / sqrt(d)``.

    ``kvh`` divides ``h`` (grouped-query attention; the group is read
    off the shapes): query head ``i`` reads k/v head ``i // (h / kvh)``,
    as if both were repeated with ``jnp.repeat`` on the heads' axis. They
    are not: the forward and ``bwd_dq`` name the group's row in their
    k/v index maps, ``bwd_dkv`` walks the k/v heads and sums a group's
    ``dk`` and ``dv`` in its float32 accumulator, and ``dk``, ``dv``
    come at ``kvh`` heads. With ``kvh == h`` every call is the text it
    was (docs/kernels.md).

    ``mask``: None, or a (b, sq, sk) int8 (or bool) array shared by the
    heads of a batch row, not 0 where the pair is attended; under
    ``causal`` a pair is attended where both say so. It is no
    differentiated operand. The three kernels read its (block_q,
    block_k) tile beside q, k and v, and the blocks are derived with the
    tile counted, so a masked call's are narrower (docs/kernels.md). A
    row with no attended key has no meaning (its output is a mean of
    values, its gradients 0 where the cotangent is).

    ``window``: 0, or the keys a query sees under ``causal``, its own
    and the ``window - 1`` before it (``s <= t and s > t - window``). It
    is index arithmetic inside the kernels, like the causal edge, and
    never an operand: the mask is drawn from the tile's positions, a
    step or a forward piece on either side of the band computes nothing,
    and its index map names the band's nearest block, so nothing is
    copied for it (docs/kernels.md). A window of at least the keys is
    the causal call. The tiles are the causal call's.

    ``block_diffusion``: ``()``, or ``(L, B)`` for self-attention over
    ``2 L`` positions, a noised copy of ``L`` tokens and then the clean
    one, in blocks of ``B`` tokens (``B`` divides ``L``): with ``b(i) =
    (i mod L) // B`` a noised query sees the noised keys of its own
    block and the clean keys of earlier blocks, a clean query the clean
    keys of its own and earlier blocks, and no clean query a noised key:
    ``L L + L B`` of the ``4 L L`` pairs. Not ``causal``, no window, mask
    or dropout beside it. Index arithmetic like the window: the mask
    from the tile's positions (:func:`_bd_mask`), the dead tiles (the
    clean x noised quadrant, the clean x clean upper triangle, all but
    the diagonal tiles of noised x noised, the noised x clean tiles of
    later blocks) skipped and named as live neighbours, and a diagonal
    tile of noised x noised walked as the ``BD_SUB``-wide sub-blocks of
    its diagonal where ``B`` divides that side (:func:`_bd_sub`). The
    tiles are derived for ``L`` positions, so that none straddles the halves; ``L``
    has to be a multiple of 128 (docs/kernels.md).

    Pads the key length to a multiple of 128 and the query length to a
    multiple of 8 (of 128 from 512 positions on), tiles them by divisors,
    pads each head dim to a multiple of 64 (padded keys masked, padded
    head dims sliced off), runs the Pallas kernels, and is differentiable
    via the custom VJP. ``dropout_rate`` > 0
    applies in-kernel counter-based dropout to the attention
    probabilities (requires ``dropout_seed``, an int32 scalar). The
    attention ops call this by themselves from 1024 positions, and from
    256 when they drop probabilities: the lengths from which one layer's
    forward + backward was faster here than through XLA's materialised
    s² attention in every column timed on a v5e (PERF.md section 6, PR
    30; ``ops/nn_ops.py::MultiHeadAttentionOp.auto_takes_flash``).

    ``block_q`` / ``block_k`` = None take the forward's blocks from the
    shapes (:func:`fwd_tiles`): the k/v block as wide as fits, walked in
    pieces of 512 keys, under a q block of up to 1,024 rows. Timed on a
    v5e, device time, bf16 (PERF.md section 6, PR 32): at bh 144, s
    1024, d 64, causal the forward takes 1.09 ms a call as it was
    (512x512, a (rows, 1) column for each row statistic), 0.56 with the
    running maximum lane-replicated and the running sum lane-wise, 0.49
    at 1024x1024 in two pieces; at bh 32, s 4096, 192 / 128, causal 3.25,
    1.84 and 1.60 at 1024x4096. Masks only on the pieces the diagonal
    crosses and the score scale folded into q were timed too and were
    worth nothing (the kernel waits on the matrix unit, not on the
    selects); they are not in it. A value given wins.
    ``bwd_block_q`` / ``bwd_block_k`` = None
    take each backward kernel's tile from the shapes (:func:`bwd_tiles`);
    a value given is used by both. Timed on a v5e over 128 to 1024 a
    side (PERF.md section 6, PR 28): at bh 144, s 1024, d 64, causal the
    dq kernel takes 3.09 ms a call at 128x128 and 0.66 at 1024x1024, dkv
    3.58 and 0.91; the largest tile that compiles won, or came within 3%
    of the winner, at every shape. Under ``causal`` a grid step above
    the diagonal is skipped and names its neighbour's k/v (q, in dkv)
    block again, so nothing is copied for it.

    ``mesh`` / ``spec``: inside a multi-device ``jit`` GSPMD cannot
    partition a Mosaic kernel, so with a mesh of more than one device
    the call runs under ``shard_map``. ``spec`` is the (b, h, s, d)
    PartitionSpec of the operands; only its batch and head entries are
    used — sequence and head_dim stay whole on every device."""
    if window < 0 or (window and not causal):
        raise ValueError(f"window={window} wants causal=True and >= 0")
    if window >= k.shape[2]:           # the band is the whole triangle
        window = 0
    bd = tuple(int(n) for n in block_diffusion)
    if bd:
        length, block = bd
        if causal or window or mask is not None or dropout_rate > 0.0:
            raise NotImplementedError(
                "the block-diffusion mask is built with no causal edge, "
                "window, mask operand or dropout beside it")
        if q.shape[2] != 2 * length or k.shape[2] != 2 * length \
                or length % 128 or block <= 0 or length % block:
            raise NotImplementedError(
                f"block_diffusion={bd} wants {2 * length} queries and keys"
                f" (got {q.shape[2]} and {k.shape[2]}), a length that is "
                f"a multiple of 128 and of the block")
    if mask is not None:
        if window:
            raise NotImplementedError(
                "a masked flash call takes no window: say it in the mask")
        o, lse = flash_attention_forward(
            q, k, v, mask, causal=causal, sm_scale=sm_scale,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            block_q=block_q, block_k=block_k, interpret=interpret,
            mesh=mesh)
        return flash_attention_from_forward(
            q, k, v, mask, o, lse, causal=causal, sm_scale=sm_scale,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            block_q=block_q, block_k=block_k, bwd_block_q=bwd_block_q,
            bwd_block_k=bwd_block_k, interpret=interpret, mesh=mesh)
    if interpret is None:
        interpret = pallas_interpret()
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if mesh is not None and mesh.size > 1:
        return _flash_sharded(
            q, k, v, mesh, spec, causal=causal, sm_scale=sm_scale,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            block_q=block_q, block_k=block_k, bwd_block_q=bwd_block_q,
            bwd_block_k=bwd_block_k, interpret=interpret, window=window,
            block_diffusion=bd)
    (qp, kp, vp), seed, cut, plan = _prepare(
        q, k, v, False, causal, sm_scale, dropout_rate, dropout_seed,
        block_q, block_k, bwd_block_q, bwd_block_k,
        tiled=bd[0] if bd else None)
    if bd and any(bd[0] % n for blocks in plan[3:] for n in blocks):
        raise NotImplementedError(
            f"blocks {plan[3:]} do not tile the {bd[0]} positions of a "
            f"half of block_diffusion={bd}")
    o = _flash(qp, kp, vp, seed, plan.kv_len, plan.sm_scale, causal,
               *plan.fwd_blocks, plan.dq_blocks, plan.dkv_blocks,
               float(dropout_rate), interpret, window, bd)
    return cut(o)


class _Plan(NamedTuple):
    """What a call's shapes decide: the keys that are no padding, the
    score scale, the heads of a batch row and each kernel's blocks."""
    kv_len: int
    sm_scale: float
    heads: int
    fwd_blocks: tuple
    dq_blocks: tuple
    dkv_blocks: tuple


def _prepare(q, k, v, masked, causal, sm_scale, dropout_rate, dropout_seed,
             block_q, block_k, bwd_block_q, bwd_block_k, tiled=None):
    """``((qp, kp, vp), seed, cut, plan)``: the operands padded and flat,
    q (batch * heads, sq, d), k and v (batch * kv heads, sk, .) at their
    own head count (the calls take the group from the two); the dropout
    seed as the kernels read it; ``cut``, which gives a flat padded
    output its (b, h, sq, dv) form back; and the :class:`_Plan`, its
    blocks derived for a ``masked`` call or an unmasked one, as divisors
    of the padded lengths or, ``tiled`` given, of that many positions."""
    b, h, sq, d = q.shape
    kvh, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    if h % kvh or v.shape[1] != kvh:
        raise ValueError(f"{h} query heads do not read k's {kvh} and v's "
                         f"{v.shape[1]} heads in groups")
    if causal and sq != sk:
        raise NotImplementedError("causal flash requires sq == sk")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # hardware-aligned sequence sizes: keys in lanes of 128; q rows in
    # sublanes of 8 up to 512 of them (one tile, no multiple of 128
    # needed), in multiples of 128 above, to be tiled by a divisor. An
    # explicit block is clamped to them and pads to a multiple of itself
    sq_to = block_q and min(block_q, -(-sq // 8) * 8)
    sk_to = block_k and min(block_k, -(-sk // 128) * 128)
    # head_dim: pad only to a multiple of 64. d=64 (BERT/GPT-class) stays
    # unpadded — padding to the full 128 lane width doubled k/v HBM
    # traffic and the PV-matmul passes (measured: flash lost to XLA
    # attention below seq 1024 because of it). The MXU handles 64-lane
    # tiles natively.
    qp = _pad_to(_pad_to(q, sq_to or (8 if sq <= 512 else 128), 2), 64, 3)
    kp = _pad_to(_pad_to(k, sk_to or 128, 2), 64, 3)
    vp = _pad_to(_pad_to(v, sk_to or 128, 2), 64, 3)
    sq_p, d_p = qp.shape[2], qp.shape[3]
    sk_p, dv_p = kp.shape[2], vp.shape[3]
    # forward blocks from the shapes; an explicit one wins
    derived = fwd_tiles(tiled or sq_p, tiled or sk_p, d_p, q.dtype,
                        dropout_rate > 0.0, dv_p, masked)
    block_q, block_k = sq_to or derived[0], sk_to or derived[1]
    dq_blocks, dkv_blocks = bwd_tiles(tiled or sq_p, tiled or sk_p, d_p,
                                      q.dtype, dropout_rate > 0.0, dv_p,
                                      masked)
    # an explicit backward block wins, for both kernels
    if bwd_block_q is not None:
        bq = _explicit_block(bwd_block_q, block_q)
        dq_blocks, dkv_blocks = (bq, dq_blocks[1]), (bq, dkv_blocks[1])
    if bwd_block_k is not None:
        bk = _explicit_block(bwd_block_k, block_k)
        dq_blocks, dkv_blocks = (dq_blocks[0], bk), (dkv_blocks[0], bk)

    if dropout_seed is None:
        seed = jnp.zeros((1, 1), jnp.int32)
    else:
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1, 1)

    def cut(o):
        return o.reshape(b, h, sq_p, dv_p)[:, :, :sq, :dv]

    flat = (qp.reshape(b * h, sq_p, d_p), kp.reshape(b * kvh, sk_p, d_p),
            vp.reshape(b * kvh, sk_p, dv_p))
    return flat, seed, cut, _Plan(sk, sm_scale, h, (block_q, block_k),
                                  dq_blocks, dkv_blocks)


def _padded_mask(mask, sq_p, sk_p):
    """The (batch, sq, sk) mask as the kernels read it: int8, padded with
    0 (a padded pair is not attended) to the operands' padded lengths."""
    mask = mask.astype(jnp.int8)
    return jnp.pad(mask, ((0, 0), (0, sq_p - mask.shape[1]),
                          (0, sk_p - mask.shape[2])))


def _masked_call_checks(q, k, mask, mesh, interpret, dropout_rate,
                        dropout_seed):
    if mask.shape != (q.shape[0], q.shape[2], k.shape[2]):
        raise ValueError(
            f"mask {mask.shape} is not (batch, sq, sk) = "
            f"{(q.shape[0], q.shape[2], k.shape[2])}")
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "a masked flash call runs on one device (no shard_map wrap)")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    return pallas_interpret() if interpret is None else interpret


def flash_attention_forward(q, k, v, mask, *, causal: bool = False,
                            sm_scale: Optional[float] = None,
                            dropout_rate: float = 0.0, dropout_seed=None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None, mesh=None):
    """The forward kernel of a MASKED call alone: ``(o, lse)``, the
    output (b, h, sq, dv) and each row's log-sum-exp over its attended
    keys (b, h, sq) float32, with NO gradient (the operands are read
    detached). :func:`flash_attention_from_forward` makes ``o``
    differentiable; :func:`flash_attention_head_mean` reads ``lse``.
    ``mask`` and the other arguments as :func:`flash_attention`'s."""
    interpret = _masked_call_checks(q, k, mask, mesh, interpret,
                                    dropout_rate, dropout_seed)
    (qp, kp, vp), seed, cut, plan = _prepare(
        *map(jax.lax.stop_gradient, (q, k, v)), True, causal, sm_scale,
        dropout_rate, dropout_seed, block_q, block_k, None, None)
    o, lse = _noted_fwd_call(
        qp, kp, vp, seed, plan.kv_len, plan.sm_scale, causal,
        *plan.fwd_blocks, float(dropout_rate), interpret,
        mask=_padded_mask(mask, qp.shape[1], kp.shape[1]), heads=plan.heads)
    b, h, sq = q.shape[:3]
    return cut(o), lse.reshape(b, h, -1)[:, :, :sq]


def flash_attention_from_forward(q, k, v, mask, o, lse, *,
                                 causal: bool = False,
                                 sm_scale: Optional[float] = None,
                                 dropout_rate: float = 0.0,
                                 dropout_seed=None,
                                 block_q: Optional[int] = None,
                                 block_k: Optional[int] = None,
                                 bwd_block_q: Optional[int] = None,
                                 bwd_block_k: Optional[int] = None,
                                 interpret: Optional[bool] = None,
                                 mesh=None):
    """``o``, differentiable in q, k and v: the value is the ``o`` handed
    in, which :func:`flash_attention_forward` gave for the same
    operands, mask and options, and the backward runs the dq and dkv
    kernels from them and ``lse``. ``o`` and ``lse`` are arguments here,
    so a caller under ``jax.checkpoint`` may name them for its policy
    and keep them (see ``_from_forward``)."""
    interpret = _masked_call_checks(q, k, mask, mesh, interpret,
                                    dropout_rate, dropout_seed)
    (qp, kp, vp), seed, cut, plan = _prepare(
        q, k, v, True, causal, sm_scale, dropout_rate, dropout_seed,
        block_q, block_k, bwd_block_q, bwd_block_k)
    b, h, sq_p = q.shape[0], q.shape[1], qp.shape[1]
    op = _pad_to(_pad_to(o, sq_p, 2), 64, 3).reshape(b * h, sq_p, -1)
    lsep = _pad_to(lse, sq_p, 2).reshape(b * h, sq_p)
    out = _from_forward(
        qp, kp, vp, seed, _padded_mask(mask, sq_p, kp.shape[1]), op, lsep,
        plan.kv_len, plan.sm_scale, causal, plan.heads, plan.dq_blocks,
        plan.dkv_blocks, float(dropout_rate), interpret)
    return cut(out)


# ---------------------------------------------------------------------------
# the heads' mean probability of a masked call
# ---------------------------------------------------------------------------
def _head_mean_kernel(q_ref, k_ref, lse_ref, mask_ref, out_ref, acc_sc, *,
                      sm_scale, causal, kv_len, block_q, block_k):
    """One head's probabilities of one tile, added into the tile's
    float32 accumulator, which stays in scratch while the heads (the
    last grid axis) go by; the last head's step writes it out. The tile
    is held keys-major, (block_k, block_q), as ``bwd_dkv`` holds it: the
    head's log-sum-exp, a (1, block_q) row, then meets it as a sublane
    broadcast, the mask tile is the transposed mask's, and the
    accumulator is transposed ONCE a tile, into the (block_q, block_k)
    output block. ``lse`` comes with log(heads) added (the mean's
    division) and +inf-like on padded rows, whose probabilities are then
    0 like every pair the masks leave out: no second select. A tile
    above the causal diagonal computes nothing and is written as
    zeros."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    head = pl.program_id(3)
    heads = pl.num_programs(3)

    @pl.when(head == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    live = ((iq + 1) * block_q - 1 >= ik * block_k) if causal else True

    @pl.when(live)
    def _compute():
        valid = jnp.logical_and(
            _key_mask(iq, ik, block_q, block_k, kv_len, causal,
                      keys_major=True), _attended(mask_ref))
        acc_sc[:] += _bwd_p(q_ref[0], k_ref[0], lse_ref[0], valid, sm_scale,
                            keys_major=True)

    @pl.when(head == heads - 1)
    def _finish():
        out_ref[0] = acc_sc[:].T


def _head_mean_vmem_bytes(block_q, block_k, d, itemsize):
    """Working set of one grid step of the head-mean kernel: the
    double-buffered float32 output block, q and k blocks and log-sum-exp
    row, the mask tile (:func:`_mask_vmem_bytes`), the float32
    accumulator and about two float32 tiles of intermediates (the scores
    and probabilities on their way into it; its transpose on the way
    out)."""
    qk = -(-d // 128) * 128
    blocks = (block_q + block_k) * qk * itemsize + 8 * block_q * 4
    return (2 * (blocks + block_q * block_k * 4)
            + _mask_vmem_bytes(block_k, block_q)
            + 3 * block_q * block_k * 4)


def head_mean_tiles(sq, sk, d, dtype):
    """``(block_q, block_k)`` of the head-mean kernel for padded lengths:
    the tile of the most pairs that fits ``BWD_VMEM_BUDGET``, of two
    such the one with more queries (its rows are the lanes)."""
    itemsize = jnp.dtype(dtype).itemsize
    tiles = list(itertools.product(_tile_sizes(sq), _tile_sizes(sk)))
    fits = [t for t in tiles if _head_mean_vmem_bytes(*t, d, itemsize)
            <= BWD_VMEM_BUDGET] or tiles[:1]
    return max(fits, key=lambda t: (t[0] * t[1], t[0]))


@functools.partial(jax.jit, inline=True, static_argnames=tuple(
    n for n in _STATIC if n not in ("dropout_rate", "window", "bd")))
def _head_mean_call(q, k, lse, mask_t, kv_len, sm_scale, causal, block_q,
                    block_k, interpret, heads):
    """q (b * heads, s, d), k (b * kv heads, s, d); lse (b * heads, 1,
    sq); the transposed mask (b, sk, sq) int8 -> (b, sq, sk) float32.
    The heads are the innermost axis, so a group's heads name one k
    block one after another and it is copied once a group."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    kv_row = _kv_row(bh // k.shape[0])
    dead = (lambda i, j: j * block_k > (i + 1) * block_q - 1) if causal \
        else (lambda i, j: False)

    def row(b, i, j, n):
        # a dead tile's steps all name head 0's blocks: one copy, not
        # one a head
        return b * heads + jnp.where(dead(i, j), 0, n)

    return pl.pallas_call(
        functools.partial(_head_mean_kernel, sm_scale=sm_scale,
                          causal=causal, kv_len=kv_len, block_q=block_q,
                          block_k=block_k),
        grid=(bh // heads, sq // block_q, sk // block_k, heads),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, j, n: (row(b, i, j, n), i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, n: (kv_row(row(b, i, j, n)), j, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j, n: (row(b, i, j, n), 0, i)),
            pl.BlockSpec((1, block_k, block_q),
                         lambda b, i, j, n: (b, j, i))],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda b, i, j, n: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((bh // heads, sq, sk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_k, block_q), jnp.float32)],
        interpret=interpret,
        name="flash_attention_head_mean",
    )(q, k, lse, mask_t)


def flash_attention_head_mean(q, k, lse, mask, *, causal: bool = False,
                              sm_scale: Optional[float] = None,
                              interpret: Optional[bool] = None):
    """The heads' mean attention probability of a masked call,
    ``p[b, t, s] = (1 / h) sum_i exp(q_i[t] . k_i[s] * scale -
    lse_i[t])`` on the attended pairs and 0 elsewhere, (b, sq, sk)
    float32: q (b, h, s, d), k (b, kvh, s, d) with ``h % kvh == 0`` (head
    ``i`` reads k's head ``i // (h / kvh)``), ``lse`` (b, h, sq) the
    log-sum-exp
    :func:`flash_attention_forward` gave for them and ``mask``. The one
    array over (queries, keys) it writes has no head axis: the heads are
    the innermost, sequential grid axis and add into an accumulator that
    stays in VMEM (docs/kernels.md). No gradient: the operands are read
    detached."""
    if interpret is None:
        interpret = pallas_interpret()
    q, k, lse = map(jax.lax.stop_gradient, (q, k, lse))
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if h % kvh:
        raise ValueError(f"{h} query heads do not read {kvh} in groups")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qp = _pad_to(_pad_to(q, 8 if sq <= 512 else 128, 2), 64, 3)
    kp = _pad_to(_pad_to(k, 128, 2), 64, 3)
    sq_p, sk_p, d_p = qp.shape[2], kp.shape[2], qp.shape[3]
    block_q, block_k = head_mean_tiles(sq_p, sk_p, d_p, q.dtype)
    mask_t = jnp.swapaxes(_padded_mask(mask, sq_p, sk_p), 1, 2)
    # exp(s - (lse + log h)) is the head's share of the mean; a padded
    # row's statistic is so large that its every probability is 0
    lse = jnp.pad(lse.astype(jnp.float32) + math.log(h),
                  ((0, 0), (0, 0), (0, sq_p - sq)), constant_values=-NEG_INF)
    if events.enabled():
        events.instant("flash.grid", kernel="flash_attention_head_mean",
                       block_q=block_q, block_k=block_k, tile="keys_major",
                       steps=b * h * (sq_p // block_q) * (sk_p // block_k),
                       **({"kv_group": h // kvh} if kvh != h else {}))
    p = _head_mean_call(
        qp.reshape(b * h, sq_p, d_p), kp.reshape(b * kvh, sk_p, d_p),
        lse.reshape(b * h, 1, sq_p), mask_t, sk, sm_scale, causal, block_q,
        block_k, interpret, h)
    return p[:, :sq, :sk]


def _flash_sharded(q, k, v, mesh, spec, *, dropout_rate, dropout_seed,
                   **kw):
    """:func:`flash_attention` on each device's (batch, head) shard."""
    from jax.sharding import PartitionSpec as P
    spec = P(*(tuple(spec or ()) + (None, None))[:2], None, None)
    axes = tuple(a for e in spec if e is not None
                 for a in (e if isinstance(e, tuple) else (e,)))
    seed = jnp.zeros((), jnp.int32) if dropout_seed is None \
        else jnp.asarray(dropout_seed, jnp.int32).reshape(())

    def local(q_, k_, v_, seed_):
        if dropout_rate > 0.0 and axes:
            # the keep mask hashes the LOCAL batch-head index: give each
            # shard its own seed or every shard drops the same entries
            seed_ = seed_ + jax.lax.axis_index(axes) * jnp.int32(40503)
        return flash_attention(q_, k_, v_, dropout_rate=dropout_rate,
                               dropout_seed=seed_, **kw)

    # check_vma off: pallas_call outputs carry no varying-axes info
    return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3 + (P(),),
                         out_specs=spec, check_vma=False)(q, k, v, seed)


def block_diffusion_mask(length: int, block: int) -> np.ndarray:
    """The (2 L, 2 L) boolean table of the block-diffusion mask, queries
    by keys, written out from the halves and the blocks: what the kernels
    draw a tile at a time (:func:`flash_attention`'s
    ``block_diffusion``), for the paths that build a mask and for tests."""
    i = np.arange(2 * length)
    clean, blk = i >= length, (i % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return np.where(q_clean, k_clean & (kb <= qb),
                    np.where(k_clean, kb < qb, kb == qb))


def block_diffusion_visited(bh: int, length: int, block: int, d: int,
                            dtype, kv_group: int = 1) -> dict:
    """``{"fwd" | "bwd_dq" | "bwd_dkv": pairs}``: the pairs of the tiles
    (the forward's pieces; of a walked diagonal tile, :func:`_bd_sub`,
    its sub-blocks alone) each kernel's grid computes for a call of
    ``bh`` (batch x query head)s under ``block_diffusion=(length,
    block)`` at head size ``d``, at the tiles the call derives from its
    shapes; the mask attends ``bh (L L + L B)`` of them."""
    d = -(-d // 64) * 64
    tiles = dict(zip(("bwd_dq", "bwd_dkv"),
                     bwd_tiles(length, length, d, dtype, False)),
                 fwd=fwd_tiles(length, length, d, dtype, False))
    return {k: grid_steps(k, bh, 2 * length, 2 * length, *tiles[k], False,
                          0, kv_group, (length, block))["visited_pairs"]
            for k in ("fwd", "bwd_dq", "bwd_dkv")}


def mha_reference(q, k, v, *, causal: bool = False,
                  sm_scale: Optional[float] = None, precision=None):
    """Plain-XLA attention used as the numerics golden for the kernels.
    Same layout as :func:`flash_attention`. ``precision`` feeds the
    einsums (pass ``jax.lax.Precision.HIGHEST`` to force multi-pass fp32
    on the MXU, whose default is a single bf16 pass)."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = (jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=precision)
         .astype(jnp.float32) * sm_scale)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((sq, sk), dtype=bool), sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                      precision=precision).astype(q.dtype)


def dropout_keep_mask(b, h, sq, sk, rate, seed):
    """The kernel's counter-based keep mask, computed in plain XLA.

    Bit-identical to what :func:`_tile_keep_mask` generates inside the
    Pallas kernels under ANY block decomposition (same hash of the same
    absolute coordinates), so an explicit-mask golden —
    ``where(keep, softmax(s)/(1-rate), 0) @ v`` — reproduces the
    kernel's dropout semantics exactly. Used by the on-chip validator
    to check the compiled vjp without finite differences (MXU bf16
    rounding swamps an eps-sized central difference)."""
    bh = jnp.arange(b * h, dtype=jnp.int32)[:, None, None]
    qp = jnp.arange(sq, dtype=jnp.int32)[None, :, None]
    kp = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
    keep = _position_keep(jnp.int32(seed), bh, qp, kp, rate)
    return keep.reshape(b, h, sq, sk)
