"""The routed experts' way back to tokens as one Pallas TPU kernel.

``ops/moe_ops.py`` runs its grouped products over rows sorted by expert.
Back at the tokens, forward (``_combine``) and backward (``_rows_for``'s
transpose), each token wants

    out[t] = sum over j < top_k of  w[t, j] * src[at[t, j]]
             where 0 <= at[t, j] < len(src), else nothing

XLA's version (``moe_ops._of_each_choice``, the path of every other
shape and the tests' oracle) is a gather a choice that WRITES a whole
``(tokens, hidden)`` array, zeros wherever that choice's row is not
among ``src``'s (7 of 8 to 31 of 32 of them at the benchmark's shapes),
and a sum that reads all ``top_k`` back. Here the sum is walked from the
rows' side. ``order[r]`` names the assignment ``t * top_k + j`` that row
``r`` of ``src`` holds (the callers have it: it is the sort, and ``at``
is its inverse); the held experts' groups lead, ``inside[g]`` rows
each, and what follows them is nobody's.

The grid runs over tiles of ``T`` tokens; a tile's float32 sums are its
output block in VMEM. ``order`` is a STABLE sort by expert of
assignments that ascend by token, so inside each group the rows ascend
by token too, and a tile's rows are one contiguous run a group:
``starts[i, g]``, the first row of group ``g`` whose token is in tile
``i`` or later, is one compare-and-count over the rows in XLA, integers
only, and is scalar-prefetched with ``order`` and the gates. ``src``
stays in HBM. Mosaic copies nothing out of it that is not 8 rows long
(16 of bf16) and as aligned, so a grid step lists the aligned blocks its
runs touch (SMEM scratch), keeps ``SLOTS`` copies of them in flight, and
as each lands adds the run's rows in it, times their gates, onto their
tokens' rows: each live row is read once (and the few beside a run's
ends with it), each row of ``out`` written once, by Pallas' own
pipeline while the next tile is walked. A token's row is one sublane of
``hidden / 128`` registers, so a row costs about ``5 * hidden / 128``
vector slots and two scalar reads; nothing is proportional to the dead
rows.

Everything is float32 but ``src``, which may be bf16 (the backward's
cotangent rows): a block is widened once into a float32 scratch, the
products and the sum are float32, and the caller rounds the sum.
Neither function here is differentiated: both callers are
``custom_vjp``s already.
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
#: copies of row blocks a grid step keeps in flight
SLOTS = 8
#: what a grid step's blocks may count of Mosaic's default scoped VMEM
#: (16 MiB: the call asks for no more, so XLA keeps the rest of VMEM for
#: what it prefetches around the call) and the token tiles tried
VMEM_BUDGET = 12 * 1024 * 1024
TILES = (1024, 512, 256, 128, 64, 32, 16, 8)
#: ``order``, every assignment's gate and ``starts`` are scalar-
#: prefetched, 4 bytes each: what they may take of a v5e core's 1 MiB of
#: SMEM
SMEM_BUDGET = 512 * 1024


def row_unit(dtype) -> int:
    """Rows in the smallest block Mosaic copies out of HBM: a tile of
    sublanes, two rows a sublane where they are 16 bits."""
    return SUBLANES * 4 // jnp.dtype(dtype).itemsize


def vmem_bytes(tile: int, hidden: int, dtype) -> int:
    """Working set of a grid step: both buffers of the tile's sums, the
    row blocks in flight, and a block's float32 copy where the rows are
    narrower."""
    size, unit = jnp.dtype(dtype).itemsize, row_unit(dtype)
    return (2 * tile * hidden * 4 + SLOTS * unit * hidden * size
            + (unit * hidden * 4 if size < 4 else 0))


def tile_tokens(tokens: int, hidden: int, dtype) -> int:
    """Tokens a grid step sums: the largest of ``TILES`` whose working
    set is under ``VMEM_BUDGET``, and no more than covers the tokens
    there are. 0: not even eight fit."""
    fits = [t for t in TILES if vmem_bytes(t, hidden, dtype) <= VMEM_BUDGET]
    if not fits:
        return 0
    return min([fits[0]] + [t for t in TILES if t >= tokens])


def takes_kernel(tokens: int, hidden: int, top_k: int, rows: int, held: int,
                 dtype) -> bool:
    """Whether these shapes run the kernel: ``hidden`` in whole lanes,
    float32 or bf16 rows in whole blocks, the scalars inside SMEM's
    room, eight tokens' sums inside VMEM's, and more written by the
    plain path (``top_k`` arrays of ``tokens`` rows) than the kernel
    walks and writes (``rows + tokens``)."""
    dtype = jnp.dtype(dtype)
    if (hidden % LANES or tokens <= 0 or rows <= 0 or held <= 0
            or dtype not in (jnp.dtype(F32), jnp.dtype(jnp.bfloat16))
            or rows % row_unit(dtype)):
        return False
    tile = tile_tokens(tokens, hidden, dtype)
    return (tile > 0 and top_k * tokens >= rows + tokens
            and 4 * (rows + top_k * tokens
                     + (-(-tokens // tile) + 1) * held
                     + 3 * _jobs(rows, held, dtype)) <= SMEM_BUDGET)


def _jobs(rows: int, held: int, dtype) -> int:
    """The most blocks one tile's runs can touch: a run of ``n`` rows
    touches at most ``n / unit + 2``."""
    return rows // row_unit(dtype) + 2 * held


def _kernel(starts_ref, order_ref, *refs, tile, unit, held, k, weighted):
    """One tile of tokens. ``starts_ref``: (tiles + 1) x held run
    starts, flat; ``order_ref``: the assignment a row holds; then every
    assignment's gate if ``weighted``, ``src`` in HBM, the tile's sums,
    the slots and their semaphores, the tile's list of blocks (which,
    and the run's first and last row in it) and a block's float32 copy
    if it is narrower."""
    gate_ref, rest = (refs[0], refs[1:]) if weighted else (None, refs)
    src_ref, out_ref, slots, sems, block, first, last, *wide = rest
    i = pl.program_id(0)
    out_ref[...] = jnp.zeros(out_ref.shape, F32)

    def list_group(g, n):
        lo, hi = starts_ref[i * held + g], starts_ref[(i + 1) * held + g]
        b0 = jax.lax.div(lo, unit)
        b1 = jnp.where(hi > lo, jax.lax.div(hi + unit - 1, unit), b0)

        def list_block(b, n):
            block[n] = b
            first[n] = jnp.maximum(lo, b * unit) - b * unit
            last[n] = jnp.minimum(hi, (b + 1) * unit) - b * unit
            return n + 1
        return jax.lax.fori_loop(b0, b1, list_block, n)
    jobs = jax.lax.fori_loop(0, held, list_group, 0)

    def copy(j):
        slot = jax.lax.rem(j, SLOTS)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(pl.multiple_of(block[j] * unit, unit), unit), :],
            slots.at[slot], sems.at[slot])

    for j in range(SLOTS):
        @pl.when(j < jobs)
        def _():
            copy(j).start()

    def add_block(j, carry):
        copy(j).wait()
        slot = jax.lax.rem(j, SLOTS)
        rows = slots.at[slot]
        if wide:
            rows, = wide
            rows[...] = slots[slot].astype(F32)
        base = block[j] * unit

        def add(r, c):
            a = order_ref[base + r]
            # a >= 0: the truncating division is the floor, one scalar op
            t = jax.lax.div(a, jnp.int32(k)) - i * tile
            row = rows[pl.ds(r, 1), :]
            if weighted:
                row = row * gate_ref[a]
            out_ref[pl.ds(t, 1), :] += row
            return c
        jax.lax.fori_loop(first[j], last[j], add, 0)

        # the slot is read: the copy that takes it next may start
        @pl.when(j + SLOTS < jobs)
        def _():
            copy(j + SLOTS).start()
        return carry
    jax.lax.fori_loop(0, jobs, add_block, 0)


@functools.partial(jax.jit, static_argnames=("tokens", "k", "held", "tile",
                                             "interpret"), inline=True)
def _call(scalars, src, tokens, k, held, tile, interpret):
    """(tokens in whole sublanes, hidden) float32 sums; ``scalars``:
    the run starts ((tiles + 1) * held,) and the rows' assignments
    (rows,), int32, and, where they are weighted, every assignment's
    gate (tokens * k,) float32; ``src`` (rows, hidden)."""
    rows, hidden = src.shape
    unit, jobs = row_unit(src.dtype), _jobs(rows, held, src.dtype)
    wide = [] if src.dtype == F32 else [pltpu.VMEM((unit, hidden), F32)]
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, unit=unit, held=held, k=k,
                          weighted=len(scalars) == 3),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(-(-tokens // tile),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, hidden), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((SLOTS, unit, hidden), src.dtype),
                            pltpu.SemaphoreType.DMA((SLOTS,))]
            + [pltpu.SMEM((jobs,), jnp.int32)] * 3 + wide),
        # whole sublanes of tokens: a last tile that reaches past them
        # writes the rows there are
        out_shape=jax.ShapeDtypeStruct(
            (-(-tokens // SUBLANES) * SUBLANES, hidden), F32),
        # every copy a grid step starts it waits for: the tiles are
        # independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        # what XLA's scheduler may count on around the call (it takes a
        # call without one for no time at all, and overlaps nothing
        # with it): every row read and multiplied once, every sum
        # written once
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * hidden, transcendentals=0,
            bytes_accessed=(rows * hidden * src.dtype.itemsize
                            + tokens * hidden * 4
                            + sum(4 * x.size for x in scalars))),
        interpret=interpret, name="moe_token_sum")(*scalars, src)


def run_starts(order, inside, tokens: int, k: int, tile: int):
    """``starts[i, g]``: the first row of group ``g`` whose token is in
    tile ``i`` or a later one, ``(tiles + 1, held)``; the last line is
    the groups' ends. Rows ascend by group and, inside a group, by
    token, so ``group * (tokens + 1) + token`` ascends over the live
    rows and a start is the count of rows below a bound: two compares
    and counts over the rows, no gather and no sort."""
    rows, held = order.shape[0], inside.shape[0]
    ends = jnp.cumsum(inside.astype(jnp.int32))
    row = jnp.arange(rows, dtype=jnp.int32)
    group = jnp.sum(row[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    key = jnp.where(group < held, group * (tokens + 1) + order // k,
                    jnp.iinfo(jnp.int32).max)
    bound = (jnp.arange(held, dtype=jnp.int32)[None, :] * (tokens + 1)
             + jnp.minimum(jnp.arange(-(-tokens // tile) + 1,
                                      dtype=jnp.int32) * tile,
                           tokens)[:, None])
    return jnp.sum(key[None, None, :] < bound[:, :, None], axis=2,
                   dtype=jnp.int32)


def token_sum(src, order, inside, tokens: int, k: int, w=None, *, tile=None,
              interpret=None):
    """``out[t] = sum of w[t, j] * src[r]`` over the live rows ``r``
    that hold one of token ``t``'s assignments (``order[r] == t * k +
    j``): (tokens, hidden) float32.

    ``src``: (rows, hidden) float32 or bf16, ``rows`` in whole blocks
    of :func:`row_unit`. ``order``: (rows,) the assignment each row
    holds, a stable sort by group of assignments that ascend by token.
    ``inside``: (held,) the rows of each group, the groups leading in
    order; the rows after them are not read (the absent experts', which
    the TPU's grouped products leave unwritten, and the padding, which
    names assignment 0). ``w``: (tokens, k) float32, or None for ones.
    ``tile`` overrides the tokens a grid step sums (the tests').

    With ``at`` the inverse of ``order`` this is ``sum_j w[t, j] *
    src[at[t * k + j]]`` over the ``at`` among the live rows, the plain
    path's sum."""
    if interpret is None:
        interpret = pallas_interpret()
    if tile is None:
        tile = tile_tokens(tokens, src.shape[1], src.dtype)
    order = order.astype(jnp.int32)
    scalars = (run_starts(order, inside, tokens, k, tile).reshape(-1), order)
    if w is not None:
        scalars += (w.astype(F32).reshape(-1),)
    return _call(scalars, src, tokens, k, inside.shape[0], tile,
                 bool(interpret))[:tokens]
