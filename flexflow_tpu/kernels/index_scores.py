"""The sparse-attention indexer's scores and their pull-back with a
tile's heads kept on the chip, as two Pallas TPU kernels.

``ops/sparse_attention.py::index_scores`` is

  raw[j, t, s] = qi[t, j] . ki[s]              operands in ``mdt``, f32 sums
  I[t, s]      = scale * sum_j w[t, j] * relu(raw[j, t, s])

with ``j`` heads of ``c`` entries against ONE key head. XLA's version
(the path of every other shape and the tests' oracle) writes ``raw``,
``(j, rows, keys)`` float32, to HBM, and in the backward reads it for
``relu`` and its derivative, writes the cotangent of the same shape,
rounds it, turns it and reads it for two more products and a reduction:
268 MB a pass for 512 queries of 16 heads against 8,192 keys (PERF.md
section 6, PR 54). Here a grid step holds one (query tile x key tile) of
one head's ``raw`` at a time in VMEM and writes only arrays without a
head axis over (queries, keys).

``index_scores_fwd``: the tile is held KEYS-MAJOR, ``raw^T = ki qi_j^T``
(block_k, block_q), so that a head's weights, a (1, block_q) row, meet it
as a sublane broadcast (never a (rows, 1) column: ``flash_attention.py``);
the heads add into one float32 accumulator, turned once a tile into the
(block_q, block_k) output block.

``index_scores_bwd``: the tile is held queries-major. With
``m_j = [raw_j > 0] * d`` (``d`` the scores' cotangent, ``relu``'s
derivative 0 at 0 as ``jax.nn.relu``'s), rounded to ``mdt`` as the plain
path rounds what it multiplies,

  G_j[t]  = sum_s m_j[t, s] ki[s]              accumulated over key tiles
  dki[s]  = sum_j sum_t m_j[t, s] (w_j[t] qi_j[t])
  dqi_j   = scale * w_j * G_j
  dwi_j   = scale * sum_s d relu(raw_j) = scale * qi_j . G_j

so one kernel makes ``raw`` once and two more products of its size a
head, and the head weights never meet a tile: they scale ``G`` and the
queries outside the kernel, arrays of (rows, j * c). ``dki`` of a query
tile is written KEYS-ON-LANES, (c, block_k) = (w qi_j)^T m_j: the product
whose streamed operand is the head's ``c`` rows and whose held one is the
tile, so no tile is transposed; the query tiles' parts are summed outside
(float32).

Heads narrower than a vreg's 128 lanes (``c`` 64: two a vreg) are read
in GROUPS of whole lanes: the group's queries are one (block_q, 128)
operand and the key comes once a member, zero outside the member's lanes,
so ``raw`` of a member is the group's product with its padded key (the
same MXU passes as a 64-deep product: a pass is 128 deep either way) and
a group's ``G`` lands in its own lanes with no slice or shift.

``causal`` (rows and keys are positions of one sequence, as the loss's
backward calls both, once a layer): a tile wholly past the diagonal is
not visited. Its step names the diagonal's blocks, so nothing is
fetched; the forward writes nothing there, and the backward counts the
cotangent past the diagonal as 0 whatever the buffer holds, so the
loss's passes may write the cotangent over the scores in place.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import pallas_interpret
from .flash_attention import _pad_to

LANES = 128
F32 = jnp.float32
#: the tiles tried, largest first, and what a grid step's blocks and
#: values may count of VMEM (the calls ask for it: ``vmem_limit_bytes``)
BLOCKS = (512, 256, 128)
VMEM_BUDGET = 24 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))      # contract the last axis of both


def lane_group(c: int) -> int:
    """Heads of ``c`` entries that share a vreg's lanes."""
    return LANES // c if c < LANES else 1


def vmem_bytes(kernel: str, block_q: int, block_k: int, width: int, c: int,
               dtype) -> int:
    """Working set of a grid step of ``kernel`` ("fwd" / "bwd"): both
    buffers of its blocks and the float32 tiles it holds at once."""
    size, tile = jnp.dtype(dtype).itemsize, block_q * block_k * 4
    gw = lane_group(c) * c
    keys = lane_group(c) * block_k * gw * size
    if kernel == "fwd":
        return 2 * (block_q * width * size + keys + tile) + 3 * tile
    return (2 * (2 * block_q * width * size + keys + tile
                 + block_q * width * 4 + max(c, 8) * block_k * 4)
            + 2 * tile + block_q * block_k * size)


def tiles(kernel: str, q_rows: int, keys: int, j: int, c: int, dtype):
    """``(block_q, block_k)`` of ``kernel``: the largest of ``BLOCKS``
    that divide the rows and the keys and fit ``VMEM_BUDGET``, the key
    tile shrinking first. ``(0, 0)``: none does."""
    for bq in BLOCKS:
        for bk in BLOCKS:
            if bk <= bq and not (q_rows % bq or keys % bk) and vmem_bytes(
                    kernel, bq, bk, j * c, c, dtype) <= VMEM_BUDGET:
                return bq, bk
    return 0, 0


def takes_kernel(q_rows: int, keys: int, j: int, c: int, dtype) -> bool:
    """Whether these shapes run the kernels: heads in whole lanes (``c``
    a multiple of 128, or 32 or 64 with the heads in whole groups: a
    head's rows of the turned queries are then whole packed sublanes),
    float32 or bf16 operands, and tiles of whole lanes that divide the
    rows and the keys, forward and backward."""
    return (q_rows > 0 and keys > 0 and j > 0 and c % 32 == 0
            and (c % LANES == 0 or LANES % c == 0)
            and j % lane_group(c) == 0
            and jnp.dtype(dtype) in (jnp.dtype(F32), jnp.dtype(jnp.bfloat16))
            and all(tiles(k, q_rows, keys, j, c, dtype)[0]
                    for k in ("fwd", "bwd")))


def _live(iq, ik, block_q, block_k, causal):
    """Causal: whether any key of tile ``ik`` is at or before the last
    query of tile ``iq`` (rows and keys both count from 0)."""
    return (ik * block_k <= (iq + 1) * block_q - 1) if causal else True


def _fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, groups, r, gw, causal,
                block_q, block_k):
    """q (1, block_q, groups * gw); k (1, r, block_k, gw), member ``i``'s
    key in its own lanes; w (1, heads, block_q) with the scale in it;
    out (1, block_q, block_k). A tile past the diagonal computes
    nothing and writes nothing: its step names the diagonal's block,
    which stays as it was computed."""
    live = _live(pl.program_id(1), pl.program_id(2), block_q, block_k, causal)

    @pl.when(live)
    def _compute():
        acc = jnp.zeros((block_k, block_q), F32)
        for g in range(groups):
            q = q_ref[0, :, g * gw:(g + 1) * gw]
            for i in range(r):
                h = g * r + i
                raw_t = jax.lax.dot_general(k_ref[0, i], q, _NT,
                                            preferred_element_type=F32)
                acc = acc + w_ref[0, h:h + 1, :] * jnp.maximum(raw_t, 0.0)
        o_ref[0] = acc.T


def _bwd_kernel(q_ref, qw_ref, k_ref, d_ref, g_ref, dk_ref, *, groups, r, gw,
                c, causal, block_q, block_k):
    """q as the forward's; qw (1, heads * c, block_q), the weighted
    queries turned; k as the forward's; d (1, block_q, block_k) float32;
    ``G`` (1, block_q, groups * gw) float32, resident while the key tiles
    go by; this query tile's part of ``dki`` turned, (1, 1, c, block_k)."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    live = _live(iq, ik, block_q, block_k, causal)

    @pl.when(ik == 0)
    def _init():
        g_ref[...] = jnp.zeros(g_ref.shape, F32)

    @pl.when(live)
    def _compute():
        d = d_ref[0]
        if causal:      # what lies past the diagonal is nobody's
            at = [jax.lax.broadcasted_iota(jnp.int32, d.shape, a)
                  for a in (0, 1)]
            d = jnp.where(ik * block_k + at[1] <= iq * block_q + at[0], d,
                          0.0)
        dk = jnp.zeros((c, block_k), F32)
        for g in range(groups):
            q = q_ref[0, :, g * gw:(g + 1) * gw]
            part = jnp.zeros((block_q, gw), F32)
            for i in range(r):
                h = g * r + i
                k = k_ref[0, i]
                raw = jax.lax.dot_general(q, k, _NT,
                                          preferred_element_type=F32)
                m = jnp.where(raw > 0.0, d, 0.0).astype(k.dtype)
                part = part + jnp.dot(m, k, preferred_element_type=F32)
                dk = dk + jnp.dot(qw_ref[0, h * c:(h + 1) * c, :], m,
                                  preferred_element_type=F32)
            g_ref[0, :, g * gw:(g + 1) * gw] += part
        dk_ref[0, 0] = dk

    if causal:
        @pl.when(jnp.logical_not(live))
        def _dead():
            dk_ref[0, 0] = jnp.zeros((c, block_k), F32)


def _params(kernel, block_q, block_k, width, c, dtype):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(16 * 1024 * 1024, vmem_bytes(
            kernel, block_q, block_k, width, c, dtype) + 4 * 1024 * 1024))


def _products(b, q_rows, keys, width, causal):
    """Operations of one product over the pairs the grid visits."""
    return 2 * b * q_rows * keys * width // (2 if causal else 1)


def _key_map(causal, block_q, block_k):
    """A dead tile names the diagonal's key tile: nothing is fetched,
    and of the forward's output nothing is written."""
    if not causal:
        return lambda b, i, j: j
    return lambda b, i, j: jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)


# jitted with ``inline=True`` as the other kernels' calls are: a step's
# chunks of one shape trace each body once
@functools.partial(jax.jit, static_argnames=(
    "c", "causal", "block_q", "block_k", "interpret"), inline=True)
def _fwd_call(q, k, w, c, causal, block_q, block_k, interpret):
    b, rows, width = q.shape
    r, keys, gw = k.shape[1], k.shape[2], k.shape[3]
    at = _key_map(causal, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=width // gw, r=r, gw=gw,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(b, rows // block_q, keys // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, r, block_k, gw),
                         lambda b, i, j: (b, 0, at(b, i, j), 0)),
            pl.BlockSpec((1, w.shape[1], block_q), lambda b, i, j: (b, 0, i))],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda b, i, j: (b, i, at(b, i, j))),
        out_shape=jax.ShapeDtypeStruct((b, rows, keys), F32),
        compiler_params=_params("fwd", block_q, block_k, width, c, q.dtype),
        # what XLA's scheduler may count on around the call (ROADMAP S20)
        cost_estimate=pl.CostEstimate(
            flops=_products(b, rows, keys, width, causal), transcendentals=0,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + k.size * k.dtype.itemsize * (rows // block_q)
                            + 4 * (w.size + b * rows * keys))),
        interpret=interpret, name="index_scores_fwd",
    )(q, k, w)


@functools.partial(jax.jit, static_argnames=(
    "c", "causal", "block_q", "block_k", "interpret"), inline=True)
def _bwd_call(q, qw, k, d, c, causal, block_q, block_k, interpret):
    b, rows, width = q.shape
    r, keys, gw = k.shape[1], k.shape[2], k.shape[3]
    at = _key_map(causal, block_q, block_k)
    wide = pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, groups=width // gw, r=r, gw=gw, c=c,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(b, rows // block_q, keys // block_k),
        in_specs=[
            wide,
            pl.BlockSpec((1, qw.shape[1], block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, r, block_k, gw),
                         lambda b, i, j: (b, 0, at(b, i, j), 0)),
            pl.BlockSpec((1, block_q, block_k),
                         lambda b, i, j: (b, i, at(b, i, j)))],
        out_specs=[wide, pl.BlockSpec((1, 1, c, block_k),
                                      lambda b, i, j: (b, i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((b, rows, width), F32),
                   jax.ShapeDtypeStruct((b, rows // block_q, c, keys), F32)],
        compiler_params=_params("bwd", block_q, block_k, width, c, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=3 * _products(b, rows, keys, width, causal),
            transcendentals=0,
            bytes_accessed=(2 * q.size * q.dtype.itemsize
                            + k.size * k.dtype.itemsize * (rows // block_q)
                            + 4 * (d.size + q.size
                                   + b * (rows // block_q) * c * keys))),
        interpret=interpret, name="index_scores_bwd",
    )(q, qw, k, d)


def _operands(kernel, qi, ki, mdt, block_q, block_k):
    """``(q, k, block_q, block_k)``: the queries (b, rows, j * c) and the
    key once a lane-group member (b, r, keys, gw), in ``mdt``, rows and
    keys padded with zeros to whole tiles (a zero row or key scores 0,
    where ``relu`` and its derivative are 0)."""
    b, rows, j, c = qi.shape
    q = _pad_to(qi.astype(mdt).reshape(b, rows, j * c), block_q or LANES, 1)
    k = _pad_to(ki.astype(mdt), block_k or LANES, 1)
    if not takes_kernel(q.shape[1], k.shape[1], j, c, mdt):
        raise ValueError(f"the index-score kernels take no {j} heads of {c} "
                         f"in {jnp.dtype(mdt).name}")
    derived = tiles(kernel, q.shape[1], k.shape[1], j, c, mdt)
    block_q, block_k = block_q or derived[0], block_k or derived[1]
    r = lane_group(c)
    k = jnp.stack([jnp.pad(k, ((0, 0), (0, 0), (i * c, (r - 1 - i) * c)))
                   for i in range(r)], 1)
    return q, k, block_q, block_k


def index_scores_fwd(qi, ki, wi, mdt, *, causal: bool = False,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """``ops/sparse_attention.py::index_scores`` through the kernel:
    ``qi`` (b, rows, j, c), ``ki`` (b, keys, c), ``wi`` (b, rows, j) ->
    (b, rows, keys) float32, the products' operands in ``mdt``.
    ``causal``: row ``t`` and key ``s`` are positions of one sequence;
    the tiles wholly past the diagonal are not visited and NOT WRITTEN
    (whatever the buffer held; the others are whole: the diagonal's
    upper half is computed). No gradient: :func:`index_scores_bwd` is
    the pull-back."""
    if interpret is None:
        interpret = pallas_interpret()
    qi, ki, wi = map(jax.lax.stop_gradient, (qi, ki, wi))
    _, rows, j, c = qi.shape
    q, k, block_q, block_k = _operands("fwd", qi, ki, mdt, block_q, block_k)
    w = _pad_to(jnp.swapaxes(wi.astype(F32) * (j * c) ** -0.5, 1, 2),
                q.shape[1], 2)
    out = _fwd_call(q, k, w, c, causal, block_q, block_k, bool(interpret))
    return out[:, :rows, :ki.shape[1]]


def index_scores_bwd(qi, ki, wi, d_scores, mdt, *, causal: bool = False,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """``(dqi, dki, dwi)`` for the cotangent ``d_scores`` (b, rows, keys)
    of :func:`index_scores_fwd`'s output, each in its operand's shape
    and type: what ``jax.vjp`` of ``index_scores`` hands back, with the
    same roundings (what a product multiplies is in ``mdt``, every sum
    float32). ``causal``: ``d_scores`` is not read past the diagonal
    (it counts as 0 there, whatever it holds), and the tiles wholly
    past it are not visited."""
    if interpret is None:
        interpret = pallas_interpret()
    b, rows, j, c = qi.shape
    keys = ki.shape[1]
    q, k, block_q, block_k = _operands("bwd", qi, ki, mdt, block_q, block_k)
    scale = (j * c) ** -0.5
    w = wi.astype(F32) * scale
    rounded = qi.astype(mdt).astype(F32)
    qw = (w[..., None] * rounded).astype(mdt).reshape(b, rows, j * c)
    qw = _pad_to(jnp.swapaxes(qw, 1, 2), q.shape[1], 2)
    d = _pad_to(_pad_to(d_scores.astype(F32), q.shape[1], 1), k.shape[2], 2)
    g, dk = _bwd_call(q, qw, k, d, c, causal, block_q, block_k,
                      bool(interpret))
    g = g[:, :rows].reshape(b, rows, j, c)
    dk = jnp.swapaxes(jnp.sum(dk, 1), 1, 2)[:, :keys]
    return ((w[..., None] * g).astype(qi.dtype), dk.astype(ki.dtype),
            (scale * jnp.sum(rounded * g, -1)).astype(wi.dtype))
