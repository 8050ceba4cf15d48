"""The gated delta rule as Pallas TPU kernels (forward + backward): the
chunks' terms, for a decay a channel and a decay a head, and the scan
that carries the state from chunk to chunk.

``ops/recurrent_ops.py`` runs the recurrence in chunks of ``C`` tokens:
everything of a chunk that does not depend on the state it starts from
(``A``, ``B``, ``(I + Diag(beta) A)^-1``, ``W``, ``U0``, the decayed
copies of q and k) is computed for all chunks at once, then the state is
carried over the chunks in order. The first part of this module is that
first half. A grid
step takes a few chunks of one (batch, head): it reads q, k, v, g, beta of
those tokens into VMEM once, and what XLA's version of the same algebra
(:func:`flexflow_tpu.ops.recurrent_ops._chunk_terms`, the fallback and
the tests' oracle) writes to HBM between its fusions stays there: the
running log-decay ``G``, the ``(SUB, SUB, d)`` differences of a
sub-block against itself, ``A``, the inverse. There is no triangular
solve: the ``SUB x SUB`` diagonal blocks of ``I + N`` (``N = Diag(beta)
A``, strictly lower) are inverted by the nilpotent doubling ``(I - N)(I +
N^2)(I + N^4)(I + N^8)``, exact because ``N^SUB = 0``, and merged a level
of halves at a time, ``T <- T - T N_level T``; all of it float32 products
(``Precision.HIGHEST``).

Every exponent taken is a difference of running log-decays that is <= 0,
as in the plain code and for its reason (a chunk's decays sum past
float32's -88.7): a sub-block against itself through ``G_i - G_j`` for
``j <= i``; the later half of a span against its earlier half through the
later half's first row ``n``, ``exp(G_i - G_n)`` on the rows' side and
``exp(G_n - G_j)`` on the columns' (one exponential an element a level:
a row is on one side or the other).

The outputs leave in the layout the scan reads, chunk leading. The
backward kernel takes the cotangents of the six terms and returns ``dq,
dk, dv, dg, dbeta`` in float32; the residuals of the ``custom_vjp`` are
the five inputs and nothing else: ``A`` and the inverse are formed again
in VMEM (``d(M^-1) = -M^-T dT M^-T``).

Products round their operands to ``mdt`` where the plain code does (the
spans' products, ``W``, ``U0`` and their transposes in the backward);
running sums, exponentials, the sub-blocks' sums, the inverse and the
gradients are float32.

The second part of the module is the HEAD form (a decay a head, Gated
DeltaNet's: ``g`` one scalar a head-token), the same algorithm whose
``A`` and ``B`` are formed differently: ``A = (K K^T) * L``, ``B = (Q
K^T) * L`` with ``L_ij = exp(G_i - G_j)``, one product each and one ``(C,
C)`` matrix of differences, so no sub-block loop and no span levels
(:func:`head_chunk_terms`, against
:func:`flexflow_tpu.ops.recurrent_ops._chunk_terms_head`). A grid step
takes a few chunks of one q/k head and of the ``group`` value heads it
serves: q and k are read at their own head through the block index,
never repeated, and their raw products are made once for the group. It
shares ``_mm``, ``_column``, ``_span_mask``, ``_inverse`` (the same
blocked inverse, ``N`` split by masks alone), the block specs and
``VMEM_LIMIT`` with the channel form and changes no line of it.

The third part is the SCAN, one kernel pair for both forms
(:func:`scan_chunks`, against ``jax.lax.scan`` over
:func:`flexflow_tpu.ops.recurrent_ops._chunk_step`): the grid's last
axis is the groups of chunks in order, a block of heads' state rides in
VMEM scratch from a chunk to the next, the backward walks the chunks
last to first carrying the state's cotangent. It reads the six terms
where the terms kernels left them and changes no line of theirs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import events
from ._interpret import pallas_interpret

SUB = 16                # rows of a sub-block of a chunk
LANES = 128
#: chunks of one (batch, head) a grid step takes: the blocks of the small
#: operands (beta, the last row's decay) want 8 rows or all of them, and
#: eight chunks' independent chains of float32 products fill the matrix
#: unit's pipeline where one chunk's ten dependent ones wait on it
CHUNKS_PER_STEP = 8
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def takes_kernel(chunk: int, dk: int, dv: int) -> bool:
    """Whether these shapes run the kernels: a chunk that is a
    power-of-two number of sub-blocks, head sizes in whole lanes."""
    spans = chunk // SUB
    return (chunk % SUB == 0 and spans > 0 and spans & (spans - 1) == 0
            and dk % LANES == 0 and dv % LANES == 0)


# ---------------------------------------------------------------------------
# pieces of a grid step: values (n, c, .) of n chunks, float32
# ---------------------------------------------------------------------------
def _mm(a, b, ta=False, tb=False, mdt=None):
    """Batched over the leading axis: ``a @ b`` with ``a`` / ``b``
    transposed on request. ``mdt`` None: an exact float32 product;
    otherwise the operands are rounded to ``mdt`` and summed in
    float32."""
    if mdt is not None:
        a, b = a.astype(mdt), b.astype(mdt)
    return jax.lax.dot_general(
        a, b, (((1 if ta else 2,), (2 if tb else 1,)), ((0,), (0,))),
        precision=_HIGHEST if mdt is None else None,
        preferred_element_type=F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _halves(c):
    """The halves a chunk's spans are split at: SUB, 2 SUB, .. c / 2."""
    out, half = [], SUB
    while half < c:
        out.append(half)
        half *= 2
    return out


def _running(g):
    """The running sum of ``g`` inside each chunk, as a product with the
    lower triangle of ones."""
    n, c, _ = g.shape
    low = (_iota((n, c, c), 2) <= _iota((n, c, c), 1)).astype(F32)
    return _mm(low, g)


def _column(row):
    """(n, 1, c) values along the lanes -> (n, c, 1) down the rows."""
    n, _, c = row.shape
    eye = _iota((n, c, c), 1) == _iota((n, c, c), 2)
    return jnp.sum(jnp.where(eye, row, 0.0), -1, keepdims=True)


def _sub_rows(x):
    """(n, c, d) -> (n c / SUB, SUB, d): a sub-block a leading index."""
    return x.reshape(-1, SUB, x.shape[-1])


def _own_column(n, c):
    """(n c, c) int32: a column's offset from the first column of its
    row's own sub-block (0 .. SUB - 1 inside it), and the row's offset
    inside its sub-block."""
    row, col = _iota((n * c, c), 0), _iota((n * c, c), 1)
    i_loc = row & (SUB - 1)
    return col - ((row & (c - 1)) - i_loc), i_loc


def _sub_block_terms(big_g, k, q):
    """A sub-block against itself: ``A`` (strictly lower) and, with
    ``q``, ``B`` (lower) on the diagonal ``SUB x SUB`` blocks of (n, c,
    c), zero elsewhere. A column ``j`` of every sub-block a step: the
    differences ``G_i - G_j`` of its rows, their exponentials and the two
    sums over the channels are made and reduced where they are."""
    n, c, _ = k.shape
    gs, ks = _sub_rows(big_g), _sub_rows(k)
    qs = None if q is None else _sub_rows(q)
    sub_row = _iota(gs.shape, 1)
    rel, i_loc = _own_column(n, c)
    acc_a = jnp.zeros((n * c, c), F32)
    acc_b = None if q is None else jnp.zeros((n * c, c), F32)
    for j in range(SUB):
        e = jnp.exp(jnp.where(sub_row >= j, gs - gs[:, j:j + 1], 0.0))
        t = e * ks[:, j:j + 1]
        hit = rel == j
        acc_a = jnp.where(hit, jnp.sum(ks * t, -1, keepdims=True
                                       ).reshape(n * c, 1), acc_a)
        if q is not None:
            acc_b = jnp.where(hit, jnp.sum(qs * t, -1, keepdims=True
                                           ).reshape(n * c, 1), acc_b)
    a = jnp.where(rel < i_loc, acc_a, 0.0).reshape(n, c, c)
    if q is None:
        return a, None
    return a, jnp.where(rel <= i_loc, acc_b, 0.0).reshape(n, c, c)


def _span_decay(big_g, half):
    """A level of spans of ``2 half`` rows, each through its later half's
    first row ``n``: ``exp(G_i - G_n)`` on the later half's rows,
    ``exp(G_n - G_j)`` on the earlier half's, both <= 0 because ``G``
    falls along a chunk. Returns it (n, c, d) and which rows are
    later-half ones."""
    shape = big_g.shape
    g3 = big_g.reshape(-1, 2 * half, shape[-1])
    diff = g3 - g3[:, half:half + 1]
    e = jnp.exp(jnp.where(_iota(g3.shape, 1) >= half, diff, -diff))
    return e.reshape(shape), (_iota(shape, 1) & half) != 0


def _span_mask(n, c, half):
    """(n, c, c): row in the later half and column in the earlier half
    of one span of ``2 half`` rows."""
    row, col = _iota((n, c, c), 1), _iota((n, c, c), 2)
    span = 2 * half
    same = (row - (row & (span - 1))) == (col - (col & (span - 1)))
    return same & ((row & half) != 0) & ((col & half) == 0)


def _inverse(n_sub, n_spans):
    """``(I + N)^-1`` for ``N = n_sub + sum(n_spans)`` strictly lower:
    ``n_sub`` on the diagonal sub-blocks, ``n_spans[l]`` on level l's
    off-diagonal halves. Float32 throughout."""
    n, c, _ = n_sub.shape
    eye = (_iota((n, c, c), 1) == _iota((n, c, c), 2)).astype(F32)
    t, x, p = eye - n_sub, n_sub, 2
    while p < SUB:                      # (I - N)(I + N^2)(I + N^4)..
        x = _mm(x, x)
        t = t + _mm(t, x)
        p *= 2
    for level in n_spans:               # [[P, 0], [L, Q]]^-1
        t = t - _mm(_mm(t, level), t)
    return t


def _in_chunk(q, k, g, beta_row, mdt, with_b):
    """What forward and backward both form: the running log-decay, beta
    down the rows, ``A``, ``B`` (forward only), each level's decayed
    copies and mask, and ``(I + Diag(beta) A)^-1``."""
    n, c, _ = k.shape
    big_g = _running(g)
    beta = _column(beta_row)
    a, b = _sub_block_terms(big_g, k, q if with_b else None)
    n_sub, n_spans, levels = beta * a, [], []
    for half in _halves(c):
        e, later = _span_decay(big_g, half)
        ke, qe = k * e, q * e
        mask = _span_mask(n, c, half)
        a_l = jnp.where(mask, _mm(ke, ke, tb=True, mdt=mdt), 0.0)
        a = a + a_l
        n_spans.append(beta * a_l)
        if with_b:
            b = b + jnp.where(mask, _mm(qe, ke, tb=True, mdt=mdt), 0.0)
        levels.append((half, e, later, ke, qe, mask))
    return big_g, beta, a, b, levels, _inverse(n_sub, n_spans)


def _last_row(x):
    """(n, c, d) -> (n, 1, d), the chunk's last row, as a sum over the
    rows with the others masked (Mosaic takes a row out of the middle of
    a tile as a broadcast's operand, not as a value to store)."""
    last = _iota(x.shape, 1) == x.shape[1] - 1
    return jnp.sum(jnp.where(last, x, 0.0), 1, keepdims=True)


def _load(ref, n):
    x = ref[...].astype(F32)
    return x.reshape(n, -1, x.shape[-1])


# ---------------------------------------------------------------------------
# forward kernel: grid (batch x head, groups of chunks)
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref, b_ref,
                qd_ref, kd_ref, dec_ref, least_ref, *, mdt):
    n = beta_ref.shape[0]
    q, k, v, g = (_load(r, n) for r in (q_ref, k_ref, v_ref, g_ref))
    big_g, beta, _, b, _, t = _in_chunk(q, k, g, beta_ref[...], mdt, True)
    decay = jnp.exp(big_g)
    g_last = _last_row(big_g)
    w_ref[...] = _mm(t, beta * k * decay, mdt=mdt).astype(w_ref.dtype)
    u_ref[...] = _mm(t, beta * v, mdt=mdt)
    b_ref[...] = b.astype(b_ref.dtype)
    qd_ref[...] = (q * decay).astype(qd_ref.dtype)
    kd_ref[...] = (k * jnp.exp(g_last - big_g)).astype(kd_ref.dtype)
    dec_ref[...] = jnp.exp(g_last)
    least_ref[...] = jnp.min(big_g, axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# backward kernel: same grid
# ---------------------------------------------------------------------------
def _sub_block_grads(big_g, k, q, da, db):
    """The cotangents ``da`` (strictly lower) and ``db`` (lower) of the
    diagonal sub-blocks of ``A`` and ``B``, (n, c, c) and zero off
    them, back to k, q and ``G``: (n, c, d) each."""
    n, c, d = k.shape
    gs, ks, qs = _sub_rows(big_g), _sub_rows(k), _sub_rows(q)
    sub_row = _iota(gs.shape, 1)
    # a sub-block's SUB columns of da, db, gathered beside its rows by an
    # exact product with a 0 / 1 matrix: (n c / SUB, SUB, SUB)
    pick = ((_iota((n, c, SUB), 1) & (SUB - 1)) == _iota((n, c, SUB), 2)
            ).astype(F32)
    das = _mm(da, pick).reshape(-1, SUB, SUB)
    dbs = _mm(db, pick).reshape(-1, SUB, SUB)
    dks, dqs, dgs = (jnp.zeros(gs.shape, F32) for _ in range(3))
    col_k = jnp.zeros(gs.shape, F32)    # row j: what column j sent to k_j
    for j in range(SUB):
        e = jnp.exp(jnp.where(sub_row >= j, gs - gs[:, j:j + 1], 0.0))
        t = e * ks[:, j:j + 1]
        ca, cb = das[:, :, j:j + 1], dbs[:, :, j:j + 1]
        u = ca * ks + cb * qs
        dks = dks + ca * t
        dqs = dqs + cb * t
        dgs = dgs + u * t
        col_k = jnp.where(sub_row == j,
                          jnp.sum(u * e, 1, keepdims=True), col_k)
    # G_j takes minus what its rows took: k_j times what k_j took
    return ((dks + col_k).reshape(n, c, d), dqs.reshape(n, c, d),
            (dgs - ks * col_k).reshape(n, c, d))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, dw_ref, du_ref,
                db_ref, dqd_ref, dkd_ref, ddec_ref, dq_ref, dk_ref, dv_ref,
                dg_ref, dbeta_ref, *, mdt):
    n = beta_ref.shape[0]
    q, k, v, g = (_load(r, n) for r in (q_ref, k_ref, v_ref, g_ref))
    c = k.shape[1]
    big_g, beta, a, _, levels, t = _in_chunk(q, k, g, beta_ref[...], mdt,
                                             False)
    dw, du, db, dqd, dkd = (r[...].astype(F32) for r in (
        dw_ref, du_ref, db_ref, dqd_ref, dkd_ref))
    decay = jnp.exp(big_g)
    k_dec = k * decay
    # W = T (beta k exp G), U0 = T (beta v)
    xw, xu = beta * k_dec, beta * v
    dt = _mm(dw, xw, tb=True, mdt=mdt) + _mm(du, xu, tb=True, mdt=mdt)
    dxw, dxu = _mm(t, dw, ta=True, mdt=mdt), _mm(t, du, ta=True, mdt=mdt)
    # T = (I + Diag(beta) A)^-1
    dm = -_mm(_mm(t, dt, ta=True), t, tb=True)
    da = beta * dm
    dbeta = (jnp.sum(dm * a, -1, keepdims=True)
             + jnp.sum(dxw * k_dec, -1, keepdims=True)
             + jnp.sum(dxu * v, -1, keepdims=True))
    dv = beta * dxu
    dk = dxw * beta * decay
    d_g = dxw * xw
    # q exp(G), k exp(G_C - G), exp(G_C)
    dq = dqd * decay
    d_g = d_g + dqd * q * decay
    g_last = _last_row(big_g)
    fall = jnp.exp(g_last - big_g)
    dk = dk + dkd * fall
    through = dkd * k * fall
    d_g = d_g - through
    row = _iota(d_g.shape, 1)
    d_g = d_g + jnp.where(
        row == c - 1, jnp.sum(through, 1, keepdims=True)
        + ddec_ref[...] * jnp.exp(g_last), 0.0)
    # the spans, a level at a time
    for half, e, later, ke, qe, mask in levels:
        da_l, db_l = jnp.where(mask, da, 0.0), jnp.where(mask, db, 0.0)
        dke = (_mm(da_l, ke, mdt=mdt) + _mm(da_l, ke, ta=True, mdt=mdt)
               + _mm(db_l, qe, ta=True, mdt=mdt))
        dqe = _mm(db_l, ke, mdt=mdt)
        dk = dk + dke * e
        dq = dq + dqe * e
        z = dke * ke + dqe * qe
        z = jnp.where(later, z, -z)     # the exponent is +-(G - G_n)
        z3 = z.reshape(-1, 2 * half, z.shape[-1])
        z3 = z3 - jnp.where(_iota(z3.shape, 1) == half,
                            jnp.sum(z3, 1, keepdims=True), 0.0)
        d_g = d_g + z3.reshape(z.shape)
    # the sub-blocks against themselves
    rel, i_loc = _own_column(n, c)
    on_a = (rel >= 0) & (rel < i_loc)
    on_b = (rel >= 0) & (rel <= i_loc)
    dks, dqs, dgs = _sub_block_grads(
        big_g, k, q, jnp.where(on_a.reshape(da.shape), da, 0.0),
        jnp.where(on_b.reshape(db.shape), db, 0.0))
    d_g = d_g + dgs
    # g's cotangent: the running sum's transpose
    up = (_iota((n, c, c), 2) >= _iota((n, c, c), 1)).astype(F32)
    dq_ref[...] = (dq + dqs).reshape(dq_ref.shape)
    dk_ref[...] = (dk + dks).reshape(dk_ref.shape)
    dv_ref[...] = dv.reshape(dv_ref.shape)
    dg_ref[...] = _mm(up, d_g).reshape(dg_ref.shape)
    eye = _iota((n, c, c), 1) == _iota((n, c, c), 2)
    dbeta_ref[...] = jnp.sum(jnp.where(eye, dbeta, 0.0), 1, keepdims=True)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------
#: Mosaic's scoped-VMEM limit for these kernels: a v5e core has 128 MiB,
#: and the default limit of 16 MiB is under a backward step's working set
#: at eight chunks (:func:`vmem_bytes`).
VMEM_LIMIT = 64 * 1024 * 1024


def vmem_bytes(kernel, chunk, dk, dv, itemsize, per_step=CHUNKS_PER_STEP):
    """Working set of one grid step: the double-buffered operand and
    output blocks and the float32 (rows, d) and (rows, chunk) values the
    step holds at once (a (rows, chunk) value fills whole lanes in VMEM).
    The counts of values are held to Mosaic for a described v5e at cell
    5's shape: the forward compiles under a limit of 12 MiB and not of
    10, the backward under 18 and not 16 (PERF.md section 6, PR 36)."""
    rows = per_step * chunk
    wide = rows * max(dk, dv) * 4
    square = rows * max(chunk, LANES) * 4
    small = per_step * 8 * LANES * 4
    inputs = rows * (3 * dk + dv) * 4 + small
    terms = (rows * (3 * dk * itemsize + dv * 4)
             + rows * max(chunk, LANES) * itemsize + 2 * small)
    if kernel == "fwd":
        return 2 * (inputs + terms) + 18 * wide + 12 * square
    return 2 * (2 * inputs + terms) + 32 * wide + 16 * square


def _specs(bh_first, rows, *last):
    """A block of ``rows`` along the axis after (before) the batch x
    head axis, whole in the axes after it."""
    zeros = (0,) * len(last)
    if bh_first:
        return pl.BlockSpec((None, rows) + last,
                            lambda b, i: (b, i) + zeros)
    return pl.BlockSpec((rows, None) + last, lambda b, i: (i, b) + zeros)


def _layout(q, v, chunk, per_step):
    bh, t, dk = q.shape
    dv, n = v.shape[2], t // chunk
    rows = per_step * chunk
    inputs = [_specs(True, rows, dk)] * 2 + [
        _specs(True, rows, dv), _specs(True, rows, dk),
        _specs(True, per_step, 1, chunk)]
    terms = [_specs(False, per_step, chunk, dk),        # W
             _specs(False, per_step, chunk, dv),        # U0
             _specs(False, per_step, chunk, chunk),     # B
             _specs(False, per_step, chunk, dk),        # q exp(G)
             _specs(False, per_step, chunk, dk),        # k exp(G_C - G)
             _specs(False, per_step, 1, dk)]            # exp(G_C)
    return (bh, n // per_step), n, inputs, terms


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=VMEM_LIMIT)
_STATIC = ("chunk", "per_step", "mdt", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _fwd_call(q, k, v, g, beta, chunk, per_step, mdt, interpret):
    grid, n, inputs, terms = _layout(q, v, chunk, per_step)
    bh, _, dk = q.shape
    dv = v.shape[2]

    def out(dt, *last):
        return jax.ShapeDtypeStruct((n, bh) + last, dt)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mdt=mdt),
        grid=grid, in_specs=inputs,
        out_specs=terms + [_specs(False, per_step, 1, dk)],
        out_shape=[out(mdt, chunk, dk), out(F32, chunk, dv),
                   out(mdt, chunk, chunk), out(mdt, chunk, dk),
                   out(mdt, chunk, dk), out(F32, 1, dk), out(F32, 1, dk)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gated_delta_rule_fwd",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_call(q, k, v, g, beta, cts, chunk, per_step, mdt, interpret):
    grid, _, inputs, terms = _layout(q, v, chunk, per_step)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, mdt=mdt),
        grid=grid, in_specs=inputs + terms, out_specs=inputs,
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32)
                   for x in (q, k, v, g, beta)],
        compiler_params=_PARAMS, interpret=interpret,
        name="gated_delta_rule_bwd",
    )(q, k, v, g, beta, *cts)


def _note(kernel, layer, q, v, chunk, per_step, mdt):
    """One ``kda.kernel`` instant per emitted call, at trace time."""
    if events.enabled():
        bh, t, dk = q.shape
        n = t // chunk
        events.instant(
            "kda.kernel", kernel=kernel, layer=layer, chunk=chunk, sub=SUB,
            chunks=bh * n, grid_steps=bh * n // per_step,
            chunks_per_step=per_step,
            vmem_bytes=vmem_bytes(kernel, chunk, dk, v.shape[2],
                                  jnp.dtype(mdt).itemsize, per_step))


def _noted_fwd_call(q, k, v, g, beta, chunk, per_step, mdt, layer,
                    interpret):
    _note("fwd", layer, q, v, chunk, per_step, mdt)
    return tuple(_fwd_call(q, k, v, g, beta, chunk, per_step, mdt,
                           interpret))


_terms = jax.custom_vjp(_noted_fwd_call, nondiff_argnums=(5, 6, 7, 8, 9))


def _terms_fwd(q, k, v, g, beta, *static):
    return _noted_fwd_call(q, k, v, g, beta, *static), (q, k, v, g, beta)


def _terms_bwd(chunk, per_step, mdt, layer, interpret, res, cts):
    _note("bwd", layer, res[0], res[2], chunk, per_step, mdt)
    # (the least running log-decay is a reading, not a term)
    return tuple(_bwd_call(*res, list(cts[:6]), chunk, per_step, mdt,
                           interpret))


_terms.defvjp(_terms_fwd, _terms_bwd)


def chunk_terms(q, k, v, g, beta, chunk, mdt, *, layer=None,
                interpret=None, mesh=None, spec=None):
    """The state-independent terms of every chunk, by the kernels:
    ``q``, ``k``, ``g``: (B, H, T, dk), ``v``: (B, H, T, dv), ``beta``:
    (B, H, T), float32. Returns what ``lax.scan`` over the chunks reads,
    chunk leading: ``W`` (N, B, H, C, dk), ``U0`` (.., C, dv) float32,
    ``B`` (.., C, C), ``q exp(G)``, ``k exp(G_C - G)`` in ``mdt``,
    ``exp(G_C)`` (N, B, H, dk) float32; and the least running log-decay
    a chunk and channel, (N, B, H, dk). ``T`` is padded to whole grid
    steps (``N`` counts the padded chunks): a padded token writes
    nothing (beta 0) and decays nothing (g 0).

    ``mesh`` / ``spec`` as :func:`flash_attention` takes them: under a
    mesh of more than one device the call runs under ``shard_map`` over
    the batch and head entries of ``spec``; every (batch, head) is a
    grid row of its own."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bh = (tuple(spec or ()) + (None, None))[:2]
        local = functools.partial(chunk_terms, chunk=chunk, mdt=mdt,
                                  layer=layer, interpret=interpret)
        # check_vma off: pallas_call outputs carry no varying-axes info
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*bh, None, None),) * 4 + (P(*bh, None),),
            out_specs=(P(None, *bh, None, None),) * 5
            + (P(None, *bh, None),) * 2, check_vma=False)(q, k, v, g, beta)
    b, h, t, dk = q.shape
    n = -(-t // chunk)
    per_step = min(n, CHUNKS_PER_STEP)
    n = -(-n // per_step) * per_step
    pad = n * chunk - t

    def rows(x):
        x = jnp.pad(x.astype(F32),
                    ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape((b * h,) + x.shape[2:])

    *terms, dec, least = _terms(
        rows(q), rows(k), rows(v), rows(g),
        rows(beta).reshape(b * h, n, 1, chunk), chunk, per_step,
        jnp.dtype(mdt), layer, bool(interpret))
    return tuple([x.reshape((n, b, h) + x.shape[2:]) for x in terms]
                 + [dec.reshape(n, b, h, dk), least.reshape(n, b, h, dk)])


# ---------------------------------------------------------------------------
# the head form: a decay a HEAD (Gated DeltaNet's). ``g`` is one scalar a
# head-token, so ``A = (K K^T) * L`` and ``B = (Q K^T) * L`` with ``L_ij =
# exp(G_i - G_j)``: one product each and one (c, c) matrix of differences,
# no sub-block loop and no span levels. A grid step takes a few chunks of
# one q/k head and of the ``group`` value heads it serves: values of the
# value heads are (group n, c, .), a head's n chunks after another's.
# ---------------------------------------------------------------------------
#: chunks of value heads a grid step of the head form takes (the chunks of
#: one q/k head times the heads it serves)
HEAD_CHUNKS_PER_STEP = 8


def _head_per_step(chunks: int, group: int) -> int:
    """Chunks of a q/k head a grid step takes."""
    return max(1, min(chunks, HEAD_CHUNKS_PER_STEP // group))


def head_vmem_bytes(kernel, chunk, dk, dv, group, itemsize, per_step=None):
    """Working set of one grid step of the head form, as
    :func:`vmem_bytes` counts the channel form's: the double-buffered
    operand and output blocks and the float32 (rows, d) and (rows,
    chunk) values a step holds at once, ``rows`` the group's. The counts
    of values are held to Mosaic for a described v5e at cell 10's shape
    (two heads of four chunks a step): the forward compiles under a
    limit of 6.5 MiB and not of 6, the backward under 8 and not 7.5
    (PERF.md section 6, PR 58)."""
    if per_step is None:
        per_step = _head_per_step(HEAD_CHUNKS_PER_STEP, group)
    rows = group * per_step * chunk
    wide = rows * max(dk, dv) * 4
    square = rows * max(chunk, LANES) * 4
    small = group * per_step * 8 * LANES * 4
    inputs = rows * (2 * dk // group + dv) * 4 + 2 * small
    terms = (rows * (3 * dk * itemsize + dv * 4)
             + rows * max(chunk, LANES) * itemsize + 2 * small)
    if kernel == "fwd":
        return 2 * (inputs + terms) + 6 * wide + 8 * square
    return 2 * (2 * inputs + terms) + 8 * wide + 8 * square


def takes_head_kernel(chunk: int, dk: int, dv: int, group: int) -> bool:
    """Whether a decay a head runs the kernels at these shapes: what the
    channel form asks (head sizes in whole lanes, a chunk the inverse's
    levels divide) and a group whose backward step fits the limit."""
    return (takes_kernel(chunk, dk, dv) and group >= 1
            and head_vmem_bytes("bwd", chunk, dk, dv, group, 4)
            <= VMEM_LIMIT)


def _tile(x, group):
    """A q/k head's (n, ..) value at each head it serves: (group n, ..)."""
    return x if group == 1 else jnp.concatenate([x] * group, 0)


def _fold(x, group):
    """(group n, ..) -> (n, ..): summed over the heads a q/k head serves."""
    n = x.shape[0] // group
    out = x[:n]
    for h in range(1, group):
        out = out + x[h * n:(h + 1) * n]
    return out


def _along_lanes(col):
    """(n, c, 1) values down the rows -> (n, 1, c) along the lanes."""
    n, c, _ = col.shape
    eye = _iota((n, c, c), 1) == _iota((n, c, c), 2)
    return jnp.sum(jnp.where(eye, col, 0.0), 1, keepdims=True)


def _load_step(q_ref, k_ref, v_ref, g_ref, beta_ref):
    """A grid step's operands as float32 values: the group's size; q, k
    (n, c, dk); v (group n, c, dv); g and beta (group n, 1, c), a chunk's
    scalars along the lanes."""
    group, n = g_ref.shape[:2]
    gn = group * n
    return (group, _load(q_ref, n), _load(k_ref, n), _load(v_ref, gn),
            _load(g_ref, gn), _load(beta_ref, gn))


def _in_chunk_head(q, k, g_row, beta_row, mdt, group):
    """What forward and backward both form for a decay a head: the
    running log-decay down the rows and along the lanes, ``L``, beta
    down the rows, the raw ``K K^T`` and ``Q K^T`` (made once a q/k head
    and read by each head it serves), ``A`` and ``(I + Diag(beta)
    A)^-1``. ``q``, ``k``: (n, c, dk); ``g_row``, ``beta_row``: (group n,
    1, c)."""
    gn, _, c = g_row.shape
    row, col = _iota((gn, c, c), 1), _iota((gn, c, c), 2)
    low = col <= row
    g_col = jnp.sum(jnp.where(low, g_row, 0.0), -1, keepdims=True)
    g_lane = _along_lanes(g_col)
    # every exponent a difference <= 0, exactly 0 on the diagonal
    big_l = jnp.where(low, jnp.exp(jnp.where(low, g_col - g_lane, 0.0)), 0.0)
    beta = _column(beta_row)
    kk = _tile(_mm(k, k, tb=True, mdt=mdt), group)
    qk = _tile(_mm(q, k, tb=True, mdt=mdt), group)
    a = jnp.where(col < row, kk * big_l, 0.0)
    # N = Diag(beta) A split for the inverse by masks alone
    nn = beta * a
    own = (row - (row & (SUB - 1))) == (col - (col & (SUB - 1)))
    t = _inverse(jnp.where(own, nn, 0.0),
                 [jnp.where(_span_mask(gn, c, half), nn, 0.0)
                  for half in _halves(c)])
    return g_col, g_lane, big_l, beta, kk, qk, a, t


def _last_lane(row):
    """(n, 1, c) -> (n, 1, 1): the chunk's last entry."""
    last = _iota(row.shape, 2) == row.shape[2] - 1
    return jnp.sum(jnp.where(last, row, 0.0), 2, keepdims=True)


def _store_heads(ref, x):
    """(group n, ..) head after head -> the block (n, group, ..) the scan
    reads, chunk leading."""
    n, group = ref.shape[:2]
    for h in range(group):
        ref[:, h] = x[h * n:(h + 1) * n].astype(ref.dtype)


def _load_terms(ref):
    """The block (n, group, ..) of a term's cotangent -> (group n, ..)
    float32, head after head."""
    group = ref.shape[1]
    return jnp.concatenate([ref[:, h] for h in range(group)],
                           0).astype(F32)


def _head_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref,
                     b_ref, qd_ref, kd_ref, dec_ref, least_ref, *, mdt):
    group, q, k, v, g_row, beta_row = _load_step(q_ref, k_ref, v_ref, g_ref,
                                                 beta_ref)
    g_col, g_lane, big_l, beta, _, qk, _, t = _in_chunk_head(
        q, k, g_row, beta_row, mdt, group)
    decay = jnp.exp(g_col)
    g_last = _last_lane(g_lane)
    kt, qt = _tile(k, group), _tile(q, group)
    _store_heads(w_ref, _mm(t, beta * kt * decay, mdt=mdt))
    _store_heads(u_ref, _mm(t, beta * v, mdt=mdt))
    _store_heads(b_ref, qk * big_l)
    _store_heads(qd_ref, qt * decay)
    _store_heads(kd_ref, kt * jnp.exp(g_last - g_col))
    lanes = (v.shape[0], 1, dec_ref.shape[-1])
    _store_heads(dec_ref, jnp.broadcast_to(jnp.exp(g_last), lanes))
    _store_heads(least_ref, jnp.broadcast_to(
        jnp.min(g_lane, axis=2, keepdims=True), lanes))


def _head_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, dw_ref, du_ref,
                     db_ref, dqd_ref, dkd_ref, ddec_ref, dq_ref, dk_ref,
                     dv_ref, dg_ref, dbeta_ref, *, mdt):
    group, q, k, v, g_row, beta_row = _load_step(q_ref, k_ref, v_ref, g_ref,
                                                 beta_ref)
    gn, c = v.shape[:2]
    g_col, g_lane, big_l, beta, kk, qk, a, t = _in_chunk_head(
        q, k, g_row, beta_row, mdt, group)
    row, col = _iota((gn, c, c), 1), _iota((gn, c, c), 2)
    low, strict = col <= row, col < row
    dw, du, db, dqd, dkd = (_load_terms(r) for r in (
        dw_ref, du_ref, db_ref, dqd_ref, dkd_ref))
    ddec = jnp.sum(_load_terms(ddec_ref), 2, keepdims=True)     # (gn, 1, 1)

    def over_d(x):
        return jnp.sum(x, -1, keepdims=True)

    decay = jnp.exp(g_col)
    kt, qt = _tile(k, group), _tile(q, group)
    k_dec = kt * decay
    # W = T (beta k exp G), U0 = T (beta v)
    xw, xu = beta * k_dec, beta * v
    dt = _mm(dw, xw, tb=True, mdt=mdt) + _mm(du, xu, tb=True, mdt=mdt)
    dxw, dxu = _mm(t, dw, ta=True, mdt=mdt), _mm(t, du, ta=True, mdt=mdt)
    # T = (I + Diag(beta) A)^-1
    dm = -_mm(_mm(t, dt, ta=True), t, tb=True)
    da = jnp.where(strict, beta * dm, 0.0)
    dbeta = over_d(dm * a) + over_d(dxw * k_dec) + over_d(dxu * v)
    dv = beta * dxu
    dk = dxw * beta * decay
    d_g = over_d(dxw * xw)
    # q exp(G), k exp(G_C - G), exp(G_C)
    dq = dqd * decay
    d_g = d_g + over_d(dqd * qt) * decay
    g_last = _last_lane(g_lane)
    fall = jnp.exp(g_last - g_col)
    dk = dk + dkd * fall
    through = over_d(dkd * kt) * fall
    d_g = d_g - through
    d_g = d_g + jnp.where(
        _iota(d_g.shape, 1) == c - 1,
        jnp.sum(through, 1, keepdims=True) + ddec * jnp.exp(g_last), 0.0)
    # A = (K K^T) * L, B = (Q K^T) * L: L's cotangent folded into G's, a
    # row takes what its differences took and a column gives it back
    db = jnp.where(low, db, 0.0)
    dl = jnp.where(strict, (da * kk + db * qk) * big_l, 0.0)
    d_g = d_g + jnp.sum(dl, 2, keepdims=True) \
        - _column(jnp.sum(dl, 1, keepdims=True))
    # the raw products, their cotangents summed over the heads served
    dkk, dqk = _fold(da * big_l, group), _fold(db * big_l, group)
    dk = (_fold(dk, group) + _mm(dkk, k, mdt=mdt)
          + _mm(dkk, k, ta=True, mdt=mdt) + _mm(dqk, q, ta=True, mdt=mdt))
    dq = _fold(dq, group) + _mm(dqk, k, mdt=mdt)
    dq_ref[...] = dq.reshape(dq_ref.shape)
    dk_ref[...] = dk.reshape(dk_ref.shape)
    dv_ref[...] = dv.reshape(dv_ref.shape)
    # g's cotangent: the running sum's transpose, along the lanes
    dg_ref[...] = jnp.sum(jnp.where(low, d_g, 0.0), 1,
                          keepdims=True).reshape(dg_ref.shape)
    dbeta_ref[...] = _along_lanes(dbeta).reshape(dbeta_ref.shape)


def _head_layout(q, v, chunk, per_step):
    """Grid (batch x q/k head, groups of chunks): q, k (BH, T, dk) read
    at their own head; v (BH, group, T, dv), g and beta (BH, group, N,
    1, C) at the heads it serves; the terms (N, BH, group, C, .)."""
    bh, t, dk = q.shape
    group, dv = v.shape[1], v.shape[3]
    n, rows = t // chunk, per_step * chunk

    def of_head(*block):        # blocks along the axis after the group's
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec((None, group) + block,
                            lambda b, i: (b, 0, i) + zeros)

    def term(*last):
        zeros = (0,) * (len(last) + 1)
        return pl.BlockSpec((per_step, None, group) + last,
                            lambda b, i: (i, b) + zeros)

    inputs = [_specs(True, rows, dk)] * 2 + [
        of_head(rows, dv), of_head(per_step, 1, chunk),
        of_head(per_step, 1, chunk)]
    terms = [term(chunk, dk), term(chunk, dv), term(chunk, chunk),
             term(chunk, dk), term(chunk, dk), term(1, LANES)]
    return (bh, n // per_step), n, group, inputs, terms


def _head_cost(kernel, q, v, chunk, itemsize):
    """What a call does, for XLA's scheduler: the products' operations
    (the float32 ones of the inverse at their six passes), an
    exponential a pair of rows, the operands' and terms' bytes."""
    bh, t, dk = q.shape
    group, dv = v.shape[1], v.shape[3]
    heads = bh * group * (t // chunk)           # chunks of value heads
    square, wide = 2 * chunk ** 3, 2 * chunk * chunk * (dk + dv)
    raw = 2 * 2 * chunk * chunk * dk / group
    flops = raw + 10 * 6 * square + wide
    inputs = 4 * chunk * (2 * dk / group + dv + 2)
    terms = chunk * (3 * dk * itemsize + 4 * dv + chunk * itemsize)
    if kernel == "bwd":
        flops += 2 * 6 * square + 2 * wide + 2 * raw
        inputs *= 2
    return pl.CostEstimate(flops=int(heads * flops),
                           transcendentals=heads * chunk * (chunk + 2),
                           bytes_accessed=int(heads * (inputs + terms)))


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _head_fwd_call(q, k, v, g, beta, chunk, per_step, mdt, interpret):
    grid, n, group, inputs, terms = _head_layout(q, v, chunk, per_step)
    bh, _, dk = q.shape
    dv = v.shape[3]

    def out(dt, *last):
        return jax.ShapeDtypeStruct((n, bh, group) + last, dt)
    return pl.pallas_call(
        functools.partial(_head_fwd_kernel, mdt=mdt),
        grid=grid, in_specs=inputs, out_specs=terms + [terms[-1]],
        out_shape=[out(mdt, chunk, dk), out(F32, chunk, dv),
                   out(mdt, chunk, chunk), out(mdt, chunk, dk),
                   out(mdt, chunk, dk), out(F32, 1, LANES),
                   out(F32, 1, LANES)],
        compiler_params=_PARAMS, interpret=interpret,
        cost_estimate=_head_cost("fwd", q, v, chunk,
                                 jnp.dtype(mdt).itemsize),
        name="gated_delta_rule_head_fwd",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _head_bwd_call(q, k, v, g, beta, cts, chunk, per_step, mdt, interpret):
    grid, _, _, inputs, terms = _head_layout(q, v, chunk, per_step)
    return pl.pallas_call(
        functools.partial(_head_bwd_kernel, mdt=mdt),
        grid=grid, in_specs=inputs + terms, out_specs=inputs,
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32)
                   for x in (q, k, v, g, beta)],
        compiler_params=_PARAMS, interpret=interpret,
        cost_estimate=_head_cost("bwd", q, v, chunk,
                                 jnp.dtype(mdt).itemsize),
        name="gated_delta_rule_head_bwd",
    )(q, k, v, g, beta, *cts)


def _head_note(kernel, layer, q, v, chunk, per_step, mdt):
    """One ``gdn.kernel`` instant per emitted call, at trace time."""
    if events.enabled():
        bh, t, dk = q.shape
        group, dv = v.shape[1], v.shape[3]
        n = t // chunk
        events.instant(
            "gdn.kernel", kernel=kernel, layer=layer, chunk=chunk,
            chunks=bh * group * n, grid_steps=bh * n // per_step,
            chunks_per_step=group * per_step, group=group,
            vmem_bytes=head_vmem_bytes(kernel, chunk, dk, dv, group,
                                       jnp.dtype(mdt).itemsize, per_step))


def _noted_head_fwd_call(q, k, v, g, beta, chunk, per_step, mdt, layer,
                         interpret):
    _head_note("fwd", layer, q, v, chunk, per_step, mdt)
    return tuple(_head_fwd_call(q, k, v, g, beta, chunk, per_step, mdt,
                                interpret))


_head_terms = jax.custom_vjp(_noted_head_fwd_call,
                             nondiff_argnums=(5, 6, 7, 8, 9))


def _head_terms_fwd(q, k, v, g, beta, *static):
    return _noted_head_fwd_call(q, k, v, g, beta, *static), \
        (q, k, v, g, beta)


def _head_terms_bwd(chunk, per_step, mdt, layer, interpret, res, cts):
    _head_note("bwd", layer, res[0], res[2], chunk, per_step, mdt)
    # (the least running log-decay is a reading, not a term)
    return tuple(_head_bwd_call(*res, list(cts[:6]), chunk, per_step, mdt,
                                interpret))


_head_terms.defvjp(_head_terms_fwd, _head_terms_bwd)


def head_chunk_terms(q, k, v, g, beta, chunk, mdt, *, layer=None,
                     interpret=None, mesh=None, spec=None):
    """:func:`chunk_terms` for a decay a HEAD: ``q``, ``k``: (B, H /
    group, T, dk), a head of theirs read in place by the ``group``
    consecutive heads of ``v`` it serves; ``v``: (B, H, T, dv); ``g``,
    ``beta``: (B, H, T), float32. Returns, chunk leading, ``W`` (N, B,
    H, C, dk), ``U0`` (.., C, dv) float32, ``B`` (.., C, C), ``q
    exp(G)``, ``k exp(G_C - G)`` in ``mdt``, ``exp(G_C)`` (N, B, H, 1)
    float32; and the least running log-decay a chunk, (N, B, H, 1).
    ``T`` is padded to whole grid steps as there.

    Under a mesh of more than one device the call runs under
    ``shard_map`` over the batch and head entries of ``spec``; the
    caller sees to it that every device holds whole groups (the head
    entry's degree divides q's heads)."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bh = (tuple(spec or ()) + (None, None))[:2]
        local = functools.partial(head_chunk_terms, chunk=chunk, mdt=mdt,
                                  layer=layer, interpret=interpret)
        # check_vma off: pallas_call outputs carry no varying-axes info
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*bh, None, None),) * 3 + (P(*bh, None),) * 2,
            out_specs=(P(None, *bh, None, None),) * 5
            + (P(None, *bh, None),) * 2, check_vma=False)(q, k, v, g, beta)
    b, hk, t, dk = q.shape
    h, dv = v.shape[1], v.shape[3]
    group = h // hk
    n = -(-t // chunk)
    per_step = _head_per_step(n, group)
    n = -(-n // per_step) * per_step
    pad = n * chunk - t

    def rows(x, *shape):
        x = jnp.pad(x.astype(F32),
                    ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape(shape)

    *terms, dec, least = _head_terms(
        rows(q, b * hk, n * chunk, dk), rows(k, b * hk, n * chunk, dk),
        rows(v, b * hk, group, n * chunk, dv),
        rows(g, b * hk, group, n, 1, chunk),
        rows(beta, b * hk, group, n, 1, chunk), chunk, per_step,
        jnp.dtype(mdt), layer, bool(interpret))
    return tuple([x.reshape((n, b, h) + x.shape[3:]) for x in terms]
                 + [dec.reshape(n, b, h, LANES)[..., :1],
                    least.reshape(n, b, h, LANES)[..., :1]])


# ---------------------------------------------------------------------------
# the scan: the state from chunk to chunk, for both forms of the decay.
# Given the state ``S`` a chunk starts from (dk, dv a head, float32) and
# the chunk's six terms,
#
#     U  = U0 - W S
#     O  = (q e^G) S + B U
#     S' = e^{G_C} S + (k e^{G_C - G})^T U
#
# (``ops/recurrent_ops.py::_chunk_step``, the fallback and the tests'
# oracle, under a ``lax.scan``: a ``while`` of N dependent iterations, a
# dynamic slice of every term and a dynamic update of the stacked outputs
# an iteration, the state to HBM and back between them). Here the grid's
# last axis is the groups of chunks in order, a block of heads' state
# rides in VMEM scratch from a chunk to the next, and a step's products
# are batched over the block's heads, whose chains do not wait on each
# other. The state is held TRANSPOSED, (dv, dk): the channel form's decay
# a channel of k then lies along the lanes as the terms kernel wrote it
# and is spread down the rows for nothing, and the head form's scalar is
# the same operand with a row of 1. The terms come in chunk leading as
# the terms kernels leave them, ``O`` leaves as (B H, T, dv) rows, and
# the state each chunk started from is kept for the backward, which walks
# the chunks last to first carrying the state's cotangent, forms ``U``
# again and writes the six terms' cotangents in the terms' own types.
# Products round their operands to the terms' ``mdt`` exactly where
# ``_chunk_step`` does (``S`` and ``U``, and in the backward the
# cotangents they meet); sums, states and decays are float32.
# ---------------------------------------------------------------------------
#: (batch x value head) rows a grid step of the scan takes: their chains
#: of dependent products are independent of each other
SCAN_HEADS_PER_STEP = 8
#: chunks a grid step of the scan walks
SCAN_CHUNKS_PER_STEP = 4
#: Mosaic's scoped-VMEM limit for the scan kernels (:func:`scan_vmem_bytes`)
SCAN_VMEM_LIMIT = 48 * 1024 * 1024


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is no more than ``most``."""
    return next(d for d in range(min(n, most), 0, -1) if n % d == 0)


def scan_vmem_bytes(kernel, chunk, dk, dv, itemsize,
                    heads=SCAN_HEADS_PER_STEP,
                    per_step=SCAN_CHUNKS_PER_STEP):
    """Working set of one grid step of the scan: the double-buffered
    blocks of the terms, the outputs' rows and the starting states (the
    backward: the terms' cotangents too), the state in scratch and the
    float32 values a chunk's products hold at once."""
    lanes = max(chunk, LANES)
    terms = chunk * (3 * dk * itemsize + dv * 4 + lanes * itemsize) \
        + 8 * LANES * 4
    rows, state = chunk * dv * 4, dk * dv * 4
    blocks = terms + rows + state
    if kernel == "scan_bwd":
        blocks += terms
    values = (6 if kernel == "scan_fwd" else 12) * rows + 3 * state
    return heads * (2 * per_step * blocks + state + values)


def _scan_fwd_kernel(w_ref, u0_ref, b_ref, qd_ref, kd_ref, dec_ref, o_ref,
                     starts_ref, s_ref):
    per_step, c, mdt = w_ref.shape[0], w_ref.shape[2], w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    state = s_ref[...]                          # (heads, dv, dk): S^T
    for i in range(per_step):
        starts_ref[i] = state
        sm = state.astype(mdt)
        u = (u0_ref[i] - _mm(w_ref[i], sm, tb=True, mdt=mdt)).astype(mdt)
        o_ref[:, i * c:(i + 1) * c, :] = (
            _mm(qd_ref[i], sm, tb=True, mdt=mdt) + _mm(b_ref[i], u, mdt=mdt))
        state = dec_ref[i] * state + _mm(u, kd_ref[i], ta=True, mdt=mdt)
    s_ref[...] = state


def _scan_bwd_kernel(w_ref, u0_ref, b_ref, qd_ref, kd_ref, dec_ref,
                     starts_ref, do_ref, dw_ref, du0_ref, db_ref, dqd_ref,
                     dkd_ref, ddec_ref, ds_ref):
    per_step, c, mdt = w_ref.shape[0], w_ref.shape[2], w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ds = ds_ref[...]            # (heads, dv, dk): dS'^T, the state a chunk
    for i in reversed(range(per_step)):         # leaves
        state = starts_ref[i]
        w, b, qd, kd = w_ref[i], b_ref[i], qd_ref[i], kd_ref[i]
        sm, dsm = state.astype(mdt), ds.astype(mdt)
        u = (u0_ref[i] - _mm(w, sm, tb=True, mdt=mdt)).astype(mdt)
        do = do_ref[:, i * c:(i + 1) * c, :].astype(mdt)
        # O = (q e^G) S + B U;  S' = e^{G_C} S + (k e^{G_C - G})^T U
        du = _mm(b, do, ta=True, mdt=mdt) + _mm(kd, dsm, tb=True, mdt=mdt)
        dum = du.astype(mdt)
        db_ref[i] = _mm(do, u, tb=True, mdt=mdt).astype(db_ref.dtype)
        dqd_ref[i] = _mm(do, sm, mdt=mdt).astype(dqd_ref.dtype)
        dkd_ref[i] = _mm(u, dsm, mdt=mdt).astype(dkd_ref.dtype)
        held = jnp.sum(ds * state, axis=1, keepdims=True)   # (heads, 1, dk)
        if ddec_ref.shape[-1] == 1:             # a decay a head
            held = jnp.sum(held, axis=2, keepdims=True)
        ddec_ref[i] = held
        # U = U0 - W S
        du0_ref[i] = du
        dw_ref[i] = (-_mm(dum, sm, mdt=mdt)).astype(dw_ref.dtype)
        ds = (dec_ref[i] * ds + _mm(do, qd, ta=True, mdt=mdt)
              - _mm(dum, w, ta=True, mdt=mdt))
    ds_ref[...] = ds


def _scan_layout(terms, heads, per_step, backward):
    """Grid (blocks of batch x head rows, groups of chunks), the block
    specs of the six terms (N, BH, ..), of the rows (BH, N C, dv) and of
    the states (N, BH, dv, dk); the backward walks the groups last to
    first."""
    n, bh, c, dk = terms[0].shape
    dv, groups = terms[1].shape[3], n // per_step

    def at(j):
        return groups - 1 - j if backward else j

    def term(*last):
        zeros = (0,) * len(last)
        return pl.BlockSpec((per_step, heads) + last,
                            lambda r, j: (at(j), r) + zeros)

    rows = pl.BlockSpec((heads, per_step * c, dv),
                        lambda r, j: (r, at(j), 0))
    return ((bh // heads, groups), [term(*x.shape[2:]) for x in terms],
            rows, term(dv, dk))


def _scan_cost(kernel, w, u0, dec):
    """What a call does, for XLA's scheduler: the products of a (head,
    chunk), the terms', rows' and states' bytes."""
    n, bh, c, dk = w.shape
    dv, itemsize = u0.shape[3], w.dtype.itemsize
    wide, square = 2 * c * dk * dv, 2 * c * c * dv
    terms = c * (3 * dk * itemsize + 4 * dv + c * itemsize) \
        + 4 * dec.shape[3]
    flops, moved = 3 * wide + square, terms + 4 * (c + dk) * dv
    if kernel == "scan_bwd":
        flops, moved = 8 * wide + 2 * square, 2 * terms + 4 * (c + dk) * dv
    return pl.CostEstimate(flops=n * bh * flops, transcendentals=0,
                           bytes_accessed=n * bh * moved)


_SCAN_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=SCAN_VMEM_LIMIT)
_SCAN_STATIC = ("heads", "per_step", "interpret")


@functools.partial(jax.jit, static_argnames=_SCAN_STATIC, inline=True)
def _scan_fwd_call(w, u0, b, qd, kd, dec, heads, per_step, interpret):
    n, bh, c, dk = w.shape
    dv = u0.shape[3]
    grid, terms, rows, states = _scan_layout((w, u0, b, qd, kd, dec), heads,
                                             per_step, False)
    return pl.pallas_call(
        _scan_fwd_kernel, grid=grid, in_specs=terms,
        out_specs=[rows, states],
        out_shape=[jax.ShapeDtypeStruct((bh, n * c, dv), F32),
                   jax.ShapeDtypeStruct((n, bh, dv, dk), F32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],
        compiler_params=_SCAN_PARAMS, interpret=interpret,
        cost_estimate=_scan_cost("scan_fwd", w, u0, dec),
        name="gated_delta_rule_scan_fwd",
    )(w, u0, b, qd, kd, dec)


@functools.partial(jax.jit, static_argnames=_SCAN_STATIC, inline=True)
def _scan_bwd_call(w, u0, b, qd, kd, dec, starts, do, heads, per_step,
                   interpret):
    dv, dk = starts.shape[2:]
    grid, terms, rows, states = _scan_layout((w, u0, b, qd, kd, dec), heads,
                                             per_step, True)
    return pl.pallas_call(
        _scan_bwd_kernel, grid=grid, in_specs=terms + [states, rows],
        out_specs=terms,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (w, u0, b, qd, kd, dec)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],
        compiler_params=_SCAN_PARAMS, interpret=interpret,
        cost_estimate=_scan_cost("scan_bwd", w, u0, dec),
        name="gated_delta_rule_scan_bwd",
    )(w, u0, b, qd, kd, dec, starts, do)


def _scan_note(kernel, scope, layer, w, u0, heads, per_step):
    """One ``kda.kernel`` / ``gdn.kernel`` instant per emitted call of
    the scan, at trace time."""
    if events.enabled():
        n, bh, c, dk = w.shape
        events.instant(
            scope + ".kernel", kernel=kernel, layer=layer, chunk=c,
            chunks=bh * n, grid_steps=bh * n // (heads * per_step),
            heads_per_step=heads, chunks_per_step=per_step,
            vmem_bytes=scan_vmem_bytes(kernel, c, dk, u0.shape[3],
                                       w.dtype.itemsize, heads, per_step))


def _noted_scan_fwd_call(w, u0, b, qd, kd, dec, heads, per_step, scope,
                         layer, interpret):
    _scan_note("scan_fwd", scope, layer, w, u0, heads, per_step)
    return tuple(_scan_fwd_call(w, u0, b, qd, kd, dec, heads, per_step,
                                interpret))


_scan = jax.custom_vjp(_noted_scan_fwd_call,
                       nondiff_argnums=(6, 7, 8, 9, 10))


def _scan_fwd(w, u0, b, qd, kd, dec, *static):
    o, starts = _noted_scan_fwd_call(w, u0, b, qd, kd, dec, *static)
    return (o, starts), (w, u0, b, qd, kd, dec, starts)


def _scan_bwd(heads, per_step, scope, layer, interpret, res, cts):
    _scan_note("scan_bwd", scope, layer, res[0], res[1], heads, per_step)
    # (the states are handed out for the tests to read, not to be pulled
    # back through)
    return tuple(_scan_bwd_call(*res, cts[0], heads, per_step, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_chunks(w, u0, b, q_dec, k_dec, decay, *, scope="kda", layer=None,
                interpret=None, mesh=None, spec=None):
    """The recurrence over the chunks from a zero state, by the scan
    kernels, on the terms as :func:`chunk_terms` / :func:`head_chunk_terms`
    return them, chunk leading: ``W`` (N, B, H, C, dk), ``U0`` (.., C, dv)
    float32, ``B`` (.., C, C), ``q exp(G)``, ``k exp(G_C - G)``, and
    ``exp(G_C)`` (N, B, H, dk), a decay a channel, or (N, B, H, 1), a
    decay a head. Returns ``O`` (B, H, N C, dv) float32 and the state
    each chunk starts from, transposed, (N, B, H, dv, dk) (what the
    backward keeps; no cotangent is taken for it). ``scope`` (``"kda"`` /
    ``"gdn"``) and ``layer`` name the caller in the ``<scope>.kernel``
    instants.

    Under a mesh of more than one device the call runs under
    ``shard_map`` over the batch and head entries of ``spec``: every
    (batch, head) carries a state of its own."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bh = (tuple(spec or ()) + (None, None))[:2]
        local = functools.partial(scan_chunks, scope=scope, layer=layer,
                                  interpret=interpret)
        # check_vma off: pallas_call outputs carry no varying-axes info
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(None, *bh, None, None),) * 5 + (P(None, *bh, None),),
            out_specs=(P(*bh, None, None), P(None, *bh, None, None)),
            check_vma=False)(w, u0, b, q_dec, k_dec, decay)
    n, bsz, h, c, dk = w.shape
    dv = u0.shape[-1]

    def rows(x, *last):
        return x.reshape((n, bsz * h) + (last or x.shape[3:]))

    o, starts = _scan(
        rows(w), rows(u0), rows(b), rows(q_dec), rows(k_dec),
        rows(decay, 1, decay.shape[-1]),
        _divisor(bsz * h, SCAN_HEADS_PER_STEP),
        _divisor(n, SCAN_CHUNKS_PER_STEP), scope, layer, bool(interpret))
    return o.reshape(bsz, h, n * c, dv), starts.reshape(n, bsz, h, dv, dk)
