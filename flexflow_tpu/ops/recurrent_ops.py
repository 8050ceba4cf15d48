"""Layers that carry a state along the sequence.

The gated delta rule (linear attention with a decay a channel and a
delta-rule write): a head keeps a ``d_k x d_v`` state ``S`` and, a token,

    S <- Diag(exp(g_t)) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

Run token by token that is ``T`` dependent steps. :func:`gated_delta_rule`
computes the same thing in chunks of ``C`` tokens: inside a chunk the
writes ``u_i = beta_i (v_i - (state before i)^T k_i)`` solve one
unit-lower-triangular ``C x C`` system a head, which does not depend on
the state the chunk starts from, so every chunk's system is solved at
once; between chunks ``S`` is carried in order (by the scan kernels of
``kernels/gated_delta_rule.py`` wherever the terms come from its
kernels, otherwise by a ``lax.scan``). With ``G`` the running
sum of ``g`` inside the chunk (every entry <= 0), ``S_0`` the state at the
chunk's start:

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)          (j <  i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)          (j <= i)
    [W  U0] = (I + Diag(beta) A)^-1 Diag(beta) [k * exp(G)   v]
    U   = U0 - W S_0
    O   = (q * exp(G)) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

The decay is a channel's, so ``A`` and ``B`` are not a product of two
decayed matrices a head: ``exp(G_i)`` times ``exp(-G_j)`` overflows
float32 once a chunk's decays sum past 88, and they do. Every exponent
taken here is a DIFFERENCE that is <= 0. A chunk is halved down to
sub-blocks of ``SUB`` rows: the second half of a span against its first
half goes through the second half's first row ``n`` (``exp(G_i - G_n)``
on the rows' side, ``exp(G_n - G_j)`` on the columns': one matrix
product a span, and the decayed copies of k and q made for all of a
chunk's spans together are as large as k and q), a sub-block against
itself through the ``(SUB, SUB, d)`` tensor of differences, reduced on
the spot.

With a decay a HEAD (``g`` one scalar a head-token, Gated DeltaNet's
form) the same equations hold with ``exp(g_t)`` a scalar, and the
chunk's matrices are products after all:

    A = (K K^T) * L,   B = (Q K^T) * L,   L_ij = exp(G_i - G_j)  (j <= i)

one matrix product a head-chunk and one ``C x C`` matrix of differences
(what :func:`_ssm_chunks` builds): no halving, no ``(SUB, SUB, d)``
tensor, one exponential a pair of rows and not one a pair a channel.
There a head of q and k may serve several heads of v (value head ``j``
reads q/k head ``j // group``): the raw products are made at the q/k
heads and shared. Where the shapes take them (:func:`head_decay_impl`)
these terms come from the head form of the kernels
(``kernels/gated_delta_rule.py::head_chunk_terms``: ``L``, ``A`` and the
inverse stay in VMEM, q and k are read at their own heads); otherwise
from :func:`_chunk_terms_head`, plain JAX, which is also the kernels'
oracle.

The state-space (Mamba-2) recurrence: a head keeps a ``P x N`` state
(``P`` its channels, ``N`` the state size) under a SCALAR decay a token,

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,   a_t = exp(dt_t A),  A < 0
    y_t = S_t C_t + D x_t

with ``B_t``, ``C_t`` (N wide) shared by every head. In chunks
(:func:`state_space_scan`), ``G`` the running sum of ``dt A`` inside a
chunk and ``S_0`` the state at its start:

    L_ij = exp(G_i - G_j)                             (j <= i)
    Y    = (L * C B^T)(dt * X) + exp(G) * (C S_0^T)
    S_C  = exp(G_C) S_0 + ((dt * X) * exp(G_C - G))^T B

Again every exponent is a difference that is <= 0. The decay is a
head's, so ``L`` is one ``C x C`` matrix a head and ``C B^T`` one for all
heads; nothing is halved or solved.

The selective scan (Mamba-1): ``D`` channels, each a state of ``N``
entries, under a decay that differs by channel AND by state entry,

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c]

so there is no ``C x C`` matrix of decays a head to multiply a product
by: a pair of tokens has ``N x D`` of them. :func:`selective_scan` walks
the TOKENS, in chunks that are rematerialised one at a time: a chunk's
``exp(dt A)`` and ``dt B x`` (``C x N x D`` each, every exponent <= 0)
are made at once, the state is carried through them step by step
(:func:`_decayed_sums`, whose backward is one walk the other way), and
the backward pass holds the state each chunk starts from.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import WeightSpec
from ..ffconst import InitializerType, OperatorType
from ..kernels import delta_mix as mix_kernels
from ..kernels import selective_scan as ssm1_kernels
from ..kernels import state_space as ssm_kernels
from ..kernels.gated_delta_rule import (SUB, chunk_terms, head_chunk_terms,
                                        scan_chunks, takes_head_kernel,
                                        takes_kernel)
from ..obs import events
from .nn_ops import MultiHeadAttentionOp, _rms, short_conv
from .registry import (OpDef, checkpointed, compute_dtype, register,
                       wrap_specs)

CHUNK = 64          # tokens a step of the scan
NORM_EPS = 1e-6     # under the root of q's and k's lengths


def _chunk_terms(q, k, v, g, beta, mdt):
    """Everything of a chunk that does not depend on the state it starts
    from, in plain JAX: the path of the shapes the kernels do not take,
    and what the kernels are tested against. ``q``, ``k``, ``g``: (B, H,
    N, C, dk), ``v``: (.., dv), ``beta``: (B, H, N, C); float32. Returns
    ``W`` (.., C, dk), ``U0`` (.., C, dv), ``B`` (.., C, C), ``q *
    exp(G)``, ``k * exp(G_C - G)``, ``exp(G_C)`` (.., dk) and the least
    ``G``."""
    n_c = k.shape[3]
    lead = k.shape[:3]
    spans = n_c // SUB          # a power of two of sub-blocks, or one
    sub = SUB if n_c % SUB == 0 and spans & (spans - 1) == 0 else n_c
    big_g = jnp.cumsum(g, axis=3)

    def prod(a, b):
        return jnp.einsum("...id,...jd->...ij", a.astype(mdt),
                          b.astype(mdt),
                          preferred_element_type=jnp.float32)

    def blocks(x, rows):
        return x.reshape(lead + (n_c // rows, rows) + x.shape[4:])

    def on_diagonal(x):                 # (.., m, r, r) -> (.., m r, m r)
        m, r = x.shape[-3], x.shape[-1]
        return jnp.einsum("...mij,mn->...minj", x, np.eye(m, dtype=x.dtype)
                          ).reshape(x.shape[:-3] + (m * r, m * r))

    # a sub-block against itself: the differences themselves
    gs, ks, qs = blocks(big_g, sub), blocks(k, sub), blocks(q, sub)
    low = np.tril(np.ones((sub, sub), bool))
    e = jnp.exp(jnp.where(low[..., None],
                          gs[..., :, None, :] - gs[..., None, :, :], 0.0))
    a = on_diagonal(jnp.where(np.tril(low, -1), jnp.sum(
        ks[..., :, None, :] * ks[..., None, :, :] * e, -1), 0.0))
    b = on_diagonal(jnp.where(low, jnp.sum(
        qs[..., :, None, :] * ks[..., None, :, :] * e, -1), 0.0))
    # the second half of a span against its first half, through the
    # second half's first row n: G_i - G_n <= 0 on the rows' side and
    # G_n - G_j <= 0 on the columns'; spans of 2 SUB rows, 4 SUB, .. C
    half = sub
    while half < n_c:
        gs, ks, qs = (blocks(x, 2 * half) for x in (big_g, k, q))
        g_n = gs[..., half:half + 1, :]
        rows = jnp.exp(gs[..., half:, :] - g_n)
        cols = ks[..., :half, :] * jnp.exp(g_n - gs[..., :half, :])
        pad = ((0, 0),) * (len(lead) + 1) + ((half, 0), (0, half))
        a += on_diagonal(jnp.pad(prod(ks[..., half:, :] * rows, cols), pad))
        b += on_diagonal(jnp.pad(prod(qs[..., half:, :] * rows, cols), pad))
        half *= 2

    # (I + Diag(beta) A)^-1 by one float32 triangular solve a chunk,
    # against the identity; W and U0 are then products like any other
    decay = jnp.exp(big_g)
    eye = jnp.eye(n_c, dtype=jnp.float32)
    inverse = jax.lax.linalg.triangular_solve(
        eye + beta[..., None] * a, jnp.broadcast_to(eye, a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    w = prod(inverse, jnp.swapaxes(beta[..., None] * k * decay, -1, -2))
    u0 = prod(inverse, jnp.swapaxes(beta[..., None] * v, -1, -2))
    g_last = big_g[..., -1:, :]
    return (w.astype(mdt), u0, b.astype(mdt), (q * decay).astype(mdt),
            (k * jnp.exp(g_last - big_g)).astype(mdt),
            jnp.exp(g_last[..., 0, :]), jnp.min(big_g))


def _chunk_terms_head(q, k, v, g, beta, mdt):
    """:func:`_chunk_terms` for a decay a head: ``g``, ``beta``: (B, H,
    N, C) at ``v``'s heads; ``q``, ``k``: (B, H / group, N, C, dk), a
    head of theirs read by ``group`` consecutive heads of ``v``. The
    same seven terms, ``exp(G_C)`` as (B, H, N, 1)."""
    group = v.shape[1] // k.shape[1]
    n_c = k.shape[3]
    big_g = jnp.cumsum(g, axis=3)

    def prod(a, b):
        return jnp.einsum("...id,...jd->...ij", a.astype(mdt),
                          b.astype(mdt),
                          preferred_element_type=jnp.float32)

    def served(x):              # a q/k head's array at each head it serves
        return jnp.repeat(x, group, axis=1) if group > 1 else x

    # L: the differences themselves, a head; 0 above the diagonal
    low = np.tril(np.ones((n_c, n_c), bool))
    big_l = jnp.where(low, jnp.exp(jnp.where(
        low, big_g[..., :, None] - big_g[..., None, :], 0.0)), 0.0)
    a = jnp.where(np.tril(low, -1), served(prod(k, k)) * big_l, 0.0)
    b = served(prod(q, k)) * big_l
    q, k = served(q), served(k)
    decay = jnp.exp(big_g)[..., None]
    eye = jnp.eye(n_c, dtype=jnp.float32)
    inverse = jax.lax.linalg.triangular_solve(
        eye + beta[..., None] * a, jnp.broadcast_to(eye, a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    w = prod(inverse, jnp.swapaxes(beta[..., None] * k * decay, -1, -2))
    u0 = prod(inverse, jnp.swapaxes(beta[..., None] * v, -1, -2))
    g_last = big_g[..., -1:]
    return (w.astype(mdt), u0, b.astype(mdt), (q * decay).astype(mdt),
            (k * jnp.exp(g_last - big_g)[..., None]).astype(mdt),
            jnp.exp(g_last), jnp.min(big_g))


def _chunk_step(mdt, state, terms):
    """One chunk given the state it starts from (B, H, dk, dv float32):
    its outputs (B, H, C, dv) and the state it leaves."""
    w, u0, b, q_dec, k_dec, decay = terms

    def mm(pattern, x, y):
        return jnp.einsum(pattern, x.astype(mdt), y.astype(mdt),
                          preferred_element_type=jnp.float32)

    u = u0 - mm("bhcd,bhde->bhce", w, state)
    out = mm("bhcd,bhde->bhce", q_dec, state) + mm("bhij,bhje->bhie", b, u)
    state = decay[..., None] * state + mm("bhcd,bhce->bhde", k_dec, u)
    return state, out


def _plain_scan(terms, mdt, *, layer=None, mesh=None, spec=None):
    """The state carried over the chunks by a ``lax.scan`` over
    :func:`_chunk_step`, its body rematerialised, on the six terms chunk
    leading (N, B, H, ..): ``O`` (B, H, N C, dv). The fallback, and what
    the scan kernels are timed and tested against."""
    w, u0 = terms[0], terms[1]
    state = jnp.zeros(w.shape[1:3] + (w.shape[-1], u0.shape[-1]),
                      jnp.float32)
    _, out = jax.lax.scan(                      # over the chunks: N leads
        checkpointed(lambda s, xs: _chunk_step(mdt, s, xs), site="kda.step",
                     layer=layer, specs=(spec, spec), mesh=mesh),
        state, terms)
    out = jnp.moveaxis(out, 0, 2)                  # (B, H, N, C, dv)
    return out.reshape(out.shape[:2] + (-1,) + out.shape[4:])


def _in_chunks(x, chunk, axis=2):
    """(B, H, T, ..) -> (B, H, N, C, ..) float32 (``axis``: where T
    stands); the padded positions write nothing (beta, dt 0) and decay
    nothing (g 0)."""
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, -x.shape[axis] % chunk)
    x = jnp.pad(x.astype(jnp.float32), widths)
    return x.reshape(x.shape[:axis] + (-1, chunk) + x.shape[axis + 1:])


def _holds_whole_heads(heads, mesh, spec) -> bool:
    """Whether every device of ``mesh`` holds whole heads of an operand
    of ``heads`` heads sharded by the head entry of ``spec``."""
    if mesh is None or mesh.size == 1:
        return True
    entry = (tuple(spec or ()) + (None, None))[1]
    degree = 1
    for axis in (entry if isinstance(entry, tuple) else (entry,)):
        degree *= mesh.shape[axis] if axis is not None else 1
    return heads % degree == 0


def head_decay_impl(chunk, q_heads, heads, dk, dv, mesh=None, spec=None):
    """``"kernel"`` or ``"plain"`` for a decay a head, from what the
    call can observe: the shapes (:func:`takes_head_kernel`) and, under a
    mesh of several devices, whether every device holds whole groups
    (the head entry of ``spec`` divides q's and k's heads too)."""
    if heads % q_heads or not takes_head_kernel(chunk, dk, dv,
                                                heads // q_heads):
        return "plain"
    return "kernel" if _holds_whole_heads(q_heads, mesh, spec) else "plain"


def mix_impl(weights, tokens, mesh=None, spec=None):
    """``"kernel"`` or ``"plain"`` for a layer's q, k and v chains (the
    taps, the SiLU and the unit length between a projection and the
    recurrence), from what the call can observe: the shapes
    (``kernels/delta_mix.py::takes_kernel``, every branch's) and, under
    a mesh of several devices, whether every device holds whole heads
    of q and k as of v."""
    for n in "qkv":
        heads, d, taps = weights["conv_" + n].shape
        if not (mix_kernels.takes_kernel(d, taps, tokens, jnp.float32)
                and _holds_whole_heads(heads, mesh, spec)):
            return "plain"
    return "kernel"


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK,
                     mdt=jnp.float32, *, layer=None, mesh=None, spec=None):
    """The recurrence of the module's docstring from a zero state, in
    chunks, heads leading: ``q``, ``k``, ``g``: (B, H, T, dk), ``g`` <= 0
    the log of the decay; ``v``: (B, H, T, dv); ``beta``: (B, H, T).
    ``mdt``: the type the products' operands are rounded to (sums,
    states, decays and the inverse are float32). Returns ``o`` (B, H,
    T, dv) float32 and the most negative running sum of ``g`` inside any
    chunk.

    ``g`` of (B, H, T), one scalar a head-token, is a decay a HEAD and
    takes a path of its own (nothing is broadcast over the channels into
    the channel kernels); ``q`` and ``k`` may then have ``H / group``
    heads. Its chunks' terms come from the head form of the kernels
    (``head_chunk_terms``, one ``gdn.kernel`` instant a call) where
    :func:`head_decay_impl` says ``"kernel"``, otherwise from
    :func:`_chunk_terms_head` under ``remat.gdn.terms``.

    The chunks' terms come from the Pallas kernels of
    ``kernels/gated_delta_rule.py`` where the shapes take them
    (:func:`takes_kernel`: a chunk of a power-of-two number of
    sub-blocks, head sizes in whole lanes; ``layer`` names the caller in
    their ``kda.kernel`` instants, ``mesh`` / ``spec`` are
    ``flash_attention``'s), forward and backward under one
    ``custom_vjp`` that keeps the five inputs; otherwise from
    :func:`_chunk_terms`, rematerialised, with autodiff's backward.

    Wherever the terms came from the kernels, of either form, the state
    is carried over the chunks by the scan kernel pair of the same
    module (``scan_chunks``: the same predicate, no other; the state in
    VMEM from a chunk to the next, the outputs written as ``(B H, T,
    dv)`` rows, one ``kda.kernel`` / ``gdn.kernel`` instant a call with
    ``kernel="scan_fwd"`` / ``"scan_bwd"``) under a ``custom_vjp`` that
    keeps the six terms and the state each chunk starts from. Every
    other shape takes a plain ``lax.scan`` over :func:`_chunk_step`, its
    body rematerialised: the backward pass holds the chunk-boundary
    states, the terms the scan reads and one chunk's matrices, not the
    ``(SUB, SUB, d)`` differences nor every chunk's intermediate
    products. That scan is also what the kernels are tested against."""
    t = q.shape[2]
    scope = None                # the kernels' instants, where they run
    if g.ndim == 3 and head_decay_impl(
            chunk, k.shape[1], v.shape[1], k.shape[-1], v.shape[-1], mesh,
            spec) == "kernel":
        scope = "gdn"
        *terms, least = head_chunk_terms(q, k, v, g, beta, chunk, mdt,
                                         layer=layer, mesh=mesh, spec=spec)
        least = jnp.min(least)
    elif g.ndim == 3:
        *terms, least = checkpointed(
            lambda *a: _chunk_terms_head(*a, mdt), site="gdn.terms",
            layer=layer, specs=(spec,) * 5, mesh=mesh)(
            *(_in_chunks(x, chunk) for x in (q, k, v, g, beta)))
        terms = [jnp.moveaxis(x, 2, 0) for x in terms]
    elif takes_kernel(chunk, k.shape[-1], v.shape[-1]):
        scope = "kda"
        *terms, least = chunk_terms(q, k, v, g, beta, chunk, mdt,
                                    layer=layer, mesh=mesh, spec=spec)
        least = jnp.min(least)
    else:
        *terms, least = checkpointed(
            lambda *a: _chunk_terms(*a, mdt), site="kda.terms", layer=layer,
            specs=(spec,) * 5, mesh=mesh)(
            *(_in_chunks(x, chunk) for x in (q, k, v, g, beta)))
        terms = [jnp.moveaxis(x, 2, 0) for x in terms]
    if scope is not None:       # the terms as the kernels left them
        out, _ = scan_chunks(*terms, scope=scope, layer=layer, mesh=mesh,
                             spec=spec)
    else:
        out = _plain_scan(terms, mdt, layer=layer, mesh=mesh, spec=spec)
    return out[:, :, :t], jax.lax.stop_gradient(least)


def _ssm_chunks(mdt, dtx, bm, cm, big_g):
    """Everything of the chunks that does not depend on the state a
    chunk starts from, all chunks at once: ``dtx = dt * x`` (B, M, C, H,
    P), ``bm``, ``cm`` (B, M, C, N), ``big_g`` the running log-decay
    inside each chunk (B, M, C, H); float32. Returns the outputs the
    chunk's own tokens give, ``(L * C B^T)(dt x)`` (B, M, C, H, P), and
    what the chunk adds to the state, ``((dt x) * exp(G_C - G))^T B``
    (B, M, H, P, N)."""
    def mm(pattern, x, y):
        return jnp.einsum(pattern, x.astype(mdt), y.astype(mdt),
                          preferred_element_type=jnp.float32)

    n_c = dtx.shape[2]
    g = jnp.moveaxis(big_g, 2, 3)                       # (B, M, H, C)
    low = np.tril(np.ones((n_c, n_c), bool))
    # L: the differences themselves, a head; 0 above the diagonal
    decay = jnp.where(low, jnp.exp(jnp.where(
        low, g[..., :, None] - g[..., None, :], 0.0)), 0.0)
    inside = mm("bmhij,bmjhp->bmihp",
                mm("bmin,bmjn->bmij", cm, bm)[:, :, None] * decay, dtx)
    added = mm("bmjhp,bmjn->bmhpn",
               dtx * jnp.exp(big_g[:, :, -1:] - big_g)[..., None], bm)
    return inside, added


def state_space_scan(x, dt, a, bm, cm, chunk: int, mdt=jnp.float32, *,
                     layer=None, kernels=True):
    """The state-space recurrence of the module's docstring from a zero
    state, in chunks, without the ``D`` skip: ``x`` (B, T, H, P), ``dt``
    (B, T, H) > 0 the step size, ``a`` (H,) < 0, ``bm``, ``cm`` (B, T, N)
    (one group: every head reads the same) or (B, T, groups, N) (head
    ``h`` reads group ``h // (H / groups)``). ``mdt``: the type the
    products' operands are rounded to (sums, log-decays, their
    exponentials and the states are float32). Returns ``y`` (B, T, H, P)
    float32 and the most negative log-decay summed over one chunk.

    Where the shapes take them (``ssm_kernels.takes_kernel``: a chunk in
    whole tiles of 128, the state and ``H P`` in whole lanes of whole
    heads) the chunks run in the Pallas kernels of
    ``kernels/state_space.py``, forward and backward under one
    ``custom_vjp`` that keeps its five inputs and the chunk-boundary
    states: ``L``, a ``C x C`` float32 matrix a head a chunk, exists a
    128 x 128 tile at a time in VMEM, and a block of heads' state rides
    there from chunk to chunk (``layer`` names the caller in their
    ``ssm.kernel`` instants). Every other shape, and a caller that says
    ``kernels=False`` (a mesh of several devices), takes plain JAX: what
    a chunk's own tokens give, and what it adds to the state, is made
    for all chunks at once (:func:`_ssm_chunks`), rematerialised, so the
    backward pass makes ``L`` again instead of holding it; one
    ``lax.scan`` over the chunks then carries the state, ``S <- exp(G_C)
    S + added``, and keeps the state each chunk starts from (M of them:
    what the scan stacks is ``P x N`` a head a chunk, not a chunk's
    outputs); those states are read by one product for all chunks. A
    sequence that is no whole number of chunks is padded with positions
    that write nothing and decay nothing (``dt`` 0). Several groups on
    the plain path are that path once a group, over the group's heads."""
    t = x.shape[1]
    f32 = jnp.float32
    groups = bm.shape[2] if bm.ndim == 4 else 1
    by_kernels = kernels and ssm_kernels.takes_kernel(
        chunk, *x.shape[2:], bm.shape[-1], groups)
    if groups > 1 and not by_kernels:
        per = x.shape[2] // groups
        ys, leasts = zip(*(
            state_space_scan(x[:, :, sl], dt[:, :, sl], a[sl], bm[:, :, g],
                             cm[:, :, g], chunk, mdt, layer=layer,
                             kernels=False)
            for g, sl in ((g, slice(g * per, (g + 1) * per))
                          for g in range(groups))))
        return jnp.concatenate(ys, axis=2), jnp.min(jnp.stack(leasts))

    def in_chunks(v):           # (B, T, ..) -> (B, M, C, ..)
        return _in_chunks(v, chunk, axis=1)

    dt_c, cm_c = in_chunks(dt), in_chunks(cm)
    big_g = jnp.cumsum(dt_c * a.astype(f32), axis=2)    # (B, M, C, H)
    if by_kernels:
        def whole(v):           # (B, M, C, ..) -> (B, M C, ..)
            return v.reshape((v.shape[0], -1) + v.shape[3:])

        def last(v):            # .. -> (B, .., M C): tokens along lanes
            return jnp.moveaxis(whole(v), 1, -1)

        def side_by_side(v):    # the groups' N columns, one after another
            v = whole(v)
            return v.reshape(v.shape[:2] + (-1,))

        y, _ = ssm_kernels.scan_chunks(
            last(in_chunks(x)), last(dt_c), last(big_g),
            side_by_side(in_chunks(bm)), side_by_side(cm_c), chunk, mdt,
            layer=layer, groups=groups)
        y = jnp.moveaxis(y, -1, 1)                      # (B, M C, H, P)
    else:
        inside, added = checkpointed(
            lambda *v: _ssm_chunks(mdt, *v), site="ssm.chunk", layer=layer)(
            in_chunks(x) * dt_c[..., None], in_chunks(bm), cm_c, big_g)
        whole = jnp.exp(big_g[:, :, -1])                # (B, M, H)

        def step(state, now):   # the state a chunk starts from, stacked
            keeps, adds = now
            return keeps[..., None, None] * state + adds, state

        _, starts = jax.lax.scan(
            step, jnp.zeros(added.shape[:1] + added.shape[2:], f32),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
        before = jnp.einsum("bmin,mbhpn->bmihp", cm_c.astype(mdt),
                            starts.astype(mdt), preferred_element_type=f32) \
            * jnp.exp(big_g)[..., None]
        y = inside + before                             # (B, M, C, H, P)
        y = y.reshape((y.shape[0], -1) + y.shape[3:])
    return y[:, :t], jax.lax.stop_gradient(jnp.min(big_g[:, :, -1]))


@jax.custom_vjp
def _decayed_sums(da, dbx, h0):
    """``h_t = da_t * h_{t-1} + dbx_t`` from ``h0`` (B, N, D), token by
    token over the leading axis of ``da``, ``dbx`` (C, B, N, D): every
    ``h_t``, stacked (C, B, N, D). Elementwise, float32. Its backward is
    one walk from the last token to the first carrying the state's
    cotangent, ``G_t = g_t + da_{t+1} G_{t+1}``; the operands' follow
    from the stacked ``G`` at once (``d dbx = G``, ``d da_t = G_t
    h_{t-1}``): the residuals are ``da``, the states and ``h0``."""
    def step(h, now):
        h = now[0] * h + now[1]
        return h, h

    return jax.lax.scan(step, h0, (da, dbx))[1]


def _decayed_sums_fwd(da, dbx, h0):
    hs = _decayed_sums(da, dbx, h0)
    return hs, (da, hs, h0)


def _decayed_sums_bwd(res, g):
    da, hs, h0 = res

    def step(later, now):       # ``later``: da_{t+1} G_{t+1}
        total = now[0] + later
        return now[1] * total, total

    d_h0, total = jax.lax.scan(step, jnp.zeros_like(h0), (g, da),
                               reverse=True)
    return total * jnp.concatenate([h0[None], hs[:-1]]), total, d_h0


_decayed_sums.defvjp(_decayed_sums_fwd, _decayed_sums_bwd)


def _selective_chunk(h0, x, dt, bm, cm, a):
    """One chunk of :func:`selective_scan` from the state ``h0`` (B, N,
    D): ``x``, ``dt`` (C, B, D), ``bm``, ``cm`` (C, B, N), ``a`` (N, D).
    Returns the state it leaves and its outputs (C, B, D)."""
    da = jnp.exp(dt[:, :, None, :] * a)                 # dt a <= 0
    dbx = (dt * x)[:, :, None, :] * bm[..., None]
    hs = _decayed_sums(da, dbx, h0)
    return hs[-1], jnp.sum(hs * cm[..., None], axis=2)


def selective_scan(x, dt, a, bm, cm, chunk: int, *, layer=None,
                   kernels=True):
    """The selective scan of the module's docstring from a zero state,
    without the ``D`` skip: ``x``, ``dt`` (B, T, D) with ``dt`` > 0 the
    step size a channel-token, ``a`` (N, D) < 0, ``bm``, ``cm`` (B, T,
    N); everything float32 (no product here has a matrix unit's shape:
    ``N`` is 16). Returns ``y`` (B, T, D) and the most negative ``dt a``
    of any token, channel and state entry.

    Where the shapes take them (``ssm1_kernels.takes_kernel``: the
    channels in whole blocks of 1,024, a state of whole eights, a chunk
    of whole sublane tiles) the walk runs in the Pallas kernels of
    ``kernels/selective_scan.py``, forward and backward under one
    ``custom_vjp`` that keeps its five inputs and the chunk-boundary
    states: the state rides in VMEM from token to token and chunk to
    chunk, and nothing of shape ``(.., N, D)`` but those boundary states
    reaches HBM (``layer`` names the caller in their ``ssm1.kernel``
    instants). Every other shape, and a caller that says
    ``kernels=False`` (a mesh of several devices), takes plain JAX: one
    ``lax.scan`` over the chunks carries the state (``N x D`` float32 a
    sequence); its body, rematerialised (site ``ssm1.chunk``), is
    :func:`_selective_chunk`, so what the backward pass holds is the
    state each chunk starts from and one chunk's ``(C, N, D)`` arrays at
    a time, never ``(T, N, D)``. A sequence that is no whole number of
    chunks is padded with positions that write nothing and decay
    nothing (``dt`` 0); one shorter than a chunk is one chunk of its
    own length."""
    b, t, _ = x.shape
    chunk = min(int(chunk), t)
    m = -(-t // chunk)

    def least():
        return jax.lax.stop_gradient(jnp.min(dt * jnp.min(a, 0)))

    if kernels and ssm1_kernels.takes_kernel(chunk, *a.shape[::-1]):
        def padded(v):          # (B, T, ..) -> (B, M C, ..)
            return _in_chunks(v, chunk, axis=1).reshape((b, m * chunk, -1))

        y = ssm1_kernels.scan_chunks(
            padded(x), padded(dt), a.astype(jnp.float32), padded(bm),
            padded(cm), chunk, layer=layer)
        return y[:, :t], least()

    one = checkpointed(_selective_chunk, site="ssm1.chunk", layer=layer)
    _, y = jax.lax.scan(
        lambda h, now: one(h, *now, a),
        jnp.zeros((b,) + a.shape, jnp.float32),
        # (B, T, ..) -> (M, C, B, ..): chunks and their tokens lead
        tuple(jnp.moveaxis(_in_chunks(v, chunk, axis=1), 0, 2)
              for v in (x, dt, bm, cm)))
    y = jnp.moveaxis(y.reshape((m * chunk, b) + y.shape[3:]), 0, 1)
    return y[:, :t], least()


def _unit(x):
    """x / |x|_2 over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + NORM_EPS)


@register
class GatedDeltaRuleOp(OpDef):
    """A gated delta-rule linear-attention layer (Kimi Delta Attention's
    form): ``H`` heads of ``d``, each the recurrence of
    :func:`gated_delta_rule` over its own q, k, v.

      q, k, v = silu(short_conv(x w))      K causal depthwise taps each
      q <- q / |q| * d^-1/2;  k <- k / |k|            a head
      g = -exp(A_log) * softplus((x wf_a) wf_b + dt_bias)   a channel
      beta = sigmoid(x wb)                                   a head
      o = gated_delta_rule(q, k, v, g, beta)
      y = [RMSNorm_d(o; o_norm) * sigmoid((x wg_a) wg_b)] wo

    With ``decay = "head"`` in the parameters (Gated DeltaNet's form,
    Qwen3-Next's linear layers) ``num_key_heads`` heads of q and k serve
    ``num_heads`` heads of v (value head ``j`` reads q/k head ``j //
    group``), the decay is a scalar a value head and the gate is
    full-rank under a SiLU:

      g = -exp(A_log) * softplus(x wa + dt_bias)             a head
      y = [RMSNorm_d(o; o_norm) * silu(x wz)] wo

    with ``A_log`` and ``dt_bias`` a head and no low-rank pair. The
    recurrence then runs under the name scope ``gdn.scan`` (the chunks'
    terms by the head form of the kernels and the state by the scan
    kernels at head sizes in whole lanes, their backward too, otherwise
    :func:`_chunk_terms_head` and a ``lax.scan``; the ``gdn.scan``
    instant's ``impl`` and ``scan`` say which), its instant and counters
    are ``gdn.*``.

    No bias in any projection. The projections are matrix products at
    the compute dtype with float32 accumulation; taps, gates, norms,
    decays, the state and the chunks' inverse are float32. The
    recurrence (the chunks' terms and the scan over the chunk states,
    both by the kernels of ``kernels/gated_delta_rule.py`` at head sizes
    in whole lanes; not the projections) runs under the name scope
    ``kda.scan``. At head sizes in whole lanes q, k and v go from the
    projections' products to the recurrence through the two kernels of
    ``kernels/delta_mix.py`` (:func:`mix_impl`; taps, SiLU, unit length
    and the turn to heads-first in one pass each way) under ``kda.mix``
    / ``gdn.mix``; the layer's ``kda.scan`` / ``gdn.scan`` instant says
    so in ``mix``. Training and evaluation only: there is no
    decode path that carries the state from call to call."""
    op_type = OperatorType.OP_GATED_DELTA_RULE
    keeps_output_for_block = True   # ``emit``: the layer is one checkpoint

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        h, d, k = params["num_heads"], params["head_dim"], params["taps"]
        r = params.get("gate_rank") or d
        hk = params.get("num_key_heads") or h

        def fans(i, o):
            return {"fans": (i, o)}
        ws = []
        for n, heads in (("q", hk), ("k", hk), ("v", h)):
            ws += [WeightSpec(f"w{n}", (e, heads, d), dt,
                              init_args=fans(e, heads * d)),
                   # one filter a channel: K taps in, K positions reached
                   WeightSpec(f"conv_{n}", (heads, d, k), dt,
                              init_args=fans(k, k))]
        uniform = InitializerType.UNIFORM
        if params.get("decay") == "head":
            return ws + [
                WeightSpec("wa", (e, h), dt),
                # A = exp(A_log) uniform in (1e-4, 16), softplus(dt_bias)
                # log-uniform in (1e-3, 1e-1), both a head
                WeightSpec("A_log", (h,), dt, uniform,
                           {"min": 1e-4, "max": 16.0, "map": "log"}),
                WeightSpec("dt_bias", (h,), dt, uniform,
                           {"min": math.log(1e-3), "max": math.log(1e-1),
                            "map": "inverse_softplus_of_exp"}),
                WeightSpec("wb", (e, h), dt),
                WeightSpec("wz", (e, h, d), dt, init_args=fans(e, h * d)),
                WeightSpec("o_norm", (d,), dt, InitializerType.ONE),
                WeightSpec("wo", (h, d, e), dt, init_args=fans(h * d, e))]
        return ws + [
            WeightSpec("wf_a", (e, r), dt),
            WeightSpec("wf_b", (r, h, d), dt, init_args=fans(r, h * d)),
            # how fast a state decays: A = exp(A_log) uniform in (1, 16),
            # softplus(dt_bias) log-uniform in (1e-3, 1e-1)
            WeightSpec("A_log", (h,), dt, uniform,
                       {"min": 1.0, "max": 16.0, "map": "log"}),
            WeightSpec("dt_bias", (h, d), dt, uniform,
                       {"min": math.log(1e-3), "max": math.log(1e-1),
                        "map": "inverse_softplus_of_exp"}),
            WeightSpec("wb", (e, h), dt),
            WeightSpec("wg_a", (e, r), dt),
            WeightSpec("wg_b", (r, h, d), dt, init_args=fans(r, h * d)),
            WeightSpec("o_norm", (d,), dt, InitializerType.ONE),
            WeightSpec("wo", (h, d, e), dt, init_args=fans(h * d, e))]

    @staticmethod
    def projections(x, weights, mdt, *, layer=None, specs=None, mesh=None,
                    shard=(None, None)):
        """``q, k, v, g, beta`` as the recurrence takes them and the
        output gate, all float32 and heads leading: (B, H, T, d) but
        ``beta`` (B, H, T). ``layer`` names the caller in its branches'
        ``remat.wrap`` instants, which count one device's bytes by
        ``specs`` (``x``'s, the weights' by name) over ``mesh``.

        Where :func:`mix_impl` says ``"kernel"`` (``shard``: the
        kernels' ``(mesh, spec)``, ``_kernel_shard_spec``'s) q, k and v
        go from the projection's product, left tokens-first as the
        product writes it, to heads-first through
        ``kernels/delta_mix.py`` under the name scope ``kda.mix`` /
        ``gdn.mix``, q's ``d ** -0.5`` inside; otherwise through
        ``short_conv``, ``silu`` and ``_unit``, which is also the
        kernels' oracle."""
        f32 = jnp.float32
        scope = "gdn" if "wa" in weights else "kda"
        by_kernel = mix_impl(weights, x.shape[1], *shard) == "kernel"

        def mm(pattern, a, w):
            return jnp.einsum(pattern, a.astype(mdt), w.astype(mdt),
                              preferred_element_type=f32)

        def mixed(x, w, taps, unit, scale=1.0, part=None):
            if by_kernel:
                p = mm("bte,ehd->bthd", x, w)
                with jax.named_scope(scope + ".mix"):
                    return mix_kernels.delta_mix(
                        p, taps, unit=unit, scale=scale, eps=NORM_EPS,
                        scope=scope, layer=layer, part=part, mesh=shard[0],
                        spec=shard[1])
            # a head's channels: (B, T, d)
            z = jax.nn.silu(jax.vmap(short_conv, (1, 0), 1)(
                mm("bte,ehd->bhtd", x, w), taps.astype(f32)))
            return _unit(z) if unit else z

        def low_rank(x, a, b):
            return mm("btr,rhd->bhtd", mm("bte,er->btr", x, a), b)

        def decay(x, a, b, a_log, dt_bias):
            return -jnp.exp(a_log.astype(f32))[:, None, None] \
                * jax.nn.softplus(low_rank(x, a, b)
                                  + dt_bias.astype(f32)[:, None])

        # each branch rematerialised by itself: the backward pass then
        # holds one branch's intermediate arrays at a time
        x_spec, w_specs = specs or (None, {})

        def branch(fn, *names, **static):
            return checkpointed(
                lambda x, *w: fn(x, *w, **static), site="kda.branch",
                layer=layer, part=names[0],
                weights=range(1, len(names) + 1), mesh=mesh,
                specs=(x_spec,) + tuple(w_specs.get(n) for n in names))(
                x, *(weights[n] for n in names))

        def head_decay(x, w, a_log, dt_bias):   # (B, H, T): a head's own
            return -jnp.exp(a_log.astype(f32))[:, None] * jnp.swapaxes(
                jax.nn.softplus(mm("bte,eh->bth", x, w)
                                + dt_bias.astype(f32)), 1, 2)

        def full_rank(x, w):
            return jax.nn.silu(mm("bte,ehd->bhtd", x, w))

        d = weights["wq"].shape[-1]
        if by_kernel:
            q, k, v = (branch(mixed, "w" + n, "conv_" + n, unit=n != "v",
                              scale=d ** -0.5 if n == "q" else 1.0,
                              part="w" + n) for n in "qkv")
        else:
            q = branch(mixed, "wq", "conv_q", unit=True) * d ** -0.5
            k = branch(mixed, "wk", "conv_k", unit=True)
            v = branch(mixed, "wv", "conv_v", unit=False)
        if "wa" in weights:
            g = branch(head_decay, "wa", "A_log", "dt_bias")
        else:
            g = branch(decay, "wf_a", "wf_b", "A_log", "dt_bias")
        beta = jnp.swapaxes(
            jax.nn.sigmoid(mm("bte,eh->bth", x, weights["wb"])), 1, 2)
        if "wz" in weights:
            gate = branch(full_rank, "wz")
        else:
            gate = jax.nn.sigmoid(branch(low_rank, "wg_a", "wg_b"))
        return q, k, v, g, beta, gate

    def emit(self, params, inputs, weights, ctx, name):
        (x,) = inputs
        if getattr(ctx, "kv_mode", None) is not None:
            raise NotImplementedError(
                f"{name}: the gated delta rule has no decode path that "
                f"carries its state beside a KV cache")
        mdt = compute_dtype(ctx, x.dtype)
        chunk = int(params.get("chunk", CHUNK))
        h, d = weights["wv"].shape[1:]
        b, t = x.shape[:2]
        by_head = "wa" in weights
        scope = "gdn" if by_head else "kda"
        # a compiled kernel inside a multi-device jit runs on each
        # device's (batch, head) shard, as the attention kernels do
        mesh, spec = MultiHeadAttentionOp._kernel_shard_spec(ctx, b, h)
        mix = mix_impl(weights, t, mesh, spec) if events.enabled() else None
        if events.enabled() and by_head:
            chunks = -(-t // chunk)
            hk, dk = weights["wk"].shape[1:]
            # (the scan kernels run wherever the terms' do: one predicate)
            impl = head_decay_impl(chunk, hk, h, dk, d, mesh, spec)
            events.instant("gdn.scan", layer=name,
                           key_heads=hk, value_heads=h,
                           key_head_dim=dk, head_dim=d,
                           taps=weights["conv_q"].shape[-1],
                           tokens=b * t, chunk=chunk, chunks=chunks,
                           state_bytes=4 * b * chunks * h * d * d,
                           impl=impl, scan=impl, mix=mix)
        elif events.enabled():
            chunks = -(-t // chunk)
            impl = "kernel" if takes_kernel(chunk, d, d) else "plain"
            events.instant("kda.scan", layer=name, heads=h, head_dim=d,
                           taps=weights["conv_q"].shape[-1],
                           tokens=b * t, chunk=chunk, chunks=chunks,
                           state_bytes=4 * b * chunks * h * d * d,
                           impl=impl, scan=impl, mix=mix)

        # The layer is rematerialised whole, and inside it each branch
        # of the projections once more: what it keeps for the backward
        # pass is its input, and while the recurrence's backward runs,
        # the five arrays the recurrence read. The projections,
        # convolutions and gates are a dozen (tokens, H x d) float32
        # arrays, 0.95 GB a layer at 4096 tokens (1.9 GB at 8192); the
        # price is the layer's forward pass run once more, and each
        # branch's twice more. A rematerialised block around the layer
        # would run it a third time (its branches a fourth) only to hand
        # its output on: ``keeps_output_for_block`` has the block keep
        # the output instead (one (tokens, hidden) array, 37.7 MB at
        # 4096 x 2304), so the counts are 2 and 3 inside a block as
        # outside one.
        specs, wrap_mesh = wrap_specs(ctx)

        def layer(x, weights):
            q, k, v, g, beta, gate = self.projections(
                x, weights, mdt, layer=name, specs=specs, mesh=wrap_mesh,
                shard=(mesh, spec))
            with jax.named_scope(scope + ".scan"):
                o, least = gated_delta_rule(q, k, v, g, beta, chunk, mdt,
                                            layer=name, mesh=mesh,
                                            spec=spec)
            y = _rms(o, weights["o_norm"], params.get("eps", 1e-5)) * gate
            return jnp.einsum("bhtd,hde->bte", y.astype(mdt),
                              weights["wo"].astype(mdt),
                              preferred_element_type=jnp.float32), least

        out, least = checkpointed(
            layer, site="kda.layer", layer=name, weights=(1,), specs=specs,
            mesh=wrap_mesh)(x, weights)
        # counters add over layers and steps: the sum of each scan's
        # most negative running log-decay, beside the number of scans
        ctx.count(scope + ".log_decay_min", least)
        ctx.count(scope + ".scans", jnp.float32(1.0))
        return [out.astype(x.dtype)]

    def flops(self, params, in_shapes, out_shapes):
        """By the recurrent form, which no implementation changes: a
        head-token decays the state (d^2), reads it twice (S^T k, S^T q:
        2 d^2 each) and writes it once (2 d^2)."""
        tokens = float(np.prod(in_shapes[0][:-1]))
        e = in_shapes[0][-1]
        h, d, k = params["num_heads"], params["head_dim"], params["taps"]
        r = params.get("gate_rank") or d
        hk = params.get("num_key_heads") or h
        if params.get("decay") == "head":
            proj = e * d * (2 * hk + 3 * h) + 2 * e * h
        else:
            proj = 4 * e * h * d + 2 * (e * r + r * h * d) + e * h
        return tokens * (2.0 * proj + (2 * k + 1) * (2 * hk + h) * d
                         + 7.0 * h * d * d)

    def backward_flops_factor(self):
        return 2.0


@register
class StateSpaceMixerOp(OpDef):
    """A state-space mixer (Mamba-2's form, ``G`` groups of B/C: 1 where
    the parameters name none): ``H`` heads of ``P`` channels, each the
    recurrence of :func:`state_space_scan` over a state of ``P x N``;
    head ``h`` reads the B and C of group ``h // (H / G)``.

      [z | xBC | dt] = x in_proj           H P | H P + 2 G N | H
      xBC = silu(short_conv(xBC; conv_w) + conv_b)     K causal taps
      [x | B | C] = xBC                    x: (T, H, P); B, C: (T, G, N)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            a head
      y = state_space_scan(x, dt, A, B, C) + D x               D a head
      y = RMSNorm(y * silu(z); norm)       the gate BEFORE the norm,
                                           the mean over each group's
                                           H P / G channels
      out = y out_proj

    No bias but the convolution's. The two projections and the
    recurrence's products are at the compute dtype with float32
    accumulation; the taps, softplus, log-decays and their exponentials,
    ``L``, the state and the norm are float32. The recurrence (the
    chunks' terms, by the kernels of ``kernels/state_space.py`` on one
    device at a chunk in whole tiles of 128 and ``H P`` and ``N`` in
    whole lanes, which the published 256, 64 x 64 and 128 are; the scan
    over the chunk states and the product that reads them; not the
    projections) runs under the name scope ``ssm.scan``, the kernels'
    backward too; everything else is plain JAX on XLA, its backward
    autodiff's. Training and evaluation only: there is no decode path
    that carries the state from call to call."""
    op_type = OperatorType.OP_STATE_SPACE_MIXER
    keeps_output_for_block = True   # ``emit``: the layer is one checkpoint

    def infer(self, params, in_shapes, in_dtypes):
        return [(in_shapes[0], in_dtypes[0])]

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        h, p, n, k = (params["num_heads"], params["head_dim"],
                      params["state"], params["taps"])
        inner, uniform = h * p, InitializerType.UNIFORM
        n *= params.get("groups", 1)        # B and C of every group
        return [
            WeightSpec("in_proj", (e, 2 * inner + 2 * n + h), dt),
            # one filter a channel: K taps in, K positions reached
            WeightSpec("conv_w", (inner + 2 * n, k), dt,
                       init_args={"fans": (k, k)}),
            WeightSpec("conv_b", (inner + 2 * n,), dt,
                       InitializerType.ZERO),
            # how fast a state decays: A = exp(A_log) uniform in (1, 16),
            # softplus(dt_bias) log-uniform in (1e-3, 1e-1)
            WeightSpec("dt_bias", (h,), dt, uniform,
                       {"min": math.log(1e-3), "max": math.log(1e-1),
                        "map": "inverse_softplus_of_exp"}),
            WeightSpec("A_log", (h,), dt, uniform,
                       {"min": 1.0, "max": 16.0, "map": "log"}),
            WeightSpec("D", (h,), dt, InitializerType.ONE),
            WeightSpec("norm", (inner,), dt, InitializerType.ONE),
            WeightSpec("out_proj", (inner, e), dt)]

    def emit(self, params, inputs, weights, ctx, name):
        (u,) = inputs
        if getattr(ctx, "kv_mode", None) is not None:
            raise NotImplementedError(
                f"{name}: the state-space mixer has no decode path that "
                f"carries its state beside a KV cache")
        mdt = compute_dtype(ctx, u.dtype)
        f32 = jnp.float32
        h, p, n = params["num_heads"], params["head_dim"], params["state"]
        chunk, inner = int(params["chunk"]), h * p
        groups = params.get("groups", 1)
        b, t = u.shape[:2]
        # the chunks' terms by the kernels where the shapes take them,
        # on one device (under a mesh the plain path, which GSPMD
        # partitions like any other XLA op)
        mesh = getattr(ctx, "mesh", None)
        kernels = mesh is None or mesh.size == 1
        if events.enabled():
            events.instant("ssm.layer", layer=name, heads=h, head_dim=p,
                           state=n, groups=groups, taps=params["taps"],
                           tokens=b * t, chunk=chunk,
                           chunks=-(-t // chunk),
                           impl="kernel" if kernels
                           and ssm_kernels.takes_kernel(chunk, h, p, n,
                                                        groups)
                           else "plain")

        # The layer is rematerialised whole, as the gated delta rule is
        # and for its reasons: what it keeps for the backward pass is
        # its input, not the (tokens, 2 H P + 2 N + H) float32
        # projection and the half dozen (tokens, H P) arrays behind it;
        # a rematerialised block around it keeps its output
        # (``keeps_output_for_block``) and does not run it a third time.
        def layer(u, w):
            zxbcdt = jnp.einsum("bte,ec->btc", u.astype(mdt),
                                w["in_proj"].astype(mdt),
                                preferred_element_type=f32)
            z, xbc, dt = jnp.split(
                zxbcdt, [inner, 2 * inner + 2 * groups * n], -1)
            xbc = jax.nn.silu(short_conv(xbc, w["conv_w"].astype(f32))
                              + w["conv_b"].astype(f32))
            x, bm, cm = jnp.split(xbc, [inner, inner + groups * n], -1)
            if groups > 1:
                bm, cm = (v.reshape(b, t, groups, n) for v in (bm, cm))
            dt = jax.nn.softplus(dt + w["dt_bias"].astype(f32))
            with jax.named_scope("ssm.scan"):
                y, least = state_space_scan(
                    x.reshape(b, t, h, p), dt,
                    -jnp.exp(w["A_log"].astype(f32)), bm, cm, chunk, mdt,
                    layer=name, kernels=kernels)
            # the skip on the channels as they lie, a head's ``D`` along
            # its own: a (T, H, P) view of x or y between the projection
            # and the kernels is a copy of either on the chip (P is 64,
            # half a vector's lanes)
            y = y.reshape(b, t, inner) + jnp.repeat(w["D"].astype(f32), p) * x
            y = y * jax.nn.silu(z)
            # the mean square over a group's channels
            y = _rms(y.reshape(b, t, groups, -1),
                     w["norm"].reshape(groups, -1), params["eps"]
                     ).reshape(b, t, inner)
            return jnp.einsum("btc,ce->bte", y.astype(mdt),
                              w["out_proj"].astype(mdt),
                              preferred_element_type=f32), least

        specs, wrap_mesh = wrap_specs(ctx)
        out, least = checkpointed(
            layer, site="ssm.layer", layer=name, weights=(1,), specs=specs,
            mesh=wrap_mesh)(u, weights)
        # counters add over layers and steps: the sum of each layer's
        # most negative whole-chunk log-decay, beside the number of layers
        ctx.count("ssm.min_chunk_log_decay", least)
        ctx.count("ssm.layers", jnp.float32(1.0))
        return [out.astype(u.dtype)]

    def flops(self, params, in_shapes, out_shapes):
        """By the recurrent form, which no implementation changes: a
        head-token decays the state (P N), writes it (2 P N) and reads
        it (2 P N), and adds the skip."""
        tokens = float(np.prod(in_shapes[0][:-1]))
        e = in_shapes[0][-1]
        h, p, n, k = (params["num_heads"], params["head_dim"],
                      params["state"], params["taps"])
        inner = h * p
        bc = 2 * n * params.get("groups", 1)
        proj = e * (2 * inner + bc + h) + inner * e
        return tokens * (2.0 * proj + (2 * k + 1) * (inner + bc)
                         + 5.0 * inner * n + 2 * inner)

    def backward_flops_factor(self):
        return 2.0


@register
class SelectiveScanMixerOp(OpDef):
    """A selective-scan (Mamba-1) mixer: ``D = inner`` channels, each a
    state of ``N`` entries under :func:`selective_scan`.

      [x | z] = u in_proj                      D | D, no bias
      x  = silu(short_conv(x; conv_w) + conv_b)            K causal taps
      [d | B | C] = x x_proj                   R | N | N, no bias
      dt = softplus(d dt_proj + dt_bias)       the step size through a
                                               LOW-RANK pair, R -> D
      A  = -exp(A_log)                         (N, D): a decay a channel
                                               AND a state entry
      m  = selective_scan(x, dt, A, B, C) + D x
      out = (m * silu(z)) out_proj             the gate, no norm

    An op of its own and not a form of :class:`StateSpaceMixerOp`: no
    weight, no projection's split and no line of the recurrence is
    shared (the step size, B and C come from the CONVOLVED x, the decay
    is no head's, nothing is normed), so a ``form`` would be two ops in
    one ``emit``; what the two share they import (``short_conv``,
    ``checkpointed``).

    ``memory_out``: ``m`` (the scan's output with the skip, BEFORE the
    gate) is a second output, which a later layer reads
    (:meth:`hands_on`: a rematerialised block hands it on beside the
    residual stream). The four projections are at the compute dtype with
    float32 accumulation; the taps, softplus, ``dt A`` and its
    exponential, the state and the products with B and C are float32.
    The recurrence runs under the name scope ``ssm1.scan``: by the
    kernels of ``kernels/selective_scan.py`` on one device at the
    channels in whole blocks of 1,024, a state of whole eights and a
    chunk of whole sublane tiles, which the published 5,120, 16 and 64
    are, their backward too; plain JAX elsewhere. The layer is not
    rematerialised whole (a block around it is); on the plain path the
    scan's chunks are. Training and evaluation only: there is no decode
    path that carries the state from call to call."""
    op_type = OperatorType.OP_SELECTIVE_SCAN_MIXER

    def hands_on(self, params):
        return (1,) if params.get("memory_out") else ()

    def infer(self, params, in_shapes, in_dtypes):
        outs = [(in_shapes[0], in_dtypes[0])]
        if params.get("memory_out"):
            outs.append((tuple(in_shapes[0][:-1]) + (params["inner"],),
                         in_dtypes[0]))
        return outs

    def weights(self, params, in_shapes, in_dtypes):
        e, dt = in_shapes[0][-1], in_dtypes[0]
        d, n, r, k = (params["inner"], params["state"], params["dt_rank"],
                      params["taps"])
        return [
            WeightSpec("in_proj", (e, 2 * d), dt),
            WeightSpec("conv_w", (d, k), dt, init_args={"fans": (k, k)}),
            WeightSpec("conv_b", (d,), dt, InitializerType.ZERO),
            WeightSpec("x_proj", (d, r + 2 * n), dt),
            WeightSpec("dt_proj", (r, d), dt),
            # softplus(dt_bias) log-uniform in (1e-3, 1e-1); A = -(1..N)
            # in every channel (S4D-real), state entries down the rows
            WeightSpec("dt_bias", (d,), dt, InitializerType.UNIFORM,
                       {"min": math.log(1e-3), "max": math.log(1e-1),
                        "map": "inverse_softplus_of_exp"}),
            WeightSpec("A_log", (n, d), dt, InitializerType.CONSTANT,
                       {"rows": "log_count"}),
            WeightSpec("D", (d,), dt, InitializerType.ONE),
            WeightSpec("out_proj", (d, e), dt)]

    def emit(self, params, inputs, weights, ctx, name):
        (u,) = inputs
        if getattr(ctx, "kv_mode", None) is not None:
            raise NotImplementedError(
                f"{name}: the selective-scan mixer has no decode path "
                f"that carries its state beside a KV cache")
        mdt = compute_dtype(ctx, u.dtype)
        f32 = jnp.float32
        d, n, r = params["inner"], params["state"], params["dt_rank"]
        chunk = int(params["chunk"])
        b, t = u.shape[:2]
        memory_out = bool(params.get("memory_out"))
        # the walk by the kernels where the shapes take them, on one
        # device (under a mesh the plain path, which GSPMD partitions
        # like any other XLA op)
        mesh = getattr(ctx, "mesh", None)
        kernels = mesh is None or mesh.size == 1
        if events.enabled():
            events.instant("ssm1.scan", layer=name, channels=d, state=n,
                           dt_rank=r, taps=params["taps"], tokens=b * t,
                           chunk=min(chunk, t), chunks=-(-t // min(chunk, t)),
                           state_bytes=b * n * d * 4,
                           memory_out=memory_out,
                           impl="kernel" if kernels
                           and ssm1_kernels.takes_kernel(min(chunk, t), d, n)
                           else "plain")

        def mm(pattern, x, w):
            return jnp.einsum(pattern, x.astype(mdt), w.astype(mdt),
                              preferred_element_type=f32)

        w = weights
        x, z = jnp.split(mm("bte,ec->btc", u, w["in_proj"]), [d], -1)
        x = jax.nn.silu(short_conv(x, w["conv_w"].astype(f32))
                        + w["conv_b"].astype(f32))
        low, bm, cm = jnp.split(mm("btc,cr->btr", x, w["x_proj"]),
                                [r, r + n], -1)
        dt = jax.nn.softplus(mm("btr,rc->btc", low, w["dt_proj"])
                             + w["dt_bias"].astype(f32))
        with jax.named_scope("ssm1.scan"):
            y, least = selective_scan(x, dt, -jnp.exp(w["A_log"].astype(f32)),
                                      bm, cm, chunk, layer=name,
                                      kernels=kernels)
        memory = y + w["D"].astype(f32) * x
        out = mm("btc,ce->bte", memory * jax.nn.silu(z), w["out_proj"])
        # counters add over layers and steps: the sum of each layer's
        # most negative ``dt A``, beside the number of scans
        ctx.count("ssm1.log_decay_min", least)
        ctx.count("ssm1.scans", jnp.float32(1.0))
        return [out.astype(u.dtype)] \
            + ([memory.astype(u.dtype)] if memory_out else [])

    def flops(self, params, in_shapes, out_shapes):
        """By the recurrent form: a channel-token decays its state (N),
        writes it (2 N) and reads it (2 N), and adds the skip."""
        tokens = float(np.prod(in_shapes[0][:-1]))
        e = in_shapes[0][-1]
        d, n, r, k = (params["inner"], params["state"], params["dt_rank"],
                      params["taps"])
        proj = e * 2 * d + d * (r + 2 * n) + r * d + d * e
        return tokens * (2.0 * proj + (2 * k + 1) * d + 5.0 * d * n + 2 * d)

    def backward_flops_factor(self):
        return 2.0
