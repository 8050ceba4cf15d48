"""The residual streams' mixes as Pallas TPU kernels (forward + backward).

``ops/hyper_ops.py`` keeps a token's residual as ``n`` streams of ``C``
channels and reads and writes them around every sub-layer through maps
of that token's own. XLA's version of the passes over the streams (the
module's plain functions, the fallback and the tests' oracle) moves the
stream tensor some thirty times a sub-layer, forward and backward, and
turns its layout between them (PERF.md section 6, PR 40). Here a grid
step takes a tile of ``T`` tokens' whole ``n C`` entries into VMEM and
everything a node needs of them is taken from that one read.

Every kernel sees the streams stream-major, ``(n, tokens, C)``: a
stream is a ``(tokens, C)`` matrix of its own under the plain ``(8,
128)`` tiling (``C`` in whole lanes, nothing padded), a tile is the
block ``(n, T, C)``, stream ``i`` is ``ref[i]``, and the product with
``phi`` is the sum of the streams' ``(T, C) . (C, K)``. The graph's
tensor stays ``(b, s, n, C)``; the view is a transpose and a reshape
around each call, and XLA, which is free to lay a value inside the step
as it likes, gives the four-axis array the layout ``{3,1,2,0:T(8,128)}``
in which both are bitcasts: nothing turns the streams between two
nodes, at a rematerialised block's edge, where the embedding is copied
to them or where they are summed. (Not ``(b s, n C)`` with the streams
as lane ranges: in row-major that is the graph's own bytes, but no
TILED layout of the four-axis array is, and ``jax.checkpoint`` holds a
block's entry and incoming cotangent in the graph's shape, so every
such edge would turn the tensor four times; PERF.md section 6, PR 42.)
``phi`` is held transposed, ``(KP, n C)`` with its
``K = n (n + 2)`` columns as rows (``KP`` = 32 for 24: as ``(n C, K)``
its columns would be padded to 128 lanes, four times the bytes).

Four kernels, two ``custom_vjp`` functions:

``hyper_connection_pre_fwd`` reads ``X`` once: the sum of squares, ``x
phi``, ``Hpre = sigmoid(a_pre (x phi) / rms + b_pre)`` and ``u = Hpre
X``. Out: ``u`` and a token's ``KP`` statistics (the raw products and, in
column ``K``, the norm's reciprocal): the other two maps are made from
them in XLA, tokens-last, and they are the residuals with ``X``.
``hyper_connection_post_fwd`` reads ``X`` and ``y`` and writes ``Hres X +
Hpost^T y``. ``hyper_connection_post_bwd`` reads the new streams'
cotangent, ``X`` and ``y`` and writes ``Hres^T dX'``, ``dy`` and the
``n + n n`` sums a token that are the maps' cotangents.
``hyper_connection_pre_bwd`` reads ``X``, ``du``, the statistics'
cotangent (XLA's backward of the gates and the Sinkhorn scan) and what
``post`` left for ``X`` (the ``pre`` function's third output IS its
input, so that ``X`` has one consumer), forms ``dHpre`` from the tile and
writes the WHOLE ``dX`` once; ``dphi`` accumulates in its output block
over the token axis, which is therefore sequential.

All of it float32; the three products with ``phi`` on float32 operands
at ``Precision.HIGHEST``.
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
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: Mosaic's scoped-VMEM limit for these kernels (a v5e core has 128 MiB;
#: the default of 16 MiB holds 128 tokens of one operand at 4 x 3584) and
#: what a grid step's working set may count of it (:func:`vmem_bytes`)
VMEM_LIMIT = 100 * 1024 * 1024
VMEM_BUDGET = 72 * 1024 * 1024
TILES = (256, 128, 64, 32, 16, 8)
KERNELS = ("pre_fwd", "post_fwd", "post_bwd", "pre_bwd")


def stats_width(n: int) -> int:
    """``KP``: a token's ``n (n + 2)`` products and the norm's
    reciprocal, in whole sublanes (the rows of ``phi`` transposed)."""
    return -(-(n * (n + 2) + 1) // SUBLANES) * SUBLANES


def vmem_bytes(kernel: str, n: int, c: int, tile: int) -> int:
    """Working set of one grid step: the double-buffered blocks of the
    operands and outputs (a block of fewer than 128 columns fills whole
    lanes), the (tile, C) float32 values the step holds at once, and for
    ``pre`` both buffers of ``phi`` (and of ``dphi``)."""
    big, one, small = n * c * 4, c * 4, LANES * 4
    phi = 2 * stats_width(n) * n * c * 4
    token = {"pre_fwd": 2 * (big + one + small) + 3 * one,
             "post_fwd": 2 * (2 * big + one + small) + 3 * one,
             "post_bwd": 2 * (3 * big + 2 * one + 2 * small) + 4 * one,
             "pre_bwd": 2 * (3 * big + one + 3 * small) + 4 * one}[kernel]
    fixed = {"pre_fwd": phi, "pre_bwd": 2 * phi}.get(kernel, 0)
    return tile * token + fixed


def tile_tokens(kernel: str, n: int, c: int, tokens: int) -> int:
    """Tokens a grid step of ``kernel`` takes: the largest of ``TILES``
    (powers of two, so that a stage's two tiles divide one padded token
    count) whose working set is under ``VMEM_BUDGET``, and no more than
    covers the tokens there are. 0: not even eight fit."""
    fits = [t for t in TILES if vmem_bytes(kernel, n, c, t) <= VMEM_BUDGET]
    if not fits:
        return 0
    return min([fits[0]] + [t for t in TILES if t >= tokens])


def takes_kernel(n: int, c: int, tokens: int) -> bool:
    """Whether these shapes run the kernels: the channels in whole lanes
    and eight tokens' streams, with what each kernel holds beside them,
    inside the VMEM budget."""
    return (c % LANES == 0 and n > 0 and tokens > 0
            and all(tile_tokens(k, n, c, tokens) for k in KERNELS))


# ---------------------------------------------------------------------------
# the kernels: refs of a tile of tokens, float32
# ---------------------------------------------------------------------------
def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _row_sum(x):
    return jnp.sum(x, -1, keepdims=True)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=F32)


def _hpre(raw, r, gate_ref):
    """(T, KP): ``Hpre`` in the first ``n`` lanes, from the raw products
    and the norm's reciprocal; ``gate_ref`` rows ``a_pre`` and ``b_pre``
    along those lanes, zero beyond."""
    return jax.nn.sigmoid(raw * r * gate_ref[0:1, :] + gate_ref[1:2, :])


def _pre_fwd_kernel(x_ref, phi_ref, gate_ref, u_ref, stats_ref, *, n, c,
                    eps):
    k, t = n * (n + 2), x_ref.shape[1]
    raw = jnp.zeros((t, phi_ref.shape[0]), F32)
    ss = jnp.zeros((t, 1), F32)
    for i in range(n):
        xi = x_ref[i]
        raw = raw + _dot(xi, phi_ref[:, i * c:(i + 1) * c], ((1,), (1,)))
        ss = ss + _row_sum(xi * xi)
    r = jax.lax.rsqrt(ss / (n * c) + eps)
    stats_ref[...] = jnp.where(_lane(raw.shape) == k, r, raw)
    hp = _hpre(raw, r, gate_ref)
    u = hp[:, 0:1] * x_ref[0]
    for i in range(1, n):
        u = u + hp[:, i:i + 1] * x_ref[i]
    u_ref[...] = u


def _post_fwd_kernel(x_ref, y_ref, maps_ref, o_ref, *, n):
    maps, y = maps_ref[...], y_ref[...]
    for i in range(n):
        acc = maps[:, i:i + 1] * y
        for j in range(n):
            at = n + i * n + j
            acc = acc + maps[:, at:at + 1] * x_ref[j]
        o_ref[i] = acc


def _post_bwd_kernel(g_ref, x_ref, y_ref, maps_ref, dx_ref, dy_ref,
                     dmaps_ref, *, n):
    maps, y = maps_ref[...], y_ref[...]
    lane = _lane(maps.shape)
    dmaps = jnp.zeros(maps.shape, F32)
    dy = jnp.zeros(y.shape, F32)
    for i in range(n):
        gi = g_ref[i]
        dy = dy + maps[:, i:i + 1] * gi
        dmaps = jnp.where(lane == i, _row_sum(gi * y), dmaps)
        for j in range(n):
            dmaps = jnp.where(lane == n + i * n + j,
                              _row_sum(gi * x_ref[j]), dmaps)
    dy_ref[...] = dy
    dmaps_ref[...] = dmaps
    for j in range(n):
        acc = jnp.zeros(y.shape, F32)
        for i in range(n):
            at = n + i * n + j
            acc = acc + maps[:, at:at + 1] * g_ref[i]
        dx_ref[j] = acc


def _pre_bwd_kernel(x_ref, du_ref, gx_ref, stats_ref, dstats_ref, phi_ref,
                    gate_ref, dx_ref, dphi_ref, dz_ref, *, n, c):
    k = n * (n + 2)
    stats, dstats, du = stats_ref[...], dstats_ref[...], du_ref[...]
    lane = _lane(stats.shape)
    r = stats[:, k:k + 1]
    raw = jnp.where(lane < k, stats, 0.0)
    hp = _hpre(raw, r, gate_ref)
    dhp = jnp.zeros(stats.shape, F32)
    for i in range(n):
        dhp = jnp.where(lane == i, _row_sum(du * x_ref[i]), dhp)
    dz = dhp * hp * (1.0 - hp)          # zero beyond the first n lanes
    dt = dz * gate_ref[0:1, :]
    draw = jnp.where(lane < k, dstats, 0.0) + dt * r
    # the norm: r = (sum x^2 / nC + eps)^-1/2, dr/dx = -r^3 x / nC
    dr = dstats[:, k:k + 1] + _row_sum(dt * raw)
    coef = dr * (r * r * r) * (-1.0 / (n * c))

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, F32)

    for i in range(n):
        at = slice(i * c, (i + 1) * c)
        xi = x_ref[i]
        dx_ref[i] = (hp[:, i:i + 1] * du
                     + _dot(draw, phi_ref[:, at], ((1,), (0,)))
                     + coef * xi + gx_ref[i])
        dphi_ref[:, at] += _dot(draw, xi, ((0,), (0,)))
    dz_ref[...] = dz


# ---------------------------------------------------------------------------
# the calls: (n, tokens, C) streams, tokens in whole tiles
# ---------------------------------------------------------------------------
def _rows(tile, width):
    return pl.BlockSpec((tile, width), lambda i: (i, 0))


def _streams(n, tile, c):
    """A tile of tokens of every stream of an (n, tokens, C) operand."""
    return pl.BlockSpec((n, tile, c), lambda i: (0, i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=VMEM_LIMIT)


def _shape(*shape):
    return jax.ShapeDtypeStruct(shape, F32)


_STATIC = ("n", "tile", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("eps",), inline=True)
def _pre_fwd_call(x, phi_t, gate, n, eps, tile, interpret):
    _, tokens, c = x.shape
    nc, kp = n * c, phi_t.shape[0]
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, c=c, eps=eps),
        grid=(tokens // tile,),
        in_specs=[_streams(n, tile, c), _whole((kp, nc)),
                  _whole(gate.shape)],
        out_specs=[_rows(tile, c), _rows(tile, kp)],
        out_shape=[_shape(tokens, c), _shape(tokens, kp)],
        compiler_params=_params("parallel"), interpret=interpret,
        name="hyper_connection_pre_fwd")(x, phi_t, gate)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _pre_bwd_call(x, du, gx, stats, dstats, phi_t, gate, n, tile,
                  interpret):
    _, tokens, c = x.shape
    nc, kp = n * c, phi_t.shape[0]
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, c=c),
        grid=(tokens // tile,),
        in_specs=[_streams(n, tile, c), _rows(tile, c),
                  _streams(n, tile, c), _rows(tile, kp), _rows(tile, kp),
                  _whole((kp, nc)), _whole(gate.shape)],
        out_specs=[_streams(n, tile, c), _whole((kp, nc)),
                   _rows(tile, kp)],
        out_shape=[_shape(n, tokens, c), _shape(kp, nc),
                   _shape(tokens, kp)],
        # dX is written where what ``post`` left for X was read, a
        # tile's rows after that tile's were fetched; dphi is summed
        # over the tiles in its one output block
        input_output_aliases={2: 0},
        compiler_params=_params("arbitrary"), interpret=interpret,
        name="hyper_connection_pre_bwd")(x, du, gx, stats, dstats, phi_t,
                                         gate)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _post_fwd_call(x, y, maps, n, tile, interpret):
    _, tokens, c = x.shape
    m = maps.shape[1]
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n),
        grid=(tokens // tile,),
        in_specs=[_streams(n, tile, c), _rows(tile, c), _rows(tile, m)],
        out_specs=_streams(n, tile, c), out_shape=_shape(n, tokens, c),
        compiler_params=_params("parallel"), interpret=interpret,
        name="hyper_connection_post_fwd")(x, y, maps)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _post_bwd_call(g, x, y, maps, n, tile, interpret):
    _, tokens, c = x.shape
    m = maps.shape[1]
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=(tokens // tile,),
        in_specs=[_streams(n, tile, c), _streams(n, tile, c),
                  _rows(tile, c), _rows(tile, m)],
        out_specs=[_streams(n, tile, c), _rows(tile, c), _rows(tile, m)],
        out_shape=[_shape(n, tokens, c), _shape(tokens, c),
                   _shape(tokens, m)],
        # the streams' cotangent over the new streams' own, tile by tile
        input_output_aliases={0: 0},
        compiler_params=_params("parallel"), interpret=interpret,
        name="hyper_connection_post_bwd")(g, x, y, maps)


def _note(kernel, layer, x, n, tile):
    """One ``mhc.kernel`` instant per emitted call, at trace time."""
    if events.enabled():
        _, tokens, c = x.shape
        events.instant("mhc.kernel", kernel=kernel, layer=layer, tile=tile,
                       tokens=tokens, grid_steps=tokens // tile,
                       vmem_bytes=vmem_bytes(kernel, n, c, tile))


def _padded(x, tile):
    """Tokens (the axis before the last) to whole tiles: a padded
    token's streams, output and cotangents are zeros, and it adds
    nothing to ``dphi``."""
    pad = -x.shape[-2] % tile
    if not pad:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)))


def _stream_major(x):
    """(b, s, n, C) -> (n, b s, C), the kernels' view; with
    :func:`_token_major` around a call XLA is free to lay the four-axis
    array stream-major itself, and then neither is a copy."""
    b, s, n, c = x.shape
    return jnp.moveaxis(x, 2, 0).reshape(n, b * s, c)


def _token_major(x, b, s):
    """(n, tokens >= b s, C) -> (b, s, n, C)."""
    n, _, c = x.shape
    return jnp.moveaxis(x[:, :b * s].reshape(n, b, s, c), 0, 2)


# ``pre``: (x, phi_t, gate) -> (u, stats, x)
def _noted_pre(x, phi_t, gate, n, eps, tiles, layer, interpret):
    _note("pre_fwd", layer, x, n, tiles[0])
    u, stats = _pre_fwd_call(x, phi_t, gate, n, eps, tiles[0], interpret)
    return u, stats, x


_pre = jax.custom_vjp(_noted_pre, nondiff_argnums=(3, 4, 5, 6, 7))


def _pre_fwd_rule(x, phi_t, gate, *static):
    out = _noted_pre(x, phi_t, gate, *static)
    return out, (x, phi_t, gate, out[1])


def _pre_bwd_rule(n, eps, tiles, layer, interpret, res, cts):
    x, phi_t, gate, stats = res
    du, dstats, gx = cts
    _note("pre_bwd", layer, x, n, tiles[1])
    dx, dphi_t, dz = _pre_bwd_call(x, du, gx, stats, dstats, phi_t, gate,
                                   n, tiles[1], interpret)
    # a_pre and b_pre along the lanes: Hpre~ = a (raw r) + b
    k = n * (n + 2)
    t = jnp.where(_lane(stats.shape) < k, stats, 0.0) * stats[:, k:k + 1]
    dgate = jnp.stack([jnp.sum(dz * t, 0), jnp.sum(dz, 0)])
    return dx, dphi_t, dgate


_pre.defvjp(_pre_fwd_rule, _pre_bwd_rule)


# ``post``: (x, y, maps) -> the new streams
def _noted_post(x, y, maps, n, tiles, layer, interpret):
    _note("post_fwd", layer, x, n, tiles[0])
    return _post_fwd_call(x, y, maps, n, tiles[0], interpret)


_post = jax.custom_vjp(_noted_post, nondiff_argnums=(3, 4, 5, 6))


def _post_fwd_rule(x, y, maps, *static):
    return _noted_post(x, y, maps, *static), (x, y, maps)


def _post_bwd_rule(n, tiles, layer, interpret, res, g):
    x, y, maps = res
    _note("post_bwd", layer, x, n, tiles[1])
    return tuple(_post_bwd_call(g, x, y, maps, n, tiles[1], interpret))


_post.defvjp(_post_fwd_rule, _post_bwd_rule)


def _token_axes(spec):
    """The batch and sequence entries of ``spec``, the only axes a node
    is sharded by."""
    return (tuple(spec or ()) + (None, None))[:2]


def _tiles(stage, n, c, tokens):
    """(forward, backward) tiles of one stage, and the rows both divide
    (the larger: both are powers of two)."""
    fwd = tile_tokens(f"{stage}_fwd", n, c, tokens)
    bwd = tile_tokens(f"{stage}_bwd", n, c, tokens)
    return (fwd, bwd), max(fwd, bwd)


def pre_operands(phi, a_pre, b_pre):
    """The ``pre`` kernels' view of that node's weights: ``phi`` (n C,
    K) transposed and padded with zero rows to (KP, n C), and the gate
    (2, KP): ``a_pre`` and ``b_pre`` (n,) along the first ``n`` lanes,
    zero beyond."""
    n = b_pre.shape[0]
    kp = stats_width(n)
    phi_t = jnp.pad(phi.astype(F32).T, ((0, kp - phi.shape[1]), (0, 0)))
    gate = jnp.stack([jnp.full((n,), a_pre, F32), b_pre.astype(F32)])
    return phi_t, jnp.pad(gate, ((0, 0), (0, kp - n)))


def read_streams(x, phi_t, gate, eps, *, layer=None, interpret=None,
                 mesh=None, spec=None):
    """The ``pre`` node's passes over the streams, by the kernels.
    ``x``: (b, s, n, C) float32; ``phi_t`` (KP, n C) and ``gate`` (2,
    KP) as :func:`pre_operands` makes them.
    Returns ``u = Hpre X`` (b, s, C), a token's statistics (b, s, KP):
    ``x phi`` before the norm in the first ``n (n + 2)`` columns and
    ``rsqrt(mean(x^2) + eps)`` in the next; and ``x`` itself, whose
    cotangent the backward kernel adds into the one ``dX`` it writes.

    ``mesh`` / ``spec`` as :func:`flash_attention` takes them: under a
    mesh of more than one device the calls run under ``shard_map`` over
    the batch and sequence entries of ``spec``, every token being a row
    of its own; ``dphi`` is then summed over the shards by the
    ``shard_map``'s own transpose."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bs = _token_axes(spec)
        local = functools.partial(read_streams, eps=eps, layer=layer,
                                  interpret=interpret)
        # check_vma off: pallas_call outputs carry no varying-axes info
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*bs, None, None), P(), P()),
            out_specs=(P(*bs, None), P(*bs, None), P(*bs, None, None)),
            check_vma=False)(x, phi_t, gate)
    b, s, n, c = x.shape
    tiles, rows = _tiles("pre", n, c, b * s)
    u, stats, out = _pre(_padded(_stream_major(x), rows), phi_t, gate, n,
                         float(eps), tiles, layer, bool(interpret))
    return (u[:b * s].reshape(b, s, c), stats[:b * s].reshape(b, s, -1),
            _token_major(out, b, s))


def write_streams(x, y, maps, *, layer=None, interpret=None, mesh=None,
                  spec=None):
    """The ``post`` node's pass, by the kernels: ``Hres X + Hpost^T y``
    from ``x`` (b, s, n, C), ``y`` (b, s, C) and the maps (b, s, n + n
    n), ``[Hpost ; Hres]`` row-major, float32. ``mesh`` / ``spec`` as
    :func:`read_streams`."""
    if interpret is None:
        interpret = pallas_interpret()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        bs = _token_axes(spec)
        local = functools.partial(write_streams, layer=layer,
                                  interpret=interpret)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*bs, None, None), P(*bs, None), P(*bs, None)),
            out_specs=P(*bs, None, None), check_vma=False)(x, y, maps)
    b, s, n, c = x.shape
    tiles, rows = _tiles("post", n, c, b * s)

    def flat(a):
        return _padded(a.reshape(b * s, -1), rows)
    out = _post(_padded(_stream_major(x), rows), flat(y), flat(maps), n,
                tiles, layer, bool(interpret))
    return _token_major(out, b, s)
