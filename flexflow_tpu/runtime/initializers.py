"""Parameter initializers.

Reference parity: ``src/runtime/initializer.cc`` + ``initializer_kernel.cu``
(Glorot/Zero/Constant/Uniform/Normal as GPU tasks) — here pure jax.random,
executed device-side at compile time with per-weight folded keys.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..ffconst import InitializerType


def _fan_in_out(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """Fans read off a weight's shape. A weight whose shape does not say
    them (stacked experts, per-head projections) gives its own as
    ``init_args["fans"] = (fan_in, fan_out)``."""
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv OIHW: fan_in = I*kh*kw, fan_out = O*kh*kw
    receptive = 1
    for s in shape[2:]:
        receptive *= s
    return shape[1] * receptive, shape[0] * receptive


def _mapped(name, draw, xp):
    """``init_args["map"]``: a function of a UNIFORM draw, for a weight
    whose published initialisation is a distribution's image (``xp`` is
    numpy or jax.numpy)."""
    if name is None:
        return draw
    if name == "log":
        return xp.log(draw)
    if name == "inverse_softplus_of_exp":      # softplus(result) = e^draw
        dt = xp.exp(draw)
        return dt + xp.log(-xp.expm1(-dt))
    raise ValueError(f"unknown map {name!r} of a uniform draw")


def _diagonal(args, shape, xp):
    """``init_args["diagonal"]``: added on the diagonal of a square
    matrix drawn NORMAL (a map that starts near a multiple of the
    identity)."""
    if not args.get("diagonal"):
        return 0.0
    return args["diagonal"] * xp.eye(*shape)


def _drawn_columns(args, shape):
    """``init_args["column_repeats"] = r``: a GLOROT_UNIFORM weight draws
    its first ``shape[-1] / r`` columns (limits from the whole shape's
    fans) and repeats them ``r`` times along the last axis, column ``j``
    again at ``j + shape[-1] / r`` and so on. Returns the shape to draw
    and ``np.tile``'s repeats; absent, the whole shape once."""
    r = int(args.get("column_repeats", 1))
    if r < 1 or shape[-1] % r:
        raise ValueError(f"column_repeats {r} of {shape[-1]} columns")
    return (tuple(shape[:-1]) + (shape[-1] // r,),
            (1,) * (len(shape) - 1) + (r,))


def _constant(args, shape, xp):
    """``CONSTANT``: ``init_args["value"]`` everywhere, or with
    ``init_args["rows"] == "log_count"`` the log of a row's number from 1
    (S4D-real: a state entry ``n`` of every channel starts at ``A = -n``)."""
    if args.get("rows") == "log_count":
        rows = xp.log(xp.arange(1, shape[0] + 1, dtype=xp.float32))
        return xp.broadcast_to(rows.reshape((-1,) + (1,) * (len(shape) - 1)),
                               shape)
    return xp.full(shape, args.get("value", 0.0))


def initialize_host(spec, key_ints, np_dtype):
    """Host-side twin of :func:`initialize`: numpy Philox keyed by the
    integer path ``key_ints`` (deterministic across runs/platforms).

    Used for bulk parameter materialization (executor.py): jax's eager
    threefry generates ~50 MB/s per tensor un-jitted and a single jitted
    whole-init program takes minutes to SPMD-compile on a many-device
    mesh, while numpy Philox streams ~1 GB/s — the round-4 north-star
    profile showed 230 s of its 301 s compile in eager init dispatch.
    The reference initializes on-accelerator (initializer_kernel.cu);
    here init is a one-time host cost and the arrays are placed with
    their target shardings in one ``device_put``."""
    import numpy as np
    kind = spec.initializer
    shape = tuple(spec.shape)
    args = spec.init_args
    if kind == InitializerType.ZERO:
        return np.zeros(shape, np_dtype)
    if kind == InitializerType.ONE:
        return np.ones(shape, np_dtype)
    if kind == InitializerType.CONSTANT:
        return np.asarray(_constant(args, shape, np), np_dtype)
    # Philox keys are 2x uint64: word 0 = seed mixed with the path tag,
    # word 1 = the (sub-path, index) pair — all path components are
    # < 2^32 in practice, so the packing is collision-free
    seed, tag, a, b = (tuple(key_ints) + (0, 0, 0, 0))[:4]
    mask = (1 << 64) - 1
    key = np.array([(seed ^ (tag * 0x9E3779B97F4A7C15)) & mask,
                    ((a << 32) ^ (b & 0xFFFFFFFF)) & mask], np.uint64)
    gen = np.random.Generator(np.random.Philox(key=key))
    if kind == InitializerType.UNIFORM:
        lo, hi = args.get("min", -0.05), args.get("max", 0.05)
        return _mapped(args.get("map"), gen.uniform(lo, hi, shape),
                       np).astype(np_dtype)
    if kind == InitializerType.NORMAL:
        mean, std = args.get("mean", 0.0), args.get("stddev", 0.05)
        return (mean + std * gen.standard_normal(shape)
                + _diagonal(args, shape, np)).astype(np_dtype)
    if kind == InitializerType.GLOROT_UNIFORM:
        fan_in, fan_out = args.get("fans") or _fan_in_out(shape)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        drawn, r = _drawn_columns(args, shape)
        w = gen.uniform(-limit, limit, drawn).astype(np_dtype)
        return w if drawn == shape else np.tile(w, r)
    raise ValueError(kind)


def initialize(spec, rng, jnp_dtype):
    """Materialize one WeightSpec."""
    kind = spec.initializer
    shape = spec.shape
    args = spec.init_args
    if kind == InitializerType.ZERO:
        return jnp.zeros(shape, jnp_dtype)
    if kind == InitializerType.ONE:
        return jnp.ones(shape, jnp_dtype)
    if kind == InitializerType.CONSTANT:
        return jnp.asarray(_constant(args, shape, jnp), jnp_dtype)
    if kind == InitializerType.UNIFORM:
        lo, hi = args.get("min", -0.05), args.get("max", 0.05)
        return _mapped(args.get("map"),
                       jax.random.uniform(rng, shape, jnp_dtype, lo, hi),
                       jnp)
    if kind == InitializerType.NORMAL:
        mean, std = args.get("mean", 0.0), args.get("stddev", 0.05)
        return mean + std * jax.random.normal(rng, shape, jnp_dtype) \
            + _diagonal(args, shape, jnp)
    if kind == InitializerType.GLOROT_UNIFORM:
        fan_in, fan_out = args.get("fans") or _fan_in_out(shape)
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        drawn, r = _drawn_columns(args, shape)
        w = jax.random.uniform(rng, drawn, jnp_dtype, -limit, limit)
        return w if drawn == tuple(shape) else jnp.tile(w, r)
    raise ValueError(kind)
