"""Sequence/context-parallel attention: ring attention.

The reference has NO sequence parallelism (SURVEY.md §5: "no ring
attention, no blockwise, no Ulysses") — this module is the beyond-reference
capability the rebuild makes first-class.

:func:`ring_attention` — blockwise attention with K/V chunks rotating
around the mesh axis via ``lax.ppermute`` (ICI neighbor exchange), log-
sum-exp merging of per-chunk partial results, and a custom VJP that runs
a second ring pass rotating (k, v, dk, dv) together so every device
accumulates gradient contributions for every chunk. Peak memory per
device stays O(seq/N · seq/N) and communication rides the ICI ring.

Written to be used inside ``shard_map`` over a mesh axis that shards the
sequence dimension.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import NEG_INF


def _chunk_attn(q, k, v, sm_scale, mode):
    """Partial attention of local q against one k/v chunk.

    mode: 0 = full (all keys visible), 1 = causal diagonal, 2 = skip.
    Returns (o_unnormalized? no — normalized o, lse) in f32.
    q: (b, h, sq, d); k/v: (b, h, sc, d).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    sq, sc = s.shape[-2], s.shape[-1]
    if mode == 1:
        i = jax.lax.broadcasted_iota(jnp.int32, (sq, sc), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (sq, sc), 1)
        s = jnp.where(j <= i, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = m[..., 0] + jnp.log(l_safe[..., 0])       # (b, h, sq)
    return o / l_safe, lse


def _merge(o_acc, lse_acc, o_new, lse_new):
    """Log-sum-exp merge of two normalized partial attention results."""
    lse_max = jnp.maximum(lse_acc, lse_new)
    a = jnp.exp(lse_acc - lse_max)
    b = jnp.exp(lse_new - lse_max)
    denom = a + b
    lse_out = lse_max + jnp.log(denom)
    w_a = (a / denom)[..., None]
    w_b = (b / denom)[..., None]
    return o_acc * w_a + o_new * w_b, lse_out


def _ring_fwd_pass(q, k, v, axis_name, causal, sm_scale):
    """One full ring rotation computing (o, lse); everything f32 inside."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    o = jnp.zeros((b, h, sq, d), jnp.float32)
    lse = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    kc, vc = k, v
    for r in range(n):
        src = (idx - r) % n               # whose chunk we hold this step
        if causal:
            # src < idx: fully visible; src == idx: diagonal; src > idx: skip
            def full_case(args):
                qq, kk, vv = args
                return _chunk_attn(qq, kk, vv, sm_scale, 0)

            def diag_case(args):
                qq, kk, vv = args
                return _chunk_attn(qq, kk, vv, sm_scale, 1)

            def skip_case(args):
                # zeros derived from the inputs so the branch output's
                # device-varying type matches the compute branches
                qq, _, _ = args
                z = (qq * 0).astype(jnp.float32)
                return z, jnp.sum(z, axis=-1) + NEG_INF

            branch = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
            o_c, lse_c = jax.lax.switch(
                branch, [full_case, diag_case, skip_case], (q, kc, vc))
        else:
            o_c, lse_c = _chunk_attn(q, kc, vc, sm_scale, 0)
        o, lse = _merge(o, lse, o_c, lse_c)
        if r != n - 1:
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
    return o, lse


def _chunk_grads(q, k, v, do, lse, delta, sm_scale, mode):
    """Per-chunk flash-style backward math (recompute p from lse).

    Returns (dq, dk, dv) in f32. mode as in _chunk_attn."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    sq, sc = s.shape[-2], s.shape[-1]
    if mode == 1:
        i = jax.lax.broadcasted_iota(jnp.int32, (sq, sc), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (sq, sc), 1)
        s = jnp.where(j <= i, s, NEG_INF)
    p = jnp.exp(s - lse[..., None])                   # (b,h,sq,sc)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_core(q, k, v, axis_name, causal, sm_scale):
    o, _ = _ring_fwd_pass(q, k, v, axis_name, causal, sm_scale)
    return o.astype(q.dtype)


def _ring_core_fwd(q, k, v, axis_name, causal, sm_scale):
    o, lse = _ring_fwd_pass(q, k, v, axis_name, causal, sm_scale)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _ring_core_bwd(axis_name, causal, sm_scale, res, do):
    q, k, v, o, lse = res
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o, axis=-1)                # (b, h, sq)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    kc, vc, dkc, dvc = k, v, dk, dv
    for r in range(n):
        src = (idx - r) % n
        if causal:
            def full_case(args):
                return _chunk_grads(*args, sm_scale, 0)

            def diag_case(args):
                return _chunk_grads(*args, sm_scale, 1)

            def skip_case(args):
                qq, kk, vv, *_ = args
                return ((qq * 0).astype(jnp.float32),
                        (kk * 0).astype(jnp.float32),
                        (vv * 0).astype(jnp.float32))

            branch = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
            dq_c, dk_c, dv_c = jax.lax.switch(
                branch, [full_case, diag_case, skip_case],
                (q, kc, vc, do32, lse, delta))
        else:
            dq_c, dk_c, dv_c = _chunk_grads(q, kc, vc, do32, lse, delta,
                                            sm_scale, 0)
        dq = dq + dq_c
        dkc = dkc + dk_c
        dvc = dvc + dv_c
        # rotate k/v AND their gradient accumulators together; after n
        # rotations every accumulator is back on its home device having
        # collected every device's contribution
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
    return dq.astype(q.dtype), dkc.astype(k.dtype), dvc.astype(v.dtype)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Ring attention over a sequence-sharded mesh axis.

    Call inside ``shard_map``: q/k/v are the LOCAL sequence chunks
    (b, h, seq/N, d) and ``axis_name`` the mesh axis sharding the sequence.
    Differentiable; causal masking respects global positions (chunks are
    contiguous slices in axis order)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring_core(q, k, v, axis_name, causal, sm_scale)
