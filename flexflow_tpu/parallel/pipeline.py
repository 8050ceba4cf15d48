"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference only reserves an enum/task ids for pipelining
(``OP_PIPELINE``, ``ffconst.h:159``; ``PIPELINE_*_TASK_ID``,
``model.h:190-192``) — no implementation exists (SURVEY.md §2.6). This
module supplies the real thing, TPU-style: stages are a mesh axis ("pp"),
stage parameters are stacked on a leading stage dim sharded over that axis,
and the schedule is a ``lax.scan`` whose per-step activation hand-off is a
``ppermute`` to the next stage — XLA lowers it to neighbor collective-
permutes over ICI. Reverse-mode AD through the scan + ppermute gives the
backward pipeline for free (cotangents flow stage S-1 → 0 through the
transposed permutes), so one ``jax.grad`` of the pipelined loss is a full
1F1B-equivalent-work backward schedule.

Constraints (the standard SPMD-pipeline shape): all stages run the same
``stage_fn`` with shape-preserving activations (e.g. transformer blocks);
embedding/head run outside the pipelined region.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def _squeeze_stage(params):
    """Drop the local (length-1) leading stage dim of each leaf."""
    return jax.tree.map(lambda x: x[0], params)


def gpipe(stage_fn: Callable[..., Any], axis_name: str,
          n_microbatches: int, with_step_arg: bool = False,
          n_chunks: int = 1):
    """Build the pipelined apply for use INSIDE shard_map over `axis_name`.

    stage_fn(stage_params, x) -> y with y.shape == x.shape.
    With ``with_step_arg``, stage_fn(stage_params, x, t) also receives the
    schedule step t (traced int32) — used e.g. to derive per-microbatch
    dropout rng inside a pipelined region.

    Returned fn(stacked_params_local, xs) where:
      - stacked_params_local: pytree whose leaves have local shape
        (1, ...) — this stage's slice of the (S, ...) stacked params —
        or (v, 1, ...) with ``n_chunks = v > 1`` (see below);
      - xs: (M, mb, ...) microbatched input (replicated across stages);
    returns (M, mb, ...) outputs of the final stage (replicated).

    Schedule, ``n_chunks == 1`` (GPipe): T = M + S - 1 steps; at step t
    stage s computes microbatch t - s (bubble steps compute masked
    garbage that receives no gradient).

    Schedule, ``n_chunks = v > 1`` (interleaved / circular, the
    Megatron-interleaved bubble reduction): the block stack is split into
    v*S chunks; device s owns chunks {s, S+s, ..., (v-1)S+s} and the
    activation ring wraps S-1 -> 0, so each microbatch circles the ring v
    times. T = M*v + S - 1 steps and the bubble fraction drops from
    (S-1)/M to (S-1)/(M*v). stage_fn receives ONE chunk's params per
    step. Requires M % S == 0 (round-robin microbatch rotation).
    """
    v = n_chunks

    def apply(stacked_params_local, xs):
        S = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        M = n_microbatches
        if v == 1:
            params = _squeeze_stage(stacked_params_local)
            # neighbor hand-off, no wraparound: stage s -> s+1
            perm = [(i, i + 1) for i in range(S - 1)]
        else:
            # local leaves are (v, 1, ...): drop the sharded stage dim
            params = jax.tree.map(lambda x: x[:, 0], stacked_params_local)
            perm = [(i, (i + 1) % S) for i in range(S)]  # ring

        outputs0 = jnp.zeros_like(xs)
        state0 = jnp.zeros_like(xs[0])

        def body(carry, t):
            state, outputs = carry
            # local clock: how many chunk-computations this device has
            # started. chunk slot k and microbatch m follow the circular
            # round-robin (v == 1 reduces to m = u, k = 0).
            u = jnp.clip(t - stage, 0, M * v - 1)
            k = (u // S) % v
            m = jnp.clip((u % S) + S * (u // (S * v)), 0, M - 1)
            if v == 1:
                chunk_params = params
            else:
                chunk_params = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, k, 0,
                                                       keepdims=False),
                    params)
            # stage 0 pulls a fresh microbatch on its first chunk; all
            # other (stage, chunk) slots consume the handed-off activation
            mb_t = lax.dynamic_index_in_dim(xs, m, 0, keepdims=False)
            x_in = jnp.where(jnp.logical_and(stage == 0, k == 0),
                             mb_t, state)
            y = stage_fn(chunk_params, x_in, t) if with_step_arg \
                else stage_fn(chunk_params, x_in)
            # the last chunk of the last stage finishes microbatch m
            out_idx = t - stage
            valid = jnp.logical_and(
                jnp.logical_and(stage == S - 1, k == v - 1),
                jnp.logical_and(out_idx >= 0, out_idx < M * v))
            cur = lax.dynamic_index_in_dim(outputs, m, 0, keepdims=False)
            upd = jnp.where(valid, y, cur)
            outputs = lax.dynamic_update_index_in_dim(outputs, upd, m, 0)
            state = lax.ppermute(y, axis_name, perm)
            return (state, outputs), None

        (_, outputs), _ = lax.scan(body, (state0, outputs0),
                                   jnp.arange(M * v + S - 1))
        # broadcast final-stage outputs to every stage (masked psum)
        outputs = lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            axis_name)
        return outputs

    return apply


class PipelinedBlocks:
    """High-level dp×pp runner for a stack of identical blocks.

    Wraps ``n_stages`` groups of blocks: stage parameters are stacked on a
    leading dim and placed with ``NamedSharding(P('pp', ...))``; input
    batches are split into microbatches; the pipelined apply runs under
    ``shard_map`` over a (dp, pp) mesh and is differentiable end-to-end.
    """

    def __init__(self, mesh: Mesh, stage_fn, n_stages: int,
                 n_microbatches: int, dp_axis: str = "dp",
                 pp_axis: str = "pp", n_chunks: int = 1):
        if pp_axis not in mesh.axis_names:
            raise ValueError(f"pipeline axis {pp_axis!r} is not a mesh "
                             f"axis ({mesh.axis_names})")
        pp_size = mesh.shape[pp_axis]
        if n_stages != pp_size:
            raise ValueError(
                f"n_stages ({n_stages}) must equal the '{pp_axis}' "
                f"axis size ({pp_size}): one stage per pipeline rank")
        if n_chunks > 1 and n_microbatches % n_stages != 0:
            raise ValueError(
                f"interleaved schedule needs M % S == 0, got "
                f"M={n_microbatches} S={n_stages}")
        self.mesh = mesh
        self.stage_fn = stage_fn
        self.n_stages = n_stages
        self.n_microbatches = n_microbatches
        self.n_chunks = n_chunks
        self.dp_axis = dp_axis
        self.pp_axis = pp_axis

    def _pp_lead(self):
        return (self.pp_axis,) if self.n_chunks == 1 \
            else (None, self.pp_axis)

    def shard_params(self, stacked_params):
        """Place stacked params: (S, ...) with the stage dim over the pp
        axis, or (v, S, ...) for the interleaved schedule ([k, s] is
        global chunk s + k*S, see ``gpipe(n_chunks=v)``)."""
        lead = self._pp_lead()

        def put(x):
            spec = P(*lead, *([None] * (x.ndim - len(lead))))
            return jax.device_put(x, NamedSharding(self.mesh, spec))
        return jax.tree.map(put, stacked_params)

    def microbatch(self, x):
        """(B, ...) -> (M, B/M, ...)"""
        M = self.n_microbatches
        if x.shape[0] % M != 0:
            raise ValueError(f"batch {x.shape} not divisible into {M} "
                             f"microbatches")
        return x.reshape((M, x.shape[0] // M) + x.shape[1:])

    def apply(self, stacked_params, x):
        """Differentiable pipelined forward of the block stack.
        x: (B, ...) full batch (dp-sharded on the batch dim outside)."""
        xs = self.microbatch(x)
        engine = gpipe(self.stage_fn, self.pp_axis, self.n_microbatches,
                       n_chunks=self.n_chunks)
        lead = self._pp_lead()
        in_param_spec = jax.tree.map(
            lambda v: P(*lead, *([None] * (v.ndim - len(lead)))),
            stacked_params)
        dp = self.dp_axis if self.dp_axis in self.mesh.axis_names else None
        xs_spec = P(None, dp, *([None] * (xs.ndim - 2)))

        fn = shard_map(
            engine, mesh=self.mesh,
            in_specs=(in_param_spec, xs_spec),
            out_specs=xs_spec,
            check_vma=False)
        ys = fn(stacked_params, xs)
        return ys.reshape((-1,) + ys.shape[2:])


def stack_stage_params(per_stage_params: Sequence[Any]):
    """[stage0_params, stage1_params, ...] -> stacked pytree with leading
    stage dim (the layout ``PipelinedBlocks`` shards over pp)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def gpipe_ragged(block_fn: Callable[..., Any], axis_name: str,
                 n_microbatches: int, counts: Sequence[int],
                 prologue_fn: Optional[Callable[..., Any]] = None,
                 epilogue_fn: Optional[Callable[..., Any]] = None):
    """Ragged GPipe: per-stage block counts may differ, and stage 0 /
    stage S-1 may run extra non-block programs (embedding prologue /
    LM-head epilogue) — lifting the uniform-repeated-block restriction
    of ``gpipe`` (the reference never implemented pipelining at all;
    ``ffconst.h:159`` reserves OP_PIPELINE).

    - block_fn(block_params, x, t) -> y, shape-preserving; one template
      block. Stage s applies its ``counts[s]`` blocks per step; stacked
      params are padded to ``cmax = max(counts)`` and masked slots pass
      x through unchanged (SPMD: every scan step costs cmax blocks
      anyway — the win of raggedness is absorbing blocks/prologue/
      epilogue that would otherwise run REPLICATED outside the region).
    - prologue_fn(pro_params, raw_mb, t) -> x: stage 0 turns the raw
      per-microbatch input (e.g. token ids) into the entry activation.
      None = raw_xs already are the entry activations.
    - epilogue_fn(epi_params, y, t) -> out: stage S-1 maps the exit
      activation to the final output (shape may differ from x, e.g.
      vocab logits). None = identity.

    Returned apply(stacked_local, pro_params, epi_params, raw_xs,
    hidden_example, out_example):
      - stacked_local: (1, cmax, ...) leaves — this stage's padded
        block params;
      - raw_xs: pytree of (M, mb, ...) microbatched raw inputs
        (replicated across stages);
      - hidden_example/out_example: shape/dtype exemplars (one
        microbatch) for the ring state and the output buffer.
    Returns (M, mb, ...) outputs of the final stage (replicated).
    """
    M = n_microbatches
    counts = list(counts)
    cmax = max(counts)

    def apply(stacked_local, pro_params, epi_params, raw_xs,
              hidden_example, out_example):
        S = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        my_count = jnp.asarray(counts, jnp.int32)[stage]
        block_params = jax.tree.map(lambda x: x[0], stacked_local)
        perm = [(i, i + 1) for i in range(S - 1)]

        outputs0 = jnp.zeros((M,) + out_example.shape, out_example.dtype)
        state0 = jnp.zeros(hidden_example.shape, hidden_example.dtype)

        def body(carry, t):
            state, outputs = carry
            m_in = jnp.clip(t, 0, M - 1)
            raw_mb = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, m_in, 0,
                                                   keepdims=False),
                raw_xs)

            def enter_stage0(_):
                if prologue_fn is None:
                    return raw_mb
                return prologue_fn(pro_params, raw_mb, t)

            x_in = lax.cond(stage == 0, enter_stage0,
                            lambda _: state, operand=None)

            def blk(x, scan_in):
                p_k, k = scan_in
                y = block_fn(p_k, x, t)
                return jnp.where(k < my_count, y, x), None

            y, _ = lax.scan(blk, x_in,
                            (block_params,
                             jnp.arange(cmax, dtype=jnp.int32)))

            # the last stage finishes microbatch m = t - (S-1)
            m_out = t - (S - 1)
            valid = jnp.logical_and(stage == S - 1,
                                    jnp.logical_and(m_out >= 0,
                                                    m_out < M))

            def run_epilogue(_):
                out = epilogue_fn(epi_params, y, t) \
                    if epilogue_fn is not None else y
                return out

            out = lax.cond(valid, run_epilogue,
                           lambda _: jnp.zeros(out_example.shape,
                                               out_example.dtype),
                           operand=None)
            mo = jnp.clip(m_out, 0, M - 1)
            cur = lax.dynamic_index_in_dim(outputs, mo, 0, keepdims=False)
            upd = jnp.where(valid, out, cur)
            outputs = lax.dynamic_update_index_in_dim(outputs, upd, mo, 0)
            state = lax.ppermute(y, axis_name, perm)
            return (state, outputs), None

        (_, outputs), _ = lax.scan(body, (state0, outputs0),
                                   jnp.arange(M + S - 1))
        outputs = lax.psum(
            jnp.where(stage == S - 1, outputs,
                      jnp.zeros_like(outputs)), axis_name)
        return outputs

    return apply
