"""Executor: lowers a (graph, strategy) pair to jitted SPMD train/eval steps.

This replaces the reference's entire Legion execution stack — per-op
IndexLaunchers, FFMapper routing, NCCL cliques, Legion tracing
(``src/runtime/model.cc:2415-2469``, ``src/mapper/mapper.cc``) — with ONE
pjit-compiled function per step kind:

  - the op graph is interpreted once at trace time (topological emission);
  - the searched strategy is applied as ``with_sharding_constraint`` on op
    outputs and ``NamedSharding`` placement of parameters;
  - XLA GSPMD inserts the ICI collectives the strategy implies, fuses
    elementwise chains (the reference's FusedOp pass), and overlaps
    compute/comm (the reference's Legion async task graph);
  - jit caching plays the role of Legion tracing: iteration 2+ replays the
    compiled executable.

Backward is jax.grad over the traced graph — the analog of the reference's
per-op backward tasks driven in reverse topo order (``model.cc:2438``).
"""
from __future__ import annotations

import functools
import itertools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ffconst import (CompMode, DataType, LossType, MetricsType, OperatorType)
from .core.layer import Layer
from .core.tensor import Tensor
from .dtypes import to_jnp
from .obs import events as obs_events
from .ops import EmitCtx, ensure_weight_specs, get_op_def
from .ops.registry import KEPT_BY_BLOCK, checkpointed, kept_by_block
from .parallel import reshard as reshard_mod
from .parallel.machine import DeviceMesh
from .parallel.strategy import ShardingStrategy
from .runtime import losses as losses_mod
from .runtime import metrics as metrics_mod
from .runtime.initializers import initialize, initialize_host  # noqa: F401
from .runtime.optimizers import Optimizer
from jax import shard_map


def _npdt(dtype) -> "np.dtype":
    """numpy dtype for a framework DataType (bfloat16 via ml_dtypes)."""
    return np.dtype(to_jnp(dtype))


def _trace_sync_on() -> bool:
    """``FF_TRACE_SYNC=1``: block on the step's outputs inside the
    instrumentation span so it measures TRUE step latency instead of
    dispatch time (the async-dispatch loop otherwise returns as soon as
    XLA enqueues the step). Read per call — only on the traced path —
    so a debug session can toggle it without rebuilding the step."""
    from .obs.events import _env_on
    return _env_on(os.environ.get("FF_TRACE_SYNC"))


def _instrument_step(fn, name: str):
    """Wrap a jitted step with per-step telemetry: a span per call with
    the compile-vs-steady split (the FIRST call of a fresh jit traces +
    compiles; later calls replay the executable) and a step counter.
    With ``FF_TRACE_SYNC=1`` the span additionally blocks on the step's
    outputs, so it records device latency, not dispatch latency.

    Wrapping records one ``executor.jit`` instant: ``name`` and the
    ``fun_name`` under which XLA's own events (``xla.trace`` /
    ``xla.lower`` / ``xla.backend_compile``, obs/xla_events.py) will
    tell of this function, so a reader of the ring knows the program's
    steps from whatever else the process compiled. The train and the
    eval step are both ``step_fn`` to JAX (the name is in the HLO
    module's, so in every cache key: it stays); an event of that name
    inside an ``executor.eval_step`` span is the eval step's.

    Disabled-mode cost is one flag check plus an int increment. What
    holds it is the benchmark itself: the driver's runs are untraced, so
    every PR's ``train_tokens_per_s`` is measured through this wrapper
    with the recorder off. The raw jitted callable stays reachable as
    ``wrapped.__wrapped__``, and the jit inspection surface callers rely
    on (``lower`` for HLO dumps — utils/debug.py — plus ``trace``/
    ``eval_shape``) is re-exposed on the wrapper: a compile made through
    it lies under no ``executor.<name>_step`` span, and is known by its
    ``fun_name`` alone."""
    # itertools.count: serving instance clones share one compiled
    # forward across N scheduler workers, and next() is atomic under
    # the GIL — a read-modify-write int would double-label "compile"
    calls = itertools.count()
    obs_events.instant("executor.jit", name=name,
                       fun_name=getattr(fn, "__name__", "<unnamed>"))

    def wrapped(*args, **kwargs):
        n = next(calls)
        if not obs_events.enabled():
            return fn(*args, **kwargs)
        obs_events.counter(f"executor.{name}_steps")
        with obs_events.span(f"executor.{name}_step",
                             phase="compile" if n == 0 else "steady",
                             step=n):
            out = fn(*args, **kwargs)
            if _trace_sync_on():
                jax.block_until_ready(out)
            return out

    wrapped.__wrapped__ = fn
    for attr in ("lower", "trace", "eval_shape", "clear_cache"):
        if hasattr(fn, attr):
            setattr(wrapped, attr, getattr(fn, attr))
    return wrapped


#: where ``_forward`` leaves the step's device counters
#: (``EmitCtx.counters``) among the captured values
COUNTERS_KEY = "step_counters"


def _emit_scoped(op, layer: Layer, ins, w, ctx):
    """``op.emit`` under ``jax.named_scope(layer.name)``: every device
    op the layer lowers to carries, in its ``op_name`` metadata, the
    name the strategy audit and the cost model use for that layer.
    Trace-time metadata only: no computation changes."""
    with jax.named_scope(layer.name):
        return op.emit(layer.params, ins, w, ctx, layer.name)


# The policy by which a rematerialised block that holds a layer that
# says ``OpDef.keeps_for_block`` keeps what is marked ``KEPT_BY_BLOCK``
# beside its entry (``_emit_remat``): ``emit_layers`` marks such a
# layer's outputs. One object for every trace: JAX caches its partial
# evaluation by the policy.
KEEP_MARKED = jax.checkpoint_policies.save_only_these_names(KEPT_BY_BLOCK)


def device_bytes(tree) -> int:
    """Bytes of the placed arrays of ``tree`` on the device that holds
    the most of them (its shards; on one device, everything)."""
    held: Dict[Any, int] = {}
    for a in jax.tree.leaves(tree):
        for shard in getattr(a, "addressable_shards", ()):
            held[shard.device] = held.get(shard.device, 0) \
                + int(shard.data.nbytes)
    return max(held.values(), default=0)


def _needs_rng(layer: Layer) -> bool:
    if layer.op_type in (OperatorType.OP_DROPOUT,
                         OperatorType.OP_BLOCK_DIFFUSION_NOISE):
        return True
    if layer.op_type == OperatorType.OP_MULTIHEAD_ATTENTION:
        return layer.params.get("dropout", 0.0) > 0.0
    return False


class GraphProgram:
    """Topologically-ordered emission plan for a layer graph."""

    def __init__(self, layers: Sequence[Layer], input_tensors: Sequence[Tensor],
                 output_tensors: Sequence[Tensor]):
        self.layers = list(layers)
        self.input_tensors = list(input_tensors)
        self.output_tensors = list(output_tensors)

    def init_env(self, inputs: Dict[str, Any]) -> Dict[int, Any]:
        env: Dict[int, Any] = {}
        for t in self.input_tensors:
            if t.name in inputs:
                env[t.guid] = inputs[t.name]
            elif t.get_tensor() is not None:
                # constant input (create_constant / frontend const folding):
                # baked into the jitted program at trace time
                env[t.guid] = jnp.asarray(t.get_tensor(), to_jnp(t.dtype))
            else:
                raise KeyError(f"missing input {t.name}")
        return env

    def emit_layers(self, layers: Sequence[Layer],
                    env: Dict[int, Any],
                    params: Dict[str, Dict[str, Any]], ctx: EmitCtx,
                    strategy: Optional[ShardingStrategy] = None,
                    capture: Optional[Dict[int, Any]] = None) -> None:
        bf16_act = bool(getattr(ctx.config, "bf16_activations", False)) \
            if ctx.config is not None else False
        # per-op device-subset placement (parallel/banks.py): member
        # layers of a bank are emitted together as one vmap whose mapped
        # dim is sharded over the bank axes — each device subset computes
        # only its own members, concurrently (reference MachineView
        # placement, machine_view.h:14-62)
        bank_out: Dict[str, Any] = {}
        # name -> (group, emit_fn) for BOTH subset-placement kinds
        # (stacked banks and heterogeneous place groups): member layers
        # are emitted together at the first member's position
        grouped: Dict[str, Tuple[Any, Any]] = {}
        if strategy is not None:
            present = {l.name for l in layers}
            for bk in getattr(strategy, "banks", None) or ():
                if set(bk.members) <= present:
                    for m in bk.members:
                        grouped[m] = (bk, self._emit_bank)
            for pg in getattr(strategy, "place_groups", None) or ():
                if set(pg.members) <= present:
                    for m in pg.members:
                        grouped[m] = (pg, self._emit_place_group)
        for layer in layers:
            if layer.name in grouped:
                if layer.name not in bank_out:
                    grp, emit_fn = grouped[layer.name]
                    # several layers emitted as one: the scope is the
                    # first member's name
                    with jax.named_scope(grp.members[0]):
                        emit_fn(grp, layers, env, params, ctx, strategy,
                                bank_out)
                o = bank_out[layer.name]
                if bf16_act and hasattr(o, "dtype") \
                        and o.dtype == jnp.float32:
                    o = o.astype(jnp.bfloat16)
                env[layer.outputs[0].guid] = o
                if capture is not None:
                    capture[layer.outputs[0].guid] = bank_out[layer.name]
                continue
            op = get_op_def(layer.op_type)
            ins = [env[t.guid] for t in layer.inputs]
            w = params.get(layer.name, {})
            ctx.op_sharding = strategy.ops.get(layer.name) \
                if strategy is not None else None
            ctx.input_specs = [strategy.tensor_spec(t) for t in layer.inputs] \
                if strategy is not None else None
            outs = _emit_scoped(op, layer, ins, w, ctx)
            if len(outs) != len(layer.outputs):
                raise RuntimeError(
                    f"op {layer.name} emitted {len(outs)} outputs, "
                    f"expected {len(layer.outputs)}")
            for i, (o, t) in enumerate(zip(outs, layer.outputs)):
                cast = (bf16_act and hasattr(o, "dtype")
                        and o.dtype == jnp.float32)
                pre_cast = o
                if cast:
                    # end-to-end bf16 activations: inter-op tensors live
                    # in bf16 (weights stay fp32 masters; losses/norms
                    # upcast internally)
                    o = o.astype(jnp.bfloat16)
                if strategy is not None:
                    sh = strategy.output_sharding(layer.name, i)
                    if sh is not None:
                        # layout-op outputs take the PLANNED transition
                        # (explicit collectives under shard_map) — a bare
                        # constraint lets GSPMD propagate it backward
                        # through reshape/concat, the documented CPU
                        # miscompile (parallel/reshard.py)
                        o = reshard_mod.constrain_output(
                            o, sh, strategy, layer)
                        if cast:
                            pre_cast = reshard_mod.constrain_output(
                                pre_cast, sh, strategy, layer)
                if op.keeps_for_block(layer.params):
                    # the identity but under a rematerialised block's
                    # policy (_emit_remat), which keeps what is so named
                    o = kept_by_block(
                        o, strategy.tensor_spec(t)
                        if strategy is not None else None, ctx.mesh)
                env[t.guid] = o
                if capture is not None:
                    # capture keeps the pre-bf16-cast (but still
                    # sharding-constrained) value: the CE-on-logits
                    # fusion reads logits from here, and the loss must
                    # consume full-precision logits even when
                    # --bf16-activations quantizes the live graph
                    capture[t.guid] = pre_cast if cast else o

    def _emit_bank(self, bk, layers, env, params, ctx,
                   strategy: ShardingStrategy,
                   bank_out: Dict[str, Any]) -> None:
        """Emit one bank group: stack member inputs along a leading bank
        dim, vmap the member op over it, shard the mapped dim over the
        bank axes. Each device subset computes only its slice of the
        vmap — its own members — so the group runs concurrently across
        subsets; the downstream per-member reads (``out[k]``) are where
        GSPMD inserts the one rejoin all-gather."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        by_name = {l.name: l for l in layers}
        members = [by_name[n] for n in bk.members]
        op = get_op_def(members[0].op_type)
        mesh = strategy.dmesh.mesh
        bank_spec = bk.axes[0] if len(bk.axes) == 1 else tuple(bk.axes)
        # data parallelism inside each subset over the leftover axes
        batch_spec = None
        ish = members[0].inputs[0].shape
        if bk.batch_axes and ish:
            bdeg = 1
            for a in bk.batch_axes:
                bdeg *= strategy.dmesh.axis_sizes[a]
            if ish[0] % bdeg == 0:
                batch_spec = (bk.batch_axes[0] if len(bk.batch_axes) == 1
                              else tuple(bk.batch_axes))
        from .parallel.banks import rejoin_stack, shard_stack
        xs = jnp.stack([env[m.inputs[0].guid] for m in members])
        in_sp = P(bank_spec, batch_spec, *([None] * (xs.ndim - 2)))
        xs = shard_stack(xs, members[0].inputs[0], in_sp, strategy)
        w = params.get(bk.param_name, {})
        emit_params = members[0].params
        if getattr(bk, "padded", False):
            # heterogeneous members: emit with weight-sizing params
            # (e.g. num_entries) maxed to match the padded stack
            from .parallel.banks import _PAD_FREE_PARAMS
            emit_params = dict(members[0].params)
            for key in _PAD_FREE_PARAMS.get(members[0].op_type, ()):
                emit_params[key] = max(m.params[key] for m in members)

        def one(x_k, w_k):
            return op.emit(emit_params, [x_k], w_k, ctx,
                           members[0].name)[0]

        out = jax.vmap(one)(xs, w)
        out_sp = P(bank_spec, batch_spec, *([None] * (out.ndim - 2)))
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, out_sp))
        out = rejoin_stack(out, bank_spec, batch_spec, strategy)
        for k, m in enumerate(members):
            bank_out[m.name] = out[k]

    def _emit_place_group(self, pg, layers, env, params, ctx,
                          strategy: ShardingStrategy,
                          bank_out: Dict[str, Any]) -> None:
        """Emit one heterogeneous placement region (PlaceGroup): a
        shard_map over the place axis whose body ``lax.switch``es on
        the member block coordinate — each device EXECUTES only its
        member's op (MPMD-inside-SPMD), so mixed-type independent ops
        run concurrently on disjoint subsets; outputs rejoin by an
        exact masked psum (only the first coordinate of each owning
        block contributes). Weights stay replicated — for distributed
        weights use a (padded) bank; this region is the
        compute-placement half of the reference's arbitrary MachineView
        (machine_view.h:14-62)."""
        from jax.sharding import PartitionSpec as P
        by_name = {l.name: l for l in layers}
        members = [by_name[n] for n in pg.members]
        mesh = strategy.dmesh.mesh
        axis = pg.axis
        P_ = strategy.dmesh.axis_sizes[axis]
        K = len(members)
        if P_ % K != 0:
            raise ValueError(f"place axis {axis} size {P_} must divide "
                             f"into {K} members")
        per = P_ // K
        for m in members:
            if len(m.inputs) != 1 or len(m.outputs) != 1:
                raise ValueError(f"place-group member {m.name} must be "
                                 f"1-in/1-out")
            if _needs_rng(m):
                raise ValueError(f"place-group member {m.name} uses "
                                 f"rng (not supported)")
        ops = [get_op_def(m.op_type) for m in members]
        for m, op in zip(members, ops):
            ss = getattr(op, "state_spec", None)
            if ss is not None and ss(
                    m.params, [t.shape for t in m.inputs],
                    [t.dtype for t in m.inputs]):
                raise ValueError(
                    f"stateful op {m.name} cannot join a place group")
        xs = [env[m.inputs[0].guid] for m in members]
        ws = [params.get(m.name, {}) for m in members]
        out_sds = [jax.eval_shape(
            lambda x, w, i=i: ops[i].emit(members[i].params, [x], w,
                                          ctx, members[i].name)[0],
            xs[i], ws[i]) for i in range(K)]

        def body(xs_l, ws_l):
            k = jax.lax.axis_index(axis)
            owner = k // per
            first = (k % per) == 0

            def branch(i):
                def go(_):
                    out = ops[i].emit(members[i].params, [xs_l[i]],
                                      ws_l[i], ctx, members[i].name)[0]
                    outs = [jnp.zeros(s.shape, s.dtype)
                            for s in out_sds]
                    # zeros_like keeps integer/bool outputs in their
                    # own dtype (a weak-float 0.0 would promote and
                    # desync the branch signatures)
                    outs[i] = jnp.where(first, out, jnp.zeros_like(out))
                    return tuple(outs)
                return go

            outs = jax.lax.switch(owner, [branch(i) for i in range(K)],
                                  None)
            return tuple(jax.lax.psum(o, axis) for o in outs)

        # replicated in/out specs: shard_map's transpose of replicated
        # operands yields EXACT gradients even on meshes with extra
        # (non-place) axes — pinned by
        # tests/test_place_groups.py::test_place_group_grads_exact
        region = shard_map(
            body, mesh=mesh,
            in_specs=(tuple(P() for _ in xs),
                      tuple(jax.tree.map(lambda _: P(), w)
                            for w in ws)),
            out_specs=tuple(P() for _ in range(K)),
            check_vma=False)
        outs = region(tuple(xs), tuple(ws))
        for m, o in zip(members, outs):
            bank_out[m.name] = o

    def emit(self, params: Dict[str, Dict[str, Any]], inputs: Dict[str, Any],
             ctx: EmitCtx, strategy: Optional[ShardingStrategy] = None,
             capture: Optional[Dict[int, Any]] = None) -> List[Any]:
        """Interpret the graph. `capture[tensor.guid]` collects intermediate
        values (used for logits extraction by the loss)."""
        env = self.init_env(inputs)
        self.emit_layers(self.layers, env, params, ctx, strategy, capture)
        return [env[t.guid] for t in self.output_tensors]


# A sequence mixer that stands where another kind does in a layout of
# layers otherwise the same: to the block finder the two are one op. Only
# a kind no older layout holds may be named here (their runs, and so
# their steps, stay the ones they were).
_MIXES_LIKE = {OperatorType.OP_STATE_SPACE_MIXER:
               OperatorType.OP_MULTIHEAD_ATTENTION,
               # beside multi-head attention only in the layout of PR 57
               # (three linear layers to one full layer); the older
               # layout that holds it has latent attention for the rest
               OperatorType.OP_GATED_DELTA_RULE:
               OperatorType.OP_MULTIHEAD_ATTENTION}


def _find_remat_blocks(layers):
    """Block boundaries for ``--remat``: the maximal repeated-block run,
    each block single-input/single-output (beside the graph's own inputs
    and constants, which every block may read: positions, masks),
    containing no stateful op (a write to ``ctx.new_state`` cannot
    cross a ``jax.checkpoint`` boundary; device counters and auxiliary
    losses leave a block as its outputs). Every block is emitted from
    its own layers with its own parameters (``_emit_remat``), so two
    blocks are the same where their ops and their outputs' shapes are:
    a period of attention layers that differ in window, rotary
    embedding or positions is a run of blocks all the same, and so is
    a period of state-space mixers or gated delta rules with one
    attention layer among them (``_MIXES_LIKE``: nine layers of ten, or
    three of four, would otherwise be two runs, and the shorter one held
    whole). Returns
    ``(start, unit, reps, entry_guids, exit_guids)`` or None.

    A graph in which an op hands an output on beside the residual
    stream (``OpDef.hands_on``: a scan's output a later layer gates,
    keys and values a later layer attends over) has no such run: its
    boundaries cross more than one tensor and the layers that read what
    another made are built of other ops. Its blocks are found by where
    the stream is cut (:func:`_find_stream_blocks`), and ``unit`` is
    then each block's length, a tuple."""
    from .parallel.pipeline_lowering import (_has_state, chunk_boundaries,
                                             find_repeated_run)
    layers = list(layers)
    handed = frozenset(l.outputs[i].guid for l in layers
                       for i in get_op_def(l.op_type).hands_on(l.params))
    if handed:
        return _find_stream_blocks(layers, handed)
    graph_inputs = frozenset(t.guid for l in layers for t in l.inputs
                             if t.owner_layer is None)
    run = find_repeated_run(
        list(layers), 1, graph_inputs,
        signature=lambda l: (_MIXES_LIKE.get(l.op_type, l.op_type),
                             tuple(t.shape for t in l.outputs)))
    if run is None:
        return None
    total, start, unit = run
    reps = total // unit
    layers = list(layers)
    region = layers[start:start + total]
    if any(_has_state(l) for l in region):
        return None
    entries = chunk_boundaries(layers, start, unit, reps)
    if entries is None:
        return None
    exits = entries[1:] + [region[-1].outputs[0].guid]
    return start, unit, reps, entries, exits


def _find_stream_blocks(layers, handed):
    """Blocks of unlike interior along one residual stream, for a graph
    with ``handed`` tensors (guids) that cross from the layer that makes
    them to a later one. A CUT is a place between two layers that one
    tensor crosses beside the handed ones; the stream is the shape most
    cuts carry, and the layers between two of its cuts are a segment (a
    mixer with its norm and add; a feed-forward with its). The segment
    that recurs most, layer for layer by op and output shapes, closes a
    block: a block is whatever segments stand before it since the last
    one (a scan, an attention layer, a gated unit of five plain ops),
    and that segment. Blocks agree in their entry's and exit's shape and
    in their last segment, in nothing else: each is emitted from its own
    layers. Returns ``(start, units, reps, entry_guids, exit_guids)``
    with ``units`` the blocks' lengths, or None (under two blocks, or a
    stateful op in one)."""
    from .parallel.pipeline_lowering import _has_state
    made = {t.guid: i for i, l in enumerate(layers) for t in l.outputs}
    shape = {t.guid: tuple(t.shape) for l in layers for t in l.outputs}
    last_read = {}
    for i, l in enumerate(layers):
        for t in l.inputs:
            if t.guid in made:
                last_read[t.guid] = i
    cuts = []                           # (first layer after it, stream guid)
    for at in range(1, len(layers)):
        crossing = [g for g, i in made.items()
                    if i < at <= last_read.get(g, -1) and g not in handed]
        if len(crossing) == 1:
            cuts.append((at, crossing[0]))
    if not cuts:
        return None
    shapes = [shape[g] for _, g in cuts]
    stream = max(set(shapes), key=shapes.count)
    cuts = [c for c in cuts if shape[c[1]] == stream]
    segments = [tuple((l.op_type, tuple(tuple(t.shape) for t in l.outputs))
                      for l in layers[lo:hi])
                for (lo, _), (hi, _) in zip(cuts, cuts[1:])]
    if not segments:
        return None
    closing = max(segments, key=segments.count)     # the first, on a tie
    ends = [i + 1 for i, seg in enumerate(segments) if seg == closing]
    if len(ends) < 2:
        return None
    bounds = [cuts[i] for i in [0] + ends]           # a cut a block edge
    start, stop = bounds[0][0], bounds[-1][0]
    if any(_has_state(l) for l in layers[start:stop]):
        return None
    units = tuple(hi - lo for (lo, _), (hi, _) in zip(bounds, bounds[1:]))
    guids = [g for _, g in bounds]
    return start, units, len(units), guids[:-1], guids[1:]


# Megatron tp split of stacked stage weights: role -> weight name ->
# dim index (within the weight's own shape) sharded over tp_axis.
# None = replicated (biases applied once, after the psum).
_TP_WEIGHT_DIMS = {
    "attn": {"wq": 1, "wk": 1, "wv": 1, "bq": 0, "bk": 0, "bv": 0,
             "wo": 0, "bo": None, "wg": 1},
    "col": {"kernel": 1, "bias": 0},
    "row": {"kernel": 0, "bias": None},
}


class Executor:
    def __init__(self, program: GraphProgram, config, dmesh: DeviceMesh,
                 strategy: ShardingStrategy, optimizer: Optimizer,
                 loss_type: LossType, metrics: Sequence[MetricsType],
                 seed: int = 0, loss_weights: Optional[Tensor] = None):
        self.program = program
        self.config = config
        self.dmesh = dmesh
        self.strategy = strategy
        self.optimizer = optimizer
        self.loss_type = LossType(loss_type)
        self.metrics = list(metrics)
        self.seed = seed
        # the tensor that weighs the loss's rows (FFModel.set_loss_weights)
        self._loss_weights_tensor = loss_weights
        # (layer, weight) -> step, of the weights that move by the sign
        # of their gradient and not by the optimizer (``WeightSpec.
        # sign_step``: a router's choice bias under its balancing rule)
        self._sign_steps = {(l.name, w.name): w.sign_step
                            for l in program.layers for w in l.weights
                            if w.sign_step}
        self._train_step = None
        self._eval_step = None
        # ZeRO-1 (runtime/zero.py): NamedSharding pytree for the updated
        # optimizer state, set by FFModel.compile when enabled
        self.opt_state_constraints = None
        # communication–computation overlap (runtime/overlap.py): the
        # bucketed grad-sync schedule, or None = the serial path
        # (bit-exact default). Built statically here so the plan
        # verifier (which runs before the first step is traced) sees
        # the schedule on strategy.overlap.
        from .runtime import overlap as overlap_mod
        self._overlap_schedule = overlap_mod.build_overlap_schedule(
            program, strategy, config)
        if self._overlap_schedule is not None:
            strategy.overlap = self._overlap_schedule.record()
            obs_events.counter("overlap.schedules_built")
        # quantized gradient collectives (ops/quantized_collectives.py):
        # when the strategy carries a QsyncPlan the runtime can honor
        # (pure-DP program, replicated weights), gradients are computed
        # and synced explicitly — quantized legs on the wire dtype,
        # error-feedback residuals as runtime state. None = the
        # implicit GSPMD sync, bit-exact legacy behavior. An imported
        # plan resolves here; a plan adopted post-build (FFModel.
        # _plan_qsync) re-resolves via attach_qsync().
        self._qsync = None
        self.attach_qsync()
        # forced kernel impls (kernels/registry.py): the adopted
        # strategy's per-op impl map, threaded through EmitCtx so
        # attention emission resolves its impl (ring lowers one
        # shard_map over the mesh's seq axis). Empty = every op's own
        # rule decides.
        self._kernel_impls: Dict[str, str] = dict(
            getattr(strategy, "kernel_impls", None) or {})
        # layer name -> "xla" | "flash" | "ring", written while a step
        # is traced: the implementation each attention op really emitted
        # (the train step's, once one was traced; else the eval step's)
        self.resolved_attention_impls: Dict[str, str] = {}
        # pipeline region (parallel/pipeline_lowering): pre/post layer
        # split + GPipe lowering of the repeated-block region
        self.pipe = getattr(strategy, "pipeline", None)
        # --remat: per-block jax.checkpoint over the repeated-block run
        # (HBM-for-FLOPs trade; the pipelined region already recomputes
        # via its scan, so remat applies to the non-pipelined path only)
        self._remat = None
        if getattr(config, "remat", "none") == "blocks" \
                and self.pipe is not None:
            import logging
            logging.getLogger("flexflow_tpu").warning(
                "--remat is skipped when a pipeline region is active: "
                "the GPipe scan already recomputes stage activations "
                "per microbatch (pre/post-region layers are never "
                "rematerialized)")
        if getattr(config, "remat", "none") == "blocks" \
                and self.pipe is None:
            self._remat = _find_remat_blocks(program.layers)
            if self._remat is None:
                import logging
                logging.getLogger("flexflow_tpu").warning(
                    "--remat requested but the graph has no eligible "
                    "repeated-block region (needs >= 2 identical "
                    "single-crossing blocks without stateful/aux-loss "
                    "ops); running without rematerialization")
        if self.pipe is not None:
            if getattr(self.pipe, "prologue", None):
                # absorbed into stage 0 (ragged schedule): the prologue
                # IS layers[:start] by construction
                self._pre_layers = []
            else:
                self._pre_layers = program.layers[:self.pipe.start]
            n_epi = len(getattr(self.pipe, "epilogue", None) or [])
            self._post_layers = program.layers[self.pipe.end + n_epi:]
        # CE-on-logits fusion: if the final op is Softmax, take its input as
        # logits (grad identical to the reference's (probs-labels)/B kernel).
        self._logits_tensor: Optional[Tensor] = None
        if (losses_mod.wants_logits(self.loss_type)
                and self.program.layers
                and self.program.output_tensors):
            final_t = self.program.output_tensors[0]
            prod = final_t.owner_layer
            if prod is not None and prod.op_type == OperatorType.OP_SOFTMAX:
                self._logits_tensor = prod.inputs[0]

    # ------------------------------------------------------------------
    def set_kernel_impls(self, plan: Dict[str, str]) -> None:
        """Adopt a kernel plan. An executor the floor guard built has
        already traced (and run) its steps under the plan it was built
        with — jit would replay those for the same arguments, and a plan
        adopted afterwards would silently never run — so a change drops
        every cached step."""
        if dict(plan) == self._kernel_impls:
            return
        self._kernel_impls = dict(plan)
        self._train_step = self._eval_step = self._forward_fn = None
        self.__dict__.pop("_decode_cache", None)

    def attach_qsync(self) -> None:
        """(Re)resolve the strategy's quantized-sync plan into an
        executable schedule. FFModel.compile calls this again after
        ``_plan_qsync`` adopts a plan (the executor may predate it —
        the floor guard builds executors mid-search), invalidating the
        cached train step when the schedule changes."""
        from .ops import quantized_collectives as qsync_mod
        sched = qsync_mod.runtime_schedule(
            self.program, self.strategy, self.config, self.dmesh)
        if (sched is None) != (self._qsync is None):
            self._train_step = None
        self._qsync = sched
        if sched is not None:
            obs_events.counter("qsync.schedules_built")

    # ------------------------------------------------------------------
    def init_params_and_state(self, rng: Optional[jax.Array] = None):
        """Materialize parameters per WeightSpec with strategy shardings
        (reference: per-op init tasks + initializer GPU kernels).

        Arrays are built HOST-SIDE (numpy Philox keyed by the weight's
        integer path — see ``initializers.initialize_host``) and placed
        with one tree-level ``device_put`` against the recorded target
        shardings. The round-4 north-star profile showed 230 s of its
        301 s compile in eager per-weight jax init dispatch; jitting the
        whole init instead takes minutes to SPMD-compile on a many-
        device mesh. Host init + bulk placement is seconds either way
        and deterministic across platforms."""
        if rng is not None:
            # API compat: derive the integer seed from a caller key
            words = jax.random.key_data(rng).ravel()
            seed = int(words[-1]) | (int(words[0]) << 32)
        else:
            seed = self.seed
        psh: Dict[str, Dict[str, Any]] = {}
        ssh: Dict[str, Dict[str, Any]] = {}
        with obs_events.span("executor.init_params") as sp:
            params, state = self._build_params_and_state(seed, psh, ssh)
            if obs_events.enabled():
                leaves = jax.tree.leaves(params)
                sp.set(parameters=sum(int(a.size) for a in leaves),
                       bytes=sum(int(a.nbytes) for a in leaves))
            # placement via the reshard planner's host→device step:
            # sharded leaves hand each device only its own slice instead
            # of staging a full per-device replica
            # (parallel/reshard.place_host)
            params = jax.tree.map(reshard_mod.place_host, params, psh)
            state = jax.tree.map(reshard_mod.place_host, state, ssh)
            if obs_events.enabled():
                sp.set(device_bytes=device_bytes((params, state)))
        return params, state

    def _build_params_and_state(self, seed, psh, ssh):
        """Host-side body of :meth:`init_params_and_state`: returns raw
        numpy (params, state) trees and records each leaf's target
        sharding into ``psh``/``ssh`` (congruent pytrees)."""
        params: Dict[str, Dict[str, Any]] = {}
        state: Dict[str, Dict[str, Any]] = {}
        region_names = set()
        if self.pipe is not None:
            region_names = {l.name for l in self.program.layers[
                self.pipe.start:self.pipe.end]}
            if getattr(self.pipe, "counts", None) is not None:
                params.update(self._init_ragged_pipeline_params(seed, psh))
            else:
                params.update(self._init_pipeline_params(seed, psh))
        # banked members (parallel/banks.py): weights are stacked along
        # a leading bank dim sharded over the bank axes, so each device
        # subset HOLDS only its members' weights (the reference's
        # per-view weight placement). Member k is initialized with the
        # exact keys the unbanked path would use — banked and unbanked
        # runs are numerically identical.
        banks = getattr(self.strategy, "banks", None) or []
        if banks:
            # prune banks whose members don't all exist in this program
            # (e.g. a stale --import against a renamed model): emitting
            # such a bank would KeyError deep inside compile. Pruning on
            # the shared strategy keeps init and emit consistent.
            names = {l.name for l in self.program.layers}
            kept = [bk for bk in banks if set(bk.members) <= names]
            if len(kept) != len(banks):
                import logging
                logging.getLogger("flexflow_tpu").warning(
                    "dropping %d bank placement(s) whose members are "
                    "not in this program", len(banks) - len(kept))
                self.strategy.banks = kept
            banks = kept
        bank_member_arrs: Dict[str, Dict[str, Any]] = {}
        bank_names = {n for bk in banks for n in bk.members}
        for li, layer in enumerate(self.program.layers):
            if layer.name in region_names:
                continue  # initialized stacked, above
            op = get_op_def(layer.op_type)
            specs = ensure_weight_specs(layer)
            if specs and layer.name in bank_names:
                arrs = {}
                for wi, spec in enumerate(specs):
                    # same key path as the unbanked branch below: banked
                    # and unbanked runs are numerically identical
                    arrs[spec.name] = initialize_host(
                        spec, (seed, 1, li, wi), _npdt(spec.dtype))
                bank_member_arrs[layer.name] = arrs
            elif specs:
                lp = {}
                for wi, spec in enumerate(specs):
                    lp[spec.name] = initialize_host(
                        spec, (seed, 1, li, wi), _npdt(spec.dtype))
                    psh.setdefault(layer.name, {})[spec.name] = \
                        self.strategy.weight_sharding(layer.name, spec.name)
                params[layer.name] = lp
            state_spec = getattr(op, "state_spec", None)
            if state_spec is not None:
                ss = state_spec(layer.params, [t.shape for t in layer.inputs],
                                [t.dtype for t in layer.inputs])
                if ss:
                    if layer.name in bank_names:
                        raise ValueError(
                            f"stateful op {layer.name} cannot be "
                            f"banked")
                    st = {}
                    for sname, (sshape, sdt) in ss.items():
                        if sname == "var":
                            st[sname] = np.ones(sshape, _npdt(sdt))
                        else:
                            st[sname] = np.zeros(sshape, _npdt(sdt))
                        ssh.setdefault(layer.name, {})[sname] = \
                            self.strategy.replicated()
                    state[layer.name] = st
        for bk in banks:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if any(m not in bank_member_arrs for m in bk.members):
                # member without weight specs: nothing to stack (the
                # emit path still banks the compute)
                continue
            bank_spec = bk.axes[0] if len(bk.axes) == 1 else tuple(bk.axes)
            lp = {}
            wnames = list(bank_member_arrs[bk.members[0]].keys())
            for wname in wnames:
                arrs = [bank_member_arrs[m][wname] for m in bk.members]
                if getattr(bk, "padded", False):
                    # heterogeneous members (e.g. different vocab
                    # sizes): zero-pad each weight to the group max —
                    # lookups are bounded by each member's true vocab,
                    # so the padding is never read
                    tgt = tuple(max(a.shape[d] for a in arrs)
                                for d in range(arrs[0].ndim))
                    arrs = [np.pad(a, [(0, t - s) for s, t in
                                       zip(a.shape, tgt)])
                            if tuple(a.shape) != tgt else a
                            for a in arrs]
                stacked = np.stack(arrs)
                psh.setdefault(bk.param_name, {})[wname] = NamedSharding(
                    self.dmesh.mesh,
                    P(bank_spec, *([None] * (stacked.ndim - 1))))
                lp[wname] = stacked
            params[bk.param_name] = lp
        return params, state

    # ------------------------------------------------------------------
    # pipeline lowering (parallel/pipeline_lowering.PipelineRegion)
    # ------------------------------------------------------------------
    def _init_pipeline_params(self, seed, psh):
        """Stacked region params: for each template layer, one leaf of
        shape (S,) + spec.shape — stage s initialized independently —
        sharded P(pp_axis, ...) so each pipeline rank holds its stage.
        Interleaved schedule (n_chunks = v > 1): (v, S) + spec.shape,
        sharded P(None, pp_axis, ...) — [k, s] is global chunk s + k*S.
        Returns raw host arrays; shardings recorded into ``psh``."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        pipe = self.pipe
        S, v = pipe.n_stages, pipe.n_chunks
        out: Dict[str, Dict[str, Any]] = {}
        for lj, layer in enumerate(pipe.template):
            specs = ensure_weight_specs(layer)
            if not specs:
                continue
            role = pipe.tp_roles.get(layer.name) \
                if pipe.tp_axis is not None else None
            lp = {}
            for wi, spec in enumerate(specs):
                slices = []
                for c in range(S * v):
                    slices.append(initialize_host(
                        spec, (seed, 2, 7000 + (lj << 12) + wi, c),
                        _npdt(spec.dtype)))
                stacked = np.stack(slices)
                wdims = [None] * len(spec.shape)
                if role is not None:
                    d = _TP_WEIGHT_DIMS[role].get(spec.name)
                    if d is not None:
                        wdims[d] = pipe.tp_axis
                if v > 1:
                    # [k, s] = chunk s + k*S: stack order is chunk-major,
                    # so the (v, S) reshape lands chunk c at [c//S, c%S]
                    stacked = stacked.reshape((v, S) + tuple(spec.shape))
                    sh = NamedSharding(self.dmesh.mesh,
                                       P(None, pipe.pp_axis, *wdims))
                else:
                    sh = NamedSharding(self.dmesh.mesh,
                                       P(pipe.pp_axis, *wdims))
                psh.setdefault(pipe.param_name(layer), {})[spec.name] = sh
                lp[spec.name] = stacked
            out[pipe.param_name(layer)] = lp
        return out

    # ------------------------------------------------------------------
    # ragged pipeline lowering (gpipe_ragged; pipeline_lowering.counts)
    # ------------------------------------------------------------------
    def _ragged_slot_of(self):
        """block index b -> (stage, slot) under the contiguous ragged
        assignment (stage s owns counts[s] consecutive blocks)."""
        out = []
        for s, c in enumerate(self.pipe.counts):
            out.extend((s, k) for k in range(c))
        return out

    def _init_ragged_pipeline_params(self, seed, psh):
        """Block params stacked (S, cmax) + spec.shape, stage dim over
        the pp axis, slot dim scanned by the engine; slots past a
        stage's count are zero (masked pass-through in the engine).
        Returns raw host arrays; shardings recorded into ``psh``."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        pipe = self.pipe
        S = pipe.n_stages
        cmax = max(pipe.counts)
        slot_of = self._ragged_slot_of()
        out: Dict[str, Dict[str, Any]] = {}
        for lj, layer in enumerate(pipe.template):
            specs = ensure_weight_specs(layer)
            if not specs:
                continue
            lp = {}
            for wi, spec in enumerate(specs):
                dt = _npdt(spec.dtype)
                rows = [[np.zeros(tuple(spec.shape), dt)
                         for _ in range(cmax)] for _ in range(S)]
                for b, (s, k) in enumerate(slot_of):
                    rows[s][k] = initialize_host(
                        spec, (seed, 3, 7000 + (lj << 12) + wi, b), dt)
                stacked = np.stack([np.stack(r) for r in rows])
                psh.setdefault(pipe.param_name(layer), {})[spec.name] = \
                    NamedSharding(
                        self.dmesh.mesh,
                        P(pipe.pp_axis, *([None] * (stacked.ndim - 1))))
                lp[spec.name] = stacked
            out[pipe.param_name(layer)] = lp
        return out

    def _make_block_fn(self, training: bool):
        """block_fn(p_k, x, t) emitting ONE template block; ``p_k`` is
        the per-slot param subtree handed over by gpipe_ragged's scan."""
        pipe = self.pipe
        template = pipe.template
        bf16_act = bool(getattr(self.config, "bf16_activations", False))

        def block_fn(p, x, t):
            rng_key = p.get("__rng__")
            env = {pipe.template_entry_guid: x}
            ctx = EmitCtx(training=training, rngs={}, state={},
                          config=self.config)
            for j, layer in enumerate(template):
                if training and rng_key is not None and _needs_rng(layer):
                    ctx.rngs[layer.name] = jax.random.fold_in(
                        jax.random.fold_in(rng_key, t), j)
                op = get_op_def(layer.op_type)
                ins = [env[tt.guid] for tt in layer.inputs]
                w = p.get(pipe.param_name(layer), {})
                outs = _emit_scoped(op, layer, ins, w, ctx)
                for o, tt in zip(outs, layer.outputs):
                    if bf16_act and hasattr(o, "dtype") \
                            and o.dtype == jnp.float32:
                        o = o.astype(jnp.bfloat16)
                    env[tt.guid] = o
            return env[pipe.template_exit_guid]

        return block_fn

    def _make_edge_fn(self, layers, out_guid, training: bool):
        """Interpret a prologue/epilogue layer list inside the pipelined
        shard_map; ``env_seed`` maps tensor guids to incoming values."""
        bf16_act = bool(getattr(self.config, "bf16_activations", False))

        def fn(p, env_seed, t):
            rng_key = p.get("__rng__")
            env = dict(env_seed)
            ctx = EmitCtx(training=training, rngs={}, state={},
                          config=self.config)
            for j, layer in enumerate(layers):
                if training and rng_key is not None and _needs_rng(layer):
                    ctx.rngs[layer.name] = jax.random.fold_in(
                        jax.random.fold_in(rng_key, t), j)
                op = get_op_def(layer.op_type)
                ins = [env[tt.guid] for tt in layer.inputs]
                w = p.get(layer.name, {})
                outs = _emit_scoped(op, layer, ins, w, ctx)
                for o, tt in zip(outs, layer.outputs):
                    if bf16_act and hasattr(o, "dtype") \
                            and o.dtype == jnp.float32:
                        o = o.astype(jnp.bfloat16)
                    env[tt.guid] = o
            return env[out_guid]

        return fn

    def _tensor_by_guid(self, guid: int):
        for l in self.program.layers:
            for t in list(l.outputs) + list(l.inputs):
                if t.guid == guid:
                    return t
        for t in self.program.input_tensors:
            if t.guid == guid:
                return t
        raise KeyError(guid)

    def _pipe_apply_ragged(self, params, env, batch, step,
                           training: bool):
        """Run a ragged pipeline region (unequal stage depths, optional
        prologue/epilogue inside stage 0 / S-1)."""
        from jax.sharding import PartitionSpec as P
        from .parallel.pipeline import gpipe_ragged
        pipe = self.pipe
        S, M = pipe.n_stages, pipe.n_microbatches
        cmax = max(pipe.counts)
        stacked = {pipe.param_name(l): params[pipe.param_name(l)]
                   for l in pipe.template
                   if pipe.param_name(l) in params}
        if training:
            base = jax.random.fold_in(jax.random.key(self.seed + 2), step)
            keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                jnp.arange(S * cmax)).reshape(S, cmax)
            stacked = dict(stacked, __rng__=keys)

        pro_params = {l.name: params[l.name] for l in pipe.prologue
                      if l.name in params}
        epi_params = {l.name: params[l.name] for l in pipe.epilogue
                      if l.name in params}
        if training:
            pro_params = dict(pro_params, __rng__=jax.random.fold_in(
                jax.random.key(self.seed + 3), step))
            epi_params = dict(epi_params, __rng__=jax.random.fold_in(
                jax.random.key(self.seed + 4), step))

        entry_t = self._tensor_by_guid(pipe.entry_guid)
        mb = entry_t.shape[0] // M
        hidden_example = jnp.zeros((mb,) + tuple(entry_t.shape[1:]),
                                   to_jnp(entry_t.dtype))
        if pipe.epilogue:
            out_t = self._tensor_by_guid(pipe.epilogue_exit_guid)
            out_example = jnp.zeros((mb,) + tuple(out_t.shape[1:]),
                                    to_jnp(out_t.dtype))
        else:
            out_example = hidden_example

        prologue_fn = None
        if pipe.prologue:
            edge = self._make_edge_fn(pipe.prologue, pipe.entry_guid,
                                      training)

            def prologue_fn(p, raw_mb, t):  # noqa: F811
                seed = {t_.guid: raw_mb[t_.name]
                        for t_ in pipe.prologue_inputs}
                return edge(p, seed, t)

            raw_xs = {}
            for t_ in pipe.prologue_inputs:
                a = batch[t_.name]
                raw_xs[t_.name] = a.reshape((M, a.shape[0] // M)
                                            + a.shape[1:])
        else:
            from .parallel.pipeline_lowering import region_entry_transition
            x = region_entry_transition(
                env[pipe.entry_guid], self.strategy,
                self._tensor_by_guid(pipe.entry_guid))
            raw_xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])

        epilogue_fn = None
        if pipe.epilogue:
            eedge = self._make_edge_fn(pipe.epilogue,
                                       pipe.epilogue_exit_guid, training)

            def epilogue_fn(p, y, t):  # noqa: F811
                return eedge(p, {pipe.exit_guid: y}, t)

        engine = gpipe_ragged(self._make_block_fn(training), pipe.pp_axis,
                              M, pipe.counts, prologue_fn=prologue_fn,
                              epilogue_fn=epilogue_fn)

        pp = pipe.pp_axis
        param_specs = jax.tree.map(
            lambda a: P(pp, *([None] * (a.ndim - 1))), stacked)
        pro_specs = jax.tree.map(lambda a: P(), pro_params)
        epi_specs = jax.tree.map(lambda a: P(), epi_params)
        dp = pipe.dp_axes if pipe.dp_axes else None
        dp = dp[0] if dp is not None and len(dp) == 1 else dp
        raw_specs = jax.tree.map(
            lambda a: P(None, dp, *([None] * (a.ndim - 2))), raw_xs)
        hid_spec = P(dp, *([None] * (hidden_example.ndim - 1)))
        out_spec = P(dp, *([None] * (out_example.ndim - 1)))
        ys_spec = P(None, dp, *([None] * (out_example.ndim - 1)))
        fn = shard_map(
            engine, mesh=self.dmesh.mesh,
            in_specs=(param_specs, pro_specs, epi_specs, raw_specs,
                      hid_spec, out_spec),
            out_specs=ys_spec, check_vma=False)
        ys = fn(stacked, pro_params, epi_params, raw_xs,
                hidden_example, out_example)
        from .parallel.pipeline_lowering import region_exit_transition
        ys = region_exit_transition(ys, self.strategy, ys_spec)
        return ys.reshape((-1,) + ys.shape[2:])

    def _make_stage_fn(self, training: bool):
        """stage_fn(params, x, t) interpreting the template chunk; params
        is the squeezed (per-stage) subtree handed over by gpipe."""
        pipe = self.pipe
        template = pipe.template

        tp_ax = pipe.tp_axis
        bf16_act = bool(getattr(self.config, "bf16_activations", False))

        def stage_fn(p, x, t):
            rng_base = p.get("__rng__")
            env = {pipe.template_entry_guid: x}
            ctx = EmitCtx(training=training, rngs={}, state={},
                          config=self.config)
            for j, layer in enumerate(template):
                if training and rng_base is not None and _needs_rng(layer):
                    key = jax.random.fold_in(
                        jax.random.fold_in(rng_base, t), j)
                    if tp_ax is not None and \
                            pipe.tp_roles.get(layer.name) == "attn":
                        # attention-prob dropout acts on tp-SHARDED
                        # heads: each shard must draw an independent
                        # mask. Role-less layers (residual dropout) see
                        # tp-REPLICATED activations and must keep the
                        # same key on every shard, or the replication
                        # invariant between psum points breaks.
                        key = jax.random.fold_in(
                            key, jax.lax.axis_index(tp_ax))
                    ctx.rngs[layer.name] = key
                op = get_op_def(layer.op_type)
                ins = [env[tt.guid] for tt in layer.inputs]
                w = p.get(pipe.param_name(layer), {})
                role = pipe.tp_roles.get(layer.name) \
                    if tp_ax is not None else None
                if role in ("attn", "row"):
                    # Megatron reduction point: emit with the bias held
                    # back (the local matmul yields a PARTIAL sum over
                    # the tp-split contraction dim), one psum over tp,
                    # then the bias applied exactly once
                    w = dict(w)
                    bias = w.pop("bo" if role == "attn" else "bias", None)
                    outs = _emit_scoped(op, layer, ins, w, ctx)
                    y = jax.lax.psum(outs[0], tp_ax)
                    if bias is not None:
                        y = (y + bias).astype(outs[0].dtype)
                    outs = [y]
                else:
                    outs = _emit_scoped(op, layer, ins, w, ctx)
                for o, tt in zip(outs, layer.outputs):
                    if bf16_act and hasattr(o, "dtype") \
                            and o.dtype == jnp.float32:
                        o = o.astype(jnp.bfloat16)
                    env[tt.guid] = o
            return env[pipe.template_exit_guid]

        return stage_fn

    def _pipe_apply(self, params, x, step, training: bool):
        """Run the pipeline region: microbatch x, shard_map the GPipe
        schedule over (dp, pp), return the region output (full batch)."""
        from jax.sharding import PartitionSpec as P
        from .parallel.pipeline import gpipe
        pipe = self.pipe
        S, M, v = pipe.n_stages, pipe.n_microbatches, pipe.n_chunks
        stacked = {pipe.param_name(l): params[pipe.param_name(l)]
                   for l in pipe.template
                   if pipe.param_name(l) in params}
        if training:
            base = jax.random.fold_in(jax.random.key(self.seed + 2), step)
            chunk_keys = jax.vmap(
                lambda i: jax.random.fold_in(base, i))(jnp.arange(S * v))
            if v > 1:
                chunk_keys = chunk_keys.reshape(v, S)
            stacked = dict(stacked, __rng__=chunk_keys)
        if x.shape[0] % M != 0:
            raise ValueError(f"batch {x.shape[0]} not divisible into "
                             f"{M} microbatches")
        from .parallel.pipeline_lowering import (region_entry_transition,
                                                 region_exit_transition)
        x = region_entry_transition(x, self.strategy,
                                    self._tensor_by_guid(pipe.entry_guid))
        xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        engine = gpipe(self._make_stage_fn(training), pipe.pp_axis, M,
                       with_step_arg=True, n_chunks=v)
        pp_lead = (pipe.pp_axis,) if v == 1 else (None, pipe.pp_axis)

        def weight_spec(lname, wname, arr):
            dims = [None] * (arr.ndim - len(pp_lead))
            role = pipe.tp_roles.get(lname) \
                if pipe.tp_axis is not None else None
            if role is not None:
                d = _TP_WEIGHT_DIMS[role].get(wname)
                if d is not None:
                    dims[d] = pipe.tp_axis
            return P(*pp_lead, *dims)

        param_specs = {
            pipe.param_name(l): {
                wname: weight_spec(l.name, wname, arr)
                for wname, arr in stacked[pipe.param_name(l)].items()}
            for l in pipe.template if pipe.param_name(l) in stacked}
        if "__rng__" in stacked:
            param_specs["__rng__"] = P(*pp_lead)
        dp = pipe.dp_axes if pipe.dp_axes else None
        dp = dp[0] if dp is not None and len(dp) == 1 else dp
        xs_spec = P(None, dp, *([None] * (xs.ndim - 2)))
        fn = shard_map(engine, mesh=self.dmesh.mesh,
                           in_specs=(param_specs, xs_spec),
                           out_specs=xs_spec, check_vma=False)
        ys = fn(stacked, xs)
        ys = region_exit_transition(ys, self.strategy, xs_spec)
        return ys.reshape((-1,) + ys.shape[2:])

    # ------------------------------------------------------------------
    def _rngs_for_step(self, step, shard_index=None):
        base = jax.random.key(self.seed + 1)
        base = jax.random.fold_in(base, step)
        if shard_index is not None:
            # shard-local emission (quantized sync): each device draws
            # INDEPENDENT dropout masks for its batch shard — the
            # distributional match for the global path's one mask
            # partitioned across shards (a shared key would correlate
            # masks across devices)
            base = jax.random.fold_in(base, shard_index)
        rngs = {}
        for li, layer in enumerate(self.program.layers):
            if _needs_rng(layer):
                rngs[layer.name] = jax.random.fold_in(base, li)
        return rngs

    def _attach_kernel_ctx(self, ctx):
        """Thread the forced kernel impls (kernels/registry.py) plus the
        seq-axis mesh context into an EmitCtx — ring attention lowers
        its shard_map against ctx.mesh/ctx.seq_axis."""
        if self._kernel_impls:
            ctx.kernel_impls = self._kernel_impls
        ctx.mesh = self.dmesh.mesh
        ctx.seq_axis = self.dmesh.seq_axis
        ctx.resolved_impls = self.resolved_attention_impls

    def _forward(self, params, state, batch, training: bool, step,
                 strategy="__use_own__", shard_index=None):
        """``strategy`` overrides the emission strategy — the quantized-
        sync path runs the forward INSIDE a shard_map on local batch
        shards and passes None (sharding constraints are meaningless in
        a manual shard region; weights arrive replicated).
        ``shard_index`` (a traced device index) marks that shard-local
        execution: absolute-batch-shape ops rescale (ctx.local_shape)
        and per-device rng streams decorrelate."""
        st = self.strategy if strategy == "__use_own__" else strategy
        rngs = self._rngs_for_step(step, shard_index) if training else {}
        ctx = EmitCtx(training=training, rngs=rngs, state=state,
                      config=self.config)
        self._attach_kernel_ctx(ctx)
        if shard_index is not None:
            ctx.local_shape = True
        capture: Dict[int, Any] = {}
        # checkpointing only matters under differentiation: eval/serving
        # forwards skip the remat path (prevent_cse barriers would only
        # inhibit XLA fusion there)
        if self.pipe is None and self._remat is not None and training:
            outs = self._emit_remat(params, batch, ctx, capture,
                                    strategy=st)
        elif self.pipe is None:
            outs = self.program.emit(params, batch, ctx, st, capture)
        else:
            env = self.program.init_env(batch)
            self.program.emit_layers(self._pre_layers, env, params, ctx,
                                     self.strategy, capture)
            if getattr(self.pipe, "counts", None) is not None:
                y = self._pipe_apply_ragged(params, env, batch, step,
                                            training)
                g = self.pipe.region_out_guid
            else:
                y = self._pipe_apply(params, env[self.pipe.entry_guid],
                                     step, training)
                g = self.pipe.exit_guid
            env[g] = y
            capture[g] = y
            self.program.emit_layers(self._post_layers, env, params, ctx,
                                     self.strategy, capture)
            outs = [env[t.guid] for t in self.program.output_tensors]
        new_state = dict(state)
        for k, v in ctx.new_state.items():
            new_state[k] = v
        # the ops' device counters ride with the captured values: the
        # loss's metrics read them from there (see _loss_and_metrics)
        if ctx.counters:
            capture[COUNTERS_KEY] = ctx.counters
        return outs, new_state, ctx.aux_losses, capture

    def _emit_remat(self, params, batch, ctx, capture,
                    strategy="__use_own__"):
        """Forward with each repeated block wrapped in ``jax.checkpoint``:
        block-internal activations are recomputed in the backward pass
        instead of living in HBM for the whole step. A block keeps its
        entry and the outputs of the ops inside it that rematerialise
        themselves whole (``OpDef.keeps_for_block``), each
        announced by a ``remat.kept`` instant."""
        st = self.strategy if strategy == "__use_own__" else strategy
        start, unit, reps, entries, exits = self._remat
        layers = self.program.layers
        # blocks of one length, or (``_find_stream_blocks``) of their own
        edges = np.cumsum([start] + list(
            (unit,) * reps if isinstance(unit, int) else unit)).tolist()
        env = self.program.init_env(batch)
        self.program.emit_layers(layers[:start], env, params, ctx,
                                 st, capture)
        x = env[entries[0]]
        # what a block may read beside its entry: the graph's inputs
        inputs_env = {t.guid: env[t.guid]
                      for l in layers[start:edges[-1]]
                      for t in l.inputs if t.owner_layer is None}
        tensors = {t.guid: t for l in layers for t in l.inputs}
        for b in range(reps):
            block = layers[edges[b]:edges[b + 1]]
            entry_g, exit_g = entries[b], exits[b]
            kept = [l for l in block
                    if get_op_def(l.op_type).keeps_for_block(l.params)]
            # what crosses the block's edges beside the stream: tensors
            # an earlier layer handed on (arguments of the checkpoint:
            # held, and their cotangents flow back) and tensors a layer
            # of this block hands to one after it (its results)
            own = {t.guid: l for l in block for t in l.outputs}
            taken = list(dict.fromkeys(
                t.guid for l in block for t in l.inputs
                if t.owner_layer is not None and t.guid not in own
                and t.guid != entry_g))
            handed = [g for g in own if g != exit_g and any(
                t.guid == g for l in layers[edges[b + 1]:]
                for t in l.inputs)]

            def block_fn(x_, p_, *taken_, _block=block, _entry=entry_g,
                         _exit=exit_g, _b=b, _kept=kept, _taken=taken,
                         _handed=handed, _own=own):
                benv = {**inputs_env, _entry: x_, **dict(zip(_taken,
                                                             taken_))}
                bctx = EmitCtx(training=ctx.training, rngs=ctx.rngs,
                               state=ctx.state, config=self.config,
                               seq_length=ctx.seq_length)
                bctx.local_shape = getattr(ctx, "local_shape", False)
                self._attach_kernel_ctx(bctx)
                self.program.emit_layers(_block, benv, p_, bctx,
                                         st, None)
                if bctx.new_state:
                    raise RuntimeError(
                        "stateful op inside a rematted block")
                for l in _kept:
                    for o in (benv[t.guid] for t in l.outputs):
                        obs_events.instant(
                            "remat.kept", block=_b, layer=l.name,
                            bytes=o.size * o.dtype.itemsize)
                for g in _handed:
                    obs_events.instant(
                        "remat.kept", block=_b, layer=_own[g].name,
                        bytes=benv[g].size * benv[g].dtype.itemsize,
                        handed_on=True)
                # the block's device counters and its ops' auxiliary
                # losses leave it as outputs: a side channel cannot
                # cross jax.checkpoint
                return (benv[_exit], bctx.counters, bctx.aux_losses,
                        [benv[g] for g in _handed])

            bp = {l.name: params[l.name] for l in block
                  if l.name in params}
            # no policy where there is nothing to keep: JAX keys its
            # partial evaluation on the policy, and such a block's step
            # stays the text it was under a plain jax.checkpoint
            x, counted, aux, made = checkpointed(
                block_fn, site="block", block=b,
                policy=KEEP_MARKED if kept else None, weights=(1,),
                specs=None if st is None else (
                    st.tensor_spec(tensors[entry_g]),
                    {l.name: st.ops[l.name].weights for l in block
                     if l.name in st.ops},
                    *(st.tensor_spec(tensors[g]) for g in taken)),
                mesh=self.dmesh.mesh, layers=[l.name for l in block])(
                x, bp, *(env[g] for g in taken))
            for key, v in counted.items():
                ctx.count(key, v)
            ctx.aux_losses.extend(aux)
            env[exit_g] = x
            capture[exit_g] = x
            env.update(zip(handed, made))
        self.program.emit_layers(layers[edges[-1]:], env,
                                 params, ctx, st, capture)
        return [env[t.guid] for t in self.program.output_tensors]

    def _loss_and_metrics(self, outs, capture, label, aux_losses):
        pred = outs[0]
        # a graph that names a weights tensor: its rows weigh the loss's
        weighted = {} if self._loss_weights_tensor is None else {
            "weights": capture[self._loss_weights_tensor.guid]}
        if weighted:
            obs_events.instant("loss.weighted", weighted=True,
                               rows=weighted["weights"].size)
        if self._logits_tensor is not None:
            logits = capture[self._logits_tensor.guid]
            loss = losses_mod.compute_loss(self.loss_type, logits, label,
                                           logits=True, **weighted)
        else:
            loss = losses_mod.compute_loss(self.loss_type, pred, label,
                                           **weighted)
        for al in aux_losses:
            loss = loss + al
        bm = metrics_mod.compute_batch_metrics(self.metrics, pred, label,
                                               self.loss_type)
        bm["loss"] = loss
        for key, v in capture.get(COUNTERS_KEY, {}).items():
            bm[metrics_mod.COUNTER_PREFIX + key] = jax.lax.stop_gradient(v)
        return loss, bm

    def _apply_update(self, params, grads, opt_state, step):
        """The optimizer phase of the train step (``step`` is 1-based):
        overlapped or plain update, by the adopted plan."""
        if self._overlap_schedule is not None:
            # overlap path (runtime/overlap.py): per-bucket updates
            # chained in backward-completion order — identity math
            # (bit-exact with the serial branch below), but the
            # barrier chain hands XLA dependency cuts so bucket k's
            # grad sync + update (+ ZeRO gather) interleave with
            # the backward of buckets k+1..
            from .runtime import overlap as overlap_mod
            new_params, new_opt_state = overlap_mod.overlapped_update(
                self.optimizer, params, grads, opt_state, step,
                self._overlap_schedule, self.opt_state_constraints)
        else:
            new_params, new_opt_state = self.optimizer.update(
                params, grads, opt_state, step)
            if self.opt_state_constraints is not None:
                # ZeRO-1 pin: keep the updated moments on their
                # sharded placement (GSPMD lowers the update to
                # reduce-scatter + sharded math instead of
                # replicating the state back)
                new_opt_state = jax.tree.map(
                    jax.lax.with_sharding_constraint,
                    new_opt_state, self.opt_state_constraints)
        for (layer, w), rate in self._sign_steps.items():
            old = params[layer][w]
            new_params[layer] = {**new_params[layer], w: old - (
                rate * jnp.sign(grads[layer][w])).astype(old.dtype)}
        return new_params, new_opt_state

    # ------------------------------------------------------------------
    def make_train_step(self):
        """Build the donated, jitted train step (fwd+bwd+update fused into
        one XLA program — the reference needed forward / zero_gradients /
        backward / update as separate task launch phases)."""
        if self._train_step is not None:
            return self._train_step

        accum = max(getattr(self.config, "gradient_accumulation_steps", 1),
                    1)
        if self.config.batch_size % accum != 0:
            raise ValueError(
                f"--gradient-accumulation-steps {accum} must divide "
                f"the batch size {self.config.batch_size}")

        # phase scopes: the backward ops carry JAX's own
        # ``transpose(jvp(ff.forward))`` / ``transpose(jvp(ff.loss))``
        def loss_fn(p, st, mb, sub_step):
            with jax.named_scope("ff.forward"):
                outs, new_state, aux, capture = self._forward(
                    p, st, mb, True, sub_step)
            with jax.named_scope("ff.loss"):
                loss, bm = self._loss_and_metrics(outs, capture,
                                                  mb["label"], aux)
            return loss, (new_state, bm)

        def step_fn(params, opt_state, state, step, batch):
            new_residual = None
            if self._qsync is not None:
                # explicit quantized gradient sync (ops/
                # quantized_collectives.py): one shard_map computes the
                # per-device local gradients and syncs every tensor on
                # the plan's wire dtypes, error-feedback residuals
                # riding the optimizer-state tree under a reserved slot
                # (stripped before the update below)
                from .ops import quantized_collectives as qsync_mod
                residual, opt_state = qsync_mod.strip_residual(opt_state)
                grads, bm, new_residual = qsync_mod.sharded_grads(
                    self, params, state, batch, step, residual)
                if residual is None and not new_residual:
                    new_residual = None   # keep the opt-state structure
                new_state = state   # stateful ops are qsync-ineligible
            elif accum <= 1:
                grads, (new_state, bm) = jax.grad(
                    loss_fn, has_aux=True)(params, state, batch, step)
            else:
                # gradient accumulation: scan over A micro-batches,
                # summing grads (mean losses => mean of micro grads ==
                # full-batch grad); one optimizer update per step.
                # Activations live one micro-batch at a time — an HBM
                # lever composing with --remat.
                def micro(carry, xs):
                    g_acc, st = carry
                    mb, i = xs
                    g, (st2, bm_i) = jax.grad(loss_fn, has_aux=True)(
                        params, st, mb, step * accum + i)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, st2), bm_i

                def to_micro(v):
                    # the RUNTIME batch (fit(batch_size=...) may differ
                    # from config.batch_size) must also divide
                    if v.shape[0] % accum != 0:
                        raise ValueError(
                            f"batch dim {v.shape[0]} not divisible "
                            f"into {accum} accumulation micro-batches")
                    return v.reshape((accum, v.shape[0] // accum)
                                     + v.shape[1:])

                mbs = jax.tree.map(to_micro, batch)
                g0 = jax.tree.map(jnp.zeros_like, params)
                (g_sum, new_state), bms = jax.lax.scan(
                    micro, (g0, state), (mbs, jnp.arange(accum)))
                grads = jax.tree.map(lambda g: g / accum, g_sum)
                # mean-valued metrics average across micro-batches;
                # count-valued ones must SUM; sqrt-of-mean ones (RMSE)
                # average the squares and sqrt once (ownership of the
                # distinction lives with the metrics module)
                def reduce_metric(k, v):
                    if metrics_mod.is_count(k):
                        return jnp.sum(v, axis=0)
                    if k in metrics_mod.RMS_KEYS:
                        return jnp.sqrt(jnp.mean(v * v, axis=0))
                    return jnp.mean(v, axis=0)

                bm = {k: reduce_metric(k, v) for k, v in bms.items()}
            # fused NaN screen for the deferred-metrics loop
            # (runtime/metrics_buffer.py): the host checks this flag at
            # flush points instead of fetching the loss every step.
            # LOSS-only on purpose — the old per-step screen checked
            # only the loss, and an auxiliary metric overflowing float32
            # on its own must not trigger a supervisor rollback
            bm["all_finite"] = jnp.all(jnp.isfinite(bm["loss"]))
            with jax.named_scope("ff.optimizer"):
                new_params, new_opt_state = self._apply_update(
                    params, grads, opt_state, step + 1)
            if new_residual is not None:
                from .ops.quantized_collectives import RESIDUAL_SLOT
                new_opt_state = dict(new_opt_state)
                new_opt_state[RESIDUAL_SLOT] = new_residual
            return new_params, new_opt_state, new_state, bm

        self._train_step = _instrument_step(
            jax.jit(step_fn, donate_argnums=(0, 1, 2)), "train")
        return self._train_step

    def make_eval_step(self):
        if self._eval_step is not None:
            return self._eval_step

        def step_fn(params, state, batch):
            outs, _, aux, capture = self._forward(
                params, state, batch, False, jnp.int32(0))
            loss, bm = self._loss_and_metrics(outs, capture, batch["label"],
                                              aux)
            return outs[0], bm

        self._eval_step = _instrument_step(jax.jit(step_fn), "eval")
        return self._eval_step

    def make_forward(self):
        """Inference-only forward (no label), jitted (cached on self)."""
        if getattr(self, "_forward_fn", None) is not None:
            return self._forward_fn

        def fwd(params, state, batch):
            outs, _, _, _ = self._forward(params, state, batch, False,
                                          jnp.int32(0))
            return outs[0] if len(outs) == 1 else outs

        self._forward_fn = _instrument_step(jax.jit(fwd), "forward")
        return self._forward_fn

    # ------------------------------------------------------------------
    # generation support (serving; the reference has no generate path)
    # ------------------------------------------------------------------
    def scored_forward(self, params, state, batch):
        """Forward returning log-domain next-token scores (B, L, V):
        the pre-softmax logits when the graph ends in Softmax (numerically
        exact), else log of the clipped output probabilities. NOT jitted —
        call inside a jitted decode loop."""
        outs, _, _, capture = self._forward(params, state, batch, False,
                                            jnp.int32(0))
        if self._logits_tensor is not None \
                and self._logits_tensor.guid in capture:
            return capture[self._logits_tensor.guid]
        return jnp.log(jnp.clip(outs[0], 1e-20))

    def kv_prefill(self, params, state, batch, prefill_len=None):
        """Full-sequence forward that also returns every causal
        attention layer's K/V buffers (the decode cache seed) plus the
        scores. ``prefill_len`` (traced) marks how many leading
        positions are real prompt — sliding-window layers use it to
        seed their O(window) ring-buffer cache. NOT jitted."""
        ctx = EmitCtx(training=False, rngs={}, state=state,
                      config=self.config)
        self._attach_kernel_ctx(ctx)
        ctx.kv_mode = "prefill"
        ctx.kv_prefill_len = prefill_len
        capture: Dict[int, Any] = {}
        outs = self.program.emit(params, batch, ctx, self.strategy,
                                 capture)
        if not ctx.new_kv:
            raise ValueError("graph has no multihead-attention layers to "
                             "cache (KV decode unsupported)")
        return outs, ctx.new_kv

    def kv_decode_step(self, params, state, batch, cache, index):
        """Single-token forward (inputs (B, 1)) against the KV cache at
        query position ``index``. Returns (scores_row (B, V), new_cache).
        NOT jitted — called inside the generate scan."""
        ctx = EmitCtx(training=False, rngs={}, state=state,
                      config=self.config)
        self._attach_kernel_ctx(ctx)
        ctx.kv_mode = "decode"
        ctx.kv_cache = cache
        ctx.kv_index = index
        capture: Dict[int, Any] = {}
        outs = self.program.emit(params, batch, ctx, self.strategy,
                                 capture)
        if self._logits_tensor is not None \
                and self._logits_tensor.guid in capture:
            scores = capture[self._logits_tensor.guid]
        else:
            scores = jnp.log(jnp.clip(outs[0], 1e-20))
        # cache layers that did not run in decode keep their buffers
        new_cache = dict(cache)
        new_cache.update(ctx.new_kv)
        return scores[:, 0, :], new_cache
