"""Operator registry.

Analog of the reference's ``Op`` contract (``include/flexflow/operator.h:51``):
each operator type registers an ``OpDef`` implementing

  - ``infer``   : shape/dtype inference (compute-graph level)
  - ``weights`` : declarative parameter specs (kernel/bias/...)
  - ``emit``    : JAX emission — the forward computation. Backward comes from
                  ``jax.grad`` over the whole graph (XLA fuses + schedules),
                  replacing the reference's per-op ``backward_task`` bodies.
  - ``flops`` / ``bytes`` : analytic cost hooks for the execution simulator
                  (analog of ``measure_operator_cost``; real on-chip
                  microbenchmarks refine these, see search/simulator.py).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ffconst import DataType, OperatorType
from ..core.tensor import WeightSpec


class EmitCtx:
    """Per-trace emission context threaded through op emission."""

    def __init__(self, training: bool, rngs: Optional[Dict[str, Any]] = None,
                 state: Optional[Dict[str, Any]] = None, config=None,
                 seq_length: int = -1):
        self.training = training
        self.rngs = rngs or {}
        self.state = state or {}          # read-only collection (e.g. BN stats)
        self.new_state: Dict[str, Any] = {}  # updated state written by ops
        self.config = config
        self.seq_length = seq_length
        self.aux_losses: List[Any] = []  # e.g. MoE load-balancing terms
        # device counters of the step: an op adds to keys of its own
        # (``count``), the executor puts the sums among the step's
        # metrics (``runtime.metrics.COUNTER_PREFIX``), and they reach
        # the host where the loss does. A rematerialised block returns
        # them, and its ops' ``aux_losses``, as outputs.
        self.counters: Dict[str, Any] = {}
        # KV-cache decode plumbing (serving; the reference has no
        # generation path at all). kv_mode: None = normal forward,
        # "prefill" = full-sequence forward that also records each
        # attention layer's per-position K/V into new_kv, "decode" =
        # single-token forward reading kv_cache and writing the updated
        # buffers to new_kv. kv_index = the (traced) query position.
        # kv_prefill_len = (traced) count of real prompt positions in
        # the prefill batch — sliding-window layers seed their
        # O(window) ring-buffer cache from it.
        self.kv_mode: Optional[str] = None
        self.kv_cache: Optional[Dict[str, Any]] = None
        self.kv_index: Any = None
        self.kv_prefill_len: Any = None
        self.new_kv: Dict[str, Any] = {}
        # local-shape execution (the quantized-sync shard_map runs the
        # graph on per-device batch SHARDS): ops whose params bake
        # absolute batch-sized shapes (Reshape) rescale their batch dim
        # by the shard factor ONLY when this is set — global emission
        # keeps the exact historical error behavior
        self.local_shape: bool = False
        # forced kernel impls (kernels/registry.py): the adopted
        # strategy's per-op impl map plus the mesh context ring
        # attention lowers its shard_map against. None/empty = no plan:
        # attention resolves by its ``auto`` rule.
        self.kernel_impls: Optional[Dict[str, str]] = None
        self.mesh = None                  # jax.sharding.Mesh
        self.seq_axis: Optional[str] = None
        # the adopted OpSharding of the op being emitted (set per layer
        # by GraphProgram.emit_layers): a compiled Pallas kernel must
        # run under shard_map with these specs, because GSPMD cannot
        # partition a Mosaic call
        self.op_sharding = None
        # the adopted specs of that op's inputs, in their order (None:
        # no strategy, or an input the plan leaves open)
        self.input_specs: Optional[List[Any]] = None
        # layer name -> emitted attention impl, shared with the executor
        self.resolved_impls: Optional[Dict[str, str]] = None

    def rng_for(self, name: str):
        return self.rngs.get(name)

    def count(self, key: str, value) -> None:
        """Add ``value`` (a device scalar) to the step's counter ``key``."""
        self.counters[key] = self.counters.get(key, 0.0) + value


# The one name (``checkpoint_name``) under which ``executor.py::
# emit_layers`` marks the outputs of a layer that says
# ``OpDef.keeps_for_block``, and which a rematerialised block that holds
# such a layer keeps beside its entry (``executor.py::KEEP_MARKED``).
KEPT_BY_BLOCK = "ff.kept_by_block"

# The wraps open in this thread's trace, innermost last, while the
# recorder is on (``checkpointed``): what ``kept_by_block`` adds to.
_WRAPS = threading.local()


def _shard_bytes(x, spec=None, mesh=None) -> int:
    """Bytes of the arrays of ``x`` (an array, or a dict, list or tuple
    of them) that ONE device holds under ``spec``: a ``PartitionSpec``
    over ``mesh`` for every array, or a dict of them by ``x``'s keys.
    No spec or no mesh: the whole arrays."""
    if isinstance(x, dict):
        by_key = spec if isinstance(spec, dict) else None
        return sum(_shard_bytes(v, by_key.get(k) if by_key is not None
                                else spec, mesh) for k, v in x.items())
    if isinstance(x, (list, tuple)):
        return sum(_shard_bytes(v, spec, mesh) for v in x)
    if not hasattr(x, "dtype") or not hasattr(x, "shape"):
        return 0
    n = int(np.prod(x.shape)) * x.dtype.itemsize
    if mesh is None or spec is None or isinstance(spec, dict):
        return n
    for entry in spec:
        for axis in (entry if isinstance(entry, tuple) else (entry,)):
            if axis is not None:
                n = -(-n // mesh.shape[axis])
    return n


def wrap_specs(ctx):
    """``(specs, mesh)`` for an op's own wrap of ``(x, weights)``: the
    adopted spec of its first input, its weights' specs by name, and
    the mesh they are over (``EmitCtx``; anything else: whole arrays)."""
    own = getattr(getattr(ctx, "op_sharding", None), "weights", None)
    return (((getattr(ctx, "input_specs", None) or [None])[0], own or {}),
            getattr(ctx, "mesh", None))


def kept_by_block(x, spec=None, mesh=None):
    """``x`` under the name ``KEPT_BY_BLOCK``: the identity, but a
    rematerialised block whose policy is ``executor.py::KEEP_MARKED``
    keeps it for its backward. Recorder on, its bytes (one device's,
    under ``spec`` over ``mesh``) are added to the innermost open wrap's
    ``kept_bytes`` (``checkpointed``)."""
    from jax.ad_checkpoint import checkpoint_name
    open_ = getattr(_WRAPS, "open", None)
    if open_:
        open_[-1]["kept"] += _shard_bytes(x, spec, mesh)
    return checkpoint_name(x, KEPT_BY_BLOCK)


def checkpointed(fn, *, site: str, policy=None, layer=None, block=None,
                 part=None, layers=None, weights: Sequence[int] = (),
                 specs=None, mesh=None):
    """``jax.checkpoint(fn, policy=policy)``: the ONE place where the
    package rematerialises. ``site`` names who asks (``block``,
    ``kda.layer``, ``kda.branch``, ``kda.terms``, ``kda.step``,
    ``gdn.terms``, ``ssm.layer``, ``ssm.chunk``, ``ssm1.chunk``, ``mhc.maps``,
    ``mhc.plain``, ``dsa.chunk``); what is wrapped, and
    what is kept, is the caller's decision and stays with it.

    On the device: the call runs under ``jax.named_scope("remat.<site>")``,
    so every op inside carries its owner in ``op_name`` beside JAX's own
    ``checkpoint`` / ``rematted_computation`` parts. Metadata only.

    On the recorder (``obs/events.py``; off: one flag read a call): one
    ``remat.wrap`` instant each time the wrap is traced, with ``site``,
    ``layer`` or ``block`` (a wrap inside a block carries both; a block
    also its ``layers``' names), ``part`` (what tells apart the wraps one
    layer makes at one site: a branch's first weight, a chunk's first
    row), ``depth`` (the wraps that enclose it in this trace),
    ``policy`` (``keep_marked`` | ``none``) and what it holds from the
    forward to the backward: ``entry_bytes`` (its array arguments but
    those at the positions ``weights``),
    ``weights_bytes``, ``kept_bytes`` (what ``kept_by_block`` marked
    inside it, 0 under no policy). One device's bytes: ``specs`` gives a
    ``PartitionSpec`` over ``mesh`` (or a dict of them) for each
    argument, None for a whole array."""
    import jax
    from ..obs import events

    def call(*args):
        recording = events.enabled()
        if recording:
            if not hasattr(_WRAPS, "open"):
                _WRAPS.open = []
            open_ = _WRAPS.open
            frame = {"kept": 0, "block": block if block is not None
                     or not open_ else open_[-1]["block"]}
            open_.append(frame)
        try:
            with jax.named_scope("remat." + site):
                out = jax.checkpoint(fn, policy=policy)(*args)
        finally:
            if recording:
                open_.pop()
        if not recording:
            return out
        held = [_shard_bytes(a, specs[i] if specs else None, mesh)
                for i, a in enumerate(args)]
        of_weights = sum(held[i] for i in weights)
        where = {k: v for k, v in (("layer", layer),
                                   ("block", frame["block"]),
                                   ("part", part), ("layers", layers))
                 if v is not None}
        events.instant(
            "remat.wrap", site=site, **where, depth=len(open_),
            policy="none" if policy is None else "keep_marked",
            entry_bytes=sum(held) - of_weights, weights_bytes=of_weights,
            kept_bytes=frame["kept"] if policy is not None else 0)
        return out

    return call


class OpDef:
    op_type: OperatorType = OperatorType.OP_INVALID
    # True for an op whose ``emit`` wraps its WHOLE body in
    # ``jax.checkpoint``. Such an op keeps its input and runs itself
    # again for its own backward, so a rematerialised block around it
    # would call it a third time only to hand its output on: the block
    # keeps that output instead (``executor.py::KEPT_BY_BLOCK``), one
    # array for each such layer of each block, live until the block's
    # backward. Not for an op that rematerialises a PART of itself
    # (``HyperConnectionOp`` wraps its maps and its second run is one
    # read of the streams, where keeping its outputs would hold eight
    # (tokens, hidden) arrays a block; ``RoutedExpertsOp`` wraps its
    # overflow loop): the block's second run is where its backward's
    # residuals come from.
    keeps_output_for_block: bool = False

    def keeps_for_block(self, params: Dict[str, Any]) -> bool:
        """Whether THIS layer says so: the class's answer, unless an op
        rematerialises itself only under some of its parameters
        (``MultiHeadAttentionOp`` with an indexer). Such an op may mark
        further values of its own ``KEPT_BY_BLOCK``."""
        return self.keeps_output_for_block

    def hands_on(self, params: Dict[str, Any]) -> Tuple[int, ...]:
        """The outputs of THIS layer, by index, that a layer further on
        reads beside the residual stream (a scan's output a gated memory
        unit reads, keys and values another attention layer attends
        over). A run of rematerialised blocks hands them from the block
        that makes them to the blocks that read them
        (``executor.py::_find_remat_blocks``); they are held, not made
        again."""
        return ()

    # ---- graph level ----
    def infer(self, params: Dict[str, Any],
              in_shapes: Sequence[Tuple[int, ...]],
              in_dtypes: Sequence[DataType]) -> List[Tuple[Tuple[int, ...], DataType]]:
        raise NotImplementedError

    def weights(self, params: Dict[str, Any],
                in_shapes: Sequence[Tuple[int, ...]],
                in_dtypes: Sequence[DataType]) -> List[WeightSpec]:
        return []

    # ---- execution level ----
    def emit(self, params: Dict[str, Any], inputs: List[Any],
             weights: Dict[str, Any], ctx: EmitCtx, name: str) -> List[Any]:
        raise NotImplementedError

    # ---- cost level (simulator) ----
    def flops(self, params, in_shapes, out_shapes) -> float:
        """Forward FLOPs estimate. Default: one op per output element."""
        return float(sum(int(np.prod(s)) for s in out_shapes))

    def backward_flops_factor(self) -> float:
        """bwd/fwd FLOP ratio. 2.0 for matmul-like ops (dgrad+wgrad)."""
        return 1.0

    def bytes_moved(self, params, in_shapes, out_shapes) -> Optional[float]:
        """Forward bytes to and from memory, for an op that moves more
        than its inputs, outputs and weights once each. None: that."""
        return None


OPS: Dict[OperatorType, OpDef] = {}


def register(cls):
    inst = cls()
    if inst.op_type == OperatorType.OP_INVALID:
        raise ValueError(f"{cls.__name__} does not declare an op_type")
    OPS[inst.op_type] = inst
    return cls


def get_op_def(op_type: OperatorType) -> OpDef:
    return OPS[OperatorType(op_type)]


def bf16_enabled(ctx) -> bool:
    """Whether emission may cast f32 matmul operands to bf16 (MXU path)."""
    cfg = getattr(ctx, "config", None) if ctx is not None else None
    if cfg is None:
        return True
    return getattr(cfg, "use_bf16_compute", True) and \
        getattr(cfg, "allow_tensor_op_math_conversion", True)


def compute_dtype(ctx, ref_dtype=None):
    """bf16 when enabled and the reference dtype is f32/bf16, else f32."""
    import jax.numpy as jnp
    if bf16_enabled(ctx) and ref_dtype in (None, jnp.float32, jnp.bfloat16):
        return jnp.bfloat16
    return jnp.float32


def matmul(a, b, *, prefer_bf16: bool = True, precision=None, ctx=None):
    """MXU-friendly matmul: bf16 inputs, fp32 accumulation.

    ``ctx`` (EmitCtx) gates the bf16 cast on
    ``config.use_bf16_compute`` / ``allow_tensor_op_math_conversion``.
    Unlike the reference (math conversion OFF by default, model.cc:3491),
    the TPU-native default is ON — bf16 is the MXU's native input dtype;
    ``--f32-compute`` / ``--no-tensor-op-math-conversion`` disables it."""
    import jax.numpy as jnp
    if ctx is not None:
        prefer_bf16 = prefer_bf16 and bf16_enabled(ctx)
    if prefer_bf16 and a.dtype in (jnp.float32, jnp.bfloat16):
        a16 = a.astype(jnp.bfloat16)
        b16 = b.astype(jnp.bfloat16)
        out = jnp.matmul(a16, b16, preferred_element_type=jnp.float32)
        return out.astype(a.dtype) if a.dtype != jnp.float32 else out
    # f32-compute path: still accumulate in f32 for low-precision operands
    if a.dtype == jnp.bfloat16:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32) \
            .astype(a.dtype)
    return jnp.matmul(a, b)
