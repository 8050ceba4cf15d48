"""FFModel: the model-building and training API.

Reference parity: ``FFModel`` (``include/flexflow/model.h:326-958``,
``src/runtime/model.cc``) — layer builder methods (dense/conv2d/embedding/
multihead_attention/moe/...), ``compile`` (graph lowering + strategy
search + executable build), ``fit``/``forward``/``backward``/``update``
training drivers, and ``eval``.

TPU-native differences:
  - ``compile`` lowers the lazy Layer graph to a jitted SPMD step over a
    device mesh instead of Legion index-space task launches;
  - the parallelization strategy is a per-op PartitionSpec assignment found
    by the search (search/), or canonical data-parallel with
    ``--only-data-parallel``;
  - backward is jax.grad; gradient sync is XLA collectives implied by
    weight shardings (reference: per-view NCCL cliques, model.cc:3129).
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from .config import FFConfig, FFIterationConfig
from .core.layer import Layer
from .core.tensor import Tensor, WeightSpec
from .dtypes import from_numpy_dtype, to_jnp
from .executor import Executor, GraphProgram, device_bytes
from .ffconst import (ActiMode, AggrMode, CompMode, DataType, InitializerType,
                      LossType, MetricsType, OperatorType, ParameterSyncType,
                      PoolType)
from .obs import events as obs_events
from .ops import get_op_def
from .parallel.machine import DeviceMesh, MachineSpec
from .parallel.strategy import ShardingStrategy
from .runtime.dataloader import SingleDataLoader
from .runtime.metrics import PerfMetrics
from .runtime.metrics_buffer import MetricsBuffer
from .runtime.optimizers import Optimizer, SGDOptimizer

#: help string of ``ff_model_compiles_total``: it counts calls, not what
#: XLA built (the recorder's ``xla.compiles/<fun_name>`` does that)
_COMPILES_HELP = ("FFModel.compile() calls and fresh decode programs "
                  "(not XLA builds: see xla.compiles/<fun_name>)")

_LOSS_NAMES = {
    "categorical_crossentropy": LossType.LOSS_CATEGORICAL_CROSSENTROPY,
    "sparse_categorical_crossentropy":
        LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
    "mean_squared_error": LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
    "mse": LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
    "identity": LossType.LOSS_IDENTITY,
}

_METRIC_NAMES = {
    "accuracy": MetricsType.METRICS_ACCURACY,
    "categorical_crossentropy": MetricsType.METRICS_CATEGORICAL_CROSSENTROPY,
    "sparse_categorical_crossentropy":
        MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY,
    "mean_squared_error": MetricsType.METRICS_MEAN_SQUARED_ERROR,
    "root_mean_squared_error": MetricsType.METRICS_ROOT_MEAN_SQUARED_ERROR,
    "mean_absolute_error": MetricsType.METRICS_MEAN_ABSOLUTE_ERROR,
}


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.layers: List[Layer] = []
        self.input_tensors: List[Tensor] = []
        self.graph_inputs: List[Tensor] = []
        self._may_be_unread: set = set()   # guids, ``create_tensor``
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[LossType] = None
        self.metrics: List[MetricsType] = []
        self.label_tensor: Optional[Tensor] = None
        self.executor: Optional[Executor] = None
        self.dmesh: Optional[DeviceMesh] = None
        self.strategy: Optional[ShardingStrategy] = None
        self.params = None
        self.state = None
        self.opt_state = None
        self.iter_config = FFIterationConfig()
        self._step = 0
        self._output_tensor: Optional[Tensor] = None
        self._dataloaders: List[Tuple[Tensor, np.ndarray]] = []
        self._current_metrics: Optional[Dict[str, float]] = None
        # live deferred-metrics accumulator while a training driver
        # (fit / resilience supervisor) is running — checkpoint saves
        # flush + NaN-screen through it (runtime/metrics_buffer.py)
        self._metrics_buffer: Optional[MetricsBuffer] = None

    # ==================================================================
    # graph construction helpers
    # ==================================================================
    def _add_layer(self, op_type: OperatorType, inputs: Sequence[Tensor],
                   params: Dict[str, Any], name: Optional[str] = None
                   ) -> Layer:
        if name is None:
            # deterministic per-model naming (layer index, not a global
            # counter) so params/checkpoints from two identically-built
            # models share keys — required for checkpoint restore
            name = f"{OperatorType(op_type).name.lower()}_{len(self.layers)}"
        # params/strategy dicts are name-keyed: uniquify collisions
        used = {l.name for l in self.layers}
        base, k = name, 1
        while name in used:
            name = f"{base}_{k}"
            k += 1
        layer = Layer(op_type, name, list(inputs), params)
        op = get_op_def(op_type)
        in_shapes = [t.shape for t in inputs]
        in_dtypes = [t.dtype for t in inputs]
        out_specs = op.infer(layer.params, in_shapes, in_dtypes)
        for i, (shape, dt) in enumerate(out_specs):
            layer.outputs.append(Tensor(shape, dt, layer, i,
                                        name=f"{layer.name}:out{i}"))
        # resolve weight specs now so the search's cost model sees
        # weight memory + gradient-sync volumes (executor reuses these)
        layer.weights = op.weights(layer.params, in_shapes, in_dtypes)
        self.layers.append(layer)
        return layer

    def _unary(self, op_type: OperatorType, x: Tensor, name=None, **params
               ) -> Tensor:
        return self._add_layer(op_type, [x], params, name).outputs[0]

    def _binary(self, op_type: OperatorType, a: Tensor, b: Tensor, name=None
                ) -> Tensor:
        return self._add_layer(op_type, [a, b], {}, name).outputs[0]

    # ==================================================================
    # tensor creation (reference FFModel::create_tensor)
    # ==================================================================
    def create_tensor(self, dims: Sequence[int],
                      dtype: DataType = DataType.DT_FLOAT,
                      create_grad: bool = True, name: Optional[str] = None,
                      may_be_unread: bool = False) -> Tensor:
        """A graph input. ``may_be_unread``: the caller feeds it whether
        or not a layer reads it (the positions of a model with no
        positional embedding); unread, ``fit`` takes its array and drops
        it, and ``compile`` does not take it for the label."""
        t = Tensor(dims, dtype, None, 0, name=name, create_grad=create_grad)
        self.input_tensors.append(t)
        if may_be_unread:
            self._may_be_unread.add(t.guid)
        return t

    def create_constant(self, dims: Sequence[int], value: float,
                        dtype: DataType = DataType.DT_FLOAT) -> Tensor:
        t = self.create_tensor(dims, dtype, create_grad=False)
        t.set_tensor(np.full(dims, value, dtype=np.dtype(to_jnp(dtype))))
        return t

    # ==================================================================
    # layer builders (reference model.h:326-958)
    # ==================================================================
    def dense(self, input: Tensor, out_dim: int,
              activation: ActiMode = ActiMode.AC_MODE_NONE,
              use_bias: bool = True,
              datatype: Optional[DataType] = None,
              kernel_initializer=None, bias_initializer=None,
              kernel_regularizer=None, name: Optional[str] = None) -> Tensor:
        params = {"out_dim": out_dim, "activation": ActiMode(activation),
                  "use_bias": use_bias}
        if datatype is not None:
            params["dtype"] = DataType(datatype)
        if kernel_initializer is not None:
            params["kernel_initializer"] = kernel_initializer
        return self._add_layer(OperatorType.OP_LINEAR, [input], params,
                               name).outputs[0]

    def conv2d(self, input: Tensor, out_channels: int,
               kernel_h: int, kernel_w: int, stride_h: int, stride_w: int,
               padding_h: int, padding_w: int,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               groups: int = 1, use_bias: bool = True,
               kernel_initializer=None, name: Optional[str] = None) -> Tensor:
        params = {"out_channels": out_channels, "kernel_h": kernel_h,
                  "kernel_w": kernel_w, "stride_h": stride_h,
                  "stride_w": stride_w, "padding_h": padding_h,
                  "padding_w": padding_w, "activation": ActiMode(activation),
                  "groups": groups, "use_bias": use_bias}
        if kernel_initializer is not None:
            params["kernel_initializer"] = kernel_initializer
        return self._add_layer(OperatorType.OP_CONV2D, [input], params,
                               name).outputs[0]

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: PoolType = PoolType.POOL_MAX,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               name: Optional[str] = None) -> Tensor:
        params = {"kernel_h": kernel_h, "kernel_w": kernel_w,
                  "stride_h": stride_h, "stride_w": stride_w,
                  "padding_h": padding_h, "padding_w": padding_w,
                  "pool_type": PoolType(pool_type),
                  "activation": ActiMode(activation)}
        return self._add_layer(OperatorType.OP_POOL2D, [input], params,
                               name).outputs[0]

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
                  dtype: DataType = DataType.DT_FLOAT,
                  shared_op=None, kernel_initializer=None,
                  name: Optional[str] = None) -> Tensor:
        params = {"num_entries": num_entries, "out_dim": out_dim,
                  "aggr": AggrMode(aggr), "dtype": DataType(dtype)}
        if kernel_initializer is not None:
            params["kernel_initializer"] = kernel_initializer
        return self._add_layer(OperatorType.OP_EMBEDDING, [input], params,
                               name).outputs[0]

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int,
                            kdim: int = 0, vdim: int = 0, dropout: float = 0.0,
                            bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False, causal: bool = False,
                            rope: bool = False, rope_theta: float = 10000.0,
                            num_kv_heads: int = 0,
                            sliding_window: int = 0,
                            kernel_initializer=None,
                            qk_norm: bool = False,
                            qk_norm_eps: float = 1e-6,
                            positions: Optional[Tensor] = None,
                            indexer: Optional[dict] = None,
                            output_gate: bool = False,
                            sm_scale: Optional[float] = None,
                            rotary_dim: Optional[int] = None,
                            qk_norm_zero_centered: bool = False,
                            differential: Optional[dict] = None,
                            kv_out: bool = False,
                            kv_projected: bool = False,
                            block_diffusion_block: int = 0,
                            name: Optional[str] = None
                            ) -> Union[Tensor, Tuple[Tensor, ...]]:
        """Multi-head attention (``ops.nn_ops.MultiHeadAttentionOp``):
        ``num_heads`` query heads of ``kdim / num_heads`` on
        ``num_kv_heads`` key/value heads (0: as many), through the
        output projection to ``embed_dim``. ``causal`` masks the keys
        after a query, ``sliding_window`` also those more than that
        many positions before it (on the full training or eval forward
        the flash kernels draw the band themselves, from 1,024
        positions on a TPU; prefill and decode keep XLA and the
        ring-buffer cache). ``rope`` turns q and k by ``positions``
        ((batch, seq) int32; default 0 .. seq - 1) and ``qk_norm`` puts
        an RMSNorm with one learned scale a projection on every query
        and key head before it; each may be set without the other (a
        layer with ``qk_norm`` and no ``rope`` is a NoPE layer, and takes
        no ``positions``). ``output_gate``: the heads' outputs are
        multiplied, element by element, by the sigmoid of a projection
        of the query input with a weight of its own (``wg``, input x
        heads x head size, no bias), before the output projection.
        ``sm_scale``: what the scores are multiplied by before the
        softmax (None: ``1 / sqrt(head size)``; a model that publishes
        its own multiplier gives it here; not built beside an indexer
        or on the ring path). ``rotary_dim`` (None: the head size): the
        rotary embedding turns the first ``rotary_dim`` entries of each
        head among themselves and passes the rest (a model's
        ``partial_rotary_factor`` times its head size; such a layer
        keeps the plain chain, not ``kernels/qk_norm_rope``; not built
        beside an indexer or on the ring path).
        ``qk_norm_zero_centered``: the q/k norms multiply by ``1 + w``
        with ``w`` drawn at 0. ``indexer``: learned sparse attention
        (below). ``differential`` (``{"lambda_init": x}``, optionally
        ``"eps"``): differential attention, adjacent heads in pairs, two
        softmaxes a pair over a value twice as wide, their difference
        under a learned scalar that starts at ``lambda_init``, an
        RMSNorm over the pair's values (``MultiHeadAttentionOp.
        _emit_differential``); causal, with or without a window, and
        nothing else of the above but ``bias`` and ``num_kv_heads``.
        Such a layer may hand its projected keys and values on
        (``kv_out``: the result is ``(output, k, v)``, k and v (batch,
        seq, kv heads, head size) after the bias) and may take another
        layer's in their place (``kv_projected``: ``key`` and ``value``
        are such tensors, and the layer has no ``wk``, ``wv``).
        ``block_diffusion_block`` (``B`` > 0): self-attention over ``2
        L`` positions, a noised copy of ``L`` tokens and then the clean
        one, under the block-diffusion mask (a noised query sees the
        noised keys of its own block of ``B`` and the clean keys of
        earlier blocks, a clean query the clean keys of its own and
        earlier blocks; ``kernels.flash_attention``'s
        ``block_diffusion``, drawn inside the flash kernels where ``L``
        is a multiple of 128, an explicit mask elsewhere). Not
        ``causal``; ``rope`` is allowed, and ``positions`` may then be
        (batch, L): both halves turn by them, so a noised token and its
        clean copy turn alike. Not built beside a window, an indexer,
        differential attention, an output gate, dropout, the ring path
        or a key/value cache."""
        params = {"embed_dim": embed_dim, "num_heads": num_heads,
                  "kdim": kdim, "vdim": vdim, "dropout": dropout,
                  "bias": bias, "add_bias_kv": add_bias_kv,
                  "add_zero_attn": add_zero_attn, "causal": causal}
        if num_kv_heads and num_kv_heads != num_heads:
            # grouped-query attention (LLaMA-2/3 family): kv projections
            # and the KV cache carry num_kv_heads head groups
            if num_heads % num_kv_heads != 0:
                raise ValueError(
                    f"num_kv_heads {num_kv_heads} must divide "
                    f"num_heads {num_heads}")
            params["num_kv_heads"] = int(num_kv_heads)
        if sliding_window:
            # Mistral-family local attention: queries see the last
            # `sliding_window` positions only (requires causal)
            if not causal:
                raise ValueError("sliding_window requires causal "
                                 "attention")
            if sliding_window <= 0:
                raise ValueError(
                    f"sliding_window must be positive, "
                    f"got {sliding_window}")
            params["sliding_window"] = int(sliding_window)
        if rope:
            # in-op rotary embeddings (LLaMA family; enables the fused
            # flash-attention and KV-decode paths for RoPE models)
            params["rope"] = True
            params["rope_theta"] = float(rope_theta)
        if qk_norm:
            # an RMSNorm over each query and key head's entries, one
            # learned scale a projection, before the rotary embedding
            params["qk_norm"] = True
            params["qk_norm_eps"] = float(qk_norm_eps)
            if qk_norm_zero_centered:
                params["qk_norm_zero_centered"] = True
        elif qk_norm_zero_centered:
            raise ValueError("qk_norm_zero_centered is read by "
                             "qk_norm=True only")
        if rotary_dim is not None:
            head = (kdim or embed_dim) // num_heads
            if not rope or indexer or rotary_dim % 2 \
                    or not 0 < rotary_dim <= head:
                raise ValueError(
                    f"rotary_dim {rotary_dim}: an even share of a head of "
                    f"{head}, on a rope=True layer with no indexer")
            if rotary_dim != head:
                params["rotary_dim"] = int(rotary_dim)
        if indexer:
            # learned sparse attention (``ops/sparse_attention``): each
            # query attends the ``topk`` keys its index scores select
            if not causal or dropout or sliding_window:
                raise ValueError("an indexer selects among the causal "
                                 "keys of a layer with no dropout and "
                                 "no window")
            for size in ("heads", "head_dim", "topk", "q_chunk"):
                if int(indexer[size]) < 1:
                    raise ValueError(f"indexer {size} = {indexer[size]}")
                params["indexer_" + size] = int(indexer[size])
        if output_gate:
            if indexer:
                raise ValueError("an output gate beside an indexer is "
                                 "not built")
            params["output_gate"] = True
        if sm_scale is not None:
            if indexer or not sm_scale > 0:
                raise ValueError(f"sm_scale {sm_scale}: a positive "
                                 f"multiplier, on a layer with no indexer")
            params["sm_scale"] = float(sm_scale)
        if differential is not None:
            unbuilt = [k for k, v in (
                ("rope", rope), ("qk_norm", qk_norm), ("indexer", indexer),
                ("output_gate", output_gate), ("sm_scale", sm_scale),
                ("dropout", dropout), ("not causal", not causal)) if v]
            kvh = num_kv_heads or num_heads
            if unbuilt or num_heads % 2 or kvh % 2 \
                    or (num_heads // 2) % (kvh // 2) \
                    or (kdim or embed_dim) != (vdim or embed_dim):
                raise ValueError(
                    f"differential attention: causal, {num_heads} query "
                    f"heads on {kvh} key/value heads in adjacent pairs, "
                    f"one head size; not built beside {unbuilt}")
            params["differential"] = True
            params["lambda_init"] = float(differential["lambda_init"])
            params["subln_eps"] = float(differential.get("eps", 1e-5))
        elif kv_out or kv_projected:
            raise ValueError("kv_out and kv_projected are built for "
                             "differential attention only")
        if kv_projected:
            want = (query.shape[0], query.shape[1],
                    num_kv_heads or num_heads,
                    (kdim or embed_dim) // num_heads)
            if tuple(key.shape) != want or tuple(value.shape) != want:
                raise ValueError(
                    f"kv_projected: keys {key.shape} and values "
                    f"{value.shape} in heads, {want}, are wanted")
            params["kv_projected"] = True
            # whose keys and values: the trace says so (``attn.diff``)
            params["kv_source"] = getattr(key.owner_layer, "name", "input")
        if kv_out:
            params["kv_out"] = True
        if block_diffusion_block:
            unbuilt = [k for k, v in (
                ("causal", causal), ("sliding_window", sliding_window),
                ("indexer", indexer), ("differential", differential),
                ("output_gate", output_gate), ("dropout", dropout)) if v]
            length, odd = divmod(query.shape[1], 2)
            if unbuilt or block_diffusion_block < 0 or odd \
                    or length % block_diffusion_block \
                    or key is not query or value is not query:
                raise ValueError(
                    f"block_diffusion_block {block_diffusion_block}: "
                    f"self-attention over 2 L positions, L a multiple of "
                    f"the block (got {query.shape[1]}); not built beside "
                    f"{unbuilt}")
            params["block_diffusion_block"] = int(block_diffusion_block)
        inputs = [query, key, value]
        if positions is not None:
            # (batch, seq) int32: what the rotary embedding turns by
            # (without it: 0 .. seq - 1)
            if not rope:
                raise ValueError("positions are read by rope=True only")
            inputs.append(positions)
        outputs = self._add_layer(OperatorType.OP_MULTIHEAD_ATTENTION,
                                  inputs, params, name).outputs
        return tuple(outputs) if kv_out else outputs[0]

    def gated_short_conv(self, input: Tensor, taps: int,
                         name: Optional[str] = None) -> Tensor:
        """A gated short convolution (``ops.nn_ops.GatedShortConvOp``):
        ``[B ; C ; x] = u w_in``, a causal depthwise convolution of
        ``taps`` positions over ``B * x``, ``y = (C * conv) w_out``, all
        at the input's width."""
        if taps < 1:
            raise ValueError(f"a convolution of {taps} taps")
        return self._unary(OperatorType.OP_GATED_SHORT_CONV, input, name,
                           taps=int(taps))

    def gated_delta_rule(self, input: Tensor, num_heads: int,
                         head_dim: int, taps: int, eps: float = 1e-5,
                         num_key_heads: Optional[int] = None,
                         decay: str = "channel",
                         name: Optional[str] = None) -> Tensor:
        """A gated delta-rule linear-attention layer
        (``ops.recurrent_ops.GatedDeltaRuleOp``): ``num_heads`` heads of
        ``head_dim``, q, k and v each through a causal depthwise
        convolution of ``taps`` positions, a step size a head, a gated
        RMSNorm (``eps``) before the output projection. ``decay``:
        ``"channel"`` (Kimi Delta Attention's form: a decay a channel
        from a low-rank pair, a low-rank sigmoid gate) or ``"head"``
        (Gated DeltaNet's: one scalar a head-token, a full-rank SiLU
        gate), where ``num_key_heads`` heads of q and k (default: as
        many) may serve ``num_heads`` heads of v, each ``num_heads /
        num_key_heads`` consecutive ones."""
        if taps < 1 or num_heads < 1 or head_dim < 1:
            raise ValueError(f"{num_heads} heads of {head_dim} behind "
                             f"convolutions of {taps} taps")
        more = {}
        if decay == "head":
            more["decay"] = "head"
            if num_key_heads and num_key_heads != num_heads:
                if num_heads % num_key_heads:
                    raise ValueError(
                        f"{num_key_heads} key heads do not divide "
                        f"{num_heads} value heads")
                more["num_key_heads"] = int(num_key_heads)
        elif decay != "channel" or num_key_heads not in (None, num_heads):
            raise ValueError(
                f"decay {decay!r} with {num_key_heads} key heads: a decay "
                f"a 'channel' (as many key heads as value heads) or a "
                f"'head'")
        return self._unary(OperatorType.OP_GATED_DELTA_RULE, input, name,
                           num_heads=int(num_heads),
                           head_dim=int(head_dim), taps=int(taps),
                           eps=float(eps), **more)

    def state_space_mixer(self, input: Tensor, num_heads: int,
                          head_dim: int, state: int, taps: int,
                          chunk: int, groups: int = 1, eps: float = 1e-5,
                          name: Optional[str] = None) -> Tensor:
        """A state-space (Mamba-2) mixer
        (``ops.recurrent_ops.StateSpaceMixerOp``): ``num_heads`` heads of
        ``head_dim`` channels, each a ``head_dim x state`` state under a
        scalar decay a token; x, B and C through one causal depthwise
        convolution of ``taps`` positions with a bias; ``groups`` of B
        and C, head ``h`` reading group ``h // (num_heads / groups)``;
        computed in chunks of ``chunk`` positions, a gated RMSNorm
        (``eps``) whose mean square runs over each group's channels
        before the output projection. A share of a mixer's heads is a
        mixer of whole groups (it needs nothing of its neighbours'
        before the output projection, whose part it gives)."""
        if min(taps, num_heads, head_dim, state, chunk, groups) < 1:
            raise ValueError(
                f"{num_heads} heads of {head_dim} x {state} in {groups} "
                f"groups, chunks of {chunk}, behind a convolution of "
                f"{taps} taps")
        if num_heads % groups:
            raise ValueError(f"{num_heads} heads in {groups} groups of B "
                             f"and C: a group is whole heads")
        # a graph of one group and all its heads is the one it was
        more = {} if groups == 1 else {"groups": int(groups)}
        return self._unary(OperatorType.OP_STATE_SPACE_MIXER, input, name,
                           num_heads=int(num_heads),
                           head_dim=int(head_dim), state=int(state),
                           taps=int(taps), chunk=int(chunk),
                           eps=float(eps), **more)

    def selective_scan_mixer(self, input: Tensor, inner: int, state: int,
                             dt_rank: int, taps: int, chunk: int,
                             memory_out: bool = False,
                             name: Optional[str] = None
                             ) -> Union[Tensor, Tuple[Tensor, Tensor]]:
        """A selective-scan (Mamba-1) mixer
        (``ops.recurrent_ops.SelectiveScanMixerOp``): ``inner`` channels,
        each a state of ``state`` entries under a decay a channel and a
        state entry; x through a causal depthwise convolution of ``taps``
        positions with a bias, the step size from it through a low-rank
        pair of ``dt_rank``, B and C from it too, the output gated by
        ``silu(z)`` with no norm; token steps in rematerialised chunks
        of ``chunk`` positions. ``memory_out``: the result is ``(output,
        memory)``, the second the scan's output with the skip and BEFORE
        the gate, (batch, seq, inner), for a later layer to read."""
        if min(inner, state, dt_rank, taps, chunk) < 1:
            raise ValueError(
                f"{inner} channels of {state} state entries, a step size "
                f"of rank {dt_rank}, in chunks of {chunk} behind a "
                f"convolution of {taps} taps")
        more = {"memory_out": True} if memory_out else {}
        outputs = self._add_layer(
            OperatorType.OP_SELECTIVE_SCAN_MIXER, [input],
            dict(inner=int(inner), state=int(state), dt_rank=int(dt_rank),
                 taps=int(taps), chunk=int(chunk), **more), name).outputs
        return tuple(outputs) if memory_out else outputs[0]

    def latent_attention(self, input: Tensor, positions: Tensor,
                         num_heads: int, q_rank: Optional[int],
                         kv_rank: int, nope_dim: int, rope_dim: int,
                         v_dim: int, rope_theta: float = 10000.0,
                         eps: float = 1e-6, rope: bool = True,
                         rope_scaling: Optional[dict] = None,
                         name: Optional[str] = None) -> Tensor:
        """Causal multi-head latent attention (``ops.nn_ops.
        LatentAttentionOp``): low-rank q (``q_rank``; None: one full
        projection and no norm) and kv (``kv_rank``) with a norm on each
        latent, q/k heads of ``nope_dim + rope_dim`` (rotary on the last
        ``rope_dim``, one rotary key shared by the heads; ``rope=False``:
        those entries as they are), v heads of ``v_dim``.
        ``positions``: (batch, seq) int32, what the rotary embedding
        turns by. ``rope_scaling``: the published ``config.json`` group
        of a YaRN-rescaled rotary embedding (``type: "yarn"``, ``factor``,
        ``original_max_position_embeddings``, ``beta_fast``,
        ``beta_slow``, ``mscale``, ``mscale_all_dim``): blended
        frequencies and a larger score scale (``ops.nn_ops.
        rope_frequencies``, ``yarn_mscale``); None: neither."""
        params = dict(num_heads=num_heads, q_rank=q_rank, kv_rank=kv_rank,
                      nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
                      rope_theta=float(rope_theta), eps=eps)
        if not rope:
            params["rope"] = False
        if rope_scaling:
            if rope_scaling.get("type") != "yarn" or not rope:
                raise ValueError(
                    f"rope_scaling {rope_scaling} (rope={rope}): only "
                    f"type 'yarn' on a rotary embedding is built")
            params["rope_scaling"] = dict(rope_scaling)
        return self._add_layer(OperatorType.OP_LATENT_ATTENTION,
                               [input, positions], params,
                               name).outputs[0]

    def hyper_connection_pre(self, streams: Tensor, iters: int = 20,
                             eps: float = 1e-6, norm_eps: float = 1e-6,
                             clamp: Tuple[float, float] = (-30.0, 30.0),
                             name: Optional[str] = None
                             ) -> Tuple[Tensor, Tensor, Tensor]:
        """The reading half of a hyper-connected sub-layer (``ops.
        hyper_ops.HyperConnectionOp``): from the residual ``streams``
        (batch, seq, n, hidden) the sub-layer's input ``Hpre X`` (batch,
        seq, hidden), the maps ``[Hpost ; Hres]`` of every token and the
        streams again, both for :meth:`hyper_connection_post` (which
        takes them from here so that this node is the incoming streams'
        one consumer). ``iters`` Sinkhorn-Knopp
        iterations with ``eps`` in each denominator over ``Hres~``
        clipped to ``clamp``; ``norm_eps`` under the root of the
        streams' mean square. The draw of its weights is the op's own
        (``hyper_ops.MAPS_DRAW``)."""
        if len(streams.shape) != 4:
            raise ValueError(f"streams of shape {streams.shape}: "
                             f"(batch, seq, streams, hidden) is wanted")
        if iters < 1 or not clamp[0] < clamp[1]:
            raise ValueError(f"{iters} iterations, clamp {clamp}")
        return tuple(self._add_layer(
            OperatorType.OP_HYPER_CONNECTION, [streams],
            dict(stage="pre", iters=int(iters), eps=float(eps),
                 norm_eps=float(norm_eps),
                 clamp=[float(clamp[0]), float(clamp[1])]), name).outputs)

    def hyper_connection_post(self, streams: Tensor, output: Tensor,
                              maps: Tensor,
                              name: Optional[str] = None) -> Tensor:
        """The writing half: ``Hres X + Hpost^T output``, the new
        streams, from the maps and the ``streams``
        :meth:`hyper_connection_pre` gave."""
        return self._add_layer(OperatorType.OP_HYPER_CONNECTION,
                               [streams, output, maps],
                               dict(stage="post"), name).outputs[0]

    def routed_experts(self, input: Tensor, num_experts: int, top_k: int,
                       expert_dim: int, shared_dim: int = 0,
                       experts_held: Optional[int] = None,
                       first_held: int = 0, scale: float = 1.0,
                       bias_std: float = 0.0, rows_factor: int = 2,
                       scoring: str = "sigmoid",
                       shared_gate: bool = False,
                       choice_bias: bool = True,
                       router_repeats: int = 1,
                       latent: int = 0,
                       activation: str = "swiglu",
                       bias_step: float = 0.0,
                       name: Optional[str] = None) -> Tensor:
        """One sparse, dropless mixture-of-experts feed-forward layer
        (``ops.moe_ops.RoutedExpertsOp``): ``scoring`` (``"sigmoid"``
        with a bias-corrected choice, or ``"softmax"`` with none) over
        ``num_experts``, the top ``top_k``, experts of width
        ``expert_dim`` and a shared one of ``shared_dim`` (0: none).
        ``activation``: ``"swiglu"``, three matrices an expert
        (``w_down(silu(w_gate x) * w_up x)``), or ``"relu2"``, two
        (``w_down relu(w_up x)^2``, no gate matrix), in the routed
        experts and the shared one alike. ``latent`` l > 0: the routed
        experts read ``x w_latent_in`` (l wide) and their weighted sum
        goes back through ``w_latent_out`` (l to hidden), both weights
        the op's; the router and the shared expert read ``x``.
        ``experts_held`` (default: all) and ``first_held`` say which
        experts' weights live here: the layer routes over all of them
        and computes the part of the result that its own give.
        ``rows_factor``: the rows the grouped products are handed, in
        uniform shares of the held experts (``RoutedExpertsOp.
        rows_multiplied``). ``shared_gate``: the shared expert's output
        times ``sigmoid(x . w_s)``, one scalar a token from a weight of
        its own. ``choice_bias`` false: the op has no ``bias`` weight
        (softmax scores only, whose choice reads none).
        ``router_repeats`` r: the router is INITIALISED with its first
        ``num_experts / r`` columns drawn and repeated r times, so that
        at those weights every token scores alike in each of r shares
        of the experts (its top ``top_k`` lie evenly over the shares
        where r divides ``top_k``); the op's mathematics is unchanged.
        ``bias_step`` u > 0: the choice's bias follows the balancing
        rule, ``bias_i -= u * sign(load_i - mean load)`` after every
        training step, over all ``num_experts``; 0: it stays as drawn."""
        held = num_experts if experts_held is None else experts_held
        if not 0 <= first_held <= first_held + held <= num_experts:
            raise ValueError(
                f"experts {first_held}..{first_held + held} are not "
                f"among the {num_experts} the router scores")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        if rows_factor < 1:
            raise ValueError(f"a row budget of {rows_factor} shares")
        if scoring not in ("sigmoid", "softmax") \
                or (scoring == "softmax" and bias_std):
            raise ValueError(f"scores by {scoring!r} with a choice bias "
                             f"of spread {bias_std}")
        if activation not in ("swiglu", "relu2") or latent < 0:
            raise ValueError(f"experts of activation {activation!r} in a "
                             f"latent of {latent}")
        more = {} if rows_factor == 2 else {"rows_factor": int(rows_factor)}
        if scoring != "sigmoid":
            more["scoring"] = scoring
        if shared_gate:
            if not shared_dim:
                raise ValueError("shared_gate without a shared expert")
            more["shared_gate"] = True
        if not choice_bias:
            if scoring != "softmax":
                raise ValueError(f"scores by {scoring!r} read a choice "
                                 f"bias")
            more["choice_bias"] = False
        if router_repeats != 1:
            if router_repeats < 1 or num_experts % router_repeats:
                raise ValueError(f"router_repeats {router_repeats} of "
                                 f"{num_experts} experts")
            more["router_repeats"] = int(router_repeats)
        if latent:
            more["latent"] = int(latent)
        if activation != "swiglu":
            more["activation"] = activation
        if bias_step:
            if bias_step < 0 or scoring != "sigmoid":
                raise ValueError(f"a bias step of {bias_step} under "
                                 f"scores by {scoring!r}")
            more["bias_step"] = float(bias_step)
        return self._unary(OperatorType.OP_ROUTED_EXPERTS, input, name,
                           num_experts=num_experts, top_k=top_k,
                           expert_dim=expert_dim, shared_dim=shared_dim,
                           experts_held=held, first_held=first_held,
                           scale=float(scale), bias_std=float(bias_std),
                           **more)

    def next_token_loss(self, logits: Tensor, ids: Tensor, offset: int,
                        weight: float,
                        name: Optional[str] = None) -> Tensor:
        """Add ``weight`` x the mean cross-entropy of ``logits[:, t]``
        against ``ids[:, t + offset]`` to the training loss
        (``ops.nn_ops.NextTokenLossOp``)."""
        return self._add_layer(OperatorType.OP_NEXT_TOKEN_LOSS,
                               [logits, ids],
                               {"offset": int(offset),
                                "weight": float(weight)}, name).outputs[0]

    def block_diffusion_noise(self, ids: Tensor, block_length: int,
                              mask_token_id: int, t_min: float = 1e-3,
                              eval_noise_seed: int = 0,
                              name: Optional[str] = None
                              ) -> Tuple[Tensor, Tensor]:
        """``(z_ids, weights)`` of a block-diffusion training step
        (``ops.nn_ops.BlockDiffusionNoiseOp``): (batch, 2 L) ids, a copy
        of ``ids`` with tokens replaced by ``mask_token_id`` (each with
        its block's probability ``t``) and then ``ids`` as they are, and
        each token's loss weight ``masked / t``, (batch, L). The draw is
        the step's in training and ``eval_noise_seed``'s otherwise."""
        if block_length < 1 or not 0.0 < t_min <= 1.0:
            raise ValueError(f"blocks of {block_length} tokens, t_min "
                             f"{t_min}")
        return tuple(self._add_layer(
            OperatorType.OP_BLOCK_DIFFUSION_NOISE, [ids],
            {"block_length": int(block_length),
             "mask_token_id": int(mask_token_id), "t_min": float(t_min),
             "eval_noise_seed": int(eval_noise_seed)}, name).outputs)

    def set_loss_weights(self, weights: Tensor) -> None:
        """Name the tensor whose entries weigh the rows of the training
        loss: with it the sparse cross-entropy is ``sum(w * nll) /
        rows`` in place of the mean (``runtime.losses.compute_loss``);
        metrics that count rows weigh nothing. ``weights`` has the
        output's shape less its last axis. Call before ``compile``."""
        self._loss_weights_tensor = weights

    def batch_norm(self, input: Tensor, relu: bool = True,
                   eps: float = 1e-5, momentum: float = 0.1,
                   name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_BATCHNORM, input, name, relu=relu,
                           eps=eps, momentum=momentum)

    def layer_norm(self, input: Tensor, axes: Sequence[int],
                   elementwise_affine: bool = True, eps: float = 1e-5,
                   name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_LAYERNORM, input, name,
                           axes=list(axes),
                           elementwise_affine=elementwise_affine, eps=eps)

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 name: Optional[str] = None,
                 zero_centered: bool = False) -> Tensor:
        """``x / rms(x) * scale``; ``zero_centered``: ``* (1 + scale)``
        with ``scale`` drawn at 0."""
        more = {"zero_centered": True} if zero_centered else {}
        return self._unary(OperatorType.OP_RMSNORM, input, name, eps=eps,
                           **more)

    def lstm(self, input: Tensor, hidden_size: int, num_layers: int = 1,
             name: Optional[str] = None) -> Tensor:
        """Multi-layer LSTM over (batch, seq, features) — lax.scan
        recurrence (reference: legacy nmt/lstm.cu app)."""
        return self._unary(OperatorType.OP_LSTM, input, name,
                           hidden_size=hidden_size, num_layers=num_layers)

    def batch_matmul(self, a: Tensor, b: Tensor,
                     a_seq_length_dim: int = -1, b_seq_length_dim: int = -1,
                     name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.OP_BATCHMATMUL, [a, b],
                               {"a_seq_length_dim": a_seq_length_dim,
                                "b_seq_length_dim": b_seq_length_dim},
                               name).outputs[0]

    def softmax(self, input: Tensor, axis: int = -1,
                name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_SOFTMAX, input, name, axis=axis)

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0,
                name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_DROPOUT, input, name, rate=rate,
                           seed=seed)

    def flat(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_FLAT, input, name)

    def concat(self, tensors: Sequence[Tensor], axis: int,
               name: Optional[str] = None) -> Tensor:
        return self._add_layer(OperatorType.OP_CONCAT, list(tensors),
                               {"axis": axis}, name).outputs[0]

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]],
              axis: int, name: Optional[str] = None) -> List[Tensor]:
        if isinstance(sizes, int):
            n = input.shape[axis % len(input.shape)] // sizes
            sizes = [n] * sizes
        return self._add_layer(OperatorType.OP_SPLIT, [input],
                               {"sizes": list(sizes), "axis": axis},
                               name).outputs

    def reshape(self, input: Tensor, shape: Sequence[int],
                name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_RESHAPE, input, name,
                           shape=list(shape))

    def transpose(self, input: Tensor, perm: Sequence[int],
                  name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_TRANSPOSE, input, name,
                           perm=list(perm))

    def reverse(self, input: Tensor, axis: int,
                name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_REVERSE, input, name, axis=axis)

    # ---- elementwise binary ----
    def add(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_ADD, x, y, name)

    def subtract(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_SUB, x, y, name)

    def multiply(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_MUL, x, y, name)

    def divide(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_DIV, x, y, name)

    def max(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_MAX, x, y, name)

    def equal(self, x, y, name=None):
        """Elementwise equality (DT_BOOLEAN output, broadcasting) —
        reference OP_EW_EQUAL (onnx Equal)."""
        return self._binary(OperatorType.OP_EW_EQUAL, x, y, name)

    def greater(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_GREATER, x, y, name)

    def less(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_LESS, x, y, name)

    def min(self, x, y, name=None):
        return self._binary(OperatorType.OP_EW_MIN, x, y, name)

    # ---- elementwise unary ----
    def relu(self, x, name=None):
        return self._unary(OperatorType.OP_RELU, x, name)

    def sigmoid(self, x, name=None):
        return self._unary(OperatorType.OP_SIGMOID, x, name)

    def tanh(self, x, name=None):
        return self._unary(OperatorType.OP_TANH, x, name)

    def elu(self, x, name=None):
        return self._unary(OperatorType.OP_ELU, x, name)

    def gelu(self, x, name=None):
        return self._unary(OperatorType.OP_GELU, x, name)

    def identity(self, x, name=None):
        return self._unary(OperatorType.OP_IDENTITY, x, name)

    def exp(self, x, name=None):
        return self._unary(OperatorType.OP_EXP, x, name)

    def log(self, x, name=None):
        return self._unary(OperatorType.OP_LOG, x, name)

    def sqrt(self, x, name=None):
        return self._unary(OperatorType.OP_SQRT, x, name)

    def rsqrt(self, x, name=None):
        return self._unary(OperatorType.OP_RSQRT, x, name)

    def sin(self, x, name=None):
        return self._unary(OperatorType.OP_SIN, x, name)

    def cos(self, x, name=None):
        return self._unary(OperatorType.OP_COS, x, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OperatorType.OP_POW, x, name, scalar=exponent)

    def scalar_multiply(self, x, scalar: float, inplace=False, name=None):
        return self._unary(OperatorType.OP_SCALAR_MULTIPLY, x, name,
                           scalar=scalar)

    def scalar_add(self, x, scalar: float, inplace=False, name=None):
        return self._unary(OperatorType.OP_SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x, scalar: float, inplace=False, name=None):
        return self._unary(OperatorType.OP_SCALAR_SUB, x, name, scalar=scalar)

    def scalar_true_divide(self, x, scalar: float, inplace=False, name=None):
        return self._unary(OperatorType.OP_SCALAR_TRUE_DIV, x, name,
                           scalar=scalar)

    def cast(self, x, dtype: DataType, name=None):
        return self._unary(OperatorType.OP_CAST, x, name,
                           dtype=DataType(dtype))

    def mean(self, x, dims: Sequence[int], keepdims: bool = False, name=None):
        return self._unary(OperatorType.OP_MEAN, x, name, axes=list(dims),
                           keepdims=keepdims)

    def reduce_sum(self, x, axes: Sequence[int], keepdims: bool = False,
                   name=None):
        return self._unary(OperatorType.OP_REDUCE_SUM, x, name,
                           axes=list(axes), keepdims=keepdims)

    def slice_tensor(self, x: Tensor, starts: Sequence[int],
                     ends: Sequence[int], axes: Optional[Sequence[int]] = None,
                     name=None):
        return self._unary(OperatorType.OP_SLICE, x, name,
                           starts=list(starts), ends=list(ends),
                           axes=list(axes) if axes is not None else
                           list(range(len(starts))))

    def squeeze(self, x: Tensor, axes: Sequence[int], name=None):
        return self._unary(OperatorType.OP_SQUEEZE, x, name, axes=list(axes))

    def unsqueeze(self, x: Tensor, axes: Sequence[int], name=None):
        return self._unary(OperatorType.OP_UNSQUEEZE, x, name,
                           axes=list(axes))

    def pad(self, x: Tensor, pads: Sequence[Tuple[int, int]],
            value: float = 0.0, name=None):
        return self._unary(OperatorType.OP_PAD, x, name,
                           pads=[tuple(p) for p in pads], value=value)

    def gather(self, x: Tensor, index: Tensor, dim: int = 0, name=None):
        return self._add_layer(OperatorType.OP_GATHER, [x, index],
                               {"dim": dim}, name).outputs[0]

    def top_k(self, input: Tensor, k: int, sorted: bool = False,
              name: Optional[str] = None) -> List[Tensor]:
        return self._add_layer(OperatorType.OP_TOPK, [input],
                               {"k": k, "sorted": sorted}, name).outputs

    # ---- MoE family (reference src/ops/moe.cc:20-44) ----
    def group_by(self, input: Tensor, assign: Tensor, n: int,
                 alpha: float = 1.0, name: Optional[str] = None
                 ) -> List[Tensor]:
        return self._add_layer(OperatorType.OP_GROUP_BY, [input, assign],
                               {"n": n, "alpha": alpha}, name).outputs

    def aggregate(self, inputs: Sequence[Tensor], n: int,
                  lambda_bal: float = 0.0, name: Optional[str] = None
                  ) -> Tensor:
        return self._add_layer(OperatorType.OP_AGGREGATE, list(inputs),
                               {"n": n, "lambda_bal": lambda_bal},
                               name).outputs[0]

    def aggregate_spec(self, inputs: Sequence[Tensor], n: int,
                       lambda_bal: float = 0.0, name: Optional[str] = None
                       ) -> Tensor:
        return self._add_layer(OperatorType.OP_AGG_SPEC, list(inputs),
                               {"n": n, "lambda_bal": lambda_bal},
                               name).outputs[0]

    def cache(self, input: Tensor, num_batches: int, score_fn=None,
              name: Optional[str] = None) -> Tensor:
        return self._unary(OperatorType.OP_CACHE, input, name,
                           num_batches=num_batches)

    def moe(self, input: Tensor, num_exp: int, num_select: int,
            expert_hidden_size: int, alpha: float = 1.0,
            lambda_bal: float = 0.0) -> Tensor:
        """MoE composite — same wiring as reference ``FFModel::moe``
        (``src/ops/moe.cc:20-44``)."""
        gate_preds = self.dense(input, num_exp, ActiMode.AC_MODE_RELU)
        topk_out = self.top_k(gate_preds, num_select, False)
        exp_tensors = self.group_by(input, topk_out[1], num_exp, alpha)
        agg_inputs = [self.softmax(topk_out[0]), topk_out[1], topk_out[1],
                      gate_preds]
        for i in range(num_exp):
            exp_pred = self.dense(exp_tensors[i], expert_hidden_size,
                                  ActiMode.AC_MODE_RELU)
            agg_inputs.append(self.softmax(exp_pred))
        return self.aggregate(agg_inputs, num_exp, lambda_bal)

    # ==================================================================
    # optimizer / compile / fit (reference model.cc:2803, cffi fit)
    # ==================================================================
    def set_optimizer(self, optimizer: Optimizer):
        self.optimizer = optimizer

    optimizer_prop = property(lambda s: s.optimizer, set_optimizer)

    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: Union[LossType, str, None] = None,
                metrics: Optional[Sequence[Union[MetricsType, str]]] = None,
                comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
                machine_spec: Optional[MachineSpec] = None,
                strategy: Optional[ShardingStrategy] = None,
                output_tensor: Optional[Tensor] = None,
                search_budget: Optional[int] = None):
        """Lower graph → (strategy, jitted step). Reference call stack:
        ``FFModel::compile`` → graph_optimize → convert_graph_to_operators
        → NCCL setup (``model.cc:2803-3168``).

        The whole call is the recorder's span ``model.compile`` and its
        phases are ``compile.mesh`` / ``search`` / ``plan`` / ``verify``
        / ``init`` / ``opt_state`` inside it (docs/observability.md,
        "Set-up and compiles"). ``_compile_phases`` holds the seconds of
        this call's search, verify and init phases and of the whole,
        recorder on or off, each the very reading its span carries."""
        obs_events.configure(self.config)
        self._compile_phases: Dict[str, float] = {}
        with obs_events.timed_span("model.compile") as whole:
            self._compile(optimizer, loss_type, metrics, comp_mode,
                          machine_spec, strategy, output_tensor,
                          search_budget)
            whole.set(n_devices=self.dmesh.num_devices,
                      n_layers=len(self.layers))
        self._compile_phases["compile_s"] = round(whole.dur, 6)
        # one a compile() CALL, whatever XLA then built or found cached:
        # XLA's own builds are the recorder's ``xla.compiles/<fun_name>``
        # and ``xla.cache_hits`` / ``xla.cache_misses`` (obs/xla_events.py)
        from .obs.metrics_registry import REGISTRY
        REGISTRY.counter("ff_model_compiles_total", _COMPILES_HELP).inc(
            model=getattr(self, "_model_name", "") or "<unnamed>")

    @contextlib.contextmanager
    def _compile_phase(self, key: str, ndigits: int):
        """One phase of ``compile()``: the recorder's span
        ``compile.<key>`` and ``_compile_phases["<key>_s"]``, both from
        one reading of the clock at each end."""
        with obs_events.timed_span("compile." + key) as phase:
            yield phase
        self._compile_phases[key + "_s"] = round(phase.dur, ndigits)

    def _compile(self, optimizer, loss_type, metrics, comp_mode,
                 machine_spec, strategy, output_tensor, search_budget):
        """The body of :meth:`compile`, under its span."""
        # phase -> typed reason, for every compile phase that was
        # allowed to fail and did (search/optimizer.py note_skip)
        self._compile_skips: Dict[str, str] = {}
        if optimizer is not None:
            self.optimizer = optimizer
        if self.optimizer is None:
            self.optimizer = SGDOptimizer(lr=self.config.learning_rate)
        if isinstance(loss_type, str):
            loss_type = _LOSS_NAMES[loss_type.lower()]
        self.loss_type = LossType(loss_type) if loss_type is not None \
            else LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY
        self.metrics = [
            _METRIC_NAMES[m.lower()] if isinstance(m, str) else MetricsType(m)
            for m in (metrics or [])]

        # output tensor = last layer's first output unless specified
        self._output_tensor = output_tensor or self.layers[-1].outputs[0]

        # Partition created tensors into graph inputs (consumed by a layer)
        # and the label tensor (created but unconsumed) — reference compile
        # creates the label tensor itself (model.cc:3086).
        consumed = {t.guid for l in self.layers for t in l.inputs}
        # constants (attached host values) are baked in at trace time, not
        # fed per batch
        self.graph_inputs = [t for t in self.input_tensors
                             if t.guid in consumed
                             and t.get_tensor() is None]
        self.const_inputs = [t for t in self.input_tensors
                             if t.guid in consumed
                             and t.get_tensor() is not None]
        unconsumed = [t for t in self.input_tensors
                      if t.guid not in consumed
                      and t.guid not in self._may_be_unread
                      and t.get_tensor() is None]
        if self.label_tensor is None and len(unconsumed) == 1:
            self.label_tensor = unconsumed[0]

        with obs_events.span("compile.mesh"):
            # join the multi-host world first (reference: GASNet launch +
            # control replication happen before graph_optimize) so that
            # MachineSpec.detect sees the GLOBAL device view
            from .parallel.distributed import maybe_initialize
            if maybe_initialize(self.config):
                # multi-process world: start the failure-detection layer
                # (per-rank heartbeats + bounded barriers) alongside it —
                # every later cross-rank wait goes through it
                from .resilience import coord
                c = coord.ensure_started(self.config)
                try:
                    # clock handshake for cross-rank trace alignment
                    # (tools/fftrace.py): one bounded barrier, every rank
                    # anchors its monotonic clock at the release instant.
                    # Unconditional — every rank reaches compile, so the
                    # rendezvous can never depend on per-rank trace flags
                    c.clock_sync("compile")
                except Exception:  # noqa: BLE001 — alignment is best-effort
                    pass
            # (after the rendezvous: asking for the platform starts the
            # backend, which jax.distributed must precede)
            from .utils.compilation_cache import enable_compilation_cache
            enable_compilation_cache()
            if machine_spec is not None:
                spec = machine_spec
            elif self.config.machine_model_file:
                # --machine-model-file: the described machine drives the cost
                # model / simulator / topology (reference machine_model.cc);
                # execution is clamped to the live devices
                spec = MachineSpec.from_file(self.config.machine_model_file)
                import jax
                spec.num_devices = min(spec.num_devices, len(jax.devices()))
            else:
                spec = MachineSpec.detect()
        mesh_shape = self.config.mesh_shape
        pp = self.config.pipeline_stages
        pp_tp = max(self.config.pipeline_tp, 1)
        if pp_tp > 1 and pp <= 1:
            raise ValueError(
                f"--pp-tp {pp_tp} requires --pp > 1 (stage-internal "
                f"tensor parallelism only exists inside a pipeline); "
                f"for tp without pipelining use a transformer_strategy "
                f"or the search")
        if mesh_shape is None and pp <= 1 and strategy is None \
                and self.config.machine_model_file \
                and not self.config.import_strategy_file \
                and getattr(spec, "ici_shape", None) \
                and int(np.prod(spec.ici_shape)) == spec.num_devices:
            # the described machine's ICI topology drives the mesh layout
            # (reference machine_model.cc: the machine file IS the view).
            # Strategy imports keep the default factorization — the
            # saved mesh_axes must keep matching what compile builds.
            mesh_shape = tuple(spec.ici_shape)
        if strategy is None and pp > 1 and mesh_shape is None:
            # dp × pp (× tp) mesh: middle axis carries the pipeline
            # stages, trailing axis the stage-internal tensor split
            nd = spec.num_devices
            if nd % (pp * pp_tp) != 0:
                raise ValueError(f"--pp {pp} x --pp-tp {pp_tp} does "
                                 f"not divide {nd} devices")
            mesh_shape = tuple(
                d for d in (nd // (pp * pp_tp), pp, pp_tp) if d > 1)
        seq_par = max(int(getattr(self.config, "seq_parallel_degree", 0)
                          or 0), 0)
        if seq_par > 1 and (pp > 1 or self.config.tensor_parallel > 1):
            raise ValueError(
                "--seq-parallel (the reserved ring-attention axis) does "
                "not compose with --pp/--tp presets; use the search")
        self.dmesh = DeviceMesh(spec, mesh_shape=mesh_shape,
                                seq=seq_par)
        if search_budget is not None:
            self.config.search_budget = search_budget

        exec_layers, exec_outputs = self.layers, [self._output_tensor]
        tp_deg = max(self.config.tensor_parallel, 1)
        if self.config.sequence_parallel and tp_deg <= 1:
            raise ValueError(
                "--sp requires --tp N (N > 1): the sequence dim is "
                "sharded over the tensor-parallel axes")
        if tp_deg > 1 and pp > 1:
            raise ValueError(
                "--tp does not compose with --pp directly; use --pp-tp "
                "for Megatron tp inside pipeline stages")
        if strategy is None and tp_deg > 1:
            # --tp/--sp: the Megatron dp x tp (x sp) preset directly,
            # no search (reference --enable-parameter-parallel analog
            # made a first-class mode). An existing mesh (explicit
            # --mesh-shape or the machine file's ICI shape) is kept and
            # validated; otherwise a (dp, tp) mesh is built.
            from .parallel.presets import transformer_strategy
            nd = self.dmesh.num_devices
            if nd % tp_deg != 0:
                raise ValueError(
                    f"--tp {tp_deg} does not divide {nd} devices")
            if mesh_shape is None:
                self.dmesh = DeviceMesh(
                    spec, mesh_shape=tuple(
                        d for d in (nd // tp_deg, tp_deg) if d > 1))
            axes = self.dmesh.axis_names
            # trailing axes must realize EXACTLY the requested degree
            tp_axes: list = []
            prod = 1
            for ax in reversed(axes):
                if prod == tp_deg:
                    break
                tp_axes.insert(0, ax)
                prod *= self.dmesh.axis_sizes[ax]
            if prod != tp_deg:
                raise ValueError(
                    f"--tp {tp_deg} not realizable from the trailing "
                    f"axes of mesh {dict(self.dmesh.axis_sizes)} "
                    f"(they give {prod}); pass a compatible --mesh-shape")
            dp_axes = tuple(a for a in axes if a not in tp_axes)
            strategy = transformer_strategy(
                self.layers, self.input_tensors, self.dmesh,
                dp_axes=dp_axes, tp_axes=tuple(tp_axes),
                sp=self.config.sequence_parallel)
        if strategy is None and pp > 1:
            # pipeline through the product path (reference reserves
            # OP_PIPELINE, ffconst.h:159, without implementing it);
            # axes resolved by position to keep dp/pp/tp unambiguous
            # when sizes coincide
            from .parallel.presets import pipeline_strategy
            kw = {}
            if self.config.mesh_shape is None:
                # we built the mesh as (dp, pp, tp) above — bind axes by
                # position (size-matching is ambiguous when sizes tie);
                # an explicit --mesh-shape keeps the size-match default
                nd = self.dmesh.num_devices
                sizes = (nd // (pp * pp_tp), pp, pp_tp)
                roles = [r for r, d in zip(("dp", "pp", "tp"), sizes)
                         if d > 1]
                by_role = dict(zip(roles, self.dmesh.axis_names))
                kw = dict(pp_axis=by_role["pp"],
                          tp_axis=by_role.get("tp"),
                          dp_axes=(by_role["dp"],) if "dp" in by_role
                          else ())
            strategy = pipeline_strategy(
                self.layers, self.graph_inputs, self.dmesh, n_stages=pp,
                n_microbatches=self.config.pipeline_microbatches,
                n_chunks=self.config.pipeline_chunks, tp=pp_tp,
                ragged=self.config.pipeline_ragged, **kw)
        if strategy is not None:
            self.strategy = strategy
        else:
            with self._compile_phase("search", 3):
                self.strategy, program_info = self._optimize_strategy()
            if self.strategy.dmesh is not self.dmesh:
                # the search chose a strategy on its own mesh layout
                # (e.g. a (dp, S) pipeline mesh) — adopt it
                self.dmesh = self.strategy.dmesh
            if program_info is not None:
                # search rewrote the graph (inserted parallel ops) —
                # reference convert_graph_to_operators (model.cc:2834)
                exec_layers = program_info.layers
                exec_outputs = program_info.output_tensors
                self._output_tensor = exec_outputs[0]

        with obs_events.span("compile.plan"):
            # label tensor adopts the final op's batch sharding
            # (reference model.cc:3086-3124)
            prebuilt = getattr(self, "_prebuilt_executor", None)
            if prebuilt is not None and prebuilt[0] is self.strategy \
                    and prebuilt[1] is not None:
                # the floor guard already compiled this exact program
                # (same strategy object, same metrics) — adopt its executor
                # so the jitted train step is not rebuilt; params/state are
                # re-initialized below
                self.executor = prebuilt[1]
                self._prebuilt_executor = None
            else:
                program = GraphProgram(exec_layers,
                                       self.graph_inputs + self.const_inputs,
                                       exec_outputs)
                self.executor = Executor(
                    program, self.config, self.dmesh, self.strategy,
                    self.optimizer, self.loss_type, self.metrics,
                    seed=self.config.seed,
                    loss_weights=getattr(self, "_loss_weights_tensor", None))
            # searched data movement: one reshard planner per strategy plans
            # every layout transition (bank boundaries, pipeline-region
            # entry/exit, layout-op output constraints) with scored explicit
            # collectives; chosen step sequences annotate the strategy audit
            from .parallel.reshard import ReshardPlanner
            pl = getattr(self.strategy, "resharder", None)
            if pl is None or pl.dmesh is not self.dmesh:
                pl = ReshardPlanner(self.dmesh)
                self.strategy.resharder = pl
            pl.audit_path = getattr(self, "_strategy_audit_path", None)
            # overlap (runtime/overlap.py): multi-leg tier-staged reshard
            # plans execute with their fabric legs pipelined when on
            from .runtime.overlap import overlap_enabled
            pl.overlap_on = overlap_enabled(self.config)
            if self.config.export_strategy_file \
                    and getattr(self.strategy, "overlap", None):
                # the search exported before the executor built the bucket
                # schedule (same ordering as banks/zero): rewrite the
                # overlap section so --import round-trips the exact
                # schedule this compile audited and verified
                try:
                    import json as _json
                    with open(self.config.export_strategy_file) as f:
                        doc = _json.load(f)
                    doc["overlap"] = dict(self.strategy.overlap)
                    with open(self.config.export_strategy_file, "w") as f:
                        _json.dump(doc, f, indent=1)
                except Exception:  # noqa: BLE001 — export is best-effort
                    pass
            # per-parameter ZeRO (search/zero_plan.py, arXiv 2004.13336):
            # score each parameter's update path (replicated all-reduce vs
            # reduce-scatter + sharded update + all-gather over the placed
            # tier path) and adopt an assignment under the device-memory
            # envelope. Runs BEFORE plan verification so the verifier's
            # memory envelope and zero-soundness checks bind on the
            # assignment the run will actually use. The uniform --zero flag
            # bypasses this entirely (pinned legacy behavior below).
            self._plan_zero()
            # quantized gradient collectives (ops/quantized_collectives.py,
            # arXiv 2506.17615): plan per-tensor/per-phase wire dtypes for
            # gradient sync, scored by the same calibrated cost model.
            # Runs BEFORE plan verification so the qsync check binds on the
            # plan the run will actually use.
            self._plan_qsync()
            # forced kernel impls (kernels/registry.py): adopt what
            # --kernel-impl forces or an imported strategy carries
            # (attention xla/flash/ring). Runs BEFORE plan verification so
            # the kernel check and the seq-aware memory envelope bind on
            # the impls the run will actually execute.
            self._plan_kernels()
        # static plan verification (analysis/plan_verifier.py): prove
        # the adopted strategy executable — axis soundness, shard
        # divisibility, legal reshard lowerings at every seam, memory
        # envelope, collective-order consistency — BEFORE params
        # materialize; an unsound plan raises PlanVerificationError
        # with the op/seam attributed instead of miscompiling later
        if self.config.plan_verify \
                and os.environ.get("FF_PLAN_VERIFY", "") != "0":
            from .analysis.plan_verifier import verify_model
            with self._compile_phase("verify", 6):
                report = verify_model(self)
            self._plan_verify_report = report
        # init/materialization separated from search: on a virtual
        # many-device CPU mesh the replicated-shard host copies
        # dominate, which would misattribute wall time to the search
        with self._compile_phase("init", 3):
            self.params, self.state = self.executor.init_params_and_state()
        with obs_events.span("compile.opt_state") as sp:
            self.opt_state = self.optimizer.init_state(self.params)
            self._place_opt_state()
            if obs_events.enabled():
                sp.set(device_bytes=device_bytes(self.opt_state))
        self._step = 0

    def _place_opt_state(self):
        """The fresh optimizer state onto the plan's placement: ZeRO's
        sharded moments, the quantized sync's residuals."""
        if self.config.shard_optimizer_states and self.opt_state:
            # ZeRO-1: moments sharded over the axes their weight is
            # replicated on (runtime/zero.py); the executor pins the
            # updated state to the same placement inside the step
            from .runtime.zero import (shard_optimizer_state,
                                       state_constraints)
            self.opt_state = shard_optimizer_state(self.opt_state,
                                                   self.dmesh)
            self.executor.opt_state_constraints = \
                state_constraints(self.opt_state)
        elif self.opt_state and getattr(self.strategy, "zero", None):
            # per-parameter searched assignment: only the leaves the
            # plan shards move; the executor pins the updated state to
            # the assigned specs in-jit so GSPMD lowers the update to
            # reduce-scatter + sharded math + all-gather per leaf
            from .runtime.zero import (shard_optimizer_state,
                                       state_constraints)
            self.opt_state = shard_optimizer_state(
                self.opt_state, self.dmesh, self.strategy.zero)
            self.executor.opt_state_constraints = \
                state_constraints(self.opt_state)
        if getattr(self.executor, "_qsync", None) is not None \
                and isinstance(self.opt_state, dict):
            # error-feedback residuals for the quantized grad sync:
            # sharding-aware runtime state seeded at zero, one
            # (degree,) + shape leaf per quantized tensor, riding the
            # optimizer-state tree (checkpointed with it; the executor
            # strips the slot before the optimizer update)
            from .ops import quantized_collectives as qsync_mod
            res = qsync_mod.init_residuals(
                self.executor._qsync, self.executor.program, self.dmesh)
            if res:
                self.opt_state[qsync_mod.RESIDUAL_SLOT] = res

    def _optimize_strategy(self):
        """Strategy selection: search unless --only-data-parallel.
        Returns (strategy, program_info_or_None) — Unity search may rewrite
        the executable graph."""
        # On one device the search still matters when a budget is set
        # explicitly: algebraic substitutions (fusions/eliminations) can
        # rewrite the graph even without parallelism choices.
        single_no_budget = (self.dmesh.num_devices == 1
                            and self.config.search_budget <= 0)
        if self.config.only_data_parallel or single_no_budget \
                or self.config.search_algo == "dp":
            return ShardingStrategy.data_parallel(
                self.layers, self.graph_inputs, self.dmesh), None
        import importlib.util
        if importlib.util.find_spec("flexflow_tpu.search") is None:
            return ShardingStrategy.data_parallel(
                self.layers, self.graph_inputs, self.dmesh), None
        from .search.optimizer import optimize_strategy
        return optimize_strategy(self)

    def _plan_zero(self):
        """Adopt a per-parameter optimizer-state sharding assignment
        (``FFConfig.zero_policy``, search/zero_plan.py). An assignment
        already on the strategy (``--import`` round-trip) is honored
        as-is; the legacy uniform ``--zero`` flag bypasses planning
        entirely (its behavior is pinned bit-identical)."""
        cfg = self.config
        if self.strategy is None:
            return
        if self.config.shard_optimizer_states:
            self.strategy.zero = None
            return
        if getattr(self.strategy, "zero", None) is not None:
            return  # imported with the strategy: honor it verbatim
        policy = str(getattr(cfg, "zero_policy", "off") or "off").lower()
        if policy in ("off", "false", "no", ""):
            return
        if policy not in ("auto", "memory", "all"):
            raise ValueError(
                f"unknown zero_policy {policy!r} "
                f"(expected off/auto/memory/all)")
        from .runtime.zero import opt_slots
        if self.dmesh.num_devices <= 1 \
                or opt_slots(self.optimizer) <= 0:
            return
        if getattr(self.strategy, "pipeline", None) is not None:
            # pipelined regions stack their parameters (and state)
            # under template keys the per-layer assignment cannot
            # address — claiming savings the runtime can't realize
            # would make the memory envelope optimistic; skip
            return
        from .search.zero_plan import audit_record, plan_zero_assignment
        cost_model = getattr(self, "_search_cost_model", None)
        if cost_model is None or cost_model.spec is not self.dmesh.spec:
            # non-searched paths (DP preset, --tp, pipeline presets):
            # a bare cost model over the machine spec, placement-aware
            # on multi-tier machines so the collectives price against
            # their real fabric tier (PR 9)
            from .search.costmodel import OpCostModel
            from .search.optimizer import _attach_placement
            cost_model = OpCostModel(self.dmesh.spec)
            _attach_placement(cfg, cost_model, self.dmesh)
        hbm = float(cfg.device_mem_mb) * (1 << 20) \
            if getattr(cfg, "device_mem_mb", 0) \
            else getattr(self.dmesh.spec, "hbm_bytes", None)
        assignment = plan_zero_assignment(
            self.strategy, self.executor.program.layers, self.dmesh,
            cost_model, self.optimizer, policy=policy,
            overhead_frac=getattr(cfg, "zero_overhead_frac", 0.05),
            hbm_bytes=hbm)
        self.strategy.zero = assignment
        if assignment is None:
            return
        record = audit_record(assignment)
        self._zero_record = record
        audit_path = getattr(self, "_strategy_audit_path", None)
        if audit_path:
            from .obs.audit import annotate_strategy_audit
            annotate_strategy_audit(audit_path, {"zero": record})
        if cfg.export_strategy_file:
            # the search exported before the assignment existed (same
            # ordering as banks): rewrite the zero section so --import
            # round-trips the per-parameter decision
            try:
                import json as _json
                with open(cfg.export_strategy_file) as f:
                    doc = _json.load(f)
                doc["zero"] = assignment.to_json()
                with open(cfg.export_strategy_file, "w") as f:
                    _json.dump(doc, f, indent=1)
            except Exception:  # noqa: BLE001 — export is best-effort
                pass
        if cfg.profiling:
            s = assignment.summary()
            print(f"zero plan ({policy}): {s['n_sharded']}/"
                  f"{s['n_params']} opt states sharded, "
                  f"{s['bytes_saved_total'] / 2**20:.2f} MiB/device "
                  f"saved, predicted overhead "
                  f"{s['overhead_s_total'] * 1e3:.3f} ms/step")

    def _plan_qsync(self):
        """Adopt a per-tensor, per-phase quantized grad-sync plan
        (``FFConfig.quantized_collectives``, ops/quantized_collectives.
        py). A plan already on the strategy (``--import`` round-trip)
        is honored verbatim; ``off`` (the default) leaves the implicit
        full-precision sync untouched — bit-exact."""
        cfg = self.config
        if self.strategy is None:
            return
        from .ops.quantized_collectives import (audit_record, plan_qsync,
                                                qsync_disabled,
                                                resolve_qsync_mode,
                                                resolve_qsync_wire)
        if getattr(self.strategy, "qsync", None) is not None:
            if qsync_disabled(cfg):
                # explicit disable (--no-quantized-collectives /
                # FF_QUANTIZED_COLLECTIVES=off) beats an imported
                # plan: the user asked for the full-precision path —
                # the A/B knob against an exported quantized strategy
                import logging
                logging.getLogger("flexflow_tpu").warning(
                    "stripping the imported strategy's quantized-"
                    "collectives plan (explicitly disabled)")
                self.strategy.qsync = None
            # else: imported with the strategy — honor it verbatim.
            # Either way the executor may predate the resolution, so
            # re-resolve the runtime schedule.
            self.executor.attach_qsync()
            return
        mode = resolve_qsync_mode(cfg)
        if mode == "off" or self.dmesh.num_devices <= 1:
            return
        wire = resolve_qsync_wire(cfg)
        cost_model = getattr(self, "_search_cost_model", None)
        if cost_model is None or cost_model.spec is not self.dmesh.spec:
            # non-searched paths (DP preset, --tp): a bare cost model,
            # placement-aware on multi-tier machines so DCN legs price
            # against their real fabric tier (PR 9)
            from .search.costmodel import OpCostModel
            from .search.optimizer import _attach_placement
            cost_model = OpCostModel(self.dmesh.spec)
            _attach_placement(cfg, cost_model, self.dmesh)
        cost_model.attach_quantization(mode, wire)
        plan = plan_qsync(self.strategy, self.executor.program.layers,
                          self.dmesh, cost_model, mode=mode, wire=wire)
        self.strategy.qsync = plan
        self.executor.attach_qsync()
        if plan is None:
            return
        if not getattr(self.strategy, "axis_tiers", None):
            # make the exported artifact self-describing: the plan's
            # per-phase tiers were derived from the mesh — record the
            # axis→tier map the verifier (and a later --import on a
            # different machine) checks the quantized legs against
            try:
                self.strategy.axis_tiers = dict(self.dmesh.axis_tiers)
            except Exception:  # noqa: BLE001 — tierless machine
                pass
        record = audit_record(plan)
        self._qsync_record = record
        audit_path = getattr(self, "_strategy_audit_path", None)
        if audit_path:
            from .obs.audit import annotate_strategy_audit
            annotate_strategy_audit(audit_path,
                                    {"quantized_sync": record})
        if cfg.export_strategy_file:
            # the search exported before the plan existed (same
            # ordering as banks/zero/overlap): rewrite the qsync
            # section so --import round-trips the decision
            try:
                import json as _json
                with open(cfg.export_strategy_file) as f:
                    doc = _json.load(f)
                doc["qsync"] = plan.to_json()
                with open(cfg.export_strategy_file, "w") as f:
                    _json.dump(doc, f, indent=1)
            except Exception:  # noqa: BLE001 — export is best-effort
                pass
        if cfg.profiling:
            s = plan.summary()
            print(f"qsync plan ({mode}, wire {wire}): "
                  f"{s['n_quantized']}/{s['n_params']} grad syncs "
                  f"quantized, predicted "
                  f"{s['baseline_s_total'] * 1e3:.3f} -> "
                  f"{s['quantized_s_total'] * 1e3:.3f} ms/step")

    def _plan_kernels(self):
        """Adopt forced kernel implementations (kernels/registry.py).
        With nothing forced every op chooses its own kernel from its
        shapes and there is no plan. An assignment already on the
        strategy (``--import`` round-trip) is honored verbatim; a forced
        choice (``--kernel-impl`` / ``FF_KERNEL_IMPL``) is held to its
        availability predicate — forcing ``ring`` on a mesh without a
        sequence axis is a typed compile-time error attributed to the
        op."""
        cfg = self.config
        if self.strategy is None or self.executor is None:
            return
        strat = self.strategy
        if getattr(strat, "kernel_impls", None):
            # imported with the strategy: honor verbatim — the plan
            # verifier re-checks every predicate on this mesh/shapes
            self.executor.set_kernel_impls(strat.kernel_impls)
            return
        policy = str(getattr(cfg, "kernel_impls", "auto") or "auto").lower()
        if policy in ("off", "none"):
            return
        from .kernels import registry as kreg
        forced = kreg.resolve_forced(cfg)
        if not forced:
            return
        if getattr(strat, "pipeline", None) is not None:
            # pipeline stages emit inside their own shard_map region —
            # the ring collective cannot nest there and the kernel ctx
            # is not threaded through stage emission; keep the ops' rules
            import logging
            logging.getLogger("flexflow_tpu").warning(
                "kernel impls are not planned under pipeline "
                "parallelism; ignoring forced %s", dict(forced))
            return
        impl = forced[kreg.ATTENTION]   # the registry's one kind
        seq_deg = int(getattr(self.dmesh, "seq_degree", 0) or 0)
        # the kind key reaches every attention op of whatever kind
        # (ops/nn_ops.py::MultiHeadAttentionOp._impl_for); the layers
        # named below also get the predicate check and an audit row
        plan: Dict[str, str] = {kreg.ATTENTION: impl}
        audit_ops: List[Dict] = []
        for layer in self.executor.program.layers:
            ctx = kreg.layer_ctx(layer, seq_deg)
            if ctx is None:
                continue
            reason = kreg.get_impl(kreg.ATTENTION, impl).available(ctx)
            if reason is not None:
                raise ValueError(
                    f"{layer.name}: forced kernel impl "
                    f"attention:{impl} is not available on this "
                    f"mesh/shapes: {reason}")
            audit_ops.append({"name": layer.name, "op": kreg.ATTENTION,
                              "impl": impl, "forced": True})
            plan[layer.name] = impl
        strat.kernel_impls = plan
        # the executor snapshotted (the then-empty) strategy.kernel_impls
        # at construction — refresh so the jitted step traces the plan
        self.executor.set_kernel_impls(plan)
        record = {"policy": policy, "backend": jax.default_backend(),
                  "seq_degree": seq_deg, "n_ops": len(audit_ops),
                  "ops": audit_ops}
        self._kernel_record = record
        audit_path = getattr(self, "_strategy_audit_path", None)
        if audit_path:
            from .obs.audit import annotate_strategy_audit
            annotate_strategy_audit(audit_path, {"kernels": record})
        if cfg.export_strategy_file:
            # the search exported before the assignment existed (same
            # ordering as banks/zero/overlap/qsync): rewrite the
            # kernel_impls section so --import round-trips it verbatim
            try:
                import json as _json
                with open(cfg.export_strategy_file) as f:
                    doc = _json.load(f)
                doc["kernel_impls"] = dict(plan)
                with open(cfg.export_strategy_file, "w") as f:
                    _json.dump(doc, f, indent=1)
            except Exception:  # noqa: BLE001 — export is best-effort
                pass
        if cfg.profiling:
            print(f"kernel plan ({policy}): {len(audit_ops)} attention "
                  f"ops forced onto {impl}")

    # ------------------------------------------------------------------
    def create_data_loader(self, tensor: Tensor, data: np.ndarray):
        """Reference ``FFModel.create_data_loader`` parity: registers the
        full array for one tensor; fit() shards batches from it."""
        data = np.ascontiguousarray(data)
        self._dataloaders.append((tensor, data))
        return (tensor, data)

    def _combined_loader(self, x=None, y=None,
                         batch_size: Optional[int] = None,
                         shuffle: bool = True) -> SingleDataLoader:
        bs = batch_size or self.config.batch_size
        arrays: Dict[str, np.ndarray] = {}
        graph_inputs = getattr(self, "graph_inputs", self.input_tensors)
        if x is not None or y is not None:
            xs = x if isinstance(x, (list, tuple)) else [x]
            fed = [t for t in self.input_tensors if t in graph_inputs
                   or t.guid in self._may_be_unread]
            if len(xs) == len(fed) != len(graph_inputs):
                # an array for an input that no layer reads is dropped
                xs = [a for t, a in zip(fed, xs) if t in graph_inputs]
            if len(xs) != len(graph_inputs):
                raise ValueError(f"{len(xs)} arrays for "
                                 f"{len(graph_inputs)} inputs")
            for t, arr in zip(graph_inputs, xs):
                arrays[t.name] = np.ascontiguousarray(arr)
            arrays["label"] = np.ascontiguousarray(y)
        else:
            gi_guids = {t.guid for t in graph_inputs}
            for t, arr in self._dataloaders:
                is_label = (t is self.label_tensor
                            or t.guid not in gi_guids)
                arrays["label" if is_label else t.name] = arr
        shardings = self.strategy.batch_shardings(graph_inputs,
                                                  self._output_tensor)
        return SingleDataLoader(arrays, bs, shardings, shuffle=shuffle,
                                seed=self.config.seed,
                                prefetch=self.config.prefetch_batches)

    def fit(self, x=None, y=None, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, callbacks=None, verbose=True):
        """Training loop (reference ``flexflow_cffi.py:2062-2104``; Legion
        trace ≙ jit cache).

        Async dispatch: per-step metrics stay device-resident in a
        :class:`MetricsBuffer` and are fetched in ONE ``device_get`` at
        ``print_freq``/epoch boundaries (the reference gets the same
        overlap from Legion's deferred futures); a bounded in-flight
        window (``config.async_dispatch_steps``) keeps the host from
        racing ahead. ``FF_SYNC_EVERY_STEP=1`` restores the old
        fetch-every-step loop for debugging."""
        if self.executor is None:
            raise ValueError("call compile() first")
        epochs = epochs or self.config.epochs
        loader = self._combined_loader(x, y, batch_size)
        history = []
        # the buffer stays attached through the epoch-end callbacks
        # (their checkpoint saves screen through it) and is detached
        # when fit ends — INCLUDING on exceptions, or a stale poisoned
        # buffer would block save_checkpoint of later clean params
        try:
            for epoch in range(epochs):
                with obs_events.span("fit.epoch", epoch=epoch) as ep_span:
                    if self._fit_epoch(epoch, loader, callbacks, verbose,
                                       history, ep_span):
                        break
        finally:
            self._metrics_buffer = None
        self._current_metrics = history[-1] if history else {}
        if self.config.trace_export_file:
            from .obs.trace_export import export_chrome_trace
            if obs_events.enabled():
                export_chrome_trace(self.config.trace_export_file)
        self._end_of_training_telemetry()
        return history

    def _fit_epoch(self, epoch: int, loader, callbacks, verbose: bool,
                   history: list, epoch_span) -> bool:
        """One epoch of :meth:`fit`, inside its ``fit.epoch`` span;
        returns whether a callback asked to stop. The loop's layer
        boundaries each get a span, and nothing else does:
        ``fit.loader_next`` (the loader), ``executor.train_step`` (the
        dispatch, ``executor.py::_instrument_step``),
        ``metrics_buffer.window_wait`` and ``metrics_buffer.flush`` (the
        device fetches, ``runtime/metrics_buffer.py``) and
        ``fit.callbacks`` (user code)."""
        # re-fetch per epoch: callbacks (e.g. LearningRateScheduler) may
        # invalidate the jitted step to apply new hyperparams
        step_fn = self.executor.make_train_step()
        pm = PerfMetrics()
        buf = MetricsBuffer.for_config(self.config, pm=pm)
        self._metrics_buffer = buf
        t0 = time.perf_counter()
        nb = 0
        batches = iter(loader)
        while True:
            # iterated by hand: a ``for`` would hide the wait for the
            # batch (the loader's reset, host slicing, the H2D put)
            with obs_events.span("fit.loader_next"):
                batch = next(batches, None)
            if batch is None:
                break
            bm = self._run_train_step(step_fn, batch)
            bsz = next(iter(batch.values())).shape[0]
            buf.push(self._step - 1, bm, bsz)
            nb += 1
            # dynamic recompilation hook (reference model.cc:2422)
            rs = getattr(self, "_recompile_state", None)
            if rs is not None and rs.step(self):
                step_fn = self.executor.make_train_step()
            pf = self.config.print_freq
            if pf > 0 and nb % pf == 0:
                # flush REGARDLESS of verbosity: print_freq is the
                # metric-fetch cadence, not just the print cadence
                # (pending device scalars must not pile up for a whole
                # quiet epoch)
                buf.flush()
                if verbose:
                    rep = pm.report()
                    msg = " ".join(f"{k}={v:.4f}" for k, v in rep.items())
                    print(f"epoch {epoch} iter "
                          f"{nb}/{loader.num_batches} {msg}")
        buf.flush()
        dt = time.perf_counter() - t0
        rep = pm.report()
        rep["epoch_time_s"] = dt
        rep["samples_per_sec"] = pm.train_all / dt if dt > 0 else 0.0
        epoch_span.set(batches=nb)
        from .obs.metrics_registry import REGISTRY
        REGISTRY.gauge(
            "ff_train_samples_per_sec",
            "Training throughput of the last completed epoch"
        ).set(rep["samples_per_sec"])
        history.append(rep)
        if verbose:
            msg = " ".join(f"{k}={v:.4f}" for k, v in rep.items())
            print(f"epoch {epoch} done: {msg}")
        stop = False
        if callbacks:
            with obs_events.span("fit.callbacks"):
                for cb in callbacks:
                    cb.on_epoch_end(epoch, rep, self)
                    stop = stop or getattr(cb, "stop_requested", False)
        return stop

    def _end_of_training_telemetry(self) -> None:
        """End-of-training observability hooks shared by :meth:`fit`
        and the resilience Supervisor: the step-time attribution
        harness (``FF_ATTRIB`` — profiles the compiled plan once and
        writes the measured side + drift report next to the predicted
        audit breakdown) and the per-rank ring dump that
        ``tools/fftrace.py`` merges across a multi-process world. Both
        best-effort, both strictly after the last step — zero per-step
        cost."""
        from .obs import attribution as obs_attrib
        from .obs import events as obs_events
        if obs_attrib.attribution_enabled(self.config):
            try:
                obs_attrib.run_attribution(self)
            except Exception as e:  # noqa: BLE001 — never kill training
                import logging
                logging.getLogger("flexflow_tpu").warning(
                    "attribution failed: %r", e)
        if obs_events.enabled():
            import jax
            from .obs.events import _env_on
            if jax.process_count() > 1 \
                    or _env_on(os.environ.get("FF_TRACE_DUMP")):
                from .obs.trace_export import dump_rank_trace
                dump_rank_trace()

    def _run_train_step(self, step_fn, batch):
        # fault-injection sites (resilience/faults.py): crash/device-loss
        # clauses fire BEFORE the step runs, NaN/Inf gradient-corruption
        # clauses poison the state after; active() is one cached check,
        # so fault-free runs pay nothing measurable
        from .resilience import coord, faults
        coord.check()  # surface a detected peer-rank failure pre-step
        if faults.active():
            faults.raise_pending(self._step)
        self.params, self.opt_state, self.state, bm = step_fn(
            self.params, self.opt_state, self.state,
            jnp.int32(self._step), batch)
        if faults.active():
            bad = faults.poison_value(self._step)
            if bad is not None:
                poison = jnp.float32(bad)
                self.params = jax.tree.map(
                    lambda a: (a * poison).astype(a.dtype)
                    if jnp.issubdtype(a.dtype, jnp.inexact) else a,
                    self.params)
                # the in-jit all_finite flag saw the CLEAN loss; the
                # host-side poison must flip it or the deferred NaN
                # screen would wave the poisoned step through
                bm = dict(bm, loss=poison,
                          all_finite=jnp.logical_and(
                              bm.get("all_finite", True),
                              jnp.isfinite(poison)))
        self._step += 1
        return bm

    # phase-level API parity (forward/backward/update as in model.cc)
    def forward(self, batch=None, seq_length: int = -1):
        fwd = self.executor.make_forward()
        if batch is None:
            batch = self._peek_batch()
        self._last_fwd = fwd(self.params, self.state, batch)
        return self._last_fwd

    def generate(self, prompt_ids, prompt_len: "int | np.ndarray",
                 max_new_tokens: int, temperature: float = 0.0,
                 seed: int = 0, extra_inputs=None,
                 eos_token_id: int | None = None,
                 kv_cache: Union[bool, str] = "auto",
                 top_k: int = 0, top_p: float = 1.0):
        """Autoregressive generation for causal LMs (GPT-2 / LLaMA /
        transformer-LM family; the reference has no generation path —
        its Triton backend serves fixed forwards only).

        ``prompt_ids``: (batch, seq_len) int32, the prompt in columns
        [0, prompt_len) and anything (e.g. zeros) after. ``prompt_len``
        may be a (batch,) int array for RAGGED prompts — each row
        decodes from its own length (the batched-serving case). ``temperature``
        0 = greedy argmax, > 0 = sampling from the pre-softmax logits
        (numerically exact — no re-log of already-softmaxed probs).
        ``eos_token_id``: rows that emit it keep emitting it for the
        remaining steps (the scan length stays static — standard jit
        practice). Returns the completed (batch, seq_len) ids.

        ``kv_cache``: "auto" (default) decodes incrementally against a
        per-layer K/V cache — one prefill forward then one O(1)-length
        forward per token — when the graph supports it (causal
        multihead-attention layers, no pipeline region, inputs limited
        to input_ids/position_ids), silently falling back to the exact
        full-re-forward path otherwise. True forces the KV path (raises
        when unsupported), False forces the re-forward oracle."""
        if self.executor is None:
            raise ValueError("call compile() first")
        ids0 = jnp.asarray(prompt_ids, jnp.int32)
        b, L = ids0.shape
        if np.ndim(prompt_len) > 0:
            # ragged prompts: one length per batch row
            prompt_len = np.asarray(prompt_len, np.int32)
            if prompt_len.shape != (b,):
                raise ValueError(
                    f"ragged prompt_len must have shape ({b},), got "
                    f"{prompt_len.shape}")
            if not ((prompt_len >= 1).all()
                    and (prompt_len + max_new_tokens <= L).all()):
                raise ValueError(
                    f"each prompt_len must satisfy 1 <= len and "
                    f"len + max_new_tokens <= {L}; got {prompt_len} "
                    f"with max_new_tokens={max_new_tokens}")
        else:
            if prompt_len < 1:
                raise ValueError(
                    "prompt_len must be >= 1 (the first token "
                    "conditions decode)")
            if prompt_len + max_new_tokens > L:
                raise ValueError(
                    f"prompt_len {prompt_len} + max_new_tokens "
                    f"{max_new_tokens} exceeds the sequence length {L}")
        self._refuse_block_diffusion_decode()
        names = {t.name for t in self.graph_inputs}
        fixed = {k: jnp.asarray(v)
                 for k, v in (extra_inputs or {}).items()}
        if "position_ids" in names and "position_ids" not in fixed:
            fixed["position_ids"] = jnp.tile(
                jnp.arange(L, dtype=jnp.int32)[None], (b, 1))

        # failed KV attempts are remembered per (batch, seq) shape — the
        # unit of trace/compile — so repeated auto-mode requests at a
        # failing shape don't re-pay the attempt, while other shapes
        # (e.g. shorter prompts that fit) still get the KV path
        kv_failed_shapes = getattr(self.executor, "_kv_failed_shapes",
                                   None)
        if kv_failed_shapes is None:
            kv_failed_shapes = self.executor._kv_failed_shapes = set()
        want_kv = kv_cache if isinstance(kv_cache, bool) \
            else (self._kv_decode_eligible(names, extra_inputs)
                  and (b, L) not in kv_failed_shapes)
        if want_kv:
            try:
                return self._generate_kv(ids0, prompt_len, max_new_tokens,
                                         temperature, seed, eos_token_id,
                                         top_k, top_p)
            except Exception as e:
                if kv_cache is True:
                    raise
                kv_failed_shapes.add((b, L))
                self.__dict__.setdefault("_kv_fallback_reasons", {})[
                    (b, L)] = f"{type(e).__name__}: {e}"[:500]
                # the fallback is exact but O(L)-per-token — a serving
                # deployment quietly riding it is a perf regression, so
                # it is observable (Prometheus + /healthz), not just a
                # warn-once log line
                self._kv_fallback_count = getattr(
                    self, "_kv_fallback_count", 0) + 1
                from .obs.metrics_registry import REGISTRY
                REGISTRY.counter(
                    "ff_kv_fallback_total",
                    "KV-cache decode attempts that fell back to the "
                    "full re-forward path").inc(
                        model=getattr(self, "_model_name", "")
                        or "<unnamed>")
                import logging
                logging.getLogger("flexflow_tpu").warning(
                    "KV-cache decode failed for this graph at shape "
                    "(%d, %d); falling back to full re-forward "
                    "generation (cached: subsequent auto-mode calls at "
                    "this shape skip the KV attempt)", b, L,
                    exc_info=True)
        return self._generate_reforward(ids0, prompt_len, max_new_tokens,
                                        temperature, seed, eos_token_id,
                                        fixed, top_k, top_p)

    def _kv_decode_eligible(self, names, extra_inputs) -> bool:
        """KV decode needs: no pipeline region, inputs limited to
        input_ids(+position_ids), and every attention layer a causal
        OP_MULTIHEAD_ATTENTION (primitive-built attention, e.g. LLaMA's
        explicit-mask batch_matmul form, carries baked seq-length
        constants that a length-1 trace cannot satisfy)."""
        if self.executor.pipe is not None or extra_inputs:
            return False
        if not names <= {"input_ids", "position_ids"}:
            return False
        mha = [l for l in self.executor.program.layers
               if l.op_type == OperatorType.OP_MULTIHEAD_ATTENTION]
        # differential attention has no cache of its own, and none in
        # which layers share one layer's keys and values: the re-forward
        # path decodes such a model
        return bool(mha) and all(l.params.get("causal", False)
                                 and not l.params.get("differential")
                                 for l in mha)

    def _refuse_block_diffusion_decode(self) -> None:
        """A model trained under the block-diffusion mask yields a BLOCK
        a decoding step and commits its cache a block at a time; neither
        ``generate`` path does that, and next-token sampling from such a
        model would be another model's text."""
        if any(l.params.get("block_diffusion_block")
               or l.op_type == OperatorType.OP_BLOCK_DIFFUSION_NOISE
               for l in self.executor.program.layers):
            raise NotImplementedError(
                "generate() cannot decode a block-diffusion model yet: a "
                "step has to yield a block and the key/value cache commit "
                "a block at a time (ROADMAP.md, reach queue)")

    def _generate_kv(self, ids0, prompt_len, max_new_tokens, temperature,
                     seed, eos_token_id, top_k=0, top_p=1.0):
        """Incremental decode: one full-sequence prefill builds the
        per-layer K/V cache, then each generated token is one seq-len-1
        forward — per-token cost independent of how many tokens have
        been generated (the re-forward path is O(L) per token).

        Prefill and decode are SEPARATE jitted programs so serving can
        observe the two phases the serving objective is built from: the
        prefill span is the prompt cost, the decode span divided by
        ``max_new_tokens`` is the per-token decode-step latency the
        serving search (search/serving_plan.py) ranks plans by — and
        what ``ff_decode_step_seconds{bucket=...}`` reports. The split
        also lets one prefill program serve every sampling config at a
        shape (the old fused program re-traced per temperature/top-k)."""
        ex = self.executor
        b, L = ids0.shape
        has_pos = "position_ids" in {t.name for t in self.graph_inputs}
        ragged = np.ndim(prompt_len) > 0

        def prefill(params, state, ids0, plen):
            batch = {"input_ids": ids0}
            if has_pos:
                batch["position_ids"] = jnp.tile(
                    jnp.arange(L, dtype=jnp.int32)[None], (b, 1))
            # ragged prompts keep the full cache (the ring-buffer seed
            # needs one shared prompt length); masks stay per-row exact
            _, cache = ex.kv_prefill(params, state, batch,
                                     prefill_len=None if ragged else plen)
            return cache

        def decode(params, state, ids0, cache, key0, plen):
            done0 = jnp.zeros((b,), jnp.bool_)

            def step(carry, i):
                ids, cache, key, done = carry
                cur = plen + i         # index being generated; (B,) when
                tok = self._read_token_row(ids, cur, ragged)
                if ragged:             # prompts are ragged
                    pos_in = (cur - 1)[:, None].astype(jnp.int32)
                else:
                    pos_in = jnp.full((b, 1), cur - 1, dtype=jnp.int32)
                sb = {"input_ids": tok}
                if has_pos:
                    sb["position_ids"] = pos_in
                row, cache = ex.kv_decode_step(params, state, sb, cache,
                                               cur - 1)
                key, nxt, done = self._sample_next(row, key, temperature,
                                                   eos_token_id, done,
                                                   top_k, top_p)
                ids = self._write_token(ids, nxt, cur, ragged)
                return (ids, cache, key, done), nxt

            (ids, _, _, _), _ = jax.lax.scan(
                step, (ids0, cache, key0, done0),
                jnp.arange(max_new_tokens))
            return ids

        pk = ("kv_prefill", b, L, ragged)
        dk = ("kv_decode", b, L, max_new_tokens, float(temperature),
              eos_token_id, int(top_k), float(top_p), ragged)
        prefill_fn = self._decode_cache_get(pk, prefill)
        decode_fn = self._decode_cache_get(dk, decode)
        plen = jnp.asarray(prompt_len, jnp.int32)
        from .obs import events as obs_events
        from .obs import request_trace
        from .obs.metrics_registry import DECODE_STEP_BUCKETS, REGISTRY
        t0 = time.perf_counter()
        cache = jax.block_until_ready(
            prefill_fn(self.params, self.state, ids0, plen))
        t1 = time.perf_counter()
        out = jax.block_until_ready(
            decode_fn(self.params, self.state, ids0, cache,
                      jax.random.key(seed), plen))
        t2 = time.perf_counter()
        step_s = (t2 - t1) / max(int(max_new_tokens), 1)
        # tag the phase spans with the ambient request trace (set by the
        # serving front) so a request's prefill/decode link into its
        # lifecycle; None outside a traced request — dropped by attrs
        tid = request_trace.current_id()
        span_attrs = {"trace": tid} if tid else {}
        obs_events.record_span("generate.prefill", t0, t1 - t0,
                               batch=b, seq=L, **span_attrs)
        obs_events.record_span("generate.decode", t1, t2 - t1,
                               batch=b, tokens=int(max_new_tokens),
                               **span_attrs)
        REGISTRY.histogram(
            "ff_decode_step_seconds",
            "Per-token decode-step latency by batch bucket",
            buckets=DECODE_STEP_BUCKETS).observe(step_s, bucket=str(b))
        REGISTRY.histogram(
            "ff_prefill_seconds",
            "Prompt prefill latency by batch bucket",
            buckets=DECODE_STEP_BUCKETS).observe(t1 - t0, bucket=str(b))
        # always-on measured sink for serving drift detection: the MIN
        # observed prefill/decode-step per batch size (min = closest to
        # the cost model's contention-free prediction; bounded — one
        # small dict entry per batch size ever decoded). Unlocked
        # update: worst case a concurrent generate at the same batch
        # size loses one sample, and serving sessions serialize decode
        # per instance anyway  # ffcheck: ok(guarded-field)
        rec = getattr(self, "_decode_measured", None)
        if rec is None:
            rec = self._decode_measured = {}
        old = rec.get(b)
        rec[b] = {
            "prefill_s": (t1 - t0) if old is None
            else min(old["prefill_s"], t1 - t0),
            "decode_step_s": step_s if old is None
            else min(old["decode_step_s"], step_s),
            "n": 1 if old is None else old["n"] + 1,
        }
        return out

    def generate_beam(self, prompt_ids, prompt_len: int,
                      max_new_tokens: int, num_beams: int = 4,
                      eos_token_id: int | None = None):
        """Beam-search decoding over the KV cache (deterministic; no
        length penalty — scores are summed token log-probs). Requires a
        KV-decode-eligible graph (see ``_kv_decode_eligible``); beams
        live on the batch dim (b*K rows), the cache is gathered by beam
        index each step. Returns the best (batch, seq_len) ids.

        Beyond-reference: the reference has no generation path at all;
        beam completes the greedy/temperature/top-k/top-p family."""
        if self.executor is None:
            raise ValueError("call compile() first")
        ids0 = jnp.asarray(prompt_ids, jnp.int32)
        b, L = ids0.shape
        K = int(num_beams)
        if K < 1:
            raise ValueError(f"num_beams must be >= 1, got {K}")
        if np.ndim(prompt_len) > 0:
            raise ValueError("generate_beam needs one scalar prompt_len "
                             "(per-row prompt lengths are unsupported "
                             "for beam search)")
        if prompt_len < 1:
            raise ValueError("prompt_len must be >= 1")
        if prompt_len + max_new_tokens > L:
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens} exceeds the sequence length {L}")
        names = {t.name for t in self.graph_inputs}
        if not self._kv_decode_eligible(names, None):
            raise ValueError("generate_beam requires a KV-decode-"
                             "eligible graph (causal fused attention)")
        ex = self.executor
        has_pos = "position_ids" in names
        NEG = jnp.float32(-1e30)

        def decode(params, state, ids0, plen):
            batch = {"input_ids": ids0}
            if has_pos:
                batch["position_ids"] = jnp.tile(
                    jnp.arange(L, dtype=jnp.int32)[None], (b, 1))
            _, cache = ex.kv_prefill(params, state, batch,
                                     prefill_len=plen)
            # beams on the batch dim: row r's beams are rows r*K..r*K+K-1
            ids = jnp.repeat(ids0, K, axis=0)              # (b*K, L)
            cache = jax.tree.map(lambda a: jnp.repeat(a, K, axis=0),
                                 cache)
            # all beams start identical: only beam 0 is live, so the
            # first step picks the row's top-K distinct tokens
            scores0 = jnp.tile(jnp.where(jnp.arange(K) == 0, 0.0, NEG),
                               (b,))                       # (b*K,)
            done0 = jnp.zeros((b * K,), jnp.bool_)

            def step(carry, i):
                ids, cache, scores, done = carry
                cur = plen + i
                tok = jax.lax.dynamic_slice_in_dim(ids, cur - 1, 1,
                                                   axis=1)
                sb = {"input_ids": tok}
                if has_pos:
                    sb["position_ids"] = jnp.full((b * K, 1), cur - 1,
                                                  dtype=jnp.int32)
                row, cache = ex.kv_decode_step(params, state, sb, cache,
                                               cur - 1)       # (b*K, V)
                V = row.shape[-1]
                logp = jax.nn.log_softmax(row.astype(jnp.float32),
                                          axis=-1)
                if eos_token_id is not None:
                    # a finished beam persists unchanged: only its eos
                    # continuation is allowed, at zero added cost
                    eos_only = jnp.where(
                        jnp.arange(V)[None, :] == eos_token_id, 0.0, NEG)
                    logp = jnp.where(done[:, None], eos_only, logp)
                total = scores[:, None] + logp             # (b*K, V)
                flat = total.reshape(b, K * V)
                top_s, top_i = jax.lax.top_k(flat, K)      # (b, K)
                beam = top_i // V                          # source beam
                token = (top_i % V).astype(jnp.int32)
                src = (jnp.arange(b)[:, None] * K + beam).reshape(-1)
                ids = jnp.take(ids, src, axis=0)
                cache = jax.tree.map(
                    lambda a: jnp.take(a, src, axis=0), cache)
                done = jnp.take(done, src, axis=0)
                scores = top_s.reshape(-1)
                token = token.reshape(-1)
                if eos_token_id is not None:
                    token = jnp.where(done, jnp.int32(eos_token_id),
                                      token)
                    done = jnp.logical_or(done,
                                          token == eos_token_id)
                ids = jax.lax.dynamic_update_slice_in_dim(
                    ids, token[:, None], cur, axis=1)
                return (ids, cache, scores, done), None

            (ids, _, scores, _), _ = jax.lax.scan(
                step, (ids, cache, scores0, done0),
                jnp.arange(max_new_tokens))
            best = jnp.argmax(scores.reshape(b, K), axis=-1)   # (b,)
            return ids.reshape(b, K, L)[jnp.arange(b), best]

        ck = ("beam", b, L, max_new_tokens, K, eos_token_id)
        fn = self._decode_cache_get(ck, decode)
        return fn(self.params, self.state, ids0, jnp.int32(prompt_len))

    # decode executables are cached per (shape, steps, sampling params);
    # arbitrary client-supplied floats (temperature/top_p) would grow the
    # cache without bound on a long-running server — LRU-capped
    _DECODE_CACHE_CAP = 16

    def _decode_cache_get(self, ck, builder):
        import collections
        cache = self.executor.__dict__.setdefault(
            "_decode_cache", collections.OrderedDict())
        fn = cache.get(ck)
        if fn is None:
            fn = cache[ck] = jax.jit(builder)
            # a fresh decode program counts like a compile() call: the
            # same per-model counter, so it covers the generate paths
            from .obs.metrics_registry import REGISTRY
            REGISTRY.counter("ff_model_compiles_total", _COMPILES_HELP).inc(
                model=getattr(self, "_model_name", "") or "<unnamed>")
        else:
            cache.move_to_end(ck)
        while len(cache) > self._DECODE_CACHE_CAP:
            cache.popitem(last=False)
        return fn

    @staticmethod
    def _read_token_row(arr, cur, ragged):
        """Row at position cur-1 per batch row: (B, ...) gather that
        works for scalar cur (shared position) and (B,) cur (ragged)."""
        if ragged:
            if arr.ndim == 2:      # ids (B, L)
                return jnp.take_along_axis(arr, (cur - 1)[:, None],
                                           axis=1)
            gidx = jnp.broadcast_to((cur - 1)[:, None, None],
                                    (arr.shape[0], 1, arr.shape[-1]))
            return jnp.take_along_axis(arr, gidx, axis=1)
        return jax.lax.dynamic_slice_in_dim(arr, cur - 1, 1, axis=1)

    @staticmethod
    def _write_token(ids, nxt, cur, ragged):
        """Write nxt at column cur (per-row when ragged)."""
        if ragged:
            sel = jnp.arange(ids.shape[1])[None, :] == cur[:, None]
            return jnp.where(sel, nxt[:, None], ids)
        return jax.lax.dynamic_update_slice_in_dim(ids, nxt[:, None],
                                                   cur, axis=1)

    def _sample_next(self, row, key, temperature, eos_token_id, done,
                     top_k: int = 0, top_p: float = 1.0):
        """Shared sampling step: ``row`` is (B, V) log-domain scores
        (pre-softmax logits when the graph exposes them). HF processor
        order: temperature, then top-k, then top-p (nucleus)."""
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            logits = row / temperature
            use_k = top_k and 0 < top_k < logits.shape[-1]
            if use_k or top_p < 1.0:
                # ONE descending vocab sort serves both filters: the kth
                # value is desc[:, k-1], and masking to -inf preserves
                # the survivors' descending order for the nucleus scan
                desc = jnp.sort(logits, axis=-1)[:, ::-1]
                if use_k:
                    kth = desc[:, top_k - 1][:, None]
                    logits = jnp.where(logits < kth, -jnp.inf, logits)
                    desc = jnp.where(
                        jnp.arange(desc.shape[-1])[None, :] >= top_k,
                        -jnp.inf, desc)
                if top_p < 1.0:
                    # nucleus: keep the smallest prefix of descending-
                    # prob tokens whose cumulative probability reaches p
                    probs = jax.nn.softmax(desc, axis=-1)
                    cum = jnp.cumsum(probs, axis=-1)
                    excluded = cum - probs > top_p  # prefix >= p before
                    kept = jnp.where(excluded, jnp.inf, desc)
                    thresh = jnp.min(kept, axis=-1, keepdims=True)
                    logits = jnp.where(logits < thresh, -jnp.inf, logits)
            nxt = jax.random.categorical(sub, logits, axis=-1)
        else:
            nxt = jnp.argmax(row, axis=-1)
        nxt = nxt.astype(jnp.int32)
        if eos_token_id is not None:
            eos = jnp.int32(eos_token_id)
            nxt = jnp.where(done, eos, nxt)
            done = jnp.logical_or(done, nxt == eos)
        return key, nxt, done

    def _generate_reforward(self, ids0, prompt_len, max_new_tokens,
                            temperature, seed, eos_token_id, fixed,
                            top_k=0, top_p=1.0):
        """Exact oracle path: full forward per step; the causal mask
        guarantees positions < t ignore columns >= t."""
        ex = self.executor
        b, L = ids0.shape
        ragged = np.ndim(prompt_len) > 0

        def decode(params, state, ids0, key0, fixed, plen):
            done0 = jnp.zeros((b,), jnp.bool_)

            def step(carry, i):
                ids, key, done = carry
                scores = ex.scored_forward(params, state,
                                           {"input_ids": ids, **fixed})
                cur = plen + i                # index being generated
                row = self._read_token_row(scores, cur, ragged)[:, 0, :]
                key, nxt, done = self._sample_next(row, key, temperature,
                                                   eos_token_id, done,
                                                   top_k, top_p)
                ids = self._write_token(ids, nxt, cur, ragged)
                return (ids, key, done), nxt

            (ids, _, _), _ = jax.lax.scan(
                step, (ids0, key0, done0), jnp.arange(max_new_tokens))
            return ids

        # jit cached per (shape, steps, temperature, eos, sampling,
        # fixed-input set); prompt_len is a TRACED argument so serving
        # traffic with varying prompt lengths reuses one compiled
        # program per shape
        ck = ("fwd", b, L, max_new_tokens, float(temperature),
              eos_token_id, int(top_k), float(top_p), ragged,
              tuple(sorted(fixed)))
        fn = self._decode_cache_get(ck, decode)
        return fn(self.params, self.state, ids0, jax.random.key(seed),
                  fixed, jnp.asarray(prompt_len, jnp.int32))

    def zero_gradients(self):
        pass  # grads are recomputed functionally each step

    def backward(self, seq_length: int = -1):
        pass  # fused into train step (jax.grad)

    def update(self):
        pass  # fused into train step

    def _peek_batch(self):
        loader = self._combined_loader()
        loader.reset()
        return loader.next_batch()

    def eval(self, x=None, y=None, batch_size: Optional[int] = None,
             verbose: bool = False) -> Dict[str, float]:
        loader = self._combined_loader(x, y, batch_size, shuffle=False)
        step_fn = self.executor.make_eval_step()
        pm = PerfMetrics()
        for batch in loader:
            _, bm = step_fn(self.params, self.state, batch)
            bsz = next(iter(batch.values())).shape[0]
            pm.update({k: np.asarray(v) for k, v in bm.items()}, bsz)
        rep = pm.report()
        self._current_metrics = rep
        if verbose:
            print("eval:", rep)
        return rep

    # ------------------------------------------------------------------
    def get_layer_by_name(self, name: str) -> Optional[Layer]:
        for l in self.layers:
            if l.name == name:
                return l
        return None

    def get_layers(self) -> Dict[int, Layer]:
        return dict(enumerate(self.layers))

    def get_perf_metrics(self):
        return self._current_metrics

    # ------------------------------------------------------------------
    # checkpoint / resume (beyond-reference: the reference has no built-in
    # checkpointing, SURVEY.md §5)
    def save_checkpoint(self, directory: str, step: Optional[int] = None,
                        max_to_keep: int = 3):
        from .runtime.checkpoint import save_model_checkpoint
        buf = self._metrics_buffer
        if buf is not None:
            # deferred NaN screen ALWAYS runs before a checkpoint save:
            # pending steps are flushed and a non-finite one raises here
            # — a poisoned state must never reach a checkpoint
            buf.flush()
            buf.raise_if_poisoned()
        return save_model_checkpoint(self, directory, step, max_to_keep)

    def restore_checkpoint(self, directory: str,
                           step: Optional[int] = None) -> int:
        from .runtime.checkpoint import restore_model_checkpoint
        return restore_model_checkpoint(self, directory, step)

    # dynamic recompilation (reference recompile_on_condition, model.cc:2422)
    def recompile_on_condition(self, trigger, alter) -> "object":
        from .runtime.recompile import RecompileState
        rs = RecompileState(trigger, alter, ff=self)
        self._recompile_state = rs
        return rs

    # weights access (reference Parameter.get/set_weights NumPy round-trip)
    def get_weights(self, layer_name: str, weight_name: str = "kernel"
                    ) -> np.ndarray:
        return np.asarray(self.params[layer_name][weight_name])

    def set_weights(self, layer_name: str, weight_name: str,
                    value: np.ndarray):
        cur = self.params[layer_name][weight_name]
        if cur.shape != value.shape:
            raise ValueError(f"weight {layer_name}/{weight_name} has "
                             f"shape {cur.shape}, got {value.shape}")
        self.params[layer_name][weight_name] = jax.device_put(
            jnp.asarray(value, cur.dtype), cur.sharding)

    def set_state(self, layer_name: str, key: str, value: np.ndarray):
        """Overwrite one non-trainable state entry (e.g. batch-norm
        running mean/var imported from a trained torch model)."""
        cur = self.state[layer_name][key]
        if cur.shape != tuple(value.shape):
            raise ValueError(f"state {layer_name}/{key} has shape "
                             f"{cur.shape}, got {tuple(value.shape)}")
        self.state[layer_name][key] = jax.device_put(
            jnp.asarray(value, cur.dtype), cur.sharding)

    @property
    def label_tensor_for_loaders(self) -> Tensor:
        if self.label_tensor is None:
            out = self._output_tensor or self.layers[-1].outputs[0]
            self.label_tensor = Tensor(out.shape, DataType.DT_INT32,
                                       name="label")
        return self.label_tensor
