"""Pallas TPU kernels — the hand-written hot-op layer.

The reference backs its hot ops with cuDNN/cuBLAS kernels (e.g. attention
via ``cudnnMultiHeadAttnForward``, ``src/ops/attention.cu:35``). Here XLA
covers most of that ground; this package holds the kernels XLA needs help
with:

  - ``flash_attention``: fused, tiled, online-softmax attention (fwd+bwd)
    that never materializes the (seq, seq) score matrix in HBM.
  - ``ring_attention``: sequence/context-parallel attention over a sharded
    sequence axis (a capability the reference LACKS — SURVEY.md §5
    "Long-context / sequence parallelism: not present").
  - ``ulysses_attention``: all-to-all (DeepSpeed-Ulysses style) sequence
    parallelism: swap seq-sharding for head-sharding around local flash
    attention.
  - ``opt_update``: fused one-HBM-pass Adam update for the ZeRO-sharded
    optimizer path (the ``opt_update:fused`` kernel tier).
  - ``gated_delta_rule``: the in-chunk terms of linear attention's chunked
    recurrence (fwd+bwd), taken by ``ops/recurrent_ops.py`` where the
    shapes allow; not a registry entry.
  - ``hyper_connection``: the residual streams' mixes (fwd+bwd), a tile of
    tokens' whole ``n x C`` entries in VMEM, taken by ``ops/hyper_ops.py``
    where the channels are whole lanes; not a registry entry.
  - ``moe_token_sum``: the routed experts' way back to tokens, forward
    and in the row gather's transpose: a tile of tokens' float32 sums in
    VMEM, the live rows of the sorted domain copied in aligned blocks by
    the kernel's own DMAs and added by index, taken by
    ``ops/moe_ops.py`` where the shapes allow (whole lanes, fewer rows
    than ``top_k`` arrays of tokens, one device); not a registry entry.

``registry`` makes the implementation choice a searched dimension: per-op
variants with availability predicates and calibrated cost entry points
(docs/kernels.md).

All kernels run compiled on TPU and in Pallas interpret mode on CPU, so the
test suite exercises them without hardware.
"""
from .flash_attention import (dropout_keep_mask, flash_attention,
                              mha_reference)
from .opt_update import fused_adam_update
from .registry import (DEFAULT_IMPLS, KernelImpl, REGISTRY, attention_ctx,
                       available_impls, get_impl, parse_forced,
                       resolve_forced)
from .ring_attention import ring_attention, ulysses_attention

__all__ = [
    "DEFAULT_IMPLS",
    "KernelImpl",
    "REGISTRY",
    "attention_ctx",
    "available_impls",
    "dropout_keep_mask",
    "flash_attention",
    "fused_adam_update",
    "get_impl",
    "mha_reference",
    "parse_forced",
    "resolve_forced",
    "ring_attention",
    "ulysses_attention",
]
