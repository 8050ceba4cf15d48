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
  - ``gated_delta_rule``: the in-chunk terms of linear attention's chunked
    recurrence (fwd+bwd), taken by ``ops/recurrent_ops.py`` where the
    shapes allow; not a registry entry.
  - ``delta_mix``: a delta-rule layer's q, k and v from the projections'
    float32 output to the recurrence's operand in one pass (the causal
    taps, the SiLU, the unit length with its scale, the turn to
    heads-first; fwd+bwd), taken by
    ``ops/recurrent_ops.py::GatedDeltaRuleOp.projections`` where a head
    is whole lanes; not a registry entry.
  - ``hyper_connection``: the residual streams' mixes (fwd+bwd), a tile of
    tokens' whole ``n x C`` entries in VMEM, taken by ``ops/hyper_ops.py``
    where the channels are whole lanes; not a registry entry.
  - ``moe_token_sum``: the routed experts' way back to tokens, forward
    and in the row gather's transpose: a tile of tokens' float32 sums in
    VMEM, the live rows of the sorted domain copied in aligned blocks by
    the kernel's own DMAs and added by index, taken by
    ``ops/moe_ops.py`` where the shapes allow (whole lanes, fewer rows
    than ``top_k`` arrays of tokens, one device); not a registry entry.
  - ``qk_norm_rope``: an attention layer's q and k from the projections'
    float32 output to the flash kernels' operand in one pass (the heads'
    RMSNorm, the rotary embedding, the cast, the turn to heads-first;
    fwd+bwd), taken by ``ops/nn_ops.py::MultiHeadAttentionOp`` where a
    head is whole lanes and the layer is on the flash path on one
    device; not a registry entry.
  - ``index_scores``: the sparse-attention indexer's scores and their
    pull-back inside its loss's backward (two kernels): a (query tile x
    key tile)'s heads of scores stay in VMEM and only arrays without a
    head axis over (queries, keys) are written, taken by
    ``ops/sparse_attention.py`` where the heads are whole lanes and the
    tiles divide the chunks; not a registry entry.

Each op chooses its kernel from what it can observe (shapes, dropout,
platform). ``registry`` holds the one override: attention's three names,
the predicate a forced choice is held to, and the parser of the forcing
spec (docs/kernels.md).

All kernels run compiled on TPU and in Pallas interpret mode on CPU, so the
test suite exercises them without hardware.
"""
from .flash_attention import (dropout_keep_mask, flash_attention,
                              flash_attention_forward,
                              flash_attention_from_forward,
                              flash_attention_head_mean, mha_reference)
from .registry import (KernelImpl, REGISTRY, attention_ctx, get_impl,
                       parse_forced, resolve_forced)
from .ring_attention import ring_attention

__all__ = [
    "KernelImpl",
    "REGISTRY",
    "attention_ctx",
    "dropout_keep_mask",
    "flash_attention",
    "flash_attention_forward",
    "flash_attention_from_forward",
    "flash_attention_head_mean",
    "get_impl",
    "mha_reference",
    "parse_forced",
    "resolve_forced",
    "ring_attention",
]
