"""Forced kernel implementations — attention's three paths.

An op chooses its own kernel from what it can observe: shapes, dropout,
platform (``ops/nn_ops.py::MultiHeadAttentionOp.auto_takes_flash``, the
``takes_kernel`` of ``kernels/gated_delta_rule.py``,
``kernels/hyper_connection.py`` and ``kernels/moe_token_sum.py``).
Nothing is priced. This module is the one override: attention's three
implementation names, the availability predicate each forced choice is
held to, and the parser of the forcing spec. A forced choice is adopted
by ``FFModel._plan_kernels``, serializes with the strategy
(``kernel_impls`` block) and is re-checked by the plan verifier on the
adopted mesh/shapes (``plan_verifier._check_kernel``).

Forcing: ``FFConfig.kernel_impls`` / ``--kernel-impl`` / the
``FF_KERNEL_IMPL`` env var take ``<op>:<impl>`` pairs (comma-separated),
e.g. ``attention:flash`` or ``attention:ring``.

See docs/kernels.md.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional

# the one op kind with more than one implementation to force
ATTENTION = "attention"


def _attn_xla(ctx: Dict[str, Any]) -> Optional[str]:
    return None  # the reference path is always legal


def _attn_flash(ctx: Dict[str, Any]) -> Optional[str]:
    """Pallas flash kernel: tiled online-softmax attention.

    Structural legality only — the kernel runs compiled on TPU and in
    interpret mode on CPU, so the backend is no availability question.
    """
    if ctx.get("causal", False) and \
            ctx.get("q_len", 0) != ctx.get("kv_len", 0):
        return "flash kernel does not mask causal cross-attention " \
               "(q_len != kv_len)"
    if ctx.get("block_diffusion_block", 0) and ctx.get("q_len", 0) % 256:
        return "the flash kernels draw the block-diffusion mask in tiles " \
               "of 128 that divide a half of the sequence"
    return None      # a sliding window is the kernels' own band arithmetic


def _attn_ring(ctx: Dict[str, Any]) -> Optional[str]:
    """Ring attention over the mesh's sequence axis (``seq``)."""
    deg = int(ctx.get("seq_degree", 0) or 0)
    if deg < 2:
        return "ring attention requires a mesh sequence axis " \
               "(seq degree >= 2); this mesh has none"
    if ctx.get("latent", False):
        return "ring attention is the multi-head op's; latent " \
               "attention has no ring path"
    if ctx.get("indexer", False):
        return "ring attention takes no mask of selected keys"
    q_len = int(ctx.get("q_len", 0) or 0)
    kv_len = int(ctx.get("kv_len", 0) or 0)
    if q_len != kv_len:
        return "ring attention requires self-attention (q_len == kv_len)"
    if q_len % deg != 0:
        return f"sequence length {q_len} is not divisible by the " \
               f"seq-axis degree {deg}"
    if ctx.get("sliding_window", 0):
        return "ring attention has no sliding-window mask support"
    if ctx.get("block_diffusion_block", 0):
        return "ring attention has no block-diffusion mask support"
    if ctx.get("dropout", 0.0):
        return "ring attention has no in-kernel dropout"
    return None


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One implementation variant of a multi-impl op kind."""
    op: str                                     # ATTENTION
    name: str                                   # e.g. "flash"
    predicate: Callable[[Dict[str, Any]], Optional[str]]

    def available(self, ctx: Dict[str, Any]) -> Optional[str]:
        """None when legal on ``ctx``, else a human-readable reason."""
        return self.predicate(ctx)


REGISTRY: Dict[str, Dict[str, KernelImpl]] = {
    ATTENTION: {
        "xla": KernelImpl(ATTENTION, "xla", _attn_xla),
        "flash": KernelImpl(ATTENTION, "flash", _attn_flash),
        "ring": KernelImpl(ATTENTION, "ring", _attn_ring),
    },
}


def impl_names(op: str) -> List[str]:
    return list(REGISTRY[op])


def get_impl(op: str, name: str) -> KernelImpl:
    try:
        return REGISTRY[op][name]
    except KeyError:
        known = {k: sorted(v) for k, v in REGISTRY.items()}
        raise KeyError(
            f"unknown kernel impl {op}:{name} (known: {known})") from None


def attention_ctx(params: Dict[str, Any], q_len: int, kv_len: int,
                  *, seq_degree: int = 0, latent: bool = False
                  ) -> Dict[str, Any]:
    """Predicate context for an attention layer's params + shapes
    (``latent``: a ``LatentAttentionOp``, which is always causal
    self-attention)."""
    return {
        "q_len": int(q_len),
        "kv_len": int(kv_len),
        "causal": latent or bool(params.get("causal", False)),
        "sliding_window": int(params.get("sliding_window", 0) or 0),
        "dropout": float(params.get("dropout", 0.0) or 0.0),
        "seq_degree": int(seq_degree),
        "latent": bool(latent),
        "indexer": bool(params.get("indexer_heads")),
        "block_diffusion_block": int(
            params.get("block_diffusion_block", 0) or 0),
    }


def layer_ctx(layer, seq_degree: int = 0) -> Optional[Dict[str, Any]]:
    """:func:`attention_ctx` of a graph layer of either attention kind;
    None for every other op."""
    from ..ffconst import OperatorType
    if layer.op_type not in (OperatorType.OP_MULTIHEAD_ATTENTION,
                             OperatorType.OP_LATENT_ATTENTION):
        return None
    latent = layer.op_type == OperatorType.OP_LATENT_ATTENTION
    q_len = int(layer.inputs[0].shape[1]) if layer.inputs else 0
    kv_len = int(layer.inputs[1].shape[1]) \
        if len(layer.inputs) > 1 and not latent else q_len
    return attention_ctx(layer.params, q_len, kv_len,
                         seq_degree=seq_degree, latent=latent)


# ----------------------------------------------------------------------
# forcing: config flag / env var
# ----------------------------------------------------------------------
def parse_forced(spec: str) -> Dict[str, str]:
    """Parse ``"attention:ring"`` into an op->impl map.

    Unknown ops/impls raise ValueError — a typo'd force must fail loudly,
    never silently fall back to the op's own rule.
    """
    out: Dict[str, str] = {}
    for part in str(spec or "").split(","):
        part = part.strip()
        if not part or part == "auto":
            continue
        if ":" not in part:
            raise ValueError(
                f"--kernel-impl takes <op>:<impl> pairs, got {part!r}")
        op, impl = (p.strip() for p in part.split(":", 1))
        if op not in REGISTRY:
            raise ValueError(
                f"unknown kernel op {op!r} (known: {sorted(REGISTRY)})")
        if impl not in REGISTRY[op]:
            raise ValueError(
                f"unknown impl {impl!r} for op {op!r} "
                f"(known: {sorted(REGISTRY[op])})")
        out[op] = impl
    return out


def resolve_forced(cfg) -> Dict[str, str]:
    """Forced op->impl choices from config and environment.

    Precedence (later wins): ``cfg.kernel_impls`` < ``FF_KERNEL_IMPL``.
    """
    forced = parse_forced(getattr(cfg, "kernel_impls", "auto")
                          if cfg is not None else "auto")
    forced.update(parse_forced(os.environ.get("FF_KERNEL_IMPL", "")))
    return forced
