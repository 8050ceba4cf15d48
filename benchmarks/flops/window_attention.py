"""Operations of one call of a flash-attention kernel whose layer has a
sliding window (``flexflow_tpu/kernels/flash_attention.py`` with
``window=``), from its shapes and the layer's window alone, and from
them the least time a chip could take for the call.

The products a call needs and the bytes it moves are
``flops/flash_attention.py``'s, imported here and changed in nothing:
2, 3 and 4 products of ``2 x bh x pairs x d`` for the forward, ``dq``
and ``dkv`` call, every operand and result once. What a window changes
is ``pairs``: a query sees the ``window`` keys that end with its own,

  ``window x s - window x (window - 1) / 2``   where ``window < s``
  ``s (s + 1) / 2``                            where it has none, or one
                                               of at least ``s``

(14,681,088 of 33,558,528 at 8,192 positions and a window of 2,048:
43.75%). A count over the causal triangle for a banded call would read
up to 2.3 times too high; a kernel that computes the tiles outside the
band reads lower here, which is what it earned.
"""
import importlib.util
import os


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("_bench_flops_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_flash = _sibling("flash_attention")
PRODUCTS = _flash.PRODUCTS
bytes_moved = _flash.bytes_moved


def band_pairs(s: int, window: int) -> int:
    """The (query, key) pairs a causal call over ``s`` positions leaves
    under a window of ``window`` keys a query (0: no window)."""
    if not window or window >= s:
        return s * (s + 1) // 2
    return window * s - window * (window - 1) // 2


def operations(kernel: str, operands: list, window: int) -> float:
    """``operands``: ``[(dtype, dims), ...]`` of the call, which is
    causal self-attention (the only kind that takes a window)."""
    (_, (bh, sq, d)), (_, (_, sk, _)) = operands[1], operands[2]
    if sq != sk:
        raise ValueError(f"a windowed call has sq == sk, not {sq} and "
                         f"{sk}")
    return float(PRODUCTS[kernel] * 2 * bh * band_pairs(sq, window) * d)


def roofline_s(kernel: str, operands: list, results: list, window: int,
               peak: dict):
    """``(seconds, bound)``: the larger of operations over the chip's
    bf16 peak and bytes over its HBM bandwidth, and which it was."""
    compute = operations(kernel, operands, window) / peak["bf16_flops_per_s"]
    memory = bytes_moved(kernel, operands, results) / peak["hbm_bytes_per_s"]
    return (compute, "operations") if compute >= memory \
        else (memory, "bytes")
