"""Operations and bytes of one call of each flash-attention kernel
(``flexflow_tpu/kernels/flash_attention.py``) where q and k have one
head size and v and the output another, as latent attention has them
(q.k over 192, p.v over 128), and from them the least time a chip could
take for the call. ``flops/flash_attention.py`` assumes one size and
stays as it is; the conventions here are its conventions.

A call's operands are ``(seed, q, k, v, ...)`` with ``q`` and ``k`` of
``(batch x heads, s, d)`` and ``v`` of ``(batch x heads, s, dv)``.

Operations: the matrix products the algorithm needs, ``2 x bh x pairs``
times the size each contracts or produces over —

  ``flash_attention_fwd``      S = Q K^T (d), O = P V (dv)
  ``flash_attention_bwd_dq``   S again (d), dP = dO V^T (dv), dQ = dS K (d)
  ``flash_attention_bwd_dkv``  S again (d), dV = P^T dO (dv),
                               dP = dO V^T (dv), dK = dS^T Q (d)

``pairs`` is ``sq x sk``, under a causal mask the ``s (s + 1) / 2`` the
mask leaves, so a kernel cannot read above 100% by skipping what it is
allowed to skip. Exponentials and row sums are not counted.

Bytes: every operand and every result once; the two row statistics one
float32 a query row, not the 128 lanes the interface hands them in.

At the cell's shape (``joyai_llm_flash.train.1chip``: bh 32, s 4096, d
192, dv 128, bf16, causal) all three are bound by operations on a v5e:
forward 171.9 GFLOP = 0.87 ms against 168 MB = 0.21 ms; dq 275 GFLOP =
1.40 ms; dkv 344 GFLOP = 1.74 ms.
"""

# per kernel: how many products contract or produce over (d, dv)
PRODUCTS = {"flash_attention_fwd": (1, 1), "flash_attention_bwd_dq": (2, 1),
            "flash_attention_bwd_dkv": (2, 2)}
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
# where the row statistics sit: (operands, results)
_ROW_STATS = {"flash_attention_fwd": ((), (1,)),
              "flash_attention_bwd_dq": ((5, 6), ()),
              "flash_attention_bwd_dkv": ((5, 6), ())}


def operations(kernel: str, operands: list, causal: bool) -> float:
    """``operands``: ``[(dtype, dims), ...]`` of the call."""
    (_, (bh, sq, d)), (_, (_, sk, _)), (_, (_, _, dv)) = operands[1:4]
    if causal and sq != sk:
        raise ValueError(f"causal flash attention has sq == sk, not "
                         f"{sq} and {sk}")
    pairs = sq * (sq + 1) // 2 if causal else sq * sk
    n_d, n_dv = PRODUCTS[kernel]
    return float(2 * bh * pairs * (n_d * d + n_dv * dv))


def bytes_moved(kernel: str, operands: list, results: list) -> int:
    total = 0
    for shapes, stats in zip((operands, results), _ROW_STATS[kernel]):
        for i, (dtype, dims) in enumerate(shapes):
            n = 1 if dtype.startswith("f8") else _BYTES[dtype]
            for dim in dims[:-1] if i in stats else dims:
                n *= dim
            total += n
    return total


def roofline_s(kernel: str, operands: list, results: list, causal: bool,
               peak: dict):
    """``(seconds, bound)``: the larger of operations over the chip's
    bf16 peak and bytes over its HBM bandwidth, and which it was."""
    compute = operations(kernel, operands, causal) / peak["bf16_flops_per_s"]
    memory = bytes_moved(kernel, operands, results) / peak["hbm_bytes_per_s"]
    return (compute, "operations") if compute >= memory \
        else (memory, "bytes")
