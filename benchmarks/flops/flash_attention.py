"""Operations and bytes of one call of each flash-attention kernel
(``flexflow_tpu/kernels/flash_attention.py``), from its shapes alone,
and from them the least time a chip could take for the call.

A call's operands are ``(seed, q, k, v, ...)`` with ``q`` of shape
``(batch x heads, sq, d)`` and ``k`` of ``(batch x heads, sk, d)``, as
the compiled step's custom call lists them.

Operations: the matrix products the kernel's algorithm needs, given what
it is handed, each ``2 x bh x pairs x d`` —

  ``flash_attention_fwd``      2: S = Q K^T, O = P V
  ``flash_attention_bwd_dq``   3: S again (only its row statistics were
                                  kept), dP = dO V^T, dQ = dS K
  ``flash_attention_bwd_dkv``  4: S again, dV = P^T dO, dP = dO V^T,
                                  dK = dS^T Q

``pairs`` is ``sq x sk``, and under a causal mask only the query-key
pairs the mask leaves: ``s (s + 1) / 2``. A kernel that skips masked
blocks therefore cannot read above 100%, and one that computes them
reads lower, which is what it earned. (``flops/gpt2_124m.py`` counts the
full square for the end-to-end ``mfu.train``, the MFU literature's
convention; the two are different yardsticks.) The exponentials and the
row sums run on the vector unit and are not counted.

Bytes: every operand and every result once, as the algorithm needs
them. The kernels' interface hands the two row statistics (log-sum-exp
and delta, one float32 a query row) broadcast to 128 lanes; they are
counted one value a row. Counted as handed they are four times q's
bytes, the three calls would be bound by bytes (184, 300 and 323 us at
the shapes below) and each share would read about twice as high, for
traffic the algorithm does not ask for.

At the cells' shapes (``gpt2_124m.train.1chip``: bh 144, s 1024, d 64,
bf16 operands, causal) all three are bound by operations on a v5e, the
forward only just: 19.3 GFLOP = 98 us against 76 MB = 93 us; dq 147
against 117 us; dkv 196 against 140 us.
"""

PRODUCTS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
            "flash_attention_bwd_dkv": 4}
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def operations(kernel: str, operands: list, causal: bool) -> float:
    """``operands``: ``[(dtype, dims), ...]`` of the call."""
    (_, (bh, sq, d)), (_, (_, sk, _)) = operands[1], operands[2]
    if causal and sq != sk:
        raise ValueError(f"causal flash attention has sq == sk, not "
                         f"{sq} and {sk}")
    pairs = sq * (sq + 1) // 2 if causal else sq * sk
    return float(PRODUCTS[kernel] * 2 * bh * pairs * d)


# where the row statistics sit: (operands, results)
_ROW_STATS = {"flash_attention_fwd": ((), (1,)),
              "flash_attention_bwd_dq": ((5, 6), ()),
              "flash_attention_bwd_dkv": ((5, 6), ())}


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
