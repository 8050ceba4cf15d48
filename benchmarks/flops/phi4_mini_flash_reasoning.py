"""Operations per token of what ONE chip computes of the SambaY /
differential-attention configuration
(``configs/phi4_mini_flash_reasoning.json``), from its sizes alone, in
``flops/granite_4_0_h_micro.py``'s conventions, and the operations and
bytes of one call of a flash kernel as this model issues it.

Forward = 2 x (parameters a token meets in a matrix multiplication),
plus differential attention's FOUR s x s products a query pair (two
softmaxes: q1.k1 and q2.k2 over d, P1 V and P2 V over 2 d), counted over
the full width of keys a query may see although the mask is causal (the
MFU literature's convention: ``seq`` keys in a whole or cross layer,
``min(seq, sliding_window)`` in a window layer), plus the selective scan
BY ITS RECURRENT FORM; training = 3 x forward. Nothing recomputed is
counted, and nothing an implementation adds.

  selective scan   in_proj (hidden x 2 D), x_proj (D x (R + 2 N)),
                   dt_proj (R x D), out_proj (D x hidden), D = expand x
                   hidden. The recurrence, a channel-token: the state's
                   decay (N), one write (dt x B: 2 N), one read (C . s:
                   2 N), the skip (2). These 5 N + 2 are VECTOR
                   operations: no matrix unit runs them (N is 16 and the
                   decay differs by channel and entry), and ``mfu.train``
                   counts them one for one beside the products, as cell
                   9's count does its recurrence: 0.7 of this model's 69
                   GFLOP a token. The exponentials (N a channel-token)
                   are not counted
  attention        wq and wo (hidden x heads x d), wk and wv (hidden x
                   kv heads x d; none in a cross layer, which reads
                   another layer's); the four products, per query PAIR
  gated memory     two matrices of hidden x D
  feed-forward     three matrices of hidden x intermediate, every layer
  head             hidden x the vocabulary slice

The embedding is a look-up; the LayerNorms, gates, the convolution's
taps, softplus, softmax, lambda and the pair norm run on the vector
unit: not counted.
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


_unequal = _sibling("mla_attention")       # products over (d, dv), bytes
_band = _sibling("window_attention")       # the pairs a window leaves

MIXERS = ("mamba1", "mamba1_memory")
ATTENTION = ("diff_sliding_attention", "diff_attention_kv",
             "diff_cross_attention")


def _mixer(s: dict) -> float:
    h, n, r = s["hidden_size"], s["mamba_d_state"], s["mamba_dt_rank"]
    d = s["mamba_expand"] * h
    proj = h * 2 * d + d * (r + 2 * n) + r * d + d * h
    return 2 * proj + d * (5 * n + 2)


def _attention(s: dict, kind: str, seq: int) -> float:
    h, heads, kv = (s["hidden_size"], s["num_attention_heads"],
                    s["num_key_value_heads"])
    d = s.get("head_dim") or h // heads
    proj = 2 * h * heads * d                           # wq, wo
    if kind != "diff_cross_attention":
        proj += 2 * h * kv * d                         # wk, wv
    keys = min(seq, s["sliding_window"]) \
        if kind == "diff_sliding_attention" else seq
    # a query pair: two products over d, two over 2 d
    return 2 * proj + (heads // 2) * 2 * keys * (2 * d + 2 * 2 * d)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    total = 0.0
    for kind in s["layer_types"]:
        if kind in MIXERS:
            total += _mixer(s)
        elif kind in ATTENTION:
            total += _attention(s, kind, seq)
        elif kind == "gated_memory":
            total += 2 * 2 * h * s["mamba_expand"] * h
        else:
            raise ValueError(f"layer kind {kind!r}")
        total += 2 * 3 * h * s["intermediate_size"]
    return total + 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)


# -- one call of a flash kernel ---------------------------------------------
# A differential layer's call: q (b x pairs, s, d), k (b x key pairs, s,
# d) and v (b x key pairs, s, 2 d) read in place, causal, in a band where
# the layer has a window. A function of the shapes and the window, not of
# the kernels' tiles: a grid that visits tiles outside the band reads
# lower, which is what it earned.

def flash_operations(kernel: str, operands: list, window: int) -> float:
    (_, (bh, sq, d)), (_, (_, sk, _)), (_, (_, _, dv)) = operands[1:4]
    if sq != sk:
        raise ValueError(f"a causal call has sq == sk, not {sq}, {sk}")
    n_d, n_dv = _unequal.PRODUCTS[kernel]
    return float(2 * bh * _band.band_pairs(sq, window)
                 * (n_d * d + n_dv * dv))


def flash_bytes(kernel: str, operands: list, results: list) -> int:
    return _unequal.bytes_moved(kernel, operands, results)


def flash_roofline_s(kernel: str, operands: list, results: list,
                     window: int, peak: dict):
    """``(seconds, bound)``: the larger of operations over the chip's
    bf16 peak and bytes over its HBM bandwidth, and which it was."""
    compute = flash_operations(kernel, operands, window) \
        / peak["bf16_flops_per_s"]
    memory = flash_bytes(kernel, operands, results) / peak["hbm_bytes_per_s"]
    return (compute, "operations") if compute >= memory \
        else (memory, "bytes")
