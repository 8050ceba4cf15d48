"""Operations per token of what ONE chip computes of the state-space /
attention hybrid configuration (``configs/granite_4_0_h_micro.json``),
from its sizes alone, in ``flops/kimi_linear_48b_a3b.py``'s conventions.

Forward = 2 x (parameters a token meets in a matrix multiplication),
plus attention's two s x s products, counted over the full square
although the mask is causal (the MFU literature's convention), plus the
state-space recurrence BY ITS RECURRENT FORM; training = 3 x forward.
Nothing recomputed is counted, and nothing a chunked implementation
adds (the in-chunk ``L * C B^T`` matrices): ``mfu.train`` reads the same
work whatever implements the scan.

  mamba layer      in_proj (hidden x (2 inner + 2 state + heads)) and
                   out_proj (inner x hidden), inner = heads x head_dim;
                   the recurrence, a head-token: the state's decay
                   (P N), one write (dt x B^T: 2 P N), one read
                   (S C: 2 P N) and the D skip (2 P)
  attention layer  wq and wo (hidden x heads x d), wk and wv (hidden x
                   kv heads x d); q.k and p.v over d, per QUERY head
  feed-forward     three matrices of hidden x shared_intermediate_size,
                   in every layer
  head             hidden x the vocabulary slice

The embedding is a look-up; the norms, the gate, the convolution's taps
(2 x 4 operations a channel), softplus, softmax and the four scalar
multipliers run on the vector unit: not counted.
"""


def _mamba(s: dict) -> float:
    h = s["hidden_size"]
    heads, p, n = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    inner = heads * p
    proj = h * (2 * inner + 2 * s["mamba_n_groups"] * n + heads) + inner * h
    return 2 * proj + heads * (5 * p * n + 2 * p)


def _attention(s: dict, seq: int) -> float:
    h, heads, kv = (s["hidden_size"], s["num_attention_heads"],
                    s["num_key_value_heads"])
    d = s.get("head_dim") or h // heads
    return 2 * (2 * h * heads * d + 2 * h * kv * d) \
        + 2 * seq * heads * 2 * d


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    total = 0.0
    for kind in s["layer_types"]:
        total += _mamba(s) if kind == "mamba" else _attention(s, seq)
        total += 2 * 3 * h * s["shared_intermediate_size"]
    return total + 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
