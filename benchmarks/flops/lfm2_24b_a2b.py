"""Operations per token of what ONE chip computes of the hybrid
convolution/attention configuration (``configs/lfm2_24b_a2b.json``),
from its sizes alone.

Forward = 2 x (parameters a token meets in a matrix multiplication) plus
attention's two s x s products, counted over the full square although
the mask is causal (the MFU literature's convention, as
``flops/gpt2_124m.py``); training = 3 x forward. Nothing recomputed is
counted.

  conv layer       w_in (hidden -> 3 hidden) and w_out (hidden -> hidden)
  attention layer  wq and wo (hidden x heads x d), wk and wv (hidden x
                   kv heads x d); q.k and p.v over d, per QUERY head
                   (K and V repeated or not, the products are the same)
  dense layer      three matrices of hidden x intermediate
  expert layer     the router over the PUBLISHED expert count and the
                   routed experts at what a token is expected to meet
                   HERE: top_k x held / published of them (uniform
                   routing; the program's counters give the real load)
  head             hidden x the vocabulary slice

The embedding is a look-up; the norms, the gates, the convolution's
taps (2 x 3 operations a channel), the rotary embedding, softmax and
sigmoid run on the vector unit: not counted.
"""


def _operator(s: dict, kind: str, seq: int) -> float:
    h = s["hidden_size"]
    if kind == "conv":
        return 2 * (3 * h * h + h * h)
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    d = s.get("head_dim") or h // heads
    proj = 2 * h * heads * d + 2 * h * kv * d
    return 2 * proj + 2 * seq * heads * 2 * d


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_tok"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * met)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    total = 0.0
    for i, kind in enumerate(s["layer_types"]):
        total += _operator(s, kind, seq)
        total += (2 * 3 * h * s["intermediate_size"]
                  if i < s["num_dense_layers"] else _expert_layer(s))
    return total + 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
