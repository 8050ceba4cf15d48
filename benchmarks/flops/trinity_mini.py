"""Operations per token of what ONE chip computes of the window/full
attention mixture-of-experts configuration (``configs/trinity_mini.json``),
from its sizes alone, in ``flops/lfm2_24b_a2b.py``'s conventions.

Forward = 2 x (parameters a token meets in a matrix multiplication) plus
attention's two products over the keys a query may see, counted over
the full width although the mask is causal (the MFU literature's
convention): ``seq`` keys a query head in a full layer, ``min(seq,
window)`` in a window layer, which is what the model asks for whatever
computes it. Training = 3 x forward. Nothing recomputed is counted.

  attention layer  wq, wg and wo (hidden x heads x d), wk and wv (hidden
                   x kv heads x d); q.k and p.v over d, per QUERY head
  dense layer      three matrices of hidden x intermediate
  expert layer     the router over the PUBLISHED expert count, the
                   routed experts at what a token is expected to meet
                   HERE (top_k x held / published of them: uniform
                   routing; the program's counters give the real load),
                   and the shared expert whole
  head             hidden x the vocabulary slice

The embedding is a look-up; the norms, the gate's sigmoid and product,
the rotary embedding, softmax and the routers' sigmoid run on the vector
unit: not counted.
"""


def _attention(s: dict, kind: str, seq: int) -> float:
    h = s["hidden_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    proj = 3 * h * heads * d + 2 * h * kv * d          # wq, wg, wo; wk, wv
    keys = min(seq, s["sliding_window"]) \
        if kind == "sliding_attention" else seq
    return 2 * proj + 2 * keys * heads * 2 * d


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_tok"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * (met + s["num_shared_experts"]))


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    total = 0.0
    for i, kind in enumerate(s["layer_types"]):
        total += _attention(s, kind, seq)
        total += (2 * 3 * h * s["intermediate_size"]
                  if i < s["num_dense_layers"] else _expert_layer(s))
    return total + 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
