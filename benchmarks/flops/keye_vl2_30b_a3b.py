"""Operations per token of what ONE chip computes of the sparse-attention
mixture-of-experts configuration (``configs/keye_vl2_30b_a3b.json``),
from its sizes alone: what the model asks for, whatever computes it.

Forward = 2 x (parameters a token meets in a matrix multiplication) plus
the products over keys; training = 3 x forward. Nothing recomputed is
counted, and none of the masked-out pairs that a path which multiplies
a whole chunk of keys and masks afterwards also multiplies.

  attention   wq and wo (hidden x heads x d), wk and wv (hidden x kv
              heads x d); q.k and p.v over d, per QUERY head, over the
              min(seq, topk) keys a query attends
  indexer     wq_idx (hidden x J x c), wk_idx (hidden x c), w_idx
              (hidden x J); qI.kI over c for each of the J heads against
              ``seq`` keys a query: the full square, the MFU literature's
              convention for a causal product (``flops/gpt2_124m.py``)
  experts     the router over the PUBLISHED expert count and the routed
              experts at what a token is expected to meet HERE: top_k x
              held / published of them (uniform routing; the program's
              counters give the real load)
  head        hidden x the vocabulary slice

The embedding is a look-up; the norms, the rotary embedding, ReLU, the
weighted sum over the indexer's heads, the selection, softmax and the
alignment loss run on the vector unit: not counted.
"""


def _attention(s: dict, seq: int) -> float:
    h = s["hidden_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    proj = 2 * h * heads * d + 2 * h * kv * d
    keys = min(seq, s["sa_config"]["topk"])
    return 2 * proj + 2 * keys * heads * 2 * d


def _indexer(s: dict, seq: int) -> float:
    h, sa = s["hidden_size"], s["sa_config"]
    j, c = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2 * (h * j * c + h * c + h * j) + 2 * seq * j * c


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_tok"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * met)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    layer = (_attention(sizes, seq) + _indexer(sizes, seq)
             + _expert_layer(sizes))
    return sizes["num_hidden_layers"] * layer \
        + 2 * sizes["hidden_size"] * sizes["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
