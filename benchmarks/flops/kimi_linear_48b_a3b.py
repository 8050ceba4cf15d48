"""Operations per token of what ONE chip computes of the hybrid
linear-attention / latent-attention configuration
(``configs/kimi_linear_48b_a3b.json``), from its sizes alone.

Forward = 2 x (parameters a token meets in a matrix multiplication),
plus latent attention's two s x s products, counted over the full
square although the mask is causal (the MFU literature's convention, as
``flops/gpt2_124m.py``), plus the gated delta rule BY ITS RECURRENT
FORM; training = 3 x forward. Nothing recomputed is counted, and
nothing a chunked implementation adds (the in-chunk matrices, the
triangular solve): ``mfu.train`` reads the same work whatever
implements the recurrence.

  linear attention   wq, wk, wv, wo (hidden x heads x d), the two
                     low-rank gates (hidden x d and d x heads x d each),
                     wb (hidden x heads); the recurrence, a head-token:
                     the state's decay (d^2), two reads of it (S^T k,
                     S^T q: 2 d^2 each) and one write (2 d^2)
  latent attention   wq (no q latent), wkv_a, wkv_b, wo; q.k over
                     (nope + rope) and p.v over v_dim, per head
  dense layer        three matrices of hidden x intermediate
  expert layer       the router over the PUBLISHED expert count, the
                     shared expert, and the routed experts at what a
                     token is expected to meet HERE: top_k x held /
                     published of them (uniform routing; the program's
                     counters give the real load)
  head               hidden x the vocabulary slice

The embedding is a look-up; the norms, the gates, the convolutions' taps
(2 x 4 operations a channel, three times), softmax and sigmoid run on
the vector unit: not counted.
"""


def _linear_attention(s: dict) -> float:
    h, lin = s["hidden_size"], s["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    proj = 4 * h * heads * d + 2 * (h * d + d * heads * d) + h * heads
    return 2 * proj + 7 * heads * d * d


def _latent_attention(s: dict, seq: int) -> float:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    proj = (h * heads * qk
            + h * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
            + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"]
                                           + s["v_head_dim"])
            + heads * s["v_head_dim"] * h)
    return 2 * proj + 2 * seq * heads * (qk + s["v_head_dim"])


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_token"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * (s["num_shared_experts"] + met))


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    linear = set(s["linear_attn_config"]["kda_layers"])
    total = 0.0
    for n in range(1, s["num_hidden_layers"] + 1):      # from 1
        total += _linear_attention(s) if n in linear \
            else _latent_attention(s, seq)
        total += (2 * 3 * h * s["intermediate_size"]
                  if n <= s["first_k_dense_replace"] else _expert_layer(s))
    return total + 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
