"""Operations per token of what ONE chip computes of the DeepSeek-V3-
shaped configuration with hyper-connected residual streams
(``configs/xing4_29b_a4b.json``), from its sizes alone:
``flops/joyai_llm_flash.py``'s count over the heads held here, plus the
hyper-connections' one matrix product a sub-layer.

Forward = 2 x (parameters a token meets in a matrix multiplication) plus
attention's two s x s products, counted over the full square although
the mask is causal (the MFU literature's convention); training = 3 x
forward. Nothing recomputed is counted.

  latent attention   wq_a, wq_b, wkv_a, wkv_b, wo; q.k over
                     (nope + rope) and p.v over v_dim, per head HELD
  hyper-connection   phi, (streams x hidden) x streams (streams + 2), a
                     sub-layer: two a layer
  dense layer        three matrices of hidden x intermediate
  expert layer       the router over the PUBLISHED expert count, the
                     shared expert, and the routed experts at what a
                     token is expected to meet HERE: top_k x held /
                     published of them
  MTP module         W_eh (2 hidden -> hidden) and one expert layer
  head               once for the trunk, once more for the module

Embeddings are look-ups; the norms, the rotary embedding, softmax and
sigmoid, and of the hyper-connections the Sinkhorn iterations and the
mixes over the streams (``Hpre X``, ``Hres X + Hpost^T y``: 2 x 4 x
3584 and 2 x 20 x 3584 operations a token and sub-layer, bound by
memory) run on the vector unit: not counted.
"""


def _attention(s: dict, seq: int) -> float:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    proj = (h * s["q_lora_rank"] + s["q_lora_rank"] * heads * qk
            + h * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
            + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"]
                                           + s["v_head_dim"])
            + heads * s["v_head_dim"] * h)
    return 2 * proj + 2 * seq * heads * (qk + s["v_head_dim"])


def _maps(s: dict) -> float:
    n = s.get("hc_mult") or 1
    return 2 * n * s["hidden_size"] * n * (n + 2) if n > 1 else 0.0


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("n_routed_experts_published") or s["n_routed_experts"]
    met = s["num_experts_per_tok"] * s["n_routed_experts"] / published
    return 2 * (h * published + 3 * h * f * (s["n_shared_experts"] + met))


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s, h = sizes, sizes["hidden_size"]
    dense = s["first_k_dense_replace"]
    layers = s["num_hidden_layers"]
    total = layers * (_attention(s, seq) + 2 * _maps(s))
    total += dense * 2 * 3 * h * s["intermediate_size"]
    total += (layers - dense) * _expert_layer(s)
    heads = 1
    if s.get("num_nextn_predict_layers", 0):
        total += 2 * 2 * h * h + _attention(s, seq) + 2 * _maps(s) \
            + _expert_layer(s)
        heads = 2
    return total + heads * 2 * h * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
