"""Operations per token of what ONE chip computes of the gated-delta-rule
/ gated-attention mixture-of-experts configuration
(``configs/qwen3_next_80b_a3b.json``), from its sizes alone, in
``flops/kimi_linear_48b_a3b.py``'s and ``flops/trinity_mini.py``'s
conventions.

Forward = 2 x (parameters a token meets in a matrix multiplication),
plus full attention's two products over ``seq`` keys a query head,
counted over the full width although the mask is causal (the MFU
literature's convention), plus the gated delta rule BY ITS RECURRENT
FORM; training = 3 x forward. Nothing recomputed is counted, and nothing
a chunked implementation adds (the in-chunk matrices, the triangular
solve): ``mfu.train`` reads the same work whatever implements the
recurrence.

  linear layer   wq, wk (hidden x key heads x d), wv, wz, wo (hidden x
                 value heads x d), wa, wb (hidden x value heads); the
                 recurrence, a VALUE head-token: the state's decay
                 (d^2), two reads of it (S^T k, S^T q: 2 d^2 each) and
                 one write (2 d^2)
  full layer     wq, wg and wo (hidden x heads x d), wk and wv (hidden x
                 kv heads x d); q.k and p.v over d, per QUERY head
  expert layer   the router over the PUBLISHED expert count, the routed
                 experts at what a token is expected to meet HERE (top_k
                 x held / published of them: uniform routing; the
                 program's counters give the real load), the shared
                 expert whole and its gate (hidden x 1)
  head           hidden x the vocabulary slice

The embedding is a look-up; the norms, the gates, the convolutions' taps
(2 x 4 operations a channel), the rotary embedding, softmax and sigmoid
run on the vector unit: not counted.

The three flash kernels' own operations and bytes are
``flops/window_attention.py``'s (``flops/flash_attention.py``'s at no
window: the causal triangle's pairs, every operand and result once, K
and V at their own 2 heads), which the ``qwen3next_flash_*_roofline``
readers use; :func:`flash_call` gives the same two numbers from the
sizes alone, for the test that holds the readers to a hand count.
"""

PRODUCTS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
            "flash_attention_bwd_dkv": 4}


def _linear_attention(s: dict) -> float:
    h, d = s["hidden_size"], s["linear_value_head_dim"]
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    proj = h * d * (2 * hk + 3 * hv) + 2 * h * hv
    return 2 * proj + 7 * hv * d * d


def _full_attention(s: dict, seq: int) -> float:
    h = s["hidden_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    proj = 3 * h * heads * d + 2 * h * kv * d          # wq, wg, wo; wk, wv
    return 2 * proj + 2 * seq * heads * 2 * d


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_tok"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * met
                + 3 * h * s["shared_expert_intermediate_size"] + h)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s = sizes
    total = 0.0
    for i in range(s["num_hidden_layers"]):
        total += _full_attention(s, seq) \
            if (i + 1) % s["full_attention_interval"] == 0 \
            else _linear_attention(s)
        total += _expert_layer(s)
    return total + 2 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)


def flash_call(kernel: str, sizes: dict, seq: int, batch: int = 1,
               operand_bytes: int = 2):
    """``(operations, bytes)`` of one call of ``kernel`` by a full layer
    at ``seq`` positions: the causal triangle's pairs a query head; q,
    o and their cotangents at the query heads, k, v and theirs at the
    key/value heads, the two row statistics one float32 a query row."""
    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    ops = PRODUCTS[kernel] * 2 * batch * heads * (seq * (seq + 1) // 2) * d
    at_q = batch * heads * seq * d * operand_bytes
    at_kv = batch * kv * seq * d * operand_bytes
    rows = batch * heads * seq * 4
    moved = {"flash_attention_fwd": 2 * at_q + 2 * at_kv + rows,
             # q, do and dq at the query heads
             "flash_attention_bwd_dq": 3 * at_q + 2 * at_kv + 2 * rows,
             "flash_attention_bwd_dkv": 2 * at_q + 4 * at_kv + 2 * rows}
    return float(ops), moved[kernel]
