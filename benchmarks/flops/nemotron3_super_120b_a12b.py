"""Operations per token of what ONE chip computes of the Mamba-2 /
LatentMoE / attention configuration
(``configs/nemotron3_super_120b_a12b.json``), from its sizes alone, in
``flops/granite_4_0_h_micro.py``'s and ``flops/qwen3_next_80b_a3b.py``'s
conventions.

Forward = 2 x (parameters a token meets in a matrix multiplication),
plus attention's two s x s products, counted over the full square
although the mask is causal (the MFU literature's convention), plus the
state-space recurrence BY ITS RECURRENT FORM; training = 3 x forward.
Nothing recomputed is counted, and nothing a chunked implementation
adds (the in-chunk ``L * C B^T`` matrices): ``mfu.train`` reads the same
work whatever implements the scan. A layer is ONE sub-layer, by its
letter of ``hybrid_override_pattern``; every count is of the HELD share
(the heads, groups and experts the file's keys give), not the model's.

  M  in_proj (hidden x (2 inner + 2 groups x state + heads)) and
     out_proj (inner x hidden), inner = heads x head_dim; the
     recurrence, a head-token: the state's decay (P N), one write (dt x
     B^T: 2 P N), one read (S C: 2 P N) and the D skip (2 P)
  *  wq and wo (hidden x heads x d), wk and wv (hidden x kv heads x d);
     q.k and p.v over d, per QUERY head
  E  the router over the PUBLISHED expert count; the two latent
     projections (hidden x latent each); the routed experts at what a
     token is expected to meet HERE (top_k x held / published of them:
     uniform routing; the program's counters give the real load), each
     TWO matrices of latent x moe_intermediate_size (ReLU-squared: no
     gate matrix); the shared expert whole, two matrices of hidden x
     moe_shared_expert_intermediate_size
  head  hidden x the vocabulary slice

The embedding is a look-up; the norms, the gate, the convolution's taps
(2 x 4 operations a channel), softplus, softmax, sigmoid and the squared
ReLU run on the vector unit: not counted.

The three flash kernels' own operations and bytes are
``flops/window_attention.py``'s at no window (the causal triangle's
pairs, every operand and result once, K and V at their own head), which
the ``trinity_flash_*_roofline`` readers use in this cell too.
"""


def _mamba(s: dict) -> float:
    h = s["hidden_size"]
    heads, p, n = s["mamba_num_heads"], s["mamba_head_dim"], \
        s["ssm_state_size"]
    inner = heads * p
    proj = h * (2 * inner + 2 * s["n_groups"] * n + heads) + inner * h
    return 2 * proj + heads * (5 * p * n + 2 * p)


def _attention(s: dict, seq: int) -> float:
    h, heads, kv, d = (s["hidden_size"], s["num_attention_heads"],
                       s["num_key_value_heads"], s["head_dim"])
    return 2 * (2 * h * heads * d + 2 * h * kv * d) \
        + 2 * seq * heads * 2 * d


def _expert_layer(s: dict) -> float:
    h, latent = s["hidden_size"], s["moe_latent_size"]
    published = s.get("num_experts_published") or s["n_routed_experts"]
    met = s["num_experts_per_tok"] * s["n_routed_experts"] / published
    shared = s["moe_shared_expert_intermediate_size"] * s["n_shared_experts"]
    return 2 * (h * published + 2 * h * latent
                + 2 * latent * s["moe_intermediate_size"] * met
                + 2 * h * shared)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s = sizes
    layer = {"M": _mamba(s), "*": _attention(s, seq), "E": _expert_layer(s)}
    return sum(layer[c] for c in s["hybrid_override_pattern"]) \
        + 2 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)


def published_parameters(sizes: dict) -> dict:
    """The WHOLE model's parameter count from the published values the
    file keeps beside the held ones (``*_published``), and what a token
    meets of it: the check that the equations are the model's (120.67 B,
    12.77 B: the name's "120B-A12B")."""
    s = sizes
    h, p, n = s["hidden_size"], s["mamba_head_dim"], s["ssm_state_size"]
    heads, groups = s["mamba_num_heads_published"], s["n_groups_published"]
    inner, bc = heads * p, 2 * groups * n
    mamba = h * (2 * inner + bc + heads) + (inner + bc) * (
        s["conv_kernel"] + 1) + 3 * heads + inner + inner * h + h
    q, kv, d = (s["num_attention_heads_published"],
                s["num_key_value_heads_published"], s["head_dim"])
    attention = 2 * h * q * d + 2 * h * kv * d + h
    experts = s["num_experts_published"]
    outside = h * experts + experts + 2 * h * s["moe_latent_size"] \
        + 2 * h * s["moe_shared_expert_intermediate_size"] \
        * s["n_shared_experts"] + h
    one = 2 * s["moe_latent_size"] * s["moe_intermediate_size"]
    pattern = s["hybrid_override_pattern_published"]
    m, a, e = (pattern.count(c) for c in "M*E")
    ends = 2 * s["vocab_size_published"] * h + h
    return {
        "total": m * mamba + a * attention + e * (outside + experts * one)
        + ends,
        "a_token": m * mamba + a * attention
        + e * (outside + s["num_experts_per_tok"] * one) + ends,
        "mamba_layer": mamba, "attention_layer": attention,
        "expert_layer_outside_routed": outside, "routed_expert": one}
