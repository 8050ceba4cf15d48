"""Operations of what ONE chip computes of the block-diffusion
mixture-of-experts configuration (``configs/sdar_30b_a3b.json``), from its
sizes alone, in ``flops/trinity_mini.py``'s conventions, and of one call
of a flash kernel under the block-diffusion mask.

A step trains ``seq`` tokens and runs the decoder over ``2 x seq``
positions for them (a noised copy beside the clean one), so per TOKEN:

  forward = 2 positions x [2 x (parameters a position meets in a matrix
            product) + attention's two products over the keys a query
            may see]  +  the head over the token's ONE row (the noised
            half's)

  attention layer  wq and wo (hidden x heads x d), wk and wv (hidden x
                   kv heads x d); q.k and p.v over d per QUERY head, over
                   the mask's own pairs: ``L L + L B`` of the ``4 L L``,
                   (L + B) / 2 keys a position on average (the mask is
                   what the model asks for whatever computes it; a
                   causal decoder's count takes the full width by the
                   MFU literature's convention, which has no such
                   convention for this mask)
  expert layer     the router over the PUBLISHED expert count and the
                   routed experts at what a position is expected to meet
                   HERE (top_k x held / published of them: uniform
                   routing; the program's counters give the real load);
                   all ``2 x seq`` positions are routed, the last
                   layer's clean half too, whose output feeds nothing:
                   the program computes it and it is counted
  head             hidden x the vocabulary slice, ``seq`` rows

Training = 3 x forward. Nothing recomputed is counted. The embedding is
a look-up; the norms, the rotary embedding, softmax, the routers'
softmax, the noise draw and the loss's weights run on the vector unit:
not counted.

A kernel's call: ``flops/flash_attention.py``'s products (2, 3 and 4 of
``2 x bh x pairs x d``) and bytes, changed in nothing but ``pairs``: the
LIVE pairs of the mask, ``L L + L B`` for ``2 L`` queries and keys
(16,793,600 of 67,108,864 at L 4,096 and B 4: 25.0%). A kernel that
computes whole tiles the mask leaves mostly dead reads lower here, which
is what it earned; a count over the square would read four times too
high.
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


_flash = _sibling("flash_attention")
PRODUCTS = _flash.PRODUCTS
bytes_moved = _flash.bytes_moved


def live_pairs(length: int, block: int) -> int:
    """The (query, key) pairs the block-diffusion mask attends over
    ``2 x length`` positions in blocks of ``block``: noised x noised
    ``length x block``, noised x clean ``length (length - block) / 2``,
    clean x clean ``length (length + block) / 2``."""
    return length * length + length * block


def _attention(s: dict, seq: int) -> float:
    """Per POSITION of the 2 x seq."""
    h = s["hidden_size"]
    heads, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["head_dim"])
    proj = 2 * h * heads * d + 2 * h * kv * d              # wq, wo; wk, wv
    keys = live_pairs(seq, s["block_length"]) / (2 * seq)
    return 2 * proj + 2 * keys * heads * 2 * d


def _expert_layer(s: dict) -> float:
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    published = s.get("num_experts_published") or s["num_experts"]
    met = s["num_experts_per_tok"] * s["num_experts"] / published
    return 2 * (h * published + 3 * h * f * met)


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    s = sizes
    layer = _attention(s, seq) + _expert_layer(s)
    return 2 * s["num_hidden_layers"] * layer \
        + 2 * s["hidden_size"] * s["vocab_size"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)


def flash_operations(kernel: str, operands: list, block: int) -> float:
    """``operands``: ``[(dtype, dims), ...]`` of the call, which is
    self-attention over ``2 L`` positions under the mask."""
    (_, (bh, sq, d)), (_, (_, sk, _)) = operands[1], operands[2]
    if sq != sk or sq % 2:
        raise ValueError(f"a block-diffusion call has sq == sk == 2 L, "
                         f"not {sq} and {sk}")
    return float(PRODUCTS[kernel] * 2 * bh * live_pairs(sq // 2, block) * d)


def flash_roofline_s(kernel: str, operands: list, results: list,
                     block: int, peak: dict):
    """``(seconds, bound)``: the larger of operations over the chip's
    bf16 peak and bytes over its HBM bandwidth, and which it was."""
    compute = flash_operations(kernel, operands, block) \
        / peak["bf16_flops_per_s"]
    memory = bytes_moved(kernel, operands, results) / peak["hbm_bytes_per_s"]
    return (compute, "operations") if compute >= memory \
        else (memory, "bytes")
