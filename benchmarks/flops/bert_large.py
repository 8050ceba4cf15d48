"""Operations per token of a BERT-style encoder with a classifier head,
from its sizes alone (the program's own counts are not consulted).

Forward = 2 x (parameters that sit in a matrix multiplication) plus the
two s x s attention products; training = 3 x forward (backward is two
matmuls per forward matmul). Nothing recomputed is counted. The
embeddings are look-ups, not multiplications, and the pooler and the
classifier see one token per sequence.
"""


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    h, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * ffn          # q, k, v, o; two FFN
    head = (h * h + h * sizes["num_labels"]) / seq
    attention = 4 * seq * h                      # q.k^T and p.v
    return (2 * (sizes["num_layers"] * per_layer + head)
            + sizes["num_layers"] * attention)


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
