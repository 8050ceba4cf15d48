"""Operations per token of a GPT-2-style decoder, from its sizes alone.

Forward = 2 x (parameters that sit in a matrix multiplication, the
output head among them) plus the two s x s attention products, counted
full although the mask is causal (the convention of the MFU literature:
a kernel that skips the masked half shows as a higher share, which is
what it earned); training = 3 x forward. The MLP is 4 x hidden wide.
"""


def forward_flops_per_token(sizes: dict, seq: int) -> float:
    h = sizes["hidden_size"]
    per_layer = 4 * h * h + 2 * h * (4 * h)
    head = h * sizes["vocab_size"]
    attention = 4 * seq * h
    return (2 * (sizes["num_layers"] * per_layer + head)
            + sizes["num_layers"] * attention)


def train_flops_per_token(sizes: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(sizes, seq)
