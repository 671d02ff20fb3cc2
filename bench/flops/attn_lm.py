"""Model FLOPs of the transformer of ``reference/attn_lm.py``.

Counted, per token and layer: the q, k, v and output projections
2 d H hd each, the scores and their product with v 2 S H hd each over all
S positions of the row (the dense path computes every score), and the MLP
2 d f twice; and the output head 2 d V per token.  Not counted:
elementwise work, the softmax, the norms, recomputation.
"""


def forward_flops(m: dict, tokens: int, seq: int) -> int:
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    hh = m["num_heads"] * m["head_dim"]
    layer = 8 * d * hh + 4 * seq * hh + 4 * d * m["d_ff"]
    return tokens * (L * layer + 2 * d * V)


def train_flops(m: dict, traffic: dict) -> int:
    """Forward and backward (twice the forward) of the selected clients'
    rows through the client and server stages, once per round."""
    k = max(int(round(traffic["clients"] * traffic["participation"])), 1)
    tokens = k * traffic["rows_per_client"] * traffic["seq"]
    return 3 * forward_flops(m, tokens, traffic["seq"])
