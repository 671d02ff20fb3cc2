"""Model FLOPs of the Mamba-2 language model of ``reference/ssd_lm.py``.

Counted: every matrix product of the chunked SSD algorithm at the
configuration's chunk length Q, per token and layer
  in-projections  2 d (2 d_inner + 2 N + H)
  out-projection  2 d_inner d
  causal convs    2 k (d_inner + 2 N)
  within chunks   2 Q N (scores C B^T) + 2 Q d_inner (scores times x)
  chunk states    2 N d_inner (each chunk's state) + 2 N d_inner (outputs)
and the output head 2 d V per token.  Not counted: elementwise work, the
norms, the recurrence between chunks (H N P per chunk), recomputation.
"""


def forward_flops(m: dict, tokens: int, seq: int) -> int:
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    n, k = m["ssm_state"], m["ssm_conv"]
    di = d * m["ssm_expand"]
    h = di // m["ssm_head_dim"]
    q = min(m["ssm_chunk"], seq)
    layer = (2 * d * (2 * di + 2 * n + h) + 2 * di * d
             + 2 * k * (di + 2 * n)
             + 2 * q * n + 2 * q * di
             + 4 * n * di)
    return tokens * (L * layer + 2 * d * V)


def train_flops(m: dict, traffic: dict) -> int:
    """Forward and backward (twice the forward) of the selected clients'
    rows through the client and server stages, once per round."""
    k = max(int(round(traffic["clients"] * traffic["participation"])), 1)
    tokens = k * traffic["rows_per_client"] * traffic["seq"]
    return 3 * forward_flops(m, tokens, traffic["seq"])

