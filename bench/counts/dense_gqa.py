"""Model FLOPs of a dense GQA decoder per generated token, for `mfu`.

2 x the matmul parameters (every projection, the MLP and the LM head; the
embedding lookup is a gather) plus 4 x layers x heads x head_dim x context
for the attention scores and the weighted sum over `context` positions.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    e, h, k, d = (m["hidden_size"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    per_layer = e * h * d + 2 * e * k * d + h * d * e \
        + 3 * e * m["intermediate_size"]
    return m["num_hidden_layers"] * per_layer + e * m["vocab_size"]


def token_flops(m: dict, context: int) -> int:
    return 2 * matmul_params(m) + 4 * m["num_hidden_layers"] \
        * m["num_attention_heads"] * m["head_dim"] * context
