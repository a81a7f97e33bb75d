"""Packed cache bytes (codes at their stored widths, quantization
parameters, the bf16 staging window; `engine.cache_bytes`) in bits per
live K or V value at the window's close."""


def read(run):
    m = run["config"]["model"]
    values = run["context_tokens"] * m["num_hidden_layers"] \
        * m["num_key_value_heads"] * m["head_dim"] * 2
    if not values:
        return None
    return 8.0 * run["cache_bytes"]["packed_bytes"] / values
