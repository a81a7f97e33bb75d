"""Packed cache bytes (codes at their stored widths, quantization
parameters, the bf16 staging window; `engine.cache_bytes`) in bits per
live K or V value at the window's close: the live tokens times the values
the configuration's adapter says the cache stores per token."""


def read(run):
    values = run["context_tokens"] * run["kv_values_per_token"]
    if not values:
        return None
    return 8.0 * run["cache_bytes"]["packed_bytes"] / values
