"""Operations and bytes that one decode step's attention needs over a
ZipCache paged cache, counted from the live context at the stored widths.

Counted once each, per layer, per live slot, per KV head:
  * codes: salient tokens at `high_bits`, regular tokens at `low_bits`,
    for K and for V;
  * quantization parameters (bf16): K channelwise scale and zero for each
    of the two stores (2 x head_dim each), V's channel normalizer
    (head_dim per store) and per-token scale and zero (2 per token);
  * the bf16 staging window, K and V;
  * q in and the attention output out, bf16, per query head.
Not counted: capacity pages past the live tokens, page padding, positions
and saliency state.  So the same work counts the same whatever kernel does
it.  FLOPs: q.k and p.v, 2 x head_dim each, per query head per live token.

`slots` lists one (quantized tokens, window tokens) pair per live slot:
the tokens folded into the quantized stores and those still in the window.
"""

from __future__ import annotations

from typing import Iterable, Tuple

OP_NAMES = ("qattn_paged_segment",)     # the kernel's name in device traces
PARAM_BYTES = 2                         # bf16 scales and zero points
ACT_BYTES = 2                           # bf16 q, output and window


def split(quantized: int, saliency_ratio: float) -> Tuple[int, int]:
    """(salient, regular) tokens of a quantized context."""
    hi = int(round(saliency_ratio * quantized))
    return hi, quantized - hi


def step(model: dict, policy: dict, slots: Iterable[Tuple[int, int]]):
    """(flops, bytes) of one decode step over every layer."""
    h, k, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    layers = model["num_hidden_layers"]
    hb, lb = policy["high_bits"], policy["low_bits"]
    flops = nbytes = 0
    for quantized, win in slots:
        hi, lo = split(quantized, policy["saliency_ratio"])
        codes = 2 * d * (hi * hb + lo * lb) / 8            # K and V
        params = PARAM_BYTES * (2 * (2 * d) + 2 * d + 2 * (hi + lo))
        window = 2 * win * d * ACT_BYTES
        per_kv_head = codes + params + window
        nbytes += layers * (k * per_kv_head + 2 * h * d * ACT_BYTES)
        flops += layers * h * 4 * d * (hi + lo + win)
    return flops, nbytes
