"""jit wrapper: run qattn_segment over the MixedKVCache's hi/lo stores (packed
path), handle the bf16 window in jnp, and merge segments flash-decoding style.

`decode_attend_mixed` is a drop-in replacement for core.kvcache.attend_decode
whenever both stores carry channelwise-K / CST-V quantization (the ZipCache
configuration) — validated against it in tests.

Per-layer/head precision maps (core/precision.py) never reach this kernel:
effective bits only narrow the code range inside the container width the
quantizer params absorb, so `k_bits`/`v_bits` stay the static container
widths and one warm program serves every map (tests/test_precision.py runs
the kernel-vs-oracle check under heterogeneous maps).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import kvcache as kvc
from repro.kernels import interpret_default
from repro.kernels.decode_qattn import kernel as K
from repro.kernels.decode_qattn import ref as R

NEG_INF = -1e30


def _pad_slots(store_arrays, block_s):
    """Pad the slot axis (axis 2 for (b,hk,S,*), axis 1 for pos) to block_s."""
    k_codes, k_scale, k_zero, v_codes, v_cscale, v_tscale, v_tzero, pos = store_arrays
    s = k_codes.shape[2]
    pad = (-s) % block_s
    if pad == 0:
        return store_arrays
    p4 = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return (p4(k_codes), k_scale, k_zero, p4(v_codes), v_cscale,
            p4(v_tscale), p4(v_tzero),
            jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1))


def _segment_kernel(q, store: kvc.TokenStore, block_s: int, interpret: bool):
    kq, vq = store.k, store.v
    arrays = (kq.codes, kq.scale, kq.zero, vq.codes, vq.channel_scale,
              vq.scale, vq.zero, store.pos)
    arrays = _pad_slots(arrays, block_s)
    return K.qattn_segment(
        q, *arrays, k_bits=kq.bits, v_bits=vq.bits,
        block_s=min(block_s, arrays[0].shape[2]), interpret=interpret)


def _segment_window(q, k_win, v_win, win_pos, scale):
    return R.segment_attend_ref(
        q, k_win.astype(jnp.float32), v_win.astype(jnp.float32),
        win_pos >= 0, scale)


def decode_attend_mixed(
    q: jnp.ndarray,
    cache: kvc.MixedKVCache,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token decode attention over the mixed cache via the packed kernel.

    q: (b, h, d). Requires hi/lo stores in the ZipCache configuration
    (channelwise K with scale/zero, CST V with token params + channel scale).
    Returns out (b, h, dv).
    """
    interpret = interpret_default() if interpret is None else interpret
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    stats = []
    for store in (cache.hi, cache.lo):
        if store.capacity == 0:
            continue
        if store.k.bits >= 16:  # raw segment: jnp path
            stats.append(R.segment_attend_ref(
                q, store.k.dequantize().astype(jnp.float32),
                store.v.dequantize().astype(jnp.float32),
                store.valid, scale))
        else:
            stats.append(_segment_kernel(q, store, block_s, interpret))
    if cache.window:
        stats.append(_segment_window(q, cache.k_win, cache.v_win, cache.win_pos, scale))
    return R.merge_segments_ref(stats).astype(q.dtype)
