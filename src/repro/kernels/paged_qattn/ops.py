"""jit wrapper: paged decode attention over a `PagedKVCache`, pages in place.

`attend_paged` is a drop-in replacement for the paged backend's gather+dense
decode path (`kvc.attend_decode(q, cache.dense_view())`) whenever the hi/lo
stores carry channelwise-K / CST-V quantization or raw >=16-bit storage (the
ZipCache and fp16 configurations): each segment — hi store, lo store, bf16
staging window — is consumed directly from its page pools via the slot's
page table (kernel.qattn_paged_segment), and the per-segment flash stats are
merged exactly as the reference does (ref.merge_segments_weights).  It also
reconstructs the head-pooled per-slot softmax weights the probe-state update
consumes (paper Eq. 8), so it plugs into `CacheBackend.attend` unchanged.

Caveats vs the dense path (both harmless to the engine):
  * batch rows with no valid token anywhere return zeros, where the dense
    softmax returns a garbage uniform average — such rows are retired slots,
    masked by every consumer;
  * out/slot_weights agree with the gather path to float tolerance, not
    bitwise (flash accumulation reassociates the softmax), which keeps
    greedy argmax token-identical (tests/test_paged_qattn.py).

Precision maps and the downshift ladder (core/precision.py) are INVISIBLE
here by design: per-layer/head effective bits narrow the code RANGE the
quantizers emit while the scale/zero params absorb the narrower qmax, and
codes stay packed in the same container width (TokenStore bits — what the
static `k_bits`/`v_bits` kernel parameters and every block shape are derived
from).  A store folded at any rung therefore dequantizes through the exact
same kernel program: no retrace, no new specialization, and the kernel-vs-
oracle equality under heterogeneous maps is covered by
tests/test_precision.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import kvcache as kvc
from repro.kernels import interpret_default
from repro.kernels.paged_qattn import kernel as K
from repro.kernels.paged_qattn import ref as R


def kernel_supported(cache) -> bool:
    """Static check: every non-empty quantized store must be in the ZipCache
    configuration (channelwise K, CST V); raw (bits >= 16) segments always
    qualify.  Groupwise/tokenwise stores (KIVI/GEAR policies) do not."""
    for store in (cache.hi, cache.lo):
        if store.table.shape[1] == 0:
            continue
        km, vm = store.k_meta, store.v_meta
        if km.bits < 16:
            if km.scale is None or km.scale.shape[-2] != 1 \
                    or km.channel_scale is not None:
                return False
        if vm.bits < 16:
            if vm.scale is None or vm.scale.shape[-1] != 1 \
                    or vm.channel_scale is None:
                return False
    return True


def policy_supported(ccfg) -> bool:
    """`kernel_supported` for every cache a policy can build, judged on an
    abstract (shape-only) cache of that policy so it cannot drift from the
    store layout."""
    from repro.core import paged
    cache = jax.eval_shape(
        lambda: paged.PagedKVBackend(ccfg).init_cache(1, 1, 8, 256))
    return kernel_supported(cache)


def _pad_tokens(x, s_pad, value=0.0):
    """Pad axis -2 (token axis) of (b,hk,S,1) params up to S_pad."""
    pad = s_pad - x.shape[-2]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)),
                   constant_values=value)


def _store_operands(q, store):
    """Kernel operands for a quantized/raw PagedStore segment."""
    b, h, d = q.shape
    hk = store.k_pages.shape[1]
    page = store.k_pages.shape[2]
    npp = store.table.shape[1]
    s_pad = npp * page
    s_seg = store.pos.shape[-1]
    dv_packed = store.v_pages.shape[-1]
    km, vm = store.k_meta, store.v_meta
    dv = vm.shape[-1]
    pos = jnp.pad(store.pos, ((0, 0), (0, s_pad - s_seg)), constant_values=-1)
    # dense dequantize rounds to the store dtype (scale's dtype) before
    # attention reads f32 — the kernel must round identically
    if km.bits >= 16:
        k_scale = jnp.ones((b, hk, 1, d), jnp.float32)
        k_zero = jnp.zeros((b, hk, 1, d), jnp.float32)
        k_dtype = jnp.float32
    else:
        k_scale, k_zero = km.scale, km.zero
        k_dtype = km.scale.dtype
    if vm.bits >= 16:
        v_cscale = jnp.ones((b, hk, 1, dv), jnp.float32)
        v_tscale = jnp.ones((b, hk, s_pad, 1), jnp.float32)
        v_tzero = jnp.zeros((b, hk, s_pad, 1), jnp.float32)
        v_dtype = jnp.float32
    else:
        v_cscale = vm.channel_scale
        v_tscale = _pad_tokens(vm.scale, s_pad)
        v_tzero = _pad_tokens(vm.zero, s_pad)
        v_dtype = vm.scale.dtype
    return dict(k_pages=store.k_pages, k_scale=k_scale, k_zero=k_zero,
                v_pages=store.v_pages, v_cscale=v_cscale, v_tscale=v_tscale,
                v_tzero=v_tzero, pos=pos, table=store.table,
                k_bits=km.bits, v_bits=vm.bits, k_dtype=k_dtype,
                v_dtype=v_dtype, s_seg=s_seg)


def _window_operands(q, cache):
    """Kernel operands for the raw bf16 staging-window segment."""
    b, h, d = q.shape
    hk = cache.win_k_pages.shape[1]
    page = cache.page_size
    npp = cache.win_table.shape[1]
    s_pad = npp * page
    w = cache.window
    dv = cache.win_v_pages.shape[-1]
    return dict(
        k_pages=cache.win_k_pages,
        k_scale=jnp.ones((b, hk, 1, d), jnp.float32),
        k_zero=jnp.zeros((b, hk, 1, d), jnp.float32),
        v_pages=cache.win_v_pages,
        v_cscale=jnp.ones((b, hk, 1, dv), jnp.float32),
        v_tscale=jnp.ones((b, hk, s_pad, 1), jnp.float32),
        v_tzero=jnp.zeros((b, hk, s_pad, 1), jnp.float32),
        pos=jnp.pad(cache.win_pos, ((0, 0), (0, s_pad - w)),
                    constant_values=-1),
        table=cache.win_table, k_bits=16, v_bits=16,
        k_dtype=jnp.float32, v_dtype=jnp.float32, s_seg=w)


def _segment_stats(q, ops, scale, interpret, use_ref):
    """Run one segment through the kernel (or the jnp oracle) and normalize
    its stats to the shared merge contract (p relative to the segment max)."""
    args = (q, ops["k_pages"], ops["k_scale"], ops["k_zero"], ops["v_pages"],
            ops["v_cscale"], ops["v_tscale"], ops["v_tzero"], ops["pos"],
            ops["table"])
    kw = dict(k_bits=ops["k_bits"], v_bits=ops["v_bits"], scale=scale,
              k_dtype=ops["k_dtype"], v_dtype=ops["v_dtype"])
    if use_ref:
        return R.paged_segment_ref(*args, **kw)
    acc, m, l, p, mrun = K.qattn_paged_segment(*args, interpret=interpret, **kw)
    p_rel = p * jnp.exp(mrun - m[..., None])
    return acc, m, l, p_rel


def attend_paged(
    q: jnp.ndarray,
    cache,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    use_ref: bool = False,
) -> kvc.DecodeAttnOut:
    """One-token decode attention over a `PagedKVCache`, no dense gather.

    q: (b, h, d).  Returns DecodeAttnOut(out (b,h,dv) in q's dtype,
    slot_weights (b, S_hi+S_lo+W) f32 in hi/lo/window order — the same
    contract as `kvc.attend_decode` on the gathered view).
    use_ref=True runs the pure-jnp page-walking oracle instead of Pallas
    (ref.paged_segment_ref) through the identical merge."""
    interpret = interpret_default() if interpret is None else interpret
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    segs = []
    for store in (cache.hi, cache.lo):
        if store.table.shape[1] == 0:
            continue
        segs.append(_store_operands(q, store))
    if cache.win_table.shape[1]:
        segs.append(_window_operands(q, cache))
    stats = [_segment_stats(q, ops, scale, interpret, use_ref) for ops in segs]
    out, weights = R.merge_segments_weights(stats)
    slot_w = jnp.concatenate(
        [jnp.mean(w[:, :, :ops["s_seg"]], axis=1)
         for w, ops in zip(weights, segs)], axis=-1)
    return kvc.DecodeAttnOut(out.astype(q.dtype), slot_w)
