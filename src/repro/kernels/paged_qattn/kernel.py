"""Pallas TPU kernel: one-token decode attention over a PAGED quantized store.

The paged cache layout (core/paged.py) keeps the bulky payload — bit-packed
hi/lo codes and the bf16 staging window — in fixed-size pages addressed
through per-slot page tables, while the small quantization metadata (per-token
scales, channel normalizers, positions) stays dense per slot.  The gather
fallback materializes a dense (slots, heads, seq, dim) view of every segment
on every decode step; this kernel instead WALKS the page table: the table is
a scalar-prefetch operand, so each grid step's BlockSpec index map resolves
(slot, logical page) -> physical page id and the DMA engine fetches that page
of the pool directly — the dense view never exists in HBM.

Grid (b, hk, n_pages): flash-style online-softmax accumulation over a slot's
logical pages in VMEM scratch (running max m / running sum l), emitting
flash-decoding merge stats (acc, m, l) per (batch, kv-head) so the wrapper
combines the hi/lo/window segments exactly as the dense reference does.  Two
side outputs make the softmax row recoverable WITHOUT a second pass over the
pages: the per-page unnormalized probabilities `p` (written relative to the
running max at that page) and the running max `m_run` per page — rescaling
`p * exp(m_run - m_final)` yields exp(s - m_final) per slot, which the
wrapper pools into the per-slot saliency weights (paper Eq. 8 input).

Dequant schemes match core/quant.py (the ZipCache configuration):
  K: channelwise  — k = (codes - zero_c) * scale_c         params (b,hk,1,d)
  V: CST          — v = (codes - zero_t) * scale_t * c_ch  (Alg. 1)
bits >= 16 marks a RAW segment (fp16 stores, the bf16 staging window): pages
hold values, not codes, and the caller passes identity parameters.

`k_dtype`/`v_dtype` replicate `QuantizedTensor.dequantize`'s final cast: the
dense reference rounds dequantized values to the store dtype (bf16 in
serving) before attention lifts them back to f32, so the kernel must round
identically or its scores drift a bf16 ulp off the gather path's.

TPU note: Mosaic requires the last two dims of every block to be divisible
by (8, 128) or to equal the array's.  So the per-page operands and outputs
carry the page axis OUTSIDE their last two dims (pos (b,npp,1,page), p
(b,hk,npp,g,page), m_run (b,hk,npp,g,1)) and the wrapper reshapes them;
packed codes unpack in int32 lanes (Mosaic has no uint8 arithmetic).
Codes unpack in PLANE order (`plane_perm`): field j of every byte becomes
one contiguous block of d/pf lanes, so position j*(d/pf) + i holds channel
i*pf + j.  Interleaving the fields back into channel order inside the
kernel is a lane interleave, which Mosaic lowers into ~13,000 shuffle,
rotate and select ops per page, about 20x the attention math; whole planes
join with none.  The permutation lives in `qattn_paged_segment`: it carries
the per-slot operands on the channel axes the codes meet (q, k_scale,
k_zero along K's contraction; v_cscale along V's channels) into plane order
before the call and returns acc to channel order after it, so its contract
stays in channel order.  tests/test_tpu_compile.py compiles the kernel for a
described v5e chip at yi-6b widths and bounds each quantized segment's
kernel body by twice the raw window segment's op count; CI runs the kernel
in interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 operands contract in full f32 on every backend: the TPU's default
# would round them to bf16, so the chip would not compute what the CPU
# interpreter (and the gather path's reference) computes
_F32 = jax.lax.Precision.HIGHEST


def plane_perm(bits: int, d: int):
    """Channel held at each lane of a `d`-wide row that `_unpack` returns.

    With pf = 8 // bits, core/packing.py stores channel i*pf + j in field j
    of byte i; `_unpack` lays field j of every byte out as one contiguous
    plane, so plane-order position j*(d/pf) + i holds channel i*pf + j.
    None where unpacking keeps channel order (raw segments, 8-bit codes)."""
    if bits >= 16 or bits == 8:
        return None
    pf = 8 // bits
    return np.arange(d).reshape(d // pf, pf).T.reshape(-1)


def _unpack(codes, bits):
    """codes (S, d//pf) -> (S, d) f32 via shift/mask, in PLANE order.

    Field j of every byte fills lanes [j*d/pf, (j+1)*d/pf) (`plane_perm`):
    joining whole planes costs no lane shuffles, where interleaving the
    fields back into channel order costs thousands per page.
    bits >= 16: raw segment — pages hold values already, pass through."""
    if bits >= 16:
        return codes.astype(jnp.float32)
    pf = 8 // bits
    w = codes.astype(jnp.int32) & 0xFF    # int32 lanes: Mosaic has no u8 math
    if pf == 1:
        return w.astype(jnp.float32)
    mask = 2**bits - 1
    fields = [(w >> (j * bits)) & mask for j in range(pf)]
    return jnp.concatenate(fields, axis=-1).astype(jnp.float32)


def _paged_qattn_kernel(tbl_ref,  # scalar prefetch: (b, npp) page table
                        q_ref, kc_ref, ks_ref, kz_ref, vc_ref, vcs_ref,
                        vts_ref, vtz_ref, pos_ref,
                        acc_out, m_out, l_out, p_out, mrun_out,
                        acc_ref, m_ref, l_ref,
                        *, scale: float, k_bits: int, v_bits: int,
                        k_dtype, v_dtype):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (g, d)
    k = _unpack(kc_ref[0, 0], k_bits)                   # (page, d)
    k = (k - kz_ref[0, 0, 0].astype(jnp.float32)[None, :]) \
        * ks_ref[0, 0, 0].astype(jnp.float32)[None, :]
    if k_bits < 16:  # dense ref rounds dequantized values to the store dtype
        k = k.astype(k_dtype).astype(jnp.float32)
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            precision=_F32)                    # (g, page)
    valid = pos_ref[0, 0] >= 0                          # (1, page)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)       # (g, page)
    p_out[0, 0, 0] = p                                  # relative to m_new
    mrun_out[0, 0, 0] = m_new

    v = _unpack(vc_ref[0, 0], v_bits)                   # (page, dv)
    v = (v - vtz_ref[0, 0].astype(jnp.float32)) * vts_ref[0, 0].astype(jnp.float32)
    v = v * vcs_ref[0, 0, 0].astype(jnp.float32)[None, :]
    if v_bits < 16:
        v = v.astype(v_dtype).astype(jnp.float32)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, precision=_F32, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _fin():
        acc_out[0, 0] = acc_ref[...]
        m_out[0, 0] = m_ref[...]
        l_out[0, 0] = l_ref[...]


@functools.partial(
    jax.jit, static_argnames=("k_bits", "v_bits", "scale", "k_dtype",
                              "v_dtype", "interpret"))
def qattn_paged_segment(q, k_pages, k_scale, k_zero, v_pages, v_cscale,
                        v_tscale, v_tzero, pos, table, *, k_bits: int,
                        v_bits: int, scale: float, k_dtype=jnp.float32,
                        v_dtype=jnp.float32, interpret: bool = False):
    """One-token attention over a paged store segment, pages read in place.

    q (b,h,d) | k_pages (P,hk,page,d/pf_k) | k params (b,hk,1,d)
    v_pages (P,hk,page,dv/pf_v) | v_cscale (b,hk,1,dv) | v_t* (b,hk,S_pad,1)
    pos (b,S_pad) int32 (<0 = empty) | table (b,npp) int32 physical page ids.
    S_pad == npp * page (caller pads the dense per-token metadata up to whole
    pages; pool pages already cover the padded region).

    Returns flash-decoding stats, all f32:
      acc (b,h,dv), m (b,h), l (b,h) — segment accumulator / max / sum;
      p (b,h,S_pad) — exp(s - m_run(page)) per slot (0 where invalid);
      m_run (b,h,S_pad) — the running max `p` is relative to, expanded
      per-slot so `p * exp(m_run - m_all)` rescales in one broadcast.
    """
    b, h, d = q.shape
    _, hk, page, _ = k_pages.shape
    npp = table.shape[1]
    dv = v_cscale.shape[-1]
    g = h // hk
    # the kernel unpacks codes in plane order: carry K's contraction axis
    # (q, k_scale, k_zero) and V's channel axis (v_cscale) into it, and
    # bring acc back to channel order after the call
    k_perm, v_perm = plane_perm(k_bits, d), plane_perm(v_bits, dv)
    if k_perm is not None:
        q, k_scale, k_zero = q[..., k_perm], k_scale[..., k_perm], \
            k_zero[..., k_perm]
    if v_perm is not None:
        v_cscale = v_cscale[..., v_perm]
    q4 = q.reshape(b, hk, g, d)
    grid = (b, hk, npp)
    kernel = functools.partial(
        _paged_qattn_kernel, scale=scale, k_bits=k_bits, v_bits=v_bits,
        k_dtype=k_dtype, v_dtype=v_dtype)
    ck = k_pages.shape[-1]
    cv = v_pages.shape[-1]
    s_pad = npp * page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page, ck),
                         lambda b_, h_, j, tbl: (tbl[b_, j], h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page, cv),
                         lambda b_, h_, j, tbl: (tbl[b_, j], h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, dv), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page, 1), lambda b_, h_, j, tbl: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, page, 1), lambda b_, h_, j, tbl: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, 1, page), lambda b_, h_, j, tbl: (b_, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dv), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, h_, j, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, page),
                         lambda b_, h_, j, tbl: (b_, h_, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda b_, h_, j, tbl: (b_, h_, j, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    acc, m, l, p, mrun = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, npp, g, page), jnp.float32),
            jax.ShapeDtypeStruct((b, hk, npp, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(table, q4, k_pages, k_scale, k_zero, v_pages, v_cscale, v_tscale,
      v_tzero, pos.reshape(b, npp, 1, page))
    # pages -> slots: (b,hk,npp,g,page)->(b,h,S_pad); the per-page running
    # max broadcasts over its page's slots
    p = jnp.swapaxes(p, 2, 3).reshape(b, h, s_pad)
    mrun = jnp.broadcast_to(mrun, (b, hk, npp, g, page))
    mrun = jnp.swapaxes(mrun, 2, 3).reshape(b, h, s_pad)
    if v_perm is not None:
        acc = acc[..., np.argsort(v_perm)]
    return acc.reshape(b, h, dv), m.reshape(b, h), l.reshape(b, h), p, mrun
