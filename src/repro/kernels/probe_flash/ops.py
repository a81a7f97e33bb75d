"""jit wrapper: padding, probe-row gather, interpret fallback, and the
`probe_flash_attention` entry point used by models/attention.py."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import saliency as sal
from repro.kernels import interpret_default
from repro.kernels.probe_flash import kernel as K


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def probe_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    probe: Optional[sal.ProbeSpec] = None,
    q_block: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Kernel-backed mirror of models.attention.blocked_attention.

    Returns (out (b,h,lq,dv), probe colsum (b,lkv) | None).
    """
    interpret = interpret_default() if interpret is None else interpret
    b, h, lq, d = q.shape
    lkv = k.shape[2]
    bq = min(q_block, max(lq, 8))
    bk = min(q_block, max(lkv, 8))

    qp_ = _pad_to(q, bq, 2)
    kp_ = _pad_to(k, bk, 2)
    vp_ = _pad_to(v, bk, 2)
    lq_p = qp_.shape[2]
    # flash_fwd places the causal diagonal at kv_len - lq_padded + q_offset;
    # q_offset = lq_p - lq restores the TRUE kv_len - lq geometry.
    out, lse = K.flash_fwd(qp_, kp_, vp_, causal=causal, block_q=bq, block_k=bk,
                           q_offset=lq_p - lq, kv_len=lkv,
                           interpret=interpret)
    out = out[:, :, :lq]
    lse = lse[:, :, :lq]

    colsum = None
    if probe is not None:
        np_true = int(probe.positions.shape[0])
        npb = min(256, max(np_true, 8))
        pos = probe.positions.astype(jnp.int32)
        pad = (-np_true) % npb
        pos_p = jnp.pad(pos, (0, pad), constant_values=-1)
        safe = jnp.clip(pos_p, 0, lq - 1)
        qp = jnp.take(q, safe, axis=2)
        lse_p = jnp.take(lse, safe, axis=2)
        pos_b = jnp.broadcast_to(pos_p[None], (b, pos_p.shape[0]))
        colsum = K.probe_colsum(
            qp, lse_p, pos_b, kp_, causal=causal, block_p=npb, block_k=bk,
            lq=lq, kv_len=lkv, interpret=interpret)[:, :lkv]
    return out, colsum
