"""jit wrapper for the CSTQuant kernel: batching, channel-scale computation,
CPU interpret fallback."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.cst_quant import kernel as K

EPS = 1e-8


def cst_quantize(x: jnp.ndarray, bits: int, token_block: int = 256,
                 interpret: bool | None = None):
    """Fused CSTQuant over (..., T, C). Returns (codes, token_scale,
    token_zero, channel_scale) with leading dims preserved.

    channel scales are computed OUTSIDE the kernel (one cheap column reduce);
    the kernel fuses normalize + quantize + pack in a single VMEM pass.
    """
    interpret = interpret_default() if interpret is None else interpret
    *lead, t, ch = x.shape
    xf = x.reshape(-1, t, ch)
    amax = jnp.max(jnp.abs(xf.astype(jnp.float32)), axis=1, keepdims=True)
    cs = jnp.sqrt(jnp.maximum(amax, EPS))            # (B, 1, C)

    tb = min(token_block, t)
    while t % tb:
        tb //= 2
    tb = max(tb, 1)

    def one(args):
        xi, ci = args
        return K.cst_quantize_pallas(xi, ci, bits, token_block=tb, interpret=interpret)

    codes, scale, zero = jax.lax.map(one, (xf, cs))
    pf = 8 // bits
    return (codes.reshape(*lead, t, ch // pf),
            scale.reshape(*lead, t, 1),
            zero.reshape(*lead, t, 1),
            cs.reshape(*lead, 1, ch))
