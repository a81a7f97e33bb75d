# Pallas TPU kernels for ZipCache's compute hot-spots:
#   cst_quant     — fused channel-separable tokenwise quantization + bit-pack
#   probe_flash   — blocked flash attention with probe-score side output (Eq. 9)
#   decode_qattn  — decode attention reading the PACKED quantized KV cache
#   paged_qattn   — decode attention walking the paged cache's page tables
# Each has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper with
# interpret fallback on CPU), ref.py (pure-jnp oracle used by the tests).

import jax


def interpret_default() -> bool:
    """Kernels run in interpret mode on the CPU backend only.  Any other
    backend compiles them, so a process that came up on the wrong device
    fails at compile time instead of serving through the interpreter."""
    return jax.default_backend() == "cpu"
