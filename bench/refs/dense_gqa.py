"""Plain reference of a dense GQA decoder (Llama layout: Yi, Qwen2).

Follows the published description: token embedding; per layer RMSNorm,
grouped-query attention with rotary position embedding (rotate-half form)
and optional q/k/v biases, residual, RMSNorm, SwiGLU MLP, residual; final
RMSNorm and an untied LM head.  Straight `jax.numpy` over the whole
sequence with a causal mask: no cache, no quantization, no kernels, no
batching.  It imports nothing of the program under test and reads only the
benchmark's own weights (`bench/lib/weights.py` layout).

`precision="f32"` is the reference: float32 activations, every matmul at
`Precision.HIGHEST` (the TPU's default would round f32 operands to bf16).
`precision="fp8"` is the control, the next precision below the served
bf16: computed in float8 (e4m3) where the served model holds bf16.  Every
weight is rounded to e4m3 with one scale per output channel, and every
activation the served model stores (the residual stream, norm outputs,
q/k/v, attention probabilities and outputs, the MLP's hidden units) with
one scale per token row; products still accumulate in float32.

Layer by layer, queries in blocks: the reference runs after the program's
state is freed, beside the weights, so it must fit in what is left.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512
VOCAB_CHUNK = 20_000


def _fp8(x, axes):
    """Round x to e4m3 with one scale per slice over `axes` (amax / 448)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _a(x, fp8):
    """An activation as stored: unchanged in float32, e4m3 per row."""
    return _fp8(x, (-1,)) if fp8 else x


def _mm(spec, x, w, fp8, w_in_axes):
    """einsum(spec, x, w) in float32 at HIGHEST, the result stored as an
    activation; with fp8 the weight is first rounded to e4m3 per output
    channel (one scale per slice over `w_in_axes`)."""
    w = w.astype(jnp.float32)
    if fp8:
        w = _fp8(w, w_in_axes)
    return _a(jnp.einsum(spec, x, w, precision=HI), fp8)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (..., T, D), rotate-half form: pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, layers, i, m, fp8):
    """Decoder layer `i` (traced: one program serves every layer) over the
    whole sequence x (T, E) float32."""
    lw = {k: a[i] for k, a in layers.items()}
    t = x.shape[0]
    h, k, d = m.heads, m.kv_heads, m.head_dim
    pos = jnp.arange(t)
    a = _a(_rms(x, lw["ln1"], m.eps), fp8)
    q = _mm("te,ehd->htd", a, lw["wq"], fp8, (0,))
    kk = _mm("te,ehd->htd", a, lw["wk"], fp8, (0,))
    v = _mm("te,ehd->htd", a, lw["wv"], fp8, (0,))
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)[:, None, :]
        kk = kk + lw["bk"].astype(jnp.float32)[:, None, :]
        v = v + lw["bv"].astype(jnp.float32)[:, None, :]
        q, kk, v = _a(q, fp8), _a(kk, fp8), _a(v, fp8)
    q = _a(_rope(q, pos, m.rope_theta), fp8) / jnp.sqrt(jnp.float32(d))
    kk = _a(_rope(kk, pos, m.rope_theta), fp8)
    # queries in blocks (the last one padded) so the scores stay small
    nb = -(-t // Q_BLOCK)
    qg = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - t), (0, 0)))
    qg = qg.reshape(k, h // k, nb, Q_BLOCK, d)

    def block(j):
        rows = j * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("kgqd,ksd->kgqs", qg[:, :, j], kk, precision=HI)
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        p = _a(jax.nn.softmax(s, axis=-1), fp8)
        return _a(jnp.einsum("kgqs,ksd->kgqd", p, v, precision=HI), fp8)

    o = jax.lax.map(block, jnp.arange(nb))          # (nb, k, g, Q, d)
    o = jnp.moveaxis(o, 0, 2).reshape(h, nb * Q_BLOCK, d)[:, :t]
    x = _a(x + _mm("htd,hde->te", o, lw["wo"], fp8, (0, 1)), fp8)
    b = _a(_rms(x, lw["ln2"], m.eps), fp8)
    gate = _mm("te,ef->tf", b, lw["w_gate"], fp8, (0,))
    up = _mm("te,ef->tf", b, lw["w_up"], fp8, (0,))
    hid = _a(jax.nn.silu(gate) * up, fp8)
    return _a(x + _mm("tf,fe->te", hid, lw["w_down"], fp8, (0,)), fp8)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _head(x, rows, final_norm, lm_head, m, fp8):
    """Logits (R, V) float32 at sequence rows `rows`."""
    hsel = _a(_rms(x[rows], final_norm, m.eps), fp8)
    v = lm_head.shape[1]
    n = -(-v // VOCAB_CHUNK)
    while v % n:
        n += 1
    c = v // n

    def chunk(i):
        # logits leave the fp8 path as float32 accumulations, unrounded
        w = jax.lax.dynamic_slice_in_dim(lm_head, i * c, c, axis=1)
        w = w.astype(jnp.float32)
        w = _fp8(w, (0,)) if fp8 else w
        return jnp.einsum("re,ev->rv", hsel, w, precision=HI)

    out = jax.lax.map(chunk, jnp.arange(n))          # (n, R, c)
    return jnp.moveaxis(out, 0, 1).reshape(hsel.shape[0], v)


class Spec:
    """Hashable static view of a config file's model block."""

    def __init__(self, m: dict):
        self.heads = m["num_attention_heads"]
        self.kv_heads = m["num_key_value_heads"]
        self.head_dim = m["head_dim"]
        self.eps = float(m["rms_norm_eps"])
        self.rope_theta = float(m["rope_theta"])
        self._key = (self.heads, self.kv_heads, self.head_dim, self.eps,
                     self.rope_theta)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Spec) and self._key == other._key


def logits(w: dict, m: dict, tokens, rows, precision: str = "f32"):
    """Reference logits (len(rows), V) float32 for one sequence `tokens`
    (T,) at positions `rows` (the logits at row r predict token r + 1)."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    fp8 = precision == "fp8"
    spec = Spec(m)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _a(jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32), fp8)
    for i in range(m["num_hidden_layers"]):
        x = _layer(x, w["layers"], jnp.int32(i), spec, fp8)
    return _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                 w["lm_head"], spec, fp8)
