"""Shared model machinery: schema-first parameters, norms, rotary embeddings.

Parameters are declared as a SCHEMA (nested dict of `ParamDef`), from which we
can derive, without ever allocating full arrays:
  * `abstract(schema)`      -> ShapeDtypeStruct pytree (for .lower())
  * `logical_specs(schema)` -> logical-axis-name pytree (for sharding rules)
  * `materialize(schema)`   -> real initialized params (for smoke tests/training)

Logical axis names used across the zoo:
  embed, vocab, heads, kv_heads, qk_dim, v_dim, head_dim, mlp, experts,
  moe_mlp, latent, rope_dim, ssm_in, ssm_state, ssm_heads, conv, layers, stage
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | embed | small
    scale: float = 1.0          # fan-in style multiplier applied at init
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(fn: Callable[[ParamDef], Any], schema):
    return jax.tree_util.tree_map(fn, schema, is_leaf=is_def)


def abstract(schema):
    return tree_map_defs(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), schema)


def logical_specs(schema):
    return tree_map_defs(lambda p: p.axes, schema)


def _init_leaf(p: ParamDef, key) -> jnp.ndarray:
    if p.init == "zeros":
        return jnp.zeros(p.shape, p.dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    fan_in = p.shape[0] if p.shape else 1
    if p.init == "embed":
        std = 1.0
    elif p.init == "small":
        std = 0.02
    else:  # normal: truncated-normal fan-in scaling
        std = 1.0 / math.sqrt(max(fan_in, 1))
    x = jax.random.truncated_normal(key, -2.0, 2.0, p.shape, jnp.float32) * std * p.scale
    return x.astype(p.dtype)


def materialize(schema, seed: int = 0, device=None):
    """Initialized params, built on `device` (default: the default device).

    One jitted program builds every leaf, so the compiler fuses each leaf's
    random draw, scaling and cast into the store dtype: no whole-leaf
    float32 temporary ever exists on the device (a 6B-parameter model would
    not fit its 16 GB chip otherwise).  Values equal the eager per-leaf
    build for the same seed."""
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=is_def)

    def build(key):
        keys = jax.random.split(key, max(len(leaves), 1))
        return [_init_leaf(p, k) for p, k in zip(leaves, keys)]

    out = (None if device is None
           else jax.sharding.SingleDeviceSharding(device))
    vals = jax.jit(build, out_shardings=out)(  # retrace: ok(params are built once per process, at setup)
        jax.random.PRNGKey(seed))
    return jax.tree_util.tree_unflatten(treedef, vals)


def stack_schema(schema, n: int, axis_name: str = "layers"):
    """Add a leading stacked dimension (for scan-over-layers parameters)."""
    return tree_map_defs(
        lambda p: dataclasses.replace(p, shape=(n, *p.shape), axes=(axis_name, *p.axes)),
        schema,
    )


def count_params(schema) -> int:
    leaves = jax.tree_util.tree_leaves(schema, is_leaf=is_def)
    return int(sum(np.prod(p.shape) for p in leaves))


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray, ctx=None) -> jnp.ndarray:
    """Embedding lookup as a one-hot matmul (TPU-native, MaxText iota-embed
    style): partitions cleanly when the table is sharded (vocab -> model,
    embed -> data/FSDP), where a gather forces SPMD replicate-fallback.

    Sharding constraints keep the (tokens, vocab) one-hot batch-sharded and
    force XLA to all-gather the (small) table's FSDP shards instead of the
    (enormous) one-hot — without them SPMD gathers the one-hot over batch.
    """
    oh = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    if ctx is not None and ctx.mesh is not None:
        oh = ctx.shard(oh, (ctx.data_axes,) + (None,) * (oh.ndim - 2) + ("model",))
        out = jnp.einsum("...v,ve->...e", oh, table)
        return ctx.shard(out, (ctx.data_axes,) + (None,) * (out.ndim - 1))
    return jnp.einsum("...v,ve->...e", oh, table)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * weight


def rotary_cos_sin(positions: jnp.ndarray, dim: int, theta: float, dtype=jnp.float32):
    """positions: (...,) int -> cos/sin (..., dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., seq, dim) with cos/sin (..., seq, dim/2) broadcastable."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...e,ef->...f", x, w_gate)
    u = jnp.einsum("...e,ef->...f", x, w_up)
    return jnp.einsum("...f,fe->...e", jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, w_down)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """Mean next-token CE over valid positions. logits (..., vocab) fp any."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
