"""Seeded random weights, made by the benchmark.

The benchmark makes the weights itself, so the plain reference never reads
anything the program under test made.  One jitted call draws every leaf on
the device, straight in the served type (bf16): each leaf's draw, scale and
cast fuse, so no whole-leaf float32 temporary exists.

The layout comes from the configuration's adapter
(`bench/adapters/<reference>.py`, `leaves(model)`): a nested dict of
{leaf name: (shape, init)}, where `init` is one of

    "gain"              1 + 0.1 N(0, 1)          (norm gains)
    "bias"              0.1 N(0, 1)              (biases)
    "embed"             truncated normal in [-2, 2]
    ("matrix", fan_in)  truncated normal in [-2, 2] / sqrt(fan_in)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
# Biases and norm gains are drawn around their usual values so that both
# paths are exercised: a zero bias or a unit gain would hide a dropped term.
BIAS_STD = 0.1
GAIN_STD = 0.1


def _leaf(init, shape, key):
    if init == "gain":
        return (1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(DTYPE)
    if init == "bias":
        return (BIAS_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(DTYPE)
    if init == "embed":
        std = 1.0
    elif isinstance(init, tuple) and init[0] == "matrix":
        std = 1.0 / math.sqrt(init[1])
    else:
        raise ValueError(f"unknown init {init!r}")
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * std).astype(DTYPE)


def make(leaves: dict, seed: int, device=None) -> dict:
    """Every leaf of the layout `leaves`, drawn from `seed` in one jitted
    call on `device` (default: JAX's default device)."""
    flat, treedef = jax.tree_util.tree_flatten(
        leaves, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        keys = jax.random.split(key, len(flat))
        return [_leaf(init, shape, k) for (shape, init), k in zip(flat, keys)]

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    # the seed may exceed 32 bits: fold its high word in separately
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    vals = jax.jit(build, out_shardings=out)(key)
    return jax.tree_util.tree_unflatten(treedef, vals)
