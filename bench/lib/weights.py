"""Seeded random weights for a dense GQA decoder, made by the benchmark.

The benchmark makes the weights itself, so the plain reference never reads
anything the program under test made.  One jitted call draws every leaf on
the device, straight in the served type (bf16): each leaf's draw, scale and
cast fuse, so no whole-leaf float32 temporary exists.

The neutral layout (stacked over layers) is what the reference reads:

    embed (V, E)  lm_head (E, V)  final_norm (E,)
    layers: ln1 (L, E)  wq (L, E, H, D)  wk/wv (L, E, K, D)  wo (L, H, D, E)
            [bq (L, H, D)  bk/bv (L, K, D)]  ln2 (L, E)
            w_gate/w_up (L, E, F)  w_down (L, F, E)

`to_program` re-nests the same arrays (no copy) into the tree the serving
engine takes.
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


def shapes(m: dict) -> dict:
    """Leaf shapes of the neutral layout for a model spec `m` (the config
    file's `model` block)."""
    e, h, k, d = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    layers = {"ln1": (n, e), "wq": (n, e, h, d), "wk": (n, e, k, d),
              "wv": (n, e, k, d), "wo": (n, h, d, e), "ln2": (n, e),
              "w_gate": (n, e, f), "w_up": (n, e, f), "w_down": (n, f, e)}
    if m["qkv_bias"]:
        layers.update(bq=(n, h, d), bk=(n, k, d), bv=(n, k, d))
    return {"embed": (v, e), "lm_head": (e, v), "final_norm": (e,),
            "layers": layers}


def _fan_in(name: str, shape) -> int:
    if name in ("wo",):
        return shape[-3] * shape[-2]
    return shape[-3] if name in ("wq", "wk", "wv") else shape[-2]


def _leaf(name: str, shape, key):
    if name in ("ln1", "ln2", "final_norm"):
        return (1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(DTYPE)
    if name in ("bq", "bk", "bv"):
        return (BIAS_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(DTYPE)
    std = 1.0 if name == "embed" else 1.0 / math.sqrt(_fan_in(name, shape))
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * std).astype(DTYPE)


def make(m: dict, seed: int, device=None) -> dict:
    """Every weight of model spec `m`, drawn from `seed` in one jitted call
    on `device` (default: JAX's default device)."""
    tree = shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(path[-1].key) for path, _ in flat]
    leaf_shapes = [s for _, s in flat]

    def build(key):
        keys = jax.random.split(key, len(names))
        return [_leaf(n, s, k) for n, s, k in zip(names, leaf_shapes, keys)]

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    # the seed may exceed 32 bits: fold its high word in separately
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    vals = jax.jit(build, out_shardings=out)(key)
    return jax.tree_util.tree_unflatten(treedef, vals)


def to_program(w: dict) -> dict:
    """The serving engine's parameter tree over the same arrays."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": w["final_norm"],
            "groups": {"sub0": {"ln1": lw["ln1"], "attn": attn,
                                "ln2": lw["ln2"],
                                "mlp": {k: lw[k] for k in
                                        ("w_gate", "w_up", "w_down")}}}}
