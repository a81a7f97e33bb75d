"""A dense GQA decoder (Llama layout: Yi, Qwen2) as the harness sees it.

Everything in the benchmark that depends on the architecture sits behind
one such module, `bench/adapters/<reference>.py`, found by the `reference`
name a configuration file carries (as `refs/` and `counts/` are):

    leaves(model)              {leaf name: (shape, init)}, nested: the
                               neutral layout the reference reads
    to_program(w, model)       the engine's parameter tree over the same
                               arrays (no copy)
    arch_config(model, name)   the program's ArchConfig
    KERNELS                    kernel names, each a `bench/counts/<kernel>.py`
                               with `OP_NAMES` and `step(model, policy, slots)`
    kv_values_per_token(model) K/V values the cache stores per token, over
                               all layers

The neutral layout here (stacked over layers):

    embed (V, E)  lm_head (E, V)  final_norm (E,)
    layers: ln1 (L, E)  wq (L, E, H, D)  wk/wv (L, E, K, D)  wo (L, H, D, E)
            [bq (L, H, D)  bk/bv (L, K, D)]  ln2 (L, E)
            w_gate/w_up (L, E, F)  w_down (L, F, E)

`init` is one of the draws `bench/lib/weights.py` makes: "gain", "bias",
"embed", or ("matrix", fan_in).
"""

from __future__ import annotations

KERNELS = ("paged_qattn",)

_GAINS = ("ln1", "ln2", "final_norm")
_BIASES = ("bq", "bk", "bv")


def _init(name: str, shape):
    if name in _GAINS:
        return "gain"
    if name in _BIASES:
        return "bias"
    if name == "embed":
        return "embed"
    if name == "wo":
        return ("matrix", shape[-3] * shape[-2])
    return ("matrix", shape[-3] if name in ("wq", "wk", "wv") else shape[-2])


def leaves(m: dict) -> dict:
    """Leaf shapes and draws of the neutral layout for a model spec `m`
    (the configuration file's `model` block)."""
    e, h, k, d = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    layers = {"ln1": (n, e), "wq": (n, e, h, d), "wk": (n, e, k, d),
              "wv": (n, e, k, d), "wo": (n, h, d, e), "ln2": (n, e),
              "w_gate": (n, e, f), "w_up": (n, e, f), "w_down": (n, f, e)}
    if m["qkv_bias"]:
        layers.update(bq=(n, h, d), bk=(n, k, d), bv=(n, k, d))
    tree = {"embed": (v, e), "lm_head": (e, v), "final_norm": (e,)}
    out = {name: (s, _init(name, s)) for name, s in tree.items()}
    out["layers"] = {name: (s, _init(name, s)) for name, s in layers.items()}
    return out


def to_program(w: dict, m: dict) -> dict:
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


def arch_config(m: dict, name: str):
    """The program's ArchConfig for a configuration file's model block."""
    from repro.configs.base import ArchConfig
    # the program's dense family derives head_dim as hidden_size / heads
    if m["hidden_size"] != m["num_attention_heads"] * m["head_dim"]:
        raise ValueError(
            f"{name}: hidden_size {m['hidden_size']} is not "
            f"num_attention_heads {m['num_attention_heads']} x head_dim "
            f"{m['head_dim']}, which a dense GQA model must be")
    return ArchConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        qkv_bias=bool(m["qkv_bias"]),
        norm_eps=float(m["rms_norm_eps"]),
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]))


def kv_values_per_token(m: dict) -> int:
    """K and V, one head_dim vector per KV head, in every layer."""
    return m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"] * 2
