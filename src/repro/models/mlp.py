"""FFN mixers: dense SwiGLU and fine-grained MoE (DeepSeek/Jamba style).

MoE design (see DESIGN.md §4): experts are sharded over the `model` mesh axis
(expert parallelism).  Dispatch is capacity-based slotting computed LOCALLY
per shard inside `shard_map` — tokens are already replicated across the model
axis (they are data-sharded only), so each expert shard gathers its own
experts' tokens without any all-to-all; the combine is a single psum over
`model`, the same collective a Megatron TP MLP would issue.  Without a mesh
(smoke tests) the same dispatch runs with all experts local.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import common
from repro.models.common import ParamDef


def dense_mlp_schema(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    e, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamDef((e, f), ("embed", "mlp")),
        "w_up": ParamDef((e, f), ("embed", "mlp")),
        "w_down": ParamDef((f, e), ("mlp", "embed")),
    }


def dense_mlp(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return common.swiglu(x, params["w_gate"], params["w_up"], params["w_down"])


def moe_schema(cfg: ArchConfig) -> dict:
    e, f, n = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    # expert weights use "expert_in" (NOT "embed") so they are sharded over
    # the model axis only — FSDP-sharding their inner dim would force a
    # reshard at the shard_map boundary (involuntary full remat in SPMD).
    s = {
        "router": ParamDef((e, n), ("embed", "experts"), init="small", dtype=jnp.float32),
        "w_gate": ParamDef((n, e, f), ("experts", "expert_in", "moe_mlp")),
        "w_up": ParamDef((n, e, f), ("experts", "expert_in", "moe_mlp")),
        "w_down": ParamDef((n, f, e), ("experts", "moe_mlp", "expert_in")),
    }
    if cfg.n_shared_experts:
        s["shared"] = dense_mlp_schema(cfg, cfg.n_shared_experts * cfg.moe_d_ff)
    return s


class MoEOut(NamedTuple):
    y: jnp.ndarray
    aux_loss: jnp.ndarray


def _expert_ffn(buf: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """buf: (E_loc, C, e) -> (E_loc, C, e), per-expert SwiGLU."""
    g = jnp.einsum("xce,xef->xcf", buf, w_gate)
    u = jnp.einsum("xce,xef->xcf", buf, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("xcf,xfe->xce", h, w_down)


def _dispatch_compute(
    x_flat: jnp.ndarray,      # (N, e) local tokens
    gates: jnp.ndarray,       # (N, k) fp32 combine weights
    eidx: jnp.ndarray,        # (N, k) int32 global expert ids
    w_gate: jnp.ndarray,      # (E_loc, e, f)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    e_offset: jnp.ndarray,    # scalar: first global expert id on this shard
    capacity: int,
) -> jnp.ndarray:
    """Capacity-slotted local MoE dispatch → (N, e) partial output
    (contributions of this shard's experts only)."""
    n, k = eidx.shape
    e_loc = w_gate.shape[0]
    flat_e = (eidx.reshape(-1) - e_offset).astype(jnp.int32)
    valid = (flat_e >= 0) & (flat_e < e_loc)
    key = jnp.where(valid, flat_e, e_loc)            # invalids sort last
    sort_idx = jnp.argsort(key, stable=True)
    sorted_e = key[sort_idx]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc), side="left")
    pos_in_e = jnp.arange(n * k) - starts[jnp.clip(sorted_e, 0, e_loc - 1)]
    keep = (sorted_e < e_loc) & (pos_in_e < capacity)
    dest = jnp.where(keep, sorted_e * capacity + pos_in_e, e_loc * capacity)
    token_of = sort_idx // k

    buf = jnp.zeros((e_loc * capacity + 1, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[token_of], mode="drop")
    h = _expert_ffn(buf[: e_loc * capacity].reshape(e_loc, capacity, -1), w_gate, w_up, w_down)
    h_flat = jnp.concatenate([h.reshape(e_loc * capacity, -1),
                              jnp.zeros((1, h.shape[-1]), h.dtype)], axis=0)
    contrib = h_flat[dest] * gates.reshape(-1)[sort_idx][:, None].astype(h.dtype)
    out = jnp.zeros_like(x_flat).at[token_of].add(
        jnp.where(keep[:, None], contrib, 0), mode="drop")
    return out


def moe_ffn(
    params: dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    mesh=None,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
) -> MoEOut:
    """Fine-grained MoE FFN. x: (b, s, e) (s may be 1 for decode)."""
    b, s, e = x.shape
    n_exp, k = cfg.n_experts, cfg.top_k

    logits = jnp.einsum("bse,en->bsn", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style): E * Σ_e f_e * P_e
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, n_exp, dtype=jnp.float32), axis=2), axis=(0, 1))
    aux = n_exp * jnp.sum(me * ce) * cfg.router_aux_coef

    gates_f = gate_vals.reshape(b * s, k)
    eidx_f = eidx.reshape(b * s, k).astype(jnp.int32)
    x_flat = x.reshape(b * s, e)

    if mesh is None:
        cap = max(1, int(math.ceil(b * s * k / n_exp * cfg.capacity_factor)))
        y = _dispatch_compute(
            x_flat, gates_f, eidx_f, params["w_gate"], params["w_up"], params["w_down"],
            jnp.zeros((), jnp.int32), cap)
        y = y.reshape(b, s, e)
    else:
        dp = math.prod(mesh.shape[a] for a in data_axes)
        ep = mesh.shape[model_axis]
        e_loc = n_exp // ep
        assert n_exp % ep == 0, f"experts {n_exp} not divisible by EP {ep}"
        # tokens shard over the data axes when divisible; tiny batches
        # (long-context decode with b=1) stay replicated.
        tokens_sharded = (b * s) % dp == 0 and b % dp == 0
        n_local = (b // dp) * s if tokens_sharded else b * s
        cap = max(1, int(math.ceil(n_local * k / n_exp * cfg.capacity_factor)))

        def shard_fn(xf, gf, ef, wg, wu, wd):
            off = jax.lax.axis_index(model_axis).astype(jnp.int32) * e_loc
            part = _dispatch_compute(xf, gf, ef, wg, wu, wd, off, cap)
            return jax.lax.psum(part, model_axis)

        tok = P(tuple(data_axes), None) if tokens_sharded else P(None, None)
        exp3 = P(model_axis, None, None)
        y = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(tok, tok, tok, exp3, exp3, exp3),
            out_specs=tok,
            check_vma=False,
        )(x_flat, gates_f, eidx_f, params["w_gate"], params["w_up"], params["w_down"])
        y = y.reshape(b, s, e)

    if cfg.n_shared_experts:
        y = y + dense_mlp(params["shared"], x)
    return MoEOut(y, aux)
