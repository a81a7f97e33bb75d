"""Pipeline parallelism (GPipe schedule) over a dedicated `stage` mesh axis.

Completes the parallelism matrix (DP x FSDP x TP x EP x split-KV + PP): for
depth-dominated models, the layer-group stack (already the scan axis) shards
over `stage` — each stage owns n_groups/S contiguous groups — and activations
flow stage-to-stage with `ppermute` under `shard_map`.  The GPipe schedule
runs M microbatches through S stages in M + S - 1 ticks (bubble fraction
(S-1)/(M+S-1)); reverse-mode AD differentiates straight through the permutes,
so the same factory yields a pipelined train step.

Scope: homogeneous-scan dense archs (the MoE dispatch uses its own shard_map,
and shard_map does not nest) — yi-6b/34b, qwen2-7b, smollm, llava backbone.
Embedding/unembed stay outside the pipelined region (replicated over stage).

Usage:
    mesh = make_pp_mesh(stages=4, data=8, model=8)   # 256 chips
    step = make_pp_train_step(cfg, mesh, microbatches=8)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shd
from repro.models import blocks, common, registry
from repro.optim import adamw


def make_pp_mesh(stages: int = 4, data: int = 8, model: int = 8) -> Mesh:
    """PP mesh. With data=model=1 a pure 1-axis stage mesh is built — the
    fully-manual configuration validated in-container.  Mixed PP x TP x DP
    (real data/model extents) uses shard_map's partial-auto mode, which the
    XLA:CPU partitioner in this container rejects with an internal check-fail
    ("Invalid binary instruction opcode copy") on full model graphs; it is
    the MaxText-style TPU-backend configuration and is left as TPU-target
    (recorded in DESIGN.md)."""
    if data == 1 and model == 1:
        return mesh_lib.make_mesh((stages,), ("stage",))
    return mesh_lib.make_mesh((stages, data, model), ("stage", "data", "model"))


def supports_pp(cfg: ArchConfig) -> bool:
    """Homogeneous dense stacks only (no in-layer shard_map, no prefix)."""
    return (not cfg.encdec and not cfg.n_experts and not cfg.ssm
            and cfg.attn_layer_period == 0 and cfg.first_dense_layers == 0)


def _stage_forward(gparams, x, cfg: ArchConfig, ctx: blocks.RunCtx):
    """Run this stage's layer groups (leading axis = local groups)."""
    def group_fn(carry, gp):
        y, _, _ = blocks.apply_group_full(gp, carry, cfg, ctx, build_cache=False)
        return y, ()
    x, _ = jax.lax.scan(group_fn, x, gparams)
    return x


def pp_forward(params, tokens, cfg: ArchConfig, mesh: Mesh,
               microbatches: int, ctx: Optional[blocks.RunCtx] = None):
    """Pipelined forward -> logits (b, l, vocab sharded as usual).

    tokens: (B, L) with B % (microbatches * data) == 0.
    """
    if ctx is None:
        ctx = (blocks.RunCtx(mesh=mesh, data_axes=("data",))
               if "data" in mesh.axis_names else blocks.RunCtx())
    # inside the stage-manual region the layer code must not issue
    # with_sharding_constraint (mixed manual/auto WSC trips an XLA:CPU
    # check-fail); GSPMD auto-propagates data/model sharding from the inputs.
    inner_ctx = blocks.RunCtx(q_block=ctx.q_block)
    S = mesh.shape["stage"]
    n_groups = cfg.n_scan_groups
    assert n_groups % S == 0, (n_groups, S)
    B, L = tokens.shape
    M = microbatches
    assert B % M == 0

    x = common.embed_lookup(params["embed"], tokens, ctx=ctx)   # (B, L, e)
    x = x.reshape(M, B // M, L, -1)

    perm = [(i, (i + 1) % S) for i in range(S)]

    def staged(gparams, x_mbs):
        # gparams: this stage's (n_groups/S, ...) slice;  x_mbs: (M, mb, L, e)
        sidx = jax.lax.axis_index("stage")
        mb_shape = x_mbs.shape[1:]
        buf = jnp.zeros(mb_shape, x_mbs.dtype)     # activation held by stage
        outs = jnp.zeros_like(x_mbs)               # last stage's results

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t; others receive from stage-1
            recv = jax.lax.ppermute(buf, "stage", perm)
            inject = x_mbs[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(sidx == 0,
                            jnp.where(t < M, inject, jnp.zeros_like(inject)),
                            recv)
            out = _stage_forward(gparams, cur, cfg, inner_ctx)
            # the microbatch finishing at the LAST stage on tick t entered at
            # tick t - (S - 1)
            done_idx = t - (S - 1)
            is_done = (sidx == S - 1) & (done_idx >= 0) & (done_idx < M)
            outs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(done_idx, 0, M - 1), axis=0),
                lambda o: o, outs)
            return (out, outs), ()

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(M + S - 1))
        # broadcast the last stage's outputs to every stage (zeros elsewhere)
        mask = (sidx == S - 1).astype(x_mbs.dtype)
        return jax.lax.psum(outs * mask, "stage")

    # manual over `stage` only (`axis_names`); data/model stay GSPMD-auto
    # so the per-stage layer code keeps its usual TP/DP shardings (incl.
    # WSC constraints)
    y = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
        check_vma=False,
        axis_names={"stage"},
    )(params["groups"], x)
    y = y.reshape(B, L, -1)
    from repro.models import lm
    return lm.unembed(params, cfg, y)


def make_pp_train_step(cfg: ArchConfig, mesh: Mesh, microbatches: int = 4,
                       opt_cfg: Optional[adamw.AdamWConfig] = None,
                       q_block: int = 512):
    """Pipelined train_step(params, opt_state, batch) -> (params, opt, metrics)."""
    assert supports_pp(cfg), f"{cfg.name}: PP supports homogeneous dense stacks"
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    ctx = (blocks.RunCtx(mesh=mesh, data_axes=("data",), q_block=q_block)
           if "data" in mesh.axis_names else blocks.RunCtx(q_block=q_block))

    def loss_of(params, batch):
        logits = pp_forward(params, batch["tokens"], cfg, mesh, microbatches, ctx)
        return common.cross_entropy_loss(logits, batch["labels"], batch.get("mask"))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        params, opt_state, met = adamw.adamw_update(opt_cfg, grads, opt_state)
        return params, opt_state, {"loss": loss, **met}

    return train_step


def pp_param_shardings(cfg: ArchConfig, mesh: Mesh):
    """Default rules + the layer-stack ('layers') axis sharded over stage."""
    return shd.param_shardings(cfg, mesh, overrides={"layers": "stage"})


def pp_lowering_inputs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    aparams = registry.abstract_params(cfg)
    aopt = adamw.adamw_init_abstract(aparams)
    abatch = registry.train_batch_spec(cfg, shape)
    p_shard = pp_param_shardings(cfg, mesh)
    z_shard = shd.zero1_shardings(cfg, mesh, overrides={"layers": "stage"})
    o_shard = adamw.AdamWState(z_shard, z_shard, z_shard, shd.replicated(mesh))
    b_shard = shd.batch_shardings(abatch, mesh)
    return (aparams, aopt, abatch), (p_shard, o_shard, b_shard), (p_shard, o_shard, None)
