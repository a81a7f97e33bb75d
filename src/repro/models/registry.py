"""Uniform model API over the zoo: schema / loss / prefill / decode dispatch.

Launchers, tests and the dry-run all consume models only through this module,
so decoder-only and encoder-decoder families (and the frontend stubs) stay
behind one interface.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import blocks, common, encdec, lm


def schema(cfg: ArchConfig) -> dict:
    return encdec.encdec_schema(cfg) if cfg.encdec else lm.lm_schema(cfg)


def abstract_params(cfg: ArchConfig):
    return common.abstract(schema(cfg))


def materialize_params(cfg: ArchConfig, seed: int = 0, device=None):
    return common.materialize(schema(cfg), seed, device=device)


def param_logical_specs(cfg: ArchConfig):
    return common.logical_specs(schema(cfg))


def loss_fn(params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig,
            ctx: Optional[blocks.RunCtx] = None):
    if cfg.encdec:
        return encdec.loss_fn(params, batch, cfg, ctx)
    return lm.loss_fn(params, batch, cfg, ctx)


def prefill(params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig, ctx: blocks.RunCtx):
    if cfg.encdec:
        logits, caches = encdec.forward(
            params, batch["frontend_embeds"], batch["tokens"], cfg, ctx,
            build_cache=True, remat=False)
        return logits[:, -1], caches
    out = lm.prefill(params, batch["tokens"], cfg, ctx,
                     frontend_embeds=batch.get("frontend_embeds"))
    return out.logits_last, out.caches


def decode_step(params, token: jnp.ndarray, caches: Any, cfg: ArchConfig,
                ctx: blocks.RunCtx, is_probe: jnp.ndarray,
                active: Optional[jnp.ndarray] = None):
    """is_probe: () or (b,) probe flags; active: optional (b,) live-slot mask
    (continuous batching — masked slots don't append KV or advance state)."""
    if cfg.encdec:
        return encdec.decode_step(params, token, caches, cfg, ctx, is_probe, active)
    out = lm.decode_step(params, token, caches, cfg, ctx, is_probe, active)
    return out.logits, out.caches


def recompress(caches: Any, cfg: ArchConfig, ctx: blocks.RunCtx,
               rows: Optional[jnp.ndarray] = None, slot=None, rung=None):
    """rows: optional (b,) bool — restrict recompression to those slots
    (per-request cadence, paper Alg. 3 under continuous batching).
    slot: optional traced scalar — recompress exactly ONE slot via the
    backend's per-slot program (paged layout: ~1/batch the FLOPs of the
    rows-masked program; requires ctx.backend.recompress_slot).
    rung: optional traced int32 downshift rung(s) — (b,) with rows, scalar
    with slot — lowering the folded slots' lo-store effective bits (the
    pressure ladder; decoder-only caches only)."""
    if cfg.encdec:
        assert slot is None, "per-slot recompress: decoder-only caches only"
        assert rung is None, "downshift ladder: decoder-only caches only"
        def fn(_, sc):
            return (), encdec.DecLayerCaches(
                ctx.backend.recompress(sc.self_cache, rows=rows), sc.cross_cache)
        _, new = jax.lax.scan(fn, (), caches)
        return new
    return lm.recompress_caches(caches, cfg, ctx, rows=rows, slot=slot,
                                rung=rung)


def insert_caches(dst: Any, src: Any, slot) -> Any:
    """Insert a 1-request cache slice into batch row `slot` of a running
    decode batch (jetstream-style).  Handles both cache tree layouts: the lm
    dict ({"prefix": [per-layer], "groups": leaves stacked (G, b, ...)}) and
    the encdec scanned tree (leaves stacked (L, b, ...)) — and both cache
    element layouts: paged elements scatter onto the slot's pages, everything
    else (mixed caches, SSM states) is a plain leading-axis row write.
    Jittable with a traced `slot`; static shapes preserved.

    Extension point: the generic row-write is only correct for layouts whose
    leaves are directly batch-indexed.  A new `CacheBackend` layout with
    indirection (per-head pools, radix trees) must add its element dispatch
    here and in `free_caches`, as the paged layout does."""
    from repro.core import kvcache as kvc
    from repro.core import paged as paged_lib

    def ins(d, s, axis):
        # flatten with paged elements as leaves: they need table-mediated
        # writes, the rest pairs up positionally for plain row updates
        is_paged = lambda x: isinstance(x, paged_lib.PagedKVCache)
        d_leaves, treedef = jax.tree_util.tree_flatten(d, is_leaf=is_paged)
        s_leaves = jax.tree_util.tree_leaves(s, is_leaf=is_paged)
        if len(d_leaves) != len(s_leaves):
            raise ValueError(
                f"cache slice has {len(s_leaves)} elements, batch has {len(d_leaves)}")
        out = [paged_lib.insert_slot(dl, sl, slot, batch_axis=axis)
               if is_paged(dl)
               else kvc.tree_update_rows(dl, sl, slot, axis=axis)
               for dl, sl in zip(d_leaves, s_leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    if isinstance(dst, dict) and "prefix" in dst:
        prefix = [ins(d, s, 0) for d, s in zip(dst["prefix"], src["prefix"])]
        groups = ins(dst["groups"], src["groups"], 1)
        return {"prefix": prefix, "groups": groups}
    return ins(dst, src, 1)


def extract_caches(caches: Any, slot) -> Any:
    """Capture one slot's complete state across the cache tree — the device
    half of swap-out (`core/swap.py` mirrors the result into host buffers).
    Paged elements gather their payload pages through the slot's table plus
    their metadata rows (`paged.extract_slot`); everything else (mixed
    caches, SSM states) is a plain leading-axis row slice.  The result is a
    pytree of arrays, NOT a cache — `restore_caches` pairs it back up
    positionally against the live tree, same contract as `insert_caches`.
    Jittable with a traced `slot`; static shapes (one warm program serves
    every slot and occupancy)."""
    from repro.core import paged as paged_lib

    def ext(d, axis):
        is_paged = lambda x: isinstance(x, paged_lib.PagedKVCache)
        leaves = jax.tree_util.tree_flatten(d, is_leaf=is_paged)[0]
        return [paged_lib.extract_slot(el, slot, batch_axis=axis)
                if is_paged(el)
                else jax.lax.dynamic_slice_in_dim(el, slot, 1, axis=axis)
                for el in leaves]

    if isinstance(caches, dict) and "prefix" in caches:
        return {"prefix": [ext(d, 0) for d in caches["prefix"]],
                "groups": ext(caches["groups"], 1)}
    return ext(caches, 1)


def restore_caches(caches: Any, payload: Any, slot) -> Any:
    """Inverse of `extract_caches`: write a swapped-out slot's payload back
    into batch row `slot` of the live tree.  Paged elements scatter onto the
    physical pages the allocator re-granted host-side (`paged.restore_slot`);
    the rest are plain row writes.  Bitwise: the restored rows/pages are
    exactly the bytes `extract_caches` captured, so a swapped-then-restored
    request decodes identically to one that was never evicted."""
    from repro.core import paged as paged_lib

    def rst(d, p, axis):
        is_paged = lambda x: isinstance(x, paged_lib.PagedKVCache)
        leaves, treedef = jax.tree_util.tree_flatten(d, is_leaf=is_paged)
        if len(leaves) != len(p):
            raise ValueError(
                f"swap payload has {len(p)} elements, batch has {len(leaves)}")
        out = [paged_lib.restore_slot(el, pl, slot, batch_axis=axis)
               if is_paged(el)
               else jax.lax.dynamic_update_slice_in_dim(
                   el, pl.astype(el.dtype), slot, axis=axis)
               for el, pl in zip(leaves, p)]
        return jax.tree_util.tree_unflatten(treedef, out)

    if isinstance(caches, dict) and "prefix" in caches:
        prefix = [rst(d, p, 0)
                  for d, p in zip(caches["prefix"], payload["prefix"])]
        groups = rst(caches["groups"], payload["groups"], 1)
        return {"prefix": prefix, "groups": groups}
    return rst(caches, payload, 1)


def copy_caches(caches: Any, moves: Any) -> Any:
    """Apply one set of physical page moves ({segment: (src_ids, dst_ids)})
    to every paged element of the cache tree — the device half of
    copy-on-write privatization (core/alloc.py `privatize`).  The page
    POOLS are segment-shaped, identical across layers/groups, and every
    element shares the one allocator table, so a single move set is valid
    tree-wide; group-stacked leaves broadcast inside `paged.copy_pages`.
    Non-paged elements are untouched (dedup is a paged-freelist feature)."""
    from repro.core import paged as paged_lib

    is_paged = lambda x: isinstance(x, paged_lib.PagedKVCache)
    return jax.tree_util.tree_map(
        lambda el: paged_lib.copy_pages(el, moves) if is_paged(el) else el,
        caches, is_leaf=is_paged)


def free_caches(caches: Any, slot) -> Any:
    """Retire batch row `slot` across the whole cache tree: invalidate each
    layer's positions/counters (cheap row writes — see kvcache.free_slot;
    the paged layout's pages stay untouched, validity is pos-driven).
    Non-KV elements (SSM states) are left stale: they are masked while the
    slot is inactive and fully overwritten by the next insert_caches."""
    from repro.core import backend as backend_lib
    from repro.core import kvcache as kvc
    from repro.core import paged as paged_lib

    def fr(el, axis):
        if isinstance(el, paged_lib.PagedKVCache):
            return paged_lib.free_slot(el, slot, batch_axis=axis)
        if isinstance(el, kvc.MixedKVCache):
            return kvc.free_slot(el, slot, batch_axis=axis)
        return el

    is_cache = backend_lib.is_kv_cache
    if isinstance(caches, dict) and "prefix" in caches:
        prefix = [fr(el, 0) for el in caches["prefix"]]
        groups = jax.tree_util.tree_map(
            lambda el: fr(el, 1), caches["groups"], is_leaf=is_cache)
        return {"prefix": prefix, "groups": groups}
    return jax.tree_util.tree_map(
        lambda el: fr(el, 1), caches, is_leaf=is_cache)


def init_caches(cfg: ArchConfig, ctx: blocks.RunCtx, b: int, l_src: int = 0,
                dtype=jnp.bfloat16):
    if cfg.encdec:
        return encdec.init_caches(cfg, ctx, b, l_src, dtype)
    return lm.init_caches(cfg, ctx, b, dtype)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — the dry-run contract)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_spec(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, l = shape.global_batch, shape.seq_len
    if cfg.encdec:
        return {
            "frontend_embeds": _sds((b, l, cfg.d_model), dtype),
            "tokens": _sds((b, l), jnp.int32),
            "labels": _sds((b, l), jnp.int32),
        }
    if cfg.frontend != "none":
        n_f = cfg.n_frontend_tokens
        return {
            "frontend_embeds": _sds((b, n_f, cfg.d_model), dtype),
            "tokens": _sds((b, l - n_f), jnp.int32),
            "labels": _sds((b, l - n_f), jnp.int32),
        }
    return {"tokens": _sds((b, l), jnp.int32), "labels": _sds((b, l), jnp.int32)}


def prefill_lengths(cfg: ArchConfig, shape: ShapeConfig):
    """(decoder/query prefill length, encoder source length or 0).

    Probe specs must be built on the QUERY length returned here."""
    l = shape.seq_len
    if cfg.encdec:
        return min(128, l), l
    if cfg.frontend != "none":
        return l, 0  # frontend tokens are part of the query sequence
    return l, 0


def prefill_batch_spec(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, l = shape.global_batch, shape.seq_len
    if cfg.encdec:
        # source occupies the assigned seq_len; decoder prompt is short
        dec_len, _ = prefill_lengths(cfg, shape)
        return {
            "frontend_embeds": _sds((b, l, cfg.d_model), dtype),
            "tokens": _sds((b, dec_len), jnp.int32),
        }
    if cfg.frontend != "none":
        n_f = cfg.n_frontend_tokens
        return {
            "frontend_embeds": _sds((b, n_f, cfg.d_model), dtype),
            "tokens": _sds((b, l - n_f), jnp.int32),
        }
    return {"tokens": _sds((b, l), jnp.int32)}


def decode_token_spec(cfg: ArchConfig, shape: ShapeConfig) -> Any:
    return _sds((shape.global_batch,), jnp.int32)


def materialize_batch(spec: Dict[str, Any], seed: int = 0, vocab: int = 256):
    """Concrete random batch matching a spec (smoke tests)."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, s in spec.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, s.shape, 0, vocab, dtype=s.dtype)
        else:
            out[name] = jax.random.normal(sub, s.shape, jnp.float32).astype(s.dtype)
    return out
