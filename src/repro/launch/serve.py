"""Serving driver: --arch <id> batched generation with ZipCache compression.

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --policy zipcache --batch 4 --prompt-len 64 --max-new 32

--continuous switches to the continuous-batching engine (request lifecycle:
submit -> step -> result; slots admit/retire independently).

Choosing a backend (--backend):
  mixed  dense per-slot cache arrays; shardable over a mesh — the default,
         and the right choice for lockstep batches and multi-host serving.
  paged  payload in fixed-size pages behind per-slot page tables; slot
         insert/free touch only that slot's pages and staging windows fold
         with a per-slot program (no slots-times recompression FLOPs under
         staggered admission).  The trade: decode attention gathers the
         slot's pages into a dense view each step (mixed reads in place),
         so pick paged when admission/retirement churn and staggered
         recompression dominate, mixed for steady full batches.  Greedy
         output is token-identical either way
         (tests/test_backend_conformance.py).  Single-host today.
--page-size trades internal fragmentation (up to page_size-1 wasted tokens
per segment per slot) against page-table size and scatter/gather fan-out.
--paged-kernel on removes the paged backend's remaining decode-path tax:
attention runs in a Pallas kernel that walks the page tables and
dequantizes pages in place, instead of gathering every slot's pages into a
dense view each step.  Greedy output stays token-identical
(tests/test_backend_conformance.py); off keeps the gather path, which is
the bitwise cross-backend reference.
--page-allocator freelist (with --backend paged --continuous) switches the
page pools to free-list allocation: pages are granted to slots on demand
and returned when a request retires or its staging window folds, so the
pool can be provisioned below slots x max_len (--pool-fraction) and long
requests borrow pages freed by short ones; admission defers (backpressure)
when the pool cannot cover a request's worst case.  Greedy output stays
bitwise token-identical to the static assignment and to mixed.
--scheduler picks the continuous engine's admission policy
(serving/scheduler.py): fifo = strict submission order (the reference);
priority = highest Request.priority first.  --preemption recompute arms
vLLM-style eviction under the priority scheduler: a running lower-priority
slot can be evicted (pages returned, tokens retained host-side) so an
urgent request is never stuck behind a long-budget monopolist, and is
later re-admitted by replaying its retained tokens — deterministic, the
victim's final tokens are unchanged (tests/test_scheduling.py).
--preemption swap (freelist only) evicts by mirroring the victim's exact
quantized cache into host memory (--swap-pool-mb budgets the host tier)
and re-admits by uploading it back through a freshly granted page table —
no prefill replay, tokens bitwise unchanged.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro import configs
from repro.core.policy import CompressionConfig
from repro.launch import mesh as mesh_lib
from repro.models import registry
from repro.runtime import compile_cache
from repro.serving import (ContinuousEngine, Request, ServeConfig,
                           ServingEngine, pack_requests)


def add_engine_args(ap: argparse.ArgumentParser) -> None:
    """The engine/`ServeConfig` argument surface, shared by this batch
    driver and the HTTP front (`repro.launch.serve_http`) so the two CLIs
    cannot drift: every flag that feeds `ServeConfig` is declared ONCE,
    here, where the conformance-axes lint cross-checks it against the
    fixture (tools/analyze/conformance_axes.py)."""
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="zipcache")
    ap.add_argument("--saliency-ratio", type=float, default=0.4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="mixed", choices=("mixed", "paged"),
                    help="KV cache layout: mixed = dense per-slot arrays "
                         "(mesh-shardable); paged = page-pool payload behind "
                         "per-slot page tables (page-local insert/free, "
                         "per-slot recompress; single-host)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per page for --backend paged (smaller = "
                         "less partial-page waste, larger = less bookkeeping)")
    ap.add_argument("--paged-kernel", default="off", choices=("on", "off"),
                    help="--backend paged only: decode attention via the "
                         "page-walking Pallas kernel (no per-step dense "
                         "gather); off = gather+dense reference path")
    ap.add_argument("--page-allocator", default="static",
                    choices=("static", "freelist"),
                    help="--backend paged only: static = every slot owns its "
                         "worst-case pages from init; freelist = pages are "
                         "granted on demand from shared pools and returned "
                         "on retirement/fold (vLLM-style elasticity), with "
                         "admission deferred when the pool cannot cover a "
                         "request's worst case")
    ap.add_argument("--pool-fraction", type=float, default=1.0,
                    help="--page-allocator freelist only: pool capacity as "
                         "a fraction of the static worst case "
                         "(slots x ceil(capacity/page) pages per segment); "
                         "< 1.0 trades concurrency under long-budget load "
                         "for memory; > 1.0 provisions slack pages so "
                         "--prefix-cache registrations can retain pages "
                         "while every slot is running")
    ap.add_argument("--admit-watermark", type=float, default=0.0,
                    help="--page-allocator freelist only: fraction of each "
                         "pool held back as admission headroom (a request "
                         "is admitted only if its worst case fits with this "
                         "reserve left over)")
    ap.add_argument("--prefix-cache", default="off", choices=("off", "on"),
                    help="--page-allocator freelist only: content-hash "
                         "shared-prefix page dedup with copy-on-write "
                         "tables — identical page-aligned prompts alias one "
                         "set of immutable hi/lo pages and skip their "
                         "prefill; a shared slot is privatized (CoW) before "
                         "its first fold.  Greedy output stays bitwise "
                         "identical to off")
    ap.add_argument("--scheduler", default="fifo",
                    choices=("fifo", "priority"),
                    help="--continuous only: admission policy. fifo = strict "
                         "submission order (head-of-line blocking, the "
                         "reference); priority = highest Request.priority "
                         "first, FIFO within a class")
    ap.add_argument("--preemption", default="off",
                    choices=("off", "recompute", "downshift", "swap"),
                    help="--scheduler priority only: recompute lets the "
                         "scheduler evict a running lower-priority slot "
                         "(pages returned, tokens retained host-side) and "
                         "re-admit it later by replaying those tokens — "
                         "deterministic, the victim's final tokens are "
                         "unchanged; downshift (freelist only) keeps the "
                         "victim decoding but early-folds its staging "
                         "window one precision rung lower, so only its "
                         "window pages return — cheap preemption that "
                         "trades the victim's precision for the urgent "
                         "request's pages; swap (freelist only) mirrors "
                         "the victim's exact quantized cache into host "
                         "memory and re-admits by uploading it back — no "
                         "prefill replay, tokens bitwise unchanged "
                         "(aliased victims and a full host pool fall back "
                         "to recompute); off never evicts")
    ap.add_argument("--swap-pool-mb", type=int, default=0,
                    help="--preemption swap only: host-memory budget (MiB) "
                         "for the swap tier's preallocated entry buffers; "
                         "0 sizes the pool at one entry per batch slot, a "
                         "positive budget caps entries at floor(mb/entry) "
                         "and further swap-outs fall back to recompute")
    ap.add_argument("--precision-map", default="",
                    help="per-layer/head (key,value) effective-bit ceilings "
                         "for the quantizers (core/precision.py): compact "
                         "rules like 'default=k8v8;layer:2-:head:0-1=k2v2' "
                         "or a KVTuner-shaped JSON object.  Containers keep "
                         "the policy's high/low bit widths — the map narrows "
                         "the code range per layer/head (scale/zero absorb "
                         "it), so cache shapes and kernels are unchanged.  "
                         "Empty = off (bitwise-identical default path)")
    ap.add_argument("--ladder-watermark", type=float, default=0.0,
                    help="--page-allocator freelist only: arm the pressure-"
                         "driven downshift ladder — when the min free "
                         "fraction across the page pools drops to or below "
                         "this value, the oldest eligible slot's staging "
                         "window is early-folded at a lowered lo-store "
                         "effective bit-width (rung +1, floor 1 bit) and "
                         "its window pages return to the pool.  Salient "
                         "(hi-store) tokens keep their bits.  0.0 = off")


def validate_engine_args(args, ap: argparse.ArgumentParser,
                         continuous: bool) -> None:
    """Reject invalid flag combinations instead of silently ignoring them
    ("reject instead of misleading").  Shared by both CLIs; `continuous`
    is the caller's engine mode (the HTTP front is always continuous)."""
    if args.paged_kernel == "on" and args.backend != "paged":
        ap.error("--paged-kernel on requires --backend paged")
    if args.paged_kernel == "on":
        from repro.kernels import paged_qattn
        if not paged_qattn.policy_supported(build_compression_config(args)):
            ap.error(f"--paged-kernel on cannot serve --policy {args.policy} "
                     "(the kernel reads channelwise-K / CST-V or raw stores)")
    if args.scheduler != "fifo" and not continuous:
        ap.error("--scheduler requires --continuous (the lockstep engine "
                 "has no admission queue to schedule)")
    if args.preemption != "off" and args.scheduler != "priority":
        # FIFO never names a victim; arming preemption under it would be a
        # silent no-op — reject instead of misleading
        ap.error(f"--preemption {args.preemption} requires --scheduler "
                 "priority")
    if args.preemption == "downshift" and args.page_allocator != "freelist":
        # a downshift's whole yield is the window pages its early fold
        # returns — without the free-list pools there is nothing to return
        ap.error("--preemption downshift requires --page-allocator freelist")
    if args.preemption == "swap" and args.page_allocator != "freelist":
        # swap-out's whole yield is the victim's pages going back to the
        # shared pools — without the free list there is nothing to return
        ap.error("--preemption swap requires --page-allocator freelist")
    if args.swap_pool_mb != 0 and args.preemption != "swap":
        ap.error("--swap-pool-mb requires --preemption swap (only the swap "
                 "tier allocates host entry buffers)")
    if args.ladder_watermark != 0.0 and args.page_allocator != "freelist":
        ap.error("--ladder-watermark requires --page-allocator freelist "
                 "(pressure is free-list pool pressure)")
    if args.precision_map:
        from repro.core import precision as precision_lib
        try:
            precision_lib.parse_precision_map(args.precision_map)
        except ValueError as e:
            ap.error(f"--precision-map: {e}")
    if args.page_allocator == "freelist" and args.backend != "paged":
        ap.error("--page-allocator freelist requires --backend paged")
    if args.page_allocator == "freelist" and not continuous:
        # the lockstep engine's caches come from compress_prefill, which is
        # always the static layout — a silent no-op would misreport memory
        ap.error("--page-allocator freelist requires --continuous (the "
                 "lockstep engine has no admission events to allocate on)")
    # these two only exist under the free-list allocator: a non-default
    # value anywhere else would be silently ignored — the exact failure
    # mode every other guard here rejects
    if args.pool_fraction != 1.0 and args.page_allocator != "freelist":
        ap.error("--pool-fraction requires --page-allocator freelist (the "
                 "static assignment always provisions the full worst case)")
    if args.admit_watermark != 0.0 and args.page_allocator != "freelist":
        ap.error("--admit-watermark requires --page-allocator freelist "
                 "(static/mixed layouts have no admission headroom to hold)")
    if args.prefix_cache == "on" and args.page_allocator != "freelist":
        ap.error("--prefix-cache on requires --page-allocator freelist "
                 "(dedup aliases free-list pages behind refcounted tables)")


def build_serve_config(args) -> ServeConfig:
    """args (from a parser `add_engine_args` populated) -> `ServeConfig`.
    The single place CLI flags meet ServeConfig — the conformance-axes
    lint reads exactly this call to learn which flags feed which fields."""
    return ServeConfig(batch_size=args.batch, prompt_len=args.prompt_len,
                       max_new_tokens=args.max_new, seed=args.seed,
                       backend=args.backend, page_size=args.page_size,
                       paged_kernel=args.paged_kernel == "on",
                       page_allocator=args.page_allocator,
                       pool_fraction=args.pool_fraction,
                       admit_watermark=args.admit_watermark,
                       scheduler=args.scheduler,
                       preemption=args.preemption,
                       prefix_cache=args.prefix_cache == "on",
                       precision_map=args.precision_map,
                       ladder_watermark=args.ladder_watermark,
                       swap_pool_mb=args.swap_pool_mb)


def build_compression_config(args) -> CompressionConfig:
    """args -> `CompressionConfig` (smoke shrinks the fold cadence so short
    runs still cross a recompression)."""
    kw = {}
    if args.policy in ("zipcache", "mikv"):
        kw["saliency_ratio"] = args.saliency_ratio
    ccfg = CompressionConfig.preset(args.policy, **kw)
    return type(ccfg)(**{**ccfg.__dict__,
                         "fp_window": 16, "recompress_interval": 16}) \
        if args.smoke else ccfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine (submit/step/result)")
    args = ap.parse_args(argv)
    validate_engine_args(args, ap, continuous=args.continuous)
    compile_cache.enable()

    cfg = configs.get_arch(args.arch, smoke=args.smoke)
    mesh = None
    if args.mesh == "single":
        mesh = mesh_lib.make_production_mesh()
    elif args.mesh not in ("1x1",):
        d, m = (int(t) for t in args.mesh.split("x"))
        mesh = mesh_lib.make_mesh((d, m), ("data", "model"))

    ccfg = build_compression_config(args)
    scfg = build_serve_config(args)
    # (--backend paged with a mesh is rejected where the backend is built,
    # launch/steps.serve_ctx — programmatic callers hit the same guard)

    params = registry.materialize_params(cfg, args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(2, cfg.vocab, size=(args.prompt_len,)).astype(np.int32)
               for _ in range(args.batch)]

    if args.continuous:
        eng = ContinuousEngine(cfg, ccfg, scfg, params, mesh=mesh)
        # under the priority scheduler, stagger priorities so the policy is
        # visible in the admission order (FIFO ignores the field entirely)
        rids = [eng.submit(Request(tokens=p, priority=(
                    i % 2 if args.scheduler == "priority" else 0)))
                for i, p in enumerate(prompts)]
        eng.run()
        for rid in rids:
            out = eng.result(rid)
            print(f"[serve] {rid}: {len(out.tokens)} tok "
                  f"({out.timings['tok_per_s']:.1f} tok/s, "
                  f"first tok {out.timings['first_token_s']:.2f}s, "
                  f"{int(out.timings['n_preemptions'])} preemptions) "
                  f"first={out.tokens[:16].tolist()}")
        ps = eng.pool_stats()
        if ps is not None:
            used = {k: f"{v['peak_used']}/{v['pool_pages']}"
                    for k, v in ps.items()
                    if isinstance(v, dict) and "peak_used" in v}
            print(f"[serve] page pools peak used {used}, "
                  f"{ps['deferrals']} admissions deferred, "
                  f"{ps['preemptions']} slots preempted")
            ds = ps["downshift"]
            if ds["downshifts"] or ds["refusals"]:
                print(f"[serve] downshift ladder: {ds['downshifts']} "
                      f"downshifts freed {ds['pages_freed']} window pages, "
                      f"{ds['refusals']} aliased-slot refusals")
            sw = ps.get("swap")
            if sw is not None and (sw["swaps_out"] or sw["swap_refusals"]):
                print(f"[serve] swap tier: {sw['swaps_out']} out / "
                      f"{sw['swaps_in']} in, {sw['host_bytes']} host bytes "
                      f"resident, {sw['swap_refusals']} refusals")
            px = ps["prefix"]
            if px["hits"] or px["misses"]:
                print(f"[serve] prefix cache: {px['hits']} hits / "
                      f"{px['misses']} misses, {px['cow_copies']} CoW "
                      f"copies, {px['prefill_tokens_skipped']} prefill "
                      f"tokens skipped")
        return {rid: eng.result(rid) for rid in rids}

    engine = ServingEngine(cfg, ccfg, scfg, params, mesh=mesh)
    batch = {"tokens": pack_requests(prompts, args.batch, args.prompt_len)}
    if cfg.encdec or cfg.frontend != "none":
        n = args.prompt_len if cfg.encdec else cfg.n_frontend_tokens
        batch["frontend_embeds"] = rng.standard_normal(
            (args.batch, n, cfg.d_model)).astype(np.float32)
        if cfg.frontend != "none" and not cfg.encdec:
            batch["tokens"] = batch["tokens"][:, : args.prompt_len - n]

    out = engine.generate(batch)
    print(f"[serve] {args.arch} policy={args.policy} "
          f"prefill={out['timings']['prefill_s']:.3f}s "
          f"decode={out['timings']['decode_s']:.3f}s "
          f"({out['timings']['tok_per_s']:.1f} tok/s)")
    print("[serve] first request tokens:", out["tokens"][0][:16].tolist())
    return out


if __name__ == "__main__":
    main()
