"""Serving engines with ZipCache streaming compression (paper Alg. 2/3).

Two engines share the same jitted programs:

  * ``ServingEngine``    — the lockstep batch path: one packed batch prefills
    together and decodes for a fixed number of steps (benchmarks, quality
    evals, and the reference for engine-equivalence tests).
  * ``EngineCore`` — continuous batching: an explicit request lifecycle
    (``submit -> step/run -> result``, plus ``stream`` for token-at-a-time
    delivery) over a fixed number of decode *slots*, with the scheduling
    POLICY injected as a `serving.scheduler.Scheduler` (admission order,
    head-of-line blocking, victim selection for preempt+recompute).  Each
    slot holds one request; a new request prefills on its own (batch=1)
    and its compressed cache slice is ``insert``-ed into the running decode
    batch (jetstream-style), a finished request ``free``-s its slot.  All
    jitted programs keep static shapes — inactive slots are masked, never
    sliced away — so the engine stays pjit/TPU-compatible.  ``step()``
    returns the typed events (`serving.events`) that iteration produced.
  * ``ContinuousEngine`` — the config-driven façade over ``EngineCore``:
    builds the scheduler from ``ServeConfig.scheduler``/``.preemption`` and
    keeps the blocking ``run()`` loop as the compatibility surface.

Preemption (``preemption="recompute"``, vLLM-style): the scheduler may name
a running victim so a more urgent request can take its slot.  The engine
returns every page the victim held to the free pools, retains its generated
tokens HOST-side, and re-admits it later by recompute: prefill the prompt
again, then replay the retained tokens through the SAME masked decode/fold
programs on the slot's own counters.  Replay re-runs the exact op sequence
of the uncontended run, so the rebuilt cache state is bitwise identical and
the request's remaining tokens are unchanged — preemption moves work in
time, never changes results (tests/test_scheduling.py).

Per-request cadence (paper Alg. 3 under continuous batching): every slot
carries its own token counter; probe rows and window recompression fire on
that counter, not on a global step, so a request admitted mid-run sees
exactly the schedule it would have seen in a fresh lockstep run — the basis
of the token-equivalence guarantee (see tests/test_serving.py).

The jitted programs:
  * prefill(params, batch)                          -> (last logits, caches)
  * decode(params, tok, caches, probes, active)     -> (logits, caches)
  * insert(caches, slice, slot)  /  free == insert(empty slice)
  * recompress(caches, rows)                        -> caches
  * sample(logits, temps, seeds, counters)          -> tokens
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import time
from typing import (Callable, Deque, Dict, Iterator, List, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import alloc as alloc_lib
from repro.core import backend as backend_lib
from repro.core import swap as swap_lib
from repro.core.policy import CompressionConfig
from repro.launch import steps as steps_lib
from repro.models import registry
from repro.runtime import tracing
from repro.serving import events as events_lib
from repro.serving import scheduler as scheduler_lib


# ---------------------------------------------------------------------------
# Probe schedule (paper Alg. 3)
# ---------------------------------------------------------------------------

def probe_flag(counter: int, interval: int, seed: int = 0) -> bool:
    """Deterministic per-request probe schedule: the most recent ~5% of each
    recompress interval plus a hashed pseudo-random ~5% of steps.

    Keyed on the request's OWN token counter (not the global engine step) so
    lockstep and continuous engines agree token-for-token regardless of when
    a request was admitted.
    """
    n_recent = max(interval // 20, 1)
    recent = (counter % interval) >= interval - n_recent
    h = (counter * 2654435761 + seed * 40503 + 12345) & 0xFFFFFFFF
    rand = ((h >> 8) % 100) < 5
    return bool(recent or rand)


# ---------------------------------------------------------------------------
# Request lifecycle types
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_size: int                  # decode slots
    prompt_len: int                  # static prompt capacity (left-padded)
    max_new_tokens: int = 128        # decode budget (cache sized for this)
    seed: int = 0
    # KV cache layout (core/backend.py): "mixed" (dense per-slot arrays) or
    # "paged" (page-pool payload behind per-slot page tables).  Greedy output
    # is token-identical across layouts (tests/test_backend_conformance.py);
    # paged makes slot insert/free page-local and folds staging windows with
    # a per-slot program instead of full-batch recomputation.
    backend: str = "mixed"
    page_size: int = 64              # tokens per page ("paged" only)
    # "paged" only: decode attention through the page-walking Pallas kernel
    # (kernels/paged_qattn) — the per-step dense gather disappears; greedy
    # output stays token-identical to the gather path and to "mixed"
    # (tests/test_backend_conformance.py).  Off by default: the gather path
    # is the bitwise cross-backend reference.
    paged_kernel: bool = False
    # "paged" only — page allocation policy (core/alloc.py):
    #   static    every slot owns its worst-case pages from init (pool =
    #             slots x ceil(capacity/page); no admission control needed)
    #   freelist  pages live in shared pools of pool_fraction x that worst
    #             case and are granted/returned per slot on demand, so long
    #             requests borrow pages freed by short ones; the engine
    #             admits a request only when the pools can cover its whole
    #             prompt + decode budget (worst case) on top of the running
    #             slots' reservations — out-of-pages pressure defers
    #             admission instead of corrupting a running slot.  Greedy
    #             output stays bitwise token-identical to static/mixed.
    page_allocator: str = "static"
    pool_fraction: float = 1.0
    # "freelist" only: fraction of each pool held back as admission
    # headroom — a request is admitted only if its worst case fits with
    # this many pages left over (0.0 = admit up to the last page)
    admit_watermark: float = 0.0
    # "freelist" only: what _admit does when the head-of-queue request's
    # worst case does not fit right now:
    #   defer  leave it queued (FIFO) and try again next step — the typed
    #          deferral is visible in pool_stats()["deferrals"]
    #   error  raise alloc.PagePoolExhausted from step() (backpressure to
    #          the caller, e.g. an async front that wants to shed load)
    backpressure: str = "defer"
    # Scheduling policy (serving/scheduler.py):
    #   fifo      strict submission order, head-of-line blocking — bitwise
    #             the pre-scheduler engine's behavior
    #   priority  highest Request.priority first (FIFO within a class);
    #             with preemption="recompute" it may evict a running
    #             lower-priority slot so an urgent request is never stuck
    #             behind a long-budget monopolist
    scheduler: str = "fifo"
    # "off" never evicts a running slot (out-of-slots pressure queues).
    # "recompute": the scheduler may name a victim; its pages are returned,
    # its generated tokens retained host-side, and it is re-admitted later
    # by re-prefilling the prompt and replaying those tokens through the
    # same decode/fold programs — deterministic: the victim's remaining
    # tokens are unchanged vs an uncontended run (tests/test_scheduling.py)
    # "downshift" (paged+freelist): cheap preemption — the victim KEEPS its
    # slot and keeps decoding; its staging window is early-folded one
    # ladder rung lower (lo-store effective bits -1, floor 1) so only its
    # window pages come back.  Unblocks page pressure without recompute's
    # re-prefill cost; trades the victim's precision instead of its latency
    # "swap" (paged+freelist): the victim's EXACT quantized cache is
    # mirrored into host memory (core/swap.py) and its pages returned;
    # re-admission uploads the mirror through the re-granted table — no
    # prefill, no recompute, tokens bitwise as if never evicted.  Aliased
    # (refcount>1) victims and a full host pool refuse the swap and fall
    # back to preempt+recompute, so progress never blocks on the host tier
    preemption: str = "off"
    # "paged"+"freelist" only: content-hash shared-prefix page dedup with
    # copy-on-write tables (core/alloc.py).  Admission hashes the request's
    # page-aligned prompt bucket; a hit points the slot's hi/lo page-table
    # rows at the existing immutable pages (refcounts bump) and skips the
    # prefill entirely — the first fold privatizes the shared pages (CoW)
    # because recompression re-splits hi/lo per slot.  Greedy output stays
    # bitwise identical to prefix_cache=False: an aliased prefill IS the
    # donor's prefill, bit for bit (tests/test_backend_conformance.py).
    prefix_cache: bool = False
    # Per-layer/head precision map (core/precision.py): ceilings on the
    # quantizers' effective bit-widths, compact rules
    # ("default=k8v8;layer:2-:head:0-1=k2v2") or the KVTuner JSON shape.
    # Storage containers keep the global high_bits/low_bits widths — the
    # map narrows the code RANGE per layer/head (scale/zero absorb it), so
    # every cache/pool/kernel shape is map-independent.  "" disables maps:
    # the bitwise-default static-qmax path.
    precision_map: str = ""
    # preemption="swap" only: host-memory budget for the swap tier's
    # preallocated entry buffers, in MiB.  0 sizes the pool at one entry
    # per batch slot (every running request could swap out at once); a
    # positive budget caps entries at floor(mb / entry_bytes) and swap-outs
    # beyond it fall back to recompute (counted as pool_full refusals).
    swap_pool_mb: int = 0
    # Downshift ladder ("paged"+"freelist" only): when the min free
    # fraction across the page pools drops to or below this watermark, the
    # engine early-folds the oldest eligible slot's staging window at a
    # lowered lo-store effective bit-width (ladder rung +1, floor 1 bit) —
    # the window's pages return to the pool and later folds of that slot
    # stay at the lowered rung.  Salient (hi-store) tokens keep their bits:
    # the ladder degrades exactly the tokens ZipCache already deems
    # regular.  0.0 disarms the pressure trigger (preemption="downshift"
    # arms the ladder programs independently).
    ladder_watermark: float = 0.0
    # sampling is per-request (SamplingParams); the lockstep generate() path
    # is always greedy — it is the reference the continuous engine is
    # verified token-identical against


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling: temperature 0 = greedy; seed makes sampled
    requests reproducible independent of slot placement/admission step."""
    temperature: float = 0.0
    seed: int = 0


@dataclasses.dataclass(eq=False)   # identity semantics: queue membership /
class Request:                     # removal must not compare token arrays
    """One generation request.

    tokens: (<= prompt_len,) int32 prompt ids (left-padded on admission).
        `submit()` COPIES them: later caller-side mutation of the buffer
        cannot change what recompute replays after a preemption.
    max_new_tokens: per-request budget, capped by ServeConfig.max_new_tokens.
    stop_tokens: generation stops when one of these is produced (EOS).
    priority: scheduling urgency (higher = sooner; only the priority
        scheduler reads it — FIFO ignores priorities entirely).
    deadline_s: wall-clock budget from submit, in seconds.  A request whose
        deadline expires — queued OR running — is cancelled at the next
        step boundary (`finish_reason="cancelled"`, typed `CancelledEvent`
        with reason "deadline"); schedulers see the field on the Request
        they are ordering.  None = no deadline.
    on_token: optional callback invoked with each fresh `TokenEvent` as the
        request decodes (the push-style twin of `engine.stream`).  A raising
        callback is detached and surfaced as a `CallbackErrorEvent`; it can
        never corrupt the step it fired in.
    """
    tokens: np.ndarray
    id: Optional[str] = None
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    max_new_tokens: Optional[int] = None
    stop_tokens: Tuple[int, ...] = ()
    priority: int = 0
    deadline_s: Optional[float] = None
    on_token: Optional[Callable[[events_lib.TokenEvent], None]] = None


@dataclasses.dataclass
class RequestOutput:
    """Final output of one request.

    timings keys: queued_s (submit -> first admission), prefill_s (incl.
    recompute replays), decode_s, tok_per_s, first_token_s (submit -> first
    sampled token), preempted_s (wall time spent evicted), n_preemptions,
    and n_deferrals (admissions the page pool deferred for THIS request —
    the per-request view of `pool_stats()`'s cumulative counters).

    tok_per_s counts DECODE-phase tokens only: the first token is sampled
    from the prefill logits at admission, so a request whose only token is
    its first (e.g. the prompt immediately hits a stop token) reports 0.0,
    not prompt-dependent noise divided by ~zero decode seconds."""
    id: str
    tokens: np.ndarray               # (n_generated,) int32, stop token included
    finish_reason: str               # "stop" | "length" | "cancelled"
    timings: Dict[str, float]


@dataclasses.dataclass
class _Slot:
    """Engine-internal per-slot decode state."""
    request: Request
    generated: List[int]
    steps: int = 0                   # decode steps done (probe counter)
    since_rc: int = 0                # tokens since last recompression
    t_submit: float = 0.0
    t_admit: float = 0.0
    prefill_s: float = 0.0


@dataclasses.dataclass
class _SwapState:
    """Host-side record of one swapped-out request (rides on the Request
    between eviction and re-admission): the swap-pool handle plus every
    per-slot counter the restore must reinstate for bitwise resumption —
    allocator occupancy (drives the page re-grant), probe/fold counters,
    and the downshift-ladder rung."""
    handle: int
    occ: alloc_lib.Occupancy
    steps: int
    since_rc: int
    rung: int


def pack_requests(requests: Sequence[np.ndarray], batch_size: int,
                  prompt_len: int, pad_id: int = 0) -> np.ndarray:
    """Left-pad + stack request prompts into a fixed-shape batch.

    Raises on overflow instead of silently truncating/dropping: too-long
    prompts and over-batch request lists are an admission-control decision
    (queue them), not something to lose data over.
    """
    if len(requests) > batch_size:
        raise ValueError(
            f"{len(requests)} requests exceed batch_size {batch_size}; "
            "queue the surplus (ContinuousEngine.submit) instead")
    out = np.full((batch_size, prompt_len), pad_id, np.int32)
    for i, r in enumerate(requests):
        r = np.asarray(r)
        if r.shape[-1] > prompt_len:
            raise ValueError(
                f"prompt of {r.shape[-1]} tokens exceeds prompt_len {prompt_len}")
        out[i, prompt_len - len(r):] = r
    return out


# ---------------------------------------------------------------------------
# Jitted sampling
# ---------------------------------------------------------------------------

def _sample_tokens(logits: jnp.ndarray, temps: jnp.ndarray,
                   seeds: jnp.ndarray, counters: jnp.ndarray) -> jnp.ndarray:
    """Per-slot greedy/temperature sampling, (b, vocab) -> (2, b) int32:
    row 0 the sampled tokens, row 1 whether each row's logits were all
    finite (rides the same readback, feeds `EngineCore.nonfinite_logits`).

    Keys derive from (request seed, token counter) so a request's sample
    stream is independent of its slot index and admission step.
    """
    keys = jax.vmap(lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
        seeds, counters)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-3)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    finite = jnp.all(jnp.isfinite(logits), axis=-1).astype(jnp.int32)
    return jnp.stack([jnp.where(temps > 0, sampled, greedy), finite])


# ---------------------------------------------------------------------------
# Per-step input staging (one host->device transfer per decode step)
# ---------------------------------------------------------------------------

# rows of the (6, b) int32 staging matrix EngineCore builds host-side each
# step; row TEMP carries the float32 temperatures bitcast to int32 so the
# whole step's scalar inputs ride ONE transfer (tools/analyze hostsync
# lint: per-slot int()/jnp.asarray churn serializes the dispatch pipeline)
_ROW_TOK, _ROW_PROBE, _ROW_ACT, _ROW_TEMP, _ROW_SEED, _ROW_CTR = range(6)


def _unpack_step_inputs(packed: jnp.ndarray):
    """(6, b) int32 staging matrix -> (tok, probes, active, temps, seeds,
    counters) with the exact dtypes the decode/sample programs expect.
    Runs jitted on device; the bitcast restores temperatures bit-exactly,
    so staging is invisible to the numerics (conformance stays bitwise)."""
    return (packed[_ROW_TOK],
            packed[_ROW_PROBE].astype(jnp.bool_),
            packed[_ROW_ACT].astype(jnp.bool_),
            jax.lax.bitcast_convert_type(packed[_ROW_TEMP], jnp.float32),
            packed[_ROW_SEED],
            packed[_ROW_CTR])


# ---------------------------------------------------------------------------
# Shared jitted-program bundle
# ---------------------------------------------------------------------------

def _on_device(method):
    """Run an engine method with the engine's device as JAX's default, so
    every host upload it makes (prompts, staging matrices, slot ids) and
    every array it creates lands on that device, not on device 0."""
    @functools.wraps(method)
    def placed(self, *args, **kwargs):
        with self._placement():
            return method(self, *args, **kwargs)
    return placed


class _EngineBase:
    def __init__(self, cfg: ArchConfig, ccfg: CompressionConfig, scfg: ServeConfig,
                 params, mesh=None, device=None):
        if device is not None and mesh is not None:
            raise ValueError("an engine is placed on a mesh or on one "
                             "device, not both")
        self.cfg = cfg
        self.ccfg = ccfg
        self.scfg = scfg
        # device: the one chip this engine replica serves from (None = the
        # default device, or the mesh).  Params are committed there, so
        # every jitted program runs there.
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        shape = ShapeConfig("serve", scfg.prompt_len, scfg.batch_size, "prefill",
                            cache_backend=scfg.backend, page_size=scfg.page_size,
                            paged_kernel=scfg.paged_kernel,
                            page_allocator=scfg.page_allocator,
                            pool_fraction=scfg.pool_fraction,
                            prefix_cache=scfg.prefix_cache,
                            precision_map=scfg.precision_map)
        self._shape = shape
        self._mesh = mesh
        self.ctx = steps_lib.serve_ctx(cfg, shape, mesh, ccfg,
                                       decode_budget=scfg.max_new_tokens,
                                       q_block=min(512, scfg.prompt_len))
        self._prefill = jax.jit(
            lambda p, b: registry.prefill(p, b, cfg, self.ctx))
        # ragged admission: per-bucket (page-aligned prompt length) prefill
        # wrappers, built lazily on first use.  jax.jit caches programs per
        # wrapper, so each bucket warms once and then serves from cache —
        # the steady-state zero-compile guarantee holds per bucket
        # (tests/test_retrace.py warms every bucket its scenario uses).
        # Construction lives in this __init__-built closure: like the
        # jitted handles above it is program BUILD, the cold side of the
        # host/device boundary the hot-loop sync lint fences off.
        self._prefill_buckets: Dict[int, Callable] = {}

        def build_bucket_prefill(bucket_len: int):
            pad_saved = scfg.prompt_len - bucket_len
            bshape = dataclasses.replace(shape, seq_len=bucket_len)
            bctx = steps_lib.serve_ctx(
                cfg, bshape, mesh, ccfg,
                decode_budget=scfg.max_new_tokens + pad_saved,
                q_block=min(512, bucket_len))
            return jax.jit(lambda p, b: registry.prefill(p, b, cfg, bctx))

        self._build_bucket_prefill = build_bucket_prefill
        self._decode = jax.jit(
            lambda p, t, c, ip: registry.decode_step(p, t, c, cfg, self.ctx, ip))
        self._recompress = jax.jit(
            lambda c: registry.recompress(c, cfg, self.ctx))
        # continuous-batching program family, built from the shared step
        # factories (launch/steps.py) over the same serving ctx
        self._decode_masked = jax.jit(steps_lib.make_continuous_decode_step(
            cfg, shape, mesh, ccfg, ctx=self.ctx)[0])
        self._insert = jax.jit(steps_lib.make_insert_step(
            cfg, shape, mesh, ccfg, ctx=self.ctx)[0])
        self._recompress_rows = jax.jit(steps_lib.make_recompress_rows_step(
            cfg, shape, mesh, ccfg, ctx=self.ctx)[0])
        # per-slot recompression program (backends that offer it — paged):
        # folds ONE slot at ~1/slots the FLOPs of the rows-masked program,
        # so staggered admission pays per-request, not `slots`x, cost
        self._recompress_slot = None
        if hasattr(self.ctx.backend, "recompress_slot"):
            self._recompress_slot = jax.jit(steps_lib.make_recompress_slot_step(
                cfg, shape, mesh, ccfg, ctx=self.ctx)[0])
        # Downshift-ladder fold programs: same recompression, plus a rung
        # DATA operand ((b,) for rows, scalar for the slot view) lowering
        # the folded slots' lo-store effective bits — one warm program per
        # SIGNATURE serves every rung and every pressure event (the
        # zero-retrace guarantee, tests/test_retrace.py).  Built only when
        # the ladder can fire so an unarmed engine keeps the exact
        # two-argument traces of the bitwise-default path.
        self._ladder = (scfg.ladder_watermark > 0
                        or scfg.preemption == "downshift")
        self._recompress_rows_rung = None
        self._recompress_slot_rung = None
        if self._ladder:
            self._recompress_rows_rung = jax.jit(
                steps_lib.make_recompress_rows_step(
                    cfg, shape, mesh, ccfg, ctx=self.ctx, ladder=True)[0])
            if self._recompress_slot is not None:
                self._recompress_slot_rung = jax.jit(
                    steps_lib.make_recompress_slot_step(
                        cfg, shape, mesh, ccfg, ctx=self.ctx, ladder=True)[0])
        self._sample = jax.jit(_sample_tokens)

    def _placement(self):
        return (contextlib.nullcontext() if self.device is None
                else jax.default_device(self.device))

    # ------------------------------------------------------------------
    def _bucket_len(self, n_tokens: int) -> int:
        """Ragged-admission bucket of a true prompt length: the smallest
        whole-page length that holds it, capped at the engine's prompt
        window.  Page demand then tracks `ceil(true_prompt/page)` instead
        of the full left-padded window, and identical prompts land in
        identical buckets — which is what makes shared-prefix keys align
        on page boundaries.  Buckets use `ServeConfig.page_size` for EVERY
        backend (the mixed layout has no pages but must bucket identically,
        or cross-backend conformance would compare different prefills)."""
        ps = self.scfg.page_size
        return min(alloc_lib.pages_for(max(n_tokens, 1), ps) * ps,
                   self.scfg.prompt_len)

    def _prefill_for(self, bucket_len: int):
        """The prefill program for one admission bucket.  Full-window
        admissions reuse the main wrapper; shorter buckets get their own
        serving ctx with `seq_len = bucket_len` and the decode budget
        EXTENDED by the saved prompt tokens, so `max_cache_len` — and with
        it every cache/pool shape — is identical across buckets and the
        slice inserts into the shared decode batch unchanged."""
        if bucket_len == self.scfg.prompt_len:
            return self._prefill
        fn = self._prefill_buckets.get(bucket_len)
        if fn is None:
            fn = self._build_bucket_prefill(bucket_len)
            self._prefill_buckets[bucket_len] = fn
        return fn

    # ------------------------------------------------------------------
    def cache_bytes(self, caches) -> Dict[str, int]:
        """Packed KV payload vs bookkeeping overhead, reported separately.

        The packed number is what compression ratios are computed from
        (TokenStore.nbytes_packed: bit-packed codes + quantization params +
        the bf16 staging window); pos/acc/nnz saliency state and counters
        are overhead, and SSM states count entirely as overhead.
        """
        return backend_lib.cache_bytes(caches)


# ---------------------------------------------------------------------------
# Lockstep engine (reference path)
# ---------------------------------------------------------------------------

class ServingEngine(_EngineBase):
    """Lockstep batch generation: all requests prefill together and decode
    the same number of steps.  Kept as the reference implementation the
    continuous engine is verified against, and for throughput benchmarks
    where requests are homogeneous by construction."""

    def _is_probe(self, i: int) -> bool:
        """Paper Alg. 3 probe schedule on the global (= per-request, since
        all requests start together) token counter."""
        return probe_flag(i, self.ccfg.recompress_interval, self.scfg.seed)

    def generate(self, batch: Dict[str, np.ndarray],
                 max_new_tokens: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Prefill + streaming decode for one packed batch.

        batch: {"tokens": (b, prompt_len) int32[, "frontend_embeds": ...]}
        Returns {"tokens": (b, n_new) int32, "timings": {...}}.
        """
        n_new = max_new_tokens if max_new_tokens is not None else self.scfg.max_new_tokens
        t0 = time.perf_counter()
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        logits, caches = self._prefill(self.params, jbatch)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        outs = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t1 = time.perf_counter()
        since_recompress = 0
        for i in range(n_new):
            outs.append(np.asarray(tok))
            logits, caches = self._decode(
                self.params, tok, caches, jnp.asarray(self._is_probe(i)))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            since_recompress += 1
            if since_recompress >= self.ccfg.recompress_interval:
                caches = self._recompress(caches)
                since_recompress = 0
        tok.block_until_ready()
        t_decode = time.perf_counter() - t1
        self.last_caches = caches
        return {
            "tokens": np.stack(outs, axis=1),
            "timings": {"prefill_s": t_prefill, "decode_s": t_decode,
                        "tok_per_s": n_new * self.scfg.batch_size / max(t_decode, 1e-9)},
        }


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

class EngineCore(_EngineBase):
    """Continuous batching over a fixed slot count, policy injected.

    Lifecycle::

        eng = ContinuousEngine(cfg, ccfg, scfg, params)   # EngineCore + scfg's scheduler
        rid = eng.submit(Request(tokens=prompt, stop_tokens=(eos,)))
        for tok in eng.stream(rid):     # drives step() while tokens pending
            ...                         # or: while eng.pending: eng.step()
        out = eng.result(rid)           # RequestOutput

    Each ``step()`` asks the injected `Scheduler` which queued requests to
    admit (and, with ``preemption="recompute"``, whether to evict a running
    victim first), decodes one token for every active slot, retires
    finished requests, and returns the typed events it produced
    (`TokenEvent` / `PreemptedEvent` / `FinishedEvent` / `CancelledEvent`
    / `CallbackErrorEvent`).  ``cancel(rid)`` retires a queued or running
    request early (slot freed, pages returned) — the hook the network
    front uses for client disconnects and expired deadlines.

    The decode batch never changes shape: admission prefills one request
    (batch=1) and inserts its cache slice into a free slot of the running
    caches; retirement invalidates the slot's row (free_caches).  Inactive
    slots decode garbage that is fully masked (their caches are invalid
    everywhere, their appends dropped) — the price of static shapes on TPU.
    """

    def __init__(self, cfg: ArchConfig, ccfg: CompressionConfig, scfg: ServeConfig,
                 params, scheduler: scheduler_lib.Scheduler, mesh=None,
                 device=None):
        if cfg.encdec or cfg.frontend != "none":
            raise NotImplementedError(
                "ContinuousEngine currently serves decoder-only text models; "
                "use the lockstep ServingEngine for encdec/frontend archs")
        if getattr(cfg, "n_experts", 0):
            # Capacity-slotted MoE dispatch flattens all batch rows into one
            # token stream: garbage tokens from inactive slots would compete
            # with live requests for expert capacity, breaking the per-row
            # isolation (and the token-equivalence guarantee).  Needs
            # active-masked routing before continuous batching is sound.
            raise NotImplementedError(
                "ContinuousEngine does not yet support MoE archs: expert "
                "capacity is shared across batch rows, so inactive slots are "
                "not isolated; use the lockstep ServingEngine")
        super().__init__(cfg, ccfg, scfg, params, mesh, device)
        with self._placement():
            self.caches = registry.init_caches(cfg, self.ctx, scfg.batch_size)
        self._free_slot = jax.jit(registry.free_caches)
        self._unstage = jax.jit(_unpack_step_inputs)
        self.scheduler = scheduler
        self.slots: List[Optional[_Slot]] = [None] * scfg.batch_size
        self.queue: Deque[Request] = collections.deque()
        self.results: Dict[str, RequestOutput] = {}
        self._ids = itertools.count()
        self._seq = itertools.count()      # arrival stamps (scheduler order)
        self._step_no = 0
        self._known: Set[str] = set()      # every id ever submitted here
        self._closed = False
        self._token_log: Dict[str, List[int]] = {}   # feeds stream()
        # live rows sampled from logits holding a NaN/inf (a health
        # counter: 0 on a correct model and device)
        self.nonfinite_logits = 0
        # cumulative work counters (`counters()`); each `engine.step` span
        # carries them as they stand when the step begins
        self._counts = dict.fromkeys(self.COUNTERS, 0)
        tracing.install_gc_span()
        self._events: List[events_lib.Event] = []    # current step's events
        # Elastic page allocation (core/alloc.py): host-side free lists +
        # page tables, synced onto the device cache tree between jitted
        # steps.  None for the mixed backend and the static paged layout.
        if scfg.backpressure not in ("defer", "error"):
            raise ValueError(
                f"ServeConfig.backpressure must be 'defer' or 'error', got "
                f"{scfg.backpressure!r}")
        if scfg.preemption not in ("off", "recompute", "downshift", "swap"):
            raise ValueError(
                f"ServeConfig.preemption must be 'off', 'recompute', "
                f"'downshift' or 'swap', got {scfg.preemption!r}")
        self._alloc: Optional[alloc_lib.FreeListAllocator] = None
        self._last_deferred: Optional[str] = None
        if getattr(self.ctx.backend, "allocator", "static") == "freelist":
            self._alloc = alloc_lib.FreeListAllocator.from_caches(
                self.caches, page_size=self.ctx.backend.page_size,
                watermark=scfg.admit_watermark)
            self._sync_tables()
        # Shared-prefix dedup (ServeConfig.prefix_cache, core/alloc.py):
        # the allocator owns the page index; the engine keeps the matched
        # device-side prefill snapshots ({key: (slice_caches, logits)}) a
        # hit re-inserts instead of prefilling, plus the jitted page-copy
        # program CoW privatization runs before a shared slot's first fold.
        if scfg.prefix_cache and self._alloc is None:
            raise ValueError(
                "ServeConfig.prefix_cache requires backend='paged' with "
                "page_allocator='freelist' (dedup aliases free-list pages)")
        # Downshift ladder (ServeConfig.ladder_watermark / "downshift"
        # preemption): pressure is PAGE-POOL pressure, and the win a
        # downshift buys is the window pages a fold returns — both only
        # exist under the free-list allocator.
        if self._ladder and self._alloc is None:
            raise ValueError(
                "the downshift ladder (ladder_watermark > 0 or "
                "preemption='downshift') requires backend='paged' with "
                "page_allocator='freelist'")
        # per-slot ladder rung: how many effective bits below the base map
        # this slot's lo store is folded at.  Reset when the slot frees.
        # The deepest rung floors the lo store at 1 effective bit.
        self._rungs = np.zeros(scfg.batch_size, np.int32)
        self._max_rung = max(ccfg.low_bits - 1, 0)
        self._prefix_on = scfg.prefix_cache
        self._prefix_snap: Dict[str, Tuple] = {}
        self._prefix_tokens_skipped = 0
        self._pending_reg: List[Tuple] = []
        self._copy_pages = None
        if self._alloc is not None:
            self._copy_pages = jax.jit(steps_lib.make_copy_pages_step(
                cfg, self._shape, mesh, ccfg, ctx=self.ctx)[0])
        # Host swap tier (preemption="swap", core/swap.py): ONE warm
        # extract/restore program pair (traced slot operand, full static
        # page extents) plus a host pool of preallocated entry buffers
        # sized from the extract program's output template.  Built only
        # when swap can fire, so every other mode keeps the exact program
        # set of the bitwise-default path.
        self._swap: Optional[swap_lib.HostSwapPool] = None
        self._swap_extract = None
        self._swap_restore = None
        if scfg.preemption == "swap":
            if self._alloc is None:
                raise ValueError(
                    "preemption='swap' requires backend='paged' with "
                    "page_allocator='freelist' (swap-out returns the "
                    "victim's pages to the free pools)")
            self._swap_extract = jax.jit(steps_lib.make_swap_extract_step(
                cfg, self._shape, mesh, ccfg, ctx=self.ctx)[0])
            self._swap_restore = jax.jit(steps_lib.make_swap_restore_step(
                cfg, self._shape, mesh, ccfg, ctx=self.ctx)[0])
            template = jax.eval_shape(   # cold path: shapes only, no device work
                self._swap_extract, self.caches,
                jax.ShapeDtypeStruct((), jnp.int32))
            self._swap = swap_lib.HostSwapPool(
                template, swap_pool_mb=scfg.swap_pool_mb,
                fallback_entries=scfg.batch_size)

    # ------------------------------------------------------------------
    # lifecycle API
    # ------------------------------------------------------------------

    @property
    def pending(self) -> bool:
        """True while any submitted request is still queued or decoding, or
        undelivered events are buffered: a between-steps `cancel()` appends
        its `CancelledEvent` into the NEXT step's drain, so drivers that
        step while `pending` must take one more step to deliver it."""
        return (bool(self.queue) or any(s is not None for s in self.slots)
                or bool(self._events))

    def _request_budget(self, request: Request) -> int:
        return (request.max_new_tokens if request.max_new_tokens is not None
                else self.scfg.max_new_tokens)

    def _request_total_tokens(self, request: Request) -> int:
        """Worst-case cached tokens of a request: its RAGGED admission
        bucket (true prompt rounded up to whole pages, not the full
        left-padded window) plus its decode budget — page demand tracks
        what the prefill actually caches."""
        return (self._bucket_len(int(request.tokens.shape[-1]))  # sync: ok(np shape tuple, host-side)
                + self._request_budget(request))

    def submit(self, request: Request) -> str:
        """Validate + enqueue a request; returns its id.

        Raises `ValueError` on prompts or budgets that can never fit the
        engine's static shapes, `events.EngineClosedError` after
        `shutdown()`, and `alloc.PoolCapacityError` when the free-list page
        pool is too small to EVER hold the request's worst case (prompt +
        decode budget) — oversized requests fail fast here instead of
        deadlocking the admission queue.  Transient out-of-pages pressure
        is NOT an error: the request queues and admission defers until
        running slots free enough pages (`ServeConfig.backpressure`)."""
        if self._closed:
            raise events_lib.EngineClosedError(
                "engine is shut down: it drains what it has but accepts no "
                "new requests")
        # Copy the prompt NOW: admission may be steps away, and recompute
        # re-prefills from request.tokens — a caller mutating its buffer
        # after submit must not change what replay prefills (the bitwise
        # preemption guarantee re-runs the ORIGINAL admission).
        request.tokens = np.array(request.tokens, dtype=np.int32)
        n = int(request.tokens.shape[-1])
        if n > self.scfg.prompt_len:
            raise ValueError(
                f"prompt of {n} tokens exceeds engine prompt_len "
                f"{self.scfg.prompt_len}")
        if request.max_new_tokens is not None and not (
                1 <= request.max_new_tokens <= self.scfg.max_new_tokens):
            raise ValueError(
                f"max_new_tokens {request.max_new_tokens} outside the "
                f"engine's [1, {self.scfg.max_new_tokens}] decode budget")
        bucket = self._bucket_len(n)
        if self._alloc is not None and not self._alloc.fits_ever(
                self._request_total_tokens(request), bucket):
            raise alloc_lib.PoolCapacityError(
                f"request needs "
                f"{self._alloc.worst_pages(self._request_total_tokens(request), bucket)} "
                f"pages worst-case, beyond the pool ({self._alloc.stats()}); "
                "raise pool_fraction or lower the request budget")
        # shared-prefix key: the content chain-hash of the request's padded
        # admission bucket, stamped once here (hashing is cheap but not
        # free, and planning probes the key many times per step)
        request._prefix_key = (
            alloc_lib.prefix_key(request.tokens, self.scfg.page_size, bucket)
            if self._prefix_on else None)
        if request.id is None:
            rid = f"req-{next(self._ids)}"
            while rid in self._known:  # user ids may shadow auto ids
                rid = f"req-{next(self._ids)}"
            request.id = rid
        elif request.id in self._known:
            raise ValueError(
                f"request id {request.id!r} already submitted; ids must be "
                "unique (re-submitting the same Request object counts)")
        if request.deadline_s is not None and request.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {request.deadline_s}")
        request._t_submit = time.perf_counter()
        request._deadline = (None if request.deadline_s is None
                             else request._t_submit + request.deadline_s)
        request._seq = next(self._seq)
        request._t_first_admit = None    # first admission (queued_s)
        request._t_first = None          # first sampled token (first_token_s)
        request._prefill_s_acc = 0.0     # carried across preemptions
        request._decode_s_acc = 0.0
        request._preempt_s = 0.0
        request._n_preempts = 0
        request._n_deferrals = 0
        self._known.add(request.id)
        self._token_log[request.id] = []
        self.queue.append(request)
        return request.id

    def poll(self, request_id: str) -> str:
        """Lifecycle state of a submitted request:

        'queued'   waiting for a free slot (or, under the free-list
                   allocator, for enough free pages — deferred admission;
                   a preempted request is queued again until recompute
                   re-admits it)
        'running'  occupying a decode slot
        'done'     retired; `result(request_id)` returns its output

        Raises the typed `events.UnknownRequestError` for an id this engine
        never saw (never submitted, or submitted to another engine).
        """
        if request_id not in self._known:
            raise events_lib.UnknownRequestError(request_id)
        if request_id in self.results:
            return "done"
        if any(s is not None and s.request.id == request_id for s in self.slots):
            return "running"
        return "queued"

    def result(self, request_id: str) -> Optional[RequestOutput]:
        """The finished request's RequestOutput — `.tokens` (stop token
        included), `.finish_reason` ("stop" | "length" | "cancelled") and
        `.timings`
        (see `RequestOutput`) — or None while it is still queued or running
        (use `poll` to distinguish).  Raises `events.UnknownRequestError`
        for an id this engine never saw."""
        if request_id not in self._known:
            raise events_lib.UnknownRequestError(request_id)
        return self.results.get(request_id)

    def stream(self, request_id: str) -> Iterator[int]:
        """Yield the request's tokens as they decode (first token included).

        The generator drives the engine itself: when it has yielded every
        token decoded so far and the request is not finished, it calls
        `step()` — so ``for tok in eng.stream(rid)`` is a complete serving
        loop (other slots keep decoding inside those steps).  Safe to call
        multiple times and after completion (each generator replays the
        full stream from its own cursor); the concatenation of yielded
        tokens is bitwise `result(request_id).tokens`.  Preemption does not
        disturb a live stream: recompute re-derives exactly the retained
        tokens, so nothing already yielded is ever revised.  A cancelled
        request's stream terminates after the tokens decoded so far (check
        `result(rid).finish_reason` to distinguish).  Raises
        `events.UnknownRequestError` for an id this engine never saw."""
        if request_id not in self._known:
            raise events_lib.UnknownRequestError(request_id)
        sent = 0
        while True:
            # finished requests stream from their result (the in-flight
            # token log is dropped at retirement — it would duplicate the
            # result array for the lifetime of the engine)
            out = self.results.get(request_id)
            log = (out.tokens if out is not None
                   else self._token_log.get(request_id, ()))
            while sent < len(log):
                yield int(log[sent])
                sent += 1
            if out is not None:
                return
            self.step()

    @_on_device
    def cancel(self, request_id: str, reason: str = "client") -> bool:
        """Retire a queued or running request early (client disconnect,
        expired deadline, or an explicit API call).

        The request's slot is freed and every page it held returned to the
        pools (visible in `pool_stats()` immediately); `result(request_id)`
        carries the tokens decoded so far with
        ``finish_reason="cancelled"``, and a typed `CancelledEvent` is
        emitted — buffered if the engine is between steps, returned by the
        next `step()`.  Returns True if the request was cancelled, False if
        it had already finished (its result stands — cancellation of a done
        request is a no-op, not an error).  Raises
        `events.UnknownRequestError` for an id this engine never saw.

        Safe to call from outside the step loop (an async server loop
        reacting to a dropped socket): all state it touches is host-side,
        and the freed slot/pages are simply absent from the next step's
        admission plan."""
        if request_id not in self._known:
            raise events_lib.UnknownRequestError(request_id)
        if request_id in self.results:
            return False
        for slot_id, s in enumerate(self.slots):
            if s is not None and s.request.id == request_id:
                self._retire(slot_id, "cancelled", cancel_reason=reason)
                return True
        # queued (possibly evicted mid-decode and waiting on recompute):
        # never re-admitted, so retire it here with whatever it decoded
        req = next(r for r in self.queue if r.id == request_id)
        self.queue.remove(req)
        # a swapped-out request dies with its host mirror: release the
        # entry so host_bytes returns to zero (the conservation invariant)
        st = getattr(req, "_swap_state", None)
        if st is not None:
            self._swap.release(st.handle)
            del req._swap_state
        now = time.perf_counter()
        resume = getattr(req, "_resume_tokens", None)
        tokens = list(resume) if resume is not None else []
        preempt_s = req._preempt_s
        if resume is not None:
            preempt_s += now - req._t_preempt
        dec_tok = max(len(tokens) - 1, 0)
        self.results[req.id] = RequestOutput(
            id=req.id,
            tokens=np.asarray(tokens, np.int32),
            finish_reason="cancelled",
            timings={
                "queued_s": (req._t_first_admit if req._t_first_admit
                             is not None else now) - req._t_submit,
                "prefill_s": req._prefill_s_acc,
                "decode_s": req._decode_s_acc,
                "tok_per_s": (dec_tok / req._decode_s_acc
                              if dec_tok and req._decode_s_acc > 0 else 0.0),
                "first_token_s": (req._t_first if req._t_first is not None
                                  else now) - req._t_submit,
                "preempted_s": preempt_s,
                "n_preemptions": req._n_preempts,
                "n_deferrals": req._n_deferrals,
            })
        self._token_log.pop(req.id, None)
        if self._last_deferred == req.id:
            self._last_deferred = None   # its blocked span ends with it
        self._events.append(events_lib.CancelledEvent(
            req.id, self._step_no, n_tokens=len(tokens), reason=reason))
        return True

    def _sweep_deadlines(self) -> None:
        """Cancel every queued or running request whose `Request.deadline_s`
        budget has expired (reason "deadline").  Runs at the top of each
        `step()`, before admission, so an expired queued request never
        wastes a prefill."""
        now = time.perf_counter()
        expired = [r.id for r in self.queue
                   if getattr(r, "_deadline", None) is not None
                   and now > r._deadline]
        expired += [s.request.id for s in self.slots
                    if s is not None
                    and getattr(s.request, "_deadline", None) is not None
                    and now > s.request._deadline]
        for rid in expired:
            self.cancel(rid, reason="deadline")

    def shutdown(self) -> None:
        """Stop accepting new work: later `submit()` calls raise
        `events.EngineClosedError`.  Queued and running requests drain
        normally through `step()`/`run()`/`stream()`."""
        self._closed = True

    def run(self, max_steps: Optional[int] = None) -> Dict[str, RequestOutput]:
        """Drive the scheduler until every submitted request finished."""
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.results

    # ------------------------------------------------------------------
    # scheduler internals
    # ------------------------------------------------------------------

    @_on_device
    def _sync_tables(self) -> None:
        """Install the allocator's current page tables onto the device cache
        tree (values only — shapes never change, so no jitted program
        retraces).  No-op unless the allocator mutated since the last sync;
        page tables are mutated ONLY here, between jitted steps, never
        inside them (static-shape discipline)."""
        if self._alloc is None or not self._alloc.dirty:
            return
        from repro.core import paged as paged_lib

        with tracing.span("engine.sync_tables"):
            t = self._alloc.tables()
            # upload each (slots, npp) table ONCE and share the device array
            # across all cache elements — with_tables broadcasts device-side
            jt = {k: jnp.asarray(v, jnp.int32)  # sync: ok(three small table uploads per allocator mutation, shared across elements)
                  for k, v in t.items()}
            is_paged = lambda x: isinstance(x, paged_lib.PagedKVCache)
            leaves, treedef = jax.tree_util.tree_flatten(
                self.caches, is_leaf=is_paged)
            self.caches = jax.tree_util.tree_unflatten(
                treedef,
                [paged_lib.with_tables(el, jt["hi"], jt["lo"], jt["win"])
                 if is_paged(el) else el for el in leaves])
            self._alloc.dirty = False

    COUNTERS = ("steps", "probe_steps", "probe_rows", "folds",
                "fold_events", "admissions", "prefill_tokens")

    def counters(self) -> Dict[str, int]:
        """Cumulative work counts since construction, served as `engine` by
        `/v1/stats`: `steps` (steps that decoded), `probe_steps` (of them,
        steps on which a live row probed), `probe_rows` (probing rows
        summed over steps), `folds` (slot windows folded) in `fold_events`
        (fold calls), `admissions` (prefills, prefix hits, swap-ins and
        recompute re-admissions), `prefill_tokens` (bucket tokens run
        through prefill)."""
        return dict(self._counts)

    def pool_stats(self) -> Optional[Dict]:
        """Free-list pool telemetry (None for static/mixed layouts):
        per-segment {pool_pages, used, free, peak_used, outstanding}, the
        cumulative admission-deferral and preemption counts (the
        per-request view of the same costs lives in
        `RequestOutput.timings`), the downshift-ladder block (downshifts
        performed, window pages they freed, aliased-slot refusals), and
        the shared-prefix block — index
        entries, hit/miss/eviction counts, CoW copies, currently shared
        pages, pages dedup is saving right now, and the prefill tokens
        whose FLOPs hits skipped — and, when preemption="swap", the host
        swap tier's block (swaps_out/swaps_in, resident host_bytes,
        swap_refusals).  Served verbatim by `/v1/stats`."""
        if self._alloc is None:
            return None
        stats = self._alloc.stats()
        stats["prefix"]["prefill_tokens_skipped"] = self._prefix_tokens_skipped
        if self._swap is not None:
            stats["swap"] = self._swap.stats()
        return stats

    @_on_device
    def free(self, slot_id: int) -> None:
        """Retire a slot: invalidate its batch row (cheap row writes; stale
        codes are masked by pos == -1 until the next insert overwrites
        them).  Under the free-list allocator, every page the slot held is
        returned to the shared pools — the elasticity event that lets a
        queued long request take over a short one's memory."""
        if self._alloc is not None:
            self._alloc.free(slot_id)
            self._sync_tables()
        self.caches = self._free_slot(
            self.caches,
            jnp.asarray(slot_id, jnp.int32))  # sync: ok(one scalar upload per retire/preempt event, not per step)
        self.slots[slot_id] = None
        self._rungs[slot_id] = 0   # the ladder rung dies with the slot

    def _retire(self, slot_id: int, reason: str,
                cancel_reason: Optional[str] = None) -> None:
        s = self.slots[slot_id]
        req = s.request
        now = time.perf_counter()
        decode_s = max(now - s.t_admit - s.prefill_s, 0.0) + req._decode_s_acc
        # the first token is sampled from the PREFILL logits at admission —
        # only the rest are decode-phase work.  A request that stops on its
        # very first token did zero decoding: report 0.0, not
        # 1 token / ~1e-9 s (the old clamp made serve.py print ~1e9 tok/s)
        dec_tok = max(len(s.generated) - 1, 0)
        first_admit = (req._t_first_admit if req._t_first_admit is not None
                       else s.t_admit)
        self.results[req.id] = RequestOutput(
            id=req.id,
            tokens=np.asarray(s.generated, np.int32),  # sync: ok(s.generated is a host-side python list)
            finish_reason=reason,
            timings={
                "queued_s": first_admit - s.t_submit,
                "prefill_s": s.prefill_s + req._prefill_s_acc,
                "decode_s": decode_s,
                "tok_per_s": (dec_tok / decode_s
                              if dec_tok and decode_s > 0 else 0.0),
                "first_token_s": (req._t_first if req._t_first is not None
                                  else now) - s.t_submit,
                "preempted_s": req._preempt_s,
                "n_preemptions": req._n_preempts,
                "n_deferrals": req._n_deferrals,
            })
        if reason == "cancelled":
            # typed terminal event IN PLACE of FinishedEvent, never both
            self._events.append(events_lib.CancelledEvent(
                req.id, self._step_no, n_tokens=len(s.generated),
                reason=cancel_reason if cancel_reason is not None else "client"))
        else:
            self._events.append(events_lib.FinishedEvent(
                req.id, self._step_no, finish_reason=reason,
                n_tokens=len(s.generated)))
        # the result array now carries the tokens; keeping the log too would
        # leak one int list per request for the engine's lifetime (stream()
        # reads finished requests from results)
        self._token_log.pop(req.id, None)
        self.scheduler.on_retire(slot_id, req)
        self.free(slot_id)

    def _maybe_finish(self, slot_id: int) -> bool:
        s = self.slots[slot_id]
        budget = (s.request.max_new_tokens
                  if s.request.max_new_tokens is not None
                  else self.scfg.max_new_tokens)
        if s.generated and s.generated[-1] in s.request.stop_tokens:
            self._retire(slot_id, "stop")
            return True
        if len(s.generated) >= budget:
            self._retire(slot_id, "length")
            return True
        return False

    def _emit_token(self, request: Request, token: int, index: int) -> None:
        """One fresh token: event, stream log, optional push callback.

        A raising callback (exactly what a socket write becomes when the
        client hangs up) must not unwind `step()` mid-iteration — that
        would abort between the token append and `_fold(due)` / `since_rc`
        reset, corrupting the fold cadence the bitwise-conformance
        guarantee rests on.  Contain it: detach the callback (a broken
        sink never raises twice) and surface a `CallbackErrorEvent`; the
        step stays transactional and tokens stay bitwise identical to a
        callback-free run (tests/test_serving.py)."""
        ev = events_lib.TokenEvent(request.id, self._step_no,
                                   token=int(token), index=index)
        self._events.append(ev)
        self._token_log[request.id].append(int(token))
        if request.on_token is not None:
            try:
                request.on_token(ev)
            except Exception as e:  # noqa: BLE001 — any sink failure contained
                request.on_token = None
                self._events.append(events_lib.CallbackErrorEvent(
                    request.id, self._step_no,
                    error=f"{type(e).__name__}: {e}"))

    def _alias_can_fold(self, req: Request) -> bool:
        """Whether the request can EVER reach a window fold: it decodes at
        most budget-1 steps (the first token comes from prefill logits), so
        `since_rc` never reaches the recompress interval when
        budget - 1 < interval — in that case an aliased admission can skip
        the hi/lo reservation entirely (the stores are never written)."""
        return (self._request_budget(req) - 1
                >= self.ccfg.recompress_interval)

    def _prefix_hit(self, req: Request) -> bool:
        """A usable shared-prefix hit needs BOTH halves: the allocator's
        page index entry (host bookkeeping) and the engine's device
        snapshot (the slice a hit re-inserts).  Demand planning and
        admission must agree on this predicate, or PoolView would reserve
        for a different admission path than the one taken."""
        key = getattr(req, "_prefix_key", None)
        return (key is not None
                and self._alloc.prefix_peek(key) is not None
                and key in self._prefix_snap)

    def _demand_pages(self, req: Request) -> Dict[str, int]:
        """Worst-case per-segment page demand of ONE queued request, as the
        admission planner should see it: ragged bucket + budget, with the
        hi/lo reservation dropped for a shared-prefix hit that can never
        fold (its aliased pages stay shared for its whole lifetime, so its
        only cost is the window)."""
        worst = self._alloc.worst_pages(
            self._request_total_tokens(req),
            self._bucket_len(int(req.tokens.shape[-1])))  # sync: ok(np shape tuple, host-side)
        if self._prefix_hit(req) and not self._alias_can_fold(req):
            worst = {**worst, "hi": 0, "lo": 0}
        return worst

    def _pool_view(self) -> scheduler_lib.PoolView:
        return scheduler_lib.PoolView(
            self._alloc,
            self._demand_pages if self._alloc is not None else None)

    def _running_views(self) -> List[scheduler_lib.SlotView]:
        return [scheduler_lib.SlotView(i, s.request, len(s.generated),
                                       self._request_budget(s.request))
                for i, s in enumerate(self.slots) if s is not None]

    def _admit(self) -> None:
        """Execute the scheduler's admission plan (and preemptions).

        Free-list admission control is unchanged from the pre-scheduler
        engine: a request is admitted only when every page pool can reserve
        its WORST case (prompt + decode budget) on top of the running
        slots' outstanding reservations and the configured watermark —
        which makes every later grant (decode appends, window folds)
        infallible by construction.  The scheduler decides ORDER and
        head-of-line blocking through `PoolView.fits`; a blocked plan
        defers (counted once per request per contiguous blocked span) or
        raises `PagePoolExhausted` per `ServeConfig.backpressure`.

        With ``preemption="recompute"``, requests still waiting after the
        plan ran may name a running victim (`Scheduler.select_victim`):
        the victim is evicted — pages returned, tokens retained — and the
        loop re-plans with the freed slot/pages, at most once per slot per
        step."""
        n_evicted = 0
        while True:
            free_slots = [i for i in range(self.scfg.batch_size)
                          if self.slots[i] is None]
            plan = self.scheduler.admit(list(self.queue), free_slots,
                                        self._pool_view())
            for slot_id, req in plan.admissions:
                self.queue.remove(req)
                self._counts["admissions"] += 1
                with tracing.span("engine.prefill", request_id=req.id,
                                  prompt_tokens=len(req.tokens)):
                    self._admit_one(slot_id, req)
            if (self.scfg.preemption in ("recompute", "downshift", "swap")
                    and self.queue and n_evicted < self.scfg.batch_size):
                victim = self.scheduler.select_victim(
                    list(self.queue), self._running_views(), self._pool_view())
                if victim is not None:
                    if self.scfg.preemption == "recompute":
                        self._preempt(victim)
                        n_evicted += 1
                        continue   # re-plan with the freed slot and pages
                    if self.scfg.preemption == "swap":
                        # swap the victim's exact cache to the host tier;
                        # a refused swap (aliased pages, full host pool)
                        # falls back to preempt+recompute so eviction still
                        # frees the slot either way
                        if not self._swap_out(victim):
                            self._preempt(victim)
                        n_evicted += 1
                        continue   # re-plan with the freed slot and pages
                    # "downshift": cheap preemption — the victim keeps its
                    # slot and keeps decoding; only its early-folded window
                    # pages return, so this unblocks PAGE pressure, not
                    # slot pressure.  An ineligible victim falls through to
                    # the normal defer/error path: downshifting cannot make
                    # progress this step.
                    if self._downshift(victim):
                        n_evicted += 1
                        continue   # re-plan with the freed window pages
            if plan.blocked is not None and self._prefix_on \
                    and self._alloc.prefix:
                # out-of-pages with prefix entries cached: evict LRU index
                # entries (pages nobody aliases return to the free lists)
                # and re-plan BEFORE counting a deferral — the cache must
                # never block an admission the pool could otherwise cover.
                # Terminates: the index strictly shrinks every pass.
                for key in self._alloc.prefix_reclaim():
                    self._prefix_snap.pop(key, None)
                continue
            if plan.blocked is not None:
                if self.scfg.backpressure == "error":
                    raise alloc_lib.PagePoolExhausted(
                        f"request {plan.blocked.id!r} needs "
                        f"{self._demand_pages(plan.blocked)} "
                        f"pages worst-case; pools: {self._alloc.stats()}")
                # count ADMISSIONS deferred, not scheduler steps: one tick
                # per request per contiguous blocked span, however many
                # steps it waits — mirrored per-request for timings.  The
                # span is keyed on the blocked request's id (NOT reset by
                # unrelated admissions: under the priority scheduler a
                # high-priority arrival can be admitted past a still-blocked
                # request without ending its span)
                if plan.blocked.id != self._last_deferred:
                    self._alloc.deferrals += 1
                    plan.blocked._n_deferrals += 1
                    self._last_deferred = plan.blocked.id
            else:
                self._last_deferred = None   # nothing blocked: span over
            break
        # Execute prefix-index registrations DEFERRED by _admit_one: a
        # registration rescinds the donor's page ownership, which raises
        # its outstanding reservation — doing that mid-plan could invalidate
        # the headroom an already-planned same-step admission was checked
        # against.  After the loop the plan is fully executed, so the
        # allocator's own guard (free >= outstanding') is the only gate.
        for key, slot_id, req, slice_caches, logits in self._pending_reg:
            s = self.slots[slot_id]
            if s is None or s.request is not req:
                continue   # retired or preempted before registration
            if self._alloc.prefix_register(key, slot_id):
                self._prefix_snap[key] = (slice_caches, logits)
        self._pending_reg = []

    def _admit_one(self, slot_id: int, req: Request) -> None:
        """Prefill (batch=1) — or alias a cached shared prefix and skip the
        prefill — insert the compressed slice into the slot, then either
        sample the first token (fresh request) or replay the retained
        tokens (recompute re-admission of a preempted request).

        The HIT path re-inserts the stored prefill snapshot: metadata rows
        and fresh window pages receive the donor's bytes, and the scatter
        onto the ALIASED hi/lo pages writes the exact bytes they already
        hold (the donor inserted from the same device buffers) — harmless
        by idempotence, so one warm `_insert` program serves both paths."""
        t0 = time.perf_counter()
        if getattr(req, "_swap_state", None) is not None:
            # host state exists: swap-in beats recompute (two PCIe
            # transfers instead of prefill + replay FLOPs), and the
            # uploaded bytes are exactly what left — no prefill below
            self._swap_in(slot_id, req, t0)
            return
        n = int(req.tokens.shape[-1])  # sync: ok(np shape tuple, host-side)
        bucket = self._bucket_len(n)
        resume = getattr(req, "_resume_tokens", None)
        if self._prefix_on and self._prefix_hit(req):
            # shared-prefix hit: point the slot's tables at the cached
            # pages (refcounts bump) and skip the prefill FLOPs entirely
            slice_caches, logits = self._prefix_snap[req._prefix_key]
            self._alloc.admit_alias(slot_id, req._prefix_key,
                                    self._request_total_tokens(req), bucket,
                                    can_fold=self._alias_can_fold(req))
            self._prefix_tokens_skipped += bucket
            self._sync_tables()
        else:
            prompt = pack_requests([req.tokens], 1, bucket)
            self._counts["prefill_tokens"] += bucket
            logits, slice_caches = self._prefill_for(bucket)(
                self.params,
                {"tokens": jnp.asarray(prompt)})  # sync: ok(the prompt upload itself — once per admission, not per step)
            if self._alloc is not None:
                # one small host read (three pos rows) -> exact per-segment
                # valid counts; grant the slot's prefill pages + reserve
                # its worst case before the insert scatters payload
                self._alloc.admit(slot_id,
                                  alloc_lib.slice_occupancy(slice_caches),
                                  self._request_total_tokens(req),
                                  bucket)
                self._sync_tables()
                if self._prefix_on and req._prefix_key is not None:
                    self._alloc.prefix_note_miss()
                    if resume is None:
                        # index this prefill once the whole plan executed
                        # (_admit flushes); recompute re-admissions are NOT
                        # donors — their replay may fold the slot before
                        # the registration could happen
                        self._pending_reg.append(
                            (req._prefix_key, slot_id, req,
                             slice_caches, logits))
        self.caches = self._insert(
            self.caches, slice_caches,
            jnp.asarray(slot_id, jnp.int32))  # sync: ok(one scalar upload per admission event)
        if resume is None:
            temp = jnp.asarray([req.sampling.temperature], jnp.float32)  # sync: ok(admission-time one-shot sample input)
            seed = jnp.asarray([req.sampling.seed], jnp.int32)  # sync: ok(admission-time one-shot sample input)
            ctr = jnp.asarray([0], jnp.int32)  # sync: ok(admission-time one-shot sample input)
            first, finite = np.asarray(  # sync: ok(admission-time readback of the first sampled token)
                self._sample(logits, temp, seed, ctr))[:, 0]
            self.nonfinite_logits += int(not finite)
            generated = [int(first)]
        else:
            # the first token was sampled at the ORIGINAL admission; the
            # prefill above rebuilt exactly the cache it was sampled from
            req._preempt_s += t0 - req._t_preempt
            generated = [int(resume[0])]
        t1 = time.perf_counter()
        self.slots[slot_id] = _Slot(
            request=req, generated=generated,
            t_submit=getattr(req, "_t_submit", t0), t_admit=t0,
            prefill_s=t1 - t0)
        if req._t_first_admit is None:
            req._t_first_admit = t0
        if resume is None:
            req._t_first = t1
            self._emit_token(req, generated[0], 0)
        else:
            del req._resume_tokens
            self._replay(slot_id, resume)
            # recompute's replay cost is admission cost, not decode speed
            self.slots[slot_id].prefill_s = time.perf_counter() - t0
        self._maybe_finish(slot_id)

    def _replay(self, slot_id: int, tokens: Sequence[int]) -> None:
        """Recompute a preempted slot's cache: feed its retained tokens
        back through the SAME masked decode and fold programs, on the
        slot's own counters (probe flags, recompress cadence, page grants).

        This re-runs the exact op sequence of the uncontended run — the
        per-slot independence the lockstep-equivalence tests establish
        means co-resident slots cannot perturb it — so the rebuilt cache
        state is bitwise identical and every later decode step produces
        the same token it would have produced without the preemption.  No
        TokenEvents fire: these tokens were already delivered when first
        decoded.  The last retained token is NOT fed — like any slot, the
        next regular step() feeds `generated[-1]`."""
        s = self.slots[slot_id]
        b = self.scfg.batch_size
        interval = self.ccfg.recompress_interval
        # same staging-matrix scheme as step(): one transfer per replayed
        # step (sampling rows stay zero — replay never samples).  The
        # matrix MUST be fresh each iteration: jax's CPU client zero-copies
        # 64-byte-aligned numpy uploads, and this loop never blocks on
        # device work, so rewriting one shared matrix in place can be
        # observed by a still-queued earlier iteration's unstage — the
        # replayed token silently changes and the rebuilt cache diverges
        # (heap-alignment + dispatch-backlog dependent, so token tests
        # only catch it intermittently; tests/test_scheduling.py pins the
        # no-mutation-after-upload discipline directly).
        for i in range(len(tokens) - 1):
            if self._alloc is not None:
                self._alloc.note_append(slot_id)
                self._sync_tables()
            stage = np.zeros((6, b), np.int32)
            stage[_ROW_ACT, slot_id] = 1
            stage[_ROW_TOK, slot_id] = int(tokens[i])
            stage[_ROW_PROBE, slot_id] = probe_flag(
                s.steps, interval, self.scfg.seed)
            tok, probes, act, _, _, _ = self._unstage(
                jnp.asarray(stage))  # sync: ok(one batched staging transfer per replayed step)
            _, self.caches = self._decode_masked(
                self.params, self.caches, tok, probes, act)
            s.steps += 1
            s.since_rc += 1
            s.generated.append(int(tokens[i + 1]))
            if s.since_rc >= interval:
                self._fold([slot_id])
                s.since_rc = 0

    def _preempt(self, slot_id: int) -> None:
        """Evict a running slot so a more urgent request can take it:
        return every page it holds to the free pools (its reservation is
        dropped — `FreeListAllocator.free`), retain its generated tokens
        host-side for recompute, and requeue it at its original arrival
        position (FIFO within its priority class)."""
        s = self.slots[slot_id]
        req = s.request
        now = time.perf_counter()
        req._resume_tokens = list(s.generated)
        req._t_preempt = now
        req._n_preempts += 1
        req._prefill_s_acc += s.prefill_s
        req._decode_s_acc += max(now - s.t_admit - s.prefill_s, 0.0)
        if self._alloc is not None:
            self._alloc.preemptions += 1
        self.free(slot_id)
        pos = next((j for j, r in enumerate(self.queue)
                    if getattr(r, "_seq", 0) > req._seq), len(self.queue))
        self.queue.insert(pos, req)
        self._events.append(events_lib.PreemptedEvent(
            req.id, self._step_no, n_generated=len(req._resume_tokens)))

    def _swap_out(self, slot_id: int) -> bool:
        """Evict a running slot to the host swap tier: mirror its EXACT
        device state (one warm jitted gather, one batched device_get),
        return every page it holds to the free pools, and requeue it at
        its arrival position.  Re-admission takes `_admit_one`'s swap-in
        branch — upload + table re-grant, no prefill, no recompute.

        Returns False with no side effects beyond a counted refusal when
        the slot still aliases shared-prefix pages (refcount > 1: its
        hi/lo pages are not exclusively its own — freeing them would pull
        pages other slots read, and privatizing first would ALLOCATE pages,
        the opposite of relief) or when the host pool has no free entry;
        the caller falls back to preempt+recompute so eviction still
        makes progress."""
        s = self.slots[slot_id]
        if s is None:
            return False
        if self._alloc.needs_privatize(slot_id):
            self._swap.note_refusal("aliased")
            return False
        handle = self._swap.reserve()    # a full pool counts its own refusal
        if handle is None:
            return False
        req = s.request
        now = time.perf_counter()
        # capture BEFORE free(): the allocator clears occupancy and the
        # rung dies with the slot.  Occupancy is a frozen dataclass, so
        # holding the reference is safe.
        st = _SwapState(
            handle=handle, occ=self._alloc.occ[slot_id],
            steps=s.steps, since_rc=s.since_rc,
            rung=int(self._rungs[slot_id]))  # sync: ok(_rungs is a host-side numpy array)
        payload = self._swap_extract(
            self.caches,
            jnp.asarray(slot_id, jnp.int32))  # sync: ok(one scalar upload per swap-out event, not per step)
        self._swap.store(handle, payload)
        req._swap_state = st
        # same host-side request bookkeeping as _preempt: _resume_tokens
        # keeps cancel()/result() uniform for evicted requests, and the
        # swap-in branch restores generated from it
        req._resume_tokens = list(s.generated)
        req._t_preempt = now
        req._n_preempts += 1
        req._prefill_s_acc += s.prefill_s
        req._decode_s_acc += max(now - s.t_admit - s.prefill_s, 0.0)
        self._alloc.preemptions += 1
        self.free(slot_id)
        pos = next((j for j, r in enumerate(self.queue)
                    if getattr(r, "_seq", 0) > req._seq), len(self.queue))
        self.queue.insert(pos, req)
        self._events.append(events_lib.SwappedEvent(
            req.id, self._step_no, direction="out",
            n_generated=len(req._resume_tokens),
            host_bytes=self._swap.stats()["host_bytes"]))
        return True

    def _swap_in(self, slot_id: int, req: Request, t0: float) -> None:
        """Re-admit a swapped-out request WITHOUT recompute: re-grant its
        pages from the captured occupancy (legal by construction — the
        same worst-case reservation covered this occupancy while it ran),
        upload the host mirror, scatter it through the new table, and
        reinstate every per-slot counter.  The restored slot's next decode
        step consumes exactly the device bytes and counter state the
        evicted slot would have had — tokens stay bitwise identical to
        recompute and to the uncontended run."""
        st: _SwapState = req._swap_state
        resume = req._resume_tokens
        bucket = self._bucket_len(int(req.tokens.shape[-1]))  # sync: ok(np shape tuple, host-side)
        self._alloc.admit(slot_id, st.occ, self._request_total_tokens(req),
                          bucket)
        self._sync_tables()
        payload = self._swap.load(st.handle)
        self.caches = self._swap_restore(
            self.caches, payload,
            jnp.asarray(slot_id, jnp.int32))  # sync: ok(one scalar upload per swap-in event, not per step)
        self._swap.release(st.handle)
        req._preempt_s += t0 - req._t_preempt
        t1 = time.perf_counter()
        self.slots[slot_id] = _Slot(
            request=req, generated=list(resume),
            steps=st.steps, since_rc=st.since_rc,
            t_submit=getattr(req, "_t_submit", t0), t_admit=t0,
            prefill_s=t1 - t0)   # admission cost = two PCIe transfers, no FLOPs
        self._rungs[slot_id] = st.rung   # later folds stay at the ladder rung
        del req._swap_state
        del req._resume_tokens
        self._events.append(events_lib.SwappedEvent(
            req.id, self._step_no, direction="in",
            n_generated=len(resume),
            host_bytes=self._swap.stats()["host_bytes"]))
        self._maybe_finish(slot_id)

    def _downshift(self, slot_id: int) -> bool:
        """One ladder downshift of a running slot: bump its rung and
        early-fold its staging window at the lowered lo-store effective
        bit-width, returning the window's pages to the pool.  The slot
        keeps decoding — precision, not residency, absorbs the pressure.

        Returns False without side effects when the slot is ineligible
        (empty, already at the deepest rung, or an empty window: nothing
        to fold means no pages to free), and False after counting a
        REFUSAL when the slot still aliases shared-prefix pages: those
        pages are immutable while refcount > 1, and privatizing them first
        would ALLOCATE pages — the opposite of relief.  The alias keeps
        its rung until CoW privatization at its own fold cadence."""
        s = self.slots[slot_id]
        if (s is None
                or int(self._rungs[slot_id]) >= self._max_rung  # sync: ok(_rungs is a host-side numpy array)
                or s.since_rc == 0):
            return False
        if self._alloc.needs_privatize(slot_id):
            self._alloc.note_downshift_refusal()
            return False
        self._rungs[slot_id] += 1
        freed = self._fold([slot_id])
        s.since_rc = 0
        self._alloc.note_downshift(slot_id, freed)
        self._events.append(events_lib.DownshiftEvent(
            s.request.id, self._step_no,
            rung=int(self._rungs[slot_id]),  # sync: ok(_rungs is host numpy)
            pages_freed=freed))
        return True

    def _ladder_step(self) -> None:
        """The pressure trigger (ServeConfig.ladder_watermark): when the
        min free fraction across the page pools sits at or below the
        watermark, downshift the OLDEST eligible slot (arrival order — it
        has decoded longest, so its remaining tokens have the least left
        to lose).  At most one downshift per step: each rung frees pages,
        so re-checking pressure next step bounds the precision loss to
        what the pool actually needs."""
        if not self._ladder or self.scfg.ladder_watermark <= 0 \
                or self._alloc is None:
            return
        if self._alloc.pool_pressure() > self.scfg.ladder_watermark:
            return
        order = sorted((i for i in range(self.scfg.batch_size)
                        if self.slots[i] is not None),
                       key=lambda i: self.slots[i].request._seq)
        for i in order:
            if self._downshift(i):
                return

    def _pack_moves(self, moves: Dict[str, Tuple[List[int], List[int]]]):
        """Fixed-shape device operands for the page-copy program: per
        segment, (src, dst) id vectors padded to the per-slot page count
        with the segment's SINK id (sink->sink self-copies absorb the
        padding), so the number of real moves never retraces the program."""
        out = {}
        for name in alloc_lib.FreeListAllocator.SEGMENTS:
            seg = self._alloc.segs[name]
            src, dst = moves.get(name, ((), ()))
            s = np.full(max(seg.npp, 1), seg.null, np.int32)
            d = np.full(max(seg.npp, 1), seg.null, np.int32)
            s[:len(src)] = src
            d[:len(dst)] = dst
            out[name] = (
                jnp.asarray(s),  # sync: ok(two small id-vector uploads per privatized segment per fold event)
                jnp.asarray(d))  # sync: ok(two small id-vector uploads per privatized segment per fold event)
        return out

    def _fold(self, due_ids: Sequence[int]) -> int:
        """Fold the due slots' staging windows (with the allocator's
        grant-before/shrink-after page movements around the jitted
        program).  Shared by step(), recompute replay, and the downshift
        ladder.  Returns how many window pages the shrink returned (the
        ladder's "pages freed"; ordinary folds ignore it).

        With the ladder armed the rung-aware programs run for EVERY fold —
        the per-slot rungs ride as data, and rung 0 reproduces the base
        map's bits — so one warm program per signature covers pressured
        and unpressured folds alike (tests/test_retrace.py)."""
        b = self.scfg.batch_size
        self._counts["fold_events"] += 1
        self._counts["folds"] += len(due_ids)
        if self._alloc is not None:
            # CoW-before-fold: recompression re-splits hi/lo per slot, so a
            # slot still aliasing shared-prefix pages must be privatized
            # first — the allocator repoints its table at fresh pages and
            # the jitted copy program materializes their payload (page ids
            # are data operands: one warm program, sink-padded id vectors)
            for i in due_ids:
                if self._alloc.needs_privatize(int(i)):
                    moves = self._alloc.privatize(int(i))
                    if moves:
                        self.caches = self._copy_pages(
                            self.caches, self._pack_moves(moves))
            # grant the hi/lo pages the fold will scatter into BEFORE
            # the program runs (writes through NULL entries would land
            # in the sink and lose tokens)
            for i in due_ids:
                self._alloc.fold_grant(int(i))
            self._sync_tables()
        # Per-slot programs fold each due slot at ~1/slots the FLOPs of
        # the rows-masked program (bitwise the same result — recompression
        # is row-independent), but every call also rewrites the cache
        # tree once.  Use them while the FLOP savings outweigh the extra
        # dispatches/copies; co-due majorities (lockstep-aligned cadence)
        # batch into the single rows-masked call as before.
        if self._recompress_slot is not None and len(due_ids) * 2 <= b:
            for i in due_ids:
                slot = jnp.asarray(int(i), jnp.int32)  # sync: ok(one scalar upload per due slot per fold event, cadence 1/interval steps)
                if self._ladder:
                    self.caches = self._recompress_slot_rung(
                        self.caches, slot,
                        jnp.asarray(int(self._rungs[i]), jnp.int32))  # sync: ok(one scalar rung upload per due slot per fold event)
                else:
                    self.caches = self._recompress_slot(self.caches, slot)
        else:
            due = np.zeros(b, bool)
            due[np.asarray(due_ids, int)] = True
            if self._ladder:
                self.caches = self._recompress_rows_rung(
                    self.caches,
                    jnp.asarray(due),  # sync: ok(one mask upload per fold event, cadence 1/interval steps)
                    jnp.asarray(self._rungs.copy()))  # sync: ok(one (b,) rung upload per fold event; copied because the live array mutates host-side between steps and CPU uploads may zero-copy alias it)
            else:
                self.caches = self._recompress_rows(
                    self.caches,
                    jnp.asarray(due))  # sync: ok(one mask upload per fold event, cadence 1/interval steps)
        freed = 0
        if self._alloc is not None:
            # the staging windows emptied: return their pages (the
            # recompression-shrink half of the elasticity story)
            for i in due_ids:
                freed += self._alloc.fold_shrink(int(i))
            self._sync_tables()
        return freed

    @_on_device
    def step(self) -> List[events_lib.Event]:
        """One scheduler iteration: run the injected scheduler's admission
        plan (and preemptions), decode one token for every active slot,
        retire finished requests, and fold staging windows on each slot's
        own cadence (paper Alg. 3 per request).  Returns the typed events
        this iteration produced, in order (empty = idle step).

        Under the free-list allocator every page movement happens here,
        host-side, between the jitted programs: a staging-window page is
        granted when a slot's append cursor crosses into it, hi/lo growth
        pages are granted immediately before a fold's write-back, and the
        emptied window's pages are returned immediately after.

        Events are DRAINED at return, not reset at entry: a `cancel()`
        issued between steps (an async server loop reacting to a
        disconnect) buffers its `CancelledEvent` into the next step's
        return value instead of being dropped.

        Host spans (`runtime/tracing`): `engine.step` around the whole
        iteration, carrying `counters()` as they stand at its start, and
        inside it `engine.admit`, `engine.prefill` (per admission),
        `engine.stage`, `engine.sync_tables`, `engine.decode`,
        `engine.readback` (the host's one wait on the device),
        `engine.emit` and `engine.fold`."""
        n = self._counts
        with tracing.span("engine.step", **n):
            with tracing.span("engine.admit"):
                self._sweep_deadlines()
                self._ladder_step()   # relieve pool pressure BEFORE planning
                self._admit()         # admission, so freed pages count this step
            b = self.scfg.batch_size
            active_ids = [i for i in range(b) if self.slots[i] is not None]
            if not active_ids:
                events, self._events = self._events, []
                return events
            n["steps"] += 1
            interval = self.ccfg.recompress_interval
            with tracing.span("engine.stage"):
                if self._alloc is not None:
                    for i in active_ids:
                        self._alloc.note_append(i)
                    self._sync_tables()
                # all per-slot scalars ride ONE (6, b) staging matrix: a
                # single host->device transfer per step instead of six (the
                # hostsync lint flags per-scalar churn; values/dtypes are
                # bit-identical after the jitted unpack, so conformance
                # stays bitwise)
                stage = np.zeros((6, b), np.int32)
                stage_temps = stage[_ROW_TEMP].view(np.float32)
                probing = 0
                for i in active_ids:
                    s = self.slots[i]
                    probe = probe_flag(s.steps, interval, self.scfg.seed)
                    probing += probe
                    stage[_ROW_TOK, i] = s.generated[-1]
                    stage[_ROW_PROBE, i] = probe
                    stage[_ROW_ACT, i] = 1
                    stage_temps[i] = s.request.sampling.temperature
                    stage[_ROW_SEED, i] = s.request.sampling.seed
                    stage[_ROW_CTR, i] = len(s.generated)
                tok, probes, act, temps, seeds, counters = self._unstage(
                    jnp.asarray(stage))  # sync: ok(the single batched host->device staging transfer per step)
            n["probe_rows"] += probing
            n["probe_steps"] += probing > 0

            with tracing.span("engine.decode"):
                logits, self.caches = self._decode_masked(
                    self.params, self.caches, tok, probes, act)
            with tracing.span("engine.readback"):
                nxt, finite = np.asarray(  # sync: ok(the single batched device->host token read per step)
                    self._sample(logits, temps, seeds, counters))
            self.nonfinite_logits += int(  # sync: ok(host numpy: the readback above)
                np.sum(finite[active_ids] == 0))

            due = []
            with tracing.span("engine.emit"):
                for i in active_ids:
                    s = self.slots[i]
                    s.steps += 1
                    s.since_rc += 1
                    s.generated.append(int(nxt[i]))
                    self._emit_token(s.request, int(nxt[i]),
                                     len(s.generated) - 1)
                    if self._maybe_finish(i):
                        continue
                    if s.since_rc >= interval:
                        due.append(i)
            if due:
                with tracing.span("engine.fold", slots=len(due)):
                    self._fold(due)
                for i in due:
                    self.slots[i].since_rc = 0
            self._step_no += 1
            events, self._events = self._events, []
            return events


class ContinuousEngine(EngineCore):
    """`EngineCore` with the scheduler built from `ServeConfig`: the
    compatibility surface every existing caller keeps using.

    ``ServeConfig.scheduler`` picks the policy ("fifo" reproduces the
    pre-split engine bitwise; "priority" orders by `Request.priority`), and
    ``ServeConfig.preemption`` arms recompute eviction.  Pass `scheduler=`
    to inject a custom `serving.scheduler.Scheduler` implementation
    directly (ServeConfig's string field is then ignored)."""

    def __init__(self, cfg: ArchConfig, ccfg: CompressionConfig, scfg: ServeConfig,
                 params, mesh=None,
                 scheduler: Optional[scheduler_lib.Scheduler] = None,
                 device=None):
        super().__init__(cfg, ccfg, scfg, params,
                         scheduler or scheduler_lib.make_scheduler(scfg.scheduler),
                         mesh=mesh, device=device)
