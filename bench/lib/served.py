"""One cell's run through the served path: HTTP front, continuous engine,
paged quantized cache and decode kernel, driven by in-process SSE clients.

The engine is built as `repro.launch.serve_http.build_frontend` builds it,
from the program's own engine flags, except that the model configuration
and the weights come from the benchmark's files.  The front is handed a
`StepProxy` that delegates every call to the engine and timestamps each
`step()`: that is the benchmark's span around the engine layer.

Set-up admits each admission group at a fixed engine step: the proxy holds
the engine (reports nothing pending, so the front parks) until the group's
requests have reached `submit`, then lets exactly the steps up to the next
group run.  After the last group the engine runs freely, and the window
opens at the end of the step before `window_open_step`.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import json
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from . import spec
from . import window as window_lib

POLL_S = 0.002
FLUSH_S = 60.0      # after the window, the longest wait for the clients


class StepProxy:
    """Delegates to the engine; records every step; holds the engine
    between set-up groups; opens and closes the window on step ends."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: List[window_lib.Step] = []
        self.hold = False
        self.stop_after: Optional[int] = None
        self.rid_of: Dict[bytes, str] = {}       # prompt bytes -> request id
        self.on_step_end: Optional[Callable[[window_lib.Step], None]] = None
        self.annotate = False

    def __getattr__(self, name):
        return getattr(self.engine, name)

    @property
    def pending(self) -> bool:
        return not self.hold and self.engine.pending

    def submit(self, request, **kw):
        rid = self.engine.submit(request, **kw)
        self.rid_of[np.asarray(request.tokens).tobytes()] = rid
        return rid

    def step(self):
        from repro.serving import events as events_lib
        ann = (jax.profiler.TraceAnnotation("engine_step") if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            events = self.engine.step()
        t1 = time.perf_counter()
        s = window_lib.Step(len(self.steps), t0, t1,
                            [(e.request_id, e.index) for e in events
                             if isinstance(e, events_lib.TokenEvent)])
        self.steps.append(s)
        if self.stop_after is not None and len(self.steps) >= self.stop_after:
            self.hold, self.stop_after = True, None
        if self.on_step_end is not None:
            self.on_step_end(s)
        return events

    async def run_to(self, n_steps: int) -> None:
        """Let the engine run until it has taken `n_steps` steps, then hold."""
        if len(self.steps) >= n_steps:
            self.hold = True
            return
        self.stop_after, self.hold = n_steps, False
        while len(self.steps) < n_steps:
            await asyncio.sleep(POLL_S)


class CacheEvents:
    """Counts persistent-cache lookups (hits and misses) while armed: a
    program fetched from the cache inside the window is set-up work that
    leaked into it, as much as a compile is."""

    _installed = None

    def __init__(self):
        self.armed = False
        self.requests = 0
        self.hits = 0

    @classmethod
    def get(cls) -> "CacheEvents":
        if cls._installed is None:
            cls._installed = cls()
            jax.monitoring.register_event_listener(cls._installed._on)
        return cls._installed

    def _on(self, event: str, **kw) -> None:
        if not self.armed:
            return
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# HTTP client
# ---------------------------------------------------------------------------

async def stream_session(port: int, prompt: np.ndarray, max_new: int,
                         got: Dict[int, tuple]) -> None:
    """POST /v1/generate with stream=true; record (token, arrival time) by
    token index into `got` until the done frame or cancellation."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"tokens": prompt.tolist(), "max_new_tokens": max_new,
                       "temperature": 0.0, "stream": True}).encode()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    try:
        await writer.drain()
        status = (await reader.readline()).decode().strip()
        while (await reader.readline()) not in (b"\r\n", b""):
            pass
        if not status.endswith("200 OK"):
            raise RuntimeError(f"{status}: {(await reader.read()).decode()}")
        event = None
        while True:
            line = await reader.readline()
            now = time.perf_counter()
            if not line:
                raise RuntimeError("SSE stream ended without a done frame")
            line = line.decode().strip()
            if not line:
                continue
            if line.startswith("event: "):
                event = line[len("event: "):]
                continue
            data = json.loads(line[len("data: "):])
            if event == "error":
                raise RuntimeError(f"engine failed: {data}")
            if event == "done":
                return
            got[int(data["index"])] = (int(data["token"]), now)
    finally:
        writer.close()


async def client(port: int, sessions, record: list, failures: list) -> None:
    """A closed-loop client: each session is sent once the last finished."""
    for s in sessions:
        got: Dict[int, tuple] = {}
        record.append((s, got))
        try:
            await stream_session(port, s.prompt, s.max_new, got)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — counted as a failed request
            failures.append(f"{type(e).__name__}: {e}")
            return


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def build_engine(config: dict, traffic: dict, params, device):
    """ContinuousEngine from the program's own engine flags (the
    configuration file's `program.argv` plus the traffic's static shapes),
    validated as the HTTP server validates them, and the ArchConfig of the
    configuration's adapter (`bench/adapters/<reference>.py`)."""
    from repro.launch import serve as serve_cli
    from repro.launch import serve_http
    from repro.serving import ContinuousEngine
    ap = serve_http.build_parser()
    args = ap.parse_args(list(config["program"]["argv"]) + [
        "--batch", str(traffic["clients"]),
        "--prompt-len", str(traffic["prompt_len"]),
        "--max-new", str(traffic["max_new"])])
    serve_cli.validate_engine_args(args, ap, continuous=True)
    cfg = spec.module("adapters", config["reference"]).arch_config(
        config["model"], config["name"])
    ccfg = serve_cli.build_compression_config(args)
    scfg = serve_cli.build_serve_config(args)
    return ContinuousEngine(cfg, ccfg, scfg, params, device=device)


def warm_folds(engine) -> None:
    """Compile the per-slot fold program, which set-up's decode never
    reaches (a slot folds every recompress_interval steps): run it on the
    engine's live caches and drop the result.  It is called as a fold
    calls it, under the engine's device placement: once on the caches just
    after the allocator re-installed its page tables (uploaded, so not yet
    committed to the device), once on caches committed to it, as a second
    fold in the same step finds them.  One result at a time, so warming
    holds no more cache copies than a fold does."""
    import jax.numpy as jnp
    if engine._recompress_slot is None:
        return
    with engine._placement():
        if engine._alloc is not None:
            engine._alloc.dirty = True          # same tables, re-uploaded
            engine._sync_tables()
        for caches in (lambda: engine.caches,
                       lambda: jax.device_put(engine.caches, engine.device)):
            out = engine._recompress_slot(caches(), jnp.asarray(0, jnp.int32))
            jax.block_until_ready(out)
            del out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

async def serve_window(engine, traffic: dict, sessions, seconds: float,
                       trace_dir: Optional[str], log) -> dict:
    """Set-up admissions, free-running decode up to the window, the window
    itself, one more step so the window's last tokens reach the clients as
    every other token does; then, with the engine held, a wait (at most
    FLUSH_S) until every client holds every token the engine generated for
    it, so the check compares all of them.  Returns what the proxy and
    clients saw."""
    from repro.runtime import compile_guard
    from repro.serving.http import HttpFrontend

    proxy = StepProxy(engine)
    front = HttpFrontend(proxy, host="127.0.0.1", port=0)
    cache_ev = CacheEvents.get()
    state: dict = {"open": None, "close": None}
    open_step = traffic["window_open_step"]
    spans = contextlib.ExitStack()          # open from window open to close

    def on_step_end(s: window_lib.Step) -> None:
        if s.index == open_step - 1:
            if trace_dir is not None:
                jax.profiler.start_trace(trace_dir)
                spans.enter_context(jax.profiler.TraceAnnotation("bench_window"))
            state["compiles"] = spans.enter_context(
                compile_guard.count_compiles())
            cache_ev.requests = cache_ev.hits = 0
            cache_ev.armed = True
            state["open"] = time.perf_counter()
        elif state["open"] is not None and state["close"] is None \
                and window_lib.close_step(proxy.steps, open_step,
                                          state["open"], seconds) == s.index:
            state["close"] = s.index
            cache_ev.armed = False
            spans.close()
            proxy.stop_after = s.index + 2       # one step more, then hold

    proxy.on_step_end = on_step_end
    proxy.annotate = trace_dir is not None
    proxy.hold = True
    await front.start()
    record: list = []
    failures: list = []
    tasks = []
    try:
        nxt = 0
        t = time.perf_counter()
        for step, n in traffic["groups"]:
            await proxy.run_to(step)
            for c in range(nxt, nxt + n):
                tasks.append(asyncio.create_task(
                    client(front.port, sessions[c], record, failures)))
            nxt += n
            while len(proxy.rid_of) < nxt and not failures:
                await asyncio.sleep(POLL_S)
            if failures:
                raise RuntimeError(f"set-up request failed: {failures}")
        await proxy.run_to(traffic["groups"][-1][0] + 1)   # last admission
        tw = time.perf_counter()
        warm_folds(engine)
        log(f"set-up: warm the per-slot fold: {time.perf_counter() - tw:.2f}s")
        proxy.hold = False
        while state["open"] is None:
            await asyncio.sleep(POLL_S)
        log(f"set-up decode: {open_step} steps ({len(traffic['groups'])} "
            f"admission groups) in {state['open'] - t:.2f}s")
        while not (state["close"] is not None and proxy.hold):
            await asyncio.sleep(POLL_S)
            if failures:
                raise RuntimeError(f"request failed: {failures}")
        tf = time.perf_counter()
        last = _last_index(proxy.steps)
        while not _delivered(record, proxy.rid_of, last) and not failures \
                and time.perf_counter() - tf < FLUSH_S:
            await asyncio.sleep(POLL_S)
        log(f"after the window: clients hold every generated token: "
            f"{_delivered(record, proxy.rid_of, last)} after "
            f"{time.perf_counter() - tf:.2f}s with the engine held")
        stats = engine.device.memory_stats() if engine.device is not None \
            else jax.devices()[0].memory_stats()
        peak = int((stats or {}).get("peak_bytes_in_use", 0))
        log(f"device memory: peak {peak} of {(stats or {}).get('bytes_limit')}"
            f" bytes")
        if trace_dir is not None:
            jax.profiler.stop_trace()
        caches = engine.cache_bytes(engine.caches)
    finally:
        for tk in tasks:
            tk.cancel()
        for tk in tasks:
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await tk
        proxy.hold = True
        with contextlib.suppress(Exception):
            await front.stop(drain=False)
    arrivals: Dict[str, Dict[int, float]] = {}
    served = []
    last = _last_index(proxy.steps)
    for s, got in record:
        rid = proxy.rid_of.get(s.prompt.tobytes())
        if rid is None:
            continue
        arrivals[rid] = {i: t for i, (_, t) in got.items()}
        n = last.get(rid, -1) + 1
        toks = [got[i][0] for i in range(n) if i in got]
        served.append({"rid": rid, "prompt": s.prompt, "tokens": toks,
                       "complete": len(toks) == n})
    w = window_lib.summarize(proxy.steps, open_step, state["close"],
                             state["open"], arrivals)
    return {"window": w, "steps": proxy.steps, "open_step": open_step,
            "close_step": state["close"], "t_open": state["open"],
            "compiles": state["compiles"].count,
            "compile_names": [n.split(" with ")[0]
                              for n in state["compiles"].names
                              if n.startswith("Compiling")],
            "cache_requests": cache_ev.requests, "cache_hits": cache_ev.hits,
            "peak_bytes": peak, "cache_bytes": caches,
            "served": served, "arrivals": arrivals,
            "attempted": len(record),
            "failed": len(failures), "failures": failures,
            "context_tokens": _context_tokens(served, last)}


def _last_index(steps) -> Dict[str, int]:
    """Per request, the highest token index the engine generated."""
    last: Dict[str, int] = {}
    for s in steps:
        for rid, i in s.tokens:
            last[rid] = max(last.get(rid, -1), i)
    return last


def _delivered(record, rid_of, last) -> bool:
    """Whether every client holds every token the engine generated for its
    request (the front delivers a stream's backlog once the engine is
    held)."""
    for s, got in record:
        rid = rid_of.get(s.prompt.tobytes())
        if rid is not None and len(got) < last.get(rid, -1) + 1:
            return False
    return True


def _context_tokens(served, last_index) -> int:
    """Live K/V positions held at the window's close, summed over slots:
    each request's prompt plus the tokens fed back through decode."""
    n = 0
    for r in served:
        i = last_index.get(r["rid"])
        if i is not None:
            n += len(r["prompt"]) + i       # token i is not yet in the cache
    return n


def free_engine(engine) -> None:
    """Drop the program's device state (caches, jitted programs) so the
    reference that follows has the chip's memory beside the weights."""
    engine.caches = None
    engine.__dict__.clear()
    gc.collect()
