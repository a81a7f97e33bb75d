"""Asyncio HTTP/SSE serving front over `EngineCore` (or `EngineRouter`).

The network edge of ROADMAP item 1: requests arrive over POST, tokens
stream back as server-sent events, client disconnects cancel the request
(`EngineCore.cancel` — slot freed, pages returned), and per-request
deadlines ride `Request.deadline_s` into the engine's own sweep.  Stdlib
only (asyncio streams + a minimal HTTP/1.1 parser): the container bakes no
HTTP framework, and the surface we need — POST + SSE + Connection: close —
is small enough that a dependency would cost more than it saves.

Endpoints
    POST /v1/generate   JSON body: {"tokens": [ints], "max_new_tokens"?,
                        "temperature"?, "seed"?, "stop_tokens"?,
                        "priority"?, "deadline_s"?, "session"?,
                        "stream"?: bool (default true)}.
                        stream=true  -> ``text/event-stream``: one
                        ``data: {"token": t, "index": i}`` event per
                        decoded token, then a terminal
                        ``event: done`` / ``data: {... "tokens": [...]}``
                        whose token list is bitwise `result(rid).tokens`
                        (the per-token events concatenate to exactly it).
                        stream=false -> one JSON response when finished.
    POST /v1/cancel     {"id": rid} -> {"cancelled": bool}.
    GET  /v1/stats      engine/router load + pool telemetry as JSON, and
                        the work counters: {"pool_stats", "engine"
                        (`EngineCore.counters()`, per replica under a
                        router), "front" (`HttpFrontend.counters()`),
                        "replicas" (router only)}.
    GET  /health        liveness probe.

Drive loop
    One background coroutine owns ``engine.step()`` — called synchronously
    on the event loop (the engine mutates host state like the admission
    deque; a thread pool would race the handlers' ``submit`` calls, and a
    step is one jitted dispatch, not something to parallelize).  Handlers
    communicate with it through per-request asyncio queues fed from the
    step's returned events.  When steps come back EMPTY (every queued
    request deferred by the page pools, or nothing pending) the loop backs
    off exponentially (`Backoff`) instead of busy-driving ``step()`` the
    way the synchronous ``stream()`` helper may; a fresh submit wakes it
    immediately (``_wake``).  A step that RAISES (a compile error, device
    out-of-memory) ends the loop: every waiting handler is failed (500 /
    an SSE ``event: error``), later submits get 503, and ``stop()`` raises
    the step's exception — a broken engine never leaves clients hanging
    or a server process exiting 0.

Cancellation
    While an SSE response is open the handler also watches the client
    socket; EOF (the client hung up) cancels the request at the engine —
    the typed `CancelledEvent` path — so a disconnected client's slot and
    pages are reclaimed within one step instead of leaking for the full
    decode budget.  An expired `deadline_s` takes the same path with
    reason "deadline" and terminates the SSE stream with
    ``finish_reason="cancelled"``.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
from typing import Dict, Optional

import numpy as np

from repro.runtime import tracing
from repro.serving import engine as engine_lib
from repro.serving import events as events_lib

# terminal events: the request left the engine, result(rid) is available
_TERMINAL = (events_lib.FinishedEvent, events_lib.CancelledEvent)


class Backoff:
    """Exponential idle backoff for the drive loop: empty-event steps sleep
    ``initial * factor^k`` capped at ``maximum``; any productive step
    resets.  Deterministic and loop-free so tests can drive it directly."""

    def __init__(self, initial: float = 0.001, maximum: float = 0.05,
                 factor: float = 2.0):
        if not (initial > 0 and maximum >= initial and factor >= 1.0):
            raise ValueError(
                f"need 0 < initial <= maximum and factor >= 1, got "
                f"({initial}, {maximum}, {factor})")
        self.initial, self.maximum, self.factor = initial, maximum, factor
        self._cur = initial

    def next_delay(self) -> float:
        """The delay to sleep NOW; grows the next one."""
        d = self._cur
        self._cur = min(self._cur * self.factor, self.maximum)
        return d

    def reset(self) -> None:
        self._cur = self.initial


def _json_response(status: str, payload) -> bytes:
    body = json.dumps(payload).encode()
    return (f"HTTP/1.1 {status}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n").encode() + body


_SSE_HEADER = (b"HTTP/1.1 200 OK\r\n"
               b"Content-Type: text/event-stream\r\n"
               b"Cache-Control: no-cache\r\n"
               b"Connection: close\r\n\r\n")


def _error_payload(error: BaseException) -> Dict:
    return {"error": f"engine step failed: {type(error).__name__}: {error}"}


def _sse(payload, event: Optional[str] = None) -> bytes:
    head = f"event: {event}\n" if event else ""
    return f"{head}data: {json.dumps(payload)}\n\n".encode()


class HttpFrontend:
    """HTTP/SSE edge around one engine (or an `EngineRouter` — the request
    API is duck-typed, so 1 replica and N replicas serve identically).

    Lifecycle::

        front = HttpFrontend(engine, host="127.0.0.1", port=0)
        await front.start()          # port=0 -> front.port has the real one
        ...
        await front.stop()           # drain=True: engine.shutdown() + drain

    ``stop(drain=False)`` detaches without closing the engine — the same
    engine instance can serve again (tests reuse one engine across server
    sessions so jit caches stay warm and steady state stays retrace-free).
    """

    COUNTERS = ("drive_steps", "tokens_routed", "tokens_written",
                "backlog_token_steps")

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0,
                 backoff: Optional[Backoff] = None):
        self.engine = engine
        self.host, self.port = host, port
        self.backoff = backoff if backoff is not None else Backoff()
        self._queues: Dict[str, asyncio.Queue] = {}
        self._wake = asyncio.Event()
        self._server: Optional[asyncio.AbstractServer] = None
        self._drive_task: Optional[asyncio.Task] = None
        self._closed = False
        self._error: Optional[BaseException] = None   # a failed step()
        # per open SSE stream: tokens routed to it but not yet written
        self._unwritten: Dict[str, int] = {}
        self._counts = dict.fromkeys(self.COUNTERS, 0)
        tracing.install_gc_span()

    def counters(self) -> Dict[str, int]:
        """Cumulative counts since construction, served as `front` by
        `/v1/stats`: `drive_steps` (engine steps the drive loop took),
        `tokens_routed` (token events put on a request's queue),
        `tokens_written` (SSE token frames written), and
        `backlog_token_steps`: at each drive step, before `step()`, the
        tokens routed to open SSE streams but not yet written, summed over
        the streams, added up over steps.  Each `front.route` span carries
        them as they stand when routing begins."""
        return dict(self._counts)

    # ------------------------------------------------------------------
    # drive loop: the ONLY caller of engine.step() while the front is up
    # ------------------------------------------------------------------

    def _drive_once(self) -> bool:
        """One engine step; route its events to the waiting handlers.
        Returns True if the step produced any events (progress)."""
        n = self._counts
        n["drive_steps"] += 1
        n["backlog_token_steps"] += sum(self._unwritten.values())
        events = self.engine.step()
        with tracing.span("front.route", events=len(events), **n):
            for ev in events:
                q = self._queues.get(ev.request_id)
                if q is not None:
                    q.put_nowait(ev)
                    if isinstance(ev, events_lib.TokenEvent):
                        n["tokens_routed"] += 1
                        if ev.request_id in self._unwritten:
                            self._unwritten[ev.request_id] += 1
        if events:
            self.backoff.reset()
            return True
        return False

    async def _drive(self) -> None:
        while not self._closed:
            if not self.engine.pending:
                # idle: park until a submit wakes us (re-check periodically
                # so a stop() or an externally-submitted request isn't
                # stranded behind a cleared flag)
                self._wake.clear()
                with contextlib.suppress(asyncio.TimeoutError):
                    await asyncio.wait_for(self._wake.wait(),
                                           timeout=self.backoff.maximum)
                continue
            try:
                progressed = self._drive_once()
            except Exception as e:  # noqa: BLE001 — surfaced by stop()
                self._fail(e)
                return
            if progressed:
                await asyncio.sleep(0)      # yield: let handlers flush SSE
            else:
                # pending but no events: every queued request is deferred
                # (page-pool pressure) — back off instead of spinning the
                # scheduler at CPU speed
                await asyncio.sleep(self.backoff.next_delay())

    def _fail(self, error: BaseException) -> None:
        """The engine's step raised: stop driving, fail every waiting
        handler (each queue gets the exception as its terminal item), and
        keep the error for `stop()` to raise."""
        self._error = error
        self._closed = True
        for q in self._queues.values():
            q.put_nowait(error)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._drive_task = asyncio.create_task(self._drive())

    async def wait(self) -> None:
        """Serve until `stop()` or a failed engine step ends the drive
        loop (then `stop()` raises that step's exception)."""
        if self._drive_task is not None:
            await asyncio.shield(self._drive_task)

    async def stop(self, drain: bool = True) -> None:
        """Stop serving.  drain=True also closes the engine (`shutdown()`)
        and steps it until every accepted request finished; drain=False
        detaches and leaves the engine open for reuse.  Raises the
        exception of a failed engine step, after closing the server."""
        self._closed = True
        self._wake.set()
        if self._drive_task is not None:
            await self._drive_task
            self._drive_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        if self._error is not None:
            raise self._error
        if drain:
            self.engine.shutdown()
            while self.engine.pending:
                self._drive_once()

    # ------------------------------------------------------------------
    # HTTP plumbing
    # ------------------------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        try:
            request_line = await reader.readline()
            if not request_line:
                return
            try:
                method, target, _ = request_line.decode().split(None, 2)
            except ValueError:
                writer.write(_json_response(
                    "400 Bad Request", {"error": "malformed request line"}))
                return
            headers: Dict[str, str] = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                key, _, val = line.decode().partition(":")
                headers[key.strip().lower()] = val.strip()
            body = b""
            length = int(headers.get("content-length", "0") or 0)
            if length:
                body = await reader.readexactly(length)
            await self._route(method, target, body, reader, writer)
        except (ConnectionResetError, BrokenPipeError, asyncio.IncompleteReadError):
            pass        # client went away mid-parse/mid-write
        finally:
            with contextlib.suppress(Exception):
                writer.close()

    async def _route(self, method: str, target: str, body: bytes,
                     reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        if method == "GET" and target == "/health":
            writer.write(_json_response("200 OK", {"ok": True}))
            await writer.drain()
        elif method == "GET" and target == "/v1/stats":
            stats = {"pool_stats": self.engine.pool_stats(),
                     "engine": self.engine.counters(),
                     "front": self.counters()}
            router_stats = getattr(self.engine, "stats", None)
            if callable(router_stats):
                stats["replicas"] = router_stats()
            writer.write(_json_response("200 OK", stats))
            await writer.drain()
        elif method == "POST" and target == "/v1/cancel":
            await self._handle_cancel(body, writer)
        elif method == "POST" and target == "/v1/generate":
            await self._handle_generate(body, reader, writer)
        else:
            writer.write(_json_response(
                "404 Not Found", {"error": f"no route {method} {target}"}))
            await writer.drain()

    async def _handle_cancel(self, body: bytes,
                             writer: asyncio.StreamWriter) -> None:
        try:
            rid = json.loads(body.decode() or "{}")["id"]
            cancelled = self.engine.cancel(rid)
        except events_lib.UnknownRequestError as e:
            writer.write(_json_response("404 Not Found", {"error": str(e)}))
        except (json.JSONDecodeError, KeyError):
            writer.write(_json_response(
                "400 Bad Request", {"error": 'body must be {"id": <rid>}'}))
        else:
            writer.write(_json_response("200 OK", {"cancelled": cancelled}))
        await writer.drain()

    def _build_request(self, spec: Dict) -> engine_lib.Request:
        return engine_lib.Request(
            tokens=np.asarray(spec["tokens"], np.int32),
            max_new_tokens=spec.get("max_new_tokens"),
            stop_tokens=tuple(spec.get("stop_tokens", ())),
            priority=int(spec.get("priority", 0)),
            deadline_s=spec.get("deadline_s"),
            sampling=engine_lib.SamplingParams(
                temperature=float(spec.get("temperature", 0.0)),
                seed=int(spec.get("seed", 0))))

    def _submit(self, req: engine_lib.Request, session: Optional[str]) -> str:
        if session is not None:
            # only the router places by session; a bare engine has no
            # affinity concept and takes the request as-is
            try:
                return self.engine.submit(req, session=session)
            except TypeError:
                pass
        return self.engine.submit(req)

    async def _handle_generate(self, body: bytes,
                               reader: asyncio.StreamReader,
                               writer: asyncio.StreamWriter) -> None:
        try:
            spec = json.loads(body.decode())
            if not isinstance(spec, dict) or "tokens" not in spec:
                raise ValueError('body must be a JSON object with "tokens"')
            req = self._build_request(spec)
            rid = self._submit(req, spec.get("session"))
        except (json.JSONDecodeError, ValueError, TypeError, KeyError) as e:
            writer.write(_json_response("400 Bad Request", {"error": str(e)}))
            await writer.drain()
            return
        except Exception as e:
            # EngineClosedError / NoReplicaError / PoolCapacityError: the
            # request was REJECTED, not failed — tell the client to go away
            writer.write(_json_response(
                "503 Service Unavailable",
                {"error": f"{type(e).__name__}: {e}"}))
            await writer.drain()
            return
        queue: asyncio.Queue = asyncio.Queue()
        if self._error is not None:
            queue.put_nowait(self._error)   # accepted after the failure
        self._queues[rid] = queue
        stream = spec.get("stream", True)
        if stream:
            self._unwritten[rid] = 0
        self._wake.set()
        try:
            if stream:
                await self._stream_sse(rid, queue, reader, writer)
            else:
                await self._respond_json(rid, queue, writer)
        finally:
            self._queues.pop(rid, None)
            self._unwritten.pop(rid, None)

    def _final_payload(self, rid: str) -> Dict:
        out = self.engine.result(rid)
        return {"id": out.id,
                "finish_reason": out.finish_reason,
                "tokens": [int(t) for t in out.tokens],
                "timings": out.timings}

    async def _respond_json(self, rid: str, queue: asyncio.Queue,
                            writer: asyncio.StreamWriter) -> None:
        while True:
            ev = await queue.get()
            if isinstance(ev, BaseException):
                writer.write(_json_response(
                    "500 Internal Server Error", _error_payload(ev)))
                await writer.drain()
                return
            if isinstance(ev, _TERMINAL):
                break
        writer.write(_json_response("200 OK", self._final_payload(rid)))
        await writer.drain()

    async def _stream_sse(self, rid: str, queue: asyncio.Queue,
                          reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        writer.write(_SSE_HEADER)
        await writer.drain()
        # the client hanging up is our cancellation signal: SSE clients
        # never send again, so any read completing means EOF/reset
        monitor = asyncio.create_task(reader.read(1))
        try:
            while True:
                getter = asyncio.create_task(queue.get())
                done, _ = await asyncio.wait(
                    {getter, monitor}, return_when=asyncio.FIRST_COMPLETED)
                if getter not in done:
                    getter.cancel()
                    with contextlib.suppress(asyncio.CancelledError):
                        await getter
                    self._cancel_quietly(rid, "client")
                    return
                ev = getter.result()
                if isinstance(ev, BaseException):
                    with contextlib.suppress(
                            ConnectionResetError, BrokenPipeError):
                        writer.write(_sse(_error_payload(ev), event="error"))
                        await writer.drain()
                    return
                if isinstance(ev, events_lib.TokenEvent):
                    try:
                        with tracing.span("front.sse_write", request_id=rid,
                                          index=ev.index):
                            writer.write(_sse(
                                {"token": ev.token, "index": ev.index}))
                        self._unwritten[rid] -= 1
                        self._counts["tokens_written"] += 1
                        await writer.drain()
                    except (ConnectionResetError, BrokenPipeError):
                        self._cancel_quietly(rid, "client")
                        return
                elif isinstance(ev, _TERMINAL):
                    with contextlib.suppress(
                            ConnectionResetError, BrokenPipeError):
                        writer.write(_sse(self._final_payload(rid),
                                          event="done"))
                        await writer.drain()
                    return
                # CallbackErrorEvent / PreemptedEvent etc. are engine-side
                # diagnostics, not stream content — the SSE contract is
                # "token events concatenate to result().tokens"
        finally:
            monitor.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await monitor

    def _cancel_quietly(self, rid: str, reason: str) -> None:
        """Cancel on disconnect: the request may have finished in the same
        step the client vanished — that race is fine, cancel() returns
        False for done requests and unknown ids cannot happen here."""
        self.engine.cancel(rid, reason=reason)
