"""The network serving edge: HTTP/SSE front, drive-loop backoff, the
multi-replica router, and the launch-surface guards.

Four layers, cheapest first:

  * `Backoff` and the drive loop against a STUB engine — deterministic
    proof that empty-event steps sleep with growing delays instead of
    busy-driving `step()` (the idle/deferred-stepping satellite);
  * `EngineRouter` placement policy against fake replicas — least-loaded
    ranking, free-page tie-breaks, session affinity, draining, id
    uniqueness — all host-pure, no engine needed;
  * the real asyncio server over a REAL engine and real sockets: SSE
    tokens bitwise `result(rid).tokens`, disconnect-cancel returning the
    slot's pages, deadlines, the cancel endpoint, error statuses — plus
    the router in front of two real replicas;
  * `launch.serve` / `launch.serve_http` argparse guards (`ap.error` ->
    SystemExit) for flag combinations that would otherwise be silently
    ignored.
"""

import asyncio
import collections
import dataclasses
import json

import numpy as np
import pytest

from repro import configs
from repro.core.policy import CompressionConfig
from repro.models import registry
from repro.serving import (CancelledEvent, ContinuousEngine, EngineRouter,
                           FinishedEvent, NoReplicaError, Request, ServeConfig,
                           TokenEvent, UnknownRequestError)
from repro.serving.http import Backoff, HttpFrontend


# ---------------------------------------------------------------------------
# Backoff + drive loop (stub engine: no jax, deterministic)
# ---------------------------------------------------------------------------

def test_backoff_grows_caps_and_resets():
    b = Backoff(initial=0.01, maximum=0.05, factor=2.0)
    assert [b.next_delay() for _ in range(4)] == [0.01, 0.02, 0.04, 0.05]
    assert b.next_delay() == 0.05          # capped
    b.reset()
    assert b.next_delay() == 0.01


def test_backoff_rejects_nonsense():
    for bad in [dict(initial=0.0), dict(maximum=0.0001), dict(factor=0.5)]:
        with pytest.raises(ValueError):
            Backoff(**bad)


class _StubEngine:
    """Minimal engine double for the drive loop: scripted step() returns."""

    def __init__(self, script=None):
        self.script = list(script or [])
        self.steps = 0
        self.pending = True

    def step(self):
        self.steps += 1
        return self.script.pop(0) if self.script else []

    def shutdown(self):
        self.pending = False


class _RecordingBackoff(Backoff):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.delays = []
        self.resets = 0

    def next_delay(self):
        d = super().next_delay()
        self.delays.append(d)
        return d

    def reset(self):
        self.resets += 1
        super().reset()


def test_drive_loop_backs_off_on_empty_steps():
    """A pending-but-deferred engine (every step returns no events — the
    page pool blocking the whole queue) must NOT be busy-stepped: the loop
    sleeps between steps with exponentially growing delays.  ~0.15s of
    wall time at initial=10ms admits only a handful of steps; a busy loop
    would take thousands."""
    stub = _StubEngine()
    bo = _RecordingBackoff(initial=0.01, maximum=0.04)
    front = HttpFrontend(stub, backoff=bo)

    async def run():
        task = asyncio.create_task(front._drive())
        await asyncio.sleep(0.15)
        front._closed = True
        front._wake.set()
        await task

    asyncio.run(run())
    assert 2 <= stub.steps <= 20, stub.steps
    assert bo.delays == sorted(bo.delays)      # non-decreasing growth
    assert bo.delays[0] == 0.01


def test_drive_once_dispatches_and_resets_backoff():
    """Productive steps route events to the registered per-request queues
    and reset the idle backoff; events for unregistered requests (e.g.
    programmatic submits) are dropped, not leaked."""
    ev = TokenEvent("r1", 0, token=7, index=0)
    other = TokenEvent("r2", 0, token=9, index=0)
    stub = _StubEngine(script=[[ev, other], []])
    bo = _RecordingBackoff(initial=0.01, maximum=0.04)
    front = HttpFrontend(stub, backoff=bo)

    async def run():
        q = asyncio.Queue()
        front._queues["r1"] = q
        assert front._drive_once() is True
        assert bo.resets == 1
        assert q.get_nowait() is ev
        assert q.empty()                       # r2's event went nowhere
        assert front._drive_once() is False    # empty step: no reset
        assert bo.resets == 1

    asyncio.run(run())


# ---------------------------------------------------------------------------
# EngineRouter placement (fake replicas: host-pure)
# ---------------------------------------------------------------------------

class _FakeReplica:
    def __init__(self, slots=2, busy=0, queued=0, free_pages=0):
        self.slots = [object() if i < busy else None for i in range(slots)]
        self.queue = collections.deque(range(queued))
        self.results = {}
        self.submitted = []
        self.free_pages = free_pages
        self.closed = False

    def submit(self, request):
        if request.id is None:
            request.id = f"fake-{len(self.submitted)}"
        self.submitted.append(request.id)
        return request.id

    def cancel(self, rid, reason="client"):
        self.cancelled = (rid, reason)
        return True

    def pool_stats(self):
        if self.free_pages == 0:
            return None
        return {"hi": {"free": self.free_pages}, "deferrals": 0}

    def shutdown(self):
        self.closed = True

    @property
    def pending(self):
        return False

    def step(self):
        return []


def _req():
    return Request(tokens=np.asarray([1, 2, 3], np.int32))


def test_router_places_least_loaded():
    a = _FakeReplica(slots=2, busy=2, queued=1)     # load 1.5
    b = _FakeReplica(slots=2, busy=1)               # load 0.5
    router = EngineRouter([a, b], names=["a", "b"])
    rid = router.submit(_req())
    assert b.submitted and not a.submitted
    assert rid.startswith("b/")
    assert router._placement[rid] == 1


def test_router_breaks_ties_toward_free_pages_then_index():
    a = _FakeReplica(slots=2, busy=1, free_pages=2)
    b = _FakeReplica(slots=2, busy=1, free_pages=9)
    router = EngineRouter([a, b])
    router.submit(_req())
    assert b.submitted and not a.submitted          # same load, more pages
    c, d = _FakeReplica(slots=2), _FakeReplica(slots=2)
    router2 = EngineRouter([c, d])
    router2.submit(_req())
    assert c.submitted and not d.submitted          # full tie: lowest index


def test_router_session_affinity_sticks_and_repins_on_drain():
    a = _FakeReplica(slots=2, busy=2, queued=3)     # heavily loaded
    b = _FakeReplica(slots=2)
    router = EngineRouter([a, b], names=["a", "b"])
    r1 = router.submit(_req(), session="s1")        # lands on b (least loaded)
    assert b.submitted == [r1]
    b.slots = [object(), object()]                  # b now the busier one
    b.queue.extend(range(4))
    r2 = router.submit(_req(), session="s1")        # affinity beats load
    assert b.submitted == [r1, r2] and not a.submitted
    router.drain("b")                               # graceful drain
    assert b.closed
    r3 = router.submit(_req(), session="s1")        # re-pinned off the drained one
    assert a.submitted == [r3]
    with pytest.raises(NoReplicaError):
        router.drain("a")
        router.submit(_req())


def test_router_rejects_duplicate_ids_and_unknown_rids():
    router = EngineRouter([_FakeReplica(), _FakeReplica()])
    req = Request(tokens=np.asarray([1], np.int32), id="dup")
    router.submit(req)
    with pytest.raises(ValueError):
        router.submit(Request(tokens=np.asarray([1], np.int32), id="dup"))
    with pytest.raises(UnknownRequestError):
        router.poll("never-seen")
    with pytest.raises(UnknownRequestError):
        router.cancel("never-seen")


def test_router_cancel_routes_to_placement():
    a, b = _FakeReplica(busy=2), _FakeReplica()
    router = EngineRouter([a, b])
    rid = router.submit(_req())                     # b: lower load
    assert router.cancel(rid, reason="deadline") is True
    assert b.cancelled == (rid, "deadline")


def test_router_affinity_map_bounded_under_session_churn():
    """Regression: one-shot sessions used to pin `_affinity` forever — the
    map grew by one entry per session for the life of the router.  Idle
    pins (no queued/running request) beyond `max_idle_sessions` must now
    be LRU-evicted, while live pins are never evicted regardless of the
    cap (a mid-flight re-pin would split a session across replicas)."""
    class _PollingReplica(_FakeReplica):
        def poll(self, rid):
            return "done" if rid in self.results else "running"

    a, b = _PollingReplica(), _PollingReplica()
    router = EngineRouter([a, b], names=["a", "b"], max_idle_sessions=8)
    for i in range(100):
        rid = router.submit(_req(), session=f"churn-{i}")
        # the request retires replica-side before the next session arrives
        (a if rid in a.submitted else b).results[rid] = object()
    assert len(router._affinity) <= 8 + 1, len(router._affinity)
    # the side tables stay bounded too (stale entries only for the few
    # surviving pins the trim never needed to reconcile)
    assert len(router._session_live) <= 8 + 1
    assert len(router._req_session) <= 8 + 1

    # live sessions are NEVER evicted, even past the cap...
    c, d = _PollingReplica(), _PollingReplica()
    live = EngineRouter([c, d], max_idle_sessions=2)
    rids = [live.submit(_req(), session=f"live-{i}") for i in range(5)]
    assert all(f"live-{i}" in live._affinity for i in range(5))
    # ...and an idle pin below the cap survives for the session's next turn
    c.results[rids[0]] = d.results[rids[0]] = object()
    pin = live._affinity["live-0"]
    live.submit(_req(), session="live-0")
    assert live._affinity["live-0"] == pin


def test_router_retires_sessions_on_finish_and_cancel_events():
    """The event-driven retirement path: FinishedEvent/CancelledEvent seen
    in `router.step()` (and a successful `cancel()`) drop the request from
    its session's live set without any poll reconciliation."""
    class _EventReplica(_FakeReplica):
        def __init__(self):
            super().__init__()
            self.to_finish = []

        @property
        def pending(self):
            return bool(self.to_finish)

        def step(self):
            evs = [FinishedEvent(request_id=r, step=0, finish_reason="stop",
                                 n_tokens=1) for r in self.to_finish]
            self.to_finish = []
            return evs

    eng = _EventReplica()
    router = EngineRouter([eng])
    r1 = router.submit(_req(), session="s")
    r2 = router.submit(_req(), session="s")
    assert router._session_live["s"] == {r1, r2}
    eng.to_finish = [r1]
    router.step()
    assert router._session_live["s"] == {r2}
    assert router.cancel(r2)
    assert "s" not in router._session_live and not router._req_session


def test_router_validates_construction():
    with pytest.raises(ValueError):
        EngineRouter([])
    with pytest.raises(ValueError):
        EngineRouter([_FakeReplica()], names=["a", "b"])
    with pytest.raises(ValueError):
        EngineRouter([_FakeReplica(), _FakeReplica()], names=["a", "a"])


# ---------------------------------------------------------------------------
# real engine + real sockets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = configs.get_arch("yi-6b", smoke=True)
    ccfg = dataclasses.replace(CompressionConfig.zipcache(),
                               fp_window=8, recompress_interval=8)
    scfg = ServeConfig(batch_size=2, prompt_len=32, max_new_tokens=48,
                       backend="paged", page_size=8,
                       page_allocator="freelist")
    params = registry.materialize_params(cfg, 0)
    return cfg, ContinuousEngine(cfg, ccfg, scfg, params)


def _prompt(cfg, seed=0, n=24):
    rng = np.random.default_rng(seed)
    return rng.integers(2, cfg.vocab, size=(n,)).tolist()


async def _open_post(port, path, payload):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    writer.write((f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    return reader, writer


async def _read_headers(reader):
    status = (await reader.readline()).decode()
    while (await reader.readline()) not in (b"\r\n", b""):
        pass
    return status


async def _read_sse(reader):
    tokens, final = [], None
    while final is None:
        line = (await reader.readline()).strip()
        if not line:
            continue
        if line.startswith(b"data: "):
            d = json.loads(line[6:])
            if "token" in d:
                tokens.append(d["token"])
            else:
                final = d
    return tokens, final


async def _request_json(port, method, path, payload=None):
    if payload is None:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"{method} {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        await writer.drain()
    else:
        reader, writer = await _open_post(port, path, payload)
    status = await _read_headers(reader)
    body = json.loads(await reader.read())
    writer.close()
    return status, body


def _with_front(engine, coro):
    """Run `coro(front)` under a live server; never drains the module
    engine (jit caches + open state are shared across tests)."""
    async def run():
        front = HttpFrontend(engine, port=0)
        await front.start()
        try:
            return await coro(front)
        finally:
            await front.stop(drain=False)
    return asyncio.run(run())


def test_http_sse_tokens_bitwise_result(engine):
    cfg, eng = engine

    async def scenario(front):
        reader, writer = await _open_post(
            front.port, "/v1/generate",
            {"tokens": _prompt(cfg), "max_new_tokens": 8})
        await _read_headers(reader)
        tokens, final = await _read_sse(reader)
        writer.close()
        return tokens, final

    tokens, final = _with_front(eng, scenario)
    out = eng.result(final["id"])
    assert tokens == final["tokens"] == [int(t) for t in out.tokens]
    assert final["finish_reason"] == out.finish_reason == "length"
    assert len(tokens) == 8


def test_http_nonstream_json_and_statuses(engine):
    cfg, eng = engine

    async def scenario(front):
        ok = await _request_json(
            front.port, "POST", "/v1/generate",
            {"tokens": _prompt(cfg, seed=1), "max_new_tokens": 4,
             "stream": False})
        bad = await _request_json(front.port, "POST", "/v1/generate",
                                  {"wrong": 1})
        lost = await _request_json(front.port, "GET", "/nope")
        health = await _request_json(front.port, "GET", "/health")
        stats = await _request_json(front.port, "GET", "/v1/stats")
        return ok, bad, lost, health, stats

    ok, bad, lost, health, stats = _with_front(eng, scenario)
    assert "200" in ok[0] and len(ok[1]["tokens"]) == 4
    assert [int(t) for t in eng.result(ok[1]["id"]).tokens] == ok[1]["tokens"]
    assert "400" in bad[0] and "tokens" in bad[1]["error"]
    assert "404" in lost[0]
    assert health[1] == {"ok": True}
    assert "200" in stats[0] and "pool_stats" in stats[1]


def test_http_disconnect_cancels_and_returns_pages(engine):
    """The acceptance criterion: hanging up an SSE connection cancels the
    request at the engine — slot freed, pages back in `pool_stats()` —
    instead of leaking the slot for the remaining decode budget."""
    cfg, eng = engine

    async def scenario(front):
        reader, writer = await _open_post(
            front.port, "/v1/generate", {"tokens": _prompt(cfg, seed=2)})
        await _read_headers(reader)
        first = (await reader.readline()).strip()   # one token arrived
        assert first.startswith(b"data: ")
        rid_known = set(eng.results)
        writer.close()                              # client vanishes
        for _ in range(400):                        # bounded wait for cancel
            await asyncio.sleep(0.01)
            new = [r for r in eng.results if r not in rid_known]
            if new:
                return new[0]
        raise AssertionError("disconnect never cancelled the request")

    rid = _with_front(eng, scenario)
    out = eng.result(rid)
    assert out.finish_reason == "cancelled"
    assert 1 <= len(out.tokens) < 48                # partial, not the budget
    stats = eng.pool_stats()
    assert all(v["used"] == 0 for v in stats.values()
               if isinstance(v, dict) and "used" in v)


def test_http_deadline_cancels(engine):
    cfg, eng = engine

    async def scenario(front):
        reader, writer = await _open_post(
            front.port, "/v1/generate",
            {"tokens": _prompt(cfg, seed=3), "deadline_s": 1e-4})
        await _read_headers(reader)
        _, final = await _read_sse(reader)
        writer.close()
        return final

    final = _with_front(eng, scenario)
    assert final["finish_reason"] == "cancelled"
    assert eng.result(final["id"]).finish_reason == "cancelled"


def test_http_cancel_endpoint(engine):
    cfg, eng = engine

    async def scenario(front):
        reader, writer = await _open_post(
            front.port, "/v1/generate", {"tokens": _prompt(cfg, seed=4)})
        await _read_headers(reader)
        line = (await reader.readline()).strip()
        rid = None
        # the id is only in the final frame; fetch it from the engine side
        rid = sorted(set(eng._known) - set(eng.results))[0] \
            if set(eng._known) - set(eng.results) else None
        cancel = await _request_json(front.port, "POST", "/v1/cancel",
                                     {"id": rid})
        unknown = await _request_json(front.port, "POST", "/v1/cancel",
                                      {"id": "ghost"})
        _, final = await _read_sse(reader)          # stream terminates
        writer.close()
        return cancel, unknown, final

    cancel, unknown, final = _with_front(eng, scenario)
    assert "200" in cancel[0] and cancel[1]["cancelled"] is True
    assert "404" in unknown[0]
    assert final["finish_reason"] == "cancelled"


def test_http_failed_step_fails_clients_and_stop_raises(engine,
                                                        monkeypatch):
    """A step that raises (a compile error, device OOM) must not strand
    its waiting clients or let the server exit cleanly: the SSE client gets
    an error frame, the JSON client a 500, a later submit too, and
    `stop()` raises the step's exception."""
    cfg, eng = engine

    def broken_step():
        raise RuntimeError("device out of memory")

    monkeypatch.setattr(eng, "step", broken_step)

    async def run():
        front = HttpFrontend(eng, port=0)
        await front.start()
        reader, writer = await _open_post(
            front.port, "/v1/generate", {"tokens": _prompt(cfg, seed=5)})
        status = await _read_headers(reader)
        frames = (await reader.read()).decode()
        writer.close()
        failed_json = await _request_json(
            front.port, "POST", "/v1/generate",
            {"tokens": _prompt(cfg, seed=6), "stream": False})
        await front.wait()                 # the drive loop has ended
        with pytest.raises(RuntimeError, match="out of memory"):
            await front.stop(drain=False)
        return status, frames, failed_json

    status, frames, failed_json = asyncio.run(run())
    assert "200" in status and "event: error" in frames
    assert "out of memory" in frames
    assert "500" in failed_json[0] and "out of memory" in failed_json[1]["error"]
    monkeypatch.undo()
    # the module engine still holds the two submitted requests: retire them
    for rid in set(eng._known) - set(eng.results):
        eng.cancel(rid)
    while eng.pending:
        eng.step()


def test_http_router_two_replicas_end_to_end(engine):
    """Two REAL engine replicas behind the router, served over HTTP:
    session-less requests spread by load, every stream stays bitwise its
    own engine's result, and per-replica stats surface."""
    cfg, eng = engine                     # reuse the warm module engine...
    ccfg = dataclasses.replace(CompressionConfig.zipcache(),
                               fp_window=8, recompress_interval=8)
    scfg = ServeConfig(batch_size=2, prompt_len=32, max_new_tokens=48,
                       backend="paged", page_size=8,
                       page_allocator="freelist")
    params = registry.materialize_params(cfg, 0)
    other = ContinuousEngine(cfg, ccfg, scfg, params)   # ...plus a fresh one
    router = EngineRouter([eng, other], names=["warm", "cold"])

    async def scenario(front):
        async def one(seed):
            reader, writer = await _open_post(
                front.port, "/v1/generate",
                {"tokens": _prompt(cfg, seed=seed), "max_new_tokens": 6})
            await _read_headers(reader)
            tokens, final = await _read_sse(reader)
            writer.close()
            return tokens, final
        results = await asyncio.gather(*[one(s) for s in (10, 11, 12)])
        stats = await _request_json(front.port, "GET", "/v1/stats")
        return results, stats

    results, stats = _with_front(router, scenario)
    placed = set()
    for tokens, final in results:
        assert tokens == final["tokens"] and len(tokens) == 6
        out = router.result(final["id"])
        assert [int(t) for t in out.tokens] == tokens
        placed.add(final["id"].split("/")[0])
    assert placed == {"warm", "cold"}          # load actually spread
    assert set(stats[1]["replicas"]) == {"warm", "cold"}
    assert set(stats[1]["pool_stats"]) == {"warm", "cold"}


# ---------------------------------------------------------------------------
# launch-surface guards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv", [
    ["--arch", "yi-6b", "--pool-fraction", "0.5"],
    ["--arch", "yi-6b", "--admit-watermark", "0.25"],
    ["--arch", "yi-6b", "--continuous", "--backend", "paged",
     "--pool-fraction", "0.5"],                  # static allocator
    ["--arch", "yi-6b", "--paged-kernel", "on"],
    ["--arch", "yi-6b", "--preemption", "recompute"],
])
def test_serve_rejects_silently_ignored_flags(argv):
    """Every flag combination the engine would silently ignore must die in
    argparse (`ap.error` -> SystemExit 2) — the satellite fix covers
    --pool-fraction/--admit-watermark without the free-list allocator."""
    from repro.launch import serve
    with pytest.raises(SystemExit) as exc:
        serve.main(argv)
    assert exc.value.code == 2


@pytest.mark.parametrize("argv", [
    ["--arch", "yi-6b", "--pool-fraction", "0.5"],
    ["--arch", "yi-6b", "--replicas", "0"],
    ["--arch", "yi-6b", "--scheduler", "priority",
     "--preemption", "recompute", "--paged-kernel", "on"],
])
def test_serve_http_rejects_invalid_combos(argv):
    from repro.launch import serve_http
    with pytest.raises(SystemExit) as exc:
        serve_http.main(argv)
    assert exc.value.code == 2


def test_serve_http_accepts_continuous_only_combos(monkeypatch):
    """The HTTP front is always continuous: combinations gated on
    --continuous in the batch driver validate cleanly here (validation
    runs with continuous=True).  Only parsing/validation is under test —
    the frontend builder is stubbed out before any engine is built."""
    import repro.launch.serve_http as sh

    class _Stop(Exception):
        pass

    captured = {}

    def no_engine(args):
        captured["args"] = args
        raise _Stop

    monkeypatch.setattr(sh, "build_frontend", no_engine)
    with pytest.raises(_Stop):
        sh.main(["--arch", "yi-6b", "--smoke", "--backend", "paged",
                 "--page-allocator", "freelist", "--pool-fraction", "0.5",
                 "--scheduler", "priority", "--preemption", "recompute"])
    assert captured["args"].pool_fraction == 0.5
    assert captured["args"].replicas == 1


_REPLICA_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from repro.launch import serve_http
from repro.serving import Request

ap = serve_http.build_parser()
args = ap.parse_args(["--arch", "yi-6b", "--smoke", "--backend", "paged",
                      "--page-allocator", "freelist", "--batch", "2",
                      "--prompt-len", "16", "--max-new", "4",
                      "--replicas", "2"])
router = serve_http.build_frontend(args).engine
rng = np.random.default_rng(0)
for i in range(4):
    router.submit(Request(tokens=rng.integers(2, 200, size=(12,))))
while router.pending:
    router.step()
placed = []
for eng in router.replicas:
    trees = (eng.params, eng.caches)
    placed.append(sorted({str(d) for t in trees
                          for x in jax.tree_util.tree_leaves(t)
                          for d in x.devices()}))
print("RESULT:" + json.dumps({"placed": placed,
                              "devices": [str(d) for d in jax.devices()]}))
"""


def test_serve_http_replicas_each_on_own_device():
    """`--replicas N` puts replica i on jax.devices()[i] — its params AND
    its caches, still there after serving (host uploads follow the
    replica); none falls back to the default device.  Subprocess: the
    device-count flag must be set before jax initializes."""
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", _REPLICA_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stderr[-3000:]
    out = json.loads(line[0][len("RESULT:"):])
    assert out["placed"] == [[out["devices"][0]], [out["devices"][1]]]


def test_serve_http_refuses_more_replicas_than_devices():
    import jax
    from repro.launch import serve_http
    ap = serve_http.build_parser()
    args = ap.parse_args(["--arch", "yi-6b", "--smoke",
                          "--replicas", str(len(jax.devices()) + 1)])
    with pytest.raises(ValueError, match="devices"):
        serve_http.build_frontend(args)
