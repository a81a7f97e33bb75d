"""The program's own spans and counters: `runtime/tracing` spans inside
`EngineCore.step` and the HTTP front, read back from a CPU profiler trace,
and the `engine` / `front` counters that `/v1/stats` serves."""

import asyncio
import dataclasses
import gc
import glob
import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.policy import CompressionConfig
from repro.models import registry
from repro.runtime import tracing
from repro.serving import ContinuousEngine, EngineRouter, ServeConfig
from repro.serving.http import HttpFrontend

SPANS = ("engine.step", "engine.admit", "engine.prefill", "engine.stage",
         "engine.sync_tables", "engine.decode", "engine.readback",
         "engine.emit", "engine.fold", "front.route", "front.sse_write",
         "host.gc")


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


def _prompt(cfg, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(2, cfg.vocab, size=(24,)).tolist()


async def _sse(port, tokens, max_new):
    """One streamed request; its SSE token frames and the done frame."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"tokens": tokens, "max_new_tokens": max_new}).encode()
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    while (await reader.readline()) not in (b"\r\n", b""):
        pass
    frames = []
    while True:
        line = (await reader.readline()).strip()
        if line.startswith(b"data: "):
            frames.append(json.loads(line[6:]))
            if "tokens" in frames[-1]:
                writer.close()
                return frames


async def _get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    while (await reader.readline()) not in (b"\r\n", b""):
        pass
    body = json.loads(await reader.read())
    writer.close()
    return body


def _serve(eng, scenario):
    async def run():
        front = HttpFrontend(eng, port=0)
        await front.start()
        try:
            return front, await scenario(front)
        finally:
            await front.stop(drain=False)
    return asyncio.run(run())


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """Two streams of 12 tokens (each slot folds once) served under the
    profiler, a collection forced inside it; the program spans read back
    as [name, start_ns, end_ns, stats] by start."""
    cfg, eng = engine
    before = eng.counters()
    out = tmp_path_factory.mktemp("trace")

    async def scenario(front):
        got = await asyncio.gather(*[_sse(front.port, _prompt(cfg, s), 12)
                                     for s in (1, 2)])
        gc.collect()
        return got

    jax.profiler.start_trace(str(out))
    try:
        front, streams = _serve(eng, scenario)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True))
    spans = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
                          for e in line.events if e.name.startswith(
                              ("engine.", "front.", "host."))]
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans, "streams": streams, "front": front.counters(),
            "before": before, "after": eng.counters()}


def _named(traced, name):
    return [s for s in traced["spans"] if s[0] == name]


def test_every_span_is_in_the_trace(traced):
    assert {s[0] for s in traced["spans"]} == set(SPANS)


@pytest.mark.parametrize("name", ["engine.admit", "engine.stage",
                                  "engine.decode", "engine.readback",
                                  "engine.emit", "engine.fold"])
def test_step_phases_nest_inside_engine_step(traced, name):
    steps = _named(traced, "engine.step")
    inner = _named(traced, name)
    assert inner
    for _, a, b, _ in inner:
        assert any(s0 <= a and b <= s1 for _, s0, s1, _ in steps), name


def test_readback_ends_before_the_next_decode(traced):
    decodes = [a for _, a, _, _ in _named(traced, "engine.decode")]
    for _, a, b, _ in _named(traced, "engine.readback"):
        later = [d for d in decodes if d > a]
        assert not later or min(later) >= b


def test_request_spans_carry_their_request_id(traced):
    rids = {f[-1]["id"] for f in traced["streams"]}
    prefill = _named(traced, "engine.prefill")
    assert {s[3]["request_id"] for s in prefill} == rids
    assert all(s[3]["prompt_tokens"] == 24 for s in prefill)
    writes = _named(traced, "front.sse_write")
    assert {s[3]["request_id"] for s in writes} == rids
    for rid in rids:
        assert sorted(s[3]["index"] for s in writes
                      if s[3]["request_id"] == rid) == list(range(12))


def test_counters_match_what_was_served(traced):
    d = {k: traced["after"][k] - traced["before"][k] for k in traced["after"]}
    steps = _named(traced, "engine.step")
    assert d["admissions"] == 2 and d["prefill_tokens"] == 2 * 24
    assert d["steps"] == len(_named(traced, "engine.decode"))
    assert d["folds"] == 2 and d["fold_events"] >= 1
    assert d["fold_events"] == len(_named(traced, "engine.fold"))
    assert 0 < d["probe_steps"] <= d["steps"]
    assert d["probe_steps"] <= d["probe_rows"] <= 2 * d["probe_steps"]
    # each step span carries the counters as they stood when it began
    assert steps[0][3] == traced["before"]
    assert set(steps[-1][3]) == set(ContinuousEngine.COUNTERS)
    front = traced["front"]
    assert front["tokens_routed"] == front["tokens_written"] == 24
    assert front["drive_steps"] == len(steps)
    assert front["backlog_token_steps"] > 0
    routes = _named(traced, "front.route")
    assert routes[-1][3]["drive_steps"] == front["drive_steps"]


def test_gc_span_installs_once():
    tracing.install_gc_span()
    tracing.install_gc_span()
    assert sum(cb is tracing._GC_SPAN for cb in gc.callbacks) == 1


@pytest.mark.parametrize("routed", [False, True])
def test_stats_serves_engine_and_front_counters(engine, routed):
    cfg, eng = engine
    served = EngineRouter([eng], names=["r0"]) if routed else eng

    async def scenario(front):
        await _sse(front.port, _prompt(cfg, 3), 4)
        return await _get(front.port, "/v1/stats")

    front, stats = _serve(served, scenario)
    want = {"r0": eng.counters()} if routed else eng.counters()
    assert stats["engine"] == want
    assert stats["front"] == front.counters()
    assert stats["front"]["tokens_written"] == 4
    assert set(stats["front"]) == set(HttpFrontend.COUNTERS)
