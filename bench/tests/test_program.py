"""The program's own spans and counters as the benchmark reads them: on the
tiny cell served under the CPU profiler (the counters against the
benchmark's reconstruction of the schedule, the readers through the traced
run's directory), and on a cut of a chip trace (the readers' numbers, the
idle gaps named by program span)."""

import asyncio
import json
import statistics

import jax
import pytest

from bench.lib import cell, program, served, spec
from bench.lib import trace as trace_lib
from bench.lib import traffic as traffic_lib
from bench.lib import weights as weights_lib
from bench.tests import tiny

CUT = spec.BENCH / "tests" / "data" / "trace_cut_yi6b_program.json"
READERS = ("host_gap_ms", "probe_step_share", "sse_backlog_tokens")
# The cut: window steps 5-7 of yi6b-decode-long on one TPU v5e (seed
# 2914000141), two probe-and-fold steps and a plain one that ends in a
# 0.09 s readback stall; the readers' numbers on it, and its idle gaps'
# names, longest first.
EXPECTED = {"host_gap_ms": 108.0752385, "probe_step_share": 200 / 3,
            "sse_backlog_tokens": 3156 / 3 / 16}
EXPECTED_NAMES = ["engine.readback", "engine.decode", "engine.readback",
                  "engine.readback"] + ["engine.sync_tables"] * 6


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """The tiny cell's window (about 20 steps: probe steps, and folds at the
    16th decode of every slot) served under the profiler."""
    c, seed = tiny.cell(), 5
    trace_dir = tmp_path_factory.mktemp("trace")
    device = jax.devices()[0]
    w = weights_lib.make(c["config"]["model"], seed, device)
    engine = served.build_engine(c["config"], c["traffic"],
                                 weights_lib.to_program(w), device)
    sessions = traffic_lib.sessions(c["traffic"], seed,
                                    c["config"]["model"]["vocab_size"])
    res = asyncio.run(served.serve_window(engine, c["traffic"], sessions,
                                          0.3, str(trace_dir), lambda m: None))
    res["window_steps"] = res["steps"][res["open_step"]:res["close_step"] + 1]
    return c, res, trace_dir


def test_counters_equal_the_reconstructed_schedule(tiny_run):
    c, res, trace_dir = tiny_run
    prog = program.load(trace_dir)
    eng = program.window_counters(prog, "engine.step")
    assert eng["steps"] == res["window"]["steps"]
    assert eng["probe_steps"] == cell.probe_steps(res, c)
    assert eng["folds"] == cell.window_work(res, c)["folds"]
    assert eng["folds"] > 0 and eng["probe_steps"] > 0
    assert eng["admissions"] == eng["prefill_tokens"] == 0
    front = program.window_counters(prog, "front.route")
    assert front["drive_steps"] == res["window"]["steps"]
    assert front["tokens_routed"] == res["window"]["tokens"]


def test_readers_find_the_traced_runs_directory(tiny_run, monkeypatch):
    _, _, trace_dir = tiny_run
    monkeypatch.setattr(program, "TRACE_DIR", trace_dir)
    w0, w1 = program.load(trace_dir)["window"]
    run = {"trace": {"window_s": (w1 - w0) / 1e9}}
    for name in READERS:
        assert spec.module("metrics", name).read(run) > 0, name
    other = {"trace": {"window_s": (w1 - w0) / 1e9 + 1e-3}}
    for r in (other, {"trace": None}):
        assert all(spec.module("metrics", n).read(r) is None for n in READERS)


def test_a_program_without_spans_reads_nothing(tmp_path, monkeypatch):
    """The benchmark's own window span alone, as a program without spans
    leaves the trace (but for `host.gc`, where this process installed it):
    every reader returns None without raising."""
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    prog = program.load(tmp_path)
    assert prog is None or {s[0] for s in prog["spans"]} <= {"host.gc"}
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    run = {"trace": {"window_s": 1.0}}
    assert all(spec.module("metrics", n).read(run) is None for n in READERS)


# ---------------------------------------------------------------------------
# a cut of a chip trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prog():
    return json.loads(CUT.read_text())


def test_cut_is_small():
    assert CUT.stat().st_size <= 1 << 20


def test_readers_on_the_cut(prog):
    got = {n: spec.module("metrics", n).read({"program": prog})
           for n in READERS}
    eng = program.window_counters(prog, "engine.step")
    assert got["probe_step_share"] == pytest.approx(
        100.0 * eng["probe_steps"] / eng["steps"])
    gaps = program.host_gaps_ns(prog)
    assert len(gaps) == eng["steps"] - 1
    assert got["host_gap_ms"] == pytest.approx(statistics.median(gaps) / 1e6)
    assert got == pytest.approx(EXPECTED)


def test_idle_gaps_by_span_are_the_reductions_gaps(prog):
    w0, w1 = prog["window"]
    red = trace_lib.reduce({
        "host": [["bench_window", w0, w1]], "modules": {},
        "ops": {d: [["busy", a, b] for a, b in v]
                for d, v in prog["busy"].items()}})
    named = program.idle_gaps_by_span(prog)
    assert [s for _, s in named] == [s for _, s in red["idle_gaps"]]
    assert [n for n, _ in named] == EXPECTED_NAMES
    share = program.share_by_name(named)
    assert sum(share.values()) == pytest.approx(100.0)
    assert share["engine.readback"] > 89


def test_cut_of_the_cut_keeps_the_form(prog):
    one = program.cut(prog, 1, skip=1)
    assert set(one) == set(prog)
    w0, w1 = one["window"]
    assert prog["window"][0] < w0 < w1 <= prog["window"][1]
    assert program.window_counters(one, "engine.step")["steps"] == 1
    assert len(program.window_spans(one, "engine.decode")) == 1
    assert all(w0 <= a <= b <= w1 for v in one["busy"].values() for a, b in v)


def test_innermost_span_by_hand():
    spans = [["engine.step", 0, 100, {}], ["engine.stage", 10, 20, {}],
             ["engine.sync_tables", 12, 18, {}], ["front.route", 100, 130, {}]]
    assert program._innermost(12, 17, spans) == "engine.sync_tables"
    assert program._innermost(9, 21, spans) == "engine.stage"
    assert program._innermost(30, 40, spans) == "engine.step"
    assert program._innermost(95, 135, spans) == "front.route"
    assert program._innermost(140, 150, spans) == "front"
