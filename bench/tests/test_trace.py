"""The trace reduction on a cut of a chip trace: two engine steps of
yi6b-decode-long's window on one TPU v5e (names shortened, times in ns)."""

import json

import pytest

from bench.lib import spec
from bench.lib import trace as trace_lib

CUT = spec.BENCH / "tests" / "data" / "trace_cut_yi6b.json"


@pytest.fixture(scope="module")
def red():
    return trace_lib.reduce(json.loads(CUT.read_text()))


def test_cut_is_small():
    assert CUT.stat().st_size <= 1 << 20


def test_window_busy_and_idle(red):
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(2.922871687, abs=1e-6)
    assert red["busy_s"] == pytest.approx(2.906561104, abs=1e-6)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(0.00558, abs=1e-4)


def test_self_times_add_up_to_busy(red):
    # ops nest (the layer loop holds its body's ops), so self times, not
    # durations, add up to the time the device was busy
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    top = trace_lib.top_ops(red)
    assert top[0][0] == "qattn_paged_segment"
    assert top[0][1] / red["busy_s"] > 0.9


def test_programs(red):
    count, seconds = red["modules"]["jit_decode"]
    assert count == 2
    assert seconds == pytest.approx(2.906491, abs=1e-5)


def test_gap_attribution(red):
    names = {n for n, _ in red["idle_gaps"]}
    assert names == {"engine_step", "front_loop"}
    assert red["idle_gaps"][0] == ["engine_step", pytest.approx(0.00436174)]
    assert all(a[1] >= b[1] for a, b in zip(red["idle_gaps"],
                                            red["idle_gaps"][1:]))


def test_self_times_by_hand():
    ev = [("loop", 0, 100), ("a", 10, 30), ("b", 40, 90), ("c", 50, 60),
          ("d", 120, 130)]
    got = dict(trace_lib._self_times(ev))
    assert got == {"loop": 100 - 20 - 50, "a": 20, "b": 50 - 10, "c": 10,
                   "d": 10}


@pytest.mark.parametrize("raw, short", [
    ("%fusion.197 = bf16[16,11008]{1,0} fusion(...)", "fusion"),
    ("%qattn_paged_segment.19 = (f32[16,4,8,128]) custom-call()",
     "qattn_paged_segment"),
    ("jit_decode(15544928193393661182)", "jit_decode"),
    ("%cond.1.clone.5 = (f32[16,4224]) conditional()", "cond.1.clone"),
])
def test_base_name(raw, short):
    assert trace_lib.base_name(raw) == short


def test_cut_keeps_the_form():
    tr = json.loads(CUT.read_text())
    one = trace_lib.cut(tr, 1)
    assert [n for n, _, _ in one["host"]] == ["bench_window", "engine_step"]
    red = trace_lib.reduce(one)
    assert red["modules"]["jit_decode"][0] == 1
    assert red["busy_s"] <= red["window_s"]
