"""Window accounting on a synthetic stream of steps and token arrivals."""

import pytest

from bench.lib import window as window_lib

Step = window_lib.Step


def _stream():
    # 6 steps of 1 s each starting at t=10; two requests, one token per
    # step each; clients see each token 0.25 s after its step ends
    steps, arrivals = [], {"a": {}, "b": {}}
    for k in range(6):
        t0 = 10.0 + k
        steps.append(Step(k, t0, t0 + 0.9, [("a", k), ("b", k)]))
        arrivals["a"][k] = t0 + 0.9 + 0.25
        arrivals["b"][k] = t0 + 0.9 + 0.5
    return steps, arrivals


def test_window_counts_whole_steps():
    steps, arrivals = _stream()
    t_open = steps[1].t1                      # end of the step before 2
    close = window_lib.close_step(steps, 2, t_open, 2.5)
    assert close == 4                         # 3.0 s >= 2.5 s at step 4
    w = window_lib.summarize(steps, 2, close, t_open, arrivals)
    assert w["steps"] == 3 and w["tokens"] == 6
    assert w["window_s"] == pytest.approx(3.0)
    assert w["tok_s"] == pytest.approx(2.0)
    assert w["step_s"] == pytest.approx([0.9, 0.9, 0.9])


def test_gaps_by_arrival_inside_the_window():
    steps, arrivals = _stream()
    t_open, t_close = steps[1].t1, steps[4].t1          # 11.9 .. 14.9
    g = window_lib.gaps(arrivals, t_open, t_close)
    # a: tokens 1..3 arrive at 12.15, 13.15, 14.15; b: 1..3 at 12.4 ..14.4
    assert sorted(g) == pytest.approx([1.0] * 6)
    w = window_lib.summarize(steps, 2, 4, t_open, arrivals)
    assert w["itl_p95_ms"] == pytest.approx(1000.0)


def test_p95_of_a_hand_made_gap_list():
    arrivals = {"r": {0: 0.0, 1: 1.0, 2: 3.0, 3: 3.5}}
    g = window_lib.gaps(arrivals, 0.5, 10.0)
    assert sorted(g) == [0.5, 1.0, 2.0]


def test_no_close_before_the_length():
    steps, _ = _stream()
    assert window_lib.close_step(steps, 2, steps[1].t1, 100.0) is None
