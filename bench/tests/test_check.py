"""The check covers each compared session's first fold, and the harness
waits for the clients to hold every token the engine generated."""

import numpy as np
import pytest

from bench.lib import check, served
from bench.lib import window as window_lib


def test_first_token_after_fold():
    # token 0 from prefill, decodes emit 1..100, the fold follows token 100
    assert check.first_token_after_fold(100) == 101


@pytest.mark.parametrize("n_tokens, early", [
    (1, 1), (101, 1), (102, 0), (140, 0)])
def test_before_first_fold(n_tokens, early):
    chosen = [{"tokens": [0] * n_tokens}, {"tokens": [0] * 200}]
    assert check.before_first_fold(chosen, 100) == early


def _record():
    prompts = [np.arange(5, dtype=np.int32), np.arange(7, dtype=np.int32)]
    sessions = [type("S", (), {"prompt": p})() for p in prompts]
    steps = [window_lib.Step(0, 0.0, 1.0, [("a", 0), ("b", 0)]),
             window_lib.Step(1, 1.0, 2.0, [("a", 1), ("b", 1)]),
             window_lib.Step(2, 2.0, 3.0, [("a", 2)])]
    rid_of = {prompts[0].tobytes(): "a", prompts[1].tobytes(): "b"}
    return sessions, steps, rid_of


def test_delivered_waits_for_every_generated_token():
    sessions, steps, rid_of = _record()
    last = served._last_index(steps)
    assert last == {"a": 2, "b": 1}
    got_a = {0: (1, 0.0), 1: (2, 0.0)}
    got_b = {0: (3, 0.0), 1: (4, 0.0)}
    record = [(sessions[0], got_a), (sessions[1], got_b)]
    assert not served._delivered(record, rid_of, last)
    got_a[2] = (5, 0.0)
    assert served._delivered(record, rid_of, last)
