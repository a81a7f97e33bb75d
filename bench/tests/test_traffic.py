"""The traffic generator: the work is fixed by the file, the seed only
draws token ids and the slot assignment."""

import numpy as np
import pytest

from bench.lib import spec
from bench.lib import traffic as traffic_lib

SEEDS = (7, 2**33 + 5)


def _lengths(per_client):
    return sorted((len(s.prompt), s.max_new) for ss in per_client for s in ss)


@pytest.mark.parametrize("name", ["decode-long"])
def test_work_does_not_depend_on_the_seed(name):
    t = spec.data("traffic", name)
    traffic_lib.validate(t)
    a, b = (traffic_lib.sessions(t, s, 64000) for s in SEEDS)
    assert _lengths(a) == _lengths(b)
    # the schedule is the file's, whatever the seed
    assert t["groups"] == spec.data("traffic", name)["groups"]
    ids_a = np.concatenate([s.prompt for ss in a for s in ss])
    ids_b = np.concatenate([s.prompt for ss in b for s in ss])
    assert ids_a.shape != ids_b.shape or not np.array_equal(ids_a, ids_b)
    # every admission group prefills the same lengths, whatever the seed;
    # the seed only moves sessions among a group's clients (slots)
    start = 0
    for _, n in t["groups"]:
        ga = sorted(len(ss[0].prompt) for ss in a[start:start + n])
        gb = sorted(len(ss[0].prompt) for ss in b[start:start + n])
        assert ga == gb
        start += n
    assert [len(ss[0].prompt) for ss in a] != [len(ss[0].prompt) for ss in b]


def test_same_seed_same_sessions():
    t = spec.data("traffic", "decode-long")
    a, b = (traffic_lib.sessions(t, 123, 64000) for _ in range(2))
    for x, y in zip(a, b):
        for s, r in zip(x, y):
            assert np.array_equal(s.prompt, r.prompt) and s.max_new == r.max_new


def test_decode_long_mix_shape():
    t = spec.data("traffic", "decode-long")
    pairs = traffic_lib.pairs(t)
    prompts = sorted(p for p, _ in pairs)
    outs = sorted(o for _, o in pairs)
    assert len(pairs) == t["clients"] == 16
    # 8 page-aligned prompt lengths in 2048-3072, each used twice
    assert len(set(prompts)) == 8 and all(p % 64 == 0 for p in prompts)
    assert all(prompts.count(p) == 2 for p in prompts)
    assert 2048 <= prompts[0] and prompts[-1] <= 3072
    assert len(set(outs)) == 8 and 512 <= outs[0] and outs[-1] <= 1024
    # every session fits the engine's 3072 + 1024 = 4096 tokens per slot
    assert t["prompt_len"] + t["max_new"] == 4096


def test_deal_spreads_pairs_over_groups():
    t = {"clients": 7, "groups": [[0, 3], [1, 2], [2, 2]]}
    assert traffic_lib.deal(t) == [[0, 3, 6], [1, 4], [2, 5]]


def test_quantiles_are_log_uniform_midpoints():
    q = traffic_lib.quantiles(1, 100, 2)
    assert q == [3, 32]        # exp(ln 100 / 4), exp(3 ln 100 / 4)


@pytest.mark.parametrize("bad", [
    {"window_open_step": 3},
    {"groups": [[0, 8], [0, 8]]},
    {"groups": [[0, 4]]},
    {"prompt_len": 2048},
])
def test_validate_refuses(bad):
    t = {**spec.data("traffic", "decode-long"), **bad}
    with pytest.raises(ValueError):
        traffic_lib.validate(t)
