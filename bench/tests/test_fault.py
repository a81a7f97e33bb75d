"""A run whose served tokens are altered where they are produced comes out
not correct: the harness's whole run at a tiny size on the CPU (the look
for a chip skipped), with every sampled token changed."""

import time

import numpy as np

from bench.lib import cell, served
from bench.tests import tiny


def _run(monkeypatch, tmp_path, alter: bool):
    if alter:
        build = served.build_engine

        def altered(*a, **k):
            eng = build(*a, **k)
            sample = eng._sample
            vocab = tiny.MODEL["vocab_size"]

            def wrong(*args):
                out = np.array(sample(*args))
                out[0] = (out[0] + 1) % vocab     # every slot's token
                return out
            eng._sample = wrong
            return eng
        monkeypatch.setattr(served, "build_engine", altered)
    return cell.run(tiny.cell(tiny.LIMITS), 11, 0.2, False, lambda m: None,
                    time.perf_counter(), str(tmp_path))["result"]


def test_sound_run_is_correct(monkeypatch, tmp_path):
    r = _run(monkeypatch, tmp_path, alter=False)
    assert r["correct"], r["check"]


def test_altered_token_is_not_correct(monkeypatch, tmp_path):
    r = _run(monkeypatch, tmp_path, alter=True)
    assert not r["correct"]
    assert r["check"]["gap_max"]["value"] > r["check"]["gap_max"]["limit"]
