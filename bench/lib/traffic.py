"""The one traffic generator: turns a traffic file's parameters and a seed
into the sessions each client sends and the step schedule of set-up.

A traffic file (`bench/traffic/<name>.json`) holds only data:

    clients          closed-loop clients, one per decode slot
    prompt_len       the engine's prompt window (static shape)
    max_new          the engine's decode budget (static shape)
    prompt_lengths   {"lo", "hi", "n", "align"}: n log-uniform quantiles
                     over [lo, hi], each rounded to a multiple of `align`
    output_lengths   {"lo", "hi", "n"}: the same, for decode budgets
    sessions_per_client  how many sessions each client's list holds
    groups           [[step, clients], ...]: set-up admits that many
                     clients' first sessions at that engine step
    window_open_step the window opens at the end of the step before this
    check_requests   served sessions the correctness check compares

The (prompt length, output budget) pairs of each admission group, the
admission steps and the window's first step are fixed by the file.  The
seed only permutes the sessions among a group's clients (and so slots) and
draws the token ids, so neither set-up nor the window's work depends on
it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

MIN_TOKEN_ID = 2          # ids 0 and 1 are left to padding and specials


@dataclasses.dataclass(frozen=True)
class Session:
    client: int
    prompt: np.ndarray        # (prompt_len,) int32 token ids
    max_new: int


def quantiles(lo: float, hi: float, n: int, align: int = 1) -> List[int]:
    """n quantiles of a log-uniform distribution over [lo, hi] (the
    midpoints of n equal-mass bins), each rounded to a multiple of
    `align` and kept inside [lo, hi]."""
    out = []
    for i in range(n):
        x = math.exp(math.log(lo) + (i + 0.5) / n * (math.log(hi)
                                                    - math.log(lo)))
        x = int(round(x / align)) * align
        out.append(min(max(x, int(math.ceil(lo / align)) * align),
                       int(hi // align) * align))
    return out


def pairs(t: dict) -> List[tuple]:
    """The fixed multiset of (prompt length, output budget) pairs, one per
    client: each quantile used equally often, prompt quantile i paired
    with output quantile (3 i) mod n so long prompts do not all get long
    answers."""
    pl = t["prompt_lengths"]
    ol = t["output_lengths"]
    ps = quantiles(pl["lo"], pl["hi"], pl["n"], pl.get("align", 1))
    os_ = quantiles(ol["lo"], ol["hi"], ol["n"], ol.get("align", 1))
    n = t["clients"]
    return [(ps[i % len(ps)], os_[(3 * i) % len(os_)]) for i in range(n)]


def deal(t: dict) -> List[List[int]]:
    """Pair indices of each admission group: the pairs dealt round-robin
    over the groups (a full group is skipped), the same for every seed."""
    sizes = [n for _, n in t["groups"]]
    out: List[List[int]] = [[] for _ in sizes]
    g = 0
    for i in range(t["clients"]):
        while len(out[g]) == sizes[g]:
            g = (g + 1) % len(sizes)
        out[g].append(i)
        g = (g + 1) % len(sizes)
    return out


def sessions(t: dict, seed: int, vocab: int) -> List[List[Session]]:
    """Each client's list of sessions for `seed`.  Clients are numbered in
    admission order; each admission group holds the same pairs of lengths
    for every seed (`deal`), and the seed only permutes them among the
    group's clients, so every admission step prefills the same lengths.
    The token ids are drawn from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x7A1F])
    base = pairs(t)
    out: List[List[Session]] = [[] for _ in range(t["clients"])]
    for r in range(t["sessions_per_client"]):
        c = 0
        for group in deal(t):
            for k in rng.permutation(len(group)):
                plen, new = base[group[k]]
                ids = rng.integers(MIN_TOKEN_ID, vocab, size=plen,
                                   dtype=np.int64)
                out[c].append(Session(c, ids.astype(np.int32), int(new)))
                c += 1
    return out


def validate(t: dict) -> None:
    """Refuse a traffic file whose sessions cannot fit the engine's static
    shapes or whose schedule is out of order."""
    for length, new in pairs(t):
        if length > t["prompt_len"] or new > t["max_new"]:
            raise ValueError(f"session ({length}, {new}) exceeds the engine's"
                             f" prompt_len {t['prompt_len']} / max_new "
                             f"{t['max_new']}")
    steps = [s for s, _ in t["groups"]]
    if steps != sorted(steps) or len(set(steps)) != len(steps):
        raise ValueError(f"admission steps {steps} must increase")
    if sum(n for _, n in t["groups"]) != t["clients"]:
        raise ValueError("the admission groups must cover every client")
    if t["window_open_step"] <= steps[-1]:
        raise ValueError("the window must open after the last admission")
