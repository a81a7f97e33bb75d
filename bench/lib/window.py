"""Window accounting in whole engine steps.

The proxy records every `engine.step()` (start, end, the tokens it
emitted); clients record when each streamed token arrived.  The window
opens at the end of the step before `open_step` and closes at the end of
the first step that ends `seconds` or more after it opened, so it holds
whole steps only: every token generated in it and all the time it took,
with no partial step at either edge.  Gaps are the clients': a gap counts
when its later token reached the client inside the window.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Step:
    index: int
    t0: float                              # host clock, step() entered
    t1: float                              # host clock, step() returned
    tokens: List[Tuple[str, int]]          # (request id, token index)


def close_step(steps: Sequence[Step], open_step: int, t_open: float,
               seconds: float) -> Optional[int]:
    """Index of the step that closes the window, or None while no step has
    ended `seconds` after `t_open`."""
    for s in steps[open_step:]:
        if s.t1 - t_open >= seconds:
            return s.index
    return None


def gaps(arrivals: Dict[str, Dict[int, float]], t_open: float,
         t_close: float) -> List[float]:
    """Client-side gaps (seconds) between consecutive tokens of a request,
    for every gap whose later token reached the client inside the window."""
    out = []
    for seen in arrivals.values():
        for i, t in seen.items():
            if i > 0 and (i - 1) in seen and t_open <= t <= t_close:
                out.append(t - seen[i - 1])
    return out


def summarize(steps: Sequence[Step], open_step: int, close: int,
              t_open: float, arrivals) -> dict:
    """The window's end-to-end numbers: tokens generated, its length, the
    rate, and the gap list with its 95th percentile."""
    win = steps[open_step:close + 1]
    n_tok = sum(len(s.tokens) for s in win)
    length = steps[close].t1 - t_open
    g = gaps(arrivals, t_open, steps[close].t1)
    return {"steps": len(win), "tokens": n_tok, "window_s": length,
            "tok_s": n_tok / length, "gaps": g,
            "itl_p95_ms": float(np.percentile(g, 95)) * 1e3 if g else None,
            "step_s": [s.t1 - s.t0 for s in win]}
