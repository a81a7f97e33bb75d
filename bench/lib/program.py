"""The serving program's own spans and counters, read from a traced run.

The program writes host spans (`repro.runtime.tracing`) into the profiler
trace that a `--trace 1` run captures, on the device ops' clock: `engine.*`
inside `EngineCore.step`, `front.*` in the HTTP front, `host.gc` around
Python's collections.  `engine.step` carries the engine's counters as they
stand when the step begins, and `front.route` the front's as they stand
when it routes a step's events.  So a counter's value at an instant is the
stat of the first such span that starts at or after it, and a counter's
window difference is its value at the window's close less its value at its
opening: the same numbers a snapshot of `counters()` at the window's edges
gives.

`load` reads the `.xplane.pb` and keeps, in a plain JSON-able form (so a
cut of a chip trace can be kept as test data):

    {"window": [start_ns, end_ns]                   the bench_window span
     "spans":  [[name, start_ns, end_ns, {stat: value}], ...]  by start
     "busy":   {device: [[start_ns, end_ns], ...]}  union of its op intervals}

`of(run)` gives a metric reader the run's program spans, or None where
there are none (a program without spans, or a run without a trace).
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
from typing import Dict, List, Optional

from . import spec
from . import trace as trace_lib

PREFIXES = ("engine.", "front.", "host.")
# where bench/run.py's traced run leaves its trace while the readers run
TRACE_DIR = spec.BENCH / ".runs" / "trace"


def load(trace_dir: str) -> Optional[dict]:
    """The program's spans and the device's busy intervals in the trace
    under `trace_dir`; None where it holds no program span."""
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        return None
    return _load_file(paths[-1], os.stat(paths[-1]).st_mtime_ns)


@functools.lru_cache(maxsize=1)            # the three readers share one load
def _load_file(path: str, mtime_ns: int) -> Optional[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans, busy = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy.setdefault(plane.name, []).extend(
                        (float(e.start_ns), float(e.end_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench_window" and window is None:
                        window = [float(e.start_ns), float(e.end_ns)]
                    elif e.name.startswith(PREFIXES):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.end_ns), dict(e.stats)])
    if window is None or not spans:
        return None
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"window": window, "spans": spans,
            "busy": {d: [list(ab) for ab in trace_lib._union(v)]
                     for d, v in busy.items()}}


def of(run: dict) -> Optional[dict]:
    """The run's program spans: `run["program"]` where the harness passes
    them, else the traced run's own trace, once its window is seen to be
    the one `run["trace"]` was reduced from; None where there are none."""
    if "program" in run:
        return run["program"]
    red = run.get("trace")
    if red is None:
        return None
    prog = load(TRACE_DIR)
    if prog is None:
        return None
    w0, w1 = prog["window"]
    if abs((w1 - w0) / 1e9 - red["window_s"]) > 1e-9:
        return None                     # another run's trace
    return prog


def counters_at(prog: dict, name: str, t: float) -> Optional[Dict]:
    """The counters carried by the first `name` span that starts at or
    after `t`."""
    for n, a, _, stats in prog["spans"]:
        if n == name and a >= t:
            return stats
    return None


def window_counters(prog: dict, name: str) -> Optional[Dict[str, float]]:
    """Each stat `name` spans carry, at the window's close less at its
    opening: the counters' window differences (`front.route`'s `events`,
    its own step's count, differences to nothing meaningful)."""
    w0, w1 = prog["window"]
    a, b = counters_at(prog, name, w0), counters_at(prog, name, w1)
    if a is None or b is None:
        return None
    return {k: b[k] - a[k] for k in b if k in a}


def window_spans(prog: dict, name: str) -> List[list]:
    w0, w1 = prog["window"]
    return [s for s in prog["spans"]
            if s[0] == name and s[1] >= w0 and s[2] <= w1]


def host_gaps_ns(prog: dict) -> List[float]:
    """Per pair of consecutive window steps: from the end of step k's
    `engine.readback` to the start of step k+1's `engine.decode`."""
    starts = [s[1] for s in window_spans(prog, "engine.decode")]
    out = []
    for _, _, end, _ in window_spans(prog, "engine.readback"):
        nxt = next((a for a in starts if a >= end), None)
        if nxt is not None:
            out.append(nxt - end)
    return out


def median_host_gap_ms(prog: dict) -> Optional[float]:
    gaps = host_gaps_ns(prog)
    return statistics.median(gaps) / 1e6 if gaps else None


def _innermost(a: float, b: float, spans: List[list]) -> str:
    """The shortest program span that covers more than half of [a, b], or
    `front` where none does."""
    best = None
    for n, s0, s1, _ in spans:
        if s0 >= b:
            break
        if min(b, s1) - max(a, s0) > (b - a) / 2 and (
                best is None or s1 - s0 < best[1]):
            best = (n, s1 - s0)
    return best[0] if best else "front"


def idle_gaps_by_span(prog: dict, top: int = 10) -> List[list]:
    """The device's idle gaps in the window, longest first, as
    `trace.reduce` lists them under `idle_gaps` (the same gaps, the same
    order), each named by the innermost program span covering most of it."""
    w0, w1 = prog["window"]
    gaps = []
    for dev in sorted(prog["busy"]):
        merged = [(max(a, w0), min(b, w1)) for a, b in prog["busy"][dev]
                  if b > w0 and a < w1]
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: -(g[1] - g[0]) / 1e9)
    return [[_innermost(a, b, prog["spans"]), (b - a) / 1e9]
            for a, b in gaps[:top]]


def share_by_name(gaps: List[list]) -> Dict[str, float]:
    """Each name's share of the listed gap time, in percent."""
    total = sum(s for _, s in gaps)
    out: Dict[str, float] = {}
    for n, s in gaps:
        out[n] = out.get(n, 0.0) + 100.0 * s / total
    return out


def cut(prog: dict, n_steps: int, skip: int = 0) -> dict:
    """Engine steps skip+1 .. skip+n_steps of the window as a smaller trace
    of the same form: the window opened at the end of step `skip` (at the
    original opening for 0) and closed at the end of the last kept step,
    the spans that start in it, the first `engine.step` and `front.route`
    after it (their counters close it), the busy intervals clipped to it."""
    w0, w1 = prog["window"]
    steps = [s for s in prog["spans"]
             if s[0] == "engine.step" and s[1] >= w0]
    c0 = steps[skip - 1][2] if skip else w0
    c1 = steps[skip + n_steps - 1][2]
    spans = [s for s in prog["spans"] if c0 <= s[1] < c1]
    for name in ("engine.step", "front.route"):
        spans += [next(s for s in prog["spans"]
                       if s[0] == name and s[1] >= c1)]
    spans.sort(key=lambda s: (s[1], -s[2]))
    busy = {d: [[max(a, c0), min(b, c1)] for a, b in v if b > c0 and a < c1]
            for d, v in prog["busy"].items()}
    return {"window": [c0, c1], "spans": spans, "busy": busy}
