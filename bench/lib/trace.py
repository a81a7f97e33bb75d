"""Reduction from a profiler trace to the window's device numbers.

`load` reads the `.xplane.pb` the JAX profiler wrote and keeps only what
the reduction needs, in a plain form (JSON-able, so a cut of a real chip
trace can be kept as test data):

    {"host":   [[name, start_ns, end_ns], ...]    bench spans only
     "ops":    {device: [[name, start_ns, end_ns], ...]}   "XLA Ops"
     "modules":{device: [[name, start_ns, end_ns], ...]}   "XLA Modules"}

`reduce` clips everything to the `bench_window` span and gives, averaged
over the devices: busy seconds (the union of op intervals), the window's
length, device time per op name, device time and count per program, and
the idle gaps, each named by the host span it fell in (`engine_step`, or
`front_loop` where no step was running).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List

HOST_SPANS = ("bench_window", "engine_step")
_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(name: str) -> str:
    """An op's or a program's short name: the HLO instruction's name
    without its text, `%` and numeric suffix (`%fusion.12 = bf16[...] ...`
    -> `fusion`), a program's without its hash (`jit_decode(123)` ->
    `jit_decode`)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = name.split("(", 1)[0]
    return _SUFFIX.sub("", name)


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"host": [], "ops": {}, "modules": {}, "lines": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            out["lines"][plane.name] = [l.name for l in plane.lines]
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key is None:
                    continue
                out[key].setdefault(plane.name, []).extend(
                    [e.name, float(e.start_ns), float(e.end_ns)]
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, float(e.start_ns), float(e.end_ns)]
                    for e in line.events if e.name in HOST_SPANS)
    return out


def _union(intervals: List[tuple]) -> List[tuple]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    wins = [(a, b) for n, a, b in tr["host"] if n == "bench_window"]
    if not wins:
        raise ValueError("the trace holds no bench_window span")
    w0, w1 = wins[0]
    steps = sorted((a, b) for n, a, b in tr["host"] if n == "engine_step")
    devices = sorted(tr["ops"])
    if not devices:
        raise ValueError("the trace holds no device ops")
    busy = 0.0
    ops: Dict[str, float] = {}
    mods: Dict[str, list] = {}
    gaps = []
    for dev in devices:
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in tr["ops"][dev]
                   if b > w0 and a < w1]
        for n, t in _self_times(clipped):
            ops[base_name(n)] = ops.get(base_name(n), 0.0) + t / 1e9
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged) / 1e9
        for n, a, b in tr["modules"].get(dev, []):
            if b > w0 and a < w1:
                m = mods.setdefault(base_name(n), [0, 0.0])
                m[0] += 1
                m[1] += (min(b, w1) - max(a, w0)) / 1e9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append([_attribute(a, b, steps), (b - a) / 1e9])
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / n,
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: [c / n, s / n] for k, (c, s) in mods.items()},
            "idle_gaps": gaps[:top], "devices": n}


def _self_times(events: List[tuple]) -> List[tuple]:
    """(name, self time): an op's duration less the ops nested in it (a
    loop or conditional holds the ops of its body on the same line)."""
    out, stack = [], []                  # stack of [name, start, end, child]
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and a >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += b - a
        stack.append([n, a, b, 0.0])
    out.extend((n, b - a - c) for n, a, b, c in stack)
    return out


def _attribute(a: float, b: float, steps: List[tuple]) -> str:
    """The host span an idle gap fell in: the one covering most of it."""
    inside = sum(max(0.0, min(b, s1) - max(a, s0)) for s0, s1 in steps)
    return "engine_step" if inside >= (b - a) / 2 else "front_loop"


def top_ops(red: dict, top: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(red["ops"].items(),
                                      key=lambda kv: -kv[1])[:top]]


def cut(tr: dict, n_steps: int) -> dict:
    """The first `n_steps` engine steps of the window, as a smaller trace
    with the same form (the window span shortened to end with them, names
    shortened)."""
    wins = [(a, b) for n, a, b in tr["host"] if n == "bench_window"]
    w0, _ = wins[0]
    steps = sorted((a, b) for n, a, b in tr["host"]
                   if n == "engine_step" and a >= w0)[:n_steps]
    w1 = steps[-1][1]
    keep = lambda evs: [[base_name(n), a, b] for n, a, b in evs
                        if b > w0 and a < w1]
    return {"host": [["bench_window", w0, w1]]
            + [["engine_step", a, b] for a, b in steps],
            "ops": {d: keep(v) for d, v in tr["ops"].items()},
            "modules": {d: keep(v) for d, v in tr["modules"].items()},
            "lines": tr.get("lines", {})}
