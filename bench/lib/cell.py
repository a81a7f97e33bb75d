"""One run of one cell, in this process: set-up, window, the check, and the
metrics, as the result line reports them."""

from __future__ import annotations

import asyncio
import os
import shutil
import time

import jax
import numpy as np

from . import check as check_lib
from . import peaks as peaks_lib
from . import served, spec
from . import trace as trace_lib
from . import traffic as traffic_lib
from . import weights as weights_lib


def _policy_matches(engine, policy: dict) -> None:
    c = engine.ccfg
    got = {"high_bits": c.high_bits, "low_bits": c.low_bits,
           "saliency_ratio": c.saliency_ratio, "fp_window": c.fp_window,
           "recompress_interval": c.recompress_interval}
    if got != {k: policy[k] for k in got}:
        raise ValueError(f"the engine runs policy {got}, the configuration "
                         f"file states {policy}")


def schedule(res: dict) -> dict:
    """Per request: admission step and prompt length, from what the proxy
    and the clients saw."""
    first = {}
    for s in res["steps"]:
        for rid, i in s.tokens:
            if i == 0:
                first[rid] = s.index
    return {r["rid"]: (first[r["rid"]], len(r["prompt"]))
            for r in res["served"] if r["rid"] in first}


def window_work(res: dict, c: dict) -> dict:
    """FLOPs/bytes of each of the adapter's kernels and model FLOPs of the
    window's steps, from the live context of each slot (prompt, tokens
    decoded, window fill)."""
    policy = c["config"]["policy"]
    model = c["config"]["model"]
    interval = policy["recompress_interval"]
    sched = schedule(res)
    kmods = {k: spec.module("counts", k) for k in
             spec.module("adapters", c["config"]["reference"]).KERNELS}
    mmod = spec.module("counts", c["config"]["reference"])
    kernels = {k: {"flops": 0, "bytes": 0, "op_names": list(m.OP_NAMES)}
               for k, m in kmods.items()}
    mf = 0
    folds = 0
    for s in res["window_steps"]:
        slots = []
        for rid, _ in s.tokens:
            a, p = sched[rid]
            j = s.index - a                        # decode steps before this
            ctx = p + j + 1                        # attended this step
            win = j % interval + 1
            slots.append((ctx - win, win))
            mf += mmod.token_flops(model, ctx)
            folds += win == interval
        for k, m in kmods.items():
            f, b = m.step(model, policy, slots)
            kernels[k]["flops"] += f
            kernels[k]["bytes"] += b
    return {"kernels": kernels, "model_flops": mf, "folds": folds}


def probe_steps(res: dict, c: dict) -> int:
    """Window steps on which some row probed (the program's own schedule,
    keyed on each request's decode counter and the fixed serve seed)."""
    from repro.serving.engine import probe_flag
    policy = c["config"]["policy"]
    sched = schedule(res)
    seed = c["config"]["program"]["serve_seed"]
    n = 0
    for s in res["window_steps"]:
        n += any(probe_flag(s.index - sched[rid][0],
                            policy["recompress_interval"], seed)
                 for rid, _ in s.tokens)
    return n


def run(c: dict, seed: int, seconds: float, trace: bool, log, t_start: float,
        work_dir: str, control: bool = False) -> dict:
    """One run: {"result": the result line, "readings": the compared
    numbers (and the control's, with `control`), "res": what the window
    recorded}."""
    os.makedirs(work_dir, exist_ok=True)
    device = jax.devices()[0]
    model = c["config"]["model"]
    traffic = c["traffic"]
    traffic_lib.validate(traffic)
    arch = spec.module("adapters", c["config"]["reference"])
    t = time.perf_counter()
    w = weights_lib.make(arch.leaves(model), seed, device)
    jax.block_until_ready(w)
    log(f"set-up: weights on the device from the seed: "
        f"{time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine = served.build_engine(c["config"], traffic,
                                 arch.to_program(w, model), device)
    _policy_matches(engine, c["config"]["policy"])
    log(f"set-up: engine build: {time.perf_counter() - t:.2f}s")
    sessions = traffic_lib.sessions(traffic, seed, model["vocab_size"])
    trace_dir = None
    if trace:
        trace_dir = os.path.join(work_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = asyncio.run(served.serve_window(engine, traffic, sessions, seconds,
                                          trace_dir, log))
    setup_s = res["t_open"] - t_start
    res["window_steps"] = res["steps"][res["open_step"]:res["close_step"] + 1]
    win = res["window"]
    log(f"set-up total: {setup_s:.2f}s")
    log(f"window: {win['steps']} steps, {win['tokens']} tokens, "
        f"{win['window_s']:.3f}s; step s min/median/max "
        f"{min(win['step_s']):.4f}/{float(np.median(win['step_s'])):.4f}/"
        f"{max(win['step_s']):.4f}; {len(win['gaps'])} client gaps, "
        f"p50/p95 {np.percentile(win['gaps'], 50):.4f}/"
        f"{np.percentile(win['gaps'], 95):.4f}s")
    work = window_work(res, c)
    log(f"window: {probe_steps(res, c)} probe steps, {work['folds']} slot "
        f"folds; {res['compiles']} compiles, {res['cache_requests']} "
        f"compile-cache lookups ({res['cache_hits']} hits) inside it"
        + (f": {res['compile_names']}" if res["compiles"] else ""))
    served.free_engine(engine)
    del engine

    ref = spec.module("refs", c["config"]["reference"])
    chosen = check_lib.sample(res["served"], traffic["check_requests"], seed)
    seq_len = traffic["prompt_len"] + traffic["max_new"]
    t = time.perf_counter()
    holes = [r["rid"] for r in res["served"] if not r["complete"]]
    interval = c["config"]["policy"]["recompress_interval"]
    last = [len(r["tokens"]) - 1 for r in chosen]
    early = check_lib.before_first_fold(chosen, interval)
    log(f"check: compared sessions end at token index {min(last)}-"
        f"{max(last)}; the first token read after a slot's first fold is "
        f"index {check_lib.first_token_after_fold(interval)}")
    read = check_lib.readings(ref, w, model, chosen, seq_len)
    if control:
        read["control"] = check_lib.readings(ref, w, model, chosen, seq_len,
                                             control=True)
    ok, shown = check_lib.judge(read, c["limits"])
    shown["sessions_before_first_fold"] = {"value": early, "limit": 0}
    if early:
        ok = False
    if holes:
        ok = False
        shown["sessions_with_missing_tokens"] = {"value": len(holes),
                                                 "limit": 0}
    if res["failed"]:
        ok = False
    log(f"check: {read['sessions']} sessions, {read['tokens']} served "
        f"tokens through the reference in {time.perf_counter() - t:.2f}s")

    kind = device.device_kind
    out = {"correct": ok, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {},
           "device": {"platform": device.platform, "kind": kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": res["peak_bytes"]}}
    run_view = {"window": win, "window_steps": res["window_steps"],
                "traffic": traffic, "config": c["config"],
                "cache_bytes": res["cache_bytes"],
                "context_tokens": res["context_tokens"],
                "kv_values_per_token": arch.kv_values_per_token(model),
                "kernels": work["kernels"],
                "model_flops": work["model_flops"], "trace": None,
                "peaks": None}
    if not trace:
        e2e = {"tok_s": win["tok_s"], "itl_p95_ms": win["itl_p95_ms"],
               "peak_hbm_gib": res["peak_bytes"] / 2**30, "setup_s": setup_s}
        for m in c["end_to_end"]:
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    else:
        tr = trace_lib.load(trace_dir)
        red = trace_lib.reduce(tr)
        run_view["trace"] = red
        run_view["peaks"] = peaks_lib.of(kind)
        out["device"]["busy_s"] = red["busy_s"]
        out["device"]["window_s"] = red["window_s"]
        for m in c["per_layer"]:
            v = spec.module("metrics", m["name"]).read(run_view)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": trace_lib.top_ops(red),
                            "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["check"] = shown
    return {"result": out, "readings": read, "res": res}
