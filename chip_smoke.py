"""Serve yi-6b on one TPU chip through the normal path and check the result.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 4 replicas behind the router vs 1

One process, no children.  The default run:

  1. refuses to start (exit 1, nothing built) unless JAX's first device is
     a TPU;
  2. builds the HTTP front exactly as `repro.launch.serve_http` does:
     yi-6b at its published widths with random weights from `--seed`, the
     paged free-list cache, the Pallas decode kernel, the ZipCache policy
     at the paper's fp_window=128 / recompress_interval=100, bound to port 0;
  3. warms it with one request, then sends two waves of seeded requests
     over localhost HTTP (1024-token prompts, 128 new tokens, so every slot
     folds its staging window; half SSE, half ``"stream": false``);
  4. checks that every request finished with its whole budget, that SSE
     tokens equal the terminal frame's and `result()`'s, and that no live
     row was sampled from non-finite logits;
  5. compares the compiled kernel (`paged_qattn.attend_paged`) with the
     gather reference (`kvc.attend_decode(cache.dense_view())`) on a
     snapshot of the live cache, and shows in the same run that the
     tolerance catches a wrong page and a missing segment.

`--four-chips` instead serves the same requests through 4 replicas (replica
i on chip i) behind `EngineRouter`, then through replica 0 alone, checks
that each replica's params and caches sit on their own chip, and that every
request got the same tokens both ways.  Nothing else runs.

Lines before the last are information.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "yi-6b"
SLOTS = 8
PROMPT_LEN = 1024
MAX_NEW = 128
WAVES = 2                      # waves of SLOTS requests after the warm-up
FOUR_CHIP_REQUESTS = 8
# Kernel vs gather reference, relative L2 error per (row, head).  Both sides
# contract in f32 (the kernel asks Precision.HIGHEST; the reference runs
# under default_matmul_precision("highest"), since the TPU's default would
# round f32 operands to bf16 and the CPU's does not), so the f32 softmax
# weights differ only by the flash merge's reassociation and the two exp
# implementations (2.8e-7 measured on a v5e).  The attention output is
# returned in the served bf16, whose rounding alone moves it by up to
# 2**-8 relative.  Each tolerance sits an order of magnitude above
# its noise; the run shows that a wrong page and a missing segment each
# exceed at least one of them.
TOL = {"out": 1e-2, "weights": 1e-4}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def engine_argv(seed: int, replicas: int) -> list:
    return ["--arch", ARCH, "--backend", "paged",
            "--page-allocator", "freelist", "--paged-kernel", "on",
            "--policy", "zipcache", "--batch", str(SLOTS),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--seed", str(seed), "--port", "0",
            "--replicas", str(replicas)]


def build_front(seed: int, replicas: int):
    from repro.launch import serve as serve_cli
    from repro.launch import serve_http
    ap = serve_http.build_parser()
    args = ap.parse_args(engine_argv(seed, replicas))
    serve_cli.validate_engine_args(args, ap, continuous=True)
    return serve_http.build_frontend(args)


def prompts(seed: int, n: int, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=(PROMPT_LEN,)).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# HTTP client
# ---------------------------------------------------------------------------

async def generate(port: int, tokens: list, stream: bool, first=None):
    """POST /v1/generate; returns (streamed tokens or None, final payload).
    `first` (an asyncio.Event) is set when the first SSE token arrives, or
    when the request ends without one."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps({"tokens": tokens, "max_new_tokens": MAX_NEW,
                       "stream": stream}).encode()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: localhost\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    try:
        status = (await reader.readline()).decode().strip()
        while (await reader.readline()) not in (b"\r\n", b""):
            pass
        if not status.endswith("200 OK"):
            raise RuntimeError(f"{status}: {(await reader.read()).decode()}")
        if not stream:
            return None, json.loads(await reader.read())
        streamed, event = [], None
        while True:
            line = (await reader.readline()).decode().strip()
            if not line:
                if reader.at_eof():
                    raise RuntimeError("SSE stream ended without a done frame")
                continue
            if line.startswith("event: "):
                event = line[len("event: "):]
                continue
            data = json.loads(line[len("data: "):])
            if event == "error":
                raise RuntimeError(f"engine failed: {data}")
            if event == "done":
                return streamed, data
            streamed.append(data["token"])
            if first is not None:
                first.set()
    finally:
        writer.close()
        if first is not None:
            first.set()          # a request that failed early releases too


def check_outputs(engine, results) -> int:
    """Every request finished with its whole budget; SSE tokens == the done
    frame's == result().  Returns the number of tokens served."""
    n = 0
    for streamed, final in results:
        out = engine.result(final["id"])
        want = [int(t) for t in out.tokens]
        if final["tokens"] != want:
            raise AssertionError(f"{final['id']}: response tokens differ "
                                 "from result()")
        if streamed is not None and streamed != want:
            raise AssertionError(f"{final['id']}: SSE tokens differ from "
                                 "result()")
        if final["finish_reason"] != "length" or len(want) != MAX_NEW:
            raise AssertionError(
                f"{final['id']}: finished {final['finish_reason']!r} after "
                f"{len(want)} of {MAX_NEW} tokens")
        n += len(want)
    return n


# ---------------------------------------------------------------------------
# kernel vs gather reference on the live cache
# ---------------------------------------------------------------------------

def _rel_err(got, want, rows):
    """Max relative L2 error over live rows (and heads), last axis."""
    got = np.asarray(got, np.float64)[rows]
    want = np.asarray(want, np.float64)[rows]
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.max(num / den))


def _errors(got, want, rows) -> dict:
    return {"out": _rel_err(got.out, want.out, rows),
            "weights": _rel_err(got.slot_weights, want.slot_weights, rows)}


def check_kernel(caches, cfg, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import kvcache as kvc
    from repro.kernels import paged_qattn

    stacked = caches["groups"]["sub0"]            # PagedKVCache, (layers, ...)
    n_layers = int(stacked.length.shape[0])
    layers = sorted({0, n_layers // 2, n_layers - 1})
    rng = np.random.default_rng(seed)
    worst = dict.fromkeys(TOL, 0.0)
    # per injected fault: how many times its error exceeds the tolerance
    # (the larger of the two metrics; the least over the layers checked)
    over_tol = {"wrong_page": np.inf, "missing_window": np.inf}
    for layer in layers:
        c = jax.tree_util.tree_map(lambda x: x[layer], stacked)
        b = int(c.length.shape[0])
        rows = np.asarray(c.length) > 0
        if not rows.any():
            raise AssertionError("the cache snapshot holds no live slot")
        q = jnp.asarray(rng.standard_normal((b, cfg.n_heads, cfg.hd)),
                        jnp.bfloat16)
        got = paged_qattn.attend_paged(q, c)
        # faults the check must catch: one page of the lo store read from
        # the wrong physical page, and the staging-window segment missing
        lo_t = c.lo.table.at[:, 0].set(c.lo.table[:, 1])
        faults = {"wrong_page": dataclasses.replace(
                      c, lo=dataclasses.replace(c.lo, table=lo_t)),
                  "missing_window": dataclasses.replace(
                      c, win_pos=jnp.full_like(c.win_pos, -1))}
        with jax.default_matmul_precision("highest"):
            want = kvc.attend_decode(q, c.dense_view())
            faulty = {k: kvc.attend_decode(q, f.dense_view())
                      for k, f in faults.items()}
        for k, e in _errors(got, want, rows).items():
            worst[k] = max(worst[k], e)
        for name, res in faulty.items():
            e = _errors(res, want, rows)
            over_tol[name] = min(over_tol[name],
                                 max(e[k] / TOL[k] for k in TOL))
    if any(worst[k] > TOL[k] for k in TOL):
        raise AssertionError(f"kernel vs gather reference: {worst} exceeds "
                             f"{TOL}")
    if min(over_tol.values()) <= 1.0:
        raise AssertionError(f"the tolerances {TOL} miss an injected fault: "
                             f"{over_tol}")
    return {"max_rel_err": worst, "tol": TOL, "fault_over_tol": over_tol,
            "layers_checked": layers}


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

async def _snapshot_late(engine, min_generated: int, box: dict) -> None:
    """Keep the cache tree of the first step at which every slot is live
    and has generated >= min_generated tokens (past its first fold, staging
    window non-empty).  Device arrays are immutable and the engine donates
    nothing, so the snapshot stays valid after the engine moves on."""
    while "caches" not in box:
        slots = engine.slots
        if all(s is not None for s in slots) and \
                min(len(s.generated) for s in slots) >= min_generated:
            box["caches"] = engine.caches
            return
        await asyncio.sleep(0.002)


async def single_chip(seed: int) -> None:
    import jax

    from repro.runtime import compile_guard

    t0 = time.perf_counter()
    front = build_front(seed, replicas=1)
    eng = front.engine
    cfg = eng.cfg
    log(f"built {cfg.name}: {cfg.n_layers} of {cfg.n_layers} layers (no "
        f"depth cut), d_model {cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} "
        f"heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}; fp_window "
        f"{eng.ccfg.fp_window}, recompress_interval "
        f"{eng.ccfg.recompress_interval}; {time.perf_counter() - t0:.1f}s")
    await front.start()
    try:
        reqs = prompts(seed, 1 + WAVES * SLOTS, cfg.vocab)
        with compile_guard.count_compiles() as warm:
            t = time.perf_counter()
            check_outputs(eng, [await generate(front.port, reqs[0], True)])
        log(f"warm-up request: {time.perf_counter() - t:.1f}s, {warm.count} "
            f"compiles taking {warm.seconds:.1f}s")
        for w in range(WAVES):
            batch = reqs[1 + w * SLOTS: 1 + (w + 1) * SLOTS]
            box = {}
            with compile_guard.count_compiles() as win:
                t = time.perf_counter()
                snap = asyncio.create_task(
                    _snapshot_late(eng, MAX_NEW - 10, box))
                results = await asyncio.gather(*[
                    generate(front.port, p, stream=i % 2 == 0)
                    for i, p in enumerate(batch)])
                wall = time.perf_counter() - t
                snap.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await snap
            n = check_outputs(eng, results)
            dec = [eng.result(f["id"]).timings["decode_s"]
                   for _, f in results]
            log(f"wave {w + 1}: {len(batch)} requests, {n} tokens in "
                f"{wall:.2f}s; decode {(n - len(batch)) / max(dec):.1f} tok/s "
                f"over {SLOTS} slots; {win.count} compiles in the window"
                + (f" ({win.seconds:.1f}s)" if win.count else ""))
        if eng.nonfinite_logits:
            raise AssertionError(f"{eng.nonfinite_logits} live rows were "
                                 "sampled from non-finite logits")
        log("no live row was sampled from non-finite logits")
        if "caches" not in box:
            raise AssertionError("no cache snapshot was taken mid-decode")
        log(f"kernel vs gather reference on the live cache: "
            f"{json.dumps(check_kernel(box['caches'], cfg, seed))}")
        log(f"pool_stats: {json.dumps(eng.pool_stats())}")
        stats = jax.devices()[0].memory_stats() or {}
        log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    finally:
        await front.stop()


async def four_chips(seed: int) -> None:
    import jax

    from repro.serving.http import HttpFrontend

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    front = build_front(seed, replicas=4)
    router = front.engine

    def check_placement():
        for i, eng in enumerate(router.replicas):
            for what, tree in (("params", eng.params),
                               ("caches", eng.caches)):
                placed = {d for x in jax.tree_util.tree_leaves(tree)
                          for d in x.devices()}
                if placed != {devices[i]}:
                    raise AssertionError(f"replica {i} {what} on {placed}, "
                                         f"expected {devices[i]}")

    check_placement()
    reqs = prompts(seed, FOUR_CHIP_REQUESTS, router.replicas[0].cfg.vocab)

    async def serve_all(front):
        # staggered admissions: each request enters once the one before it
        # streams, so both layouts fold one slot at a time (same programs)
        await front.start()
        try:
            tasks = []
            for p in reqs:
                first = asyncio.Event()
                tasks.append(asyncio.create_task(
                    generate(front.port, p, True, first)))
                await first.wait()
            results = await asyncio.gather(*tasks)
        finally:
            await front.stop(drain=False)
        check_outputs(front.engine, results)
        return [final["tokens"] for _, final in results], \
            [final["id"] for _, final in results]

    t = time.perf_counter()
    routed, ids = await serve_all(front)
    check_placement()            # still there after serving
    log(f"replica i's params and caches on chip i: "
        f"{[str(d) for d in devices[:4]]}")
    log(f"router over 4 replicas: {len(ids)} requests placed on "
        f"{sorted({i.split('/')[0] for i in ids})}, "
        f"{time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    alone, _ = await serve_all(HttpFrontend(router.replicas[0]))
    log(f"replica 0 alone: {time.perf_counter() - t:.1f}s")
    if routed != alone:
        bad = [i for i, (a, b) in enumerate(zip(routed, alone)) if a != b]
        raise AssertionError(f"requests {bad} got different tokens through "
                             "4 replicas than through 1")
    log(f"all {len(reqs)} requests: same tokens through 4 replicas as "
        "through 1")
    if any(eng.nonfinite_logits for eng in router.replicas):
        raise AssertionError("a live row was sampled from non-finite logits")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="4 replicas behind the router vs 1, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's first device is "
              f"{devices[0].platform}; nothing was built", file=sys.stderr)
        return 1

    from repro.runtime import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    run = four_chips if args.four_chips else single_chip
    asyncio.run(run(args.seed))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
