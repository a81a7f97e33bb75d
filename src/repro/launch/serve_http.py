"""HTTP/SSE serving driver: the network-facing twin of `launch.serve`.

Example (CPU smoke)::

  PYTHONPATH=src python -m repro.launch.serve_http --arch yi-6b --smoke \
      --batch 2 --prompt-len 48 --max-new 16 --port 8080

Then, from any HTTP client::

  curl -N -X POST http://127.0.0.1:8080/v1/generate \
      -d '{"tokens": [12, 7, 93], "max_new_tokens": 8}'

streams one SSE ``data: {"token": ..., "index": ...}`` event per decoded
token (the concatenation is bitwise the engine's `result(rid).tokens`),
and hanging up the connection cancels the request — its slot and pages
come back within one step (`GET /v1/stats` shows the pools).

Engine flags are `launch.serve`'s, shared via `serve.add_engine_args` so
the two CLIs cannot drift (the conformance-axes lint checks that sharing).
The HTTP front is always the continuous engine — there is no lockstep
HTTP mode — so the `--continuous`-gated combinations are simply valid
here.

``--replicas N`` runs N engine replicas behind the least-loaded
`serving.router.EngineRouter` (session affinity via the request's
``"session"`` field); replica i lives on ``jax.devices()[i]`` with its own
params, slots and page pools, and `GET /v1/stats` reports per-replica
load.  N may not exceed the device count (on the CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` provides N).
"""

from __future__ import annotations

import argparse
import asyncio

import jax

from repro import configs
from repro.launch import serve as serve_cli
from repro.models import registry
from repro.runtime import compile_cache
from repro.serving import ContinuousEngine, EngineRouter
from repro.serving.http import HttpFrontend


def build_frontend(args) -> HttpFrontend:
    """Engine replica(s) + router + HTTP front from parsed args (the
    testable seam: tests build the front without binding a real port)."""
    cfg = configs.get_arch(args.arch, smoke=args.smoke)
    ccfg = serve_cli.build_compression_config(args)
    scfg = serve_cli.build_serve_config(args)
    devices = jax.devices()
    if args.replicas > len(devices):
        raise ValueError(f"--replicas {args.replicas} needs as many devices; "
                         f"{len(devices)} available")
    replicas = []
    for device in devices[:args.replicas]:
        params = registry.materialize_params(cfg, args.seed, device=device)
        replicas.append(ContinuousEngine(cfg, ccfg, scfg, params,
                                         device=device))
    engine = (replicas[0] if args.replicas == 1
              else EngineRouter(replicas))
    return HttpFrontend(engine, host=args.host, port=args.port)


async def serve(args) -> None:
    front = build_frontend(args)
    await front.start()
    print(f"[serve_http] listening on http://{front.host}:{front.port} "
          f"({args.replicas} replica(s), arch={args.arch})")
    try:
        await front.wait()       # until interrupted or an engine step fails
    finally:
        await front.stop()       # raises a failed step's exception


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    serve_cli.add_engine_args(ap)
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for the HTTP server")
    ap.add_argument("--port", type=int, default=8080,
                    help="TCP port (0 = pick a free one and print it)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the least-loaded router; "
                         "each owns its own slots and page pools")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    # the HTTP front always drives the continuous engine
    serve_cli.validate_engine_args(args, ap, continuous=True)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    compile_cache.enable()
    try:
        asyncio.run(serve(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
