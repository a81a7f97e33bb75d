"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; its configuration,
traffic mix, limits and metric readers are files under bench/ found by
name (bench/lib/spec.py).  Earlier lines on stderr give the set-up parts,
the window's step/probe/fold counts and the compiles inside it; the last
line on stdout is one JSON object: correct, attempted, failed, metrics,
device (and with --trace 1, breakdown), then `check`, each compared number
beside its limit, which also closes stderr.

Exits non-zero without a result line when JAX finds no TPU, or fewer chips
than the cell asks for, before anything is built.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK = ROOT / "bench" / ".runs"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import spec
    c = spec.cell(args.workload, ROOT)

    from bench.lib import chip
    chips = c["entry"]["chips"]
    devices = chip.devices(chips)
    if devices is None:
        import jax
        log(f"needs {chips} TPU chip(s); JAX found {jax.devices()}: "
            f"nothing was built")
        return 3
    cache_dir = chip.use_compile_cache(ROOT)
    log(f"{args.workload} seed {args.seed}: {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {cache_dir}")

    from bench.lib import cell
    WORK.mkdir(parents=True, exist_ok=True)
    out = cell.run(c, args.seed, args.seconds, bool(args.trace), log,
                   T_START, str(WORK))["result"]
    for name, v in out["check"].items():
        log(f"check {name}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
