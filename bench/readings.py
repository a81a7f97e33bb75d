"""Readings that set a cell's limits: the program's compared numbers over
many seeds, and the control's (the reference one precision lower, in the
program's place) on some of them, all in one process on the chip.  The
control is judged against the cell's limits as a run's program is
(`control_correct`).

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 --control-seeds 11,12

Each seed runs the cell's whole set-up and a window of `--seconds`, as
`bench/run.py` does, then the check; with a control seed the control is
read at the same positions of the same served tokens.  One JSON line per
seed on stdout.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import cell, check, chip, spec
    c = spec.cell(args.workload, ROOT)
    if chip.devices(c["entry"]["chips"]) is None:
        print("readings are taken on the chip", file=sys.stderr)
        return 3
    chip.use_compile_cache(ROOT)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    work = ROOT / "bench" / ".runs"
    work.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = cell.run(c, seed, args.seconds, False,
                       lambda m: print(f"[readings] {m}", file=sys.stderr,
                                       flush=True),
                       t0, str(work), control=seed in control)
        r = out["result"]
        line = {"seed": seed, "readings": out["readings"],
                "correct": r["correct"], "check": r["check"],
                "metrics": r["metrics"],
                "steps": out["res"]["window"]["steps"]}
        if "control" in out["readings"]:
            # the control through the harness's own comparison and limits
            ok, shown = check.judge(out["readings"]["control"], c["limits"])
            line["control_correct"], line["control_check"] = ok, shown
        print(json.dumps(line), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
