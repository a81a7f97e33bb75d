"""`BENCHMARK.json` and the files it names, found by name.

    bench/configs/<config>.json     a configuration: sizes, source, cuts,
                                    the program's engine flags, the policy
    bench/refs/<reference>.py       its plain reference (named in the file)
    bench/adapters/<reference>.py   all the harness knows of the architecture:
                                    weight layout, the engine's parameter
                                    tree and ArchConfig, kernels, K/V values
                                    per token
    bench/counts/<reference>.py     its model FLOPs per token, for `mfu`
    bench/traffic/<traffic>.json    a traffic mix's parameters
    bench/limits/<workload>.json    each compared number's limit
    bench/metrics/<metric>.py       a per-layer metric's reader: read(run)
    bench/counts/<kernel>.py        a kernel's FLOPs and bytes (one per name
                                    in the adapter's KERNELS)
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def module(kind: str, name: str):
    """Import bench/<kind>/<name>.py by its file name (names may hold `-`
    and `.`, so they are not importable as package paths)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def data(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def cell(workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, resolved from BENCHMARK.json."""
    b = benchmark(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in b["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    e2e = [m for m in b["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in b["per_layer"]
             if workload in m.get("workloads", [workload])]
    return {"name": workload, "entry": w, "config": config,
            "traffic": data("traffic", w["traffic"]),
            "limits": data("limits", workload),
            "end_to_end": e2e, "per_layer": layer}
