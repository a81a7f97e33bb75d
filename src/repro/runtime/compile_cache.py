"""Where JAX's persistent compilation cache lives: one rule for every entry
point (`chip_smoke.py`, `launch.serve`, `launch.serve_http`,
`benchmarks/run.py`).

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and uses that
    directory; this module sets nothing.
  * unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).

The path is part of each entry's key, so it is fixed: never derived from a
temporary name, a process id or the time — a directory that moves never
hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
