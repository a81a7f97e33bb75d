"""bench/run.py refuses a machine without the chip before it builds
anything, and prints no result line."""

import os
import subprocess
import sys

from bench.lib import spec


def test_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "yi6b-decode-long", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was built" in p.stderr
