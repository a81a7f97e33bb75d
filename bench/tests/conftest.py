import os

# the benchmark's tests run on the CPU; bench/run.py itself refuses it
os.environ.setdefault("JAX_PLATFORMS", "cpu")
