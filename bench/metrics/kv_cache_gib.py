"""Device bytes the KV cache holds at the window's close, payload and
bookkeeping (free pool pages included): `engine.cache_bytes` of the
paged cache (`core/paged.py`, `core/alloc.py`)."""


def read(run):
    return run["cache_bytes"]["total_bytes"] / 2**30
