"""What every on-chip entry point does first: find the chips the cell asks
for, and keep JAX's persistent compilation cache inside the checkout."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional


def devices(chips: int) -> Optional[list]:
    """JAX's devices, or None where they are not `chips` TPU chips or more."""
    import jax
    found = jax.devices()
    if found[0].platform != "tpu" or len(found) < chips:
        return None
    return found


def use_compile_cache(root: Path) -> str:
    """`$JAX_COMPILATION_CACHE_DIR` where it is set, else `<root>/.jax_cache`
    (a fixed path, so a second run in the same checkout finds every
    program); every program is kept, however quick or small."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
