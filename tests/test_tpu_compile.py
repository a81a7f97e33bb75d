"""The served decode kernel compiles for a TPU v5e chip at yi-6b widths.

Interpret mode (every other kernel test) does not apply Mosaic's rules —
block shapes whose last two dims are neither (8, 128)-divisible nor the
array's own, uint8 arithmetic — so a kernel can pass all of them and still
be refused by the chip's compiler.  These tests compile `paged_qattn` for a
DESCRIBED v5e (no chip attached): each segment kind the serving cache holds
(4-bit hi store, 2-bit lo store, bf16 staging window) and the whole
`attend_paged` op, at yi-6b's decode shapes (8 slots, 32 heads over 4 KV
heads, head dim 128, 1024-token prompts + 128 new tokens, 64-token pages).
One more reads the Mosaic dump of those compiles and bounds each quantized
segment's kernel body by twice the raw window segment's LLO op count, so a
lane interleave in the unpack (thousands of shuffles per page) cannot
return unnoticed.

The topology is described inside a module fixture, never at import time:
only the worker that runs this file loads the TPU compiler library (and,
before it does, the dump test's child processes, one at a time).
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import paged
from repro.core.policy import CompressionConfig
from repro.kernels.paged_qattn import kernel as K
from repro.kernels.paged_qattn import ops as pq_ops

ROOT = Path(__file__).resolve().parents[1]
B, H, HK, D, MAX_LEN, PAGE = 8, 32, 4, 128, 1024 + 128, 64
SEGMENT_FIELDS = ("k_pages", "k_scale", "k_zero", "v_pages", "v_cscale",
                  "v_tscale", "v_tzero", "pos", "table")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache without the chip: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on_chip(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _served_cache():
    """Abstract decode cache of the served configuration (free-list pools)."""
    be = paged.PagedKVBackend(CompressionConfig.zipcache(), page_size=PAGE,
                              use_kernel=True, allocator="freelist")
    return jax.eval_shape(lambda: be.init_cache(B, HK, D, MAX_LEN))


def _compile_segment(segment, sharding):
    """Compile one segment kind of the served cache for the described chip."""
    cache = _served_cache()
    q = jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16)

    def operands(q, c):
        ops = (pq_ops._window_operands(q, c) if segment == "window"
               else pq_ops._store_operands(q, getattr(c, segment)))
        return {k: ops[k] for k in SEGMENT_FIELDS}

    arrays = jax.eval_shape(operands, q, cache)
    store = None if segment == "window" else getattr(cache, segment)
    k_bits = 16 if store is None else store.k_meta.bits
    v_bits = 16 if store is None else store.v_meta.bits
    dtype = jnp.float32 if store is None else jnp.bfloat16
    assert (k_bits, v_bits) == {"hi": (4, 4), "lo": (2, 2),
                                "window": (16, 16)}[segment]
    fn = jax.jit(lambda q, *a: K.qattn_paged_segment(
        q, *a, k_bits=k_bits, v_bits=v_bits, scale=D ** -0.5,
        k_dtype=dtype, v_dtype=dtype, interpret=False))
    return fn.lower(_on_chip(q, sharding),
                    *[_on_chip(arrays[k], sharding)
                      for k in SEGMENT_FIELDS]).compile()


# Compiles one segment in a fresh process: the Mosaic dump directory is a
# flag of the TPU compiler library, read once when a process loads it.
_DUMP_CHILD = """
import sys
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:  # noqa: BLE001
    print(f"no v5e:2x2 topology can be described here: {e}")
    sys.exit(77)
from tests.test_tpu_compile import _compile_segment
_compile_segment(sys.argv[1], SingleDeviceSharding(topo.devices[0]))
"""
_LLO_OP = re.compile(r'^\s*(%\S+ = )?"?llo\.')


def _kernel_body_ops(segment, dump_dir):
    """LLO ops of the finalized kernel body (`@main`, run once per grid
    step) of one segment, read from the Mosaic dump of a compile."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), str(ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]),
               LIBTPU_INIT_ARGS=(os.environ.get("LIBTPU_INIT_ARGS", "")
                                 + f" --xla_mosaic_dump_to={dump_dir}"))
    run = subprocess.run([sys.executable, "-c", _DUMP_CHILD, segment],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    if run.returncode == 77:
        pytest.skip(run.stdout.strip())
    assert run.returncode == 0, run.stderr[-4000:]
    (final,) = dump_dir.glob("*post-finalize-llo.txt")
    lines = final.read_text().splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.lstrip().startswith("func.func @main"))
    end = next((i for i in range(start + 1, len(lines))
                if lines[i].lstrip().startswith("func.func @")), len(lines))
    return sum(bool(_LLO_OP.match(l)) for l in lines[start + 1:end])


def test_quantized_segments_unpack_without_lane_shuffles(tmp_path):
    """A quantized page costs about what a raw window page costs per grid
    step: its codes unpack in plane order (kernel.plane_perm), with no lane
    interleave for Mosaic to lower into thousands of shuffles, rotates and
    selects.  Runs first in this file, before the module's own process
    loads the TPU compiler library for the tests below."""
    ops = {seg: _kernel_body_ops(seg, tmp_path / seg)
           for seg in ("window", "hi", "lo")}
    for seg in ("hi", "lo"):
        assert ops[seg] <= 2 * ops["window"], ops


@pytest.mark.parametrize("segment", ["hi", "lo", "window"])
def test_paged_segment_compiles_for_v5e(segment, one_chip):
    compiled = _compile_segment(segment, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_attend_paged_compiles_for_v5e(one_chip):
    """The whole op the decode step calls: three kernel launches + merge."""
    fn = jax.jit(lambda q, c: pq_ops.attend_paged(q, c, interpret=False))
    compiled = fn.lower(
        _on_chip(jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16), one_chip),
        _on_chip(_served_cache(), one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
