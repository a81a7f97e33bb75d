"""Paged decode-attention kernel conformance (kernels/paged_qattn).

Three-way agreement, swept over page sizes, head layouts and ragged slot
lengths:

  kernel (interpret-mode Pallas)  ==  ref.py (jnp page-walking oracle)
                                  ==  the gather+dense path (the paged
                                      backend's fallback and the layout
                                      conformance reference)
                                  ~=  the float (fp16-policy) reference,
                                      within quantization tolerance

"==" here is float32 agreement at 1e-5 (the flash merge reassociates the
softmax, so last-ulp equality is not defined), checked on outputs AND the
head-pooled slot weights; token-level decisions built on top are exactly
equal (greedy engine identity lives in test_backend_conformance.py).
Rows with no valid token anywhere are excluded from the dense comparison:
the kernel returns zeros where the dense softmax emits a garbage uniform
average (both are masked by every consumer).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as backend_lib
from repro.core import kvcache as kvc
from repro.core import packing
from repro.core.policy import CompressionConfig
from repro.kernels.paged_qattn import kernel as pq_kernel
from repro.kernels.paged_qattn import ops as pq_ops

QUANT_TOL = 0.35  # 4/2-bit mixed policy vs float reference (test_kvcache.py)


def _ccfg(policy="zipcache", **kw):
    return dataclasses.replace(CompressionConfig.preset(policy, **kw),
                               fp_window=8, recompress_interval=8)


def _ragged_cache(be, rng, lengths, hk, d, max_len, n_append=2,
                  dtype=jnp.float32):
    """Engine-style ragged batch: per-row b=1 prefill at its own length,
    inserted into an init_cache batch (length 0 = slot left empty), then a
    few appends so staging windows are non-empty and the last touched page
    is partially filled."""
    b = len(lengths)
    cache = be.init_cache(b, hk, d, max_len, dtype)
    for i, l in enumerate(lengths):
        if l == 0:
            continue
        k = jnp.asarray(rng.normal(size=(1, hk, l, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, hk, l, d)).astype(np.float32))
        s = jnp.asarray(rng.uniform(size=(1, l)).astype(np.float32))
        sl = be.compress_prefill(k, v, s, max_len, dtype=dtype)
        cache = be.insert(cache, sl, jnp.asarray(i, jnp.int32))
    active = jnp.asarray([l > 0 for l in lengths])
    for _ in range(n_append):
        kt = jnp.asarray(rng.normal(size=(b, hk, d)).astype(np.float32))
        cache = be.append(cache, kt, kt * 0.5, active=active)
    return cache


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_unpack_returns_plane_order(bits, d, rng):
    """The kernel unpacks a page's codes into plane order: field j of every
    byte as one contiguous block of lanes, so position j*(d/pf) + i holds
    channel i*pf + j.  8-bit codes keep channel order."""
    codes = rng.integers(0, 2**bits, size=(16, d))
    perm = pq_kernel.plane_perm(bits, d)
    if perm is None:
        assert bits == 8
        perm = np.arange(d)
    assert sorted(perm) == list(range(d))
    got = pq_kernel._unpack(packing.pack(jnp.asarray(codes), bits), bits)
    np.testing.assert_array_equal(np.asarray(got),
                                  codes[..., perm].astype(np.float32))


@pytest.mark.parametrize("page_size", [8, 16, 64])
@pytest.mark.parametrize("heads", [(4, 2), (4, 4), (8, 1), (14, 2)])
def test_paged_kernel_matches_gather_and_ref(page_size, heads, rng):
    """Sweep: kernel == oracle == gather+dense on ragged batches including a
    length-0 slot and partially-filled last pages, over GQA, MHA, MQA and
    qwen2-7b's query group of 7 at its head dim."""
    h, hk = heads
    d = 128 if h == 14 else 16
    max_len = 60  # capacities not page multiples for pages 16/64
    be = backend_lib.of(_ccfg(saliency_ratio=0.4), kind="paged",
                        page_size=page_size)
    lengths = [48, 0, 17, 33]
    cache = _ragged_cache(be, rng, lengths, hk, d, max_len)
    q = jnp.asarray(rng.normal(size=(len(lengths), h, d)).astype(np.float32))

    dense = kvc.attend_decode(q, cache.dense_view())
    ker = pq_ops.attend_paged(q, cache)
    ref = pq_ops.attend_paged(q, cache, use_ref=True)

    live = np.asarray([l > 0 for l in lengths])
    for got in (ker, ref):
        np.testing.assert_allclose(np.asarray(got.out)[live],
                                   np.asarray(dense.out)[live],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got.slot_weights)[live],
                                   np.asarray(dense.slot_weights)[live],
                                   atol=1e-6)
    # kernel vs oracle: same math, page-blocked both sides
    np.testing.assert_allclose(np.asarray(ker.out), np.asarray(ref.out),
                               atol=1e-5, rtol=1e-5)
    # empty rows: zeros, and zero slot mass (the dense path's uniform
    # garbage average is explicitly NOT replicated)
    assert np.all(np.asarray(ker.out)[~live] == 0.0)
    assert np.all(np.asarray(ker.slot_weights)[~live] == 0.0)
    # softmax mass over valid slots sums to one on live rows
    np.testing.assert_allclose(
        np.asarray(ker.slot_weights.sum(-1))[live], 1.0, rtol=1e-5)


@pytest.mark.parametrize("policy", ["zipcache", "fp16"])
def test_paged_kernel_within_quant_tol_of_float_reference(policy, rng):
    """Same tokens through the quantized kernel vs an fp16-policy float
    cache: the kernel inherits exactly the quantization error budget the
    dense path is held to (and for the fp16 policy — raw segments end to
    end — it must agree to float tolerance, not QUANT_TOL)."""
    hk, d, l = 2, 16, 48
    k = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    s = jnp.asarray(rng.uniform(size=(2, l)).astype(np.float32))
    be = backend_lib.of(_ccfg(policy), kind="paged", page_size=8)
    fl = backend_lib.of(_ccfg("fp16"), kind="paged", page_size=8)
    cache = be.compress_prefill(k, v, s if _ccfg(policy).uses_saliency else None,
                                64, dtype=jnp.float32)
    ref = fl.compress_prefill(k, v, None, 56, dtype=jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))
    out_k = pq_ops.attend_paged(q, cache).out
    out_f = kvc.attend_decode(q, ref.dense_view()).out
    tol = 1e-5 if policy == "fp16" else QUANT_TOL
    assert float(jnp.max(jnp.abs(out_k - out_f))) < tol


def test_paged_kernel_bf16_store_rounding_matches_dense(rng):
    """Serving caches are bf16: the dense path rounds dequantized values to
    the store dtype before attention, and the kernel must replicate that
    rounding or its scores sit a bf16 ulp off (the bug class that broke
    engine token-identity)."""
    hk, d, l = 2, 16, 40
    k = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    s = jnp.asarray(rng.uniform(size=(2, l)).astype(np.float32))
    be = backend_lib.of(_ccfg(saliency_ratio=0.4), kind="paged", page_size=8)
    cache = be.compress_prefill(k, v, s, 56, dtype=jnp.bfloat16)
    kt = jnp.asarray(rng.normal(size=(2, hk, d)).astype(np.float32))
    cache = be.append(cache, kt, kt * 0.5)
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))
    dense = kvc.attend_decode(q, cache.dense_view())
    ker = pq_ops.attend_paged(q, cache)
    np.testing.assert_allclose(np.asarray(ker.out), np.asarray(dense.out),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ker.slot_weights),
                               np.asarray(dense.slot_weights), atol=1e-6)


def test_backend_attend_dispatch_and_fallback(rng):
    """use_kernel=True routes supported caches through the kernel (same
    outputs as the gather path), and a policy the kernel cannot serve
    (KIVI's groupwise stores, MiKV's tokenwise values) is refused when the
    backend is built — never silently served by the gather path."""
    hk, d, l = 2, 16, 40
    k = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    s = jnp.asarray(rng.uniform(size=(2, l)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))

    be_on = backend_lib.of(_ccfg(), kind="paged", page_size=8,
                           paged_kernel=True)
    be_off = backend_lib.of(_ccfg(), kind="paged", page_size=8)
    cache = be_on.compress_prefill(k, v, s, 56, dtype=jnp.float32)
    assert pq_ops.kernel_supported(cache)
    np.testing.assert_allclose(np.asarray(be_on.attend(q, cache).out),
                               np.asarray(be_off.attend(q, cache).out),
                               atol=1e-5, rtol=1e-5)

    for policy in ("kivi", "mikv"):
        assert not pq_ops.policy_supported(_ccfg(policy))
        with pytest.raises(ValueError, match="cannot serve"):
            backend_lib.of(_ccfg(policy), kind="paged", page_size=8,
                           paged_kernel=True)
    cache_g = backend_lib.of(_ccfg("kivi"), kind="paged", page_size=8) \
        .compress_prefill(k, v, None, 56, dtype=jnp.float32)
    assert not pq_ops.kernel_supported(cache_g)

    with pytest.raises(ValueError):
        backend_lib.of(_ccfg(), kind="mixed", paged_kernel=True)


def test_probe_step_weights_bitwise_exact(rng):
    """On probe steps the kernel backend must hand back the gather path's
    slot weights BITWISE (saliency state drives recompression top-k, where
    near-ties amplify float noise into different hi/lo splits)."""
    hk, d, l = 2, 16, 40
    k = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, hk, l, d)).astype(np.float32))
    s = jnp.asarray(rng.uniform(size=(2, l)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))
    be = backend_lib.of(_ccfg(), kind="paged", page_size=8, paged_kernel=True)
    ref = backend_lib.of(_ccfg(), kind="paged", page_size=8)
    cache = be.compress_prefill(k, v, s, 56, dtype=jnp.bfloat16)
    probe = jnp.asarray([True, False])
    w_kernel = np.asarray(jax.jit(
        lambda q, c: be.attend(q, c, is_probe=probe).slot_weights)(q, cache))
    w_dense = np.asarray(ref.attend(q, cache).slot_weights)
    np.testing.assert_array_equal(w_kernel, w_dense)
