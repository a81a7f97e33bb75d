"""Per-arch smoke tests (assignment requirement): REDUCED config of each
family, one forward/train step on CPU, asserting shapes + no NaNs; plus the
serving path (prefill -> decode -> recompress) per family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ShapeConfig
from repro.core import saliency as sal
from repro.core.policy import CompressionConfig
from repro.models import blocks, registry

ARCHS = list(configs.ARCH_IDS)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = configs.get_arch(arch, smoke=True)
    params = registry.materialize_params(cfg, 0)
    shape = ShapeConfig("t", 64, 2, "train")
    batch = registry.materialize_batch(
        registry.train_batch_spec(cfg, shape, jnp.float32), 0, cfg.vocab)
    loss, metrics = jax.jit(lambda p, b: registry.loss_fn(p, b, cfg))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-v2-lite-16b", "jamba-v0.1-52b",
                                  "seamless-m4t-medium", "mamba2-2.7b", "qwen2-7b"])
def test_serve_path_smoke(arch, rng):
    cfg = configs.get_arch(arch, smoke=True)
    params = registry.materialize_params(cfg, 0)
    b, l = 2, 64
    shape = ShapeConfig("p", l, b, "prefill")
    qlen, _ = registry.prefill_lengths(cfg, shape)
    ccfg = CompressionConfig.zipcache(saliency_ratio=0.4, fp_window=8,
                                      recompress_interval=8)
    probe = sal.select_probes(qlen, "random+recent", 0.2, seed=0)
    ctx = blocks.RunCtx(ccfg=ccfg, probe=probe, max_cache_len=qlen + 16, q_block=32)
    batch = registry.materialize_batch(
        registry.prefill_batch_spec(cfg, shape, jnp.float32), 0, cfg.vocab)
    logits, caches = jax.jit(lambda p, bt: registry.prefill(p, bt, cfg, ctx))(params, batch)
    assert logits.shape == (b, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    dec = jax.jit(lambda p, t, c, ip: registry.decode_step(p, t, c, cfg, ctx, ip))
    for i in range(3):
        logits, caches = dec(params, tok, caches, jnp.asarray(i % 2 == 0))
        assert bool(jnp.isfinite(logits).all()), f"{arch} decode {i}"
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    caches = jax.jit(lambda c: registry.recompress(c, cfg, ctx))(caches)
    logits, _ = dec(params, tok, caches, jnp.asarray(True))
    assert bool(jnp.isfinite(logits).all())


def test_gradients_flow_everywhere():
    """Every parameter receives a nonzero gradient (no dead branches)."""
    cfg = configs.get_arch("jamba-v0.1-52b", smoke=True)  # richest layer mix
    params = registry.materialize_params(cfg, 0)
    shape = ShapeConfig("t", 64, 2, "train")
    batch = registry.materialize_batch(
        registry.train_batch_spec(cfg, shape, jnp.float32), 0, cfg.vocab)
    grads = jax.grad(lambda p: registry.loss_fn(p, batch, cfg)[0])(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    dead = [
        "/".join(str(getattr(p, "key", p)) for p in path)
        for path, g in flat
        if float(jnp.max(jnp.abs(g))) == 0.0
    ]
    # routers can be momentarily dead if top-k saturates; everything else must live
    real_dead = [d for d in dead if "router" not in d and "A_log" not in d]
    assert not real_dead, real_dead


def test_param_counts_match_formula():
    """Schema parameter counts track the analytic ArchConfig.param_count
    (within vocab-padding + norm-weight slack)."""
    from repro.models import common

    for arch in ["yi-6b", "qwen2-7b", "deepseek-moe-16b", "mamba2-2.7b"]:
        cfg = configs.get_arch(arch)  # FULL config, schema only (no alloc)
        n_schema = common.count_params(registry.schema(cfg))
        n_formula = cfg.param_count()
        assert abs(n_schema - n_formula) / n_formula < 0.05, (
            arch, n_schema, n_formula)


def test_full_param_counts_sane():
    expect = {  # billions, loose bands from the public configs
        "yi-34b": (30, 40), "yi-6b": (5, 7), "qwen2-7b": (6.5, 8.5),
        "smollm-360m": (0.3, 0.45), "deepseek-v2-lite-16b": (14, 18),
        "deepseek-moe-16b": (14, 18), "jamba-v0.1-52b": (45, 58),
        "mamba2-2.7b": (2.3, 3.1), "llava-next-34b": (30, 40),
    }
    from repro.models import common

    for arch, (lo, hi) in expect.items():
        cfg = configs.get_arch(arch)
        n = common.count_params(registry.schema(cfg)) / 1e9
        assert lo < n < hi, (arch, n)


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-v2-lite-16b"])
def test_jitted_materialize_matches_eager(arch):
    """`common.materialize` builds all leaves in one jitted program (no
    whole-leaf float32 temporary on the device); the values must be the
    eager per-leaf build's, bit for bit, for the same seed."""
    from repro.models import common
    schema = registry.schema(configs.get_arch(arch, smoke=True))
    leaves = jax.tree_util.tree_leaves(schema, is_leaf=common.is_def)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    eager = [common._init_leaf(p, k) for p, k in zip(leaves, keys)]
    built = jax.tree_util.tree_leaves(common.materialize(schema, 7))
    assert len(built) == len(eager)
    for a, b in zip(built, eager):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
