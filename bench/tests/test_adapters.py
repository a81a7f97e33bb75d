"""Everything the harness knows of an architecture sits in its adapter,
`bench/adapters/<reference>.py`: the dense GQA adapter gives the layout,
weights, ArchConfig and divisors the harness had before it was split out,
a second architecture plugs in as new files only, and `bench/lib/` names
no architecture."""

import dataclasses
import filecmp
import hashlib
import shutil
import time

import jax
import numpy as np
import pytest

from bench.lib import cell, spec
from bench.lib import weights as weights_lib
from bench.tests import tiny

ARCH = spec.module("adapters", "dense_gqa")

# sha256 over (path, dtype, shape, bytes) of every leaf at tiny.MODEL, from
# the weights the harness drew before the adapters existed (same seed, CPU).
DIGEST = {1: "af11bd427c17c166138cd5b04fc6ff31c60b380c81a431f7a364d89718ff6cc3",
          5: "46816c7c2cb7f71249188755871cc939085e99d23ac6f4dd9fef3dd0c6e24332"}

# The layout in flatten order, as the harness drew it before the adapters
# existed: (path, shape).
LAYOUT = {
    "yi-6b": [
        ("['embed']", (64000, 4096)), ("['final_norm']", (4096,)),
        ("['layers']['ln1']", (32, 4096)), ("['layers']['ln2']", (32, 4096)),
        ("['layers']['w_down']", (32, 11008, 4096)),
        ("['layers']['w_gate']", (32, 4096, 11008)),
        ("['layers']['w_up']", (32, 4096, 11008)),
        ("['layers']['wk']", (32, 4096, 4, 128)),
        ("['layers']['wo']", (32, 32, 128, 4096)),
        ("['layers']['wq']", (32, 4096, 32, 128)),
        ("['layers']['wv']", (32, 4096, 4, 128)),
        ("['lm_head']", (4096, 64000))],
    "qwen2-7b": [
        ("['embed']", (152064, 3584)), ("['final_norm']", (3584,)),
        ("['layers']['bk']", (24, 4, 128)), ("['layers']['bq']", (24, 28, 128)),
        ("['layers']['bv']", (24, 4, 128)),
        ("['layers']['ln1']", (24, 3584)), ("['layers']['ln2']", (24, 3584)),
        ("['layers']['w_down']", (24, 18944, 3584)),
        ("['layers']['w_gate']", (24, 3584, 18944)),
        ("['layers']['w_up']", (24, 3584, 18944)),
        ("['layers']['wk']", (24, 3584, 4, 128)),
        ("['layers']['wo']", (24, 28, 128, 3584)),
        ("['layers']['wq']", (24, 3584, 28, 128)),
        ("['layers']['wv']", (24, 3584, 4, 128)),
        ("['lm_head']", (3584, 152064))],
}

# The ArchConfig the harness built before the adapters existed.
ARCH_CONFIG = {
    "yi-6b": dict(name="yi-6b", family="dense", n_layers=32, d_model=4096,
                  n_heads=32, n_kv_heads=4, d_ff=11008, vocab=64000,
                  qkv_bias=False, norm_eps=1e-05, rope_theta=5000000.0,
                  tie_embeddings=False),
    "qwen2-7b": dict(name="qwen2-7b", family="dense", n_layers=24,
                     d_model=3584, n_heads=28, n_kv_heads=4, d_ff=18944,
                     vocab=152064, qkv_bias=True, norm_eps=1e-06,
                     rope_theta=1000000.0, tie_embeddings=False),
}

# kv_bits_per_value's divisor per live token before the adapters existed:
# layers x KV heads x head_dim x 2 (K and V).
KV_VALUES = {"yi-6b": 32 * 4 * 128 * 2, "qwen2-7b": 24 * 4 * 128 * 2}

LIB_NAMES_NO = ("dense_gqa", "num_key_value_heads", "intermediate_size",
                "ArchConfig(")


def _model(name):
    return spec.data("configs", name)["model"]


def _digest(tree) -> str:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    h = hashlib.sha256()
    for path, x in flat:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(DIGEST))
def test_tiny_weights_are_bitwise_the_same(seed):
    w = weights_lib.make(ARCH.leaves(tiny.MODEL), seed)
    assert _digest(w) == DIGEST[seed]


@pytest.mark.parametrize("name", sorted(LAYOUT))
def test_layout_is_the_same(name):
    m = _model(name)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        ARCH.leaves(m), is_leaf=lambda x: isinstance(x, tuple))
    assert [(jax.tree_util.keystr(p), s) for p, (s, _) in flat] \
        == LAYOUT[name]
    fan_in = {jax.tree_util.keystr(p).split("'")[-2]: init[1]
              for p, (_, init) in flat if isinstance(init, tuple)}
    e, f = m["hidden_size"], m["intermediate_size"]
    hd = m["num_attention_heads"] * m["head_dim"]
    assert fan_in == {"wq": e, "wk": e, "wv": e, "wo": hd, "w_gate": e,
                      "w_up": e, "w_down": f, "lm_head": e}


@pytest.mark.parametrize("name", sorted(ARCH_CONFIG))
def test_arch_config_is_the_same(name):
    from repro.configs.base import ArchConfig
    got = ARCH.arch_config(_model(name), name)
    want = ArchConfig(**ARCH_CONFIG[name])
    for f in dataclasses.fields(ArchConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


def test_dense_gqa_holds_hidden_size_to_heads_times_head_dim():
    m = dict(tiny.MODEL, head_dim=8)
    with pytest.raises(ValueError, match="head_dim"):
        ARCH.arch_config(m, "tiny")


@pytest.mark.parametrize("name", sorted(KV_VALUES))
def test_kv_values_per_token_is_the_same(name):
    assert ARCH.kv_values_per_token(_model(name)) == KV_VALUES[name]


def test_lib_names_no_architecture():
    found = [(p.name, s) for p in sorted((spec.BENCH / "lib").glob("*.py"))
             for s in LIB_NAMES_NO if s in p.read_text()]
    assert not found


# ---------------------------------------------------------------------------
# a second architecture, as new files only
# ---------------------------------------------------------------------------

SENTINEL_COUNTS = '''"""A kernel of the plugged architecture: one FLOP and two bytes per
live slot and step, so the harness's sum can be checked."""

OP_NAMES = ("sentinel_op",)


def step(model, policy, slots):
    n = len(list(slots))
    return n, 2 * n
'''


def test_a_second_architecture_plugs_in_as_new_files(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".runs", "tests"))
    adapter = (bench / "adapters" / "dense_gqa.py").read_text()
    assert 'KERNELS = ("paged_qattn",)' in adapter
    new = {
        "adapters/plugged.py": adapter.replace(
            'KERNELS = ("paged_qattn",)', 'KERNELS = ("sentinel",)'),
        "refs/plugged.py": (bench / "refs" / "dense_gqa.py").read_text(),
        "counts/plugged.py": (bench / "counts" / "dense_gqa.py").read_text(),
        "counts/sentinel.py": SENTINEL_COUNTS,
    }
    for rel, text in new.items():
        assert not (bench / rel).exists()
        (bench / rel).write_text(text)
    # every file the copy shares with bench/ is unchanged
    for kind in ("lib", "adapters", "refs", "counts", "metrics"):
        cmp = filecmp.dircmp(spec.BENCH / kind, bench / kind,
                             ignore=["__pycache__"])
        assert not cmp.diff_files and not cmp.left_only, kind
    monkeypatch.setattr(spec, "BENCH", bench)

    c = tiny.cell(tiny.LIMITS)
    c["config"]["reference"] = "plugged"
    out = cell.run(c, 7, 0.2, False, lambda m: None, time.perf_counter(),
                   str(tmp_path / "runs"))
    assert out["result"]["correct"], out["result"]["check"]
    kernels = cell.window_work(out["res"], c)["kernels"]
    assert list(kernels) == ["sentinel"]
    live = sum(len(s.tokens) for s in out["res"]["window_steps"])
    assert kernels["sentinel"] == {"flops": live, "bytes": 2 * live,
                                   "op_names": ["sentinel_op"]}
    assert spec.module("adapters", "plugged").kv_values_per_token(tiny.MODEL) \
        == ARCH.kv_values_per_token(tiny.MODEL)
