"""The kernel's and the model's operation and byte counts against hand
counts at yi-6b and qwen2-7b widths."""

import pytest

from bench.lib import spec


def _cfg(name):
    import json
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def test_paged_qattn_yi6b_hand_count():
    c = _cfg("yi-6b")
    k = spec.module("counts", "paged_qattn")
    # one slot: 2560 quantized tokens (1024 salient at 4 bits, 1536
    # regular at 2 bits) and 37 in the bf16 window
    flops, nbytes = k.step(c["model"], c["policy"], [(2560, 37)])
    d, kv, h, layers = 128, 4, 32, 32
    codes = 2 * d * (1024 * 4 + 1536 * 2) // 8            # K and V
    params = 2 * (2 * 2 * d + 2 * d + 2 * 2560)           # bf16
    window = 2 * 37 * d * 2
    qo = 2 * h * d * 2
    assert nbytes == layers * (kv * (codes + params + window) + qo)
    assert flops == layers * h * 4 * d * (2560 + 37)


def test_paged_qattn_qwen2_group_of_7():
    c = _cfg("qwen2-7b")
    k = spec.module("counts", "paged_qattn")
    one, _ = k.step(c["model"], c["policy"], [(3000, 100)])
    two, _ = k.step(c["model"], c["policy"], [(3000, 100), (3000, 100)])
    assert two == 2 * one
    assert one == 24 * 28 * 4 * 128 * 3100


@pytest.mark.parametrize("name, params", [
    # 32 x (4096*4096*2 + 2*4096*512 + 3*4096*11008) + 4096*64000
    ("yi-6b", 32 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
     + 4096 * 64000),
    ("qwen2-7b", 24 * (2 * 3584 * 3584 + 2 * 3584 * 512
                       + 3 * 3584 * 18944) + 3584 * 152064),
])
def test_mfu_flops_hand_count(name, params):
    c = _cfg(name)
    m = spec.module("counts", "dense_gqa")
    assert m.matmul_params(c["model"]) == params
    mm = c["model"]
    ctx = 3000
    assert m.token_flops(mm, ctx) == 2 * params + 4 * mm["num_hidden_layers"] \
        * mm["num_attention_heads"] * 128 * ctx


def test_peaks_refuse_an_unknown_device():
    from bench.lib import peaks
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.of("cpu")
