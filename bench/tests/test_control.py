"""The control, the reference one precision lower (fp8) put in the
program's place, comes out not correct, where the program's own run is
correct: the harness's whole run on the CPU at a size a test run holds
(16 layers of width 256, 4 slots of 512-640-token prompts, the gather
decode path; the kernel's interpreter would take minutes).

Limits at this size, set as the cell's are: the program's gap_mean read
at most 0.0135 over seeds 1-6 on the CPU, the control's at least 0.0538;
gap_max does not separate here (0.29 against 0.55) and is not held."""

import time

from bench.lib import cell
from bench.tests import tiny

LIMITS = {"gap_mean": 0.03}


def _mid():
    c = tiny.cell(LIMITS)
    c["config"]["model"].update(
        hidden_size=256, intermediate_size=768, num_attention_heads=4,
        num_key_value_heads=2, head_dim=64, num_hidden_layers=16,
        vocab_size=1024, qkv_bias=False)
    argv = c["config"]["program"]["argv"]
    argv[argv.index("--paged-kernel") + 1] = "off"
    c["traffic"].update(prompt_len=640, max_new=128, check_requests=4,
                        prompt_lengths={"lo": 512, "hi": 640, "n": 2,
                                        "align": 64})
    return c


def test_control_is_not_correct(tmp_path):
    out = cell.run(_mid(), 3, 2.0, False, lambda m: None,
                   time.perf_counter(), str(tmp_path), control=True)
    assert out["result"]["correct"], out["result"]["check"]
    ok, shown = cell.check_lib.judge(out["readings"]["control"], LIMITS)
    assert not ok, shown
