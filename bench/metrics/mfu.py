"""The whole step's share of the chip's bf16 peak: model FLOPs of every
token generated in the window (the configuration's `counts/<reference>`:
FLOPs per token at its context) over the window's length times the
peak."""


def read(run):
    w = run["window"]
    return 100.0 * run["model_flops"] / (w["window_s"] * run["peaks"]["bf16_flops"])
