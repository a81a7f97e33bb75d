"""The whole step's share of the chip's bf16 peak: model FLOPs of every
token generated in the window (`bench/counts/dense_gqa`: 2 x matmul
parameters + attention over each token's context) over the window's
length times the peak."""


def read(run):
    w = run["window"]
    return 100.0 * run["model_flops"] / (w["window_s"] * run["peaks"]["bf16_flops"])
