"""The paged decode kernel's share of its roofline (`kernels/paged_qattn`):
the larger of its bytes over peak bandwidth and its FLOPs over peak bf16
compute, both counted from the live context (`bench/counts/paged_qattn`),
over the kernel's device time in the window."""


def read(run):
    tr = run["trace"]
    k = run["kernels"]["paged_qattn"]
    if tr is None:
        return None
    t = sum(v for n, v in tr["ops"].items() if n in k["op_names"])
    if t <= 0:
        return None
    p = run["peaks"]
    ideal = max(k["flops"] / p["bf16_flops"], k["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * ideal / t
