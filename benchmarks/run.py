# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.runtime import compile_cache
    compile_cache.enable()
    from benchmarks import (
        bench_fig3_saliency,
        bench_fig5_retrieval,
        bench_fig6_efficiency,
        bench_table1_granularity,
        bench_table2_probe,
        bench_table3_quality,
        bench_tableA_ratio,
    )

    benches = [
        ("table1_granularity", bench_table1_granularity.run),
        ("fig3_saliency", bench_fig3_saliency.run),
        ("table2_probe", bench_table2_probe.run),
        ("table3_quality", bench_table3_quality.run),
        ("fig5_retrieval", bench_fig5_retrieval.run),
        ("fig6_efficiency", bench_fig6_efficiency.run),
        ("tableA_ratio", bench_tableA_ratio.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches:
        if only and only not in name:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:
            failures.append(name)
            traceback.print_exc()
            print(f"{name},0.0,ERROR:{type(e).__name__}")
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
