"""Benchmark aggregator: one section per paper table/figure + the
roofline report.  ``python -m benchmarks.run [section ...]``"""

from __future__ import annotations

import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

SECTIONS = [
    ("table1_forwarding", "paper Table 1: native vs forwarding x N"),
    ("fig4_pushdown", "paper Fig 3/4: pushdown vs client-side queries"),
    ("objsize_sweep", "paper §3.1: object size tradeoff"),
    ("composability", "paper §3.2: decomposable / holistic / approx"),
    ("ingest_fused", "paper §2.2: codec offload on the train input path"),
    ("recovery", "failure management + elastic resize"),
    ("roofline", "dry-run roofline table (reads cached cell records)"),
    ("bench_pushdown", "perf trajectory: writes BENCH_pushdown.json "
                       "(fabric ops / bytes / wall_s + codec micro-bench)"),
]


def main() -> None:
    enable_compile_cache()
    want = set(sys.argv[1:])
    failures = []
    for name, desc in SECTIONS:
        if want and name not in want:
            continue
        print(f"\n=== {name} — {desc} " + "=" * max(0, 40 - len(name)))
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            print(f"[{name}: {time.perf_counter() - t0:.1f}s]")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print("\nFAILED sections:", failures)
        raise SystemExit(1)
    print("\nall benchmark sections passed")


if __name__ == "__main__":
    main()
