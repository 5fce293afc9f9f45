"""Benchmark driver: one function per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
Prints CSV blocks per benchmark; claim checks inline as comments.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    quick = "--quick" in sys.argv
    from benchmarks import (fig3_reconfig_overhead, fig6_trace,
                            table2_actions, table3_sync_vs_async,
                            table4_throughput)
    benches = [
        ("fig3_reconfig_overhead", fig3_reconfig_overhead.main),
        ("table2_actions", table2_actions.main),
        ("table3_sync_vs_async", table3_sync_vs_async.main),
        ("table4_throughput", table4_throughput.main),
        ("fig6_trace", fig6_trace.main),
    ]
    for name, fn in benches:
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        fn(quick=quick)
        print(f"# [{name} took {time.perf_counter()-t0:.1f}s]")


if __name__ == "__main__":
    main()
