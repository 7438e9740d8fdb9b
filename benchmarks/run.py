# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

Runs the per-paper-table benchmarks at reduced (CPU) scale and the roofline
report derived from the dry-run artifacts. Each table module also caches a
JSON rendering under benchmarks/results/.

  PYTHONPATH=src python -m benchmarks.run [table ...]
"""

from __future__ import annotations

import sys
import traceback

from benchmarks import (common, roofline_report, table1_mixed,
                        table3_classifiers, table6_ewq, table7_fastewq,
                        table8_selection, table9_sizes, table13_stats,
                        table14_summary, table_fig1_entropy)

TABLES = {
    "fig1": table_fig1_entropy,
    "table1": table1_mixed,
    "table3": table3_classifiers,
    "table6": table6_ewq,
    "table7": table7_fastewq,
    "table8": table8_selection,
    "table9": table9_sizes,
    "table13": table13_stats,
    "table14": table14_summary,
    "roofline": roofline_report,
}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or list(TABLES)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = TABLES[name]
        try:
            common.emit(mod.run())
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
