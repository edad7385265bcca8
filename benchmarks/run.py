"""Benchmark harness: one module per paper table. Prints
``name,us_per_call,derived`` CSV rows (see each module's docstring for
the paper table it reproduces).

Optional argv filters select a subset by table name, e.g.
``python -m benchmarks.run table5`` — used by CI as a smoke invocation.
``--json`` additionally writes ``BENCH_<table>.json`` per selected table
that supports it (table5, and table6_streaming which writes
``BENCH_streaming.json``) — the machine-readable perf trajectory CI
archives as an artifact.
"""
from __future__ import annotations

import inspect
import sys
import traceback


def main(argv=None) -> None:
    from . import (table1_parallelism, table2_roofline,
                   table3_sparsity_utilization, table4_accuracy,
                   table5_throughput, table6_streaming)

    modules = (table4_accuracy, table3_sparsity_utilization,
               table1_parallelism, table5_throughput, table2_roofline,
               table6_streaming)
    args = list(sys.argv[1:] if argv is None else argv)
    flags = {a for a in args if a.startswith("--")}
    unknown = flags - {"--json"}
    if unknown:
        print(f"unknown flags {sorted(unknown)}; supported: --json",
              file=sys.stderr)
        sys.exit(2)
    wanted = [a for a in args if not a.startswith("--")]
    if wanted:
        selected = [m for m in modules
                    if any(w in m.__name__ for w in wanted)]
        if not selected:
            print(f"no benchmark matches {wanted}; have "
                  f"{[m.__name__ for m in modules]}", file=sys.stderr)
            sys.exit(2)
        modules = tuple(selected)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod in modules:
        try:
            kwargs = {}
            if ("--json" in flags
                    and "json_out" in inspect.signature(mod.main).parameters):
                kwargs["json_out"] = True
            mod.main(**kwargs)
        except Exception:
            failures += 1
            print(f"{mod.__name__},0.0,ERROR")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
