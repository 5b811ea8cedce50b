"""Benchmark harness entry point — one function per paper table/figure plus
the kernel microbenchmarks, secure-LM customization sweep, and the roofline
table from the dry-run farm.

    PYTHONPATH=src python -m benchmarks.run [--only table1,kernels,...] \
        [--json PATH]

Prints ``name,us_per_call,derived`` CSV (one row per measurement).
``--json PATH`` additionally writes the rows as a machine-readable
{name: us_per_call} map (e.g. BENCH_kernels.json) so the perf trajectory
is diffable across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: table1,table2,table3,"
                         "kernels,secure,lm,roofline,pareto")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write {name: us_per_call} JSON to PATH")
    args = ap.parse_args()
    want = set(filter(None, args.only.split(",")))
    if "secure_lm" in want:   # legacy name for the lm suite
        want = (want - {"secure_lm"}) | {"lm"}

    if want & {"secure", "lm"} and "jax" not in sys.modules:
        # the mesh-backend rows need >= 3 host devices; the flag only works
        # before jax initializes
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

    from repro.launch.runtime import enable_compile_cache

    from . import (kd_curves, kernel_bench, paper_tables, pareto,
                   roofline_report, secure_e2e, secure_lm)

    enable_compile_cache()

    suites = {
        "table1": paper_tables.table1,
        "table2": paper_tables.table2,
        "table3": paper_tables.table3,
        "kd": kd_curves.kd_curves,
        "kernels": kernel_bench.kernels,
        "secure": secure_e2e.secure_e2e,
        "lm": secure_lm.secure_lm,
        "roofline": roofline_report.rows,
        "pareto": pareto.pareto,
    }
    print("name,us_per_call,derived")
    failures = 0
    collected: dict[str, float] = {}
    for name, fn in suites.items():
        if want and name not in want:
            continue
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
                collected[n] = round(float(us), 3)
        except Exception:
            failures += 1
            print(f"{name},ERROR,{traceback.format_exc(limit=1)!r}",
                  file=sys.stderr)
    if args.json:
        # read-modify-write: a partial --only run updates its own rows and
        # keeps rows other suites wrote to the same file earlier
        rows: dict[str, float] = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    prev = json.load(f)
                if isinstance(prev, dict):
                    rows.update(prev)
            except (OSError, ValueError):
                print(f"warning: could not merge into unreadable "
                      f"{args.json}; rewriting", file=sys.stderr)
        rows.update(collected)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(collected)} rows to {args.json} "
              f"({len(rows)} total)", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
