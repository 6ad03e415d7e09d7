"""Benchmark harness entry point: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only table4]``
prints ``name,value,derived`` CSV lines (value in µs for timings). Every
table runs in this one process (a process that holds a chip must not start
children that need it); a table that raises fails the run with exit 1.
"""
import argparse
import importlib
import sys
import time
import traceback

TABLES = [
    ("table1_memory", "benchmarks.table1_memory"),
    ("table3_throughput", "benchmarks.table3_throughput"),
    ("table4_auc", "benchmarks.table4_auc"),
    ("table5_feature_auc", "benchmarks.table5_feature_auc"),
    ("table6_scalability", "benchmarks.table6_scalability"),
    ("roofline_report", "benchmarks.roofline_report"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    failures = 0
    print("name,value,derived")
    for name, mod_name in TABLES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            for line in mod.run():
                print(line, flush=True)
            print(f"{name}/bench_wall_s,{(time.time()-t0)*1e6:.0f},",
                  flush=True)
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc().splitlines()[-1]}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
