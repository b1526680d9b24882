"""Benchmark entrypoint: one module per paper table + the roofline report.

Tables that return metric rows are also persisted machine-readably so
the perf trajectory is trackable across PRs:

* ``BENCH_serve.json`` — serving throughput, store cache sweep, cold
  start (``--tables serve``);
* ``BENCH_query.json`` — per-dataset query times (``--tables 4``).

Schema: ``{"git_sha": ..., "generated_unix": ..., "schema_version":
..., "tables": {name: [row-dict, ...]}}``.  ``schema_version`` is
``repro.obs.metrics.SCHEMA_VERSION`` — ``check_regression.py`` refuses
to compare documents across a version bump (loud schema-drift failure
instead of a KeyError).

    PYTHONPATH=src python -m benchmarks.run [--tables 2,3,4,5,6,hod,serve,roof]
"""
import argparse
import json
import os
import subprocess
import sys
import time


def _git_sha() -> str:
    """HEAD at write time, ``-dirty``-suffixed when the tree has
    uncommitted changes — a baseline stamped mid-PR is then visibly
    provisional instead of silently claiming an older commit."""
    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.check_output(
            ["git", "rev-parse", "HEAD"], cwd=cwd,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"
    try:
        dirty = subprocess.check_output(
            ["git", "status", "--porcelain"], cwd=cwd,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return sha
    return f"{sha}-dirty" if dirty else sha


def _write_bench(path: str, tables: dict) -> None:
    from repro.obs.metrics import SCHEMA_VERSION

    doc = {"git_sha": _git_sha(), "generated_unix": int(time.time()),
           "schema_version": SCHEMA_VERSION, "tables": tables}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables", default="2,3,4,5,6,hod,serve,roof")
    ap.add_argument("--bench-dir", default=".",
                    help="where BENCH_*.json files are written")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    want = set(args.tables.split(","))
    t0 = time.time()

    if "2" in want:
        from . import table2_preprocessing
        table2_preprocessing.run()
    if "3" in want:
        from . import table3_index_size
        table3_index_size.run()
    if "4" in want:
        from . import table4_query_time
        rows = table4_query_time.run()
        _write_bench(os.path.join(args.bench_dir, "BENCH_query.json"),
                     {"query_time": rows})
    if "5" in want:
        from . import table5_closeness
        table5_closeness.run()
    if "6" in want:
        from . import table6_directed
        table6_directed.run()
    if "hod" in want:
        from . import hod_scaling
        hod_scaling.run()
    if "serve" in want:
        from . import serve_throughput
        tables = serve_throughput.run()
        _write_bench(os.path.join(args.bench_dir, "BENCH_serve.json"),
                     tables)
    if "roof" in want:
        from . import roofline
        roofline.run()
    print(f"\nall benchmarks done in {time.time()-t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
