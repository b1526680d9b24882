#!/usr/bin/env python3
"""Run one benchmark cell (see ``BENCHMARK.json`` and ``PERF.md``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout's root on a machine with the chips the cell asks
for.  The last line of standard output is the result; the numbers that
decided ``correct`` follow on standard error.  JAX's persistent compile
cache is kept at ``<checkout>/.jax_cache``.
"""
import sys
import time

if __name__ == "__main__":
    t_start = time.perf_counter()
    from yardstick.entry import prepare

    prepare()
    from yardstick.cell import main

    sys.exit(main(t_start=t_start))
