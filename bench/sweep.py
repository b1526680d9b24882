#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 100,140,180

One set-up, then one open-loop window per rate with the cell's traffic
mix at that rate.  Per rate it prints the answered rate, the latency
percentiles from the due time, and the median latency of the last
quarter of requests over the first quarter: near 1 where the system
keeps up, growing where a backlog builds.  The knee is the highest rate
whose ratio stays near 1; a cell's rate is fixed at about 0.8 of it.
Not a benchmark run: it prints no result line.
"""
import argparse
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated request rates, per second")
    args = ap.parse_args(argv)

    import jax
    from yardstick import cell as cellmod
    from yardstick import spec, stats, traffic

    cell = spec.resolve(args.workload)
    mix = traffic.validate(dict(cell.traffic))
    if mix["loop"] != "open":
        print("sweep: needs an open-loop cell", file=sys.stderr)
        return 1
    try:
        cellmod.require_chips(jax, cell.chips)
    except cellmod.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 1
    served = cellmod.setup(cell, mix["mode"], trace=False)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        res = cellmod.drive(served, dict(mix, rate_per_s=rate),
                            args.seed + i, args.seconds)
        done = [a for a in res.answered if a.done is not None]
        lat = [(a.done - a.due) * 1e3 for a in done]
        q = max(1, len(lat) // 4)
        growth = float(np.median(lat[-q:]) / np.median(lat[:q]))
        row = {"rate_per_s": rate, "requests": len(res.answered),
               "answered_per_s": len(done) / (res.end - res.start),
               "p50_ms": stats.percentile(lat, 50),
               "p90_ms": stats.percentile(lat, 90),
               "last_over_first_quarter": growth,
               **stats.lateness(res)}
        print("sweep: " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    from yardstick.entry import prepare

    prepare()
    sys.exit(main())
