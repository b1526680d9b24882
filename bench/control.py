#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

One set-up of the cell at its own size, then per seed a window of the
cell's own traffic.  For the sample of answers a run would check it
prints the comparison's numbers for the program's answers against the
reference (the lower reading), and for the answers of each of the
configuration's controls put in the program's place (the upper
reading).  A control is the reference with one guarantee the
configuration states broken, one per guarantee
(``yardstick.reference.controls_for``).  Not a benchmark run: it prints
no result line.
"""
import argparse
import dataclasses
import json
import math
import sys


def readings(cell, arcs, truth, answered, seed: int) -> dict:
    """The comparison's numbers for the program's ``answered`` and, by
    control, for each control's answers to the same sampled requests."""
    from yardstick import check, reference

    mode = cell.traffic["mode"]
    k = int(cell.config["check"][f"{mode}_answers"])
    picks = check.sample(answered, k, seed)
    guarantees = cell.config["guarantees"]
    far = None
    if reference.counts_hops(guarantees, arcs):
        far = max(_farthest(mode, answered[i].request, truth)
                  for i in picks)
    controls = {}
    for name, ctl in reference.controls_for(guarantees, arcs, far).items():
        swapped = list(answered)
        for i in picks:
            swapped[i] = with_control(answered[i], mode, ctl)
        controls[name] = check.compare(mode, swapped, truth, k,
                                       seed).numbers
    return {"program": check.compare(mode, answered, truth, k,
                                     seed).numbers,
            "controls": controls, "farthest": far}


def _farthest(mode: str, request, truth) -> float:
    """The largest finite distance in the reference's answer."""
    dists = [truth.p2p(*request)] if mode == "p2p" else truth.ssd(request)
    return max((d for d in dists if math.isfinite(d)), default=0.0)


def with_control(a, mode: str, ctl):
    """``a`` with the control's answer to the same request in place of
    the program's."""
    answer = ctl.p2p(*a.request) if mode == "p2p" else ctl.ssd(a.request)
    return dataclasses.replace(a, answer=answer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from yardstick import cell as cellmod
    from yardstick import reference, spec, traffic

    cell = spec.resolve(args.workload)
    mix = traffic.validate(dict(cell.traffic))
    try:
        cellmod.require_chips(jax, cell.chips)
    except cellmod.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 1
    served = cellmod.setup(cell, mix["mode"], trace=False)
    truth = reference.Reference(served.arcs)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = cellmod.drive(served, mix, seed, args.seconds)
        row = {"seed": seed, "requests": len(res.answered),
               **readings(cell, served.arcs, truth, res.answered, seed)}
        print("control: " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    from yardstick.entry import prepare

    prepare()
    sys.exit(main())
