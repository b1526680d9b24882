"""Engine layer: share of the SSD batches' labels left at +inf, in %,
from the ``unreachable`` tag of the engine's phase spans (the real
nodes still at +inf, summed over a batch's real rows) over ``fill``
times the graph's nodes.  The answers are exact, so on fixed batches
this is a property of the graph that no correct change moves: it shows
how much of a directed cell's answer is unreachable, and a program
that stopped answering +inf (a symmetrized graph, a lost arc
direction) would read lower."""
from yardstick.phases import _batch_spans


def read(r):
    tags = [s["args"] for s in _batch_spans(r, "engine.readback")
            if "unreachable" in s["args"]]
    if not tags:
        return None
    return (100.0 * sum(t["unreachable"] for t in tags)
            / (sum(t["fill"] for t in tags) * r.index.n))
