"""Server layer: mean coalescing wait of a P2P batch, in ms.

``QueryServer`` records one ``coalesce.wait`` span per flushed batch
(the oldest rider's wait from submit to flush) on the ``Tracer`` the
benchmark passes it in a traced run.
"""


def read(r):
    waits = [(s["t1"] - s["t0"]) / 1e6 for s in r.spans
             if s["name"] == "coalesce.wait"]
    return sum(waits) / len(waits) if waits else None
