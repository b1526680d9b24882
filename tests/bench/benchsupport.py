"""Shared set-up of the benchmark's CPU tests: a copy of the benchmark
whose configurations are cut to a size the interpreter runs in seconds,
each by the ``cpu_test`` block of its own file."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

def copy_benchmark(dst) -> str:
    """``BENCHMARK.json`` and ``bench/`` copied to ``dst``; returns it."""
    dst = str(dst)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def tiny_benchmark(dst) -> str:
    """A copy of the benchmark cut to its CPU test size (:func:`shrink`)."""
    return shrink(copy_benchmark(dst))


def shrink(root) -> str:
    """Cut the benchmark at ``root`` to its CPU test size: every
    configuration's graph and batch to its file's ``cpu_test`` block,
    closed loops to one batch of clients, and open loops sped up so that
    requests share batches."""
    bench = load(root, "BENCHMARK.json")
    batch = {}
    for entry in bench["configs"]:
        conf = load(root, entry["file"])
        conf["graph"].update(conf["cpu_test"]["graph"])
        conf["server"]["batch"] = batch[entry["name"]] = \
            conf["cpu_test"]["batch"]
        dump(root, entry["file"], conf)
    for w in bench["workloads"]:
        path = os.path.join("bench", "traffic", w["traffic"] + ".json")
        mix = load(root, path)
        if mix["loop"] == "closed":
            mix["clients"] = batch[w["config"]]
        else:
            mix["rate_per_s"] = 1000.0
        dump(root, path, mix)
    return root


def load(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def dump(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f)
