"""One run of one cell: set up, measure, check, print.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Require a TPU with as many chips as the cell asks for; there is no
   CPU fallback.
2. Set up: the persistent compile cache at ``<checkout>/.jax_cache``;
   the configuration's graph from its own generator; the served index
   (``build_served_index``); ``QueryEngine`` and ``QueryServer`` with
   the configuration's settings; one call of the cell's program at the
   served batch shape, which compiles it.
3. Measure for ``--seconds`` with the traffic mix's loop; with
   ``--trace 1`` under the profiler.
4. Read the device's peak memory, free the program's state, and compare
   a sample of the answers with the reference.
5. Print the result as the last line of standard output, and the
   numbers compared, each with its limit, as the last lines of standard
   error.

``setup_s`` runs from the process's start to the window's.  Everything
else a run learns goes on earlier lines.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from typing import List, Optional

import numpy as np

from . import check, graphs, loops, spec, stats, tracing, traffic
from .reference import Reference

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Readings:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    cell: spec.Cell
    device_kind: str
    trace: Optional[dict]       # tracing.reduce of the window
    spans: List[dict]           # the program's Tracer spans
    batches: int                # batches the server ran in the window
    batch_size: int
    index: object               # the served HoDIndex (plan shapes)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(what: str, **fields) -> None:
    print(f"{what}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require_chips(jax, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def _warm(engine, mode: str, batch: int) -> None:
    """One call of the cell's program at the served batch shape."""
    import jax

    zeros = np.zeros(batch, np.int32)
    if mode == "p2p":
        jax.block_until_ready(engine.p2p(zeros, zeros))
    else:
        jax.block_until_ready(engine.ssd(zeros))


def run(args: argparse.Namespace, t_start: float, root: str = spec.ROOT,
        require_chip: bool = True) -> dict:
    """One run; returns the result line's object."""
    cell = spec.resolve(args.workload, root)
    mix = traffic.validate(dict(cell.traffic))
    seed = abs(int(args.seed))

    import jax

    imported = time.perf_counter()
    devices = (require_chips(jax, cell.chips) if require_chip
               else jax.devices())
    say("startup", imports_s=f"{imported - t_start:.3f}",
        chip_init_s=f"{time.perf_counter() - imported:.3f}")
    compiles: List[float] = []

    def on_event(name: str, secs: float, **kw) -> None:
        if name == COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run(args, t_start, cell, mix, seed, devices, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@dataclasses.dataclass
class Served:
    """A configuration set up and warm: its data, index and server."""

    arcs: graphs.Arcs
    index: object
    engine: object
    server: object
    tracer: object
    timings: dict


def setup(cell: spec.Cell, mode: str, trace: bool,
          t_start: Optional[float] = None) -> Served:
    """Generate, build and serve ``cell``'s configuration, and run the
    ``mode`` program once at the served batch shape.  ``startup_s`` is
    the time from ``t_start`` (the process's start) to here: imports and
    the chip's initialisation."""
    import jax

    from repro.core import QueryEngine, from_edges
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import QueryServer, build_served_index
    from repro.obs.trace import Tracer

    conf = cell.config
    say("compile cache", dir=enable_compile_cache())
    timings = {}
    if t_start is not None:
        timings["startup_s"] = time.perf_counter() - t_start
    t = time.perf_counter()
    arcs = graphs.generate(conf["graph"], cell.root)
    timings["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ix, _ = build_served_index(from_edges(arcs.n, arcs.src, arcs.dst,
                                          arcs.w))
    timings["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = QueryEngine(ix, **conf["engine"])
    jax.block_until_ready(engine._core)
    timings["engine_init_s"] = time.perf_counter() - t
    srv = conf["server"]
    tracer = Tracer() if trace else None
    server = QueryServer(engine, batch_size=srv["batch"],
                         max_wait_ms=srv["max_wait_ms"],
                         cache_entries=srv["cache_entries"],
                         scheduler=srv["scheduler"], mode=mode,
                         tracer=tracer)
    t = time.perf_counter()
    _warm(engine, mode, srv["batch"])
    timings["first_call_s"] = time.perf_counter() - t
    say("setup", nodes=arcs.n, arcs=arcs.src.shape[0], levels=ix.n_levels,
        core=ix.n_core, core_mode=engine.core_mode,
        **{k: f"{v:.3f}" for k, v in timings.items()})
    return Served(arcs, ix, engine, server, tracer, timings)


def drive(served: Served, mix: dict, seed: int, seconds: float):
    """Run the mix's loop against the server for ``seconds``."""
    degree = served.arcs.out_degree()
    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, degree, seed, seconds)
        return loops.open_loop(served.server, mix["mode"], sched.due,
                               sched.requests)
    return loops.closed_loop(served.server, mix["mode"],
                             traffic.closed_stream(mix, degree, seed),
                             int(mix["clients"]), seconds)


@contextlib.contextmanager
def _window_gc():
    """Freeze what set-up left behind, so that no collection inside the
    ``with`` body walks it, and yield the seconds of each collection
    made there."""
    pauses: List[float] = []
    started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - started[0])

    gc.collect()
    gc.freeze()
    gc.callbacks.append(on_gc)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(on_gc)
        gc.unfreeze()


def _run(args, t_start, cell, mix, seed, devices, compiles) -> dict:
    conf = cell.config
    srv = conf["server"]
    kind = devices[0].device_kind
    served = setup(cell, mix["mode"], bool(args.trace), t_start)
    server, tracer, ix = served.server, served.tracer, served.index
    say("compiles", in_setup=len(compiles),
        compile_s=f"{sum(compiles):.3f}")

    n_compiles = len(compiles)
    batches0 = server.stats.batches
    setup_s = time.perf_counter() - t_start
    with (tracing.capture() if args.trace
          else contextlib.nullcontext({})) as captured, \
            _window_gc() as pauses:
        result = drive(served, mix, seed, args.seconds)
    batches = server.stats.batches - batches0
    window_compiles = len(compiles) - n_compiles
    gap, gap_at = stats.longest_gap(result)
    say("window", requests=len(result.answered), batches=batches,
        span_s=f"{result.end - result.start:.4f}",
        result_cache_hits=server.stats.cache_hits,
        compiles_in_window=window_compiles,
        longest_answer_gap_ms=f"{gap * 1e3:.1f}", at_s=f"{gap_at:.2f}",
        gc_pauses=len(pauses), gc_s=f"{sum(pauses):.4f}",
        **({k: f"{v:.3f}" for k, v in stats.lateness(result).items()}
           if mix["loop"] == "open" else {}))
    if window_compiles:
        raise RuntimeError(f"{window_compiles} programs compiled inside "
                           "the measured window")

    mem = devices[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    say("memory", peak_bytes_in_use=peak,
        bytes_limit=mem.get("bytes_limit", "not reported"))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}

    names = [m["name"] for m in cell.end_to_end if m["name"] != "setup_s"]
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    breakdown = None
    if args.trace:
        reduced = tracing.reduce(captured["trace"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]
        readings = Readings(cell, kind, reduced,
                            tracer.spans() if tracer else [], batches,
                            srv["batch"], ix)
        for name, read in spec.readers(cell).items():
            value = read(readings)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        if mix["loop"] == "open":
            values = stats.open_loop_metrics(result, mix["mode"], names)
        else:
            values = stats.closed_loop_metrics(result, names)
        values["setup_s"] = setup_s
        metrics = {k: {"value": values[k], "unit": units[k]}
                   for k in ["setup_s"] + names}

    # The reference runs with the program's device state freed.
    arcs = served.arcs
    del served, server, ix
    gc.collect()
    t = time.perf_counter()
    comparison = check.compare(mix["mode"], result.answered,
                               Reference(arcs),
                               int(conf["check"][f"{mix['mode']}_answers"]),
                               seed)
    say("check", checked=comparison.checked,
        reference_s=f"{time.perf_counter() - t:.3f}")

    out = {"correct": comparison.correct,
           "attempted": len(result.answered), "failed": result.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = comparison.report()
    return out


def main(argv=None, t_start: Optional[float] = None,
         root: str = spec.ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        out = run(args, t_start, root)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    for name, number in out["compared"].items():
        print(f"compared {name} {number['value']} limit {number['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0
