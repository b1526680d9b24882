#!/usr/bin/env python3
"""Compile each configuration's served programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/aot.py

A rehearsal without the chip: for every configuration in
``BENCHMARK.json`` it builds the served index on the host and compiles
the SSD and P2P programs that ``QueryServer`` runs, at the configured
batch, for one chip of a described ``v5e:2x2`` topology, then prints
each program's ``memory_analysis()``.  Sizes only: nothing runs, so it
gives no times.  The engine is built in ``dijkstra`` core mode so that
the host never materializes the dense core adjacency; the programs are
compiled in the core mode the served engine picks (``bellman`` past
``pack_index``'s closure limit), with the core matrix as a shape.
"""
import functools
import json
import os
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import QueryEngine, from_edges
    from repro.launch.serve import build_served_index
    from yardstick import graphs, spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for entry in spec.load_benchmark()["configs"]:
        with open(os.path.join(spec.ROOT, entry["file"])) as f:
            conf = json.load(f)
        arcs = graphs.generate(conf["graph"])
        ix, _ = build_served_index(from_edges(arcs.n, arcs.src, arcs.dst,
                                              arcs.w))
        eng = QueryEngine(ix, core_mode="dijkstra", interpret=False,
                          **conf["engine"])
        core_mode = "closure" if ix.core_closure.shape[0] else "bellman"
        batch = conf["server"]["batch"]
        plans = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng._plans)
        c = ix.n_core
        core = sds((c, c), jnp.float32)
        ends = sds((batch,), jnp.int32)
        programs = {"ssd": (eng._ssd_impl, (plans, core, ends)),
                    "p2p": (eng._p2p_impl, (plans, core, ends, ends))}
        for mode, (impl, operands) in programs.items():
            t = time.perf_counter()
            compiled = jax.jit(functools.partial(
                impl, core_mode=core_mode)).lower(*operands).compile()
            m = compiled.memory_analysis()
            print("aot: " + json.dumps({
                "config": entry["name"], "mode": mode, "batch": batch,
                "core": c, "core_mode": core_mode, "levels": ix.n_levels,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "code_bytes": m.generated_code_size_in_bytes,
                "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
                "compile_s": round(time.perf_counter() - t, 1)}),
                flush=True)
    return 0


if __name__ == "__main__":
    from yardstick.entry import prepare

    prepare()
    sys.exit(main())
