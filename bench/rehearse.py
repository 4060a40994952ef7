#!/usr/bin/env python3
"""Compile a cell's round for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name>

Builds the cell as ``bench/run.py`` does, on the first device of a
described ``v5e:2x2`` topology, compiles one round and prints its
``memory_analysis()`` (the bytes on each device) as one JSON line: what the
compiler refuses here costs no chip time.  The Pallas kernels are compiled
by Mosaic, not interpreted, for the described chip.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchlib import build as build_mod, spec
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops._interpret = lambda: False          # compile Mosaic for the chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    resolved = spec.resolve(args.workload, ROOT)
    cell = build_mod.build(resolved, topo.devices)
    shapes = jax.eval_shape(cell.engine.init_fn, jax.random.PRNGKey(0))
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes)
    t = time.perf_counter()
    compiled = cell.runner._jit.lower(state, 1).compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    out = {f.name: getattr(ma, f.name) for f in dataclasses.fields(ma)} \
        if dataclasses.is_dataclass(ma) else {
            k: getattr(ma, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")}
    out = {k: v for k, v in out.items() if not k.startswith("host_")}
    out["footprint_gib"] = (out["argument_size_in_bytes"]
                            + out["output_size_in_bytes"]
                            + out["temp_size_in_bytes"]
                            - out["alias_size_in_bytes"]) / 2**30
    out["tpu_custom_calls"] = hlo.count('custom_call_target="tpu_custom_call"')
    out["compile_s"] = time.perf_counter() - t
    print(json.dumps({"workload": args.workload, **out}), flush=True)


if __name__ == "__main__":
    main()
