#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, for many seeds in one
process.

    python3 bench/control.py --workload <name> --seeds 11 12 13 [--program]
        [--readings control bf16 half]

For each seed the plain reference follows the cell's first three rounds in
the precision the configuration states (``sound``), and each reading below
is compared with it by the numbers that decide ``correct`` (``--readings``
picks among the last three; all by default):

* ``program`` (with ``--program``): the program's own first three rounds,
  driven as ``bench/run.py`` drives them, through one compiled round for
  every seed: the sound runs that set a limit's lower reading;
* ``control``: the reference with its weights stored one precision lower
  (float8 e4m3 for bfloat16);
* ``bf16``: the reference computing with bfloat16 matmul operands and
  activations, as the program does: the gap the program's precision alone
  explains;
* ``half``: the reference with the second half of the cohort left out and
  the mean taken over the rest.

A fault that leaves the state unchanged reads 1 on both update numbers by
construction and needs no run.  Prints one JSON line per seed, with the
seconds each reading took.  Runs on the chip at the cell's own size.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, "out", "jax_cache")
READINGS = ["control", "bf16", "half"]


def lower_store(dtype):
    import jax.numpy as jnp
    return {jnp.dtype(jnp.float32): jnp.bfloat16,
            jnp.dtype(jnp.bfloat16): jnp.float8_e4m3fn,
            jnp.dtype(jnp.float16): jnp.float8_e4m3fn}[jnp.dtype(dtype)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--readings", nargs="+", default=READINGS,
                    choices=READINGS)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import gc
    import time

    import jax
    import jax.numpy as jnp
    from benchlib import build as build_mod
    from benchlib import compare, fl_ref, harness, spec

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    resolved = spec.resolve(args.workload, ROOT)
    devices = jax.devices()
    cell = build_mod.build(resolved, devices)
    dtype = cell.model.cfg.dtype
    inputs = build_mod.ReferenceInputs(cell.layout, cell.data_fn, dtype)
    refs = {name: fl_ref.Reference(cell.family, cell.config["sizes"],
                                   cell.traffic, store, compute)
            for name, store, compute in (
                ("sound", dtype, jnp.float32),
                ("control", lower_store(dtype), jnp.float32),
                ("bf16", dtype, jnp.bfloat16))
            if name == "sound" or name in args.readings}
    for seed in args.seeds:
        out, seconds = {"workload": args.workload, "seed": seed}, {}
        t = time.perf_counter()

        def lap(name):
            nonlocal t
            now = time.perf_counter()
            seconds[name], t = now - t, now

        prog = None
        if args.program:
            state = build_mod.init_state(cell, seed)
            state, snapshot = harness.first_rounds(cell, state)
            del state
            gc.collect()
            prog = compare.program_readings(*snapshot)
            lap("program")
        with jax.default_device(devices[0]):
            sound = harness.follow(refs["sound"], inputs, seed)
            lap("sound")
            if prog is not None:
                out["program"] = compare.numbers(prog, sound)
            for name in args.readings:
                fault = "half" if name == "half" else None
                got = harness.follow(refs.get(name, refs["sound"]), inputs,
                                     seed, fault)
                out[name] = compare.numbers(got, sound)
                lap(name)
        print(json.dumps({**out, "seconds": seconds}), flush=True)


if __name__ == "__main__":
    main()
