#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``).  The run builds
the federated round through the program's normal path, makes weights and
batches from the seed, warms up, measures rounds for ``--seconds`` and then
checks the first three rounds against the plain reference.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones read from a profiler trace of the window), ``device`` and the
numbers compared with their limits (``check``).  Those numbers are also the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, "out", "jax_cache")


def _fail(msg, code):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail("the program (src/repro) is not in this checkout", 2)
    # the compile cache lives at a fixed place inside the checkout, so that
    # only the first run of a cell compiles and two checkouts share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

    from benchlib import spec
    resolved = spec.resolve(args.workload, ROOT)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        _fail(f"no TPU: JAX_PLATFORMS is {platforms!r}", 3)

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX sees platform {devices[0].platform!r}", 3)
    if len(devices) < resolved["chips"]:
        _fail(f"{args.workload} needs {resolved['chips']} chips, JAX sees "
              f"{len(devices)}", 3)
    _log(f"[bench] {args.workload} seed {args.seed}: {len(devices)} x "
         f"{devices[0].device_kind}, jax {jax.__version__}")

    from benchlib import harness
    result = harness.run_cell(resolved, args.seed, args.seconds,
                              bool(args.trace), T_START, devices, log=_log)
    for k, v in result["check"].items():
        _log(f"[check] {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
