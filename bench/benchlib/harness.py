"""One run of one cell: set-up, the measured window, the check.

Set-up builds the compiled round with its state, drives it from the seed
through its first three rounds through the window's own call, and keeps the
weights after rounds 1 and 3 on the host for the check.  The window then
runs rounds back to back through ``RoundRunner.run`` until ``seconds`` have
passed.  After it, the program's state is freed and the reference follows
the same three rounds from the same weights and batches (``compare``).
"""
from __future__ import annotations

import gc
import os
import time

import jax
import numpy as np

from benchlib import build as build_mod
from benchlib import compare, fl_ref, layer_spans, metrics_io, xtrace
from benchlib import weights

CHECK_ROUNDS = 3
OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out")


def _host(params) -> dict:
    return {k: np.asarray(v) for k, v in
            weights.from_tree(jax.device_get(params)).items()}


def _annotate(name):
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def first_rounds(cell, state, phase=lambda what: None):
    """Drive the compiled round from ``state`` through its first
    ``CHECK_ROUNDS`` rounds through the window's own call.  Returns the
    state and what the check reads of the program, kept on the host: the
    weights before round 1, after it and after the last, and the round
    losses (``compare.program_readings`` takes them, after the window)."""
    p0 = _host(state.params)
    host, losses = {}, []
    for r in range(1, CHECK_ROUNDS + 1):
        state, m = cell.runner.run(state, 1)
        losses.append(float(m["loss"][0]))
        if r in (1, CHECK_ROUNDS):
            host[r] = _host(state.params)
        phase(f"round {r}")
    return state, (p0, host[1], host[CHECK_ROUNDS], losses)


def follow(ref, inputs, seed: int, fault=None) -> dict:
    """The reference's readings of the first ``CHECK_ROUNDS`` rounds from
    ``seed``, with ``fault`` planted in it."""
    params0, batch, key = inputs(seed)
    return ref.rounds(params0, batch, CHECK_ROUNDS, key, fault=fault)


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool,
             t_start: float, devices, log=print) -> dict:
    def phase(what):
        log(f"[bench] {time.perf_counter() - t_start:.3f} s: {what}")

    phase("start")
    cell = build_mod.build(resolved, devices)
    state = build_mod.init_state(cell, seed)
    phase("engine built, weights made")
    lowered = cell.runner._jit.lower(state, 1)
    phase("round lowered")
    compiled = lowered.compile()
    phase("round compiled or loaded from the cache")
    ma = compiled.memory_analysis()
    hbm = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    hlo = compiled.as_text() if trace else None
    del compiled
    log(f"[bench] chunk program: arguments {ma.argument_size_in_bytes}, "
        f"outputs {ma.output_size_in_bytes}, temporaries "
        f"{ma.temp_size_in_bytes}, aliased {ma.alias_size_in_bytes} bytes")

    state, snapshot = first_rounds(cell, state, phase)
    setup_s = time.perf_counter() - t_start
    compiles = cell.runner.cache_size()

    profile_dir = os.path.join(OUT, "profile", cell.name) if trace else None
    if trace:
        xtrace.clear(profile_dir)
        jax.profiler.start_trace(profile_dir)
    window_losses = []
    t0 = time.perf_counter()
    rounds = 0
    while True:
        with _annotate("dispatch"):
            state, m = cell.runner.run(state, 1)
        with _annotate("block"):
            jax.block_until_ready(m)
        window_losses.append(m["loss"])
        rounds += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    with _annotate("readback"):
        window_losses = [float(x[0]) for x in jax.device_get(window_losses)]
    if trace:
        jax.profiler.stop_trace()
    if cell.runner.cache_size() != compiles:
        raise RuntimeError("a chunk compiled inside the measured window")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in cell.devices)
    log(f"[bench] window: {rounds} rounds in {window_s:.4f} s; "
        f"memory_stats peak_bytes_in_use {peak}")

    del state, m
    gc.collect()
    spans = layer_spans.measure(cell, seed, log) if trace else {}
    phase("window (and layer spans) done")

    dtype = cell.model.cfg.dtype
    ref = fl_ref.Reference(cell.family, cell.config["sizes"], cell.traffic,
                           store=dtype)
    inputs = build_mod.ReferenceInputs(cell.layout, cell.data_fn, dtype)
    with jax.default_device(cell.devices[0]):
        got = follow(ref, inputs, seed)
    prog = compare.program_readings(*snapshot)
    del snapshot
    numbers = compare.numbers(prog, got, log)
    phase(f"reference followed; all numbers {numbers}")
    limits = cell.traffic["limits"]
    failed_rounds = sum(not np.isfinite(x)
                        for x in prog["losses"] + window_losses)
    correct = failed_rounds == 0 and all(
        numbers[k] <= limits[k] for k in limits)

    result = {"correct": bool(correct),
              "attempted": CHECK_ROUNDS + rounds,
              "failed": int(failed_rounds)}
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": int(peak)}
    round_s = window_s / rounds
    if not trace:
        values = {"round_s": round_s, "hbm_gib": hbm / 2**30,
                  "setup_s": setup_s}
        result["metrics"] = metrics_io.pick(resolved["end_to_end"], values)
    else:
        reduced = xtrace.reduce(xtrace.load(profile_dir), len(cell.devices))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        ctx = metrics_io.context(cell, hlo, spans, round_s, rounds)
        result["metrics"] = metrics_io.read_per_layer(
            resolved["per_layer"], reduced, ctx)
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["device"] = device
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    return result
