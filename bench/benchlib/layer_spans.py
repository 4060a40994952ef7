"""Host-clock spans around single layers of the round, after the window.

``local.update_ms`` times the dispatch body's local update and
``wire.codec_ms`` its wire rows (encode, error feedback, masks, decode), each
as its own jitted call on the cell's shapes for the clients one device
holds, with ``block_until_ready``.  A call shorter than a quarter second is
repeated and the time divided, since the host clock is good to about half a
millisecond.
"""
from __future__ import annotations

import time

import jax

from benchlib import traffic, weights

MIN_SPAN_S = 0.25


def _time(fn, args_fn, log, what):
    out = jax.block_until_ready(fn(*args_fn(None)))      # compiles
    n, t0 = 0, time.perf_counter()
    while True:
        out = jax.block_until_ready(fn(*args_fn(out)))
        n += 1
        if time.perf_counter() - t0 >= MIN_SPAN_S and n >= 2:
            break
    ms = (time.perf_counter() - t0) / n * 1e3
    log(f"[bench] {what}: {ms:.4f} ms per call over {n} calls")
    return out, ms


def measure(cell, seed, log) -> dict:
    from repro.core.aggregation import comm_state_init
    from repro.core.engine import ledger_terms, make_dispatch

    chips = len(cell.devices)
    C = cell.traffic["clients"] // chips
    _, up, down = ledger_terms(cell.model, cell.fl)
    dispatch = make_dispatch(cell.model, cell.fl, up, down, C, chunk=512)
    with jax.default_device(cell.devices[0]):
        params = jax.jit(lambda k: weights.to_tree(
            weights.make(cell.layout, k, cell.model.cfg.dtype),
            cell.model.abstract_params()))(weights.weight_key(seed))
        batch = jax.jit(cell.data_fn)(
            jax.numpy.int32(traffic.first_round(seed)))
        mb = {k: v[:C] for k, v in dispatch.model_batch(batch).items()}
        key = jax.random.PRNGKey(0)
        local = jax.jit(dispatch.local_update)
        (deltas, _, _), local_ms = _time(
            local, lambda _: (params, mb, key), log, "local update")
        comm = (comm_state_init(up, params, C) if up.stateful else None)
        wire = jax.jit(dispatch.wire_rows, donate_argnums=(0, 1))
        carry = [deltas, comm]
        del deltas

        def wire_args(out):
            if out is not None:
                carry[0], carry[1] = out
            return carry[0], carry[1], key
        _, wire_ms = _time(wire, wire_args, log, "wire rows")
    return {"local.update_ms": local_ms, "wire.codec_ms": wire_ms}
