"""End-to-end values into the result line, and the per-layer readers.

Each per-layer metric has a reader ``bench/metrics/<name>.py`` with
``read(trace, ctx) -> float | None``: ``trace`` is ``xtrace.reduce``'s
record of the traced window and ``ctx`` the run's context (below).  A
reader that finds nothing to read returns None, and the metric is left out
of the line.
"""
from __future__ import annotations

import os
import re

from benchlib import fl_ref
from benchlib import spec as specs

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def pick(defs, values) -> dict:
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in defs}


def peaks(kind: str) -> dict:
    table = specs.load_json(os.path.join(specs.BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def shapes_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def pallas_calls(hlo: str) -> dict:
    """``{instruction: kernel}`` for every Mosaic (Pallas) custom call in a
    compiled HLO text; the kernel is the function ``pallas_call`` wrapped."""
    out = {}
    for line in (hlo or "").splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        name = name.lstrip("%")
        kernel = re.search(r"jit\((\w+)\)\)?/pallas_call", line)
        out[name] = kernel.group(1) if kernel else name
    return out


def call_bytes(text: str) -> tuple:
    """(bytes in, bytes out) of one HLO instruction's text: its operands'
    shapes and its result's."""
    head, _, rest = text.partition(" = ")
    op = rest.find("(", rest.find(" custom-call") if " custom-call" in rest
                   else 0)
    depth, end = 0, len(rest)
    for i in range(op, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            end = i
            break
    return shapes_bytes(rest[op:end]), shapes_bytes(rest[:op])


def wire_bytes(cell, clients: int) -> int:
    """Least HBM bytes of the uplink wire for ``clients`` clients: read the
    f32 updates, read and write the error-feedback residual where it is on,
    write the decoded f32 rows and the payload."""
    fl = cell.traffic["fl"]
    spec = fl["uplink_compressor"]
    ef = fl_ref.uses_error_feedback(spec, fl)
    per_client = 0
    for shape, _ in cell.layout.values():
        n = 1
        for d in shape:
            n *= d
        per_client += 4 * n + (8 * n if ef else 0) + 4 * n
        per_client += fl_ref.payload_bytes(spec, n, fl)
    return clients * per_client


def context(cell, hlo, spans, round_s, rounds) -> dict:
    t = cell.traffic
    sizes = cell.config["sizes"]
    chips = len(cell.devices)
    flops = (t["clients"] * t["batch_per_client"] * t["fl"]["local_steps"]
             * cell.family.step_flops(sizes, t["seq_len"]))
    return {"peaks": peaks(cell.devices[0].device_kind), "chips": chips,
            "round_s": round_s, "rounds": rounds, "round_flops": flops,
            "spans": spans, "pallas": pallas_calls(hlo),
            "wire_bytes": wire_bytes(cell, t["clients"] // chips)}


def read_per_layer(defs, reduced, ctx) -> dict:
    out = {}
    for m in defs:
        value = specs.required_module("metrics", m["name"]).read(reduced, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
