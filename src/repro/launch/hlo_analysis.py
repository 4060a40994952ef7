"""Trip-count-aware roofline terms from compiled (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts ``while`` bodies **once**, which
under-counts scan-over-layers models by ~num_layers x (verified in
EXPERIMENTS.md §Dry-run notes). This module re-derives the three roofline
inputs directly from ``compiled.as_text()`` with loop trip counts applied:

  * flops            — 2·|out|·K per ``dot`` (contraction size K from operand
                       shapes), x trip counts. Elementwise flops are ignored
                       (transformer compute is >97% dot-shaped; documented).
  * hbm bytes        — Σ (result + operand) buffer bytes over *materialised*
                       top-level instructions (post-fusion HLO materialises
                       only fusion results; fusion internals are free), x trips.
                       An upper-ish proxy: buffer reuse isn't modelled.
  * collective bytes — per collective op, wire bytes per device:
                       all-gather: result;  all-reduce: 2·result (ring);
                       reduce-scatter: operand;  all-to-all: result;
                       collective-permute: result.  x trips.

Trip counts: for each ``while``, the largest integer ``constant(N)`` in its
condition computation (loop bounds dominate; induction starts are 0/1).
Everything is per-device (the text is the per-device SPMD program).
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->")
_OPCODE_RE = re.compile(r"^\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, []
    dt, dims = m.groups()
    dims = [int(d) for d in dims.split(",")] if dims else []
    return dt, dims


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    text: str
    operands: list


def parse_computations(hlo: str):
    """-> {comp_name: [Instr]}; also per-comp instr type map."""
    comps, cur, cur_name = {}, None, None
    for line in hlo.splitlines():
        hdr = _COMP_HDR_RE.match(line.strip()) if "{" in line else None
        if hdr and not line.lstrip().startswith("%param"):
            cur_name = hdr.group(1)
            cur = []
            comps[cur_name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        om = _OPCODE_RE.match(rest)
        opcode = om.group(1) if om else ""
        # type string = everything before the opcode token
        tpos = rest.find(opcode + "(") if opcode else -1
        type_str = rest[:tpos] if tpos > 0 else rest
        operands = re.findall(r"(%[\w.\-]+)", rest[tpos:]) if tpos > 0 else []
        cur.append(Instr(name, type_str, opcode, rest, operands))
    return comps


def _trip_count(cond_instrs) -> int:
    best = 1
    for ins in cond_instrs:
        for c in re.findall(r"constant\((\d+)\)", ins.text):
            best = max(best, int(c))
    return best


def _group_stride(text: str) -> int:
    """Stride between the first two members of the first replica group
    (1 for contiguous/model-axis groups; >= |model| for client-axis)."""
    m = re.search(r"replica_groups=\{\{(\d+),(\d+)", text)
    if m:
        return abs(int(m.group(2)) - int(m.group(1)))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  text)
    if not m:
        return 1
    g, s, dims, perm = m.groups()
    import numpy as _np
    dims = [int(d) for d in dims.split(",")]
    arr = _np.arange(int(_np.prod(dims))).reshape(dims)
    if perm:
        arr = arr.transpose([int(p) for p in perm.split(",")])
    arr = arr.reshape(int(g), int(s))
    if arr.shape[1] < 2:
        return 1
    return int(abs(arr[0, 1] - arr[0, 0]))


def _dot_flops(ins: Instr, types: dict) -> float:
    _, out_dims = _shape_elems(ins.type_str)
    out_n = math.prod(out_dims) if out_dims else 1
    mdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.text)
    if not mdims or not ins.operands:
        return 2.0 * out_n                      # fallback
    lhs = types.get(ins.operands[0])
    if lhs is None:
        return 2.0 * out_n
    _, lhs_dims = _shape_elems(lhs)
    k = 1
    for d in (mdims.group(1).split(",") if mdims.group(1) else []):
        di = int(d)
        if di < len(lhs_dims):
            k *= lhs_dims[di]
    return 2.0 * out_n * k


@dataclasses.dataclass
class HLOStats:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_client_bytes: float = 0.0   # strided replica groups = client (data/
                                     # pod) axis: the FL aggregation wire
    coll_model_bytes: float = 0.0    # contiguous groups = model (TP) axis
    coll_by_type: dict = dataclasses.field(default_factory=dict)
    coll_count: int = 0

    def add(self, other, mult=1.0):
        self.flops += mult * other.flops
        self.hbm_bytes += mult * other.hbm_bytes
        self.coll_bytes += mult * other.coll_bytes
        self.coll_client_bytes += mult * other.coll_client_bytes
        self.coll_model_bytes += mult * other.coll_model_bytes
        self.coll_count += int(mult * other.coll_count)
        for k, v in other.coll_by_type.items():
            self.coll_by_type[k] = self.coll_by_type.get(k, 0.0) + mult * v


def analyze(hlo_text: str) -> HLOStats:
    comps = parse_computations(hlo_text)
    types_per_comp = {c: {i.name: i.type_str for i in instrs}
                      for c, instrs in comps.items()}
    memo = {}

    def comp_stats(cname: str) -> HLOStats:
        if cname in memo:
            return memo[cname]
        memo[cname] = HLOStats()            # cycle guard
        st = HLOStats()
        types = types_per_comp.get(cname, {})
        for ins in comps.get(cname, []):
            if ins.opcode == "dot":
                st.flops += _dot_flops(ins, types)
            coll = next((c for c in _COLLECTIVES
                         if ins.opcode.startswith(c)), None)
            if coll:
                rb = _shape_bytes(ins.type_str)
                wire = {"all-reduce": 2 * rb, "all-gather": rb,
                        "reduce-scatter": 0.0, "all-to-all": rb,
                        "collective-permute": rb}[coll]
                if coll == "reduce-scatter":
                    ops_b = sum(_shape_bytes(types.get(o, ""))
                                for o in ins.operands)
                    wire = ops_b
                st.coll_bytes += wire
                st.coll_count += 1
                st.coll_by_type[coll] = st.coll_by_type.get(coll, 0.0) + wire
                # axis attribution: model is the minor-most mesh axis, so a
                # collective whose group members stride by >= |model| runs
                # over the client (data/pod) axes — the FL wire. Group
                # geometry is reconstructed exactly from either the explicit
                # `{{0,16,...}}` list or the `[G,S]<=[dims]T(perm)` iota form.
                if _group_stride(ins.text) >= 16:
                    st.coll_client_bytes += wire
                else:
                    st.coll_model_bytes += wire
            # ---- recurse into called computations -------------------------
            mwhile = re.search(r"condition=(%[\w.\-]+), body=(%[\w.\-]+)",
                               ins.text)
            if mwhile:
                # while: children fully counted x trips; the while op itself
                # aliases its carry — no HBM bytes of its own.
                cond, body = mwhile.groups()
                trips = _trip_count(comps.get(cond, []))
                st.add(comp_stats(body), trips)
                st.add(comp_stats(cond), trips)
                continue
            called = None
            for attr in ("calls", "to_apply"):
                mcall = re.search(attr + r"=(%[\w.\-]+)", ins.text)
                if mcall:
                    called = mcall.group(1)
            mbr = re.search(r"branch_computations=\{([^}]*)\}", ins.text)
            branches = (re.findall(r"%[\w.\-]+", mbr.group(1))
                        if mbr else [])
            if ins.opcode in ("call", "conditional", "async-start"):
                for b in ([called] if called else []) + branches:
                    st.add(comp_stats(b), 1.0)
                continue
            if called:
                # fusion / reduce / map bodies: their flops+collectives are
                # real, but their internals never touch HBM — only the fusion
                # op's own operands/results do (counted below).
                child = comp_stats(called)
                st.flops += child.flops
                st.coll_bytes += child.coll_bytes
                st.coll_count += child.coll_count
                for k, v in child.coll_by_type.items():
                    st.coll_by_type[k] = st.coll_by_type.get(k, 0.0) + v

            # ---- HBM proxy -------------------------------------------------
            if ins.opcode in ("parameter", "constant", "tuple",
                              "get-tuple-element", "bitcast", "iota",
                              "after-all", "partition-id", "replica-id"):
                continue
            if ins.opcode in ("dynamic-slice", "slice", "gather"):
                st.hbm_bytes += 2 * _shape_bytes(ins.type_str)   # read+write
            elif ins.opcode in ("dynamic-update-slice", "scatter"):
                upd = (types.get(ins.operands[1], "")
                       if len(ins.operands) > 1 else "")
                st.hbm_bytes += 2 * _shape_bytes(upd)            # in-place
            elif ins.opcode == "broadcast":
                st.hbm_bytes += (_shape_bytes(ins.type_str)
                                 + sum(_shape_bytes(types.get(o, ""))
                                       for o in ins.operands))
            else:
                st.hbm_bytes += _shape_bytes(ins.type_str)
                st.hbm_bytes += sum(_shape_bytes(types.get(o, ""))
                                    for o in ins.operands)
        memo[cname] = st
        return st

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = re.search(r"ENTRY\s+(%[\w.\-]+)", line)
            if m:
                entry = m.group(1)
            break
    if entry is None:
        # fall back: largest computation
        entry = max(comps, key=lambda c: len(comps[c])) if comps else None
    return comp_stats(entry) if entry else HLOStats()


# ----------------------------------------------------- stage cross-checking

def name_stage_mismatch(stage_names, stage_table, measured: float,
                        expected_total: float = None,
                        rtol: float = 0.02) -> str:
    """Explain a collective-bytes mismatch in pipeline-stage terms.

    ``stage_names`` / ``stage_table`` come from the flight recorder's
    ``TelemetrySpec`` (``repro.obs.telemetry``, whose per-stage byte tables
    sum to the ledger's wire total by construction); ``measured`` is what
    the HLO actually moved (e.g. all-gather bytes over the client axis) and
    ``expected_total`` what the ledger bills (defaults to ``sum(table)``).
    Returns "" when they agree within ``rtol``; otherwise a message naming
    the stage whose byte share best explains the gap — the first thing to
    look at when a wire change breaks the HLO==ledger claim."""
    expected = (float(sum(stage_table)) if expected_total is None
                else float(expected_total))
    gap = measured - expected
    if expected > 0 and abs(gap) <= rtol * expected:
        return ""
    if not stage_table:
        return (f"collective bytes mismatch: measured {measured:.0f} vs "
                f"expected {expected:.0f} (no stage table to attribute)")
    # the stage whose byte weight is closest to the gap magnitude is the
    # most likely culprit (a stage dropped from / double-counted on the
    # wire); ties go to the largest share
    best = min(range(len(stage_table)),
               key=lambda i: (abs(abs(gap) - float(stage_table[i])),
                              -float(stage_table[i])))
    share = (100.0 * float(stage_table[best]) / expected if expected
             else 0.0)
    direction = "missing from" if gap < 0 else "over-counted on"
    return (f"collective bytes mismatch: measured {measured:.0f} vs "
            f"expected {expected:.0f} (gap {gap:+.0f}); closest stage: "
            f"'{stage_names[best]}' ({float(stage_table[best]):.0f}B/unit, "
            f"{share:.0f}% of the wire) — likely {direction} the "
            f"collective")


# ------------------------------------------------------------------ roofline

# Published per-chip peaks, keyed by jax's ``device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s of inter-chip interconnect (50 GB/s per link of four).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_gbps": 819e9,
                    "ici_gbps": 50e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline(stats: HLOStats, device_kind: str) -> dict:
    hw = peaks(device_kind)
    return {
        "compute_s": stats.flops / hw["flops_bf16"],
        "memory_s": stats.hbm_bytes / hw["hbm_gbps"],
        "collective_s": stats.coll_bytes / hw["ici_gbps"],
    }


def dominant(terms: dict) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k]).replace("_s", "")
