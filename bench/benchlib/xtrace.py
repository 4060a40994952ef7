"""Reduce a profiler trace of the window to the numbers the readers need.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
record: the device operations of each chip (HLO instruction name, start and
duration in ns; synchronous and asynchronous ones), the full HLO text of
custom calls (their operand and result shapes give their bytes), and the benchmark's own host spans (``bench.dispatch``,
``bench.block``, ``bench.readback``).  ``reduce`` turns
such a record into busy and idle time, the operations that took most time
and the longest idle gaps, named by the host span they fell in.  The record
is plain JSON, so a small recorded one (``bench/testdata``) checks the
reduction without a chip.
"""
from __future__ import annotations

import glob
import os
import shutil

HOST_PREFIX = "bench."
OPS_LINES = ("XLA Ops", "Async XLA Ops")
# operations whose events enclose their bodies' operations: they count
# towards busy time but not in the table of the operations that took most
CONTAINERS = ("while", "conditional", "call")


def clear(profile_dir):
    shutil.rmtree(profile_dir, ignore_errors=True)


def short_name(text: str) -> str:
    """``"%sort.19 = (f32[..]) sort(..)"`` -> ``"sort.19"``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(profile_dir) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, texts, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.split(":")[2].split(" ")[0]
            ops = []
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for ev in line.events:
                    name = short_name(ev.name)
                    ops.append([name, int(ev.start_ns), int(ev.duration_ns)])
                    if "custom-call(" in ev.name:
                        texts[name] = ev.name
            devices[dev] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "texts": texts,
            "host": sorted(host, key=lambda h: h[1])}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window_of(record) -> tuple:
    """The measured window: from the first dispatch to the end of the
    metrics read-back, in ns."""
    host = record["host"]
    starts = [s for n, s, d in host if n == HOST_PREFIX + "dispatch"]
    ends = [s + d for n, s, d in host]
    return min(starts), max(ends)


def reduce(record, n_devices: int, top: int = 10) -> dict:
    lo, hi = window_of(record)
    per_dev_busy, busy_sets, op_time = [], {}, {}
    for dev, ops in sorted(record["devices"].items()):
        iv = _clip([[s, s + d] for _, s, d in ops], lo, hi)
        busy = _union(iv)
        busy_sets[dev] = busy
        per_dev_busy.append(_length(busy))
        for name, s, d in ops:
            c = _clip([[s, s + d]], lo, hi)
            if c and name.split(".")[0] not in CONTAINERS:
                op_time[name] = op_time.get(name, 0) + _length(c)
    if not per_dev_busy:
        raise ValueError("the trace holds no device operations")
    n = max(n_devices, 1)
    window_ns = hi - lo
    first = busy_sets[sorted(busy_sets)[0]]
    gaps = _subtract([[lo, hi]], first)
    host = record["host"]

    def label(s, e):
        mid = (s + e) / 2
        inner = [h for h in host if h[1] <= mid < h[1] + h[2]]
        return min(inner, key=lambda h: h[2])[0] if inner else "outside"

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(per_dev_busy) / n / 1e9,
        "idle_share": 1.0 - sum(per_dev_busy) / n / window_ns,
        "top_ops": [[k, v / n / 1e9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in gaps],
        "ops": record["devices"], "texts": record.get("texts", {}),
        "lo": lo, "hi": hi,
    }
