"""The readers of device time by the round program's own scopes
(``benchlib.scoped``): per hop and per wire stage on a hand-built trace and
HLO text with known answers, and the benchmark's earlier readers unchanged
beside them."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src"),
                os.path.dirname(os.path.abspath(__file__))]

from benchlib import spec, xtrace  # noqa: E402
from test_bench_harness import KERNEL_CALL  # noqa: E402


def _readers(names, reduced, ctx):
    return {m: spec.required_module("metrics", m).read(reduced, ctx)
            for m in names}


EXISTING = {"device.idle_share": 99.99704224986871,
            "step.mfu": 3.045685279187817, "local.update_ms": 412.5,
            "wire.codec_ms": 61.25, "wire_roofline": 3.9869427624529665,
            "pallas_roofline": 2605.815569197219}


def test_existing_readers_read_as_before():
    # the values the readers gave before the scoped readers came beside them
    from benchlib import metrics_io
    rec = json.load(open(os.path.join(BENCH, "testdata", "trace.json")))
    rec.pop("expected")
    rec["texts"] = {"add_clamp_fusion.2": KERNEL_CALL}
    ctx = {"peaks": metrics_io.peaks("TPU v5 lite"), "chips": 1,
           "round_s": 0.5, "rounds": 8, "round_flops": 3.0e12,
           "spans": {"local.update_ms": 412.5, "wire.codec_ms": 61.25},
           "pallas": {"add_clamp_fusion.2": "qsgd_quantize_blocked"},
           "wire_bytes": 2_000_000_000}
    got = _readers(EXISTING, xtrace.reduce(rec, 1), ctx)
    assert got == {k: pytest.approx(v, rel=1e-12)
                   for k, v in EXISTING.items()}


def _op(name, op_name, body):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = {body}{meta}"


_P = "jit(run_chunk)/while/body/closed_call/"
SCOPED_HLO = "\n".join([
    "HloModule jit_run_chunk, is_scheduled=true",
    "",
    "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
    "  %param_0 = f32[8]{0} parameter(0)",
    "  ROOT" + _op("multiply.1", _P + "hop.wire/vmap(stage.ef)/stage.topk/abs",
                   "f32[8]{0} multiply(%param_0, %param_0)")[1:],
    "}",
    "",
    # a loop the compiler made (a relayout in slices), no metadata
    "%wide.body (w: (u32[], f32[8])) -> (u32[], f32[8]) {",
    "  %w = (u32[], f32[8]{0}) parameter(0)",
    "  %gte.20 = f32[8]{0} get-tuple-element(%w), index=1",
    "  %gte.21 = u32[] get-tuple-element(%w), index=0",
    _op("dynamic-update-slice.10", None, "f32[8]{0} "
        "dynamic-update-slice(%gte.20, %gte.20, %gte.21)"),
    "  ROOT %tuple.22 = (u32[], f32[8]{0}) tuple(%gte.21, "
    "%dynamic-update-slice.10)",
    "}",
    "",
    "%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {",
    "  %arg = (s32[], f32[8]{0}) parameter(0)",
    "  %gte.0 = f32[8]{0} get-tuple-element(%arg), index=1",
    "  %gte.1 = s32[] get-tuple-element(%arg), index=0",
    _op("dot.4", _P + "hop.local_update/vmap(jvp())/dot_general",
        "f32[8]{0} multiply(%gte.0, %gte.0)"),
    # no metadata of its own: named by its fused root
    _op("fusion.1", None, "f32[8]{0} fusion(%dot.4), kind=kLoop, "
        "calls=%fused_computation.1"),
    _op("tuple.13", None, "(u32[], f32[8]{0}) tuple(%gte.1, %fusion.1)"),
    _op("while.11", None, "(u32[], f32[8]{0}) while(%tuple.13), "
        "condition=%wide.cond, body=%wide.body"),
    _op("gte.12", None, "f32[8]{0} get-tuple-element(%while.11), index=1"),
    _op("sort.2", _P + "hop.wire/vmap(stage.ef)/stage.topk/sort",
        "f32[8]{0} sort(%gte.12), dimensions={0}"),
    _op("add.3", _P + "hop.wire/vmap(stage.ef)/add",
        "f32[8]{0} add(%sort.2, %gte.0)"),
    _op("copy-start.5", None, "(f32[8]{0}, f32[8]{0}, u32[]) "
        "copy-start(%add.3)"),
    _op("copy-done.5", None, "f32[8]{0} copy-done(%copy-start.5)"),
    _op("fusion.7", _P + "hop.server_opt/add",
        "f32[8]{0} add(%copy-done.5, %gte.0)"),
    _op("dynamic-update-slice.6", _P[:-12] + "dynamic_update_slice",
        "f32[8]{0} dynamic-update-slice(%fusion.7, %gte.1)"),
    "  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(%gte.1, "
    "%dynamic-update-slice.6)",
    "}",
    "",
    "ENTRY %main (p: (s32[], f32[8])) -> (s32[], f32[8]) {",
    "  %p = (s32[], f32[8]{0}) parameter(0)",
    "  ROOT" + _op("while.9", "jit(run_chunk)/while",
                   "(s32[], f32[8]{0}) while(%p), condition=%cond, "
                   "body=%body")[1:],
    "}",
])


def _scoped_record():
    # host window [0, 210); the loop [0, 200) encloses: local update
    # [10, 40); top-k [40, 60) and [55, 90), overlapping; error feedback
    # [90, 100); an asynchronous copy [95, 151) over them; the server
    # optimizer [100, 120); the scan's own write [120, 130); a slice of the
    # compiler's relayout loop for top-k [130, 140); and one op after the
    # window
    return {"devices": {"0": [["while.9", 0, 200], ["dot.4", 10, 30],
                              ["fusion.1", 40, 20], ["sort.2", 55, 35],
                              ["while.11", 130, 10],
                              ["dynamic-update-slice.10", 130, 10],
                              ["add.3", 90, 10], ["copy-start.5", 95, 55],
                              ["copy-done.5", 150, 1], ["fusion.7", 100, 20],
                              ["dynamic-update-slice.6", 120, 10],
                              ["fusion.7", 300, 10]]},
            "host": [["bench.dispatch", 0, 10], ["bench.block", 10, 190],
                     ["bench.readback", 200, 10]]}


SCOPED = ("hop.local_update_ms", "hop.wire_ms", "hop.rest_ms",
          "device.unscoped_share", "wire.topk_ms", "wire.qsgd_ms",
          "wire.secagg_ms")


def test_scoped_readers_on_a_known_trace():
    from benchlib import scoped
    table = scoped.table(SCOPED_HLO)
    assert table["fusion.1"] == ("wire", "topk")
    assert table["copy-start.5"] == table["copy-done.5"] == ("wire", "ef")
    assert table["dynamic-update-slice.6"] == (None, None)
    assert table["dynamic-update-slice.10"] == ("wire", "topk")
    ctx = {"chips": 1, "rounds": 2, "scopes": table}
    got = _readers(SCOPED, xtrace.reduce(_scoped_record(), 1), ctx)
    ns = 1e-6 / 2                              # ns over the window -> ms
    assert got["hop.local_update_ms"] == pytest.approx(30 * ns)
    assert got["hop.wire_ms"] == pytest.approx(70 * ns)
    assert got["hop.rest_ms"] == pytest.approx(20 * ns)
    assert got["wire.topk_ms"] == pytest.approx(60 * ns)
    assert got["device.unscoped_share"] == pytest.approx(100 * 10 / 130)
    assert got["wire.qsgd_ms"] is None and got["wire.secagg_ms"] is None
    # the sums close on the synchronous busy time, copies left out
    t = scoped.times(xtrace.reduce(_scoped_record(), 1), ctx)
    assert t["busy_ms"] == pytest.approx(130 * ns)
    assert sum(t["hop"].values()) == pytest.approx(t["busy_ms"], rel=1e-12)
    assert t["async"] == {"wire": pytest.approx(56 * ns)}
    # a program without the scopes: nothing to read
    ctx["scopes"] = {}
    got = _readers(SCOPED, xtrace.reduce(_scoped_record(), 1), ctx)
    assert set(got.values()) == {None}


def test_scoped_readers_find_the_harness_hlo():
    # the harness's context holds no scope table: the readers take the
    # compiled text that run_cell holds, and find nothing outside it
    from benchlib import scoped
    ctx = {"chips": 1, "rounds": 2}
    ns = {"__name__": "benchlib.harness", "read": _readers, "SCOPED": SCOPED,
          "HLO": SCOPED_HLO}
    exec("def run_cell(reduced, ctx):\n"
         "    hlo = HLO\n"
         "    return read(SCOPED, reduced, ctx)\n", ns)
    got = ns["run_cell"](xtrace.reduce(_scoped_record(), 1), ctx)
    assert got["hop.wire_ms"] == pytest.approx(70 * 1e-6 / 2)
    assert got["wire.topk_ms"] == pytest.approx(60 * 1e-6 / 2)
    got = _readers(SCOPED, xtrace.reduce(_scoped_record(), 1), dict(ctx))
    assert set(got.values()) == {None}
