"""The benchmark's files resolve, its names keep to the contract, and its
yardstick (trace reduction, byte and FLOP counts, top-k) is right."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src"),
                os.path.dirname(os.path.abspath(__file__))]

from benchlib import fl_ref, spec, xtrace  # noqa: E402
from fault_cases import quick_compiles  # noqa: E402,F401

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCHMARK = spec.benchmark(ROOT)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    r = spec.resolve(workload, ROOT)
    assert spec.module("models", r["config"]["family"]) is not None
    for name, _, _ in fl_ref.parse(r["traffic"]["fl"]["uplink_compressor"]):
        assert spec.module("wire", name) is not None, name
    for m in r["per_layer"]:
        assert hasattr(spec.required_module("metrics", m["name"]), "read")
    assert {m["name"] for m in r["end_to_end"]} >= {"setup_s", "round_s"}
    assert r["per_layer"], "every cell reports a per-layer metric"
    assert r["traffic"]["limits"]
    assert set(r["traffic"]["limits"]) <= {"loss", "update1", "update3"}


def test_names_and_units_keep_to_the_contract():
    names = [c["name"] for c in BENCHMARK["configs"]] + CELLS + [
        m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    names += [w["config"] for w in BENCHMARK["workloads"]]
    names += [w["traffic"] for w in BENCHMARK["workloads"]]
    names += [k for c in BENCHMARK["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len(set(CELLS)) == len(CELLS)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCHMARK["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path)
        assert json.load(open(path))["reduced"] == c["reduced"]


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)


def _tiny_record():
    # one device; host spans dispatch [0,10), block [10,100), readback
    # [100,120); a loop [5,30) around ops [5,20), [15,30) (overlapping),
    # then ops [50,60), [55,70)
    return {"devices": {"0": [["while.7", 5, 25],
                              ["fusion.1", 5, 15],
                              ["fusion.2", 15, 15],
                              ["fusion.3", 50, 10],
                              ["all-gather.1", 55, 15]]},
            "host": [["bench.dispatch", 0, 10], ["bench.block", 10, 90],
                     ["bench.readback", 100, 20]]}


def test_trace_reduction_on_a_known_trace():
    r = xtrace.reduce(_tiny_record(), 1)
    assert (r["lo"], r["hi"]) == (0, 120)
    # busy: [5,30) + [50,70) = 45 ns of 120
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["idle_share"] == pytest.approx(1 - 45 / 120)
    assert r["idle_gaps"] == [["bench.block", pytest.approx(50e-9)],
                              ["bench.block", pytest.approx(20e-9)],
                              ["bench.dispatch", pytest.approx(5e-9)]]
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(15e-9)]
    assert "while.7" not in [name for name, _ in r["top_ops"]]


def test_trace_reduction_on_a_recorded_trace():
    rec = json.load(open(os.path.join(BENCH, "testdata", "trace.json")))
    want = rec.pop("expected")
    r = xtrace.reduce(rec, len(rec["devices"]))
    for k, v in want.items():
        assert r[k] == pytest.approx(v, rel=1e-9), k


KERNEL_CALL = (
    '%vmap_jit_qsgd_quantize_blocked__.71 = (s8[2,256,2048]{2,1,0:T(8,128)'
    '(4,1)S(1)}, f32[2,256,1]{2,1,0:T(8,128)S(1)}) custom-call(f32[2,256,'
    '2048]{2,1,0:T(8,128)} %copy_bitcast_fusion.8, f32[2,256,2048]{2,1,0:'
    'T(8,128)} %copy_bitcast_fusion.7), custom_call_target="tpu_custom_call"'
    ', operand_layout_constraints={f32[2,256,2048]{2,1,0}}, metadata={'
    'op_name="jit(run_chunk)/while/body/closed_call/vmap(jit(qsgd_quantize_'
    'blocked))/pallas_call"}')


def test_kernel_calls_and_their_bytes_from_hlo_text():
    from benchlib import metrics_io
    assert metrics_io.pallas_calls("  " + KERNEL_CALL) == {
        "vmap_jit_qsgd_quantize_blocked__.71": "qsgd_quantize_blocked"}
    # int8 codes and f32 scales out; two f32 blocks in (the layout
    # constraints after the operand list are not operands)
    assert metrics_io.call_bytes(KERNEL_CALL) == (
        2 * 2 * 256 * 2048 * 4, 2 * 256 * 2048 + 2 * 256 * 4)


@pytest.mark.parametrize("workload", CELLS)
def test_wire_bytes_agree_with_payload_nbytes(workload):
    from repro.compress.wire_format import payload_nbytes
    from repro.core.engine import uplink_pipeline
    from repro.core.types import FLConfig
    fl = spec.resolve(workload, ROOT)["traffic"]["fl"]
    up = uplink_pipeline(FLConfig(**fl))
    for n in (1, 1000, 4099, 51200):
        assert fl_ref.payload_bytes(fl["uplink_compressor"], n, fl) == \
            payload_nbytes(up, n), n


def test_step_flops_by_hand():
    ssm = spec.required_module("models", "ssm")
    s = dict(num_layers=2, d_model=8, vocab_size=10, ssm_state=4,
             ssm_expand=2, ssm_head_dim=4, ssm_chunk=4, ssm_conv_width=2)
    # d_inner 16, 4 heads, conv_dim 24, in_proj 8 -> 44; seq 8, chunk 4
    per_layer = 2 * 8 * 44 + 2 * 2 * 24 + 2 * 4 * 4 + 2 * 4 * 4 * 4 \
        + 4 * 4 * 4 * 4 + 2 * 16 * 8
    assert ssm.step_flops(s, 8) == 3 * 8 * (2 * per_layer + 2 * 8 * 10)
    ed = spec.required_module("models", "encdec")
    s = dict(num_layers=1, encoder_layers=1, d_model=4, d_ff=8,
             vocab_size=10, frontend_tokens=6)
    enc = 6 * (8 * 16 + 4 * 32 + 4 * 6 * 4)
    dec = 3 * (8 * 16 + 4 * 32 + 4 * 3 * 4) + 3 * 4 * 16 + 6 * 4 * 16 \
        + 4 * 3 * 6 * 4
    assert ed.step_flops(s, 3) == 3 * (enc + dec + 3 * 2 * 4 * 10)


@pytest.mark.parametrize("n,k", [(1000, 10), (64, 64), (257, 3)])
def test_topk_select_matches_lax_top_k(n, k, quick_compiles):
    import jax
    import jax.numpy as jnp
    topk = spec.required_module("wire", "topk")
    x = jax.random.normal(jax.random.PRNGKey(n), (n,))
    x = x.at[::7].set(0.5)                  # ties
    vals, idx = jax.jit(topk.select, static_argnums=1)(x, k)
    want_v, want_i = jax.lax.top_k(jnp.abs(x), k)
    assert jnp.array_equal(idx, want_i)
    assert jnp.array_equal(vals, x[want_i])


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=root)


def test_run_exits_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
