"""Flight-recorder differential harness (DESIGN.md §12).

The headline claim, in the style of tests/test_secure_agg.py: turning
``FLConfig.telemetry`` on must change **nothing** the run computes —
params, comm_state, and the CommLedger stay bit-exact on every topology,
because the telemetry hop only reads values the round program already
produced.  Around that anchor:

  * per-stage byte attribution sums to the ledger wire totals exactly in
    f32 (residual construction) and matches the direct f64 stage sum;
  * ResidualStore.stats counters agree with the slab's actual hit/evict
    behaviour, and the staleness histogram is a faithful scatter-add;
  * eval-cadence NaN gaps survive RoundStats stacking, serialize to JSON
    null, and render as ``-`` in the report;
  * the JSONL trace validates against schema v1 and the report renders
    every section it promises;
  * ``launch.hlo_analysis.name_stage_mismatch`` blames the right stage
    for a synthetic collective-bytes gap.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress.residual_store import ResidualStore
from repro.configs.registry import get_arch
from repro.core.engine import (Topology, make_round_engine, run_rounds,
                               uplink_pipeline)
from repro.core.population import ClientPopulation
from repro.core.types import FLConfig
from repro.data.pipeline import cohort_data_fn
from repro.data.synthetic import FedDataConfig, sample_round
from repro.obs.report import render, summarize
from repro.obs.telemetry import (N_STALENESS_BUCKETS, round_stats,
                                 stage_byte_table, staleness_hist,
                                 telemetry_spec, zero_stats)
from repro.obs.trace import (SCHEMA_VERSION, Tracer, validate_file,
                             validate_record)

CFG = get_arch("paper_lm")
DATA = FedDataConfig(vocab_size=CFG.vocab_size, num_clients=4, seq_len=32,
                     batch_per_client=2, heterogeneity=1.5)


def _data_fn(r):
    return sample_round(DATA, jax.random.fold_in(jax.random.PRNGKey(1), r))


# (spec, telemetry) -> (RoundRunner, first state) of a plain sim(4) run:
# its compiled round is read again by the scope test at no compile cost
_SIM4 = {}


def _run(spec, topo_fn, pop=None, n=3, telemetry=False, data_fn=None,
         **fl_kw):
    from repro.core.engine import RoundRunner
    from repro.models.model import Model
    model = Model(CFG)
    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor=spec, telemetry=telemetry, **fl_kw)
    dfn = data_fn or _data_fn
    e = make_round_engine(model, fl, topo_fn(), chunk=32, data_fn=dfn,
                          population=pop)
    st0 = e.init_fn(jax.random.PRNGKey(0))
    runner = RoundRunner(e, dfn, chunk=1, donate=False)
    st, ms = runner.run(st0, n)
    if (pop is None and data_fn is None and not fl_kw
            and e.topology == Topology.sim(4)):
        _SIM4[(spec, telemetry)] = (runner, st0)
    return e, st, ms


def _assert_leaves_equal(what, a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), f"{what}: leaf count diverged"
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True), f"{what} diverged"


# ---------------------------------------------------------------------------
# differential: telemetry on/off is bit-exact everywhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "topk:0.25>>qsgd:8",            # stateful EF chain
    "topk:0.25@kernel>>qsgd:8",     # same chain through the Pallas path
    "qsgd:4>>secagg",               # masked integer wire
    "qsgd:4@fused",                 # bit-packed wire format
])
def test_telemetry_off_path_bitexact_sim(spec):
    off = _run(spec, lambda: Topology.sim(4))
    on = _run(spec, lambda: Topology.sim(4), telemetry=True)
    _assert_leaves_equal(f"sim/{spec} params", off[1].params,
                         on[1].params)
    _assert_leaves_equal(f"sim/{spec} comm_state", off[1].comm_state,
                         on[1].comm_state)
    _assert_leaves_equal(f"sim/{spec} ledger", off[2]["ledger"],
                         on[2]["ledger"])
    assert "round_stats" not in off[2] and "round_stats" in on[2]


def test_telemetry_off_path_bitexact_async():
    topo = lambda: Topology.async_(4, buffer_size=2,
                                   latency_profile="heavy_tail")
    off = _run("topk:0.25>>qsgd:8", topo, n=6)
    on = _run("topk:0.25>>qsgd:8", topo, n=6, telemetry=True)
    _assert_leaves_equal("async params", off[1].params, on[1].params)
    _assert_leaves_equal("async comm_state", off[1].comm_state,
                         on[1].comm_state)
    _assert_leaves_equal("async ledger", off[2]["ledger"], on[2]["ledger"])
    rs = on[2]["round_stats"]
    # one arrival per event: each histogram row is a one-hot
    assert np.allclose(np.asarray(rs.staleness_hist).sum(axis=1), 1.0)
    assert (np.asarray(rs.buffer_fill) >= 1.0).all()


def test_telemetry_off_path_bitexact_population():
    pop = lambda: ClientPopulation(n_clients=32, cohort=8, capacity=12)
    dcfg = FedDataConfig(vocab_size=CFG.vocab_size, num_clients=32,
                         seq_len=32, batch_per_client=2, heterogeneity=1.5)
    outs = []
    for tele in (False, True):
        p = pop()
        outs.append(_run("topk:0.25>>qsgd:8", lambda: Topology.sim(32),
                         pop=p, telemetry=tele,
                         data_fn=cohort_data_fn(p, dcfg)))
    off, on = outs
    _assert_leaves_equal("pop params", off[1].params, on[1].params)
    _assert_leaves_equal("pop comm_state", off[1].comm_state,
                         on[1].comm_state)
    _assert_leaves_equal("pop ledger", off[2]["ledger"], on[2]["ledger"])
    rs = on[2]["round_stats"]
    # 8-client cohorts over a cold 12-slot store: first round all misses,
    # and hits + misses == cohort every round
    hm = np.asarray(rs.store_hits) + np.asarray(rs.store_misses)
    assert np.allclose(hm, 8.0)
    assert float(np.asarray(rs.store_hits)[0]) == 0.0
    assert np.allclose(np.asarray(rs.selected), 8.0)
    assert np.allclose(np.asarray(rs.available), 8.0)


# ---------------------------------------------------------------------------
# per-stage byte attribution sums exactly to the ledger
# ---------------------------------------------------------------------------

def _residual_exact(slots, totals):
    """The committed exactness predicate: f32 sequential reconstruction of
    every row lands bit-equal on the ledger total."""
    for i in range(slots.shape[0]):
        partial = np.float32(0.0)
        for v in slots[i][:-1]:
            partial = np.float32(partial + np.float32(v))
        if slots[i][-1] != np.float32(np.float32(totals[i]) - partial):
            return False
    return True


@pytest.mark.parametrize("spec", ["topk:0.05>>qsgd:8", "qsgd:4>>secagg"])
def test_stage_bytes_sum_to_ledger(spec):
    e, _, ms = _run(spec, lambda: Topology.sim(4), telemetry=True)
    up = np.asarray(ms["round_stats"].up_stage_bytes)
    dn = np.asarray(ms["round_stats"].down_stage_bytes)
    uw = np.asarray(ms["ledger"].uplink_wire)
    dw = np.asarray(ms["ledger"].downlink_wire)
    assert _residual_exact(up, uw) and _residual_exact(dn, dw)
    assert np.allclose(up.astype(np.float64).sum(1), uw, rtol=1e-6)
    assert np.allclose(dn.astype(np.float64).sum(1), dw, rtol=1e-6)
    tele = e.aux["telemetry"]
    assert len(tele.up_names) == up.shape[1]
    # the static per-unit table itself covers the whole wire: 4 clients x
    # up_total() matches the billed uplink within float-sum slack
    assert np.allclose(4.0 * tele.up_total(), uw, rtol=1e-5)


def test_stage_byte_table_matches_wire_bits():
    fl = FLConfig(uplink_compressor="topk:0.1>>qsgd:8")
    pipe = uplink_pipeline(fl)
    sizes = [1000, 4096, 33]
    table = stage_byte_table(pipe, sizes)
    direct = sum(pipe.wire_bits(n) for n in sizes) / 8.0
    assert sum(table) == pytest.approx(direct, rel=1e-9)
    # scale is linear
    assert sum(stage_byte_table(pipe, sizes, scale=3.0)) == \
        pytest.approx(3.0 * direct, rel=1e-9)


def test_telemetry_spec_extra_slot_is_residual_anchor():
    fl = FLConfig(uplink_compressor="qsgd:8")
    spec = telemetry_spec(uplink_pipeline(fl), None, [256],
                          extra_up=(("pod:qsgd8", 1234.0),))
    assert spec.up_names[-1] == "pod:qsgd8"
    assert spec.up_table[-1] == 1234.0
    assert spec.down_names == ("none",)
    z = zero_stats(spec)
    assert z.up_stage_bytes.shape == (len(spec.up_table),)
    assert z.staleness_hist.shape == (N_STALENESS_BUCKETS,)


def test_staleness_hist_scatter():
    # scalar -> one-hot in the right bucket (edges 1,2,4,8,16,32,64)
    assert np.argmax(np.asarray(staleness_hist(0.0))) == 0
    assert np.argmax(np.asarray(staleness_hist(1.0))) == 1
    assert np.argmax(np.asarray(staleness_hist(63.0))) == 6
    assert np.argmax(np.asarray(staleness_hist(1e6))) == 7
    # vector + occupancy weights: masked slots don't count
    h = np.asarray(staleness_hist(jnp.asarray([0.0, 3.0, 3.0, 99.0]),
                                  weights=jnp.asarray([1.0, 1.0, 1.0, 0.0])))
    assert h[0] == 1.0 and h[2] == 2.0 and h[7] == 0.0 and h.sum() == 3.0


def test_round_stats_defaults_zero():
    fl = FLConfig(uplink_compressor="qsgd:8")
    spec = telemetry_spec(uplink_pipeline(fl), None, [64])
    ledger = type("L", (), {"uplink_wire": jnp.float32(sum(spec.up_table)),
                            "downlink_wire": jnp.float32(0.0)})()
    rs = round_stats(spec, ledger, up_unit=jnp.float32(1.0))
    assert float(rs.store_hits) == 0.0 and float(rs.buffer_fill) == 0.0
    assert float(np.asarray(rs.up_stage_bytes).sum()) == \
        pytest.approx(sum(spec.up_table))


# ---------------------------------------------------------------------------
# ResidualStore.stats agrees with the slab
# ---------------------------------------------------------------------------

def _store(capacity=4, eviction="drop"):
    pipe = uplink_pipeline(FLConfig(uplink_compressor="topk:0.25>>qsgd:8"))
    params = {"w": jnp.zeros((40,), jnp.float32)}
    return ResidualStore(pipe, params, capacity, eviction=eviction)


@pytest.mark.parametrize("eviction", ["drop", "sketch"])
def test_store_stats_counters(eviction):
    store = _store(capacity=4, eviction=eviction)
    st = store.init()
    ids0 = jnp.asarray([0, 1, 2, 3], jnp.int32)
    s0 = store.stats(st, ids0)
    assert float(s0["hits"]) == 0.0 and float(s0["misses"]) == 4.0
    assert float(s0["evictions"]) == 0.0      # cold slab: free slots only
    rows, _ = store.gather(st, ids0)
    st = store.scatter(st, ids0, rows)
    # 2 residents + 2 strangers on a full slab: 2 hits, 2 evicting misses
    s1 = store.stats(st, jnp.asarray([0, 1, 7, 9], jnp.int32))
    assert float(s1["hits"]) == 2.0 and float(s1["misses"]) == 2.0
    assert float(s1["evictions"]) == 2.0
    want = 2.0 if eviction == "sketch" else 0.0
    assert float(s1["sketch_recovered"]) == want


def test_availability_count():
    full = ClientPopulation(n_clients=32, cohort=8)
    ids = jnp.arange(8, dtype=jnp.int32)
    assert float(full.availability_count(jnp.int32(0), ids)) == 8.0
    churn = ClientPopulation(n_clients=32, cohort=8, availability=0.5)
    c = float(churn.availability_count(jnp.int32(3), ids))
    assert 0.0 <= c <= 8.0


# ---------------------------------------------------------------------------
# eval cadence: NaN gaps survive stacking, serialization, and rendering
# ---------------------------------------------------------------------------

def test_eval_cadence_nan_stacking_and_report(tmp_path):
    def metrics_fn(state, m):
        return dict(m, eval_loss=jnp.float32(1.5))

    from repro.models.model import Model
    model = Model(CFG)
    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor="topk:0.25>>qsgd:8", telemetry=True,
                  eval_every=2)
    e = make_round_engine(model, fl, Topology.sim(4), chunk=32)
    st = e.init_fn(jax.random.PRNGKey(0))
    st, ms = run_rounds(e, st, _data_fn, 4, chunk=2, donate=False,
                        metrics_fn=metrics_fn)
    ev = np.asarray(ms["eval_loss"])
    assert np.isnan(ev).any() and np.isfinite(ev).any()
    # RoundStats leaves never gap — they are base metrics in both branches
    for leaf in jax.tree.leaves(ms["round_stats"]):
        assert np.isfinite(np.asarray(leaf)).all()

    path = tmp_path / "cadence.jsonl"
    tr = Tracer(str(path), meta=dict(arch="paper_lm"))
    tr.emit_rounds(ms, spec=e.aux["telemetry"])
    tr.close()
    records = validate_file(str(path))
    rounds = [r for r in records if r["kind"] == "round"]
    assert len(rounds) == 4
    gaps = [r["m"]["eval_loss"] for r in rounds]
    assert None in gaps and 1.5 in gaps         # NaN -> JSON null
    report = render(summarize(records))
    line = next(ln for ln in report.splitlines() if "eval_loss" in ln)
    assert " - " in f" {line} "                  # gap renders as '-'


# ---------------------------------------------------------------------------
# trace schema + report sections
# ---------------------------------------------------------------------------

def test_trace_schema_and_report_sections(tmp_path):
    path = tmp_path / "run.jsonl"
    tr = Tracer(str(path), meta=dict(arch="smoke", topology="sim"))
    with tr.span("chunk", rounds=2) as sp:
        sp["kind"] = "compile"                   # mutable retag
    with tr.span("eval"):
        pass
    tr.event("flush", round=3)
    e, _, ms = _run("topk:0.25>>qsgd:8", lambda: Topology.sim(4),
                    telemetry=True)
    tr.emit_rounds(ms, spec=e.aux["telemetry"])
    tr.close()

    records = validate_file(str(path))
    assert records[0]["kind"] == "meta"
    assert records[0]["schema"] == SCHEMA_VERSION
    kinds = [r["kind"] for r in records]
    assert "compile" in kinds and "chunk" not in kinds
    assert "flush" in kinds and "stages" in kinds
    assert sum(k == "round" for k in kinds) == 3

    report = render(summarize(records))
    for section in ("uplink byte waterfall", "time breakdown",
                    "claims-ready rows"):
        assert section in report, f"report lost its {section!r} section"
    md = render(summarize(records), md=True)
    assert md != report


def test_validate_record_rejects_malformed():
    with pytest.raises(ValueError, match="schema version"):
        validate_record({"v": 999, "kind": "meta"})
    with pytest.raises(ValueError, match="dur_s"):
        validate_record({"v": SCHEMA_VERSION, "kind": "chunk",
                         "type": "span"})
    with pytest.raises(ValueError, match="metrics dict"):
        validate_record({"v": SCHEMA_VERSION, "kind": "round", "round": 0})
    with pytest.raises(ValueError, match="kind"):
        validate_record({"v": SCHEMA_VERSION})


def test_validate_file_requires_meta_header(tmp_path):
    p = tmp_path / "headless.jsonl"
    p.write_text(json.dumps({"v": 1, "kind": "event", "type": "event"})
                 + "\n")
    with pytest.raises(ValueError, match="meta header"):
        validate_file(str(p))


# ---------------------------------------------------------------------------
# HLO mismatch attribution
# ---------------------------------------------------------------------------

def test_name_stage_mismatch():
    from repro.launch.hlo_analysis import name_stage_mismatch
    names = ("topk", "qsgd8")
    table = (900.0, 2100.0)
    # agreement within rtol -> silent
    assert name_stage_mismatch(names, table, measured=3000.0) == ""
    assert name_stage_mismatch(names, table, measured=3050.0) == ""
    # the whole qsgd8 payload missing from the collective
    msg = name_stage_mismatch(names, table, measured=900.0)
    assert "qsgd8" in msg and "missing from" in msg
    # the topk meta double-counted
    msg = name_stage_mismatch(names, table, measured=3900.0)
    assert "topk" in msg and "over-counted" in msg
    # explicit expected_total overrides the table sum
    assert name_stage_mismatch(names, table, measured=5000.0,
                               expected_total=5000.0) == ""


# ---------------------------------------------------------------------------
# Star driver compiles once per chunk shape
# ---------------------------------------------------------------------------

def test_star_runner_single_compile_per_chunk_shape():
    # Regression: the star RoundRunner used to recompile every chunk after
    # the first, because donated outputs came back with fully-replicated
    # shardings that no longer matched the jit's inferred input shardings.
    # Pinning out_shardings (and device_put-ing the carried state) keeps
    # the executable cache at exactly one entry across same-shape chunks.
    from repro.core.engine import RoundRunner
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import Model
    mesh = make_host_mesh(model=1)
    model = Model(CFG)
    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor="topk:0.25>>qsgd:8")
    e = make_round_engine(model, fl, Topology.star(), mesh=mesh, chunk=32)
    star_data = FedDataConfig(vocab_size=CFG.vocab_size,
                              num_clients=e.n_clients, seq_len=32,
                              batch_per_client=2)

    def data_fn(r):
        return sample_round(star_data,
                            jax.random.fold_in(jax.random.PRNGKey(1), r))

    runner = RoundRunner(e, data_fn, chunk=2)
    st = e.init_fn(jax.random.PRNGKey(0))
    st, _ = runner.run(st, 4)  # two chunks of the same shape
    n = runner.cache_size()
    assert n == 1, f"star runner recompiled: {n} executables for one shape"


# ---------------------------------------------------------------------------
# named scopes reach the compiled round (repro.obs.scopes)
# ---------------------------------------------------------------------------

# what a scan does to carry its state and stack its outputs
PLUMBING = {"tuple", "get-tuple-element", "dynamic-update-slice", "copy",
            "parameter", "constant", "bitcast"}


def _opcodes(hlo):
    """``{instruction: opcode}``; a fusion reads as its fused root's."""
    import re
    comp, roots, ops = None, {}, {}
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", line)
        if m:
            op = re.search(r"(?:^|\s)([a-z][\w\-]*)\(", m.group(3)).group(1)
            called = re.search(r"\bcalls=%?([\w.\-]+)", m.group(3))
            ops[m.group(2)] = (op, called.group(1) if called else None)
            if m.group(1):
                roots[comp] = op
    return {n: (roots[c] if op == "fusion" and c in roots else op)
            for n, (op, c) in ops.items()}


@pytest.mark.parametrize("spec,stages", [
    ("topk:0.25>>qsgd:8", {"topk", "qsgd", "ef"}),      # error feedback
    ("qsgd:4>>secagg", {"qsgd", "secagg"}),
])
def test_scopes_name_the_compiled_round(spec, stages):
    # the round the telemetry-off differential ran, when it ran first
    import re
    from repro.obs.scopes import scope_table, scopes_of
    if (spec, False) not in _SIM4:
        _run(spec, lambda: Topology.sim(4))
    runner, state = _SIM4[(spec, False)]
    hlo = runner._jit.lower(state, 1).compile().as_text()
    table = scope_table(hlo)
    hops = {hop for hop, _ in table.values()}
    assert {"local_update", "wire", "data"} <= hops
    found = {st for _, st in table.values()} - {None}
    assert stages <= found, found
    assert {hop for hop, st in table.values() if st} == {"wire"}
    # the weighted mean may fuse into the server step; its own operations
    # carry stage.aggregate under hop.wire all the same
    named = [scopes_of(n) for n in re.findall(r'op_name="([^"]*)"', hlo)]
    assert ("wire", "aggregate") in named
    assert {hop for hop, st in named if st} == {"wire"}
    ops = _opcodes(hlo)
    loose = {n: ops[n] for n, (hop, _) in table.items() if hop is None}
    assert set(loose.values()) <= PLUMBING, loose
