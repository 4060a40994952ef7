"""RoundEngine tests: the scan driver (run_rounds) matches the Python round
loop bit-for-bit, compiles once per chunk shape, the topology bindings expose
the canonical hop sequence, and the DGC warm-up schedule anneals the
effective top-k fraction as configured."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.engine import (RoundRunner, Topology, check_doubly_stochastic,
                               erdos_renyi_graph, expander_graph,
                               make_round_engine, mixing_matrix, run_rounds,
                               uplink_pipeline)
from repro.core.simulate import make_sim_step
from repro.core.types import FLConfig
from repro.data.synthetic import FedDataConfig, sample_round
from repro.models.model import Model

CFG = get_arch("paper_lm")
MODEL = Model(CFG)
DATA = FedDataConfig(vocab_size=CFG.vocab_size, num_clients=4, seq_len=32,
                     batch_per_client=2, heterogeneity=1.5)


def _data_fn(r):
    return sample_round(DATA, jax.random.fold_in(jax.random.PRNGKey(1), r))


def _sim(fl):
    return make_sim_step(MODEL, fl, DATA.num_clients, chunk=32)


# ---------------------------------------------------------------------------
# scan driver == Python loop
# ---------------------------------------------------------------------------

def test_run_rounds_matches_python_loop():
    """The acceptance contract: run_rounds (scan) must produce the identical
    final params as stepping the same round_fn in a Python loop for a fixed
    seed (paper_lm workload)."""
    fl = FLConfig(algorithm="fedavg", local_steps=2, local_lr=0.2,
                  uplink_compressor="topk:0.05>>qsgd:8")
    sim = _sim(fl)
    n = 5

    state_l = sim.init_fn(jax.random.PRNGKey(0))
    for r in range(n):
        state_l, m_l = sim.step_fn(state_l, _data_fn(jnp.int32(r)))

    state_s, ms = run_rounds(sim.engine, sim.init_fn(jax.random.PRNGKey(0)),
                             _data_fn, n, chunk=3)    # 3 + 2: two chunk shapes
    for a, b in zip(jax.tree.leaves(state_l.params),
                    jax.tree.leaves(state_s.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # comm_state (EF residual of the chained pipeline) matches too
    for a, b in zip(jax.tree.leaves(state_l.comm_state),
                    jax.tree.leaves(state_s.comm_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # metrics are stacked over the round dim, ledger included
    assert ms["loss"].shape == (n,)
    assert ms["ledger"].uplink_wire.shape == (n,)
    assert float(ms["ledger"].uplink_wire[0]) == \
        pytest.approx(float(m_l["ledger"].uplink_wire))


def test_run_rounds_single_compile_per_chunk_shape():
    """2 full chunks reuse ONE compiled scan; a trailing partial chunk adds
    exactly one more compilation."""
    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor="qsgd8")
    sim = _sim(fl)
    runner = RoundRunner(sim.engine, _data_fn, chunk=2)
    state = sim.init_fn(jax.random.PRNGKey(0))
    state, ms = runner.run(state, 4)          # 2 chunks, same shape
    assert ms["loss"].shape == (4,)
    size = runner.cache_size()
    assert size == 1, f"expected one compilation for two equal chunks, got {size}"
    state, _ = runner.run(state, 3)           # 2 + 1: one new shape
    assert runner.cache_size() == 2


def test_round_index_threaded_to_data_fn():
    """data_fn receives state.round — chunk boundaries must not reset it."""
    seen = []

    def data_fn(r):
        # traced; record via shape-free identity on the host at trace time
        return _data_fn(r)

    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2)
    sim = _sim(fl)
    state = sim.init_fn(jax.random.PRNGKey(0))
    state, _ = run_rounds(sim.engine, state, data_fn, 4, chunk=2)
    assert int(state.round) == 4
    state, _ = run_rounds(sim.engine, state, data_fn, 2, chunk=2)
    assert int(state.round) == 6


def test_eval_cadence_skips_evals_without_perturbing_params():
    """FLConfig.eval_every gates metrics_fn behind a cond: changing the
    cadence must not change the training trajectory (final params bitwise
    identical), skipped rounds NaN-fill only the eval-only leaves, and the
    base round metrics (loss, ledger) survive every round."""
    from repro.data.synthetic import eval_batch
    ev = eval_batch(DATA, jax.random.PRNGKey(99), batch_size=2)

    def metrics_fn(state, m):
        return dict(m, eval_loss=MODEL.loss(state.params, ev, chunk=32)[0])

    fl = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.2,
                  uplink_compressor="qsgd8", eval_every=3)
    sim = _sim(fl)
    assert sim.engine.eval_every == 3       # threaded from FLConfig

    n = 6
    s1, m1 = run_rounds(sim.engine, sim.init_fn(jax.random.PRNGKey(0)),
                        _data_fn, n, chunk=3, metrics_fn=metrics_fn)
    s2, m2 = run_rounds(sim.engine, sim.init_fn(jax.random.PRNGKey(0)),
                        _data_fn, n, chunk=3, metrics_fn=metrics_fn,
                        eval_every=1)       # override: eval every round
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ev1 = np.asarray(m1["eval_loss"])
    ev2 = np.asarray(m2["eval_loss"])
    # cadence 3 evaluates the last round of each window (rounds 2 and 5)
    assert np.isfinite(ev1[[2, 5]]).all()
    assert np.isnan(ev1[[0, 1, 3, 4]]).all()
    np.testing.assert_array_equal(ev1[[2, 5]], ev2[[2, 5]])
    # base metrics survive skipped rounds (only eval-only leaves are gated)
    assert np.isfinite(np.asarray(m1["loss"])).all()
    assert np.isfinite(np.asarray(m1["ledger"].uplink_wire)).all()


# ---------------------------------------------------------------------------
# topology bindings and the hop contract
# ---------------------------------------------------------------------------

def test_sim_program_hop_sequence():
    fl = FLConfig(algorithm="fedavg", local_steps=1,
                  uplink_compressor="topk", topk_fraction=0.05)
    eng = make_round_engine(MODEL, fl, Topology.sim(4), chunk=32)
    names = eng.program.hop_names
    # the canonical hop order: local-update -> wire -> server-opt -> ledger
    for a, b in [("local_update", "wire"), ("wire", "server_opt"),
                 ("server_opt", "ledger"), ("ledger", "finalize")]:
        assert names.index(a) < names.index(b), names
    assert eng.topology.kind == "sim"


def test_sim_only_hops_gated():
    cm = FLConfig(algorithm="fedavg", local_steps=1, cmfl_threshold=0.5)
    eng = make_round_engine(MODEL, cm, Topology.sim(4), chunk=32)
    assert "cmfl" in eng.program.hop_names
    sc = FLConfig(algorithm="scaffold", local_steps=2)
    eng = make_round_engine(MODEL, sc, Topology.sim(4), chunk=32)
    assert "control" in eng.program.hop_names


def test_topology_factories():
    assert Topology.star().kind == "star"
    assert Topology.hier(3).sync_every == 3
    assert Topology.sim(7).n_clients == 7
    g = Topology.gossip([(2, 0.5)])
    assert g.graph == ((2, 0.5),)
    a = Topology.async_(8, buffer_size=4, staleness_alpha=0.3,
                        latency_profile="heavy_tail")
    assert (a.kind, a.n_clients, a.buffer_size) == ("async", 8, 4)
    with pytest.raises(ValueError):
        make_round_engine(MODEL, FLConfig(), Topology(kind="mesh"), chunk=32)


# ---------------------------------------------------------------------------
# gossip graphs beyond rings: expander / Erdős–Rényi + doubly-stochastic check
# ---------------------------------------------------------------------------

def test_ring_mixing_matrix_is_classic():
    """The default symmetric ring is W = I/2 + (L+R)/4."""
    W = mixing_matrix(((1, 0.25), (-1, 0.25)), 4)
    check_doubly_stochastic(W)
    expect = np.eye(4) * 0.5 + 0.25 * (np.roll(np.eye(4), 1, 0)
                                       + np.roll(np.eye(4), -1, 0))
    np.testing.assert_allclose(W, expect)


@pytest.mark.parametrize("n", [4, 8, 12])
def test_expander_graph_doubly_stochastic_and_mixes_faster(n):
    g = expander_graph(n, degree=4)
    W = mixing_matrix(g, n)
    check_doubly_stochastic(W)
    ring = mixing_matrix(((1, 0.25), (-1, 0.25)), n)
    lam2 = lambda M: np.sort(np.abs(np.linalg.eigvals(M)))[-2]
    if n >= 8:    # same degree-2 graph at n=4
        assert lam2(W) < lam2(ring) + 1e-9, (lam2(W), lam2(ring))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_erdos_renyi_graph_doubly_stochastic(seed):
    n = 10
    g = erdos_renyi_graph(n, p=0.5, seed=seed)
    W = mixing_matrix(g, n)
    check_doubly_stochastic(W)
    # symmetric (matchings with a uniform Metropolis-style weight)
    np.testing.assert_allclose(W, W.T)
    # each entry is a full permutation tuple (ppermute-able matching)
    for perm, w in g:
        assert sorted(perm) == list(range(n))
        assert all(perm[perm[i]] == i for i in range(n))   # involution


def test_gossip_graph_doubly_stochastic_check_rejects():
    # overweight incoming edges -> negative self-weight
    with pytest.raises(ValueError, match="negative"):
        check_doubly_stochastic(mixing_matrix(((1, 0.8), (-1, 0.8)), 8))
    # a non-permutation entry fails loudly at edge construction
    with pytest.raises(ValueError, match="permutation"):
        mixing_matrix((((0, 0, 1, 2), 0.25),), 4)
    # the engine builder runs the check on every graph (single-device mesh:
    # C=1 collapses every ring to a self-loop, which is legitimately doubly
    # stochastic, so exercise the C>1 path through mixing_matrix directly)
    W = mixing_matrix(Topology.gossip_expander(8, 4).graph, 8)
    check_doubly_stochastic(W)


# ---------------------------------------------------------------------------
# DGC warm-up sparsity schedule
# ---------------------------------------------------------------------------

def test_dgc_warmup_fraction_anneals():
    """With dgc_warmup_rounds=W the effective transmitted fraction follows
    f_r = target^((r+1)/(W+1)): near-dense early, the target after warm-up."""
    n, W, target = 4096, 3, 0.01
    fl = FLConfig(uplink_compressor="topk", topk_fraction=target,
                  dgc_momentum=0.9, dgc_warmup_rounds=W)
    pipe = uplink_pipeline(fl)
    assert pipe.stateful
    st = pipe.init((n,))
    assert "round" in st

    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    fracs = []
    for t in range(W + 3):
        payload, st = pipe.encode(st, jax.random.PRNGKey(t), x)
        dec = pipe.decode(payload, n)
        fracs.append(float((dec != 0).mean()))
    expect = [target ** (min(r + 1, W + 1) / (W + 1.0)) for r in range(W + 3)]
    for got, want in zip(fracs, expect):
        assert got == pytest.approx(want, rel=0.1, abs=2.0 / n), (fracs, expect)
    # strictly annealing down to the target during warm-up
    assert all(a > b for a, b in zip(fracs[:W], fracs[1:W + 1])), fracs
    assert fracs[-1] == pytest.approx(target, rel=0.1)
    # wire accounting is static at the warm-up (widest) capacity
    inner_frac = target ** (1.0 / (W + 1.0))
    from repro.compress import make_compressor
    inner = make_compressor("topk", fraction=inner_frac)
    assert pipe.wire_bits(n) == inner.wire_bits(n)


def test_dgc_warmup_rejects_fraction_frozen_specs():
    """Specs whose per-stage fraction overrides the kwarg (so the warm-up
    widening could never reach the wire) must fail loudly, not silently
    transmit the target fraction from round 0."""
    for spec in ("topk:0.01", "topk:0.01>>qsgd:8", "qsgd8"):
        fl = FLConfig(uplink_compressor=spec, topk_fraction=0.01,
                      dgc_momentum=0.9, dgc_warmup_rounds=3)
        with pytest.raises(ValueError, match="warm-up"):
            uplink_pipeline(fl)
    # fraction-kwarg-driven chain forms do warm up
    fl = FLConfig(uplink_compressor="topk>>qsgd:8", topk_fraction=0.01,
                  dgc_momentum=0.9, dgc_warmup_rounds=3)
    assert uplink_pipeline(fl).name.endswith("@warmup3")


def test_gossip_rejects_dgc_momentum():
    """DGC accumulates update deltas; the gossip mix ships raw model
    parameters (accumulating those diverges) — must fail loudly."""
    mesh = jax.make_mesh((jax.device_count(),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    fl = FLConfig(uplink_compressor="topk", topk_fraction=0.05,
                  dgc_momentum=0.9)
    with pytest.raises(ValueError, match="gossip"):
        make_round_engine(MODEL, fl, Topology.gossip(), mesh=mesh, chunk=32)


def test_dgc_warmup_off_is_plain_dgc():
    fl = FLConfig(uplink_compressor="topk", topk_fraction=0.05,
                  dgc_momentum=0.9)
    pipe = uplink_pipeline(fl)
    st = pipe.init((128,))
    assert "round" not in st
    assert pipe.name.startswith("mc0.9")


def test_dgc_warmup_through_sim_engine():
    """End-to-end: the annealed pipeline threads through FLState.comm_state
    and the per-round nnz of the decoded aggregate shrinks over warm-up."""
    fl = FLConfig(algorithm="fedsgd", local_steps=1, local_lr=0.1,
                  uplink_compressor="topk", topk_fraction=0.02,
                  dgc_momentum=0.9, dgc_warmup_rounds=2)
    sim = _sim(fl)
    state = sim.init_fn(jax.random.PRNGKey(0))
    state, ms = run_rounds(sim.engine, state, _data_fn, 4, chunk=4)
    assert state.comm_state is not None
    # every per-leaf state carries the warm-up round counter at 4
    counters = [np.asarray(a) for s in state.comm_state
                for a in jax.tree.leaves(s)
                if np.asarray(a).dtype == np.int32]
    assert counters and all((c == 4).all() for c in counters)
    assert np.isfinite(np.asarray(ms["loss"])).all()
