"""Multi-device integration cases, run in a subprocess with 8 host devices
(tests/test_distributed.py drives this; the device count must be set before
jax import, which pytest's own process must not do — see dry-run notes)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from jax.sharding import AxisType                               # noqa: E402
from repro.core.types import ArchConfig, FLConfig               # noqa: E402
from repro.core.federated import make_fl_train_step             # noqa: E402
from repro.core.hierarchical import make_hier_fl_train_step     # noqa: E402
from repro.core.gossip import make_gossip_step                  # noqa: E402
from repro.models.model import Model                            # noqa: E402
from repro.data.synthetic import FedDataConfig, sample_round    # noqa: E402


def tiny_cfg(**kw):
    d = dict(name="tiny", family="dense", num_layers=2, d_model=64,
             num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=96,
             block_pattern=("attn+mlp",), dtype=jnp.float32, remat=False)
    d.update(kw)
    return ArchConfig(**d)


def mesh3():
    return jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def mesh2():
    return jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_batch(cfg, C, B, S, key):
    t = jax.random.randint(key, (C, B, S), 0, cfg.vocab_size)
    return {"tokens": t, "labels": t, "mask": jnp.ones((C, B, S)),
            "sizes": jnp.ones((C,)),
            "resources": jax.random.uniform(key, (C, 4))}


# ---------------------------------------------------------------------------

def case_fedsgd_equals_centralized():
    """FedSGD + identity compression + all clients == one centralized SGD
    step over the union batch (exactness of the aggregation wire)."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()
    fl = FLConfig(algorithm="fedsgd", local_steps=1, local_lr=0.1,
                  uplink_compressor="none", server_opt="fedavg", server_lr=1.0)
    step = make_fl_train_step(model, fl, mesh, chunk=16)
    state = step.init_fn(jax.random.PRNGKey(0))
    C, B, S = step.n_clients, 2, 16
    batch = make_batch(cfg, C, B, S, jax.random.PRNGKey(1))
    new_state, _ = jax.jit(step.step_fn)(state, batch)

    # centralized: same init, SGD over the concatenated batch
    params = model.init(jax.random.PRNGKey(0))
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()
            if k in ("tokens", "labels", "mask")}
    g = jax.grad(lambda p: model.loss(p, flat, chunk=16)[0])(params)
    ref = jax.tree.map(lambda p, g_: p - 0.1 * g_, params, g)

    err = max(float(jnp.abs(a - b).max()) for a, b in
              zip(jax.tree.leaves(new_state.params), jax.tree.leaves(ref)))
    assert err < 1e-5, err
    print("case_fedsgd_equals_centralized OK", err)


def case_all_algorithms_converge():
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh3()
    for algo, comp, sel_, E, sopt, slr in [
        ("fedsgd", "none", "all", 1, "fedavg", 1.0),
        ("fedavg", "qsgd8", "all", 2, "fedavg", 1.0),
        ("fedavg", "qsgd4", "all", 1, "fedavg", 1.0),
        ("fedavg", "uveq", "all", 1, "fedavg", 1.0),
        ("fedavg", "topk", "random", 1, "fedadam", 0.05),
        ("fedavg", "stc", "power_of_choice", 2, "fedavg", 1.0),
        ("fedavg", "sbc", "all", 1, "fedavg", 1.0),
        ("scaffold", "none", "all", 2, "fedavg", 1.0),
        ("fedprox", "sketch", "all", 2, "fedavg", 1.0),
        ("fedavg", "hsq", "multi_criteria", 1, "fedavg", 1.0),
        ("fedavg", "randmask", "all", 1, "fedavg", 1.0),
        ("fedavg", "none", "all", 1, "fedyogi", 0.05),
        ("fedavg", "none", "all", 1, "fedavgm", 0.5),
    ]:
        fl = FLConfig(algorithm=algo, local_steps=E, uplink_compressor=comp,
                      downlink_compressor="lfl8" if comp == "qsgd8" else "none",
                      selection=sel_,
                      clients_per_round=3 if sel_ != "all" else 0,
                      fedprox_mu=0.01 if algo == "fedprox" else 0.0,
                      server_opt=sopt, server_lr=slr, sketch_cols=2048,
                      local_lr=0.02 if comp == "sketch" else 0.05,
                      topk_fraction=0.05)
        step = make_fl_train_step(model, fl, mesh, chunk=16)
        state = step.init_fn(jax.random.PRNGKey(0))
        batch = make_batch(cfg, step.n_clients, 2, 16, jax.random.PRNGKey(1))
        jstep = jax.jit(step.step_fn)
        losses = []
        for _ in range(3):
            state, m = jstep(state, batch)
            losses.append(float(m["loss_all"]))
        assert all(np.isfinite(losses)), (algo, comp, losses)
        assert losses[-1] < losses[0] + 0.05, (algo, comp, losses)
        led = m["ledger"]
        assert float(led.uplink_dense) > 0
        if comp not in ("none",):
            assert float(led.uplink_wire) < float(led.uplink_dense), comp
        print(f"  {algo}/{comp}/{sel_} OK {losses}")
    print("case_all_algorithms_converge OK")


def case_ledger_accounting_exact():
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()
    fl = FLConfig(algorithm="fedsgd", uplink_compressor="none")
    step = make_fl_train_step(model, fl, mesh, chunk=16)
    state = step.init_fn(jax.random.PRNGKey(0))
    batch = make_batch(cfg, step.n_clients, 2, 16, jax.random.PRNGKey(1))
    _, m = jax.jit(step.step_fn)(state, batch)
    n_params = model.param_count()
    expect = 4.0 * n_params * step.n_clients       # f32 dense uplink
    got = float(m["ledger"].uplink_wire)
    assert abs(got - expect) / expect < 1e-6, (got, expect)
    print("case_ledger_accounting_exact OK", got)


def case_selection_counts():
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()
    for sel_, m_exp in [("random", 2), ("power_of_choice", 2),
                        ("multi_criteria", 2), ("all", 4)]:
        fl = FLConfig(algorithm="fedsgd", selection=sel_, clients_per_round=2)
        step = make_fl_train_step(model, fl, mesh, chunk=16)
        state = step.init_fn(jax.random.PRNGKey(0))
        batch = make_batch(cfg, step.n_clients, 2, 16, jax.random.PRNGKey(1))
        _, m = jax.jit(step.step_fn)(state, batch)
        assert int(m["selected"]) == m_exp, (sel_, m["selected"])
    print("case_selection_counts OK")


def case_hier_and_gossip():
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh3()
    fl = FLConfig(algorithm="fedavg", local_steps=2, uplink_compressor="qsgd8",
                  pod_compressor="qsgd8", hierarchical=True, sync_every=2)
    h = make_hier_fl_train_step(model, fl, mesh, chunk=16)
    state = h.init_fn(jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 2, 16), 0, 96)
    batch = {"tokens": t, "labels": t, "mask": jnp.ones((2, 2, 2, 16))}
    se, sc = jax.jit(h.step_edge), jax.jit(h.step_cloud)
    divs, losses = [], []
    for i in range(4):
        stepf = sc if (i + 1) % 2 == 0 else se
        state, m = stepf(state, batch)
        divs.append(float(m["pod_divergence"]))
        losses.append(float(m["loss"]))
    assert divs[0] > 0 and divs[1] == 0.0 and divs[3] == 0.0, divs
    assert losses[-1] < losses[0], losses
    # edge-only round must report fewer wire bytes than cloud round
    assert h.terms["cloud_wire"] > 0

    flg = FLConfig(algorithm="fedavg", local_steps=1,
                   uplink_compressor="qsgd8", local_lr=0.01)
    g = make_gossip_step(model, flg, mesh, chunk=16)
    gs = g.init_fn(jax.random.PRNGKey(0))
    gs.params = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(
        jax.random.PRNGKey(9), a.shape, a.dtype), gs.params)
    gstep = jax.jit(g.step_fn)
    gb = {"tokens": t[0], "labels": t[0], "mask": jnp.ones((2, 2, 16))}
    cons = []
    for _ in range(5):
        gs, m = gstep(gs, gb)
        cons.append(float(m["consensus"]))
    assert cons[-1] < cons[0] * 0.7, cons
    print("case_hier_and_gossip OK", divs, cons[:3])


def case_ef_residual_on_edge_hop():
    """RoundEngine EF fix: comm_state threads through the hierarchical edge
    hop and the gossip mix — under the biased chained pipeline
    "topk:0.01>>qsgd:8" the error-feedback residuals must be materialised in
    FLState.comm_state and EVOLVE across rounds on both topologies (they were
    silently stateless before the engine refactor)."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh3()

    def res_norms(comm_state):
        return [float(jnp.abs(a).sum()) for st in comm_state
                for a in jax.tree.leaves(st)]

    # --- hierarchical edge hop --------------------------------------------
    fl = FLConfig(algorithm="fedavg", local_steps=2,
                  uplink_compressor="topk:0.01>>qsgd:8", topk_fraction=0.01,
                  pod_compressor="qsgd8", hierarchical=True, sync_every=2)
    h = make_hier_fl_train_step(model, fl, mesh, chunk=16)
    hs = h.init_fn(jax.random.PRNGKey(0))
    assert hs.comm_state is not None, "edge pipeline must own state"
    # per-client state grid: (G, Ce) leading dims on every leaf-shaped array
    lead = jax.tree.leaves(hs.comm_state[0])[0].shape[:2]
    assert lead == (2, 2), lead
    assert all(v == 0.0 for v in res_norms(hs.comm_state))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 2, 16), 0, 96)
    batch = {"tokens": t, "labels": t, "mask": jnp.ones((2, 2, 2, 16))}
    se, sc = jax.jit(h.step_edge), jax.jit(h.step_cloud)
    hs, m1 = se(hs, batch)
    r1 = res_norms(hs.comm_state)
    assert sum(r1) > 0.0, "EF residual must be nonzero after the edge hop"
    hs, _ = sc(hs, batch)
    r2 = res_norms(hs.comm_state)
    assert r2 != r1, "EF residual must keep evolving on the cloud round's edge hop"
    assert np.isfinite(float(m1["loss"]))

    # --- gossip mix --------------------------------------------------------
    flg = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.01,
                   uplink_compressor="topk:0.01>>qsgd:8", topk_fraction=0.01)
    g = make_gossip_step(model, flg, mesh, chunk=16)
    gs = g.init_fn(jax.random.PRNGKey(0))
    assert gs.comm_state is not None
    gb = {"tokens": t[0], "labels": t[0], "mask": jnp.ones((2, 2, 16))}
    gstep = jax.jit(g.step_fn)
    gs, gm = gstep(gs, gb)
    g1 = res_norms(gs.comm_state)
    assert sum(g1) > 0.0, "EF residual must be nonzero after the gossip mix"
    gs, gm = gstep(gs, gb)
    g2 = res_norms(gs.comm_state)
    assert g2 != g1, "EF residual must keep evolving across mixes"
    assert np.isfinite(float(gm["loss"]))
    print("case_ef_residual_on_edge_hop OK", sum(r1), sum(g1))


def case_kernel_backend_edge_hop():
    """Kernel wire backend (FLConfig.backend="kernel") on the hierarchical
    edge hop: under the biased chained pipeline "topk:0.01>>qsgd:8" the
    kernel-backed EF residuals must evolve identically to pure JAX across
    edge and cloud rounds (the chain's kernel path is deterministic and
    layout padding never leaks into payloads), and so must the per-pod
    params. "Identically" here is the DESIGN.md §6 engine-scope band: the
    pallas_call boundary changes XLA's fusion (FMA contraction) of the
    *surrounding* f32 arithmetic, so single-ULP drift is permitted — the
    nonzero support must still match exactly. Also checks the gossip mix
    for the same spec."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh3()

    def assert_ulp_close(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a == 0, b == 0, err_msg=what)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8, err_msg=what)
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 2, 16), 0, 96)
    batch = {"tokens": t, "labels": t, "mask": jnp.ones((2, 2, 2, 16))}

    def run_hier(backend):
        fl = FLConfig(algorithm="fedavg", local_steps=2,
                      uplink_compressor="topk:0.01>>qsgd:8",
                      topk_fraction=0.01, pod_compressor="qsgd8",
                      hierarchical=True, sync_every=2, backend=backend)
        h = make_hier_fl_train_step(model, fl, mesh, chunk=16)
        hs = h.init_fn(jax.random.PRNGKey(0))
        se, sc = jax.jit(h.step_edge), jax.jit(h.step_cloud)
        hs, _ = se(hs, batch)
        hs, _ = sc(hs, batch)
        return hs

    a, b = run_hier("jax"), run_hier("kernel")
    assert a.comm_state is not None and b.comm_state is not None
    for sa, sb in zip(a.comm_state, b.comm_state):
        for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            assert_ulp_close(la, lb, "hier EF comm_state")
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        assert_ulp_close(la, lb, "hier params")
    res_norm = sum(float(jnp.abs(l).sum()) for s in b.comm_state
                   for l in jax.tree.leaves(s))
    assert res_norm > 0.0, "kernel-backed EF residual must actually evolve"

    def run_gossip(backend):
        flg = FLConfig(algorithm="fedavg", local_steps=1, local_lr=0.01,
                       uplink_compressor="topk:0.01>>qsgd:8",
                       topk_fraction=0.01, backend=backend)
        g = make_gossip_step(model, flg, mesh, chunk=16)
        gs = g.init_fn(jax.random.PRNGKey(0))
        gb = {"tokens": t[0], "labels": t[0], "mask": jnp.ones((2, 2, 16))}
        gs, _ = jax.jit(g.step_fn)(gs, gb)
        return gs

    ga, gb_ = run_gossip("jax"), run_gossip("kernel")
    for la, lb in zip(jax.tree.leaves(ga.comm_state),
                      jax.tree.leaves(gb_.comm_state)):
        assert_ulp_close(la, lb, "gossip EF comm_state")
    for la, lb in zip(jax.tree.leaves(ga.params),
                      jax.tree.leaves(gb_.params)):
        assert_ulp_close(la, lb, "gossip params")
    print("case_kernel_backend_edge_hop OK", res_norm)


def case_pipeline_chain_agg():
    """Tentpole: a chained CommPipeline ("topk:0.01>>qsgd:8") through the
    shard_map aggregator — state (EF residual) threads via FLState.comm_state,
    loss converges, and the chained ledger beats either stage alone."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()

    def run(comp, rounds=3, **kw):
        fl = FLConfig(algorithm="fedsgd", local_steps=1, local_lr=0.05,
                      uplink_compressor=comp, topk_fraction=0.01, **kw)
        step = make_fl_train_step(model, fl, mesh, chunk=16)
        state = step.init_fn(jax.random.PRNGKey(0))
        batch = make_batch(cfg, step.n_clients, 2, 16, jax.random.PRNGKey(1))
        jstep = jax.jit(step.step_fn)
        losses = []
        for _ in range(rounds):
            state, m = jstep(state, batch)
            losses.append(float(m["loss_all"]))
        return state, m, losses

    state, m, losses = run("topk:0.01>>qsgd:8")
    assert state.comm_state is not None          # EF residual in pipeline state
    res_norm = sum(float(jnp.abs(a).sum()) for st in state.comm_state
                   for a in jax.tree.leaves(st))
    assert res_norm > 0.0, "EF residual should be nonzero after a round"
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] + 0.05, losses

    chain_wire = float(m["ledger"].uplink_wire)
    topk_wire = float(run("topk", rounds=1)[1]["ledger"].uplink_wire)
    qsgd_wire = float(run("qsgd8", rounds=1)[1]["ledger"].uplink_wire)
    assert chain_wire < topk_wire and chain_wire < qsgd_wire, \
        (chain_wire, topk_wire, qsgd_wire)

    # DGC: momentum-corrected sparsification also threads state end-to-end
    state, m, losses = run("topk", dgc_momentum=0.9)
    assert state.comm_state is not None
    assert all(np.isfinite(losses)), losses
    print("case_pipeline_chain_agg OK",
          {"chain": chain_wire, "topk": topk_wire, "qsgd8": qsgd_wire})


def case_noniid_data_pipeline():
    cfg = FedDataConfig(vocab_size=96, num_clients=8, seq_len=32,
                        batch_per_client=4, heterogeneity=2.0)
    b = sample_round(cfg, jax.random.PRNGKey(0))
    assert b["tokens"].shape == (8, 4, 32)
    assert b["resources"].shape == (8, 4)
    # heterogeneity: client unigram distributions must differ more than iid
    def unigram_dist(toks, V=96):
        return np.bincount(np.asarray(toks).ravel(), minlength=V) / toks.size
    cfg_iid = FedDataConfig(vocab_size=96, num_clients=8, seq_len=32,
                            batch_per_client=4, heterogeneity=0.0)
    b_iid = sample_round(cfg_iid, jax.random.PRNGKey(0))

    def spread(batch):
        ds = np.stack([unigram_dist(batch["tokens"][c]) for c in range(8)])
        return float(np.abs(ds - ds.mean(0)).mean())
    assert spread(b) > 1.5 * spread(b_iid), (spread(b), spread(b_iid))
    print("case_noniid_data_pipeline OK", spread(b), spread(b_iid))


def case_compressed_agg_collectives_in_hlo():
    """The wire claim: compressed aggregation must put int8 (not f32) on the
    client-axis collective."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()

    def hlo_for(comp):
        fl = FLConfig(algorithm="fedsgd", uplink_compressor=comp)
        step = make_fl_train_step(model, fl, mesh, chunk=16)
        state = jax.eval_shape(step.init_fn,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
                 make_batch(cfg, step.n_clients, 2, 16,
                            jax.random.PRNGKey(1)).items()}
        fn = jax.jit(step.step_fn,
                     in_shardings=(step.state_shardings,
                                   step.batch_sharding_fn(batch)))
        return fn.lower(state, batch).compile().as_text()

    base = hlo_for("none")
    q = hlo_for("qsgd8")
    import re
    def gather_dtypes(txt):
        return set(re.findall(r"(\w+)\[[\d,]*\][^=]*all-gather", txt))
    assert any("s8[" in l and "all-gather" in l for l in q.splitlines()), \
        "int8 payload must be all-gathered"
    assert not any("s8[" in l and "all-gather" in l
                   for l in base.splitlines())
    print("case_compressed_agg_collectives_in_hlo OK")


def case_packed_wire_collectives_in_hlo():
    """The fused-wire claim (DESIGN.md §10): with wire_format='packed' the
    client-axis collective gathers the bit-packed u8 buffer — no s8 or f32
    code plane crosses the wire — and the staged twin still gathers s8."""
    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()

    def hlo_for(comp, wire):
        fl = FLConfig(algorithm="fedsgd", uplink_compressor=comp,
                      wire_format=wire)
        step = make_fl_train_step(model, fl, mesh, chunk=16)
        state = jax.eval_shape(step.init_fn,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
                 make_batch(cfg, step.n_clients, 2, 16,
                            jax.random.PRNGKey(1)).items()}
        fn = jax.jit(step.step_fn,
                     in_shardings=(step.state_shardings,
                                   step.batch_sharding_fn(batch)))
        return fn.lower(state, batch).compile().as_text()

    packed = hlo_for("ternary", "packed")
    staged = hlo_for("ternary", "staged")
    assert any("u8[" in l and "all-gather" in l for l in packed.splitlines()), \
        "packed payload must be all-gathered as u8"
    assert not any("s8[" in l and "all-gather" in l
                   for l in packed.splitlines()), \
        "no staged s8 code plane may cross the wire when packed"
    assert any("s8[" in l and "all-gather" in l
               for l in staged.splitlines())
    print("case_packed_wire_collectives_in_hlo OK")


def case_population_star_bitexact():
    """Degenerate ClientPopulation contract on the STAR topology (mesh
    client axes, shard_map wire): with cohort == C and capacity >= C the
    store-backed engine must reproduce the dense engine bit-for-bit in
    params AND comm_state (the slab rows ARE the dense rows: slot i <->
    client i, DESIGN.md §9)."""
    from repro.core.engine import Topology, make_round_engine, run_rounds
    from repro.core.population import ClientPopulation

    cfg = tiny_cfg()
    model = Model(cfg)
    mesh = mesh2()
    fl = FLConfig(algorithm="fedavg", local_steps=2, local_lr=0.2,
                  uplink_compressor="topk:0.25>>qsgd:8")

    def data_fn(r):
        return make_batch(cfg, 4, 2, 16, jax.random.fold_in(
            jax.random.PRNGKey(1), r))

    outs = []
    for pop in (None, ClientPopulation(n_clients=4, cohort=4, capacity=4)):
        e = make_round_engine(model, fl, Topology.star(), mesh=mesh,
                              chunk=16, population=pop)
        st = e.init_fn(jax.random.PRNGKey(0))
        st, _ = run_rounds(e, st, data_fn, 3, chunk=1, donate=False)
        comm = (st.comm_state["slab"] if isinstance(st.comm_state, dict)
                else st.comm_state)
        outs.append((st.params, comm))
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "population star engine diverged from dense"
    print("case_population_star_bitexact OK")


def case_secagg_masked_bitexact():
    """Masked == unmasked bit-exactly on the multi-device wires (DESIGN.md
    §11): the star shard_map wire (an all_gather of *masked* integer
    payloads — including a packed @fused chain where the masked uint8 planes
    stay uint8 on the collective), the hier edge hop (per-pod mask rings
    over the "data" axis) and the gossip mix (per-edge ppermute of masked
    payloads).  Params, ctx-stripped comm_state and ledger wire bytes must
    all match the unmasked run."""
    from repro.compress.secure_agg import drop_mask_ctx
    from repro.core.engine import Topology, make_round_engine, run_rounds

    cfg = tiny_cfg()
    model = Model(cfg)

    def _eq(tag, a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb), tag
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), tag

    # --- star shard_map wire ------------------------------------------------
    mesh = mesh2()

    def data_fn(r):
        return make_batch(cfg, 4, 2, 16,
                          jax.random.fold_in(jax.random.PRNGKey(1), r))

    def star_run(spec):
        fl = FLConfig(algorithm="fedavg", local_steps=2, local_lr=0.2,
                      uplink_compressor=spec)
        e = make_round_engine(model, fl, Topology.star(), mesh=mesh,
                              chunk=16)
        st = e.init_fn(jax.random.PRNGKey(0))
        st, ms = run_rounds(e, st, data_fn, 3, chunk=1, donate=False)
        return st, ms

    for base in ("topk:0.25>>qsgd:8", "ternary@fused"):
        sb, mb = star_run(base)
        sm, mm = star_run(base + ">>secagg")
        _eq(f"star params {base}", sb.params, sm.params)
        _eq(f"star comm {base}", sb.comm_state,
            drop_mask_ctx(sm.comm_state))
        _eq(f"star ledger {base}", mb["ledger"].uplink_wire,
            mm["ledger"].uplink_wire)

    # --- hier edge hop ------------------------------------------------------
    m3 = mesh3()
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 2, 16), 0, 96)
    hbatch = {"tokens": t, "labels": t, "mask": jnp.ones((2, 2, 2, 16))}

    def hier_run(spec):
        fl = FLConfig(algorithm="fedavg", local_steps=2,
                      uplink_compressor=spec, pod_compressor="qsgd8",
                      hierarchical=True, sync_every=2)
        h = make_hier_fl_train_step(model, fl, m3, chunk=16)
        state = h.init_fn(jax.random.PRNGKey(0))
        se, scl = jax.jit(h.step_edge), jax.jit(h.step_cloud)
        for i in range(3):
            state, _ = (scl if (i + 1) % 2 == 0 else se)(state, hbatch)
        return state

    hb = hier_run("qsgd8")
    hm = hier_run("qsgd8>>secagg")
    _eq("hier params", hb.params, hm.params)
    _eq("hier comm", hb.comm_state, drop_mask_ctx(hm.comm_state))

    # --- gossip mix ---------------------------------------------------------
    def gossip_run(spec):
        flg = FLConfig(algorithm="fedavg", local_steps=1,
                       uplink_compressor=spec, local_lr=0.01)
        g = make_gossip_step(model, flg, m3, chunk=16)
        gs = g.init_fn(jax.random.PRNGKey(0))
        gstep = jax.jit(g.step_fn)
        gb = {"tokens": t[0], "labels": t[0], "mask": jnp.ones((2, 2, 16))}
        for _ in range(3):
            gs, _ = gstep(gs, gb)
        return gs

    gb_ = gossip_run("qsgd8")
    gm_ = gossip_run("qsgd8>>secagg")
    _eq("gossip params", gb_.params, gm_.params)
    _eq("gossip comm", gb_.comm_state, drop_mask_ctx(gm_.comm_state))
    print("case_secagg_masked_bitexact OK")


def case_telemetry_bitexact():
    """Flight-recorder differential on the multi-device wires (DESIGN.md
    §12): telemetry on vs off is bit-exact in params, comm_state, and
    ledger on the star shard_map wire, the hier two-level program, and the
    gossip mix — and the per-stage byte slots reconstruct the ledger wire
    totals exactly in f32 (residual construction).  On hier, ONE
    TelemetrySpec serves both ``lax.cond`` branches: the appended pod slot
    is the residual anchor, landing exactly 0 on edge rounds and exactly
    the cross-pod bytes on cloud rounds."""
    from repro.core.engine import Topology, make_round_engine, run_rounds

    cfg = tiny_cfg()
    model = Model(cfg)

    def _eq(tag, a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb), tag
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), tag

    def _residual_exact(slots, totals):
        for i in range(slots.shape[0]):
            partial = np.float32(0.0)
            for v in slots[i][:-1]:
                partial = np.float32(partial + np.float32(v))
            assert slots[i][-1] == np.float32(
                np.float32(totals[i]) - partial), (i, slots[i], totals[i])

    def pair(tag, topo_fn, mesh, data_fn, spec, n=4, **fl_kw):
        fl_kw.setdefault("local_lr", 0.2)
        outs = []
        for tele in (False, True):
            fl = FLConfig(algorithm="fedavg", local_steps=1,
                          uplink_compressor=spec, telemetry=tele, **fl_kw)
            e = make_round_engine(model, fl, topo_fn(), mesh=mesh, chunk=16)
            st = e.init_fn(jax.random.PRNGKey(0))
            st, ms = run_rounds(e, st, data_fn, n, chunk=2, donate=False)
            outs.append((st, ms))
        (so, mo), (st_, mt) = outs
        _eq(f"{tag} params", so.params, st_.params)
        _eq(f"{tag} comm_state", so.comm_state, st_.comm_state)
        _eq(f"{tag} ledger", mo["ledger"], mt["ledger"])
        assert "round_stats" not in mo and "round_stats" in mt, tag
        rs = mt["round_stats"]
        _residual_exact(np.asarray(rs.up_stage_bytes),
                        np.asarray(mt["ledger"].uplink_wire))
        _residual_exact(np.asarray(rs.down_stage_bytes),
                        np.asarray(mt["ledger"].downlink_wire))
        return mt

    # --- star shard_map wire ------------------------------------------------
    mesh = mesh2()

    def star_data(r):
        return make_batch(cfg, 4, 2, 16,
                          jax.random.fold_in(jax.random.PRNGKey(1), r))

    pair("star", Topology.star, mesh, star_data, "topk:0.25>>qsgd:8")
    print("  star OK")

    # --- hier two-level program ---------------------------------------------
    m3 = mesh3()
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 2, 16), 0, 96)
    hbatch = {"tokens": t, "labels": t, "mask": jnp.ones((2, 2, 2, 16))}
    mt = pair("hier", lambda: Topology.hier(2), m3, lambda r: hbatch,
              "qsgd8", pod_compressor="qsgd8")
    pod = np.asarray(mt["round_stats"].up_stage_bytes)[:, -1]
    assert pod[0] == 0.0 and pod[2] == 0.0, pod      # edge rounds
    assert pod[1] > 0.0 and pod[1] == pod[3], pod    # cloud rounds
    print("  hier OK (pod slot", pod.tolist(), ")")

    # --- gossip mix -----------------------------------------------------------
    gb = {"tokens": t[0], "labels": t[0], "mask": jnp.ones((2, 2, 16))}
    pair("gossip", Topology.gossip, m3, lambda r: gb, "qsgd8",
         local_lr=0.01)
    print("case_telemetry_bitexact OK")


CASES = {k[5:]: v for k, v in list(globals().items())
         if k.startswith("case_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CASES[name]()
    print("PASS", name)
