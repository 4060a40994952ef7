"""Federated training CLI — the deployment path (clients on mesh axes).

    PYTHONPATH=src python -m repro.launch.train --arch paper_lm \
        --rounds 20 --compressor qsgd8 [--hierarchical] [--devices 8]

Rounds run through the RoundEngine's scan driver (``run_rounds``): ``--chunk``
rounds are compiled into one donated-argument ``jax.lax.scan``, so the hot
path pays one dispatch per chunk instead of per round (``--chunk 1`` falls
back to per-round stepping for debugging). On real TPU hardware omit
--devices (uses the actual topology). On CPU, --devices N simulates an
N-device host for the mesh (set before jax init).

The mesh path puts one client on each ``data`` slice of ``--model-parallel``
devices (default 2): one chip gives one client, four chips give two. For a
real cohort on one chip use the mesh-free ``--population C --cohort C``
path (``Topology.sim``).
"""
import argparse
import os
import sys


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_lm")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--algorithm", default="fedavg")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-lr", type=float, default=0.2)
    ap.add_argument("--compressor", default="none")
    ap.add_argument("--downlink", default="none")
    ap.add_argument("--backend", default="jax", choices=["jax", "kernel"],
                    help="encode/decode backend for every wire hop "
                         "(kernel = Pallas; interpret mode off-TPU)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="in-scan held-out-eval cadence in rounds "
                         "(FLConfig.eval_every); 0 = once per --chunk, "
                         "matching the pre-cadence host-side eval cost")
    ap.add_argument("--selection", default="all")
    ap.add_argument("--clients-per-round", type=int, default=0)
    ap.add_argument("--server-opt", default="fedavg")
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="AsyncEngine: virtual-clock buffered async FL "
                         "(DESIGN.md §7); --rounds then counts server "
                         "events (client uploads), not synchronous rounds")
    ap.add_argument("--clients", type=int, default=8,
                    help="async only: client slots (mesh-decoupled)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async FedBuff K (1 = FedAsync, 0 = n_clients "
                         "= the synchronous limit)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="async staleness decay (1+tau)^(-alpha); also "
                         "scales the adaptive server-opt moments by the "
                         "flushed buffer's mean staleness (DESIGN.md §8)")
    ap.add_argument("--latency-profile", default="heavy_tail",
                    choices=["constant", "resource", "uniform", "heavy_tail"])
    ap.add_argument("--flush-deadline", type=float, default=0.0,
                    help="async adaptive buffer sizing: also flush when the "
                         "virtual clock passes the last flush + deadline "
                         "(0 = count-only FedBuff)")
    ap.add_argument("--population", type=int, default=0,
                    help="simulate this many clients via the streaming "
                         "ClientPopulation path (mesh-free; works with "
                         "--async too): per-round cohorts + bounded "
                         "residual store (DESIGN.md §9); e.g. "
                         "--population 1000000 --cohort 1024")
    ap.add_argument("--cohort", type=int, default=1024,
                    help="clients sampled per round (population mode)")
    ap.add_argument("--store-capacity", type=int, default=0,
                    help="residual-store slots (0 = min(population, "
                         "2 x cohort))")
    ap.add_argument("--eviction", default="drop",
                    choices=["drop", "sketch"],
                    help="residual-store eviction: drop the evicted "
                         "client's pipeline state, or fold it into the "
                         "count-sketch overflow tail")
    ap.add_argument("--scenario-trace", default="static",
                    choices=["static", "diurnal", "square"],
                    help="client availability trace (core.scenario, "
                         "DESIGN.md §13): static = i.i.d. Bernoulli, "
                         "square = phase-shifted duty windows, diurnal = "
                         "sinusoid-modulated Bernoulli")
    ap.add_argument("--scenario-period", type=float, default=24.0,
                    help="availability trace period, in rounds")
    ap.add_argument("--scenario-availability", type=float, default=1.0,
                    help="availability duty-cycle rate in (0, 1]; sets "
                         "both the dense selection hop's rate and "
                         "ClientPopulation.availability under --population")
    ap.add_argument("--scenario-dropout", type=float, default=0.0,
                    help="mid-round dropout hazard per unit virtual time; "
                         "dropped clients become zero-weight rows "
                         "(partial-update semantics, secagg-safe)")
    ap.add_argument("--scenario-epoch-scale", type=float, default=0.0,
                    help="heterogeneity-aware dispatch: floor in (0, 1] "
                         "of the per-client local-epoch scale (FedMCCS "
                         "capability latency); 0 disables")
    ap.add_argument("--scenario-deadline-quantile", type=float, default=0.0,
                    help="async adaptive deadline arming: flush deadline "
                         "tracks this completion-time quantile instead of "
                         "--flush-deadline; 0 disables")
    ap.add_argument("--scenario-seed", type=int, default=0,
                    help="seed for scenario phase/dropout draws")
    ap.add_argument("--seq", type=int, default=48)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU dry runs)")
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=8,
                    help="rounds per compiled scan (run_rounds)")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="flight recorder (DESIGN.md §12): write a "
                         "schema-versioned JSONL trace here — span/event "
                         "records plus one machine-readable record per "
                         "round; implies FLConfig.telemetry. Render with "
                         "python -m repro.obs.report PATH")
    ap.add_argument("--profile-dir", default="", metavar="DIR",
                    help="with --trace: also wrap the run in "
                         "jax.profiler.trace(DIR) for TensorBoard/Perfetto")
    return ap.parse_args()


def main():
    args = _parse()
    if args.devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    from repro import checkpoint
    from repro.configs.registry import get_arch
    from repro.core.engine import RoundRunner
    from repro.core.federated import make_fl_train_step
    from repro.core.hierarchical import make_hier_fl_train_step
    from repro.core.types import FLConfig
    from repro.data.synthetic import FedDataConfig, eval_batch, sample_round
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import Model, set_activation_mesh

    cfg = get_arch(args.arch)
    model = Model(cfg)
    eval_every = args.eval_every if args.eval_every > 0 else max(1, args.chunk)
    fl = FLConfig(algorithm=args.algorithm, local_steps=args.local_steps,
                  local_lr=args.local_lr, uplink_compressor=args.compressor,
                  downlink_compressor=args.downlink, backend=args.backend,
                  selection=args.selection,
                  clients_per_round=args.clients_per_round,
                  server_opt=args.server_opt, hierarchical=args.hierarchical,
                  sync_every=args.sync_every, eval_every=eval_every,
                  async_buffer_size=args.buffer_size,
                  staleness_alpha=args.staleness_alpha,
                  latency_profile=args.latency_profile,
                  async_flush_deadline=args.flush_deadline,
                  scenario_trace=args.scenario_trace,
                  scenario_period=args.scenario_period,
                  scenario_availability=args.scenario_availability,
                  scenario_dropout=args.scenario_dropout,
                  scenario_epoch_scale=args.scenario_epoch_scale,
                  scenario_deadline_quantile=args.scenario_deadline_quantile,
                  scenario_seed=args.scenario_seed,
                  telemetry=bool(args.trace))

    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        topo_name = ("population" if args.population > 0 else
                     "async" if args.async_mode else
                     "hier" if args.hierarchical else "star")
        if args.population > 0 and args.async_mode:
            topo_name = "population-async"
        tracer = Tracer(args.trace, profile_dir=args.profile_dir,
                        meta=dict(arch=args.arch, topology=topo_name,
                                  rounds=args.rounds,
                                  compressor=args.compressor,
                                  algorithm=args.algorithm))

    def _save_checkpoint(params):
        if tracer is not None:
            with tracer.span("checkpoint", path=args.checkpoint):
                checkpoint.save(args.checkpoint, params)
        else:
            checkpoint.save(args.checkpoint, params)
        print("saved", args.checkpoint)

    def _emit_flush_events(ms):
        # host-derived async flush marks: one event per flushed generation
        if tracer is None or ms is None or "flushed" not in ms:
            return
        import numpy as np
        for i, v in enumerate(np.asarray(ms["flushed"])):
            if v > 0:
                tracer.event("flush", round=i)

    if args.population > 0:
        # mesh-free streaming-cohort path (DESIGN.md §9): --population
        # clients exist, --cohort train per round, per-client pipeline
        # state bounded by the residual store. Composes with --async
        # (slots = the cohort; --rounds counts server events).
        from repro.compress.residual_store import store_nbytes
        from repro.core.engine import (Topology, make_round_engine,
                                       run_rounds)
        from repro.core.population import ClientPopulation
        from repro.data.pipeline import cohort_data_fn

        N = args.population
        # one availability flag for both paths: the population keeps the
        # duty rate, the scenario (attached by the engine) shapes the trace
        pop = ClientPopulation(n_clients=N, cohort=min(args.cohort, N),
                               capacity=args.store_capacity,
                               eviction=args.eviction,
                               availability=args.scenario_availability)
        data = FedDataConfig(vocab_size=cfg.vocab_size, num_clients=N,
                             seq_len=args.seq,
                             batch_per_client=args.batch_per_client,
                             heterogeneity=1.5)
        data_fn = cohort_data_fn(pop, data)
        topo = (Topology.async_(N) if args.async_mode else Topology.sim(N))
        engine = make_round_engine(model, fl, topo, chunk=args.seq,
                                   data_fn=data_fn, population=pop)
        state = engine.init_fn(jax.random.PRNGKey(0))
        mb = (store_nbytes(state.comm_state) / 1e6
              if state.comm_state is not None else 0.0)
        print(f"population={N:,} cohort={pop.cohort} "
              f"capacity={pop.capacity} eviction={pop.eviction} "
              f"store={mb:.1f}MB params={model.param_count():,} "
              f"{'async' if args.async_mode else 'sync'}")
        state, ms = run_rounds(engine, state, data_fn, args.rounds,
                               chunk=args.chunk, tracer=tracer)
        for i in range(args.rounds):
            led = jax.tree.map(lambda x, i=i: x[i], ms["ledger"])
            print(f"round {i:>4} loss={float(ms['loss'][i]):.3f} "
                  f"up={float(led.uplink_wire)/1e6:.2f}MB", flush=True)
        if tracer is not None:
            tracer.emit_rounds(ms, spec=engine.aux.get("telemetry"))
            _emit_flush_events(ms)
        if args.checkpoint:
            _save_checkpoint(state.params)
        if tracer is not None:
            tracer.close()
            print(f"trace: {args.trace} (render: python -m repro.obs.report "
                  f"{args.trace})")
        return

    if args.async_mode:
        # mesh-free virtual-clock path: --rounds counts server events
        from repro.core.async_engine import make_async_step
        from repro.core.engine import run_rounds
        data = FedDataConfig(vocab_size=cfg.vocab_size,
                             num_clients=args.clients, seq_len=args.seq,
                             batch_per_client=args.batch_per_client,
                             heterogeneity=1.5)

        def data_fn(v):
            return sample_round(data, jax.random.fold_in(
                jax.random.PRNGKey(1), v))

        a = make_async_step(model, fl, args.clients, data_fn, chunk=args.seq)
        print(f"async arch={cfg.name} clients={args.clients} "
              f"K={a.buffer_size} alpha={args.staleness_alpha} "
              f"profile={args.latency_profile} "
              f"deadline={args.flush_deadline or 'off'} "
              f"params={model.param_count():,}")
        state = a.init_fn(jax.random.PRNGKey(0))
        state, ms = run_rounds(a.engine, state, data_fn, args.rounds,
                               chunk=args.chunk, tracer=tracer)
        for i in range(args.rounds):
            led = jax.tree.map(lambda x, i=i: x[i], ms["ledger"])
            print(f"event {i:>4} t={float(ms['clock'][i]):8.2f} "
                  f"v={int(ms['server_version'][i]):>3} "
                  f"tau={float(ms['staleness'][i]):>3.0f} "
                  f"loss={float(ms['loss'][i]):.3f} "
                  f"up={float(led.uplink_wire)/1e6:.2f}MB", flush=True)
        if tracer is not None:
            tracer.emit_rounds(ms, spec=a.engine.aux.get("telemetry"))
            _emit_flush_events(ms)
        if args.checkpoint:
            _save_checkpoint(state.params)
        if tracer is not None:
            tracer.close()
            print(f"trace: {args.trace} (render: python -m repro.obs.report "
                  f"{args.trace})")
        return

    n = jax.device_count()
    mp = min(args.model_parallel, n)
    if args.hierarchical and n < 2 * mp:
        raise SystemExit(
            f"--hierarchical needs a pod=2 x data x model={mp} mesh, i.e. at "
            f"least {2 * mp} devices; JAX sees {n}. On one chip use "
            f"--population (Topology.sim), or lower --model-parallel")
    if args.hierarchical:
        mesh = make_host_mesh(model=mp, pod=2, data=n // (2 * mp))
    else:
        mesh = make_host_mesh(model=mp)
    set_activation_mesh(mesh)
    print(f"mesh={dict(mesh.shape)} arch={cfg.name} "
          f"params={model.param_count():,}")

    if args.hierarchical:
        step = make_hier_fl_train_step(model, fl, mesh, chunk=args.seq)
        G, Ce = step.n_pods, step.clients_per_pod
        C = G * Ce
    else:
        step = make_fl_train_step(model, fl, mesh, chunk=args.seq)
        C = step.n_clients
    state = step.init_fn(jax.random.PRNGKey(0))

    data = FedDataConfig(vocab_size=cfg.vocab_size, num_clients=C,
                         seq_len=args.seq,
                         batch_per_client=args.batch_per_client,
                         heterogeneity=1.5)
    ev = eval_batch(data, jax.random.PRNGKey(99), batch_size=4)

    def data_fn(r):
        b = sample_round(data, jax.random.fold_in(jax.random.PRNGKey(1), r))
        if args.hierarchical:
            return {k: v.reshape((G, Ce) + v.shape[1:]) for k, v in b.items()
                    if k in ("tokens", "labels", "mask")}
        return b

    def global_params(params):
        return (jax.tree.map(lambda x: x[0], params) if args.hierarchical
                else params)

    def metrics_fn(state, m):
        # held-out eval INSIDE the compiled scan, gated to every
        # --eval-every-th round by the runner (FLConfig.eval_every)
        loss = model.loss(global_params(state.params), ev, chunk=args.seq)[0]
        return dict(m, eval_loss=loss)

    # ONE runner for the whole run — its compiled chunk scan is reused
    # across eval windows (one compilation per chunk shape)
    chunk = max(1, args.chunk)
    runner = RoundRunner(step.engine, data_fn, chunk=chunk,
                         metrics_fn=metrics_fn, tracer=tracer)
    import contextlib
    profile_cm = tracer.profile() if tracer is not None else \
        contextlib.nullcontext()
    done = 0
    with profile_cm:
        while done < args.rounds:
            k = min(chunk, args.rounds - done)
            state, ms = runner.run(state, k)
            for i in range(k):
                led = jax.tree.map(lambda x, i=i: x[i], ms["ledger"])
                print(f"round {done + i:>3} "
                      f"loss={float(ms['loss'][i]):.3f} "
                      f"up={float(led.uplink_wire)/1e6:.2f}MB "
                      f"ratio={float(led.compression_ratio()):.1f}x",
                      flush=True)
                ev_loss = float(ms["eval_loss"][i])
                if ev_loss == ev_loss:      # NaN on cadence-skipped rounds
                    print(f"eval@{done + i}: {ev_loss:.3f}", flush=True)
                    if tracer is not None:
                        tracer.event("eval", round=done + i, loss=ev_loss)
            if tracer is not None:
                # the stages naming record is written once, with the
                # first chunk's rounds
                tracer.emit_rounds(
                    ms, spec=(step.engine.aux.get("telemetry")
                              if done == 0 else None),
                    start_round=done)
            done += k
    if args.checkpoint:
        _save_checkpoint(global_params(state.params))
    if tracer is not None:
        tracer.close()
        print(f"trace: {args.trace} (render: python -m repro.obs.report "
              f"{args.trace})")


if __name__ == "__main__":
    main()
