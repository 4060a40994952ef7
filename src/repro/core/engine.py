"""RoundEngine — ONE topology-agnostic FL round executor (DESIGN.md §5).

The survey's central claim is that FL cost is dominated by *rounds of
communication*, and that schemes must be compared across topologies
(client-server, hierarchical/edge, decentralized) under identical round
semantics. This module is where those semantics live — exactly once.

A round is a :class:`RoundProgram`: an ordered sequence of **hops**

    local-update -> encode -> transport -> decode -> aggregate
                 -> server-opt -> ledger

parameterized by a :class:`Topology`:

  * ``Topology.star(client_axis)``   — clients on mesh axes, shard_map
    aggregation (``core.federated`` deployment path);
  * ``Topology.hier(sync_every)``    — client -> edge(pod) -> cloud, periodic
    cross-pod sync (``core.hierarchical``);
  * ``Topology.gossip(graph)``       — decentralized ppermute ring mixing
    (``core.gossip``);
  * ``Topology.sim(n_clients)``      — single-device vmap simulator with the
    client count decoupled from the mesh (``core.simulate``).

``FLState.comm_state`` (CommPipeline-owned error-feedback residuals / DGC
momentum) is threaded generically through *every* wire hop — star, sim,
hierarchical edge, and gossip mix alike — so biased pipelines keep their
correction state on every topology as a structural consequence of the
engine, not a per-trainer patch.

On top of the per-round program, :func:`run_rounds` compiles ``chunk`` rounds
into a single donated-argument ``jax.lax.scan`` (per-round ``CommLedger`` /
metrics stacked out), replacing the Python round loop's per-round dispatch +
host sync in every driver (launch/train, benchmarks, examples).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compress.api import Identity, make_compressor
from repro.compress.pipeline import (error_feedback, momentum_correction,
                                     scoped_decode, scoped_encode)
from repro.compress.secure_agg import (DPNoise, MASK_TAG, SecAgg,
                                       bind_n_leaves, has_mask_ctx,
                                       inject_mask_ctx)
from repro.core import aggregation, selection as sel, server_opt
from repro.core import scenario as scn_mod
from repro.core.aggregation import comm_state_init, comm_state_specs
from repro.core.types import CommLedger, FLConfig, FLState
from repro.data.pipeline import capability_latency
from repro.models import sharding as shd
from repro.models.model import Model
from repro.obs import scopes
from repro.obs import telemetry as obs_tel

PyTree = Any


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """Which shape the round's transport hops take.

    ``graph`` (gossip) is a tuple of ``(edge, mix_weight)`` entries where
    ``edge`` is either a ring offset (int — every node sends to
    ``(i+off) % C``) or an explicit permutation tuple of length C (fixed
    points ``sigma[i] == i`` do not send).  The per-node self-weight is
    whatever the incoming edge weights leave over; the builder asserts the
    resulting mixing matrix is doubly stochastic.  Use
    :func:`expander_graph` / :func:`erdos_renyi_graph` (or the
    ``Topology.gossip_*`` constructors) for non-ring graphs."""

    kind: str                          # star | hier | gossip | sim | async
    n_clients: int = 0                 # sim/async only (decoupled from mesh)
    sync_every: int = 4                # hier only (cloud hop period)
    graph: tuple = ((1, 0.25), (-1, 0.25))   # gossip only
    client_axis: str = ""              # star only ("" = from ArchConfig)
    buffer_size: int = 0               # async only: FedBuff K (0 = from
                                       # FLConfig, then C)
    staleness_alpha: float = None      # async only: (1+tau)^(-alpha) decay
                                       # (None = from FLConfig)
    latency_profile: str = ""          # async only ("" = from FLConfig)
    flush_deadline: float = None       # async only: virtual-clock flush
                                       # deadline (None = from FLConfig;
                                       # 0 = count-only FedBuff)

    @staticmethod
    def star(client_axis: str = "") -> "Topology":
        return Topology(kind="star", client_axis=client_axis)

    @staticmethod
    def hier(sync_every: int = 4) -> "Topology":
        return Topology(kind="hier", sync_every=sync_every)

    @staticmethod
    def gossip(graph=None) -> "Topology":
        return Topology(kind="gossip",
                        graph=tuple(graph) if graph else ((1, 0.25), (-1, 0.25)))

    @staticmethod
    def gossip_expander(n_clients: int, degree: int = 4) -> "Topology":
        return Topology.gossip(expander_graph(n_clients, degree))

    @staticmethod
    def gossip_er(n_clients: int, p: float = 0.5, seed: int = 0) -> "Topology":
        return Topology.gossip(erdos_renyi_graph(n_clients, p, seed))

    @staticmethod
    def sim(n_clients: int) -> "Topology":
        return Topology(kind="sim", n_clients=n_clients)

    @staticmethod
    def async_(n_clients: int, buffer_size: int = 0,
               staleness_alpha: float = None,
               latency_profile: str = "",
               flush_deadline: float = None) -> "Topology":
        """Virtual-clock asynchronous FL (core.async_engine, DESIGN.md §7):
        FedBuff buffering (``buffer_size`` K; 1 = FedAsync, 0/C = the
        degenerate synchronous limit), FedAsync staleness decay
        ``(1+tau)^(-staleness_alpha)``, per-dispatch latencies drawn from
        ``latency_profile`` over the FedMCCS device resource vectors, and
        adaptive buffer sizing via ``flush_deadline`` (> 0: also flush when
        the virtual clock passes the last flush + deadline, DESIGN.md §8).
        Knobs left at their sentinel (0 / None / \"\") fall back to the
        ``FLConfig.async_buffer_size / staleness_alpha / latency_profile /
        async_flush_deadline`` fields at engine build time."""
        return Topology(kind="async", n_clients=n_clients,
                        buffer_size=buffer_size,
                        staleness_alpha=staleness_alpha,
                        latency_profile=latency_profile,
                        flush_deadline=flush_deadline)


# ---------------------------------------------------------------------------
# Gossip graph constructors + the doubly-stochastic contract
# ---------------------------------------------------------------------------

def _graph_edges(spec, C: int):
    """Directed (src, dst) pairs for one graph entry: a ring offset (int) or
    an explicit permutation tuple (fixed points do not send)."""
    if isinstance(spec, (int, np.integer)):
        return [(i, (i + int(spec)) % C) for i in range(C)]
    sigma = tuple(int(s) for s in spec)
    if len(sigma) != C or sorted(sigma) != list(range(C)):
        raise ValueError(f"graph entry {spec!r} is not a permutation of "
                         f"range({C})")
    return [(i, sigma[i]) for i in range(C) if sigma[i] != i]


def mixing_matrix(graph, C: int) -> np.ndarray:
    """The dense (C, C) gossip mixing matrix W (row i mixes *into* node i):
    W[dst, src] += w per edge, and each node keeps whatever its incoming
    edge weights leave over (per-node self-weight)."""
    W = np.zeros((C, C))
    for spec, w in graph:
        for src, dst in _graph_edges(spec, C):
            W[dst, src] += float(w)
    np.fill_diagonal(W, np.diag(W) + 1.0 - W.sum(axis=1))
    return W


def check_doubly_stochastic(W: np.ndarray, atol: float = 1e-6) -> None:
    """Gossip averaging preserves the model mean and contracts to consensus
    iff W is doubly stochastic with non-negative entries — checked at engine
    build time for every graph."""
    if W.min() < -atol:
        raise ValueError(f"mixing matrix has negative entries "
                         f"(min {W.min():.4f}): edge weights too large — "
                         f"a node's incoming weights must sum to <= 1")
    for axis, name in ((1, "row"), (0, "column")):
        s = W.sum(axis=axis)
        if not np.allclose(s, 1.0, atol=atol):
            raise ValueError(f"mixing matrix {name} sums deviate from 1 "
                             f"(max |err| {np.abs(s - 1).max():.4f}) — "
                             f"graph is not doubly stochastic")


def expander_graph(n: int, degree: int = 4) -> tuple:
    """Circulant power-of-two expander: offsets ±1, ±2, ±4, ... with uniform
    weights 1/(E+1).  Each offset is a permutation, so the mix is a convex
    combination of permutation matrices — doubly stochastic by construction —
    with the log-diameter mixing of the hypercube family."""
    offs = []
    j = 0
    while len(offs) < degree and (1 << j) <= n // 2:
        o = 1 << j
        offs.append(o)
        if len(offs) < degree and (n - o) % n not in offs and n - o != o:
            offs.append(n - o)        # the symmetric (negative) offset
        j += 1
    w = 1.0 / (len(offs) + 1)
    return tuple((o, w) for o in offs)


def erdos_renyi_graph(n: int, p: float = 0.5, seed: int = 0) -> tuple:
    """Erdős–Rényi G(n, p) gossip graph: sample the undirected edge set,
    greedily edge-color it into matchings (each an involution permutation —
    ppermute-able), uniform edge weight 1/(max_degree + 1) so every node's
    self-weight stays non-negative (Metropolis-style) and W is symmetric
    doubly stochastic."""
    rng = np.random.default_rng(seed)
    upper = np.triu(rng.random((n, n)) < p, 1)
    edges = list(zip(*np.nonzero(upper)))
    deg = np.zeros(n, int)
    for i, j in edges:
        deg[i] += 1
        deg[j] += 1
    if not edges:
        raise ValueError(f"G({n}, {p}) sample (seed={seed}) has no edges — "
                         f"raise p or change the seed")
    w = 1.0 / (deg.max() + 1)
    # greedy edge coloring: assign each edge the smallest color unused at
    # either endpoint; each color class is a matching
    used: list = [set() for _ in range(n)]
    matchings: list = []
    for i, j in edges:
        c = 0
        while c in used[i] or c in used[j]:
            c += 1
        used[i].add(c)
        used[j].add(c)
        while len(matchings) <= c:
            matchings.append(list(range(n)))
        matchings[c][i], matchings[c][j] = j, i
    return tuple((tuple(m), w) for m in matchings)


# ---------------------------------------------------------------------------
# RoundProgram: the hop sequence
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)          # identity hash: jit-able callable
class RoundProgram:
    """One FL round as an ordered sequence of named hops.

    Each hop is ``fn(ctx) -> ctx`` over a plain dict context; the program is
    traced once under jit so hop granularity costs nothing at runtime. Each
    hop runs under ``jax.named_scope("hop.<name>")`` (HLO metadata only), so
    a device trace attributes its operations to hops (``repro.obs.scopes``).
    The final hop must leave ``ctx["new_state"]`` / ``ctx["metrics"]``."""

    topology: Topology
    hops: tuple                        # ((name, fn), ...)

    def __call__(self, state: FLState, batch) -> tuple:
        ctx = {"state": state, "batch": batch}
        for name, fn in self.hops:
            with jax.named_scope(scopes.HOP + name):
                ctx = fn(ctx)
        return ctx["new_state"], ctx["metrics"]

    @property
    def hop_names(self) -> tuple:
        return tuple(name for name, _ in self.hops)


@dataclasses.dataclass
class RoundEngine:
    """A built round executor for one (model, fl, topology) binding."""
    topology: Topology
    program: RoundProgram
    round_fn: Any                      # (state, batch) -> (state, metrics)
    init_fn: Any                       # rng -> FLState
    n_clients: int
    terms: dict
    state_shardings: Any = None        # star/hier/gossip (mesh paths)
    batch_sharding_fn: Any = None      # star only
    programs: dict = dataclasses.field(default_factory=dict)
    # extra separately-compilable programs (e.g. hier edge / cloud steps,
    # kept distinct so the dry-run HLO keeps each collective set honest)
    aux: dict = dataclasses.field(default_factory=dict)
    # topology metadata (e.g. hier's n_pods / clients_per_pod)
    eval_every: int = 1
    # metrics_fn cadence inside run_rounds (FLConfig.eval_every)


# ---------------------------------------------------------------------------
# Uplink pipeline + static ledger terms (shared by every topology)
# ---------------------------------------------------------------------------

def uplink_pipeline(fl: FLConfig):
    """The uplink CommPipeline from config: the spec string (legacy name or
    ``"a:x>>b:y"`` chain) plus the stateful correction wrapper — DGC momentum
    correction if ``dgc_momentum`` is set (with the warm-up sparsity schedule
    when ``dgc_warmup_rounds`` > 0), else error feedback for biased
    pipelines. Wrappers leave wire/entropy bits unchanged."""
    if fl.dgc_warmup_rounds > 0 and fl.dgc_momentum <= 0.0:
        raise ValueError("dgc_warmup_rounds is a DGC knob — it needs "
                         "dgc_momentum > 0 to take effect")
    frac = fl.topk_fraction
    warmup = fl.dgc_warmup_rounds if fl.dgc_momentum > 0.0 else 0
    if warmup > 0:
        # DGC warm-up: round r transmits fraction f_target^((r+1)/(W+1)) —
        # the wire payload is sized for the first (widest) round and later
        # rounds mask down inside it (static shapes under jit).
        frac = fl.topk_fraction ** (1.0 / (warmup + 1.0))
    up = make_compressor(fl.uplink_compressor, fraction=frac,
                         block=fl.qsgd_block, rows=fl.sketch_rows,
                         cols=fl.sketch_cols, backend=fl.backend,
                         wire_format=fl.wire_format)
    if warmup > 0 and not up.is_identity:
        # the widened capacity must actually reach the wire: specs with an
        # explicit per-stage fraction ("topk:0.01>>...") override the
        # fraction kwarg and would silently make the warm-up a no-op
        at_target = make_compressor(fl.uplink_compressor,
                                    fraction=fl.topk_fraction,
                                    block=fl.qsgd_block, rows=fl.sketch_rows,
                                    cols=fl.sketch_cols, backend=fl.backend,
                                    wire_format=fl.wire_format)
        if up.wire_bits(1 << 16) == at_target.wire_bits(1 << 16):
            raise ValueError(
                "dgc_warmup_rounds needs a fraction-kwarg-driven uplink "
                f"spec (e.g. 'topk' + topk_fraction); "
                f"{fl.uplink_compressor!r} ignores the warm-up widening")
    up = _apply_privacy(fl, up)
    if fl.dgc_momentum > 0.0 and not up.is_identity:
        up = momentum_correction(up, fl.dgc_momentum,
                                 warmup_rounds=warmup,
                                 final_fraction=fl.topk_fraction)
    elif up.biased and fl.error_feedback:
        up = error_feedback(up)
    return up


def _apply_privacy(fl: FLConfig, up):
    """FLConfig privacy knobs as spec-suffix equivalents (DESIGN.md §11):
    dpnoise at the wire boundary first, secagg masking outermost (so the
    noised update is what gets quantized and masked). EF/DGC wrap outside
    privacy — residuals are computed from the *unmasked* decode, so they
    match the unmasked run bit-for-bit."""
    if fl.dp_sigma > 0.0 or fl.dp_clip > 0.0:
        clip = fl.dp_clip if fl.dp_clip > 0.0 else float("inf")
        up = DPNoise(up, fl.dp_sigma, clip)
    if fl.secure_agg and not up.is_identity:
        up = SecAgg(up)   # raises with the carrier rule for float pipelines
    return up


def _param_sizes(model: Model):
    """Flat per-leaf parameter counts (the ledger's byte-accounting basis)."""
    return [int(np.prod(d.shape)) for d in
            jax.tree.leaves(model.defs,
                            is_leaf=lambda x: hasattr(x, "logical"))]


def ledger_terms(model: Model, fl: FLConfig):
    """Static per-selected-client byte terms for the round ledger."""
    up = uplink_pipeline(fl)
    down = make_compressor(fl.downlink_compressor, block=fl.qsgd_block,
                           backend=fl.backend, wire_format=fl.wire_format)
    sizes = _param_sizes(model)
    # dpnoise splits its joint L2 clip budget across this model's leaves
    # (clip/sqrt(L) each) — binding L here keeps the billed rho=0.5/sigma^2
    # equal to what encode actually spends (DESIGN.md §11)
    bind_n_leaves(up, len(sizes))
    # SCAFFOLD ships control variates, FedDANE ships a gradient round: 2x
    scaff = 2.0 if fl.algorithm in ("scaffold", "feddane") else 1.0
    t = {
        "up_wire": scaff * sum(up.wire_bits(n) for n in sizes) / 8.0,
        "up_entropy": scaff * sum(up.entropy_bits(n) for n in sizes) / 8.0,
        "down_wire": sum(down.wire_bits(n) for n in sizes) / 8.0,
        "dense": sum(32.0 * n for n in sizes) / 8.0,
        # zCDP spent per selected client this round (0 unless dpnoise is in
        # the uplink); rides the ledger like bytes (DESIGN.md §11)
        "dp_rho": up.dp_rho_per_round(),
    }
    return t, up, down


def _telemetry_spec(fl: FLConfig, up, down, sizes):
    """The static per-stage byte spec when the flight recorder is on, else
    None (repro.obs.telemetry).  Scaled exactly like ``ledger_terms``:
    SCAFFOLD / FedDANE bill 2x on the uplink."""
    if not fl.telemetry:
        return None
    scaff = 2.0 if fl.algorithm in ("scaffold", "feddane") else 1.0
    return obs_tel.telemetry_spec(up, down, sizes, up_scale=scaff)


def _make_ledger(terms: dict, n_sel) -> CommLedger:
    led = CommLedger(
        uplink_wire=n_sel * terms["up_wire"],
        uplink_entropy=n_sel * terms["up_entropy"],
        downlink_wire=n_sel * terms["down_wire"],
        uplink_dense=n_sel * terms["dense"],
        downlink_dense=n_sel * terms["dense"],
    )
    if terms.get("dp_rho", 0.0):
        led = dataclasses.replace(led, dp_rho=n_sel * jnp.float32(
            terms["dp_rho"]))
    return led


# ---------------------------------------------------------------------------
# Client local update (shared by every topology)
# ---------------------------------------------------------------------------

def _client_update(model: Model, fl: FLConfig, params, batch_c, rng,
                   control, c_i, chunk, global_grad=None, n_steps=None):
    """One client's local training. Returns (delta, mean_loss, first_loss,
    new_c_i). For ``feddane`` [49], ``global_grad`` is the aggregated
    gradient at the global params; the local steps use the DANE-corrected
    gradient g_i(w') + (g(w) − g_i(w)) + mu·(w' − w).

    ``n_steps`` (scalar int32, scenario epoch scaling) truncates the local
    solve to the first ``n_steps`` of the ``local_steps`` scan iterations:
    the scan keeps its static length (shape discipline) and later steps
    freeze the client params behind a ``jnp.where`` — same per-step
    arithmetic, statically absent when ``n_steps is None``."""
    E, lr = fl.local_steps, fl.local_lr
    loss_fn = lambda p: model.loss(p, batch_c, chunk=chunk)[0]

    ddt = jnp.bfloat16 if fl.delta_dtype == "bf16" else jnp.float32
    fast = (E == 1 and fl.algorithm in ("fedavg", "fedsgd")
            and fl.fedprox_mu == 0.0)
    if fast:
        loss, g = jax.value_and_grad(loss_fn)(params)
        delta = jax.tree.map(lambda g_: (-lr * g_).astype(ddt), g)
        return delta, loss, loss, c_i

    dane_corr = None
    if fl.algorithm == "feddane" and global_grad is not None:
        g_i0 = jax.grad(loss_fn)(params)
        dane_corr = jax.tree.map(
            lambda gg, gi: gg.astype(jnp.float32) - gi.astype(jnp.float32),
            global_grad, g_i0)

    def step(p_c, _):
        loss, g = jax.value_and_grad(loss_fn)(p_c)
        if fl.algorithm in ("fedprox", "feddane") and fl.fedprox_mu:
            g = jax.tree.map(
                lambda g_, pc, p0: g_ + fl.fedprox_mu * (pc - p0).astype(g_.dtype),
                g, p_c, params)
        if dane_corr is not None:
            g = jax.tree.map(lambda g_, d: g_ + d.astype(g_.dtype),
                             g, dane_corr)
        if fl.algorithm == "scaffold":
            g = jax.tree.map(
                lambda g_, c, ci: g_ + (c - ci).astype(g_.dtype), g, control, c_i)
        p_c = jax.tree.map(lambda a, g_: (a.astype(jnp.float32)
                                          - lr * g_.astype(jnp.float32)
                                          ).astype(a.dtype), p_c, g)
        return p_c, loss

    if n_steps is None:
        p_fin, losses = jax.lax.scan(step, params, None, length=E)
        mean_loss = losses.mean()
    else:
        def gated(p_c, j):
            p_new, loss = step(p_c, None)
            active = j < n_steps
            p_c = jax.tree.map(
                lambda old, new: jnp.where(active, new, old), p_c, p_new)
            return p_c, jnp.where(active, loss, 0.0)
        p_fin, losses = jax.lax.scan(gated, params, jnp.arange(E))
        # n_steps >= 1 always (scenario.epoch_steps floors it), so step 0
        # is active and losses[0] stays the selection hop's first loss
        mean_loss = losses.sum() / n_steps.astype(jnp.float32)
    delta = jax.tree.map(
        lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32))
        .astype(ddt), p_fin, params)
    new_c_i = c_i
    if fl.algorithm == "scaffold":
        new_c_i = jax.tree.map(
            lambda ci, c, d: ci - c - d / (E * lr), c_i, control, delta)
    return delta, mean_loss, losses[0], new_c_i


# ---------------------------------------------------------------------------
# The shared dispatch body (DESIGN.md §8) — downlink >> local-update vmap >>
# wire-boundary optimization_barrier >> CommPipeline encode/decode.  Both the
# synchronous sim/star hops and the AsyncEngine's generation dispatch run
# THESE functions, so the degenerate async == sync bit-exactness contract is
# structural: a change to the sync wire is, by construction, a change to the
# async wire (there is no second copy to diverge).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)          # identity hash: jit-able callable
class Dispatch:
    """One dispatch generation, decomposed so programs can interleave their
    topology-specific hops (selection, CMFL, SCAFFOLD control) between the
    shared stages:

      * ``downlink(params, k_down)`` — LFL-quantised global broadcast;
      * ``local_update(params, model_batch, k_loc)`` — the batched client
        vmap -> ``(deltas, mean_losses, first_losses)``;
      * ``wire_rows(deltas, comm_state, k_up)`` — the wire boundary: one
        ``optimization_barrier`` materializing the deltas, then the batched
        CommPipeline encode/decode -> ``((C,)-led decoded rows, new
        comm_state)``;
      * ``aggregate_rows(rows, w_num, wsum)`` — barrier + weighted mean of
        decoded rows (the sync wire aggregates rows it just decoded, the
        async flush aggregates rows buffered from earlier events — the
        barrier pins both to the same materialization, DESIGN.md §7/§8).

    ``__call__`` composes the first three — the AsyncEngine's whole
    per-generation computation.

    ``epoch_steps(batch) -> (n_steps, scale)`` (scenario epoch scaling,
    DESIGN.md §13) is attached only when the scenario enables it — every
    caller gates on ``epoch_steps is not None`` at build time, so the OFF
    graph is byte-identical to a dispatch built without a scenario."""

    downlink: Callable
    local_update: Callable
    wire_rows: Callable
    aggregate_rows: Callable
    n_clients: int
    epoch_steps: Optional[Callable] = None

    @staticmethod
    def model_batch(batch) -> dict:
        """Model inputs only (FL metadata keys stay out of the loss vmap)."""
        return {k: v for k, v in batch.items()
                if k not in ("sizes", "resources", "ids")}

    def __call__(self, params, batch, comm_state, k_loc, k_down, k_up):
        params = self.downlink(params, k_down)
        if self.epoch_steps is not None:
            n_steps, _ = self.epoch_steps(batch)
            deltas, losses, _ = self.local_update(
                params, self.model_batch(batch), k_loc, n_steps)
        else:
            deltas, losses, _ = self.local_update(
                params, self.model_batch(batch), k_loc)
        rows, new_comm = self.wire_rows(deltas, comm_state, k_up)
        return rows, losses, new_comm


def make_dispatch(model: Model, fl: FLConfig, up, down, C: int,
                  chunk: int, scenario=None) -> Dispatch:
    """Build the shared dispatch body for one (model, fl) binding over ``C``
    vmapped clients with uplink pipeline ``up`` / downlink ``down``.
    ``scenario`` (a :class:`repro.core.scenario.Scenario`) with
    ``epoch_scale > 0`` attaches the heterogeneity-aware per-client
    local-step budget; any other scenario knob leaves the dispatch body
    untouched (availability/dropout act on aggregation weights in the
    round programs)."""
    stateful = up.stateful
    masked = has_mask_ctx(up)

    def downlink(params, k_down):
        if down.is_identity:
            return params
        return jax.tree.map(
            lambda p: down.roundtrip(k_down,
                                     p.reshape(-1).astype(jnp.float32))
            .reshape(p.shape).astype(p.dtype), params)

    def local_update(params, model_batch, k_loc, n_steps=None):
        rngs = jax.random.split(k_loc, C)
        if n_steps is None:
            deltas, losses, first_losses, _ = jax.vmap(
                lambda b, r: _client_update(
                    model, fl, params, b, r, None, None,
                    chunk))(model_batch, rngs)
        else:
            deltas, losses, first_losses, _ = jax.vmap(
                lambda b, r, ns: _client_update(
                    model, fl, params, b, r, None, None, chunk,
                    n_steps=ns))(model_batch, rngs, n_steps)
        return deltas, losses, first_losses

    epoch_steps = None
    if scenario is not None and scenario.epoch_scale > 0.0:
        if fl.local_steps <= 1:
            raise ValueError(
                "scenario epoch scaling needs local_steps > 1 — there is "
                "no per-client budget to truncate at a single local step")
        if fl.algorithm not in ("fedavg", "fedsgd", "fedprox"):
            raise ValueError(
                f"scenario epoch scaling truncates the local scan per "
                f"client — the {fl.algorithm!r} control-variate bookkeeping "
                f"assumes a fixed step count; use fedavg/fedsgd/fedprox")

        def epoch_steps(batch):
            res = batch.get("resources", jnp.ones((C, 4), jnp.float32))
            return scn_mod.epoch_steps(scenario, fl.local_steps, res)

    def wire_rows(deltas, comm_state, k_up):
        # The wire boundary: materialize the client deltas BEFORE encoding —
        # without the barrier XLA fuses e.g. the E=1 delta multiply into the
        # error-feedback residual add as an FMA, and a consumer that receives
        # the delta materialized in an earlier program (the AsyncEngine's
        # buffered rows) could never reproduce the arithmetic (DESIGN.md §7)
        deltas = jax.lax.optimization_barrier(deltas)
        rngs_up = jax.random.split(k_up, C)
        dec_rows, st_rows = [], []
        for li, leaf in enumerate(jax.tree.leaves(deltas)):
            shape = leaf.shape[1:]
            flat = leaf.reshape(C, -1).astype(jnp.float32)
            rs = jax.vmap(lambda r: jax.random.fold_in(r, li))(rngs_up)
            if stateful:
                if masked:
                    # secagg context for this hop: a round/leaf-shared mask
                    # key, the client's vmap lane as ring index, cohort C.
                    # Injected fresh each dispatch, so async re-dispatches
                    # (flush) re-key their masks with their own k_up.
                    mkey = jax.random.fold_in(
                        jax.random.fold_in(k_up, MASK_TAG), li)

                    def one(x, r, st, i, mkey=mkey):
                        st = inject_mask_ctx(st, mkey, i, C)
                        payload, nst = scoped_encode(up, st, r, x)
                        return scoped_decode(up, payload, x.shape[0]), nst
                    dec, nst = jax.vmap(one)(
                        flat, rs, comm_state[li],
                        jnp.arange(C, dtype=jnp.int32))
                else:
                    def one(x, r, st):
                        payload, nst = scoped_encode(up, st, r, x)
                        return scoped_decode(up, payload, x.shape[0]), nst
                    dec, nst = jax.vmap(one)(flat, rs, comm_state[li])
                st_rows.append(nst)
            else:
                def one(x, r):
                    payload, _ = scoped_encode(up, up.init(x.shape), r, x)
                    return scoped_decode(up, payload, x.shape[0])
                dec = jax.vmap(one)(flat, rs)
            dec_rows.append(dec.reshape((C,) + shape))
        dec_tree = jax.tree.unflatten(jax.tree.structure(deltas), dec_rows)
        return dec_tree, (tuple(st_rows) if stateful else None)

    def aggregate_rows(rows, w_num, wsum):
        # materialize the decoded rows before aggregating — the sync wire
        # feeds rows straight out of wire_rows, the AsyncEngine feeds rows
        # committed by earlier events; the barrier makes the weighted mean
        # lower identically in both programs (bit-exact degenerate
        # equivalence, DESIGN.md §7)
        with jax.named_scope(scopes.STAGE + "aggregate"):
            rows = jax.lax.optimization_barrier(rows)
            return jax.tree.map(
                lambda leaf: ((w_num[:, None] * leaf.reshape(C, -1)).sum(0)
                              / wsum).reshape(leaf.shape[1:]), rows)

    return Dispatch(downlink=downlink, local_update=local_update,
                    wire_rows=wire_rows, aggregate_rows=aggregate_rows,
                    n_clients=C, epoch_steps=epoch_steps)


# ---------------------------------------------------------------------------
# Wire implementations (encode -> transport -> decode -> aggregate), one per
# topology.  Every one threads the pipeline comm_state.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Wire:
    """Transport hop bundle for the server topologies (star / sim)."""
    aggregate: Callable        # (deltas(C,..), weights, rng, comm_state)
    #                            -> (agg, new_comm_state)
    aggregate_dense: Callable  # (tree(C,..), weights, rng) -> agg  (SCAFFOLD)
    needs_ids: bool = False    # population wires take the cohort ids too:
    #                            aggregate(..., comm_state, ids)


def _star_wire(mesh, pspecs, up, client_axis, abs_params, need_dense) -> _Wire:
    aggregate = aggregation.make_aggregator(mesh, pspecs, up, client_axis,
                                            abstract_params=abs_params)
    agg_dense = None
    if need_dense:
        dense = aggregation.make_aggregator(mesh, pspecs, Identity(),
                                            client_axis)
        agg_dense = lambda t, w, r: dense(t, w, r, None)[0]
    return _Wire(aggregate=aggregate, aggregate_dense=agg_dense)


def _sim_wire(dispatch: Dispatch, C) -> _Wire:
    """Single-device wire, built ON the shared dispatch body: encode/decode
    rows via ``dispatch.wire_rows`` and the weighted mean via
    ``dispatch.aggregate_rows`` — the same two functions the AsyncEngine
    runs, so sync and async cannot silently diverge (DESIGN.md §8).
    Pipeline state (EF residual / DGC momentum) rides along with a leading
    C dim."""

    def aggregate(deltas, weights, rng, comm_state):
        rows, new_comm = dispatch.wire_rows(deltas, comm_state, rng)
        wsum = jnp.maximum(weights.sum(), 1e-9)
        return dispatch.aggregate_rows(rows, weights, wsum), new_comm

    def aggregate_dense(tree, weights, rng):
        wsum = jnp.maximum(weights.sum(), 1e-9)
        return jax.tree.map(
            lambda a: (weights.reshape((C,) + (1,) * (a.ndim - 1)) * a)
            .sum(0) / wsum, tree)

    return _Wire(aggregate=aggregate, aggregate_dense=aggregate_dense)


def _population_wire(dispatch: Dispatch, store, M: int) -> _Wire:
    """Sim wire over a sampled cohort with store-backed pipeline state
    (DESIGN.md §9).  ``comm_state`` is the ResidualStore dict, not dense
    (C,)-led rows: the cohort's rows are **gathered** at the dispatch
    boundary, advanced by the same ``dispatch.wire_rows`` the dense wire
    runs, and **scattered** back at the commit (the wire hop is the commit
    point for synchronous rounds — the server has irrevocably consumed the
    payload, so the residual advance is final).  With ``capacity >=
    n_clients`` and ``cohort == n_clients`` gather/scatter are identities
    and this wire is bit-exact vs :func:`_sim_wire`."""

    def aggregate(deltas, weights, rng, comm_state, ids):
        rows_in, st = store.gather(comm_state, ids)
        rows, new_rows = dispatch.wire_rows(deltas, rows_in, rng)
        st = store.scatter(st, ids, new_rows)
        wsum = jnp.maximum(weights.sum(), 1e-9)
        return dispatch.aggregate_rows(rows, weights, wsum), st

    def aggregate_dense(tree, weights, rng):
        wsum = jnp.maximum(weights.sum(), 1e-9)
        return jax.tree.map(
            lambda a: (weights.reshape((M,) + (1,) * (a.ndim - 1)) * a)
            .sum(0) / wsum, tree)

    return _Wire(aggregate=aggregate, aggregate_dense=aggregate_dense,
                 needs_ids=True)


def _star_population_wire(base: _Wire, store) -> _Wire:
    """Star wire over a population: gather the cohort's store rows OUTSIDE
    the shard_map collective, run the unchanged stateful aggregator on them
    (it treats its ``comm_state`` argument as (C,)-led rows and returns the
    advanced rows), then scatter the advance back into the store."""

    def aggregate(deltas, weights, rng, comm_state, ids):
        rows_in, st = store.gather(comm_state, ids)
        agg, new_rows = base.aggregate(deltas, weights, rng, rows_in)
        st = store.scatter(st, ids, new_rows)
        return agg, st

    return _Wire(aggregate=aggregate, aggregate_dense=base.aggregate_dense,
                 needs_ids=True)


# ---------------------------------------------------------------------------
# The server-topology round (star + sim share this body verbatim)
# ---------------------------------------------------------------------------

def _fl_scenario(fl: FLConfig):
    """The FLConfig's scenario, or None when every knob is at its default —
    the builders thread None so all scenario hops are statically absent
    (the conformance contract, tests/test_scenario.py)."""
    scn = scn_mod.Scenario.from_fl(fl)
    return scn if scn.enabled else None


def _attach_scenario(population, scenario):
    """Give the population the scenario's availability trace (its mask and
    the selection hop then share one schedule).  The population keeps its
    own duty rate; a no-op without a scenario or when the caller already
    attached one."""
    if (scenario is None or population is None
            or population.scenario is not None):
        return population
    return dataclasses.replace(population, scenario=scenario)


def _build_server_program(model: Model, fl: FLConfig, topo: Topology,
                          wire: _Wire, terms: dict, dispatch: Dispatch,
                          C: int, chunk: int,
                          population=None, tele=None,
                          store=None, scenario=None) -> RoundProgram:
    scaffold = fl.algorithm == "scaffold"
    simulator = topo.kind == "sim"

    def hop_rng(ctx):
        st = ctx["state"]
        rng, r_down, r_sel, r_up, r_next = jax.random.split(st.rng, 5)
        ctx.update(rng=rng, r_down=r_down, r_sel=r_sel, r_up=r_up,
                   r_next=r_next)
        return ctx

    def hop_cohort(ctx):
        # this round's client ids — pure in (population.seed, round), so the
        # data pipeline (cohort_data_fn) independently computes the SAME ids
        ctx["ids"] = population.cohort_ids(ctx["state"].round)
        return ctx

    def hop_downlink(ctx):
        # downlink (LFL): clients train from a quantised global model —
        # the shared dispatch body's downlink stage (DESIGN.md §8)
        ctx["params"] = dispatch.downlink(ctx["state"].params, ctx["r_down"])
        return ctx

    def hop_dane_gradient(ctx):
        # FedDANE [49]: one extra communication round — aggregate the global
        # gradient at w before the corrected local solves (ledger counts 2x)
        gg = None
        if simulator and fl.algorithm == "feddane":
            params = ctx["params"]
            g_each = jax.vmap(lambda b: jax.grad(
                lambda p: model.loss(p, b, chunk=chunk)[0])(params))(
                ctx["model_batch"])
            gg = jax.tree.map(lambda g: g.astype(jnp.float32).mean(0), g_each)
        ctx["global_grad"] = gg
        return ctx

    def hop_model_batch(ctx):
        ctx["model_batch"] = Dispatch.model_batch(ctx["batch"])
        return ctx

    def hop_local_update(ctx):
        st, params = ctx["state"], ctx["params"]
        if scaffold:
            rngs = jax.random.split(ctx["rng"], C)
            deltas, losses, first_losses, new_ci = jax.vmap(
                lambda b, r, ci: _client_update(model, fl, params, b, r,
                                                st.control, ci, chunk))(
                ctx["model_batch"], rngs, st.client_controls)
        elif ctx["global_grad"] is not None:
            # FedDANE's corrected solve carries the extra aggregated
            # gradient — the one per-client signature the shared body
            # doesn't take (async rejects feddane for the same reason)
            rngs = jax.random.split(ctx["rng"], C)
            deltas, losses, first_losses, _ = jax.vmap(
                lambda b, r: _client_update(model, fl, params, b, r,
                                            None, None, chunk,
                                            global_grad=ctx["global_grad"]))(
                ctx["model_batch"], rngs)
            new_ci = None
        elif dispatch.epoch_steps is not None:
            # scenario epoch scaling (DESIGN.md §13): the dispatch body's
            # local-update stage with per-client step budgets from the
            # FedMCCS capability profile
            n_steps, escale = dispatch.epoch_steps(ctx["batch"])
            deltas, losses, first_losses = dispatch.local_update(
                params, ctx["model_batch"], ctx["rng"], n_steps)
            ctx["scn_escale"] = escale
            new_ci = None
        else:
            # the shared dispatch body's local-update stage (DESIGN.md §8)
            deltas, losses, first_losses = dispatch.local_update(
                params, ctx["model_batch"], ctx["rng"])
            new_ci = None
        ctx.update(deltas=deltas, losses=losses, first_losses=first_losses,
                   new_ci=new_ci)
        return ctx

    def hop_select(ctx):
        batch = ctx["batch"]
        sizes = batch.get("sizes", jnp.ones((C,), jnp.float32))
        resources = batch.get("resources", jnp.ones((C, 4), jnp.float32))
        avail = None
        if population is not None and population.availability_active:
            # per-(id, round) dropout of sampled clients — statically
            # skipped at availability == 1.0 with a static trace (the
            # degenerate contract).  The mask comes from the ONE shared
            # implementation in core.scenario via the population.
            avail = population.availability_mask(ctx["state"].round,
                                                 ctx["ids"])
        elif (population is None and scenario is not None
              and scenario.availability_on):
            # dense sim/star path: the same shared trace over the static
            # client slots (ids are the vmap lanes)
            avail = scn_mod.availability_mask(
                scenario, scenario.seed, scenario.availability,
                ctx["state"].round, jnp.arange(C, dtype=jnp.int32))
        weights = sel.select(fl, ctx["r_sel"], losses=ctx["first_losses"],
                             resources=resources, sizes=sizes,
                             availability=avail)
        ctx["weights"] = weights
        if avail is not None:
            ctx["avail"] = avail
        return ctx

    def hop_scenario_dropout(ctx):
        # mid-round dropout (DESIGN.md §13): a per-client survival draw
        # against the round's elapsed virtual time (the deterministic
        # capability latency).  Dropped clients become zero-weight rows in
        # Dispatch.aggregate_rows — partial-update semantics, payload
        # shapes untouched; under secagg the decode unmasks per client via
        # the payload ctx, so zero-weighting is the existing recover path
        # (tests/test_secure_agg.py).  Appended only when the scenario's
        # dropout hazard is > 0 (the OFF graph has no such hop).
        batch = ctx["batch"]
        res = batch.get("resources", jnp.ones((C, 4), jnp.float32))
        lat = capability_latency(res)
        ids = ctx.get("ids")
        if ids is None:
            ids = jnp.arange(C, dtype=jnp.int32)
        survive = scn_mod.survival_mask(scenario, ctx["state"].round,
                                        ids, lat)
        selected_before = (ctx["weights"] > 0).astype(jnp.float32)
        ctx["weights"] = ctx["weights"] * survive
        ctx["scn_dropped"] = (selected_before * (1.0 - survive)).sum()
        return ctx

    def hop_cmfl(ctx):
        # CMFL [35]: drop updates whose sign-agreement with the previous
        # global update falls below the threshold (they are "irrelevant" and
        # never uploaded — the ledger sees the reduced n_sel). Sim path.
        st, deltas, weights = ctx["state"], ctx["deltas"], ctx["weights"]
        d_flat = jnp.concatenate([l.reshape(C, -1) for l in
                                  jax.tree.leaves(deltas)], axis=1)
        p_flat = jnp.concatenate([l.reshape(-1) for l in
                                  jax.tree.leaves(st.prev_delta)])
        rel = (jnp.sign(d_flat) == jnp.sign(p_flat)[None, :]).mean(axis=1)
        rel = jnp.where(st.round == 0, 1.0, rel)       # warm-up round
        ctx["weights"] = weights * (rel >= fl.cmfl_threshold)
        return ctx

    def hop_wire(ctx):
        # encode -> transport -> decode -> aggregate; comm_state rides along.
        # The wire-boundary optimization_barrier lives in the shared dispatch
        # body (Dispatch.wire_rows — the sim wire is built on it); the star
        # wire's shard_map aggregator encodes inside the collective, so it
        # materializes the deltas here instead (same boundary, DESIGN.md §8)
        deltas = (ctx["deltas"] if simulator
                  else jax.lax.optimization_barrier(ctx["deltas"]))
        weights = ctx["weights"]
        n_sel = (weights > 0).sum().astype(jnp.float32)
        if wire.needs_ids:
            agg, new_comm = wire.aggregate(deltas, weights, ctx["r_up"],
                                           ctx["state"].comm_state,
                                           ctx["ids"])
        else:
            agg, new_comm = wire.aggregate(deltas, weights, ctx["r_up"],
                                           ctx["state"].comm_state)
        ctx.update(agg=agg, new_comm=new_comm, n_sel=n_sel)
        return ctx

    def hop_control(ctx):
        # SCAFFOLD control-variate bookkeeping: unselected clients keep c_i
        st, weights = ctx["state"], ctx["weights"]
        selmask = (weights > 0).astype(jnp.float32)
        new_ci = jax.tree.map(
            lambda new, old: jnp.where(
                selmask.reshape((C,) + (1,) * (new.ndim - 1)) > 0, new, old),
            ctx["new_ci"], st.client_controls)
        dci = jax.tree.map(lambda a, b: a - b, new_ci, st.client_controls)
        agg_dc = wire.aggregate_dense(dci, weights, ctx["r_up"])
        control = jax.tree.map(
            lambda c, d: c + (ctx["n_sel"] / C) * d, st.control, agg_dc)
        ctx.update(new_ci=new_ci, control=control)
        return ctx

    def hop_server_opt(ctx):
        st = ctx["state"]
        new_params, new_sos = server_opt.apply(fl, st.params, ctx["agg"],
                                               st.server_opt_state)
        ctx.update(new_params=new_params, new_sos=new_sos)
        return ctx

    def hop_ledger(ctx):
        billed = ctx["n_sel"]
        if scenario is not None and scenario.dropout > 0.0:
            # a mid-round-dropped client already shipped its payload (the
            # row is zero-weighted at aggregation, not withheld — under
            # secagg its masked codes MUST arrive for the masks to
            # cancel), so billing stays at the pre-dropout selection
            billed = billed + ctx["scn_dropped"]
        ctx["billed"] = billed
        ctx["ledger"] = _make_ledger(terms, billed)
        return ctx

    def hop_telemetry(ctx):
        # flight recorder (repro.obs, DESIGN.md §12): reads already-computed
        # round values + static byte terms only — params / comm_state /
        # ledger are untouched, so the telemetry-off graph is the exact
        # subgraph with this hop removed (tests/test_obs.py)
        ctrs = (store.stats(ctx["state"].comm_state, ctx["ids"])
                if store is not None else None)
        if population is not None:
            available = population.availability_count(ctx["state"].round,
                                                      ctx["ids"])
        elif "avail" in ctx:
            available = ctx["avail"].sum()
        else:
            available = jnp.float32(C)
        ctx["round_stats"] = obs_tel.round_stats(
            tele, ctx["ledger"], up_unit=ctx["billed"], store=ctrs,
            selected=ctx["n_sel"], available=available,
            avail_duty=available / jnp.float32(C),
            dropped=ctx.get("scn_dropped"),
            epoch_scale=ctx.get("scn_escale"))
        return ctx

    def hop_finalize(ctx):
        st, weights, losses = ctx["state"], ctx["weights"], ctx["losses"]
        wsum = jnp.maximum(weights.sum(), 1e-9)
        metrics = {
            "loss": (weights * losses).sum() / wsum,
            "loss_all": losses.mean(),
            "selected": ctx["n_sel"],
            "ledger": ctx["ledger"],
        }
        if tele is not None:
            metrics["round_stats"] = ctx["round_stats"]
        new_prev = ctx["agg"] if (simulator and fl.cmfl_threshold > 0) else None
        ctx["new_state"] = FLState(
            params=ctx["new_params"], server_opt_state=ctx["new_sos"],
            control=ctx.get("control"), client_controls=ctx["new_ci"],
            comm_state=ctx["new_comm"], rng=ctx["r_next"],
            round=st.round + 1, prev_delta=new_prev,
        )
        ctx["metrics"] = metrics
        return ctx

    hops = [("rng", hop_rng)]
    if population is not None:
        hops.append(("cohort", hop_cohort))
    hops += [("downlink", hop_downlink),
             ("model_batch", hop_model_batch),
             ("dane_gradient", hop_dane_gradient),
             ("local_update", hop_local_update), ("select", hop_select)]
    if scenario is not None and scenario.dropout > 0.0:
        hops.append(("scenario_dropout", hop_scenario_dropout))
    if simulator and fl.cmfl_threshold > 0:
        hops.append(("cmfl", hop_cmfl))
    hops.append(("wire", hop_wire))
    if scaffold:
        hops.append(("control", hop_control))
    hops += [("server_opt", hop_server_opt), ("ledger", hop_ledger)]
    if tele is not None:
        hops.append(("telemetry", hop_telemetry))
    hops.append(("finalize", hop_finalize))
    return RoundProgram(topology=topo, hops=tuple(hops))


# ---------------------------------------------------------------------------
# star / sim engine builders
# ---------------------------------------------------------------------------

def _build_star(model: Model, fl: FLConfig, topo: Topology, mesh: Mesh,
                chunk: int, population=None) -> RoundEngine:
    cfg = model.cfg
    client_axis = topo.client_axis or cfg.client_axis
    axes = aggregation.client_axes(mesh, client_axis)
    C = int(np.prod([dict(mesh.shape)[a] for a in axes])) if axes else 1
    client_p = P(axes) if axes else P()

    abs_params = model.abstract_params()
    pspecs = shd.tree_specs(abs_params, model.logical_axes(),
                            mesh, cfg.fsdp)
    terms, up, down = ledger_terms(model, fl)
    scaffold = fl.algorithm == "scaffold"
    stateful = up.stateful
    scenario = _fl_scenario(fl)
    population = _attach_scenario(population, scenario)
    store = None
    if population is not None:
        if scaffold:
            raise ValueError(
                "scaffold keeps dense (C, model) client controls — "
                "incompatible with a streaming ClientPopulation")
        if population.cohort != C:
            raise ValueError(
                f"star topology dispatches one cohort slot per mesh client "
                f"({C}); got population.cohort={population.cohort}")
        store = population.make_store(up, abs_params)
    dispatch = make_dispatch(model, fl, up, down, C, chunk,
                             scenario=scenario)
    wire = _star_wire(mesh, pspecs, up, client_axis, abs_params,
                      need_dense=scaffold)
    if store is not None:
        wire = _star_population_wire(wire, store)

    clientful = shd.with_prefix(pspecs, axes if axes else None)
    state_specs = FLState(
        params=pspecs,
        server_opt_state={k: pspecs
                          for k in server_opt.state_keys(fl.server_opt)},
        control=pspecs if scaffold else None,
        client_controls=clientful if scaffold else None,
        comm_state=(store.specs() if store is not None
                    else comm_state_specs(up, abs_params, pspecs, axes)
                    if stateful else None),
        rng=P(), round=P(),
    )
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))

    def init_fn(rng):
        params = model.init(rng)
        zerosf32 = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zeros_clientful = lambda: jax.tree.map(
            lambda p: jnp.zeros((C,) + p.shape, jnp.float32), params)
        return FLState(
            params=params,
            server_opt_state=server_opt.init_state(fl.server_opt, params),
            control=zerosf32() if scaffold else None,
            client_controls=zeros_clientful() if scaffold else None,
            comm_state=(store.init() if store is not None
                        else comm_state_init(up, params, C)
                        if stateful else None),
            rng=jax.random.PRNGKey(fl.seed),
            round=jnp.zeros((), jnp.int32),
        )

    def batch_sharding_fn(batch):
        """Client dim -> client axes; for pod-clients the within-client batch
        dim additionally shards over the data axis."""
        out = {}
        sub = ("data",) if (client_axis == "pod"
                            and "data" in mesh.axis_names) else ()
        lead = tuple(client_p) or (None,)
        for k, v in batch.items():
            nd = np.ndim(v) if not hasattr(v, "ndim") else v.ndim
            if nd == 0:
                out[k] = NamedSharding(mesh, P())
            elif nd <= 2 or not sub:
                # (C,) / (C, small) metadata: client axes only
                out[k] = NamedSharding(mesh, P(*lead))
            else:
                # (C, B, ...) model inputs: within-client batch over data
                out[k] = NamedSharding(mesh, P(*lead, *sub))
        return out

    tele = _telemetry_spec(fl, up, down, _param_sizes(model))
    program = _build_server_program(model, fl, topo, wire, terms, dispatch,
                                    C, chunk, population=population,
                                    tele=tele, store=store,
                                    scenario=scenario)
    aux = {}
    if population is not None:
        aux["population"] = population
    if tele is not None:
        aux["telemetry"] = tele
    return RoundEngine(
        topology=topo, program=program, round_fn=program,
        init_fn=init_fn, n_clients=C, terms=terms,
        state_shardings=state_shardings,
        batch_sharding_fn=batch_sharding_fn,
        aux=aux,
    )


def _build_sim(model: Model, fl: FLConfig, topo: Topology,
               chunk: int, population=None) -> RoundEngine:
    C = topo.n_clients
    terms, up, down = ledger_terms(model, fl)
    scaffold = fl.algorithm == "scaffold"
    stateful = up.stateful
    scenario = _fl_scenario(fl)
    population = _attach_scenario(population, scenario)
    store = None
    if population is not None:
        if scaffold:
            raise ValueError(
                "scaffold keeps dense (C, model) client controls — "
                "incompatible with a streaming ClientPopulation")
        if population.n_clients != C:
            raise ValueError(
                f"population.n_clients ({population.n_clients}) must match "
                f"Topology.sim(n_clients={C})")
        C = population.cohort           # dispatch width = the cohort slice
        store = population.make_store(up, model.abstract_params())
    dispatch = make_dispatch(model, fl, up, down, C, chunk,
                             scenario=scenario)
    if store is not None:
        wire = _population_wire(dispatch, store, C)
    else:
        wire = _sim_wire(dispatch, C)

    def init_fn(rng):
        params = model.init(rng)
        zc = lambda: jax.tree.map(
            lambda p: jnp.zeros((C,) + p.shape, jnp.float32), params)
        zf = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FLState(
            params=params,
            server_opt_state=server_opt.init_state(fl.server_opt, params),
            control=zf() if scaffold else None,
            client_controls=zc() if scaffold else None,
            comm_state=(store.init() if store is not None
                        else comm_state_init(up, params, C)
                        if stateful else None),
            rng=jax.random.PRNGKey(fl.seed),
            round=jnp.zeros((), jnp.int32),
            prev_delta=zf() if fl.cmfl_threshold > 0 else None,
        )

    tele = _telemetry_spec(fl, up, down, _param_sizes(model))
    program = _build_server_program(model, fl, topo, wire, terms, dispatch,
                                    C, chunk, population=population,
                                    tele=tele, store=store,
                                    scenario=scenario)
    aux = {}
    if population is not None:
        aux.update(population=population, cohort=C)
    if tele is not None:
        aux["telemetry"] = tele
    return RoundEngine(topology=topo, program=program, round_fn=program,
                       init_fn=init_fn, n_clients=topo.n_clients,
                       terms=terms, aux=aux)


# ---------------------------------------------------------------------------
# hierarchical engine (client -> edge(pod) -> cloud)
# ---------------------------------------------------------------------------

def _build_hier(model: Model, fl: FLConfig, topo: Topology, mesh: Mesh,
                chunk: int) -> RoundEngine:
    assert "pod" in mesh.axis_names, "hierarchical FL needs a pod axis"
    assert fl.algorithm != "scaffold", \
        "hierarchical topology keeps no server control-variate state; " \
        "use fedavg/fedsgd/fedprox (or the star topology for SCAFFOLD)"
    cfg = model.cfg
    sizes = dict(mesh.shape)
    G, Ce = sizes["pod"], sizes["data"]

    abs_params = model.abstract_params()
    pspecs = shd.tree_specs(abs_params, model.logical_axes(), mesh, cfg.fsdp)
    gspecs = shd.with_prefix(pspecs, "pod")                  # (G, ...) params
    dspecs = shd.with_prefix(pspecs, "pod", "data")          # (G, Ce, ...)

    # edge hop uses the full uplink pipeline (EF / DGC wrappers included —
    # comm_state threads through the edge hop, closing the stateless gap)
    up = uplink_pipeline(fl)
    pod_comp = make_compressor(fl.pod_compressor, block=fl.qsgd_block,
                               backend=fl.backend,
                               wire_format=fl.wire_format)
    stateful = up.stateful

    nparams = _param_sizes(model)
    bind_n_leaves(up, len(nparams))   # dpnoise: joint clip over all leaves
    terms = {
        "edge_wire": sum(up.wire_bits(n) for n in nparams) / 8.0 * Ce * G,
        "cloud_wire": sum(pod_comp.wire_bits(n) for n in nparams) / 8.0 * G,
        "dense": sum(32.0 * n for n in nparams) / 8.0 * Ce * G,
    }
    # One TelemetrySpec serves BOTH cond branches (lax.cond needs identical
    # output structure): edge stages are static per-round bytes, and the
    # appended pod slot is the residual against the branch's own ledger —
    # ~0 on edge rounds, ~cloud_wire on cloud rounds.
    tele = None
    if fl.telemetry:
        tele = obs_tel.telemetry_spec(
            up, None, nparams, up_scale=float(Ce * G),
            extra_up=((f"pod:{fl.pod_compressor}", terms["cloud_wire"]),))

    # (G, Ce) client grid: one leading dim per (pod, data) axis
    comm_specs = (comm_state_specs(up, abs_params, pspecs, ("pod", "data"),
                                   separate=True)
                  if stateful else None)

    # ------------------------------------------------------------------ agg
    def _agg_edge(deltas, weights, rng, comm_state):
        """Edge hop: within-pod aggregation. deltas (G, Ce, ...), weights
        (G, Ce) replicated -> per-pod mean delta (G, ...). Pipeline state
        (EF residual / DGC momentum) has (G, Ce) leading dims and stays on
        its client's devices — only the payload crosses the ICI."""
        def body(dtree, w, comm):
            gi = jax.lax.axis_index("pod")
            ci = jax.lax.axis_index("data")
            out, st_out = [], []
            for li, leaf in enumerate(jax.tree.leaves(dtree)):
                flat = leaf.reshape(-1).astype(jnp.float32)
                r = jax.random.fold_in(jax.random.fold_in(rng, li),
                                       gi * Ce + ci)
                if up.is_identity:
                    contrib = w[gi, ci] * flat
                    edge = jax.lax.psum(contrib, "data") / \
                        jnp.maximum(jax.lax.psum(w[gi, ci], "data"), 1e-9)
                else:
                    st = (jax.tree.map(lambda a: a[0, 0], comm[li])
                          if stateful else up.init(flat.shape))
                    if has_mask_ctx(up):
                        # per-pod mask ring over the "data" axis (the edge
                        # cohort): pods mask independently, cohort = Ce
                        mkey = jax.random.fold_in(jax.random.fold_in(
                            jax.random.fold_in(rng, MASK_TAG), li), gi)
                        st = inject_mask_ctx(st, mkey, ci, Ce)
                    payload, new_st = up.encode(st, r, flat)
                    gath = jax.lax.all_gather(payload, "data")
                    dec = jax.vmap(lambda q: up.decode(q, flat.shape[0]))(gath)
                    wrow = w[gi]
                    edge = (wrow[:, None] * dec).sum(0) / \
                        jnp.maximum(wrow.sum(), 1e-9)
                    if stateful:
                        st_out.append(jax.tree.map(lambda a: a[None, None],
                                                   new_st))
                out.append(edge.reshape((1,) + leaf.shape[2:])
                           .astype(leaf.dtype))
            agg = jax.tree.unflatten(jax.tree.structure(dtree), out)
            return agg, (tuple(st_out) if stateful else ())

        if stateful:
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(dspecs, P(), comm_specs),
                                 out_specs=(gspecs, comm_specs),
                                 check_vma=False)(deltas, weights, comm_state)
        agg = jax.shard_map(lambda d, w: body(d, w, None)[0], mesh=mesh,
                            in_specs=(dspecs, P()),
                            out_specs=gspecs, check_vma=False)(deltas, weights)
        return agg, None

    def _sync_models(params, rng):
        """Cloud hop: periodic *model* averaging across pods (FedPAQ /
        Hier-Local-QSGD), quantised with ``pod_compressor``. All pods leave
        with the identical synced model."""
        def body(ptree):
            out = []
            for li, leaf in enumerate(jax.tree.leaves(ptree)):
                flat = leaf.reshape(-1).astype(jnp.float32)
                r = jax.random.fold_in(rng, li)
                if pod_comp.is_identity:
                    synced = jax.lax.pmean(flat, "pod")
                else:
                    pay, _ = pod_comp.encode(
                        pod_comp.init(flat.shape),
                        jax.random.fold_in(r, jax.lax.axis_index("pod")), flat)
                    gath = jax.lax.all_gather(pay, "pod")
                    dec = jax.vmap(lambda q: pod_comp.decode(
                        q, flat.shape[0]))(gath)
                    synced = dec.mean(0)
                out.append(synced.reshape(leaf.shape).astype(leaf.dtype))
            return jax.tree.unflatten(jax.tree.structure(ptree), out)

        return jax.shard_map(body, mesh=mesh, in_specs=(gspecs,),
                             out_specs=gspecs, check_vma=False)(params)

    def _pod_divergence(params):
        """Mean squared distance of per-pod models from their mean — the
        periodic-averaging 'staleness' the cloud hop resets.

        Probed on a fixed small slice of the largest leaf: an exact
        full-parameter version costs a full-model pod all-reduce per round
        (measured: +16.4 GB/dev on qwen32b — more than the FL wire itself),
        so the metric must not dominate the step it measures."""
        leaves = sorted(jax.tree.leaves(params), key=lambda l: -l.size)
        probe = leaves[0].reshape(leaves[0].shape[0], -1)[:, :4096]
        probe = probe.astype(jnp.float32)
        return jnp.mean((probe - probe.mean(0, keepdims=True)) ** 2)

    # ------------------------------------------------------------------ hops
    def _make_program(cloud: bool) -> RoundProgram:
        def hop_rng(ctx):
            st = ctx["state"]
            r_loc, r_up, r_next = jax.random.split(st.rng, 3)
            ctx.update(r_loc=r_loc, r_up=r_up, r_next=r_next)
            return ctx

        def hop_local_update(ctx):
            st = ctx["state"]
            rngs = jax.random.split(ctx["r_loc"], G * Ce).reshape(G, Ce, -1)
            model_batch = {k: v for k, v in ctx["batch"].items()
                           if k != "sizes"}
            deltas, losses = jax.vmap(lambda pg, bg, rg: jax.vmap(
                lambda bc, rc: _client_update(
                    model, fl, pg, bc, rc, None, None, chunk)[:2])(bg, rg))(
                st.params, model_batch, rngs)
            ctx.update(deltas=deltas, losses=losses)
            return ctx

        def hop_wire(ctx):
            weights = ctx["batch"].get("sizes",
                                       jnp.ones((G, Ce), jnp.float32))
            agg, new_comm = _agg_edge(ctx["deltas"], weights, ctx["r_up"],
                                      ctx["state"].comm_state)
            ctx.update(agg=agg, new_comm=new_comm)
            return ctx

        def hop_server_opt(ctx):
            # per-pod server update (vmap-free: tree ops broadcast over G)
            st = ctx["state"]
            new_params, new_sos = server_opt.apply(fl, st.params, ctx["agg"],
                                                   st.server_opt_state)
            ctx.update(new_params=new_params, new_sos=new_sos)
            return ctx

        def hop_cloud_sync(ctx):
            # periodic model averaging across pods
            ctx["new_params"] = _sync_models(
                ctx["new_params"], jax.random.fold_in(ctx["r_up"], 99))
            return ctx

        def hop_ledger(ctx):
            wire = terms["edge_wire"] + (terms["cloud_wire"] if cloud else 0.0)
            ctx["ledger"] = CommLedger(
                uplink_wire=jnp.float32(wire),
                uplink_entropy=jnp.float32(wire),
                downlink_wire=jnp.float32(0.0),
                uplink_dense=jnp.float32(terms["dense"]),
                downlink_dense=jnp.float32(0.0))
            rho = up.dp_rho_per_round()
            if rho:
                ctx["ledger"] = dataclasses.replace(
                    ctx["ledger"], dp_rho=jnp.float32(rho * Ce * G))
            return ctx

        def hop_telemetry(ctx):
            ctx["round_stats"] = obs_tel.round_stats(
                tele, ctx["ledger"], up_unit=jnp.float32(1.0),
                selected=jnp.float32(Ce * G), available=jnp.float32(Ce * G))
            return ctx

        def hop_finalize(ctx):
            st = ctx["state"]
            ctx["metrics"] = {
                "loss": ctx["losses"].mean(),
                "ledger": ctx["ledger"],
                "pod_divergence": _pod_divergence(ctx["new_params"]),
            }
            if tele is not None:
                ctx["metrics"]["round_stats"] = ctx["round_stats"]
            ctx["new_state"] = FLState(
                params=ctx["new_params"], server_opt_state=ctx["new_sos"],
                control=None, client_controls=None,
                comm_state=ctx["new_comm"], rng=ctx["r_next"],
                round=st.round + 1,
            )
            return ctx

        hops = [("rng", hop_rng), ("local_update", hop_local_update),
                ("edge_wire", hop_wire), ("server_opt", hop_server_opt)]
        if cloud:
            hops.append(("cloud_sync", hop_cloud_sync))
        hops.append(("ledger", hop_ledger))
        if tele is not None:
            hops.append(("telemetry", hop_telemetry))
        hops.append(("finalize", hop_finalize))
        return RoundProgram(topology=topo, hops=tuple(hops))

    edge_program = _make_program(cloud=False)
    cloud_program = _make_program(cloud=True)

    def round_fn(state, batch):
        """Scan-safe round: cloud sync every ``sync_every`` rounds via cond
        (the dry-run still lowers edge/cloud as two separate programs)."""
        is_cloud = (state.round + 1) % topo.sync_every == 0
        return jax.lax.cond(is_cloud, cloud_program, edge_program,
                            state, batch)

    def init_fn(rng):
        params = model.init(rng)
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (G,) + p.shape), params)
        return FLState(
            params=params,
            server_opt_state=server_opt.init_state(fl.server_opt, params),
            control=None, client_controls=None,
            comm_state=(comm_state_init(up, model.abstract_params(), (G, Ce))
                        if stateful else None),
            rng=jax.random.PRNGKey(fl.seed),
            round=jnp.zeros((), jnp.int32),
        )

    state_specs = FLState(
        params=gspecs,
        server_opt_state={k: gspecs
                          for k in server_opt.state_keys(fl.server_opt)},
        control=None, client_controls=None,
        comm_state=comm_specs, rng=P(), round=P(),
    )
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))

    return RoundEngine(
        topology=topo, program=edge_program, round_fn=round_fn,
        init_fn=init_fn, n_clients=G * Ce, terms=terms,
        state_shardings=state_shardings,
        programs={"edge": edge_program, "cloud": cloud_program},
        aux={"n_pods": G, "clients_per_pod": Ce,
             **({"telemetry": tele} if tele is not None else {})},
    )


# ---------------------------------------------------------------------------
# gossip engine (decentralized ring mixing)
# ---------------------------------------------------------------------------

def _build_gossip(model: Model, fl: FLConfig, topo: Topology, mesh: Mesh,
                  chunk: int) -> RoundEngine:
    cfg = model.cfg
    C = dict(mesh.shape)["data"]
    # biased compressors gossip with error feedback riding in comm_state —
    # but NOT DGC momentum correction: DGC accumulates update *deltas*,
    # while the gossip mix ships raw model parameters (accumulating those
    # diverges), so that knob is rejected for this topology
    if fl.dgc_momentum > 0.0:
        raise ValueError(
            "dgc_momentum accumulates update deltas; the gossip mix ships "
            "raw model parameters — use error feedback (the default for "
            "biased pipelines) instead")
    comp = make_compressor(fl.uplink_compressor, fraction=fl.topk_fraction,
                           block=fl.qsgd_block, rows=fl.sketch_rows,
                           cols=fl.sketch_cols, backend=fl.backend,
                           wire_format=fl.wire_format)
    comp = _apply_privacy(fl, comp)
    if comp.biased and fl.error_feedback:
        comp = error_feedback(comp)
    stateful = comp.stateful

    abs_params = model.abstract_params()
    pspecs = shd.tree_specs(abs_params, model.logical_axes(), mesh, cfg.fsdp)
    cspecs = shd.with_prefix(pspecs, "data")

    # general graphs: ring offsets and/or explicit permutations (expander /
    # Erdős–Rényi matchings). Every node keeps whatever its incoming edge
    # weights leave over; the mixing matrix must be doubly stochastic.
    check_doubly_stochastic(mixing_matrix(topo.graph, C))
    perms = [(_graph_edges(spec, C), w) for spec, w in topo.graph]
    # per-node self weight = 1 - sum of weights over edges INTO that node
    # (un-targeted ppermute destinations receive zeros, so a node skipped
    # by a matching keeps its own share)
    self_w_vec = np.full((C,), 1.0)
    for edges, w in perms:
        for _, dst in edges:
            self_w_vec[dst] -= w
    self_w_vec = jnp.asarray(self_w_vec, jnp.float32)

    nparams = _param_sizes(model)
    bind_n_leaves(comp, len(nparams))  # dpnoise: joint clip over all leaves
    payload_bytes = sum(comp.wire_bits(n) for n in nparams) / 8.0
    n_edges = sum(len(edges) for edges, _ in perms)
    terms = {
        # every payload crossing a directed graph edge counts once
        "mix_wire": payload_bytes * n_edges,
        "dense": sum(32.0 * n for n in nparams) / 8.0 * n_edges,
    }
    # the ledger's mix_wire is absolute (already x n_edges), so the spec is
    # scaled the same way and round_stats anchors with up_unit=1.0
    tele = (obs_tel.telemetry_spec(comp, None, nparams,
                                   up_scale=float(n_edges))
            if fl.telemetry else None)

    comm_specs = (comm_state_specs(comp, abs_params, pspecs, ("data",))
                  if stateful else None)

    def mix(params, rng, comm_state):
        def body(ptree, comm):
            self_w = self_w_vec[jax.lax.axis_index("data")]
            out, st_out = [], []
            for li, leaf in enumerate(jax.tree.leaves(ptree)):
                flat = leaf.reshape(-1).astype(jnp.float32)
                r = jax.random.fold_in(rng, li)
                st = (jax.tree.map(lambda a: a[0], comm[li])
                      if stateful else comp.init(flat.shape))
                if has_mask_ctx(comp):
                    # gossip: the ring spans all C nodes. Cancellation only
                    # holds for sums over the full cohort, so masked gossip
                    # is exact when the mixing row covers every node (all-to
                    # -all matchings); sparse matchings decode per-edge via
                    # the payload ctx, which stays exact per client.
                    mkey = jax.random.fold_in(
                        jax.random.fold_in(rng, MASK_TAG), li)
                    st = inject_mask_ctx(
                        st, mkey, jax.lax.axis_index("data"), C)
                payload, new_st = comp.encode(st, r, flat)
                n = flat.shape[0]
                mixed = self_w * flat
                for perm, w in perms:
                    nb = jax.lax.ppermute(payload, "data", perm)
                    mixed = mixed + w * comp.decode(nb, n)
                out.append(mixed.reshape(leaf.shape).astype(leaf.dtype))
                if stateful:
                    st_out.append(jax.tree.map(lambda a: a[None], new_st))
            tree = jax.tree.unflatten(jax.tree.structure(ptree), out)
            return tree, (tuple(st_out) if stateful else ())

        if stateful:
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(cspecs, comm_specs),
                                 out_specs=(cspecs, comm_specs),
                                 check_vma=False)(params, comm_state)
        mixed = jax.shard_map(lambda p: body(p, None)[0], mesh=mesh,
                              in_specs=(cspecs,),
                              out_specs=cspecs, check_vma=False)(params)
        return mixed, None

    def hop_rng(ctx):
        st = ctx["state"]
        r_mix, r_next = jax.random.split(st.rng)
        ctx.update(r_mix=r_mix, r_next=r_next)
        return ctx

    def hop_local_update(ctx):
        st = ctx["state"]

        def local(p_c, batch_c):
            loss, g = jax.value_and_grad(
                lambda p: model.loss(p, batch_c, chunk=chunk)[0])(p_c)
            p_c = jax.tree.map(
                lambda a, g_: (a.astype(jnp.float32)
                               - fl.local_lr * g_.astype(jnp.float32)
                               ).astype(a.dtype), p_c, g)
            return p_c, loss

        params, losses = jax.vmap(local)(st.params, ctx["batch"])
        ctx.update(params=params, losses=losses)
        return ctx

    def hop_mix(ctx):
        params, new_comm = mix(ctx["params"], ctx["r_mix"],
                               ctx["state"].comm_state)
        ctx.update(params=params, new_comm=new_comm)
        return ctx

    def hop_ledger(ctx):
        ctx["ledger"] = CommLedger(
            uplink_wire=jnp.float32(terms["mix_wire"]),
            uplink_entropy=jnp.float32(terms["mix_wire"]),
            downlink_wire=jnp.float32(0.0),
            uplink_dense=jnp.float32(terms["dense"]),
            downlink_dense=jnp.float32(0.0))
        rho = comp.dp_rho_per_round()
        if rho:
            # every node releases one noised payload per round
            ctx["ledger"] = dataclasses.replace(
                ctx["ledger"], dp_rho=jnp.float32(rho * C))
        return ctx

    def hop_telemetry(ctx):
        ctx["round_stats"] = obs_tel.round_stats(
            tele, ctx["ledger"], up_unit=jnp.float32(1.0),
            selected=jnp.float32(C), available=jnp.float32(C))
        return ctx

    def hop_finalize(ctx):
        st, params = ctx["state"], ctx["params"]
        # consensus error (mean squared distance to the mean model)
        leaves = jax.tree.leaves(params)
        consensus = sum(
            jnp.sum((l.astype(jnp.float32)
                     - l.astype(jnp.float32).mean(0, keepdims=True)) ** 2)
            for l in leaves) / sum(l.size for l in leaves)
        ctx["metrics"] = {"loss": ctx["losses"].mean(),
                          "consensus": consensus,
                          "ledger": ctx["ledger"]}
        if tele is not None:
            ctx["metrics"]["round_stats"] = ctx["round_stats"]
        ctx["new_state"] = FLState(
            params=params, server_opt_state={},
            control=None, client_controls=None,
            comm_state=ctx["new_comm"], rng=ctx["r_next"],
            round=st.round + 1,
        )
        return ctx

    hops = [("rng", hop_rng), ("local_update", hop_local_update),
            ("mix", hop_mix), ("ledger", hop_ledger)]
    if tele is not None:
        hops.append(("telemetry", hop_telemetry))
    hops.append(("finalize", hop_finalize))
    program = RoundProgram(topology=topo, hops=tuple(hops))

    def init_fn(rng):
        p = model.init(rng)
        ps = jax.tree.map(lambda a: jnp.broadcast_to(a, (C,) + a.shape), p)
        return FLState(
            params=ps, server_opt_state={},
            control=None, client_controls=None,
            comm_state=(comm_state_init(comp, p, C) if stateful else None),
            rng=jax.random.PRNGKey(fl.seed),
            round=jnp.zeros((), jnp.int32),
        )

    state_specs = FLState(params=cspecs, server_opt_state={},
                          control=None, client_controls=None,
                          comm_state=comm_specs, rng=P(), round=P())
    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))

    return RoundEngine(topology=topo, program=program, round_fn=program,
                       init_fn=init_fn, n_clients=C, terms=terms,
                       state_shardings=state_shardings,
                       aux=({"telemetry": tele} if tele is not None else {}))


# ---------------------------------------------------------------------------
# public builder
# ---------------------------------------------------------------------------

# above this client count a dense sim/async build would silently allocate
# O(C x model) comm_state rows (plus (C,)-wide dispatch) — the build refuses
# and points at the streaming path instead (DESIGN.md §9)
POPULATION_DENSE_LIMIT = 4096


def _check_population(fl: FLConfig, topology: Topology) -> None:
    C = topology.n_clients
    if C <= POPULATION_DENSE_LIMIT:
        return
    if topology.kind == "sim" and not uplink_pipeline(fl).stateful:
        return      # stateless sim keeps no per-client rows; C-wide is legal
    raise ValueError(
        f"{topology.kind} topology with n_clients={C} would allocate dense "
        f"per-client state — O(C x model) comm_state rows for the stateful "
        f"uplink pipeline"
        + (" and a (C x model) update buffer"
           if topology.kind == "async" else "")
        + f" — above the {POPULATION_DENSE_LIMIT}-client dense limit. "
        f"Pass a streaming population instead: "
        f"make_round_engine(..., population=ClientPopulation("
        f"n_clients={C}, cohort=1024)) (core.population; CLI: "
        f"--population {C} --cohort 1024), which bounds per-client state "
        f"by the residual-store capacity (DESIGN.md §9).")


def make_round_engine(model: Model, fl: FLConfig, topology: Topology,
                      mesh: Optional[Mesh] = None,
                      chunk: int = 512, data_fn=None,
                      population=None) -> RoundEngine:
    """Build the round executor for one (model, fl, topology) binding.

    The four legacy factories (``make_fl_train_step``,
    ``make_hier_fl_train_step``, ``make_gossip_step``, ``make_sim_step``)
    are thin wrappers over this.  The ``async`` topology additionally needs
    ``data_fn(version) -> batch`` at build time: its event scan samples each
    dispatch generation's batches internally, keyed on server version
    (core.async_engine, DESIGN.md §7).

    ``population`` (a :class:`repro.core.population.ClientPopulation`)
    switches the sim / async / star paths to streaming-cohort dispatch:
    each round touches only ``population.cohort`` sampled clients and
    per-client pipeline state lives in a bounded residual store
    (DESIGN.md §9).  Dense builds above ``POPULATION_DENSE_LIMIT`` clients
    are rejected."""
    if population is not None and topology.kind in ("hier", "gossip"):
        raise ValueError(
            f"{topology.kind} topology pins every client to a mesh device — "
            f"a streaming ClientPopulation only applies to star/sim/async")
    if topology.kind in ("hier", "gossip") and _fl_scenario(fl) is not None:
        raise ValueError(
            f"scenario client dynamics (FLConfig.scenario_*) thread through "
            f"the star/sim/async round programs; the {topology.kind} "
            f"topology has no per-client selection/weighting hop to mask")
    if topology.kind == "star":
        assert mesh is not None, "star topology needs a mesh"
        engine = _build_star(model, fl, topology, mesh, chunk,
                             population=population)
    elif topology.kind == "hier":
        assert mesh is not None, "hier topology needs a mesh"
        engine = _build_hier(model, fl, topology, mesh, chunk)
    elif topology.kind == "gossip":
        assert mesh is not None, "gossip topology needs a mesh"
        engine = _build_gossip(model, fl, topology, mesh, chunk)
    elif topology.kind == "sim":
        assert topology.n_clients > 0, "sim topology needs n_clients"
        if population is None:
            _check_population(fl, topology)
        engine = _build_sim(model, fl, topology, chunk,
                            population=population)
    elif topology.kind == "async":
        assert topology.n_clients > 0, "async topology needs n_clients"
        if population is None:
            _check_population(fl, topology)
        from repro.core.async_engine import build_async_engine
        engine = build_async_engine(model, fl, topology, data_fn, chunk,
                                    population=population)
    else:
        raise ValueError(f"unknown topology kind {topology.kind!r}")
    engine.eval_every = max(1, int(fl.eval_every))
    return engine


# ---------------------------------------------------------------------------
# run_rounds: the scan-compiled multi-round driver
# ---------------------------------------------------------------------------

def _gated_metrics(metrics_fn, state, metrics, do):
    """Run ``metrics_fn`` only when ``do`` (a traced bool) — the eval-cadence
    gate. The skipped branch keeps every base-metric leaf that survives
    ``metrics_fn`` structurally unchanged (same path/shape/dtype — the round
    loss and CommLedger must exist every round) and fills eval-only leaves
    with NaN (0 for integer dtypes), so both ``lax.cond`` branches return one
    pytree structure."""
    tmpl = jax.eval_shape(metrics_fn, state, metrics)
    base = {path: leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(metrics)[0]}

    def on(_):
        return metrics_fn(state, metrics)

    def off(_):
        leaves = []
        for path, t in jax.tree_util.tree_flatten_with_path(tmpl)[0]:
            b = base.get(path)
            if b is not None and b.shape == t.shape and b.dtype == t.dtype:
                leaves.append(b)
            else:
                fill = (jnp.nan if jnp.issubdtype(t.dtype, jnp.floating)
                        else 0)
                leaves.append(jnp.full(t.shape, fill, t.dtype))
        return jax.tree.unflatten(jax.tree.structure(tmpl), leaves)

    return jax.lax.cond(do, on, off, None)


class RoundRunner:
    """Compiles ``chunk`` rounds into one donated-argument ``jax.lax.scan``.

    The round index fed to ``data_fn`` is ``state.round`` (incremented by the
    round program), so batches are sampled *inside* the scan — one XLA
    program per chunk shape, no per-round dispatch or host sync.
    ``metrics_fn(new_state, metrics)`` (optional) appends extra per-round
    metrics (e.g. a held-out eval loss) inside the compiled program.

    ``eval_every`` (default: the engine's ``FLConfig.eval_every``) gates
    ``metrics_fn`` behind a ``lax.cond`` so the eval cost is paid only on
    every ``eval_every``-th round — the *last* round of each cadence window
    (``round % eval_every == eval_every - 1``), so a run whose length is a
    multiple of the cadence always evaluates its final round. Skipped
    rounds keep the base round metrics and NaN-fill the eval-only leaves."""

    def __init__(self, engine: RoundEngine, data_fn, chunk: int = 8,
                 metrics_fn=None, donate: bool = True, eval_every=None,
                 tracer=None):
        self.engine = engine
        self.data_fn = data_fn
        self.chunk = max(1, chunk)
        self.metrics_fn = metrics_fn
        self.tracer = tracer
        self.eval_every = max(1, int(engine.eval_every if eval_every is None
                                     else eval_every))
        ee = self.eval_every
        round_fn = engine.round_fn

        def body(state, _):
            with jax.named_scope(scopes.HOP + "data"):
                batch = data_fn(state.round)
            new_state, metrics = round_fn(state, batch)
            if metrics_fn is None:
                return new_state, metrics
            with jax.named_scope(scopes.HOP + "eval"):
                if ee == 1:
                    metrics = metrics_fn(new_state, metrics)
                else:
                    metrics = _gated_metrics(
                        metrics_fn, new_state, metrics,
                        state.round % ee == ee - 1)
            return new_state, metrics

        def run_chunk(state, k: int):
            return jax.lax.scan(body, state, None, length=k)

        # Mesh paths (star/hier/gossip) pin the state's output shardings to
        # the engine's declared NamedShardings.  Without the pin, XLA
        # normalizes equivalent-but-unequal specs (P(None, None) -> P())
        # on the way out, the donated output feeds chunk 2 with a sharding
        # that no longer compares equal to chunk 1's input, and the
        # identical chunk shape compiles twice — the star double-compile
        # the PR-9 flight recorder surfaced.  run() device_puts the initial
        # state onto the same shardings, closing the loop: one layout in,
        # the same layout out, one compilation per chunk shape.
        out_sh = getattr(engine, "state_shardings", None)
        self._jit = jax.jit(run_chunk, static_argnums=1,
                            donate_argnums=(0,) if donate else (),
                            **({"out_shardings": (out_sh, None)}
                               if out_sh is not None else {}))

    def cache_size(self):
        """Number of distinct compilations so far (one per chunk shape)."""
        return self._jit._cache_size()

    def run(self, state, n: int):
        """Run ``n`` rounds; returns (state, metrics) with every metric (and
        the per-round CommLedger) stacked over a leading (n,) round dim.
        ``n <= 0`` is a no-op returning ``(state, None)``.  Each chunk call
        runs under a ``repro.chunk`` profiler annotation, which puts it on
        the device trace's timeline."""
        if n <= 0:
            return state, None
        shardings = getattr(self.engine, "state_shardings", None)
        if shardings is not None:
            # Pre-commit the input layout on the mesh paths.  init_fn's
            # state carries default device placement; the first chunk
            # compiles for that layout, but its donated OUTPUT carries the
            # program's committed NamedShardings — so the second chunk saw
            # a different input layout and recompiled the identical chunk
            # shape (the star double-compile the PR-9 flight recorder
            # surfaced).  device_put here is a no-op for already-committed
            # state, and makes chunk 1 compile against the same layout
            # every later chunk feeds back in.
            state = jax.device_put(state, shardings)
        chunks = []
        done = 0
        while done < n:
            k = min(self.chunk, n - done)
            if self.tracer is None:
                # the Tracer's span opens the same annotation itself
                with jax.profiler.TraceAnnotation(scopes.ANNOTATION + "chunk"):
                    state, m = self._jit(state, k)
            else:
                # span kind "compile" when this chunk shape triggered a fresh
                # compilation (jit compiles lazily, so the span necessarily
                # includes the first execution too); "chunk" for cache hits.
                # block_until_ready keeps the wall-clock honest under async
                # dispatch — tracing opts into that sync cost.
                before = self.cache_size()
                with self.tracer.span("chunk", rounds=k) as sp:
                    state, m = self._jit(state, k)
                    jax.block_until_ready(m)
                    if self.cache_size() > before:
                        sp["kind"] = "compile"
            chunks.append(m)
            done += k
        if len(chunks) == 1:
            return state, chunks[0]
        metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *chunks)
        return state, metrics


def run_rounds(engine: RoundEngine, state, data_fn, n: int, chunk: int = 8,
               metrics_fn=None, donate: bool = True, eval_every=None,
               tracer=None):
    """Run ``n`` FL rounds, ``chunk`` rounds per compiled scan.

    ``data_fn(round_idx) -> batch`` must be traceable (e.g. sampling from
    ``repro.data.synthetic`` with ``jax.random.fold_in(key, round_idx)``);
    it is called inside the scan body. Returns ``(final_state, metrics)``
    where every metric leaf is stacked over a leading (n,) round dim.
    ``eval_every`` (default ``FLConfig.eval_every`` via the engine) sets the
    ``metrics_fn`` cadence — see :class:`RoundRunner`.  ``tracer`` (a
    ``repro.obs.trace.Tracer``) records per-chunk compile/execute spans and
    turns on the opt-in ``jax.profiler`` hook around the whole run."""
    runner = RoundRunner(engine, data_fn, chunk=chunk, metrics_fn=metrics_fn,
                         donate=donate, eval_every=eval_every, tracer=tracer)
    if tracer is None:
        return runner.run(state, n)
    with tracer.profile():
        return runner.run(state, n)
